"""SmallThinker configuration (PowerInfer SmallThinker-21BA3B-Instruct) ->
the program's pattern-built LM of full (not rotated) and sliding-window
(rotated) GQA layers, each followed by ReGLU experts whose router reads the
LAYER'S input, its training data, and its parameters under the plain
reference's names.

The configuration file holds this chip's share: ``moe_num_primary_experts``
is how many routed experts are HELD (ids 0 .. n-1), ``vocab_size`` the held
rows, ``num_hidden_layers`` / ``rope_layout`` / ``sliding_window_layout``
the stage's layers; the published values stand beside them under
``published``. The router keeps the published width. WHICH experts are ids
0 .. n-1 is the configuration's ``placement``: dealt by measured load
(``place_experts``), so that every seed gives this chip an even share of
the picks. ``training`` holds what is no key of the public config:
``remat`` and ``router_gradient`` (as ``builders/afmoe.py``) and
``router_picks`` (``"token_id"``: each token's experts from a table by its
id, ``builders/joyai_llm_flash.freeze_picks``); the plain reference reads
the last two.

A family's functions, as ``builders/qwen2.py`` lists them: ``build``,
``criterion``, ``train_samples``, ``reference_batch``,
``reference_params``, ``reference_loss_and_grad_norm``,
``train_flops_per_record``, ``flash_shape``; for ``python -m
benchmark.controls``: ``FAULTS``, ``planted``. The comparison that decides
``correct`` holds one thing more than the two numbers the train kind asks
for: ``attention_blocks``, the system's first full and first window
attention mixer against the reference's at the TIMED length (the whole
model is compared at a shorter one, so that the reference fits), output
and every gradient leaf by their relative L2 distance, as
``builders/joyai_llm_flash.latent_block`` holds its latent mixer.
"""

from __future__ import annotations

import contextlib
import json
import sys

import numpy as np

from benchmark import flops_smallthinker
# the same for every family that build_hybrid_lm builds: the fused-CE
# criterion, Zipf rows over the held slice, where the decoder sits, the
# attention blocks' (batch, heads, seq, head_dim); the system's forward
# with every router listened to and the deal of a load-balancing placement
from benchmark.builders import afmoe, nemotron_h
from benchmark.builders.afmoe import deal, measured_loads  # noqa: F401
from benchmark.builders.joyai_llm_flash import freeze_picks
from benchmark.builders.nemotron_h import (criterion, decoder_of,  # noqa: F401
                                           flash_shape, train_samples)
from benchmark.reference import smallthinker as reference

#: what the comparison last asked a batch for. ``reference_batch`` is its
#: one call that is handed the cell, so the block check's limits, precision,
#: length and seed are noted there
_ASKED = {}


def reference_batch(cfg, cell, seed):
    _ASKED.update(cfg=cfg, cell=cell, seed=seed)
    return nemotron_h.reference_batch(cfg, cell, seed)


def hf_config(cfg):
    """The file as the public ``config.json`` reads: the expert count is
    the router's width again (the held ones go in beside it)."""
    return dict(cfg, moe_num_primary_experts=reference.router_width(cfg))


def build(cfg, seed):
    """The config through ``interop.hf.smallthinker_lm_kwargs`` ->
    ``build_hybrid_lm``, weights from the seed. ``training.remat`` is
    applied as ``Optimizer.set_remat("block")`` applies it (the train kind
    has no line for it): the decoder's ``remat_blocks``.
    ``training.router_gradient`` ``"none"`` is ``MoE(train_router=False)``;
    ``training.router_picks`` ``"token_id"`` is
    ``MoE(pick_rows=vocab_size)`` with the tables filled here
    (``freeze_picks``: row t the layer's top k over token t's embedding
    row, which for the first layer, whose router reads the embedding
    itself, are its live picks; again after the placement has relabelled
    the routers' outputs). The plain reference reads both keys."""
    from bigdl_tpu.interop.hf import smallthinker_lm_kwargs
    from bigdl_tpu.models.hybrid import build_hybrid_lm
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(seed)
    np.random.seed(seed % (2 ** 32))
    training = cfg.get("training", {})
    router = training.get("router_gradient", "full")
    if router not in ("full", "none"):
        raise ValueError(f"training.router_gradient {router!r}: 'full' or "
                         f"'none'")
    remat = training.get("remat")
    if remat not in (None, "block"):
        raise ValueError(f"training.remat {remat!r}: 'block' or nothing")
    picks = training.get("router_picks", "scores")
    if picks not in ("scores", "token_id"):
        raise ValueError(f"training.router_picks {picks!r}: 'scores' or "
                         f"'token_id'")
    model = build_hybrid_lm(**smallthinker_lm_kwargs(
        hf_config(cfg), held_experts=reference.held_experts(cfg),
        train_router=router == "full", picks_by_token=picks == "token_id"))
    if picks == "token_id":
        freeze_picks(model)
    if cfg.get("placement"):    # before remat: its forward is listened to
        place_experts(model, cfg, seed)
        if picks == "token_id":
            freeze_picks(model)
    decoder_of(model).remat_blocks = remat == "block"
    return model


def place_experts(model, cfg, seed):
    """``builders/afmoe.place_experts`` (the picks every router's experts
    get measured by the system's own forward on the epoch's rows, the
    experts dealt by load, ids 0 .. n-1 the first chip's; one ``benchmark
    detail placement:`` line on standard error) handed this family's count
    of held experts and router width under the keys it reads."""
    afmoe.place_experts(model, dict(
        cfg, num_experts=cfg["moe_num_primary_experts"],
        published={"num_experts": reference.router_width(cfg)}), seed)


def attention_named(mix, pre=""):
    """An attention mixer's parameters (or their gradient) under the
    reference's names."""
    return {pre + "qkv_proj.weight": mix["in_proj_weight"],
            pre + "o_proj.weight": mix["out_proj_weight"]}


def named(tree, pattern, buffers=None):
    """A parameter tree of the model (or its gradient) under the names the
    reference reads, with the routers' pick tables where ``buffers`` hold
    them. Layer ``i`` of the model is the decoder's blocks ``2i``
    (attention) and ``2i + 1`` (experts)."""
    dec = tree["1"]
    out = {"model.embed_tokens.weight": tree["0"]["weight"],
           "model.norm.weight": dec["final_norm"]["weight"],
           "lm_head.weight": tree["2"]["weight"]}
    for i in range(len(pattern) // 2):
        pre = f"model.layers.{i}."
        att, ffn = dec[f"layer{2 * i}"], dec[f"layer{2 * i + 1}"]
        out[pre + "input_layernorm.weight"] = att["norm"]["weight"]
        out[pre + "post_attention_layernorm.weight"] = ffn["norm"]["weight"]
        out.update(attention_named(att["mixer"], pre + "self_attn."))
        mix, pre = ffn["mixer"], pre + "block_sparse_moe."
        out[pre + "primary_router.weight"] = mix["gate_weight"]
        table = ((buffers or {}).get("1", {}).get(f"layer{2 * i + 1}", {})
                 .get("mixer", {}).get("pick_table"))
        if table is not None:
            out[pre + "primary_router.pick_table"] = table
        for ours, theirs in (("wg", "gate_proj"), ("w1", "up_proj"),
                             ("w2", "down_proj")):
            out[pre + "experts." + theirs] = mix[ours]
    return out


def reference_params(model):
    """The model's parameters and pick tables (device arrays, no copy)
    under the names the reference reads."""
    return named(model.parameter_tree(), decoder_of(model).pattern,
                 model.buffer_tree())


def _plain_numbers(model, cfg, data, labels, dtype=None):
    """(loss, gradient norm) of the plain reference, in float32 'highest'
    or, for the control, wholly in ``dtype``; the routers' doings on the
    batch go to standard error as one ``benchmark detail`` line."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda p, x, y: reference.loss_and_grad_norm(
        p, x, y, cfg, dtype or jnp.float32))
    with jax.default_matmul_precision("highest"):
        loss, gn, picks = fn(reference_params(model),
                             jnp.asarray(data, jnp.int32) - 1,
                             jnp.asarray(labels, jnp.int32) - 1)
    print("benchmark detail routing: "
          + json.dumps(reference.pick_stats(picks, cfg)), file=sys.stderr)
    return float(loss), float(gn)


def attention_blocks(model, plain_dtype=None):
    """The system's FIRST full and FIRST window attention mixer against
    the reference's ``attention`` on the same parameters: for each, the
    relative L2 distance of the output and of the worst gradient leaf (the
    mixer's two matrices and its input), as ``{"full": {"out": ..,
    "grad": .., "leaf": ..}, "window": {..}}``. The stream is (1, the
    cell's ``seq_len``, hidden) of N(0, 1) from the seed, as the block's
    norm hands it over, and the backward's seed a second such tensor; both
    rounded to bf16 first, so that neither side rounds its input. The
    system runs as the step runs it (the cell's precision, the mixer's own
    forward: on the chip at the timed length, so through ``flash_fwd`` /
    ``flash_band_fwd`` and their backward at the timed shape); the
    reference in float32 'highest'. With ``plain_dtype`` the reference
    wholly in that dtype stands where the system stood: the control's
    reading."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.nn.module import functional_apply
    from benchmark.kinds.train import _policy
    cfg, cell, seed = _ASKED["cfg"], _ASKED["cell"], _ASKED["seed"]
    dec, mixers = decoder_of(model), {}
    for i, kind in enumerate(dec.pattern):      # by the pattern's kind, not
        if kind in "*W":                        # by what a fault left of it
            mixers.setdefault("window" if kind == "W" else "full",
                              dec._modules[f"layer{i}"].mixer)
    # (rotated, windowed) of the first layer of each kind
    flags = {"window" if w else "full": (bool(r), bool(w))
             for r, w in reversed(list(zip(cfg["rope_layout"],
                                           cfg["sliding_window_layout"])))}
    rng = np.random.default_rng(seed)
    shape = (1, cell["seq_len"], cfg["hidden_size"])
    x, seed_y = (jnp.asarray(rng.standard_normal(shape, np.float32),
                             jnp.bfloat16).astype(jnp.float32)
                 for _ in range(2))
    policy = _policy(cell["precision"])

    def system(mixer):
        def run(p, x):
            y, _ = functional_apply(
                mixer, policy.cast_params_for_compute(p),
                mixer.buffer_tree(), x.astype(policy.compute_dtype),
                training=True)
            return y
        return run

    def plain(kind, dtype):
        def run(p, x):
            p = jax.tree_util.tree_map(lambda a: a.astype(dtype), p)
            return reference.attention(attention_named(p), "",
                                       x.astype(dtype), cfg, *flags[kind])
        return run

    def output_and_gradients(mixer, run, precision):
        def scalar(p, x, seed_y):
            y = run(p, x).astype(jnp.float32)
            return jnp.sum(y * seed_y), y

        def both(p, x, seed_y):    # all three arguments: a closed-over
            # tensor would be a constant of the program and a new compile
            # a seed
            (_, y), (gp, gx) = jax.value_and_grad(
                scalar, argnums=(0, 1), has_aux=True)(p, x, seed_y)
            return dict(attention_named(gp), out=y, x=gx)

        with jax.default_matmul_precision(precision):
            return jax.jit(both)(mixer.parameter_tree(), x, seed_y)

    read = {}
    for kind, mixer in mixers.items():
        want = output_and_gradients(mixer, plain(kind, jnp.float32),
                                    "highest")
        got = output_and_gradients(mixer, plain(kind, plain_dtype),
                                   "highest") if plain_dtype \
            else output_and_gradients(mixer, system(mixer), None)
        rel = {k: float(jnp.linalg.norm((got[k] - want[k]).ravel())
                        / jnp.linalg.norm(want[k].ravel())) for k in want}
        out = rel.pop("out")
        leaf = max(rel, key=lambda k: rel[k] if np.isfinite(rel[k])
                   else np.inf)
        read[kind] = {"out": out, "grad": rel[leaf], "leaf": leaf}
    return read


def _gated(numbers, model, plain_dtype=None):
    """The two numbers as they are where ``attention_blocks`` reads within
    the cell's ``reference.block_out_rtol`` / ``block_grad_rtol`` on both
    blocks, and NaN twice where it does not, so that
    ``kinds.train.reference_check`` says not ok; the reading beside its
    limits goes to standard error as one ``benchmark detail`` line."""
    tol = _ASKED["cell"]["reference"]
    read = attention_blocks(model, plain_dtype)
    ok = bool(read) and all(
        r["out"] <= tol["block_out_rtol"]
        and r["grad"] <= tol["block_grad_rtol"] for r in read.values())
    print("benchmark detail attention_blocks: " + json.dumps(dict(
        read, ok=ok, block_out_rtol=tol["block_out_rtol"],
        block_grad_rtol=tol["block_grad_rtol"])), file=sys.stderr)
    return numbers if ok else (float("nan"), float("nan"))


def reference_loss_and_grad_norm(model, cfg, data, labels):
    return _gated(_plain_numbers(model, cfg, data, labels), model)


# ------------------------------------------------------- negative controls

#: what ``planted`` can break in the SYSTEM's modules
SYSTEM_FAULTS = ("sigmoid_router", "swiglu_experts",
                 "router_after_attention", "rope_on_full", "no_band")
#: the controls of ``benchmark.controls``: those, and the plain reference
#: computed wholly in bf16 standing where the system stood
FAULTS = SYSTEM_FAULTS + ("reference_bf16",)


@contextlib.contextmanager
def planted(model, fault):
    """ONE fault of the mathematics in the system's own modules while the
    block runs (the plain reference reads none of this): sigmoid scores
    over their sum for the softmax over the picked logits, SwiGLU for
    ReGLU, the router reading the stream AFTER the attention (what its
    expert block is given, not normed) for the layer's input, rotation
    applied on the full layers, the band dropped on the window layers.
    ``reference_bf16`` breaks nothing in the system: the plain reference
    wholly in bf16 gives the numbers that are compared as the system's,
    the limits' second reading.

    ``benchmark.controls`` computes the reference once, on the sound model,
    and does not ask the builder again, so while a control is planted the
    system's side of ``kinds.train`` carries ``attention_blocks``'
    verdict."""
    import jax.numpy as jnp
    from benchmark.kinds import train as kind
    from bigdl_tpu import nn
    from bigdl_tpu.parallel.expert import MoE
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}: one of {FAULTS}")
    with contextlib.ExitStack() as undo:
        def swap(obj, name, value):
            undo.callback(setattr, obj, name, getattr(obj, name))
            setattr(obj, name, value)

        system = kind.system_loss_and_grad_norm
        if fault == "reference_bf16":
            def numbers(model, criterion, policy, data, labels):
                return _gated(_plain_numbers(model, _ASKED["cfg"], data,
                                             labels, jnp.bfloat16), model,
                              jnp.bfloat16)
        else:
            def numbers(model, *args):
                return _gated(system(model, *args), model)
        swap(kind, "system_loss_and_grad_norm", numbers)
        for m in model.modules():
            if isinstance(m, MoE):
                if fault == "sigmoid_router":
                    swap(m, "score", "sigmoid")
                elif fault == "swiglu_experts":
                    swap(m, "activation", "swiglu")
            elif isinstance(m, nn.MultiHeadAttention):
                if fault == "rope_on_full" and not m.window:
                    swap(m, "rope", True)
                elif fault == "no_band" and m.window:
                    swap(m, "window", None)
            elif fault == "router_after_attention" \
                    and isinstance(m, nn.HybridBlock) and m.routed_ahead:
                undo.callback(m.__dict__.pop, "update_output")
                m.update_output = (
                    lambda pair, _sound=m.update_output:
                    _sound((pair[0], pair[0])))
        yield


def train_flops_per_record(cfg, cell):
    return flops_smallthinker.train_flops_per_record(cfg, cell["seq_len"])
