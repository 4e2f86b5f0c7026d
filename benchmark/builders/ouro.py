"""Ouro configuration (ByteDance Ouro-2.6B, ``model_type`` ``ouro``) -> the
program's LOOPED pattern-built LM (every layer a full-attention block and
a dense SwiGLU block with four norms, the whole stack run
``total_ut_steps`` times over the same weights, one exit gate), its
training data, and its parameters under the plain reference's names.

The configuration file holds the first pipeline stage's layers
(``num_hidden_layers``, ``layer_types``; the published values stand beside
them under ``published``); every width, every head and the whole vocabulary
are as published. ``training`` holds what is no key of the public config:
``remat`` and ``remat_keep_through`` (block remat and the last name of
``ops/remat.BLOCK_SAVED_NAMES`` it keeps) and ``exit_beta`` (the weight of
the exit distribution's entropy in the loss).

A family's functions, as ``builders/qwen2.py`` lists them: ``build``,
``criterion``, ``train_samples``, ``reference_batch``,
``reference_params``, ``reference_loss_and_grad_norm``,
``train_flops_per_record``, ``flash_shape``; for ``python -m
benchmark.controls``: ``FAULTS``, ``planted``. The comparison that decides
``correct`` holds one thing more than the two numbers the train kind asks
for, because a mean over 4 x 4,096 rows hides bf16: ``loop_blocks``, ONE
layer (its attention block and its feed-forward block, all four norms) on
a seeded stream, and the EXIT LOSS alone on seeded streams of P x T rows,
each against the reference's at the TIMED length, as
``builders/olmo_hybrid.mixer_blocks`` holds its mixers.
"""

from __future__ import annotations

import contextlib
import json
import sys

import numpy as np

from benchmark import flops_ouro, traffic
# the same for every family that build_hybrid_lm builds: the fused-CE
# criterion, Zipf rows over the vocabulary, where the decoder sits
from benchmark.builders import nemotron_h
from benchmark.builders.nemotron_h import (criterion, decoder_of,  # noqa: F401
                                           train_samples)
from benchmark.reference import ouro as reference

#: what the comparison last asked a batch for. ``reference_batch`` is its
#: one call that is handed the cell, so the block check's limits, precision,
#: length and seed are noted there
_ASKED = {}


def reference_batch(cfg, cell, seed):
    _ASKED.update(cfg=cfg, cell=cell, seed=seed)
    return nemotron_h.reference_batch(cfg, cell, seed)


def exit_beta(cfg):
    return float(cfg["training"]["exit_beta"])


def build(cfg, seed):
    """The config through ``interop.hf.ouro_lm_kwargs`` ->
    ``build_hybrid_lm``, weights from the seed. ``training.remat`` is
    applied as ``Optimizer.set_remat("block")`` applies it (the train kind
    has no line for it): the decoder's ``remat_blocks``, with
    ``training.remat_keep_through`` beside it."""
    from bigdl_tpu.interop.hf import ouro_lm_kwargs
    from bigdl_tpu.models.hybrid import build_hybrid_lm
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(seed)
    np.random.seed(seed % (2 ** 32))
    training = cfg["training"]
    if training.get("remat") not in (None, "block"):
        raise ValueError(f"training.remat {training['remat']!r}: 'block' "
                         f"or nothing")
    model = build_hybrid_lm(**ouro_lm_kwargs(cfg, exit_beta=exit_beta(cfg)))
    dec = decoder_of(model)
    dec.remat_blocks = training.get("remat") == "block"
    dec.remat_keep_through = training.get("remat_keep_through")
    return model


def layer_named(attn, ffn, pre=""):
    """One layer's parameters (or their gradient), the trees of its
    attention block and of its feed-forward block, under the reference's
    names."""
    out = {pre + "input_layernorm.weight": attn["norm"]["weight"],
           pre + "input_layernorm_2.weight": attn["norm_post"]["weight"],
           pre + "self_attn.qkv_proj.weight": attn["mixer"]["in_proj_weight"],
           pre + "self_attn.o_proj.weight": attn["mixer"]["out_proj_weight"],
           pre + "post_attention_layernorm.weight": ffn["norm"]["weight"],
           pre + "post_attention_layernorm_2.weight":
               ffn["norm_post"]["weight"]}
    for ours in ("gate", "up", "down"):
        out[pre + f"mlp.{ours}_proj.weight"] = ffn["mixer"][ours]["weight"]
    return out


def named(tree):
    """A parameter tree of the model (or its gradient) under the names the
    reference reads. Layer ``i`` of the model is the decoder's blocks
    ``2i`` (attention) and ``2i + 1`` (feed-forward)."""
    dec = tree["1"]
    out = {"model.embed_tokens.weight": tree["0"]["weight"],
           "model.norm.weight": dec["final_norm"]["weight"],
           "lm_head.weight": tree["2"]["weight"],
           "model.early_exit_gate.weight": tree["exit_gate"]["weight"],
           "model.early_exit_gate.bias": tree["exit_gate"]["bias"]}
    for i in range(sum(k.startswith("layer") for k in dec) // 2):
        out.update(layer_named(dec[f"layer{2 * i}"], dec[f"layer{2 * i + 1}"],
                               f"model.layers.{i}."))
    return out


def reference_params(model):
    """The model's parameters (device arrays, no copy) under the names the
    reference reads."""
    return named(model.parameter_tree())


def _plain_numbers(model, cfg, data, labels, dtype=None):
    """(loss, gradient norm) of the plain reference, in float32 'highest'
    or, for the control, wholly in ``dtype``."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda p, x, y: reference.loss_and_grad_norm(
        p, x, y, cfg, exit_beta(cfg), dtype or jnp.float32))
    with jax.default_matmul_precision("highest"):
        loss, gn = fn(reference_params(model),
                      jnp.asarray(data, jnp.int32) - 1,
                      jnp.asarray(labels, jnp.int32) - 1)
    return float(loss), float(gn)


def _rounded(rng, *shape, scale=1.0):
    """N(0, scale^2) rounded to bf16, so that neither side rounds it."""
    import jax.numpy as jnp
    return jnp.asarray(rng.standard_normal(shape, np.float32) * scale,
                       jnp.bfloat16).astype(jnp.float32)


def _layer_case(model, cfg, cell, policy, rng):
    """ONE layer, the decoder's first two blocks, on a (1, seq_len, hidden)
    stream of N(0, 1): (params, inputs, system, plain)."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.nn.module import functional_apply
    dec = decoder_of(model)
    blocks = [dec._modules["layer0"], dec._modules["layer1"]]
    params = {"attn": blocks[0].parameter_tree(),
              "ffn": blocks[1].parameter_tree()}
    e, length = cfg["hidden_size"], cell["seq_len"]
    inputs = {"x": _rounded(rng, 1, length, e),
              "seed_y": _rounded(rng, 1, length, e)}

    def system(p, x):
        y = x.astype(policy.compute_dtype)
        for block, tree in zip(blocks, (p["attn"], p["ffn"])):
            y, _ = functional_apply(
                block, policy.cast_params_for_compute(tree),
                block.buffer_tree(), y, training=True)
        return y

    def plain(dtype):
        def run(p, x):
            p = jax.tree_util.tree_map(lambda a: a.astype(dtype), p)
            return reference.layer(
                layer_named(p["attn"], p["ffn"], "model.layers.0."), 0,
                x.astype(dtype), cfg)
        return run

    def both(run):
        def scalar(p, x, seed_y):
            y = run(p, x).astype(jnp.float32)
            return jnp.sum(y * seed_y), y

        def out_and_grads(p, ins):     # every tensor an argument: a
            # closed-over one would be a constant of the program
            (_, y), (gp, gx) = jax.value_and_grad(
                scalar, argnums=(0, 1), has_aux=True)(p, ins["x"],
                                                      ins["seed_y"])
            return dict(layer_named(gp["attn"], gp["ffn"]), out=y, x=gx)
        return out_and_grads
    return params, inputs, both(system), lambda dtype: both(plain(dtype))


def _exit_case(model, cfg, cell, policy, rng):
    """The EXIT LOSS alone: P streams of N(0, 1) (P, 1, seq_len, hidden),
    the gate's logits N(0, 1.5^2) (spread over the passes: ``lam`` between
    0.05 and 0.95), Zipf labels and the model's own head; the system's side
    is the criterion as the step calls it (``nn.FusedLMHeadCriterion`` over
    the fused weighted pass, streams and head in the compute dtype), its
    ``out`` the P x T per-row losses (``fused_lm_head_ce(return_rows=)``),
    its gradients dL/dg, dL/dh and dL/dW."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu import nn
    from bigdl_tpu.nn import criterion as criterion_rules
    from bigdl_tpu.ops.lm_head_ce import fused_lm_head_ce
    from bigdl_tpu.utils.table import Table
    passes = cfg["total_ut_steps"]
    e, length = cfg["hidden_size"], cell["seq_len"]
    params = {"h": _rounded(rng, passes, 1, length, e),
              "g": jnp.asarray(1.5 * rng.standard_normal(
                  (passes, 1, length), np.float32)),
              "w": model.parameter_tree()["2"]["weight"]}
    labels = jnp.asarray(traffic.zipf_tokens(
        int(rng.integers(2 ** 31)), 1, length, cfg["vocab_size"],
        cell["token_zipf"])[:, 1:], jnp.float32)
    crit = nn.FusedLMHeadCriterion()

    def system(p, ins):
        cast = policy.cast_params_for_compute

        def loss(p):
            return crit.apply(Table(cast(p["h"]), cast(p["w"]),
                                    exit_logits=p["g"],
                                    exit_beta=model.exit_beta),
                              ins["labels"]).astype(jnp.float32)
        rows = fused_lm_head_ce(
            cast(p["h"]), cast(p["w"]), None,
            jnp.broadcast_to(ins["labels"], p["g"].shape),
            size_average=False, return_rows=True,
            row_weight=criterion_rules.exit_distribution(p["g"])[0])[1]
        return dict(jax.grad(loss)(p), out=rows)

    def plain(dtype):
        def run(p, ins):
            def loss(p):
                return reference.exit_loss(
                    p["h"].astype(dtype), p["w"].astype(dtype), p["g"],
                    ins["labels"].astype(jnp.int32) - 1, exit_beta(cfg))
            (_, rows), grads = jax.value_and_grad(loss, has_aux=True)(p)
            return dict(grads, out=rows)
        return run
    return params, {"labels": labels}, system, plain


def loop_blocks(model, plain_dtype=None):
    """ONE layer (``layer``) and the exit loss alone (``exit_loss``)
    against the reference's: for each, the relative L2 distance of the
    output and of the worst gradient leaf, as ``{"layer": {"out": ..,
    "grad": .., "leaf": ..}, "exit_loss": {..}}``. The system runs as the
    step runs it (the cell's precision, the modules' own forward in
    training mode: on the chip at the timed length, so through ``flash_fwd``
    and its backward at 4,096 keys of head 128 and through the fused
    weighted pass over 4 x 4,096 rows of the whole vocabulary); the
    reference in float32 'highest'. With ``plain_dtype`` the reference
    wholly in that dtype stands where the system stood: the control's
    reading."""
    import jax
    import jax.numpy as jnp
    from benchmark.kinds.train import _policy
    cfg, cell, seed = _ASKED["cfg"], _ASKED["cell"], _ASKED["seed"]
    policy = _policy(cell["precision"])
    read = {}
    for kind, case in (("layer", _layer_case), ("exit_loss", _exit_case)):
        params, inputs, system, plain = case(
            model, cfg, cell, policy, np.random.default_rng(seed))

        def run(fn, precision):
            with jax.default_matmul_precision(precision):
                return jax.jit(fn)(params, inputs)

        want = run(plain(jnp.float32), "highest")
        got = run(plain(plain_dtype), "highest") if plain_dtype \
            else run(system, None)
        rel = {k: float(jnp.linalg.norm(
            (got[k].astype(jnp.float32) - want[k]).ravel())
            / jnp.linalg.norm(want[k].ravel())) for k in want}
        out = rel.pop("out")
        leaf = max(rel, key=lambda k: rel[k] if np.isfinite(rel[k])
                   else np.inf)
        read[kind] = {"out": out, "grad": rel[leaf], "leaf": leaf}
    return read


def _gated(numbers, model, plain_dtype=None):
    """The two numbers as they are where ``loop_blocks`` reads within the
    cell's ``reference.blocks`` (``{block: {"out_rtol": .., "grad_rtol":
    ..}}``) on every block, and NaN twice where it does not, so that
    ``kinds.train.reference_check`` says not ok; the reading beside its
    limits goes to standard error as one ``benchmark detail`` line."""
    limits = _ASKED["cell"]["reference"]["blocks"]
    read = loop_blocks(model, plain_dtype)
    ok = set(read) == set(limits) and all(
        r["out"] <= limits[kind]["out_rtol"]
        and r["grad"] <= limits[kind]["grad_rtol"]
        for kind, r in read.items())
    print("benchmark detail loop_blocks: " + json.dumps(dict(
        read, ok=ok, limits=limits)), file=sys.stderr)
    return numbers if ok else (float("nan"), float("nan"))


def reference_loss_and_grad_norm(model, cfg, data, labels):
    return _gated(_plain_numbers(model, cfg, data, labels), model)


# ------------------------------------------------------- negative controls

#: the controls of ``benchmark.controls``: what ``planted`` can break in
#: the SYSTEM's modules, and the plain reference computed wholly in bf16
#: standing where the system stood
FAULTS = ("one_pass", "no_norm_between_passes", "last_pass_gradient_only",
          "exit_uniform", "no_entropy_term", "no_second_norms",
          "unweighted_rows", "reference_bf16")


def _faulty_passes(fault):
    """``nn.HybridDecoder.pass_streams`` with ONE thing wrong, the passes
    written out: the next pass reads the stream BEFORE the final norm
    (``no_norm_between_passes``); the parameters as passes 1 .. P-1 use
    them carry no gradient (``last_pass_gradient_only``). With no fault it
    is the sound loop written out, which tier-1 holds the scan to."""
    import jax
    import jax.numpy as jnp

    def pass_streams(dec, x):
        tree = dec.parameter_tree()
        if fault == "last_pass_gradient_only":
            dec.load_parameter_tree(
                jax.tree_util.tree_map(jax.lax.stop_gradient, tree))
        out = []
        for t in range(dec.passes):
            if t == dec.passes - 1:
                dec.load_parameter_tree(tree)
            s = dec.stream(x)
            out.append(dec.final_norm.forward(s))
            x = s if fault == "no_norm_between_passes" else out[-1]
        return jnp.stack(out)
    return pass_streams


@contextlib.contextmanager
def planted(model, fault):
    """ONE fault of the mathematics in the system's own modules while the
    block runs (the plain reference reads none of this): the stack run
    ONCE; no norm between passes; the last pass's gradient alone; a uniform
    exit distribution (``p = 1/P``: the gate takes no gradient); the
    entropy term left out; the sandwich's second norms (``N2``, ``N4``)
    left out; the fused loss ignoring its weight a row.
    ``reference_bf16`` breaks nothing in the system: the plain reference
    wholly in bf16 gives the numbers that are compared as the system's,
    the limits' second reading.

    ``benchmark.controls`` computes the reference once, on the sound model,
    and does not ask the builder again, so while a control is planted the
    system's side of ``kinds.train`` carries ``loop_blocks``' verdict."""
    import jax.numpy as jnp
    from benchmark.kinds import train as kind
    from bigdl_tpu import nn
    from bigdl_tpu.nn import criterion as criterion_rules
    from bigdl_tpu.ops import lm_head_ce
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
        if fault == "one_pass":
            swap(decoder_of(model), "passes", 1)
        elif fault in ("no_norm_between_passes", "last_pass_gradient_only"):
            swap(nn.HybridDecoder, "pass_streams", _faulty_passes(fault))
        elif fault == "exit_uniform":
            def uniform(logits):
                p = jnp.full(logits.shape, 1.0 / logits.shape[0],
                             jnp.float32)
                return p, jnp.sum(p * jnp.log(p), axis=0)
            swap(criterion_rules, "exit_distribution", uniform)
        elif fault == "no_entropy_term":
            swap(model, "exit_beta", 0.0)
        elif fault == "no_second_norms":
            swap(nn.HybridBlock, "update_output",
                 lambda block, x: x + block.mixer.forward(
                     block.norm.forward(x)))
        elif fault == "unweighted_rows":
            sound = lm_head_ce.fused_lm_head_ce
            swap(lm_head_ce, "fused_lm_head_ce",
                 lambda *a, row_weight=None, **k: sound(
                     *a, row_weight=None if row_weight is None
                     else jnp.ones_like(row_weight), **k))
        yield


def train_flops_per_record(cfg, cell):
    return flops_ouro.train_flops_per_record(cfg, cell["seq_len"])


def flash_shape(cfg, cell):
    """(batch, heads, seq, head_dim) of the flash-attention call of each
    ``*`` block in this cell's train step."""
    return (cell["batch_size"], cfg["num_attention_heads"], cell["seq_len"],
            cfg["head_dim"])
