"""LFM2 mixture-of-experts configuration (Liquid AI LFM2-24B-A2B,
``model_type`` ``lfm2_moe``) -> the program's pattern-built LM of
double-gated short-convolution and full GQA layers (q/k norm, rotated) over
a dense and sigmoid-routed SwiGLU feed-forwards with its head TIED to the
embedding, its training data, and its parameters under the plain
reference's names.

The configuration file holds this chip's share: ``num_experts`` is how many
routed experts are HELD (ids 0 .. n-1), ``vocab_size`` the held rows,
``layer_types`` / ``num_dense_layers`` the stage's layers; the published
values stand beside them under ``published``. The router keeps the
published width. WHICH experts are ids 0 .. n-1 is the configuration's
``placement``: dealt by measured load (``place_experts``). ``training``
holds what is no key of the public config: ``remat``, ``router_gradient``
and ``router_picks`` (as ``builders/smallthinker.py``); the plain reference
reads the last two.

A family's functions, as ``builders/qwen2.py`` lists them: ``build``,
``criterion``, ``train_samples``, ``reference_batch``,
``reference_params``, ``reference_loss_and_grad_norm``,
``train_flops_per_record``, ``flash_shape``; for ``python -m
benchmark.controls``: ``FAULTS``, ``planted``. The comparison that decides
``correct`` holds one thing more than the two numbers the train kind asks
for: ``mixer_blocks``, the system's first convolution mixer, that mixer's
local part alone and its attention mixer against the reference's at the
TIMED length (the whole model is compared on one record), output and every
gradient leaf by their relative L2 distance, as
``builders/smallthinker.attention_blocks`` holds its two attention mixers.
"""

from __future__ import annotations

import contextlib
import json
import sys

import numpy as np

from benchmark import flops_lfm2
# the same for every family that build_hybrid_lm builds: the fused-CE
# criterion, Zipf rows over the held slice, where the decoder sits; the
# system's forward with every router listened to; the pick tables by token
# id
from benchmark.builders import nemotron_h
from benchmark.builders.afmoe import measured_loads
from benchmark.builders.joyai_llm_flash import freeze_picks
from benchmark.builders.nemotron_h import (criterion, decoder_of,  # noqa: F401
                                           train_samples)
from benchmark.reference import lfm2_moe as reference

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
    return dict(cfg, num_experts=reference.router_width(cfg))


def build(cfg, seed):
    """The config through ``interop.hf.lfm2_moe_lm_kwargs`` ->
    ``build_hybrid_lm``, weights from the seed. ``training.remat`` is
    applied as ``Optimizer.set_remat("block")`` applies it (the train kind
    has no line for it): the decoder's ``remat_blocks``.
    ``training.router_gradient`` ``"none"`` is ``MoE(train_router=False)``;
    ``training.router_picks`` ``"token_id"`` is
    ``MoE(pick_rows=vocab_size)`` with the tables filled here
    (``freeze_picks``: row t the layer's top k over token t's embedding
    row; again after the placement has relabelled the routers' outputs).
    The plain reference reads both keys."""
    from bigdl_tpu.interop.hf import lfm2_moe_lm_kwargs
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
    model = build_hybrid_lm(**lfm2_moe_lm_kwargs(
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


def balanced_deal(load, chips):
    """Expert ids in the order a load-balancing placement hands them to
    ``chips`` chips of equal room: the experts by falling load, each to the
    chip whose load so far is least among those with room left (longest
    processing time first); chip c's experts are entries ``c * n : (c + 1)
    * n`` of the result, and chip 0 is the one that took the hottest
    expert. ``builders/afmoe.deal`` deals the ranks round by round, which
    leaves the first chip with the hottest expert of every round: its share
    read 13.1-16.4% of a layer's picks over twelve seeds where this reads
    12.5% to a few picks."""
    load = np.asarray(load, float)
    room = len(load) // chips
    held, total = [[] for _ in range(chips)], np.zeros(chips)
    for expert in np.argsort(-load, kind="stable"):
        chip = min((c for c in range(chips) if len(held[c]) < room),
                   key=lambda c: (total[c], c))
        held[chip].append(int(expert))
        total[chip] += load[expert]
    return np.asarray([e for chip in held for e in chip])


def place_experts(model, cfg, seed):
    """Relabels every router's outputs so that ids 0 .. n-1, the experts
    held here, are chip 0's of ``balanced_deal`` over the picks measured on
    the stream the deployment trains on (``placement``: the epoch's rows of
    Zipf tokens over the held rows, from the seed as ``train_samples``
    draws them; ``builders/afmoe.measured_loads``, the system's own
    forward). The experts' weights are seeded alike, so a relabelling
    chooses which of them this chip holds and changes no layer. Writes the
    held experts' measured picks a row to standard error as one
    ``benchmark detail`` line."""
    from benchmark import traffic
    from bigdl_tpu.parallel.expert import MoE
    place = cfg["placement"]
    if place["by"] != "measured_load":
        raise ValueError(f"placement.by {place['by']!r}: 'measured_load'")
    rows = traffic.zipf_tokens(seed, place["records"], place["seq_len"],
                               cfg["vocab_size"], place["token_zipf"])
    held = cfg["num_experts"]
    chips = reference.router_width(cfg) // held
    layers = [m for m in model.modules() if isinstance(m, MoE)]
    loads = measured_loads(model, rows[:, :-1].astype(np.float32)).mean(0)
    picks = []
    for moe, load in zip(layers, loads):
        order = balanced_deal(load, chips)
        moe.gate_weight = moe.gate_weight[:, order]
        moe.select_bias = moe.select_bias[order]
        picks.append(load[order[:held]].round().astype(int).tolist())
    print("benchmark detail placement: " + json.dumps(
        {"held_picks": picks,
         "held_share": [sum(p) / float(load.sum())
                        for p, load in zip(picks, loads)]}), file=sys.stderr)


def conv_named(mix, pre=""):
    """A convolution mixer's parameters (or their gradient) under the
    reference's names."""
    return {pre + "in_proj.weight": mix["in_proj_weight"],
            pre + "conv.weight": mix["conv_weight"],
            pre + "out_proj.weight": mix["out_proj_weight"]}


def local_named(mix, pre=""):
    """What a convolution mixer's local part reads of its parameters: the
    taps."""
    return {pre + "conv.weight": mix["conv_weight"]}


def attention_named(mix, pre=""):
    """An attention mixer's parameters (or their gradient) under the
    reference's names."""
    return {pre + "qkv_proj.weight": mix["in_proj_weight"],
            pre + "out_proj.weight": mix["out_proj_weight"],
            pre + "q_layernorm.weight": mix["q_norm"]["weight"],
            pre + "k_layernorm.weight": mix["k_norm"]["weight"]}


def named(tree, pattern, buffers=None):
    """A parameter tree of the model (or its gradient) under the names the
    reference reads, with the routers' selection bias and pick tables where
    ``buffers`` hold them. Layer ``i`` of the model is the decoder's blocks
    ``2i`` (its mixer) and ``2i + 1`` (its feed-forward). The head is the
    embedding: there is no third matrix."""
    dec = tree["1"]
    out = {"model.embed_tokens.weight": tree["0"]["weight"],
           "model.embedding_norm.weight": dec["final_norm"]["weight"]}
    for i in range(len(pattern) // 2):
        pre = f"model.layers.{i}."
        op, ffn = dec[f"layer{2 * i}"], dec[f"layer{2 * i + 1}"]
        out[pre + "operator_norm.weight"] = op["norm"]["weight"]
        out[pre + "ffn_norm.weight"] = ffn["norm"]["weight"]
        if pattern[2 * i] == "C":
            out.update(conv_named(op["mixer"], pre + "conv."))
        else:
            out.update(attention_named(op["mixer"], pre + "self_attn."))
        mix, pre = ffn["mixer"], pre + "feed_forward."
        if pattern[2 * i + 1] == "-":
            for ours, theirs in (("gate", "w1"), ("up", "w3"),
                                 ("down", "w2")):
                out[pre + theirs + ".weight"] = mix[ours]["weight"]
            continue
        out[pre + "gate.weight"] = mix["gate_weight"]
        for ours, theirs in (("wg", "w1"), ("w1", "w3"), ("w2", "w2")):
            out[pre + "experts." + theirs] = mix[ours]
        held = ((buffers or {}).get("1", {}).get(f"layer{2 * i + 1}", {})
                .get("mixer", {}))
        for ours, theirs in (("select_bias", "expert_bias"),
                             ("pick_table", "pick_table")):
            if ours in held:
                out[pre + theirs] = held[ours]
    return out


def reference_params(model):
    """The model's parameters, selection bias and pick tables (device
    arrays, no copy) under the names the reference reads."""
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


def mixer_blocks(model, plain_dtype=None):
    """The system's FIRST convolution mixer, that mixer's LOCAL part alone
    (``nn.ShortConv._local``: the split, both gates and the convolution,
    from the in-projection's output) and its FIRST attention mixer against
    the reference's ``short_conv`` / ``short_conv_local`` / ``attention``
    on the same parameters: for each, the relative L2 distance of the
    output and of the worst gradient leaf (the parameters it reads and its
    input), as ``{"conv": {"out": .., "grad": .., "leaf": ..},
    "conv_local": {..}, "attention": {..}}``. The input is (1, the cell's
    ``seq_len``, hidden; three times hidden for the local part) of N(0, 1)
    from the seed, as the block's norm hands it over, and the backward's
    seed a second tensor of the output's shape; both rounded to bf16
    first, so that neither side rounds its input (the local part's taps
    too: what it compares is the arithmetic). The system runs as the
    step runs it (the cell's precision, the mixer's own forward: on the
    chip at the timed length, so through ``flash_fwd`` and its backward at
    8,192 keys of head 64); the reference in float32 'highest'. With
    ``plain_dtype`` the reference wholly in that dtype stands where the
    system stood: the control's reading.

    The local part is the comparison that holds the PRECISION: the system
    computes it in float32 and rounds once (on the chip not even that: XLA
    keeps the float32 where the comparison widens again, and the output
    reads 0) where a bf16 evaluation rounds after every gate, tap and sum;
    the whole mixers' readings are mostly their products' outputs' one
    rounding each, which both precisions share."""
    import types
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.nn.module import functional_apply
    from benchmark.kinds.train import _policy
    cfg, cell, seed = _ASKED["cfg"], _ASKED["cell"], _ASKED["seed"]
    dec, mixers = decoder_of(model), {}
    for i, kind in enumerate(dec.pattern):      # by the pattern's kind, not
        if kind in "C*":                        # by what a fault left of it
            mixers.setdefault("conv" if kind == "C" else "attention",
                              dec._modules[f"layer{i}"].mixer)
    if "conv" in mixers:
        mixers["conv_local"] = mixers["conv"]
    e = cfg["hidden_size"]
    policy = _policy(cell["precision"])
    names = {"conv": conv_named, "conv_local": local_named,
             "attention": attention_named}
    plain_fn = {"conv": reference.short_conv,
                "conv_local": reference.short_conv_local,
                "attention": reference.attention}

    def system(kind, mixer):
        def whole(p, x):
            y, _ = functional_apply(
                mixer, policy.cast_params_for_compute(p),
                mixer.buffer_tree(), x.astype(policy.compute_dtype),
                training=True)
            return y

        def local(p, bcx):      # the method as the mixer's forward calls it
            stand_in = types.SimpleNamespace(
                embed_dim=mixer.embed_dim,
                conv_weight=policy.cast_params_for_compute(p)["conv_weight"])
            return type(mixer)._local(stand_in,
                                      bcx.astype(policy.compute_dtype))
        return local if kind == "conv_local" else whole

    def plain(kind, dtype):
        def run(p, x):
            p = jax.tree_util.tree_map(lambda a: a.astype(dtype), p)
            return plain_fn[kind](names[kind](p), "", x.astype(dtype), cfg)
        return run

    def output_and_gradients(kind, mixer, run, precision, x, seed_y):
        def scalar(p, x, seed_y):
            y = run(p, x).astype(jnp.float32)
            return jnp.sum(y * seed_y), y

        def both(p, x, seed_y):    # all three arguments: a closed-over
            # tensor would be a constant of the program and a new compile
            # a seed
            (_, y), (gp, gx) = jax.value_and_grad(
                scalar, argnums=(0, 1), has_aux=True)(p, x, seed_y)
            return dict(names[kind](gp), out=y, x=gx)

        params = mixer.parameter_tree()
        if kind == "conv_local":    # exact in bf16, as its input is
            params = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16).astype(a.dtype), params)
        with jax.default_matmul_precision(precision):
            return jax.jit(both)(params, x, seed_y)

    read = {}
    for kind, mixer in mixers.items():
        rng = np.random.default_rng(seed)
        x, seed_y = (jnp.asarray(
            rng.standard_normal((1, cell["seq_len"], width), np.float32),
            jnp.bfloat16).astype(jnp.float32)
            for width in ((3 * e if kind == "conv_local" else e), e))
        want = output_and_gradients(kind, mixer, plain(kind, jnp.float32),
                                    "highest", x, seed_y)
        got = output_and_gradients(kind, mixer, plain(kind, plain_dtype),
                                   "highest", x, seed_y) if plain_dtype \
            else output_and_gradients(kind, mixer, system(kind, mixer),
                                      None, x, seed_y)
        rel = {k: float(jnp.linalg.norm((got[k] - want[k]).ravel())
                        / jnp.linalg.norm(want[k].ravel())) for k in want}
        out = rel.pop("out")
        leaf = max(rel, key=lambda k: rel[k] if np.isfinite(rel[k])
                   else np.inf)
        read[kind] = {"out": out, "grad": rel[leaf], "leaf": leaf}
    return read


def _gated(numbers, model, plain_dtype=None):
    """The two numbers as they are where ``mixer_blocks`` reads within the
    cell's ``reference.blocks`` (``{block: {"out_rtol": .., "grad_rtol":
    ..}}``) on every block, and NaN twice where it does not, so that
    ``kinds.train.reference_check`` says not ok; the reading beside its
    limits goes to standard error as one ``benchmark detail`` line."""
    limits = _ASKED["cell"]["reference"]["blocks"]
    read = mixer_blocks(model, plain_dtype)
    ok = set(read) == set(limits) and all(
        r["out"] <= limits[kind]["out_rtol"]
        and r["grad"] <= limits[kind]["grad_rtol"]
        for kind, r in read.items())
    print("benchmark detail mixer_blocks: " + json.dumps(dict(
        read, ok=ok, limits=limits)), file=sys.stderr)
    return numbers if ok else (float("nan"), float("nan"))


def reference_loss_and_grad_norm(model, cfg, data, labels):
    return _gated(_plain_numbers(model, cfg, data, labels), model)


# ------------------------------------------------------- negative controls

#: the faults of the convolution mixer's local part (``_faulty_local``)
CONV_FAULTS = ("taps_reversed", "no_b_gate", "no_c_gate", "silu_on_conv")
#: what ``planted`` can break in the SYSTEM's modules
SYSTEM_FAULTS = CONV_FAULTS + ("no_qk_norm", "no_rope", "softmax_scores",
                               "untied_head")
#: the controls of ``benchmark.controls``: those, and the plain reference
#: computed wholly in bf16 standing where the system stood
FAULTS = SYSTEM_FAULTS + ("reference_bf16",)


def _faulty_local(fault):
    """``nn.ShortConv._local`` with ONE thing wrong: the taps reversed, so
    that position t reads t .. t+k-1 (it looks AHEAD); the ``B`` gate left
    out (``g = x``); the ``C`` gate left out (``y = c``); Mamba's SiLU on
    the convolution's output."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.nn.short_conv import causal_depthwise_conv

    def local(mixer, bcx):
        e = mixer.embed_dim
        b, c, x = (bcx[..., i * e:(i + 1) * e].astype(jnp.float32)
                   for i in range(3))
        g = x if fault == "no_b_gate" else b * x
        if fault == "taps_reversed":
            conv = causal_depthwise_conv(
                g[:, ::-1], mixer.conv_weight)[:, ::-1]
        else:
            conv = causal_depthwise_conv(g, mixer.conv_weight)
        if fault == "silu_on_conv":
            conv = jax.nn.silu(conv)
        return (conv if fault == "no_c_gate" else c * conv
                ).astype(bcx.dtype)
    return local


@contextlib.contextmanager
def planted(model, fault):
    """ONE fault of the mathematics in the system's own modules while the
    block runs (the plain reference reads none of this): the four of the
    convolution mixer (``_faulty_local`` in ``nn.ShortConv._local``'s
    place), q/k norm left out, rotation left out, a softmax over the picked
    logits for the picked sigmoid scores over their sum, the head untied
    (another seeded matrix in the tied head's place).
    ``reference_bf16`` breaks nothing in the system: the plain reference
    wholly in bf16 gives the numbers that are compared as the system's,
    the limits' second reading.

    ``benchmark.controls`` computes the reference once, on the sound model,
    and does not ask the builder again, so while a control is planted the
    system's side of ``kinds.train`` carries ``mixer_blocks``' verdict."""
    import jax.numpy as jnp
    from benchmark.kinds import train as kind
    from bigdl_tpu import nn
    from bigdl_tpu.parallel.expert import MoE
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}: one of {FAULTS}")
    with contextlib.ExitStack() as undo:
        def swap(obj, name, value, set_=setattr):
            undo.callback(set_, obj, name, getattr(obj, name))
            set_(obj, name, value)

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
        if fault in CONV_FAULTS:
            swap(nn.ShortConv, "_local", _faulty_local(fault))
        for m in model.modules():
            if isinstance(m, MoE) and fault == "softmax_scores":
                swap(m, "score", "softmax_picked")
            elif isinstance(m, nn.MultiHeadAttention):
                if fault == "no_qk_norm":
                    swap(m, "qk_norm", False)
                elif fault == "no_rope":
                    swap(m, "rope", False)
            elif isinstance(m, nn.TiedLMHead) and fault == "untied_head":
                # a plain reference, not a child: past Module.__setattr__
                swap(m, "embed_ref", nn.LookupTable(
                    m.embed_ref.n_index, m.embed_ref.n_output),
                    object.__setattr__)
        yield


def train_flops_per_record(cfg, cell):
    return flops_lfm2.train_flops_per_record(cfg, cell["seq_len"])


def flash_shape(cfg, cell):
    """(batch, heads, seq, head_dim) of the flash-attention call of the
    ``*`` block in this cell's train step (GQA is expanded before the
    kernel; the head is hidden / heads, the family has no key for it)."""
    return (cell["batch_size"], cfg["num_attention_heads"], cell["seq_len"],
            flops_lfm2.head_dim(cfg))
