"""Olmo-Hybrid configuration (Allen AI Olmo-Hybrid-7B, ``model_type``
``olmo_hybrid``) -> the program's pattern-built LM of gated delta-rule
linear-attention and full-attention layers over dense SwiGLU feed-forwards,
every block normed on its OUTPUT alone, its training data, and its
parameters under the plain reference's names.

The configuration file holds this chip's share: ``num_attention_heads`` /
``num_key_value_heads`` and ``linear_num_key_heads`` /
``linear_num_value_heads`` are the heads HELD of each layer, ``vocab_size``
the held rows, ``layer_types`` the stage's layers; the published values
stand beside them under ``published``. ``training`` holds what is no key of
the public config: ``remat``.

A family's functions, as ``builders/qwen2.py`` lists them: ``build``,
``criterion``, ``train_samples``, ``reference_batch``,
``reference_params``, ``reference_loss_and_grad_norm``,
``train_flops_per_record``, ``flash_shape``; for ``python -m
benchmark.controls``: ``FAULTS``, ``planted``. The comparison that decides
``correct`` holds one thing more than the two numbers the train kind asks
for: ``mixer_blocks``, the system's first linear-attention mixer, the
RECURRENCE alone and its attention mixer against the reference's at the
TIMED length (the whole model is compared on one record), output and every
gradient leaf by their relative L2 distance, as
``builders/lfm2_moe.mixer_blocks`` holds its mixers.
"""

from __future__ import annotations

import contextlib
import json
import sys

import numpy as np

from benchmark import flops_olmo_hybrid
# the same for every family that build_hybrid_lm builds: the fused-CE
# criterion, Zipf rows over the held slice, where the decoder sits
from benchmark.builders import nemotron_h
from benchmark.builders.nemotron_h import (criterion, decoder_of,  # noqa: F401
                                           train_samples)
from benchmark.reference import olmo_hybrid as reference

#: what the comparison last asked a batch for. ``reference_batch`` is its
#: one call that is handed the cell, so the block check's limits, precision,
#: length and seed are noted there
_ASKED = {}

KINDS = {"linear_attention": "D", "full_attention": "*"}


def reference_batch(cfg, cell, seed):
    _ASKED.update(cfg=cfg, cell=cell, seed=seed)
    return nemotron_h.reference_batch(cfg, cell, seed)


def lm_kwargs(cfg):
    """The file's keys as ``build_hybrid_lm``'s keyword groups: a layer is
    its mixer's block and a dense block, each normed on its output alone;
    MHA without a positional term and with the q/k norm over the whole
    projection; nothing carries a bias."""
    h, dk, dv = reference.linear_heads(cfg)
    if cfg["rope_parameters"]["rope_theta"] is not None \
            or cfg["attention_bias"] or cfg["tie_word_embeddings"] \
            or cfg["hidden_act"] != "silu":
        raise ValueError("olmo_hybrid: no rotation, no bias, an untied "
                         "head and SiLU are what the builder maps")
    return dict(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
        pattern="".join(KINDS[kind] + "-" for kind in cfg["layer_types"]),
        delta=dict(num_heads=h, key_head_dim=dk, value_head_dim=dv,
                   conv_kernel=cfg["linear_conv_kernel_dim"],
                   allow_neg_eigval=cfg["linear_allow_neg_eigval"],
                   norm_eps=cfg["rms_norm_eps"]),
        attention=dict(num_heads=cfg["num_attention_heads"],
                       num_kv_heads=cfg["num_key_value_heads"],
                       head_dim=cfg["head_dim"], with_bias=False,
                       qk_norm="projection",
                       qk_norm_eps=cfg["rms_norm_eps"]),
        mlp=dict(hidden_size=cfg["intermediate_size"]),
        norm_eps=cfg["rms_norm_eps"], post_norm=True, pre_norm=False)


def build(cfg, seed):
    """The config through ``lm_kwargs`` -> ``build_hybrid_lm``, weights
    from the seed. ``training.remat`` is applied as
    ``Optimizer.set_remat("block")`` applies it (the train kind has no line
    for it): the decoder's ``remat_blocks``."""
    from bigdl_tpu.models.hybrid import build_hybrid_lm
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(seed)
    np.random.seed(seed % (2 ** 32))
    remat = cfg.get("training", {}).get("remat")
    if remat not in (None, "block"):
        raise ValueError(f"training.remat {remat!r}: 'block' or nothing")
    model = build_hybrid_lm(**lm_kwargs(cfg))
    decoder_of(model).remat_blocks = remat == "block"
    return model


def delta_named(mix, pre=""):
    """A linear-attention mixer's parameters (or their gradient) under the
    reference's names."""
    return {pre + "in_proj.weight": mix["in_proj_weight"],
            pre + "conv.weight": mix["conv_weight"],
            pre + "A_log": mix["A_log"], pre + "dt_bias": mix["dt_bias"],
            pre + "o_norm.weight": mix["norm_weight"],
            pre + "o_proj.weight": mix["out_proj_weight"]}


def attention_named(mix, pre=""):
    """An attention mixer's parameters (or their gradient) under the
    reference's names."""
    return {pre + "qkv_proj.weight": mix["in_proj_weight"],
            pre + "o_proj.weight": mix["out_proj_weight"],
            pre + "q_norm.weight": mix["q_norm"]["weight"],
            pre + "k_norm.weight": mix["k_norm"]["weight"]}


def named(tree, pattern):
    """A parameter tree of the model (or its gradient) under the names the
    reference reads. Layer ``i`` of the model is the decoder's blocks
    ``2i`` (its mixer) and ``2i + 1`` (its feed-forward)."""
    dec = tree["1"]
    out = {"model.embed_tokens.weight": tree["0"]["weight"],
           "model.norm.weight": dec["final_norm"]["weight"],
           "lm_head.weight": tree["2"]["weight"]}
    for i in range(len(pattern) // 2):
        pre = f"model.layers.{i}."
        op, ffn = dec[f"layer{2 * i}"], dec[f"layer{2 * i + 1}"]
        out[pre + "post_attention_layernorm.weight"] = \
            op["norm_post"]["weight"]
        out[pre + "post_feedforward_layernorm.weight"] = \
            ffn["norm_post"]["weight"]
        if pattern[2 * i] == "D":
            out.update(delta_named(op["mixer"], pre + "linear_attn."))
        else:
            out.update(attention_named(op["mixer"], pre + "self_attn."))
        for ours in ("gate", "up", "down"):
            out[pre + f"mlp.{ours}_proj.weight"] = \
                ffn["mixer"][ours]["weight"]
    return out


def reference_params(model):
    """The model's parameters (device arrays, no copy) under the names the
    reference reads."""
    return named(model.parameter_tree(), decoder_of(model).pattern)


def _plain_numbers(model, cfg, data, labels, dtype=None):
    """(loss, gradient norm) of the plain reference, in float32 'highest'
    or, for the control, wholly in ``dtype``."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda p, x, y: reference.loss_and_grad_norm(
        p, x, y, cfg, dtype or jnp.float32))
    with jax.default_matmul_precision("highest"):
        loss, gn = fn(reference_params(model),
                      jnp.asarray(data, jnp.int32) - 1,
                      jnp.asarray(labels, jnp.int32) - 1)
    return float(loss), float(gn)


def _recurrence_case(mixer, rng, length):
    """What a recurrence reads at ``length`` positions, as the mixer hands
    it over: q and k of N(0, 1) made unit vectors (q over ``sqrt(d_k)``
    too), v of N(0, 1), ``g`` from the mixer's own ``A_log`` and
    ``dt_bias`` under an N(0, 1) input, ``beta`` the mixer's sigmoid of
    N(0, 1); q, k and v rounded to bf16, so that neither side rounds
    them."""
    import jax
    import jax.numpy as jnp
    h, dk, dv = mixer.num_heads, mixer.key_head_dim, mixer.value_head_dim

    def unit(shape):
        t = rng.standard_normal(shape, np.float32)
        return t / np.linalg.norm(t, axis=-1, keepdims=True)

    q = unit((1, length, h, dk)) * dk ** -0.5
    k = unit((1, length, h, dk))
    v = rng.standard_normal((1, length, h, dv), np.float32)
    a, b = (jnp.asarray(rng.standard_normal((1, length, h), np.float32))
            for _ in range(2))
    g = -jnp.exp(mixer.A_log) * jax.nn.softplus(a + mixer.dt_bias)
    beta = jax.nn.sigmoid(b) * (2.0 if mixer.allow_neg_eigval else 1.0)
    return tuple(jnp.asarray(t, jnp.bfloat16).astype(jnp.float32)
                 for t in (q, k, v)) + (g, beta)


def mixer_blocks(model, plain_dtype=None):
    """The system's FIRST linear-attention mixer (``delta``), the
    RECURRENCE alone as that mixer calls it, from (q, k, v, g, beta) to o
    (``delta_rule``), and its FIRST attention mixer (``attention``) against
    the reference's ``gated_delta_net`` / ``delta_rule`` / ``attention``:
    for each, the relative L2 distance of the output and of the worst
    gradient leaf (the parameters it reads and its input; the recurrence's
    five inputs), as ``{"delta": {"out": .., "grad": .., "leaf": ..},
    "delta_rule": {..}, "attention": {..}}``. The mixers' input is (1, the
    cell's ``seq_len``, hidden) of N(0, 1) from the seed, the recurrence's
    ``_recurrence_case``, and the backward's seed a second tensor of the
    output's shape; all rounded to bf16 first, so that neither side rounds
    its input. The system runs as the step runs it (the cell's precision,
    the mixer's own forward: on the chip at the timed length, so through
    ``flash_fwd`` and its backward at 8,192 keys of head 128 and through
    the chunked recurrence over 128 chunks); the reference in float32
    'highest', token by token. With ``plain_dtype`` the reference wholly in
    that dtype stands where the system stood: the control's reading.

    The recurrence alone is the comparison that holds the PRECISION: the
    system carries its state in float32 and rounds a chunk's operands once
    where a bf16 evaluation rounds the state after every token."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.nn import gated_delta_net
    from bigdl_tpu.nn.module import functional_apply
    from benchmark.kinds.train import _policy
    cfg, cell, seed = _ASKED["cfg"], _ASKED["cell"], _ASKED["seed"]
    dec, mixers = decoder_of(model), {}
    for i, kind in enumerate(dec.pattern):      # by the pattern's kind, not
        if kind in "D*":                        # by what a fault left of it
            mixers.setdefault("delta" if kind == "D" else "attention",
                              dec._modules[f"layer{i}"].mixer)
    if "delta" in mixers:
        mixers["delta_rule"] = mixers["delta"]
    e, length = cfg["hidden_size"], cell["seq_len"]
    policy = _policy(cell["precision"])
    names = {"delta": delta_named, "attention": attention_named}
    plain_fn = {"delta": reference.gated_delta_net,
                "attention": reference.attention}
    leaves = ("q", "k", "v", "g", "beta")

    def system(kind, mixer):
        def whole(p, x):
            y, _ = functional_apply(
                mixer, policy.cast_params_for_compute(p),
                mixer.buffer_tree(), x.astype(policy.compute_dtype),
                training=True)
            return y

        def recurrence(p, x):   # the function as the mixer's forward calls it
            q, k, v = (p[n].astype(policy.compute_dtype) for n in "qkv")
            return gated_delta_net.gated_delta_rule(
                q, k, v, p["g"], p["beta"], mixer.chunk_size)
        return recurrence if kind == "delta_rule" else whole

    def plain(kind, dtype):
        def run(p, x):
            p = jax.tree_util.tree_map(lambda a: a.astype(dtype), p)
            if kind == "delta_rule":
                return reference.delta_rule(*(p[n] for n in leaves))
            return plain_fn[kind](names[kind](p), "", x.astype(dtype), cfg)
        return run

    def output_and_gradients(kind, params, run, precision, x, seed_y):
        def scalar(p, x, seed_y):
            y = run(p, x).astype(jnp.float32)
            return jnp.sum(y * seed_y), y

        def both(p, x, seed_y):    # all three arguments: a closed-over
            # tensor would be a constant of the program and a new compile
            # a seed
            (_, y), (gp, gx) = jax.value_and_grad(
                scalar, argnums=(0, 1), has_aux=True)(p, x, seed_y)
            if kind == "delta_rule":    # its inputs are its leaves
                return dict(gp, out=y)
            return dict(names[kind](gp), out=y, x=gx)

        with jax.default_matmul_precision(precision):
            return jax.jit(both)(params, x, seed_y)

    read = {}
    for kind, mixer in mixers.items():
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((1, length, e), np.float32),
                        jnp.bfloat16).astype(jnp.float32)
        if kind == "delta_rule":
            params = dict(zip(leaves, _recurrence_case(mixer, rng, length)))
            wide = mixer.num_heads * mixer.value_head_dim
            shape = (1, length, mixer.num_heads, mixer.value_head_dim)
        else:
            params, wide, shape = mixer.parameter_tree(), e, (1, length, e)
        seed_y = jnp.asarray(rng.standard_normal((1, length, wide),
                                                 np.float32),
                             jnp.bfloat16).astype(jnp.float32).reshape(shape)
        want = output_and_gradients(kind, params, plain(kind, jnp.float32),
                                    "highest", x, seed_y)
        got = output_and_gradients(kind, params, plain(kind, plain_dtype),
                                   "highest", x, seed_y) if plain_dtype \
            else output_and_gradients(kind, params, system(kind, mixer),
                                      None, x, seed_y)
        rel = {k: float(jnp.linalg.norm(
            (got[k].astype(jnp.float32) - want[k]).ravel())
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

#: the faults of what a linear mixer hands its recurrence
#: (``_faulty_inputs``)
INPUT_FAULTS = ("k_not_normalised", "taps_reversed")
#: what ``planted`` can break in the SYSTEM's modules
SYSTEM_FAULTS = ("beta_not_doubled", "no_decay", "no_delta_term") \
    + INPUT_FAULTS + ("no_output_gate", "pre_norm_block", "no_qk_norm")
#: the controls of ``benchmark.controls``: those, and the plain reference
#: computed wholly in bf16 standing where the system stood
FAULTS = SYSTEM_FAULTS + ("reference_bf16",)


def _faulty_inputs(fault):
    """``nn.GatedDeltaNet._recurrence_inputs`` with ONE thing wrong: k
    left as the convolution gives it (not a unit vector); the taps
    reversed, so that position t reads t .. t+K-1 (it looks AHEAD)."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.nn.gated_delta_net import L2_EPS
    from bigdl_tpu.nn.short_conv import causal_depthwise_conv

    def inputs(mixer, proj):
        h, dk, dv = mixer.num_heads, mixer.key_head_dim, mixer.value_head_dim
        bsz, length, _ = proj.shape
        f32 = jnp.float32
        wide = proj[..., :mixer.conv_dim]
        if fault == "taps_reversed":
            conv = causal_depthwise_conv(wide[:, ::-1],
                                         mixer.conv_weight)[:, ::-1]
        else:
            conv = causal_depthwise_conv(wide, mixer.conv_weight)
        qkv = jax.nn.silu(conv)
        q = qkv[..., :mixer.d_key].reshape(bsz, length, h, dk)
        k = qkv[..., mixer.d_key:2 * mixer.d_key].reshape(bsz, length, h, dk)
        v = qkv[..., 2 * mixer.d_key:].reshape(bsz, length, h, dv)

        def unit(t):
            return t * jax.lax.rsqrt(jnp.sum(jnp.square(t), -1,
                                             keepdims=True) + L2_EPS)
        q = unit(q) * dk ** -0.5
        if fault != "k_not_normalised":
            k = unit(k)
        beta = jax.nn.sigmoid(proj[..., -2 * h:-h].astype(f32)) \
            * (2.0 if mixer.allow_neg_eigval else 1.0)
        g = -jnp.exp(mixer.A_log.astype(f32)) * jax.nn.softplus(
            proj[..., -h:].astype(f32) + mixer.dt_bias.astype(f32))
        return tuple(t.astype(proj.dtype) for t in (q, k, v)) + (g, beta)
    return inputs


@contextlib.contextmanager
def planted(model, fault):
    """ONE fault of the mathematics in the system's own modules while the
    block runs (the plain reference reads none of this): beta left in (0,
    1) where the configuration doubles it; the decay left out (``alpha =
    1``: ``g = 0`` into the recurrence); the delta term left out (``S_t =
    alpha S + beta k v^T``, plain gated linear attention: ``ops/delta_rule
    ._wy`` hands back ``W = 0`` and ``U = beta v``); k not normalised; the
    convolution's taps reversed; the output gate left out; a PRE-norm
    block where the family norms the output (``x + mixer(norm(x))`` with
    the same weight); the attention's q/k norm left out.
    ``reference_bf16`` breaks nothing in the system: the plain reference
    wholly in bf16 gives the numbers that are compared as the system's,
    the limits' second reading.

    ``benchmark.controls`` computes the reference once, on the sound model,
    and does not ask the builder again, so while a control is planted the
    system's side of ``kinds.train`` carries ``mixer_blocks``' verdict."""
    import jax
    import jax.numpy as jnp
    from benchmark.kinds import train as kind
    from bigdl_tpu import nn
    from bigdl_tpu.nn import gated_delta_net
    from bigdl_tpu.ops import delta_rule
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
        if fault in INPUT_FAULTS:
            swap(nn.GatedDeltaNet, "_recurrence_inputs",
                 _faulty_inputs(fault))
        elif fault == "no_decay":
            sound = gated_delta_net.gated_delta_rule
            swap(gated_delta_net, "gated_delta_rule",
                 lambda q, k, v, g, beta, chunk: sound(
                     q, k, v, 0.0 * g, beta, chunk))
        elif fault == "no_delta_term":
            swap(delta_rule, "_wy", lambda k, v, gc, beta, decay: (
                jnp.zeros(k.shape, jnp.float32),
                v.astype(jnp.float32) * beta[..., None]))
        elif fault == "no_output_gate":
            def ungated(mixer, o, z):
                o = o.astype(jnp.float32)
                o = o * jax.lax.rsqrt(
                    jnp.mean(jnp.square(o), -1, keepdims=True)
                    + mixer.norm_eps) * mixer.norm_weight.astype(jnp.float32)
                return o.reshape(z.shape).astype(z.dtype)
            swap(nn.GatedDeltaNet, "_gated_norm", ungated)
        elif fault == "pre_norm_block":
            swap(nn.HybridBlock, "update_output",
                 lambda block, x: x + block.mixer.forward(
                     block.norm_post.forward(x)))
        for m in model.modules():
            if isinstance(m, nn.GatedDeltaNet) \
                    and fault == "beta_not_doubled":
                swap(m, "allow_neg_eigval", False)
            elif isinstance(m, nn.MultiHeadAttention) \
                    and fault == "no_qk_norm":
                swap(m, "qk_norm", False)
        yield


def train_flops_per_record(cfg, cell):
    return flops_olmo_hybrid.train_flops_per_record(cfg, cell["seq_len"])


def flash_shape(cfg, cell):
    """(batch, heads, seq, head_dim) of the flash-attention call of the
    ``*`` block in this cell's train step (the heads held)."""
    return (cell["batch_size"], cfg["num_attention_heads"], cell["seq_len"],
            cfg["head_dim"])
