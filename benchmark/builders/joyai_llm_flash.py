"""``joyai_llm_flash`` configuration (JoyAI-LLM-Flash; DeepSeek-V3's keys
and layers) -> the program's pattern-built LM of latent-attention layers
over a dense and then expert feed-forwards with a multi-token-prediction
module, its training data, and its parameters under the plain reference's
names.

The configuration file holds this chip's share: ``n_routed_experts`` is how
many routed experts are HELD (ids 0 .. n-1), ``vocab_size`` the held rows,
``num_hidden_layers`` the stage's layers; the published values stand beside
them under ``published``. The router keeps the published width. WHICH
experts are ids 0 .. n-1 is the configuration's ``placement``: dealt by
measured load (``place_experts``), so that every seed gives this chip an
even share of the picks. ``training`` holds what is no key of the public
config: ``remat``, ``router_gradient`` (as ``builders/afmoe.py``),
``router_picks`` (``"token_id"``: each token's experts from a table by its
id, ``freeze_picks``) and ``mtp_loss_weight``; the plain reference reads
the last three.

A family's functions, as ``builders/qwen2.py`` lists them (``README.md`` is
a fixed file): ``build``, ``criterion``, ``train_samples``,
``reference_batch``, ``reference_params``,
``reference_loss_and_grad_norm``, ``train_flops_per_record``; for
``python -m benchmark.controls``: ``FAULTS``, ``planted``. The comparison
that decides ``correct`` holds one thing more than the two numbers the
train kind asks for: ``latent_block``, the system's first latent-attention
mixer against the reference's on a fixed stream, output and every gradient
leaf by their relative L2 distance (the loss and the gradient norm are
means that bf16 roundings, a rotary term and a softmax's scale all but
cancel in; a distance between tensors does not). No
``flash_shape``: the readers that ask for it find flash calls by ONE head
size, and this family's have two (``flops_mla.head_sizes``).
"""

from __future__ import annotations

import contextlib
import json
import sys

import numpy as np

from benchmark import flops_mla
# the same for every family that build_hybrid_lm builds: the fused-CE
# criterion (it reads the prediction module's stream and weight from the
# model's own training output), Zipf rows over the held slice, where the
# decoder sits; and the deal of a load-balancing placement
from benchmark.builders import nemotron_h
from benchmark.builders.afmoe import deal
from benchmark.builders.nemotron_h import (criterion, decoder_of,  # noqa: F401
                                           train_samples)
from benchmark.reference import joyai_llm_flash as reference

#: what the comparison last asked a batch for. ``reference_batch`` is its
#: one call that is handed the cell, so the block check's limits, precision,
#: length and seed are noted there
_ASKED = {}


def reference_batch(cfg, cell, seed):
    _ASKED.update(cfg=cfg, cell=cell, seed=seed)
    return nemotron_h.reference_batch(cfg, cell, seed)


def hf_config(cfg):
    """The file as the public ``config.json`` reads: the routed-expert
    count is the router's width again (the held ones go in beside it)."""
    return dict(cfg, n_routed_experts=cfg["published"]["n_routed_experts"])


def build(cfg, seed):
    """The config through ``interop.hf.joyai_llm_flash_lm_kwargs`` ->
    ``build_hybrid_lm``, weights from the seed. ``training.remat`` is
    applied as ``Optimizer.set_remat("block")`` applies it (the train kind
    has no line for it): ``remat_blocks`` of the main stack and of the
    prediction module's. ``training.router_gradient`` ``"none"`` is
    ``MoE(train_router=False)``; ``training.router_picks`` ``"token_id"``
    is ``MoE(pick_rows=vocab_size)`` with the tables filled here
    (``freeze_picks``, again after the placement has relabelled the
    routers' outputs)."""
    from bigdl_tpu import nn
    from bigdl_tpu.interop.hf import joyai_llm_flash_lm_kwargs
    from bigdl_tpu.models.hybrid import build_hybrid_lm
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(seed)
    np.random.seed(seed % (2 ** 32))
    training = cfg["training"]
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
    model = build_hybrid_lm(**joyai_llm_flash_lm_kwargs(
        hf_config(cfg), held_experts=reference.held_experts(cfg),
        train_router=router == "full",
        mtp_loss_weight=training["mtp_loss_weight"],
        picks_by_token=picks == "token_id"))
    if picks == "token_id":
        freeze_picks(model)
    if cfg.get("placement"):    # before remat: its forward is listened to
        place_experts(model, cfg, seed)
        if picks == "token_id":
            freeze_picks(model)
    for m in model.modules():
        if isinstance(m, nn.HybridDecoder):
            m.remat_blocks = remat == "block"
    return model


def freeze_picks(model):
    """Fills every expert layer's ``pick_table``, the prediction module's
    too: row ``t`` holds the top k of that layer's router over token
    ``t``'s embedding row as both stand now, at set-up (score + selection
    bias; the block's norm, whose weight is one, scales a row and moves no
    rank). From here on a token's picks are its id's: the stream the
    routers would read moves as the model trains, the table does not."""
    from bigdl_tpu import nn
    from bigdl_tpu.parallel.expert import MoE
    rows = np.asarray(next(m for m in model.modules() if isinstance(
        m, nn.LookupTable)).weight, np.float32)
    for moe in model.modules():
        if isinstance(moe, MoE):
            scores = rows @ np.asarray(moe.gate_weight, np.float32) \
                + np.asarray(moe.select_bias, np.float32)
            moe.pick_table = np.argsort(
                -scores, axis=1, kind="stable")[:, :moe.k].astype(np.float32)


def measured_loads(model, rows):
    """Picks an expert of EVERY router, the prediction module's too,
    (rows, expert layers in ``model.modules()`` order, router width), on
    each row of token ids as a batch of one: the system's own TRAINING
    forward at the training precision (the module runs in no other),
    ``MoE._route`` listened to. ``builders/afmoe.measured_loads`` runs the
    eval forward, which stops at the main stack."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.nn.module import functional_apply
    from bigdl_tpu.ops.precision import DtypePolicy
    from bigdl_tpu.parallel.expert import MoE
    route, cast = MoE._route, DtypePolicy.bf16().cast_params_for_compute
    layers = [m for m in model.modules() if isinstance(m, MoE)]

    @jax.jit
    def loads(params, buffers, ids):
        seen = {}

        def listening(moe, x):
            picked, weight = route(moe, x)
            seen[id(moe)] = jnp.bincount(picked.reshape(-1),
                                         length=moe.n_experts)
            return picked, weight

        MoE._route = listening
        try:
            functional_apply(model, cast(params), buffers, ids,
                             training=True)
        finally:
            MoE._route = route
        return jnp.stack([seen[id(m)] for m in layers])

    params, buffers = model.parameter_tree(), model.buffer_tree()
    return np.stack([np.asarray(loads(params, buffers, jnp.asarray(r[None])))
                     for r in rows])


def place_experts(model, cfg, seed):
    """Relabels every router's outputs so that ids 0 .. n-1, the experts
    held here, are chip 0's of ``builders/afmoe.deal`` over the picks
    measured on the stream the deployment trains on (``placement``: the
    epoch's rows of Zipf tokens over the held rows, from the seed as
    ``train_samples`` draws them), as ``builders/afmoe.place_experts``
    (which reads another family's key for the expert count and the eval
    forward). The experts' weights are seeded alike, so a relabelling
    chooses which of them this chip holds and changes no layer. Writes the
    held experts' measured picks a step to standard error as one
    ``benchmark detail`` line."""
    from benchmark import traffic
    from bigdl_tpu.parallel.expert import MoE
    place = cfg["placement"]
    if place["by"] != "measured_load":
        raise ValueError(f"placement.by {place['by']!r}: 'measured_load'")
    rows = traffic.zipf_tokens(seed, place["records"], place["seq_len"],
                               cfg["vocab_size"], place["token_zipf"])
    held = cfg["n_routed_experts"]
    chips = cfg["published"]["n_routed_experts"] // held
    layers = [m for m in model.modules() if isinstance(m, MoE)]
    loads = measured_loads(model, rows[:, :-1].astype(np.float32)).mean(0)
    picks = []
    for moe, load in zip(layers, loads):
        order = deal(load, chips)
        moe.gate_weight = moe.gate_weight[:, order]
        moe.select_bias = moe.select_bias[order]
        picks.append(load[order[:held]].round().astype(int).tolist())
    print("benchmark detail placement: " + json.dumps(
        {"held_picks": picks,
         "held_share": [sum(p) / float(load.sum())
                        for p, load in zip(picks, loads)]}), file=sys.stderr)


def latent_named(mix, pre=""):
    """A latent-attention mixer's parameters (or their gradient) under the
    reference's names."""
    out = {pre + theirs: mix[ours] for ours, theirs in (
        ("q_a_weight", "q_a_proj.weight"), ("q_b_weight", "q_b_proj.weight"),
        ("kv_a_weight", "kv_a_proj_with_mqa.weight"),
        ("kv_b_weight", "kv_b_proj.weight"),
        ("out_proj_weight", "o_proj.weight"))}
    for ours, theirs in (("q_a_norm", "q_a_layernorm"),
                         ("kv_a_norm", "kv_a_layernorm")):
        out[pre + theirs + ".weight"] = mix[ours]["weight"]
    return out


def _layer_params(out, pre, att, ffn, ffn_buffers, dense):
    """One layer's two blocks under the reference's names."""
    out[pre + "input_layernorm.weight"] = att["norm"]["weight"]
    out[pre + "post_attention_layernorm.weight"] = ffn["norm"]["weight"]
    out.update(latent_named(att["mixer"], pre + "self_attn."))
    mix, pre = ffn["mixer"], pre + "mlp."
    if dense:
        for name in ("gate", "up", "down"):
            out[pre + name + "_proj.weight"] = mix[name]["weight"]
        return
    out[pre + "gate.weight"] = mix["gate_weight"]
    out[pre + "gate.e_score_correction_bias"] = \
        ffn_buffers["mixer"]["select_bias"]
    if "pick_table" in ffn_buffers["mixer"]:
        out[pre + "gate.pick_table"] = ffn_buffers["mixer"]["pick_table"]
    for ours, theirs in (("wg", "gate_proj"), ("w1", "up_proj"),
                         ("w2", "down_proj")):
        out[pre + "experts." + theirs] = mix[ours]
        out[pre + "shared_experts." + theirs + ".weight"] = \
            mix["shared_" + ours]


def named(tree, buffers, pattern):
    """A parameter tree of the model (or its gradient) and its buffers
    under the names the reference reads. Layer ``i`` of the model is the
    decoder's blocks ``2i`` (attention) and ``2i + 1`` (feed-forward); the
    prediction module is layer ``num_hidden_layers``."""
    dec, dec_buf = tree["1"], buffers["1"]
    out = {"model.embed_tokens.weight": tree["0"]["weight"],
           "model.norm.weight": dec["final_norm"]["weight"],
           "lm_head.weight": tree["2"]["weight"]}
    depth = len(pattern) // 2
    for i in range(depth):
        _layer_params(out, f"model.layers.{i}.", dec[f"layer{2 * i}"],
                      dec[f"layer{2 * i + 1}"],
                      dec_buf.get(f"layer{2 * i + 1}"),
                      pattern[2 * i + 1] == "-")
    if "mtp" in tree:
        mtp, pre = tree["mtp"], f"model.layers.{depth}."
        out[pre + "enorm.weight"] = mtp["norm_embed"]["weight"]
        out[pre + "hnorm.weight"] = mtp["norm_hidden"]["weight"]
        out[pre + "eh_proj.weight"] = mtp["proj"]["weight"]
        out[pre + "shared_head.norm.weight"] = \
            mtp["stack"]["final_norm"]["weight"]
        _layer_params(out, pre, mtp["stack"]["layer0"],
                      mtp["stack"]["layer1"],
                      buffers["mtp"]["stack"]["layer1"], False)
    return out


def reference_params(model):
    """The model's parameters and the routers' selection bias (device
    arrays, no copy) under the names the reference reads."""
    return named(model.parameter_tree(), model.buffer_tree(),
                 decoder_of(model).pattern)


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


def latent_block(model, plain_dtype=None):
    """The system's FIRST latent-attention mixer against the reference's
    ``attention`` on the same parameters: relative L2 distance of the
    output and of each gradient leaf (the mixer's seven parameters and its
    input), as ``{"out": .., "grad": the worst leaf's, "leaf": its name}``.
    The stream is (1, the cell's ``seq_len``, hidden) of N(0, 1) from the
    seed, as the block's norm hands it over, and the backward's seed a
    second such tensor; both rounded to bf16 first, so that neither side
    rounds its input. The system runs as the step runs it (the cell's
    precision, the mixer's own forward: on the chip at the timed length,
    so through ``flash_mla_*`` at the timed shape); the reference in
    float32 'highest'. With ``plain_dtype`` the reference wholly in that
    dtype stands where the system stood: the control's reading."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu import nn
    from bigdl_tpu.nn.module import functional_apply
    from benchmark.kinds.train import _policy
    cfg, cell, seed = _ASKED["cfg"], _ASKED["cell"], _ASKED["seed"]
    mixer = next(m for m in model.modules()
                 if isinstance(m, nn.LatentAttention))
    rng = np.random.default_rng(seed)
    shape = (1, cell["seq_len"], cfg["hidden_size"])
    x, seed_y = (jnp.asarray(rng.standard_normal(shape, np.float32),
                             jnp.bfloat16).astype(jnp.float32)
                 for _ in range(2))
    policy = _policy(cell["precision"])

    def system(p, x):
        y, _ = functional_apply(
            mixer, policy.cast_params_for_compute(p), mixer.buffer_tree(),
            x.astype(policy.compute_dtype), training=True)
        return y

    def plain(dtype):
        def run(p, x):
            p = jax.tree_util.tree_map(lambda a: a.astype(dtype), p)
            return reference.attention(latent_named(p), "", x.astype(dtype),
                                       cfg)
        return run

    def output_and_gradients(run, precision):
        def scalar(p, x, seed_y):
            y = run(p, x).astype(jnp.float32)
            return jnp.sum(y * seed_y), y

        def both(p, x, seed_y):    # all three arguments: a closed-over
            # tensor would be a 64 MB constant of the program and a new
            # compile a seed
            (_, y), (gp, gx) = jax.value_and_grad(
                scalar, argnums=(0, 1), has_aux=True)(p, x, seed_y)
            return dict(latent_named(gp), out=y, x=gx)

        with jax.default_matmul_precision(precision):
            return jax.jit(both)(mixer.parameter_tree(), x, seed_y)

    want = output_and_gradients(plain(jnp.float32), "highest")
    got = output_and_gradients(plain(plain_dtype), "highest") \
        if plain_dtype else output_and_gradients(system, None)
    rel = {k: float(jnp.linalg.norm((got[k] - want[k]).ravel())
                    / jnp.linalg.norm(want[k].ravel())) for k in want}
    out = rel.pop("out")
    leaf = max(rel, key=lambda k: rel[k] if np.isfinite(rel[k]) else np.inf)
    return {"out": out, "grad": rel[leaf], "leaf": leaf}


def _gated(numbers, model, plain_dtype=None):
    """The two numbers as they are where ``latent_block`` reads within the
    cell's ``reference.latent_out_rtol`` / ``latent_grad_rtol``, and NaN
    twice where it does not, so that ``kinds.train.reference_check`` says
    not ok; the reading beside its limits goes to standard error as one
    ``benchmark detail`` line."""
    tol = _ASKED["cell"]["reference"]
    read = latent_block(model, plain_dtype)
    ok = bool(read["out"] <= tol["latent_out_rtol"]
              and read["grad"] <= tol["latent_grad_rtol"])
    print("benchmark detail latent_block: " + json.dumps(dict(
        read, ok=ok, latent_out_rtol=tol["latent_out_rtol"],
        latent_grad_rtol=tol["latent_grad_rtol"])), file=sys.stderr)
    return numbers if ok else (float("nan"), float("nan"))


def reference_loss_and_grad_norm(model, cfg, data, labels):
    return _gated(_plain_numbers(model, cfg, data, labels), model)


# ------------------------------------------------------- negative controls

#: what ``planted`` can break in the SYSTEM's modules
SYSTEM_FAULTS = ("no_rope_term", "no_latent_norm", "value_head_scale",
                 "no_mtp_loss", "no_route_scale")
#: the controls of ``benchmark.controls``: those, and the plain reference
#: computed wholly in bf16 standing where the system stood
FAULTS = SYSTEM_FAULTS + ("reference_bf16",)


@contextlib.contextmanager
def planted(model, fault):
    """ONE fault of the mathematics in the system's own modules while the
    block runs (the plain reference reads none of this): the rotary term
    ``qr kr^T`` dropped (both rotary parts zero), the key/value latent's
    norm dropped, the softmax's scale taken from the value head
    (1/sqrt(128) for 1/sqrt(192)), the prediction loss's weight zero,
    ``route_scale`` 1 for the published 2.5. ``reference_bf16`` breaks
    nothing in the system: the plain reference wholly in bf16 gives the
    numbers that are compared as the system's, the limits' second reading.

    ``benchmark.controls`` computes the reference once, on the sound model,
    and does not ask the builder again, so while a control is planted the
    system's side of ``kinds.train`` carries ``latent_block``'s verdict."""
    import jax.numpy as jnp
    from benchmark.kinds import train as kind
    from bigdl_tpu import nn
    from bigdl_tpu.nn import attention
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
        if fault == "no_rope_term":
            swap(attention, "rope_rotate",
                 lambda x, *args: jnp.zeros_like(x))
        for m in model.modules():
            if isinstance(m, nn.LatentAttention):
                if fault == "no_latent_norm":
                    norm = m.kv_a_norm
                    undo.callback(norm.__dict__.pop, "update_output")
                    norm.update_output = lambda x: x
                elif fault == "value_head_scale":
                    swap(m, "softmax_scale", m.v_head_dim ** -0.5)
            elif fault == "no_mtp_loss" and isinstance(m, nn.MTPModule):
                swap(m, "loss_weight", 0.0)
            elif fault == "no_route_scale" and isinstance(m, MoE):
                swap(m, "route_scale", 1.0)
        yield


def train_flops_per_record(cfg, cell):
    return flops_mla.train_flops_per_record(cfg, cell["seq_len"])
