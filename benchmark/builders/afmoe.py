"""``afmoe`` configuration (Arcee Trinity) -> the program's pattern-built
LM of sliding-window and full attention layers over dense and expert
feed-forwards, its training data, and its parameters under the plain
reference's names.

The configuration file holds this chip's share: ``num_experts`` is how many
routed experts are HELD (ids 0 .. n-1), ``vocab_size`` the held rows,
``layer_types`` / ``num_dense_layers`` the stage's layers; the published
values stand beside them under ``published``. The router keeps the
published width. WHICH experts are ids 0 .. n-1 is the configuration's
``placement``: dealt by measured load (``place_experts``), so that every
seed gives this chip an even share of the picks.
"""

from __future__ import annotations

import contextlib
import json
import sys

import numpy as np

from benchmark import flops_afmoe
# the same for every family that build_hybrid_lm builds: the fused-CE
# criterion, Zipf rows over the held slice, where the decoder sits, the
# attention blocks' (batch, heads, seq, head_dim)
from benchmark.builders.nemotron_h import (criterion, decoder_of,  # noqa: F401
                                           flash_shape, reference_batch,
                                           train_samples)
from benchmark.reference import afmoe as reference


def hf_config(cfg):
    """The file as the public ``config.json`` reads: the expert count is
    the router's width again (the held ones go in beside it)."""
    return dict(cfg, num_experts=cfg["published"]["num_experts"])


def build(cfg, seed):
    """The config through ``interop.hf.afmoe_lm_kwargs`` ->
    ``build_hybrid_lm``, weights from the seed. ``training.remat`` is
    applied as ``Optimizer.set_remat("block")`` applies it (the train kind
    has no line for it): the decoder's ``remat_blocks``.
    ``training.router_gradient`` ``"none"`` is ``MoE(train_router=False)``:
    this chip's eighth of a router's gradient is not applied (the plain
    reference reads the same key)."""
    from bigdl_tpu.interop.hf import afmoe_lm_kwargs
    from bigdl_tpu.models.hybrid import build_hybrid_lm
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(seed)
    np.random.seed(seed % (2 ** 32))
    training = cfg.get("training", {})
    router = training.get("router_gradient", "full")
    if router not in ("full", "none"):
        raise ValueError(f"training.router_gradient {router!r}: 'full' or "
                         f"'none'")
    model = build_hybrid_lm(**afmoe_lm_kwargs(
        hf_config(cfg), held_experts=reference.held_experts(cfg),
        train_router=router == "full"))
    remat = training.get("remat")
    if remat not in (None, "block"):
        raise ValueError(f"training.remat {remat!r}: 'block' or nothing")
    decoder_of(model).remat_blocks = remat == "block"
    if cfg.get("placement"):
        place_experts(model, cfg, seed)
    return model


def measured_loads(model, rows):
    """Picks an expert of EVERY router, (rows, expert layers, router
    width), on each row of token ids as a batch of one: the system's own
    forward at the training precision, ``MoE._route`` listened to."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.nn.module import functional_apply
    from bigdl_tpu.ops.precision import DtypePolicy
    from bigdl_tpu.parallel.expert import MoE
    route, cast = MoE._route, DtypePolicy.bf16().cast_params_for_compute

    @jax.jit
    def loads(params, buffers, ids):
        seen = []

        def listening(moe, x):
            picked, weight = route(moe, x)
            seen.append(jnp.bincount(picked.reshape(-1),
                                     length=moe.n_experts))
            return picked, weight

        MoE._route = listening
        try:        # not training: no checkpoint around a block's trace
            functional_apply(model, cast(params), buffers, ids)
        finally:
            MoE._route = route
        return jnp.stack(seen)

    params, buffers = model.parameter_tree(), model.buffer_tree()
    return np.stack([np.asarray(loads(params, buffers, jnp.asarray(r[None])))
                     for r in rows])


def deal(load, chips):
    """Expert ids in the order a load-balancing placement hands them to
    ``chips`` chips: ranked by load, each ``chips`` in a row dealt one a
    chip, every other row backwards; chip c's experts are entries
    ``c * n : (c + 1) * n`` of the result."""
    ranked = np.argsort(-np.asarray(load), kind="stable").reshape(-1, chips)
    ranked[1::2] = ranked[1::2, ::-1].copy()
    return ranked.T.reshape(-1)


def place_experts(model, cfg, seed):
    """Relabels every router's outputs so that ids 0 .. n-1, the experts
    held here, are chip 0's of ``deal`` over the picks measured on the
    stream the deployment trains on (``placement``: the epoch's rows of
    Zipf tokens over the held rows, from the seed as ``train_samples``
    draws them). The experts' weights are seeded alike, so a relabelling
    chooses which of them this chip holds and changes no layer. Without it
    the held share of the picks follows where the seed's few hot tokens
    land (15% of all tokens are one id) and the step's work with it. Writes
    the held experts' measured picks a step to standard error as one
    ``benchmark detail`` line."""
    from benchmark import traffic
    from bigdl_tpu.parallel.expert import MoE
    place = cfg["placement"]
    if place["by"] != "measured_load":
        raise ValueError(f"placement.by {place['by']!r}: 'measured_load'")
    rows = traffic.zipf_tokens(seed, place["records"], place["seq_len"],
                               cfg["vocab_size"], place["token_zipf"])
    held = cfg["num_experts"]
    chips = cfg["published"]["num_experts"] // held
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


def reference_params(model):
    """The model's parameters and the routers' selection bias (device
    arrays, no copy) under the names the reference reads. Layer ``i`` of
    the model is the decoder's blocks ``2i`` (attention) and ``2i + 1``
    (feed-forward)."""
    from bigdl_tpu import nn
    tree, buffers = model.parameter_tree(), model.buffer_tree()
    at = {type(m): name for name, m in model._modules.items()}
    dec_name = at[nn.HybridDecoder]
    dec, dec_buf = tree[dec_name], buffers[dec_name]
    pattern = decoder_of(model).pattern
    out = {"model.embed_tokens.weight": tree[at[nn.LookupTable]]["weight"],
           "model.norm.weight": dec["final_norm"]["weight"],
           "lm_head.weight": tree[at[nn.LMHead]]["weight"]}
    for i in range(len(pattern) // 2):
        pre = f"model.layers.{i}."
        att, ffn = dec[f"layer{2 * i}"], dec[f"layer{2 * i + 1}"]
        for ours, theirs in ((att["norm"], "input_layernorm"),
                             (att["norm_post"], "post_attention_layernorm"),
                             (ffn["norm"], "pre_mlp_layernorm"),
                             (ffn["norm_post"], "post_mlp_layernorm")):
            out[pre + theirs + ".weight"] = ours["weight"]
        mix = att["mixer"]
        for ours, theirs in (("in_proj_weight", "qkv_proj.weight"),
                             ("out_proj_weight", "o_proj.weight"),
                             ("gate_proj_weight", "gate_proj.weight")):
            out[pre + "self_attn." + theirs] = mix[ours]
        for name in ("q_norm", "k_norm"):
            out[pre + f"self_attn.{name}.weight"] = mix[name]["weight"]
        mix, pre = ffn["mixer"], pre + "mlp."
        if pattern[2 * i + 1] == "-":
            for name in ("gate", "up", "down"):
                out[pre + name + "_proj.weight"] = mix[name]["weight"]
            continue
        out[pre + "router.gate.weight"] = mix["gate_weight"]
        out[pre + "expert_bias"] = \
            dec_buf[f"layer{2 * i + 1}"]["mixer"]["select_bias"]
        for ours, theirs in (("wg", "gate_proj"), ("w1", "up_proj"),
                             ("w2", "down_proj")):
            out[pre + "experts." + theirs] = mix[ours]
            out[pre + "shared_experts." + theirs + ".weight"] = \
                mix["shared_" + ours]
    return out


def reference_loss_and_grad_norm(model, cfg, data, labels):
    """Also writes what the routers did on this batch to standard error,
    as one ``benchmark detail`` line of its own (the train kind's
    ``checks`` take two numbers from here and no more)."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda p, x, y: reference.loss_and_grad_norm(p, x, y, cfg))
    with jax.default_matmul_precision("highest"):
        loss, gn, picks = fn(reference_params(model),
                             jnp.asarray(data, jnp.int32) - 1,
                             jnp.asarray(labels, jnp.int32) - 1)
    print("benchmark detail routing: "
          + json.dumps(reference.pick_stats(picks, cfg)), file=sys.stderr)
    return float(loss), float(gn)


# ------------------------------------------------------- negative controls

#: what ``planted`` can break in the SYSTEM, for ``benchmark.controls``
FAULTS = ("no_band", "rope_on_full", "no_output_gate", "no_route_scale",
          "no_embed_scale")


@contextlib.contextmanager
def planted(model, fault):
    """ONE fault of the mathematics in the system's own modules while the
    block runs (the plain reference reads none of this): the band dropped
    on the window layers, rotation applied on the full layers, the
    attention's output gate dropped, ``route_scale`` 1 for the published
    2.826, the embedding's multiplier dropped."""
    from bigdl_tpu import nn
    from bigdl_tpu.parallel.expert import MoE
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}: one of {FAULTS}")
    attention = [m for m in model.modules()
                 if isinstance(m, nn.MultiHeadAttention)]
    with contextlib.ExitStack() as undo:
        def swap(obj, name, value):
            undo.callback(setattr, obj, name, getattr(obj, name))
            setattr(obj, name, value)

        for m in attention:
            if fault == "no_band" and m.window:
                swap(m, "window", None)
            elif fault == "rope_on_full" and not m.window:
                swap(m, "rope", True)
            elif fault == "no_output_gate":
                swap(m, "gated", False)
        for m in model.modules():
            if fault == "no_route_scale" and isinstance(m, MoE):
                swap(m, "route_scale", 1.0)
            elif fault == "no_embed_scale" and isinstance(m, nn.MulConstant):
                swap(m, "scalar", 1.0)
        yield


def train_flops_per_record(cfg, cell):
    return flops_afmoe.train_flops_per_record(cfg, cell["seq_len"])
