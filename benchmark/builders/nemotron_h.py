"""Nemotron-H configuration (``model_type`` ``nemotron_h``) -> the program's
pattern-built hybrid LM, its training data, and its parameters under the
plain reference's names.

The configuration file holds this chip's share: ``n_routed_experts`` is how
many routed experts are HELD (ids 0 .. n-1), ``vocab_size`` the held rows,
``hybrid_override_pattern`` the stage's blocks; the published values stand
beside them under ``published``. The router keeps the published width.
"""

from __future__ import annotations

import contextlib
import json
import sys

import numpy as np

from benchmark import flops_hybrid, traffic
from benchmark.reference import nemotron_h as reference


def hf_config(cfg):
    """The file as the public ``config.json`` reads: the routed-expert
    count is the router's width again (the held ones go in beside it)."""
    return dict(cfg, n_routed_experts=cfg["published"]["n_routed_experts"])


def decoder_of(model):
    from bigdl_tpu.nn import HybridDecoder
    return next(m for m in model.modules() if isinstance(m, HybridDecoder))


def build(cfg, seed):
    """The config through ``interop.hf.nemotron_h_lm_kwargs`` ->
    ``build_hybrid_lm``, weights from the seed. ``training.remat`` is
    applied as ``Optimizer.set_remat("block")`` applies it (the train kind
    has no line for it): the decoder's ``remat_blocks``."""
    from bigdl_tpu.interop.hf import nemotron_h_lm_kwargs
    from bigdl_tpu.models.hybrid import build_hybrid_lm
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(seed)
    np.random.seed(seed % (2 ** 32))
    model = build_hybrid_lm(**nemotron_h_lm_kwargs(
        hf_config(cfg), held_experts=reference.held_experts(cfg)))
    remat = cfg.get("training", {}).get("remat")
    if remat not in (None, "block"):
        raise ValueError(f"training.remat {remat!r}: 'block' or nothing")
    decoder_of(model).remat_blocks = remat == "block"
    return model


def criterion(cfg):
    from bigdl_tpu import nn
    return nn.FusedLMHeadCriterion()    # nn.LMHead => the fused-CE path


def train_samples(cfg, cell, seed):
    """Zipf token rows over the held slice of the vocabulary, target = next
    token, as the program's Samples (1-based float ids)."""
    from bigdl_tpu.dataset.base import Sample
    rows = traffic.zipf_tokens(seed, cell["records_per_epoch"],
                               cell["seq_len"], cfg["vocab_size"],
                               cell["token_zipf"])
    return [Sample(r[:-1].astype(np.float32), r[1:].astype(np.float32))
            for r in rows]


def reference_batch(cfg, cell, seed):
    ref = cell["reference"]
    rows = traffic.zipf_tokens(seed + 1, ref["batch"], ref["seq_len"],
                               cfg["vocab_size"], cell["token_zipf"])
    return rows[:, :-1].astype(np.float32), rows[:, 1:].astype(np.float32)


def reference_params(model):
    """The model's parameters and the routers' selection bias (device
    arrays, no copy) under the names the reference reads."""
    tree = model.parameter_tree()
    buffers = model.buffer_tree()
    dec = tree["1"]
    out = {"backbone.embeddings.weight": tree["0"]["weight"],
           "backbone.norm_f.weight": dec["final_norm"]["weight"],
           "lm_head.weight": tree["2"]["weight"]}
    for i, kind in enumerate(decoder_of(model).pattern):
        lay, pre = dec[f"layer{i}"], f"backbone.layers.{i}."
        mix = lay["mixer"]
        out[pre + "norm.weight"] = lay["norm"]["weight"]
        pre += "mixer."
        if kind == "M":
            for ours, theirs in (("in_proj_weight", "in_proj.weight"),
                                 ("conv_weight", "conv1d.weight"),
                                 ("conv_bias", "conv1d.bias"),
                                 ("dt_bias", "dt_bias"), ("A_log", "A_log"),
                                 ("D", "D"), ("norm_weight", "norm.weight"),
                                 ("out_proj_weight", "out_proj.weight")):
                out[pre + theirs] = mix[ours]
        elif kind == "E":
            out[pre + "gate.weight"] = mix["gate_weight"]
            out[pre + "gate.e_score_correction_bias"] = \
                buffers["1"][f"layer{i}"]["mixer"]["select_bias"]
            out[pre + "experts.up_proj"] = mix["w1"]
            out[pre + "experts.down_proj"] = mix["w2"]
            out[pre + "shared_experts.up_proj.weight"] = mix["shared_w1"]
            out[pre + "shared_experts.down_proj.weight"] = mix["shared_w2"]
        else:
            out[pre + "qkv_proj.weight"] = mix["in_proj_weight"]
            out[pre + "o_proj.weight"] = mix["out_proj_weight"]
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
FAULTS = ("no_d_skip", "no_route_scale", "softmax_router",
          "bf16_scan_state")


def _softmax_route(moe, x):
    """``MoE._route`` with GShard's router in the sigmoid one's place."""
    import jax
    import jax.numpy as jnp
    probs = jax.nn.softmax(
        jnp.dot(x, moe.gate_weight.astype(x.dtype),
                preferred_element_type=jnp.float32), axis=-1)
    w, picked = jax.lax.top_k(probs, moe.k)
    return picked, w * moe.route_scale


def _bf16_chunk_states(whole, local):
    """``ops.ssd_scan._chunk_states`` with the carried state rounded to
    bf16 after every chunk: the precision below the one the scan keeps."""
    import jax
    import jax.numpy as jnp

    def carry_on(state, inp):
        keep, add = inp
        new = keep[..., None, None] * state.astype(jnp.float32) + add
        return new.astype(jnp.bfloat16), state

    _, before = jax.lax.scan(
        carry_on, jnp.zeros(local.shape[:1] + local.shape[2:], jnp.bfloat16),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(local, 1, 0)))
    return jnp.moveaxis(before, 0, 1)


@contextlib.contextmanager
def planted(model, fault):
    """ONE fault of the mathematics in the system's own modules while the
    block runs (the plain reference reads none of this): the D skip's
    weights zeroed, ``route_scale`` 1 for the published 2.5, a softmax
    router, the scan's carried state in bf16."""
    from bigdl_tpu.nn.mamba import Mamba2
    from bigdl_tpu.ops import ssd_scan
    from bigdl_tpu.parallel.expert import MoE
    mixers = model.modules()
    with contextlib.ExitStack() as undo:
        def swap(obj, name, value):
            undo.callback(setattr, obj, name, getattr(obj, name))
            setattr(obj, name, value)

        if fault == "no_d_skip":
            for m in mixers:
                if isinstance(m, Mamba2):
                    swap(m, "D", 0.0 * m.D)
        elif fault == "no_route_scale":
            for m in mixers:
                if isinstance(m, MoE):
                    swap(m, "route_scale", 1.0)
        elif fault == "softmax_router":
            swap(MoE, "_route", _softmax_route)
        elif fault == "bf16_scan_state":
            swap(ssd_scan, "_chunk_states", _bf16_chunk_states)
        else:
            raise ValueError(f"unknown fault {fault!r}: one of {FAULTS}")
        yield


def train_flops_per_record(cfg, cell):
    return flops_hybrid.train_flops_per_record(cfg, cell["seq_len"])


def flash_shape(cfg, cell):
    """(batch, heads, seq, head_dim) of the flash-attention call of each
    ``*`` block in this cell's train step (GQA is expanded before the
    kernel)."""
    return (cell["batch_size"], cfg["num_attention_heads"], cell["seq_len"],
            cfg["head_dim"])
