"""Qwen2-family configuration -> the program's model, its training data,
and its parameters under the plain reference's names.

A builder is found by the configuration's ``family``; a new family is a new
file here with the same functions.
"""

from __future__ import annotations

import numpy as np

from benchmark import flops, traffic
from benchmark.reference import qwen2 as reference


def build(cfg, seed):
    """The public config through ``interop.hf.qwen2_lm_kwargs`` ->
    ``build_lm``, as ``load_qwen2`` does, weights from the seed."""
    from bigdl_tpu.interop.hf import qwen2_lm_kwargs
    from bigdl_tpu.models.transformer import build_lm
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(seed)
    np.random.seed(seed % (2 ** 32))
    return build_lm(**qwen2_lm_kwargs(cfg))


def criterion(cfg):
    from bigdl_tpu import nn
    return nn.FusedLMHeadCriterion()    # tied head => the fused-CE path


def train_samples(cfg, cell, seed):
    """Zipf token rows, target = next token, as the program's Samples
    (1-based float ids, its convention)."""
    from bigdl_tpu.dataset.base import Sample
    rows = traffic.zipf_tokens(seed, cell["records_per_epoch"],
                               cell["seq_len"], cfg["vocab_size"],
                               cell["token_zipf"])
    return [Sample(r[:-1].astype(np.float32), r[1:].astype(np.float32))
            for r in rows]


def reference_batch(cfg, cell, seed):
    """(data, labels) as the program takes them, for the reference check:
    ``reference.batch`` rows of ``reference.seq_len`` tokens."""
    ref = cell["reference"]
    rows = traffic.zipf_tokens(seed + 1, ref["batch"], ref["seq_len"],
                               cfg["vocab_size"], cell["token_zipf"])
    return rows[:, :-1].astype(np.float32), rows[:, 1:].astype(np.float32)


def reference_params(model):
    """The model's parameters (device arrays, no copy to the host) under the
    torch-convention names the reference reads."""
    tree = model.parameter_tree()
    if not tree:                        # a cast twin keeps them as buffers
        tree = model.buffer_tree()
    out = {"embedding.weight": tree["0"]["weight"],
           "encoder.norm.weight": tree["1"]["final_norm"]["weight"]}
    layers = sorted((k for k in tree["1"] if k.startswith("layer")),
                    key=lambda k: int(k[5:]))
    for i, key in enumerate(layers):
        lay, pre = tree["1"][key], f"encoder.layers.{i}."
        out[pre + "norm1.weight"] = lay["norm1"]["weight"]
        out[pre + "norm2.weight"] = lay["norm2"]["weight"]
        out[pre + "self_attn.in_proj_weight"] = \
            lay["self_attn"]["in_proj_weight"]
        out[pre + "self_attn.in_proj_bias"] = lay["self_attn"]["in_proj_bias"]
        out[pre + "self_attn.out_proj.weight"] = \
            lay["self_attn"]["out_proj_weight"]
        for name in ("linear1", "linear2", "linear_gate"):
            out[pre + name + ".weight"] = lay[name]["weight"]
    return out


def reference_loss_and_grad_norm(model, cfg, data, labels):
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda p, x, y: reference.loss_and_grad_norm(p, x, y, cfg))
    with jax.default_matmul_precision("highest"):
        loss, gn = fn(reference_params(model),
                      jnp.asarray(data, jnp.int32) - 1,
                      jnp.asarray(labels, jnp.int32) - 1)
    return float(loss), float(gn)


def reference_logits_fn(cfg):
    """fn(params, 1-based ids of ONE sequence, rope_theta=the config's) ->
    (S, V) float32 reference logits. ``rope_theta`` is an argument of the
    one jitted program, so the true reference and every control of
    ``reference_controls`` share it: nothing compiles twice."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda p, ids0, theta: reference.logits(
        p, ids0, dict(cfg, rope_theta=theta)))

    def logits(params, ids1, rope_theta=None):
        theta = cfg["rope_theta"] if rope_theta is None else rope_theta
        with jax.default_matmul_precision("highest"):
            return fn(params, jnp.asarray(ids1, jnp.int32)[None] - 1,
                      jnp.float32(theta))[0]

    return logits


def reference_controls(cfg, params):
    """Broken references, name -> (params, rope_theta): each is the plain
    reference with one piece of the mathematics taken out. The serve check
    must REJECT the served tokens under each (``kinds/serve.py``); if it
    accepts them, it cannot see that piece and proves nothing about it."""
    import jax.numpy as jnp

    def zeroed(suffix):
        return {k: jnp.zeros_like(v) if k.endswith(suffix) else v
                for k, v in params.items()}

    return {"no_attention": (zeroed("self_attn.out_proj.weight"), None),
            "no_qkv_bias": (zeroed("self_attn.in_proj_bias"), None),
            "rope_theta_1e4": (params, 1e4)}


def serve_model(model, cfg, seed):
    """The bf16 twin, as ``apps/transformer.py serve --bf16`` wires it,
    over the configuration's ``serve_weights``.

    ``build_lm`` draws the tied embedding from N(0, 1), the projections so
    that attention scores spread by ~1, and every q/k/v bias as 0. With
    those, the stream at a position is its own token's embedding plus
    little: the largest logit at every position is the INPUT token by
    55-100 logits, with attention or without it, so greedy tokens say
    nothing about the cache, the rope or the bias (reference on a CPU,
    PR 22). Three numbers change that, found on the reference (PERF.md
    section 6): ``embedding_scale`` shrinks the tied matrix until attention
    and the MLP carry the stream; ``query_scale`` sharpens attention so
    that what is attended, and with it the next token, changes from
    position to position (at 1 greedy decoding repeats one token for
    ever); ``qk_bias_std`` draws the q and k biases from the seed (a v bias
    would add one constant vector to every position and fix the argmax).
    Values do not change a dense model's speed (``eos_id=None``: lengths
    are the traffic's)."""
    import jax.numpy as jnp
    from bigdl_tpu import nn
    w = cfg.get("serve_weights")
    if w:
        e = cfg["hidden_size"]
        qk = e + cfg["num_key_value_heads"] * (e // cfg["num_attention_heads"])
        tree = model.parameter_tree()
        tree["0"]["weight"] = tree["0"]["weight"] * w["embedding_scale"]
        rng = traffic._rng(seed, 7)
        for key, lay in tree["1"].items():
            if not key.startswith("layer"):
                continue
            att = lay["self_attn"]
            att["in_proj_weight"] = att["in_proj_weight"].at[:e].multiply(
                w["query_scale"])
            bias = np.zeros(att["in_proj_bias"].shape, np.float32)
            bias[:qk] = rng.normal(0.0, w["qk_bias_std"], qk)
            att["in_proj_bias"] = jnp.asarray(bias,
                                              att["in_proj_bias"].dtype)
        model.load_parameter_tree(tree)
    return nn.cast_model(model)


def train_flops_per_record(cfg, cell):
    return flops.lm_train_flops_per_record(cfg, cell["seq_len"])


def flash_shape(cfg, cell):
    """(batch, heads, seq, head_dim) of the flash-attention calls in this
    cell's train step (GQA is expanded before the kernel)."""
    h = cfg["num_attention_heads"]
    return (cell["batch_size"], h, cell["seq_len"], cfg["hidden_size"] // h)
