"""The ``joyai_llm_flash`` family (JoyAI-LLM-Flash, DeepSeek-V3's layers:
latent attention whose query/key head and value head differ, a
sigmoid-routed SwiGLU expert layer that holds a share of its experts, a
multi-token-prediction module over the shared embedding and head) against
its plain reference (``benchmark/reference/joyai_llm_flash.py``), in
float32 at small sizes on the CPU; the share arithmetic; the
configuration's sizes; its cell's rehearsal and negative controls."""

import json
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, timeline
from benchmark.builders import joyai_llm_flash as builder
from benchmark.reference import joyai_llm_flash as reference
from bigdl_tpu import nn
from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.parallel.expert import MoE

logging.getLogger("bigdl_tpu.optim").setLevel(logging.WARNING)

CELL = "joyai-llm-flash-train-s8192"


def _rng(seed=0):
    return np.random.default_rng(seed)


def _normal(rng, *shape, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32) * scale)


def _close(a, b, tol=2e-4):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol * np.abs(b).max())


def _apply(module, params, x, training=True):
    return functional_apply(module, params, module.buffer_tree(), x,
                            training=training)[0]


def _flat(tree):
    return {jax.tree_util.keystr(k): np.asarray(v) for k, v in
            jax.tree_util.tree_leaves_with_path(tree)}


@pytest.fixture(scope="module")
def cut():
    cell, cfg = harness.load_cell(CELL, rehearse=True)
    return cell, cfg, builder.build(cfg, 3)


@pytest.fixture(scope="module")
def batch(cut):
    cell, cfg, _ = cut
    return tuple(jnp.asarray(t) for t in builder.reference_batch(cfg, cell, 3))


def _training_loss(model, cfg, data, labels, policy=None):
    """The program's own training loss as a function of its parameters."""
    from bigdl_tpu.ops.precision import DtypePolicy
    from bigdl_tpu.optim.optimizer import make_training_loss_fn
    return make_training_loss_fn(
        model, builder.criterion(cfg), policy or DtypePolicy(), (), False,
        model.buffer_tree(), jax.random.PRNGKey(0), data, labels)


# ------------------------------------------------------- latent attention

LATENT = dict(num_heads=4, q_lora_rank=24, kv_lora_rank=16,
              qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
              rope_theta=32e6, norm_eps=1e-6)
LATENT_CFG = dict(num_attention_heads=4, kv_lora_rank=16,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, rope_theta=32e6,
                  rms_norm_eps=1e-6)


def _latent(seed=5):
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(seed)
    np.random.seed(seed)
    m = nn.LatentAttention(40, **LATENT)
    params = m.parameter_tree()
    rng = _rng(seed)     # norms off 1, so that they are seen
    for name in ("q_a_norm", "kv_a_norm"):
        w = params[name]["weight"]
        params[name]["weight"] = w + _normal(rng, *w.shape, scale=0.3)
    return m, params


def _latent_reference_params(p):
    return {"a.q_a_proj.weight": p["q_a_weight"],
            "a.q_a_layernorm.weight": p["q_a_norm"]["weight"],
            "a.q_b_proj.weight": p["q_b_weight"],
            "a.kv_a_proj_with_mqa.weight": p["kv_a_weight"],
            "a.kv_a_layernorm.weight": p["kv_a_norm"]["weight"],
            "a.kv_b_proj.weight": p["kv_b_weight"],
            "a.o_proj.weight": p["out_proj_weight"]}


def test_latent_attention_forward_and_gradients_match_the_reference():
    """Two down-projections with a norm on each latent, two
    up-projections, ONE rotary key for all heads, a value head (12) that
    is neither the query/key head (24) nor its content part (16): output,
    input gradient and every parameter's gradient."""
    m, params = _latent()
    assert sorted(params) == ["kv_a_norm", "kv_a_weight", "kv_b_weight",
                              "out_proj_weight", "q_a_norm", "q_a_weight",
                              "q_b_weight"]
    assert params["kv_a_weight"].shape == (16 + 8, 40)
    assert params["q_b_weight"].shape == (4 * 24, 24)
    assert params["kv_b_weight"].shape == (4 * (16 + 12), 16)
    assert params["out_proj_weight"].shape == (40, 4 * 12)
    rng = _rng(2)
    x, probe = _normal(rng, 2, 37, 40), _normal(rng, 2, 37, 40)

    def plain(p, x):
        return reference.attention(_latent_reference_params(p), "a.", x,
                                   LATENT_CFG)

    _close(_apply(m, params, x), plain(params, x), tol=1e-5)
    gp, gx = jax.grad(lambda p, x: jnp.sum(_apply(m, p, x) * probe),
                      argnums=(0, 1))(params, x)
    wp, wx = jax.grad(lambda p, x: jnp.sum(plain(p, x) * probe),
                      argnums=(0, 1))(params, x)
    _close(gx, wx)
    got, want = _flat(gp), _flat(wp)
    assert set(got) == set(want) and len(got) == 7
    for k in got:
        assert want[k].any(), k
        _close(got[k], want[k])


def test_the_rotary_key_is_one_head_and_the_scale_is_the_whole_heads():
    """Moving the rotary key's columns of ``kv_a`` moves EVERY head's
    scores; the softmax is scaled by 1/sqrt(16 + 8), not by the value
    head's or the content part's size."""
    m, params = _latent()
    assert m.softmax_scale == 24 ** -0.5
    x = _normal(_rng(4), 1, 9, 40)
    base = _apply(m, params, x)
    moved = dict(params, kv_a_weight=params["kv_a_weight"].at[16:].mul(-1.0))
    o_w = params["out_proj_weight"]
    for head in range(4):       # the output through ONE head's o columns
        only = jnp.zeros_like(o_w).at[:, 12 * head:12 * (head + 1)].set(
            o_w[:, 12 * head:12 * (head + 1)])
        a = _apply(m, dict(params, out_proj_weight=only), x)
        b = _apply(m, dict(moved, out_proj_weight=only), x)
        assert np.abs(np.asarray(a - b)).max() > 1e-4, head
    m.softmax_scale = 12 ** -0.5
    try:
        assert np.abs(np.asarray(_apply(m, params, x) - base)).max() > 1e-4
    finally:
        m.softmax_scale = 24 ** -0.5


def test_on_a_tpu_the_latent_layer_takes_the_flash_kernels(monkeypatch):
    """On a TPU backend, at a sequence the kernels take: tracing a latent
    block's gradient under block remat counts ``form=mla`` and
    ``path=expanded`` once, the jaxpr holds the three kernels under their
    ``flash_mla_*`` names with a 24-wide q and k over a 16-wide v (nothing
    padded), and the kernels' output equals the XLA core's."""
    from bigdl_tpu.ops import flash_attention as fa
    from bigdl_tpu.telemetry import get_registry, instruments
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(7)
    np.random.seed(7)
    dec = nn.HybridDecoder("L", 32, latent_attention=dict(
        LATENT, qk_nope_head_dim=48, qk_rope_head_dim=16, v_head_dim=32))
    dec.remat_blocks = True
    x = _normal(_rng(7), 1, 1024, 32, scale=0.5)
    plain = _apply(dec, dec.parameter_tree(), x)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    real = fa._flash_lse
    monkeypatch.setattr(fa, "_flash_lse", lambda *a: real(
        *a[:7], True, a[8]))            # the kernels in the interpreter
    ins = instruments(get_registry())
    mla0 = ins.flash_attention_total.labels(form="mla").value
    path0 = ins.latent_attention_total.labels(path="expanded").value

    def f(p):
        return jnp.sum(_apply(dec, p, x))

    text = str(jax.make_jaxpr(jax.grad(f))(dec.parameter_tree()))
    assert ins.flash_attention_total.labels(form="mla").value == mla0 + 1
    assert ins.latent_attention_total.labels(path="expanded").value \
        == path0 + 1
    for name in ("flash_mla_fwd", "flash_mla_bwd_dkv"):
        assert text.count(f"name={name}") == 1, name    # kept, not re-run
    assert "flash_mla_bwd_dq" not in text   # dQ leaves the dK/dV call
    assert "f32[4,1024,64]" in text and "f32[4,1024,32]" in text
    _close(_apply(dec, dec.parameter_tree(), x), plain, tol=1e-4)


# ------------------------------------------------------------- the experts

MOE_CFG = dict(hidden_size=32, moe_intermediate_size=24,
               num_experts_per_tok=3, routed_scaling_factor=2.5)


def _moe(held, n_experts=32, seed=5):
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(seed)
    np.random.seed(seed)
    return MoE(32, 24, n_experts=n_experts, k=3, activation="swiglu",
               dispatch="held", held=held, bias=False, shared_hidden=24,
               route_scale=2.5)


def _moe_reference_params(p):
    return {"e.gate.weight": p["gate_weight"],
            "e.gate.e_score_correction_bias":
                jnp.zeros((p["gate_weight"].shape[1],)),
            "e.experts.gate_proj": p["wg"], "e.experts.up_proj": p["w1"],
            "e.experts.down_proj": p["w2"],
            "e.shared_experts.gate_proj.weight": p["shared_wg"],
            "e.shared_experts.up_proj.weight": p["shared_w1"],
            "e.shared_experts.down_proj.weight": p["shared_w2"]}


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST. 32 routed experts over 16 chips, 2 a chip: the
    routed parts the 16 shares compute, plus the shared expert counted
    once, equal what the uncut reference gives for the whole 32-expert
    layer (sigmoid top-3, renormalised, x 2.5)."""
    whole = _moe(tuple(range(32)))
    params = whole.parameter_tree()
    u = _normal(_rng(3), 3, 17, 32)
    want, picked = reference.moe(_moe_reference_params(params), "e.", u,
                                 dict(MOE_CFG, n_routed_experts=32))
    shared_once = reference.silu_gated(u, params["shared_wg"],
                                       params["shared_w1"],
                                       params["shared_w2"])
    total = shared_once
    for chip in range(16):
        held = (2 * chip, 2 * chip + 1)
        share = _moe(held)
        p = dict(params, **{k: params[k][jnp.asarray(held)]
                            for k in ("w1", "wg", "w2")})
        total = total + (_apply(share, p, u) - shared_once)
    _close(total, want)
    assert len(np.unique(np.asarray(picked))) > 16


# ------------------------------------------------------------------ the model

def test_the_rehearsal_keeps_what_the_cell_is_about(cut):
    """One dense layer, two expert layers, the prediction module (a third
    expert layer), 2 held of 8 experts, a value head that differs from
    the query/key head, block remat on both stacks."""
    cell, cfg, model = cut
    assert builder.decoder_of(model).pattern == "L-LELE"
    assert model.mtp.stack.pattern == "LE"
    assert model.mtp.loss_weight == cfg["training"]["mtp_loss_weight"] == 0.3
    assert cfg["n_routed_experts"] == 2
    assert cfg["published"]["n_routed_experts"] == 8
    assert cfg["v_head_dim"] != cfg["qk_nope_head_dim"] \
        + cfg["qk_rope_head_dim"]
    stacks = [m for m in model.modules() if isinstance(m, nn.HybridDecoder)]
    assert len(stacks) == 2 and all(m.remat_blocks for m in stacks)
    layers = [m for m in model.modules() if isinstance(m, MoE)]
    assert len(layers) == 3 and not any(m.train_router for m in layers)
    assert all(m.route_scale == 2.5 and m.k == 2 for m in layers)
    with pytest.raises(ValueError):
        builder.build(dict(cfg, training=dict(cfg["training"],
                                              router_gradient="some")), 3)
    with pytest.raises(ValueError):
        builder.build(dict(cfg, training=dict(cfg["training"],
                                              remat="full")), 3)


def test_both_losses_and_every_gradient_leaf_match_the_reference(cut, batch):
    """In float32: the training loss is ``L_main + 0.3 L_mtp`` of the
    plain reference, each term is the reference's own, and every leaf of
    the gradient (the five projections and two latent norms of all four
    attention blocks, the experts, the module's two norms and projection,
    the shared embedding and head) is the reference's; the routers take
    none on either side."""
    cell, cfg, model = cut
    data, labels = batch
    ids, tgt = (t.astype(jnp.int32) - 1 for t in batch)
    params = model.parameter_tree()
    loss_fn = _training_loss(model, cfg, data, labels)
    grads, (_, loss) = jax.grad(loss_fn, has_aux=True)(params)
    named = builder.reference_params(model)
    first, second, _ = reference.losses(named, ids, tgt, cfg)
    (want, _), want_grads = jax.value_and_grad(reference.loss, has_aux=True)(
        named, ids, tgt, cfg)
    assert abs(float(want) - float(first + 0.3 * second)) < 1e-6
    assert float(second) > 1.0 and abs(float(loss) - float(want)) < 2e-6 \
        * float(want)
    # each term alone: the module's weight 0 and 1
    model.mtp.loss_weight = 0.0
    try:
        only = float(_training_loss(model, cfg, data, labels)(params)[1][1])
    finally:
        model.mtp.loss_weight = 0.3
    assert abs(only - float(first)) < 2e-6 * float(first)
    got = builder.named(grads, model.buffer_tree(),
                        builder.decoder_of(model).pattern)
    leaves = len(jax.tree_util.tree_leaves(params))
    # and the three routers' selection biases and pick tables (buffers)
    assert len(got) == leaves + 6
    for name, g in got.items():
        if name.endswith(("e_score_correction_bias", "pick_table")):
            continue
        w = np.asarray(want_grads[name])
        if name.endswith("mlp.gate.weight"):
            assert not w.any() and not np.asarray(g).any(), name
            continue
        assert w.any(), name
        _close(g, w, tol=1e-4)


def test_the_embedding_and_the_head_take_gradient_from_both_losses(cut,
                                                                   batch):
    """The module has no embedding and no head of its own. With the main
    term's gradient taken out (the module's weight 1, minus the weight-0
    gradient) the lookup table and the head still get a gradient, the
    prediction module's; the two add up to the whole."""
    cell, cfg, model = cut
    data, labels = batch
    params = model.parameter_tree()
    assert "mtp" in params and sorted(params["mtp"]) == [
        "norm_embed", "norm_hidden", "proj", "stack"]
    assert not [k for k in _flat(params["mtp"]) if "LookupTable" in k]

    def grads(weight):
        model.mtp.loss_weight = weight
        try:
            g = jax.grad(_training_loss(model, cfg, data, labels),
                         has_aux=True)(params)[0]
        finally:
            model.mtp.loss_weight = 0.3
        return np.asarray(g["0"]["weight"]), np.asarray(g["2"]["weight"]), \
            _flat(g["mtp"])

    e0, h0, m0 = grads(0.0)
    e1, h1, _ = grads(1.0)
    e, h, m = grads(0.3)
    assert e0.any() and h0.any()
    assert not any(v.any() for v in m0.values())    # main loss alone
    assert sum(v.any() for v in m.values()) >= len(m) - 1   # but the router
    assert np.abs(e1 - e0).max() > 1e-5 and np.abs(h1 - h0).max() > 1e-5
    _close(e, e0 + 0.3 * (e1 - e0), tol=1e-5)
    _close(h, h0 + 0.3 * (h1 - h0), tol=1e-5)


def test_eval_is_the_main_streams_log_probabilities(cut, batch):
    """In eval the module does not run and the output is the chain's: the
    reference's main stream through the head, log-softmax."""
    from bigdl_tpu.telemetry import get_registry, instruments
    cell, cfg, model = cut
    count = instruments(get_registry()).mtp_modules_total.labels()
    before = count.value
    out = _apply(model, model.parameter_tree(), batch[0], training=False)
    assert count.value == before
    named = builder.reference_params(model)
    main, _, _ = reference.streams(named, batch[0].astype(jnp.int32) - 1,
                                   cfg)
    _close(out, jax.nn.log_softmax(main @ named["lm_head.weight"].T, -1),
           tol=1e-5)
    _apply(model, model.parameter_tree(), batch[0], training=True)
    assert count.value == before + 1


def test_the_first_label_is_the_main_losss_alone(cut, batch):
    """The module's position i is scored against label i + 1: label 0
    moves the main loss alone, on both sides by the same amount."""
    cell, cfg, model = cut
    data, labels = batch
    other = labels.at[:, 0].set(jnp.where(labels[:, 0] == 1.0, 2.0, 1.0))
    params = model.parameter_tree()
    named = builder.reference_params(model)
    ids = data.astype(jnp.int32) - 1
    a = reference.losses(named, ids, labels.astype(jnp.int32) - 1, cfg)
    b = reference.losses(named, ids, other.astype(jnp.int32) - 1, cfg)
    assert float(a[1]) == float(b[1]) and float(a[0]) != float(b[0])
    got = [float(_training_loss(model, cfg, data, t)(params)[1][1])
           for t in (labels, other)]
    assert abs((got[1] - got[0]) - float(b[0] - a[0])) < 1e-5


@pytest.mark.parametrize("ignore", [None, 7])
def test_the_criterion_adds_the_second_loss_through_the_same_head(ignore):
    """``FusedLMHeadCriterion`` on a training Table that carries ``mtp``
    and ``mtp_weight``, by hand: the main stream against the targets, plus
    the weight times the module's stream against the targets one to the
    left, its last position left out (so that what stands there, and the
    gradient into it, is nothing); ``ignore_index`` leaves the same rows
    out of both. Without the two keys the loss is the one it was."""
    from bigdl_tpu.utils.table import Table
    rng = _rng(11)
    h, z = _normal(rng, 2, 9, 16), _normal(rng, 2, 9, 16)
    w = _normal(rng, 40, 16)
    tgt = jnp.asarray(rng.integers(1, 41, (2, 9)).astype(np.float32))
    if ignore:
        tgt = tgt.at[0, 3].set(float(ignore)).at[1, 5].set(float(ignore))
    crit = nn.FusedLMHeadCriterion(ignore_index=ignore)

    def nll(x, t):
        lp = jax.nn.log_softmax(x @ w.T, -1)
        picked = jnp.take_along_axis(
            lp, (t.astype(jnp.int32) - 1)[..., None], -1)[..., 0]
        keep = jnp.ones(t.shape, bool) if ignore is None else t != ignore
        return -jnp.sum(jnp.where(keep, picked, 0.0)) / jnp.sum(keep)

    def loss(h, z, w):
        return crit.forward(Table(h, w, mtp=z, mtp_weight=0.3), tgt)

    want = nll(h, tgt) + 0.3 * nll(z[:, :-1], tgt[:, 1:])
    assert abs(float(loss(h, z, w)) - float(want)) < 1e-5
    assert abs(float(crit.forward(Table(h, w), tgt))
               - float(nll(h, tgt))) < 1e-5
    moved = z.at[:, -1].set(100.0)
    assert float(loss(h, moved, w)) == float(loss(h, z, w))
    gh, gz, gw = jax.grad(loss, argnums=(0, 1, 2))(h, z, w)
    assert not np.asarray(gz[:, -1]).any() and np.asarray(gz[:, :-1]).any()
    wh, wz = jax.grad(
        lambda h, z: nll(h, tgt) + 0.3 * nll(z[:, :-1], tgt[:, 1:]),
        argnums=(0, 1))(h, z)
    _close(gh, wh, tol=1e-5)
    _close(gz, wz, tol=1e-5)
    assert np.asarray(gw).any()


@pytest.mark.parametrize("layer,kinds", [(0, "L-"), (1, "LE")])
def test_each_kind_of_layer_matches_the_reference(cut, layer, kinds):
    cell, cfg, model = cut
    dec = builder.decoder_of(model)
    assert dec.pattern[2 * layer:2 * layer + 2] == kinds
    from bigdl_tpu.parallel.expert import token_ids
    x = _normal(_rng(layer), 2, 40, cfg["hidden_size"])
    ids = _rng(7).integers(1, cfg["vocab_size"] + 1, (2, 40))
    got = x
    with token_ids(jnp.asarray(ids, jnp.float32)):
        for i in (2 * layer, 2 * layer + 1):
            got = dec._modules[f"layer{i}"].forward(got)
    want, _ = reference.layer(builder.reference_params(model), layer, x, cfg,
                              kinds == "L-", jnp.asarray(ids - 1))
    _close(got, want, tol=1e-5)


def test_the_reference_in_bf16_is_the_tolerances_second_reading(cut, batch):
    cell, cfg, model = cut
    ids, tgt = (t.astype(jnp.int32) - 1 for t in batch)
    p = builder.reference_params(model)
    true, gn, _ = reference.loss_and_grad_norm(p, ids, tgt, cfg)
    low, gn_low, _ = reference.loss_and_grad_norm(p, ids, tgt, cfg,
                                                  jnp.bfloat16)
    assert 0 < abs(float(low) - float(true)) < 0.02 * float(true)
    assert 0 < abs(float(gn_low) - float(gn)) < 0.1 * float(gn)


def test_block_remat_is_honoured_and_changes_no_gradient(cut, batch):
    """Both stacks under ``jax.checkpoint`` a block, against neither: the
    same gradient of the whole training loss."""
    cell, cfg, model = cut
    data, labels = batch
    stacks = [m for m in model.modules() if isinstance(m, nn.HybridDecoder)]

    def grads():
        return jax.jit(jax.grad(_training_loss(model, cfg, data, labels),
                                has_aux=True))(model.parameter_tree())[0]

    kept = grads()
    for m in stacks:
        m.remat_blocks = False
    try:
        for a, b in zip(jax.tree_util.tree_leaves(grads()),
                        jax.tree_util.tree_leaves(kept)):
            _close(a, b, tol=1e-5)
    finally:
        for m in stacks:
            m.remat_blocks = True


# ------------------------------------------------------------ the mapping

def test_hf_config_maps_to_the_builders_arguments():
    from bigdl_tpu.interop.hf import joyai_llm_flash_lm_kwargs
    _, cfg = harness.load_cell(CELL)
    kw = joyai_llm_flash_lm_kwargs(builder.hf_config(cfg),
                                   held_experts=range(16))
    assert kw["pattern"] == "L-" + "LE" * 4 and kw["embed_dim"] == 2048
    assert kw["vocab_size"] == 16160 and kw["norm_eps"] == 1e-6
    assert kw["latent_attention"] == dict(
        num_heads=32, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_theta=32e6, norm_eps=1e-6)
    assert kw["mlp"] == dict(hidden_size=7168)
    assert kw["moe"] == dict(
        hidden_size=768, n_experts=256, k=8, activation="swiglu",
        dispatch="held", held=tuple(range(16)), bias=False,
        shared_hidden=768, route_scale=2.5, train_router=True, pick_rows=0)
    assert joyai_llm_flash_lm_kwargs(
        builder.hf_config(cfg), picks_by_token=True)["moe"]["pick_rows"] \
        == 16160
    assert kw["mtp"] == dict(loss_weight=0.3)
    assert set(kw) == {"vocab_size", "embed_dim", "pattern", "norm_eps",
                       "latent_attention", "mlp", "moe", "mtp"}
    fixed = joyai_llm_flash_lm_kwargs(builder.hf_config(cfg),
                                      train_router=False, mtp_loss_weight=0.1)
    assert not fixed["moe"]["train_router"] and fixed["moe"]["held"] is None
    assert fixed["mtp"] == dict(loss_weight=0.1)
    whole = joyai_llm_flash_lm_kwargs(dict(builder.hf_config(cfg),
                                           **cfg["published"]))
    assert whole["pattern"] == "L-" + "LE" * 39
    assert whole["vocab_size"] == 129280
    assert "mtp" not in joyai_llm_flash_lm_kwargs(
        dict(builder.hf_config(cfg), num_nextn_predict_layers=0))


@pytest.mark.parametrize("bad", [
    dict(n_group=8), dict(topk_group=4), dict(scoring_func="softmax"),
    dict(norm_topk_prob=False), dict(rope_scaling={"type": "yarn"}),
    dict(q_lora_rank=None), dict(tie_word_embeddings=True),
    dict(n_shared_experts=2), dict(hidden_act="gelu"),
    dict(moe_layer_freq=2), dict(attention_bias=True),
    dict(num_nextn_predict_layers=2),
    dict(first_k_dense_replace=5),      # a module over no expert layer
], ids=lambda bad: next(iter(bad)))
def test_what_is_not_mapped_is_refused(bad):
    from bigdl_tpu.interop.hf import joyai_llm_flash_lm_kwargs
    _, cfg = harness.load_cell(CELL)
    with pytest.raises(ValueError):
        joyai_llm_flash_lm_kwargs(dict(builder.hf_config(cfg), **bad))


def _sizes(cfg, vocab):
    e, n = cfg["hidden_size"], cfg["num_attention_heads"]
    dc, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    attn = e * rq + rq * n * (dc + dr) + e * (rkv + dr) \
        + rkv * n * (dc + dv) + n * dv * e + rq + rkv       # + latent norms
    return dict(attn=attn, dense=3 * e * cfg["intermediate_size"],
                router=e * cfg["published"]["n_routed_experts"],
                expert=3 * e * cfg["moe_intermediate_size"], norms=2 * e,
                vocab=2 * vocab * e + e, module=2 * e * e + 3 * e)


def test_the_published_model_has_the_published_size(cut):
    """The builder's shapes at the PUBLISHED depth, experts and vocabulary
    give 48.9B parameters and a prediction module of 1.25B beside them;
    the cut gives 680.4M; the same count at the rehearsal's sizes is what
    the builder builds."""
    _, cfg = harness.load_cell(CELL)
    pub = cfg["published"]
    z = _sizes(cfg, pub["vocab_size"])
    assert round(z["attn"] / 1e6, 2) == 26.35
    assert round(z["expert"] / 1e6, 2) == 4.72

    def moe_layer(held):
        return z["attn"] + z["norms"] + z["router"] + (1 + held) * z["expert"]

    dense_layer = z["attn"] + z["norms"] + z["dense"]
    assert round(dense_layer / 1e6, 2) == 70.39
    assert round(moe_layer(16) / 1e6, 2) == 107.09
    whole = dense_layer + (pub["num_hidden_layers"] - 1) * moe_layer(256) \
        + z["vocab"]
    assert round(whole / 1e9, 1) == 48.9
    assert round((moe_layer(256) + z["module"]) / 1e9, 2) == 1.25
    module = moe_layer(16) + z["module"]
    assert round(module / 1e6, 2) == 115.49
    here = dense_layer + 4 * moe_layer(16) + module \
        + 2 * cfg["vocab_size"] * 2048 + 2048
    assert here == 680_439_808 and round(here / 1e6, 1) == 680.4
    _, small, model = cut
    s = _sizes(small, small["vocab_size"])
    built = sum(int(np.prod(leaf.shape)) for leaf in
                jax.tree_util.tree_leaves(model.parameter_tree()))
    layer = s["attn"] + s["norms"]
    assert built == 4 * layer + s["dense"] + s["module"] \
        + 3 * (s["router"] + 3 * s["expert"]) + s["vocab"]


def test_the_configuration_keeps_every_published_key():
    """Every number of the catalog's row under its own key, but the three
    ``reduced`` ones, which stand under ``published``."""
    _, cfg = harness.load_cell(CELL)
    want = dict(
        attention_bias=False, ep_size=1, first_k_dense_replace=1,
        head_dim=64, hidden_act="silu", hidden_size=2048,
        intermediate_size=7168, kv_lora_rank=512,
        max_position_embeddings=131072, model_type="joyai_llm_flash",
        moe_intermediate_size=768, moe_layer_freq=1, n_group=1,
        n_routed_experts=256, n_shared_experts=1, norm_topk_prob=True,
        num_attention_heads=32, num_experts_per_tok=8, num_hidden_layers=40,
        num_key_value_heads=32, num_nextn_predict_layers=1, q_lora_rank=1536,
        qk_head_dim=192, qk_nope_head_dim=128, qk_rope_head_dim=64,
        rms_norm_eps=1e-6, rope_interleave=True, rope_scaling=None,
        rope_theta=32000000, routed_scaling_factor=2.5,
        scoring_func="sigmoid", tie_word_embeddings=False, topk_group=1,
        topk_method="noaux_tc", v_head_dim=128, vocab_size=129280)
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    for key, value in want.items():
        assert dict(cfg, **cfg["published"])[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 16, 16160)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["training"] == dict(
        remat="block", router_gradient="none", router_picks="token_id",
        mtp_loss_weight=0.3, why=cfg["training"]["why"])


# ---------------------------------------------------------------- placement

def test_placement_deals_every_router_the_modules_too(cut, capfd):
    """``placement`` ``measured_load``: every router's picks, the
    prediction module's among them (the TRAINING forward: the module runs
    in no other), are measured on the rows the cell trains on and the
    router's outputs relabelled so that ids 0 .. n-1 are the first chip's
    of the deal. Against the same seed built without it only the router
    matrices differ, by that permutation of their columns."""
    cell, cfg, model = cut
    route = MoE._route
    plain_cfg = {k: v for k, v in cfg.items() if k != "placement"}
    plain = builder.build(plain_cfg, 3)
    capfd.readouterr()
    placed = builder.build(cfg, 3)
    line = [ln for ln in capfd.readouterr().err.splitlines()
            if ln.startswith("benchmark detail placement: ")]
    assert len(line) == 1
    detail = json.loads(line[0].split(": ", 1)[1])
    rows = np.stack([s.feature for s in builder.train_samples(cfg, cell, 3)])
    for m in plain.modules():
        if isinstance(m, nn.HybridDecoder):
            m.remat_blocks = False
    by_row = builder.measured_loads(plain, rows)
    assert MoE._route is route
    assert by_row.shape == (cell["records_per_epoch"], 3, 8)
    assert (by_row.sum(2) == cell["seq_len"]
            * cfg["num_experts_per_tok"]).all()
    loads = by_row.mean(0)
    a, b = _flat(plain.parameter_tree()), _flat(placed.parameter_tree())
    routers = [k for k in a if "gate_weight" in k]
    assert len(routers) == 3 and "mtp" in routers[-1]
    for k in a:
        if k not in routers:
            np.testing.assert_array_equal(a[k], b[k])
    for k, load, held in zip(routers, loads, detail["held_picks"]):
        order = builder.deal(load, 4)
        np.testing.assert_array_equal(a[k][:, order], b[k])
        assert held == load[order[:2]].round().astype(int).tolist()
        assert held[0] == round(load.max())
    real_cell, real = harness.load_cell(CELL)
    assert real["placement"]["by"] == "measured_load"
    for ours, theirs in (("records", "records_per_epoch"),
                         ("seq_len", "seq_len"), ("token_zipf", "token_zipf")):
        assert real["placement"][ours] == real_cell[theirs]
        assert cfg["placement"][ours] == cell[theirs]
    with pytest.raises(ValueError):
        builder.build(dict(cfg, placement=dict(cfg["placement"],
                                               by="guess")), 3)


# ------------------------------------------------------- picks by token id

def _tabled(rows=50, seed=5):
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(seed)
    np.random.seed(seed)
    m = MoE(32, 24, n_experts=32, k=3, activation="swiglu", dispatch="held",
            held=(0, 1, 2, 3), bias=False, shared_hidden=24, route_scale=2.5,
            pick_rows=rows)
    m.pick_table = np.stack([_rng(t).permutation(32)[:3]
                             for t in range(rows)]).astype(np.float32)
    return m


@pytest.mark.parametrize("scale", [1.0, -3.0, 0.1])
def test_a_tabled_layer_picks_by_the_tokens_id_whatever_the_stream(scale):
    """``MoE(pick_rows=)``: the picks are the table's rows of the ids the
    model names (1-based), the weights the LIVE scores of those picks,
    renormalised and scaled; the plain reference's ``route`` agrees."""
    from bigdl_tpu.parallel.expert import token_ids
    m = _tabled()
    ids = _rng(2).integers(1, 51, (3, 17))
    x = scale * _normal(_rng(3), 3 * 17, 32)
    with token_ids(jnp.asarray(ids, jnp.float32)):
        picked, w = m._route(x)
    table = np.asarray(m.pick_table).astype(int)
    np.testing.assert_array_equal(picked, table[ids.reshape(-1) - 1])
    scores = np.asarray(jax.nn.sigmoid(x @ m.gate_weight))
    live = np.take_along_axis(scores, np.asarray(picked), 1)
    _close(w, 2.5 * live / live.sum(1, keepdims=True), tol=1e-6)
    want, want_w = reference.route(
        {"e.gate.weight": m.gate_weight, "e.gate.pick_table": m.pick_table},
        "e.", x, dict(MOE_CFG, training={"router_picks": "token_id"}),
        jnp.asarray(ids.reshape(-1) - 1))
    np.testing.assert_array_equal(picked, want)
    _close(w, want_w, tol=1e-6)


def test_a_tabled_layer_asks_for_the_ids_and_for_the_held_dispatch():
    m = _tabled()
    with pytest.raises(ValueError, match="token_ids"):
        m._route(_normal(_rng(3), 5, 32))
    with pytest.raises(ValueError, match="pick_rows"):
        MoE(32, 24, n_experts=8, k=2, pick_rows=50)
    assert "pick_table" not in _moe((0, 1)).buffer_tree()


def test_a_chain_without_a_prediction_module_names_its_ids_too():
    """``build_hybrid_lm(moe={"pick_rows": ..})`` with no ``mtp``: the
    chain itself runs under ``token_ids`` (eval forward, a zero table:
    every token picks experts 0 and 0)."""
    from bigdl_tpu.models.hybrid import build_hybrid_lm
    lm = build_hybrid_lm(
        50, 32, "LE", latent_attention=LATENT, moe=dict(
            hidden_size=24, n_experts=8, k=2, activation="swiglu",
            dispatch="held", held=(0, 1), bias=False, pick_rows=50))
    lm.evaluate_mode()
    out = lm.forward(jnp.asarray(_rng(1).integers(1, 51, (2, 16)),
                                 jnp.float32))
    assert out.shape == (2, 16, 50) and np.isfinite(np.asarray(out)).all()


def test_the_tables_are_the_seeded_routers_own_picks_and_then_stand(cut):
    """``training.router_picks`` ``"token_id"``: after the build row ``t``
    of every router's table, the module's too, is its top k over token
    ``t``'s embedding row (after the placement's relabelling); and with
    every parameter moved the system's measured picks are what they were,
    the rows' ids deciding them alone."""
    cell, cfg, model = cut
    assert cfg["training"]["router_picks"] == "token_id"
    rows = np.asarray(next(m for m in model.modules()
                           if isinstance(m, nn.LookupTable)).weight)
    routers = [m for m in model.modules() if isinstance(m, MoE)]
    assert len(routers) == 3
    for m in routers:
        assert m.pick_table.shape == (cfg["vocab_size"], m.k)
        _, want = jax.lax.top_k(jnp.asarray(rows) @ m.gate_weight, m.k)
        np.testing.assert_array_equal(
            np.sort(np.asarray(m.pick_table), 1), np.sort(want, 1))
    data = np.stack([s.feature for s in builder.train_samples(cfg, cell, 3)])
    params = model.parameter_tree()
    moved = jax.tree_util.tree_map(
        lambda a: a + 0.5 * _normal(_rng(9), *a.shape), params)
    stacks = [m for m in model.modules() if isinstance(m, nn.HybridDecoder)]
    for m in stacks:        # the builder measures before it sets remat
        m.remat_blocks = False
    try:
        before = builder.measured_loads(model, data)
        model.load_parameter_tree(moved)
        after = builder.measured_loads(model, data)
    finally:
        model.load_parameter_tree(params)
        for m in stacks:
            m.remat_blocks = True
    np.testing.assert_array_equal(before, after)
    with pytest.raises(ValueError, match="router_picks"):
        builder.build(dict(cfg, training=dict(cfg["training"],
                                              router_picks="hash")), 3)


# --------------------------------------------------- the cell and its gate

@pytest.fixture(scope="module")
def controlled():
    """``benchmark.controls`` at the rehearsal size in float32 (there the
    sound system is the reference to 1e-6)."""
    from benchmark import controls
    cell, cfg = harness.load_cell(CELL, rehearse=True)
    cell = dict(cell, precision="fp32",
                reference=dict(cell["reference"], loss_rtol=2e-6,
                               grad_norm_rtol=2e-5, latent_out_rtol=1e-5,
                               latent_grad_rtol=1e-4))
    return dict(controls.run(cell, cfg, 3))


def test_the_sound_system_passes_the_reference_check(controlled):
    assert set(controlled) == {"sound", *builder.FAULTS}
    assert controlled["sound"]["ok"]


@pytest.mark.parametrize("fault", builder.FAULTS)
def test_a_fault_planted_in_the_system_goes_through_the_reference_check(
        controlled, fault):
    """Each fault is in the SYSTEM's modules and goes through the
    comparison that decides ``correct``; the reference is the sound one.
    In float32 at the rehearsal size every one of them fails it: this is
    the test that holds a fault the chip's bf16 limits cannot see (the
    cell file's ``reference.why``)."""
    got, sound = controlled[fault], controlled["sound"]
    assert got["reference_loss"] == sound["reference_loss"]
    assert got["system_loss"] != sound["system_loss"]
    assert not got["ok"]


SEEN_BY_THE_BLOCK = ("no_rope_term", "no_latent_norm", "value_head_scale",
          "reference_bf16")


@pytest.fixture(scope="module")
def block_checked():
    """The controls with the two numbers' limits wide open, so that the
    latent block's distances alone decide."""
    from benchmark import controls
    cell, cfg = harness.load_cell(CELL, rehearse=True)
    cell = dict(cell, precision="fp32",
                reference=dict(cell["reference"], loss_rtol=1.0,
                               grad_norm_rtol=1.0, latent_out_rtol=1e-5,
                               latent_grad_rtol=1e-4))
    return dict(controls.run(cell, cfg, 3, faults=SEEN_BY_THE_BLOCK + (
        "no_route_scale",)))


@pytest.mark.parametrize("control", ("sound", "no_route_scale") + SEEN_BY_THE_BLOCK)
def test_the_latent_block_alone_decides_what_it_can_see(block_checked,
                                                        control):
    """What the chip's gate stands on: the rotary term, the latent's norm,
    the softmax's scale and a reference wholly in bf16 all come out not ok
    by the block's distances alone, as NaN for the two numbers; the sound
    system and a fault outside the mixer pass them."""
    got = block_checked[control]
    assert got["ok"] == (control not in SEEN_BY_THE_BLOCK)
    assert np.isnan(got["system_loss"]) == (control in SEEN_BY_THE_BLOCK)


def test_the_latent_block_reads_output_and_every_gradient_leaf(cut):
    cell, cfg, model = cut
    builder.reference_batch(cfg, dict(cell, precision="fp32"), 3)
    sound = builder.latent_block(model)
    assert sound["out"] < 1e-5 and sound["grad"] < 1e-4
    low = builder.latent_block(model, jnp.bfloat16)
    assert low["out"] > 1e-3 and low["grad"] > 1e-3
    with builder.planted(model, "value_head_scale"):
        off = builder.latent_block(model)
    assert off["out"] > 10 * low["out"] and off["grad"] > 10 * low["grad"]
    assert off["leaf"] in builder.latent_named(
        next(m for m in model.modules()
             if isinstance(m, nn.LatentAttention)).parameter_tree()) \
        or off["leaf"] == "x"


def test_a_planted_fault_is_taken_out_again(cut, batch):
    from bigdl_tpu.nn import attention
    cell, cfg, model = cut
    params = model.parameter_tree()
    rotate = attention.rope_rotate
    before = float(_training_loss(model, cfg, *batch)(params)[1][1])
    from benchmark.kinds import train as kind
    numbers = kind.system_loss_and_grad_norm
    for fault in builder.FAULTS:
        with builder.planted(model, fault):
            inside = float(_training_loss(model, cfg, *batch)(params)[1][1])
            # the bf16 reference stands where the system stood: the system
            # itself is sound under it
            assert (inside != before) == (fault in builder.SYSTEM_FAULTS)
            assert kind.system_loss_and_grad_norm is not numbers
    assert kind.system_loss_and_grad_norm is numbers
    assert attention.rope_rotate is rotate
    mixers = [m for m in model.modules()
              if isinstance(m, nn.LatentAttention)]
    assert len(mixers) == 4
    assert all(m.softmax_scale == 24 ** -0.5
               and "update_output" not in m.kv_a_norm.__dict__
               for m in mixers)
    assert all(m.route_scale == 2.5 for m in model.modules()
               if isinstance(m, MoE))
    assert model.mtp.loss_weight == 0.3
    assert float(_training_loss(model, cfg, *batch)(params)[1][1]) == before
    with pytest.raises(ValueError):
        with builder.planted(model, "no_such_fault"):
            pass


def test_the_cells_rehearsal_runs_to_its_line(capfd):
    """``python -m benchmark.run --workload <cell> --rehearse``: the whole
    control flow at the rehearsal size; exit code 3, one JSON line that
    names the CPU and is no measurement."""
    from benchmark import run
    rc = run.main(["--workload", CELL, "--seed", "2999999999", "--seconds",
                   "2", "--trace", "0", "--rehearse"])
    out, err = capfd.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 3 and line["rehearsal"] and not line["correct"]
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    detail = json.loads(next(
        ln for ln in err.splitlines()
        if ln.startswith("benchmark detail: ")).split(": ", 1)[1])
    checks = detail["checks"]
    assert checks["reference"]["ok"] and checks["loss_ok"]
    assert checks["one_step_compile"] and checks["compiles_in_window"] == 0


# ------------------------------------------------------ scopes and counters

def test_scopes_of_the_latent_layers_and_of_the_module(cut, batch):
    """The compiled training step holds instructions under ``mla_proj``
    (forward and backward), under ``mtp`` (the module's blocks AND its
    pass through the head: ``lm_head_ce`` appears under it and outside it)
    and under the expert scopes; the two new counters rose once a site."""
    from bigdl_tpu.telemetry import get_registry, instruments
    cell, cfg, model = cut
    ins = instruments(get_registry())
    latent0 = ins.latent_attention_total.labels(path="expanded").value
    mtp0 = ins.mtp_modules_total.labels().value
    loss_fn = _training_loss(model, cfg, *batch)
    hlo = jax.jit(jax.grad(loss_fn, has_aux=True)).lower(
        model.parameter_tree()).compile().as_text()
    assert ins.latent_attention_total.labels(path="expanded").value \
        >= latent0 + 4
    assert ins.mtp_modules_total.labels().value >= mtp0 + 1
    found = {s: timeline.scope_instructions(hlo, s)
             for s in ("mla_proj", "mtp", "lm_head_ce", "moe_experts")}
    assert all(found.values()), {k: len(v) for k, v in found.items()}
    assert found["mla_proj"] & found["mtp"]         # the module's own mixer
    assert found["mla_proj"] - found["mtp"]         # and the main stack's
    assert found["lm_head_ce"] & found["mtp"]       # the second pass
    assert found["lm_head_ce"] - found["mtp"]       # and the first
