"""The ``afmoe`` family (Arcee Trinity: sliding-window and full attention
layers mixed, q/k norm, a gated attention output, four norms a layer, a
sigmoid-routed SwiGLU expert layer that holds a share of its experts)
against its plain reference (``benchmark/reference/afmoe.py``), at small
sizes on the CPU; the share arithmetic of the gated expert layer; the
configuration's sizes; its cell's rehearsal and negative controls."""

import json
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import flops_afmoe, harness, timeline
from benchmark.builders import afmoe as builder
from benchmark.reference import afmoe as reference
from bigdl_tpu import nn
from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.parallel import expert
from bigdl_tpu.parallel.expert import MoE, expert_param_specs

logging.getLogger("bigdl_tpu.optim").setLevel(logging.WARNING)

CELL = "trinity-mini-train-s8192"
CFG = dict(hidden_size=32, moe_intermediate_size=24, num_experts_per_tok=3,
           route_scale=2.826)


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


# ------------------------------------------------------- the gated experts

def _moe(held, n_experts=8, seed=5, **kw):
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(seed)
    np.random.seed(seed)
    return MoE(32, 24, n_experts=n_experts, k=3, activation="swiglu",
               dispatch="held", held=held, bias=False, shared_hidden=24,
               route_scale=2.826, **kw)


def _moe_reference_params(p):
    return {"e.router.gate.weight": p["gate_weight"],
            "e.expert_bias": jnp.zeros((p["gate_weight"].shape[1],)),
            "e.experts.gate_proj": p["wg"], "e.experts.up_proj": p["w1"],
            "e.experts.down_proj": p["w2"],
            "e.shared_experts.gate_proj.weight": p["shared_wg"],
            "e.shared_experts.up_proj.weight": p["shared_w1"],
            "e.shared_experts.down_proj.weight": p["shared_w2"]}


def _grouped_form(monkeypatch, form, rows):
    """The held layer's grouped product in ``form`` whatever the backend,
    its row block (the XLA loops') or row tile (the kernels') ``rows``."""
    from bigdl_tpu.ops import grouped_matmul
    monkeypatch.setattr(expert, "takes_kernel",
                        lambda *a: form == "kernel")
    monkeypatch.setattr(expert, "_BLOCK_ROWS", rows)
    monkeypatch.setattr(grouped_matmul, "ROW_TILE", rows)
    monkeypatch.setattr(grouped_matmul, "SUB_ROWS", 8)


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("block_rows", [8, 512])
@pytest.mark.parametrize("held", [(0, 1), (2, 5, 7), tuple(range(8))])
def test_gated_experts_forward_and_gradients_match_the_reference(
        held, block_rows, form, monkeypatch):
    """Sigmoid top-3 of 8, renormalised, x 2.826, SwiGLU experts of three
    matrices and a shared one; rows in blocks of 8 (several blocks an
    expert, masked tails) and in one block, through the XLA loops and
    through the grouped kernels (in Pallas' interpreter: row tiles of 8,
    which their runs straddle, and one tile)."""
    _grouped_form(monkeypatch, form, block_rows)
    m = _moe(held)
    assert sorted(m._parameters) == ["gate_weight", "shared_w1", "shared_w2",
                                     "shared_wg", "w1", "w2", "wg"]
    cfg = dict(CFG, num_experts=len(held))
    rng = _rng(2)
    u, probe = _normal(rng, 2, 21, 32), _normal(rng, 2, 21, 32)
    params = m.parameter_tree()

    def plain_out(p, u):
        # the reference holds ids 0..n-1: renumber so that the held experts
        # come first in the router's columns
        order = list(held) + [e for e in range(8) if e not in held]
        q = _moe_reference_params(p)
        q["e.router.gate.weight"] = q["e.router.gate.weight"][:, order]
        return reference.moe(q, "e.", u, cfg)[0]

    _close(_apply(m, params, u), plain_out(params, u))
    gp, gu = jax.grad(lambda p, u: jnp.sum(_apply(m, p, u) * probe),
                      argnums=(0, 1))(params, u)
    wp, wu = jax.grad(lambda p, u: jnp.sum(plain_out(p, u) * probe),
                      argnums=(0, 1))(params, u)
    _close(gu, wu)
    for k in params:
        _close(gp[k], wp[k])


def test_the_shares_of_the_gated_layer_add_up_to_the_uncut_layer():
    """THE SHARE TEST. 16 routed experts over 8 chips, 2 a chip: the routed
    parts the 8 shares compute, plus the shared expert counted once, equal
    what the uncut reference gives for the whole 16-expert layer."""
    whole = _moe(tuple(range(16)), n_experts=16)
    params = whole.parameter_tree()
    u = _normal(_rng(3), 3, 17, 32)
    want, picked = reference.moe(_moe_reference_params(params), "e.", u,
                                 dict(CFG, num_experts=16))
    shared_once = reference.silu_gated(u, params["shared_wg"],
                                       params["shared_w1"],
                                       params["shared_w2"])
    total = shared_once
    for chip in range(8):
        held = (2 * chip, 2 * chip + 1)
        share = _moe(held, n_experts=16)
        p = dict(params, **{k: params[k][jnp.asarray(held)]
                            for k in ("w1", "wg", "w2")})
        total = total + (_apply(share, p, u) - shared_once)
    _close(total, want)
    assert len(np.unique(np.asarray(picked))) > 8


@pytest.mark.parametrize("held", [(0, 1), (2, 5, 7), tuple(range(8))])
def test_a_router_that_is_not_trained_takes_no_gradient(held):
    """``MoE(train_router=False)``: the same output; the combine weights
    are constants of the backward pass, so the router's matrix gets a zero
    gradient, the experts' and the shared expert's gradients are what they
    were, and the layer's input loses the part that came through the
    scores. The reference under ``training.router_gradient`` ``"none"``
    gives the same."""
    full, fixed = _moe(held), _moe(held, train_router=False)
    cfg = dict(CFG, num_experts=len(held),
               training={"router_gradient": "none"})
    rng = _rng(4)
    u, probe = _normal(rng, 2, 21, 32), _normal(rng, 2, 21, 32)
    params = full.parameter_tree()
    order = list(held) + [e for e in range(8) if e not in held]

    def plain_out(p, u):
        q = _moe_reference_params(p)
        q["e.router.gate.weight"] = q["e.router.gate.weight"][:, order]
        return reference.moe(q, "e.", u, cfg)[0]

    def grads(out):
        return jax.grad(lambda p, u: jnp.sum(out(p, u) * probe),
                        argnums=(0, 1))(params, u)

    np.testing.assert_array_equal(_apply(fixed, params, u),
                                  _apply(full, params, u))
    gp, gu = grads(lambda p, u: _apply(fixed, p, u))
    fp, fu = grads(lambda p, u: _apply(full, p, u))
    wp, wu = grads(plain_out)
    assert not np.asarray(gp["gate_weight"]).any()
    assert np.asarray(fp["gate_weight"]).any()
    assert np.abs(np.asarray(gu - fu)).max() > 1e-3
    _close(gu, wu)
    for k in params:
        _close(gp[k], wp[k])
        if k != "gate_weight":
            _close(gp[k], fp[k], tol=1e-6)


def test_swiglu_belongs_to_the_held_layer_and_is_sharded_with_it():
    with pytest.raises(ValueError):
        MoE(8, 8, n_experts=4, activation="swiglu")
    with pytest.raises(ValueError):
        MoE(8, 8, n_experts=4, activation="swiglu", dispatch="held",
            bias=True)
    with pytest.raises(ValueError):
        MoE(8, 8, n_experts=4, train_router=False)
    specs = expert_param_specs(_moe((0, 1)))
    assert specs["wg"] == specs["w1"] and specs["shared_wg"] == specs[
        "gate_weight"]


# ------------------------------------------------------------------ the model

@pytest.fixture(scope="module")
def cut():
    cell, cfg = harness.load_cell(CELL, rehearse=True)
    return cell, cfg, builder.build(cfg, 3)


def test_the_rehearsal_keeps_what_the_cell_is_about(cut):
    """A window SHORTER than the compared sequence, both kinds of
    attention layer, one dense layer, 2 held of 8 experts."""
    cell, cfg, model = cut
    assert cfg["sliding_window"] < cell["reference"]["seq_len"] \
        < cell["seq_len"]
    assert builder.decoder_of(model).pattern == "W-WE*E"
    assert cfg["num_experts"] == 2 and cfg["published"]["num_experts"] == 8
    real_cell, real = harness.load_cell(CELL)
    assert real["sliding_window"] < real_cell["reference"]["seq_len"]


def test_the_cells_routers_take_no_gradient_in_the_training_loss(cut):
    """``training.router_gradient`` ``"none"``: one chip's eighth of a
    router's gradient pulls the picks onto the held experts, so the cell
    does not apply it. Every expert layer is built with
    ``train_router=False`` and the program's own training loss gives each
    router matrix a zero gradient (AdamW then moves it by its weight decay
    alone) and its experts a gradient."""
    from bigdl_tpu.ops.precision import DtypePolicy
    from bigdl_tpu.optim.optimizer import make_training_loss_fn
    cell, cfg, model = cut
    assert cfg["training"]["router_gradient"] == "none"
    assert harness.load_cell(CELL)[1]["training"]["router_gradient"] == "none"
    layers = [m for m in model.modules() if isinstance(m, MoE)]
    assert len(layers) == 2 and not any(m.train_router for m in layers)
    data, labels = builder.reference_batch(cfg, cell, 3)
    loss_fn = make_training_loss_fn(
        model, builder.criterion(cfg), DtypePolicy(), (), False,
        model.buffer_tree(), jax.random.PRNGKey(0), jnp.asarray(data),
        jnp.asarray(labels))
    grads = jax.grad(loss_fn, has_aux=True)(model.parameter_tree())[0]
    flat = {jax.tree_util.keystr(k): np.asarray(v) for k, v in
            jax.tree_util.tree_leaves_with_path(grads)}
    routers = [k for k in flat if "gate_weight" in k]
    assert len(routers) == 2
    assert not any(flat[k].any() for k in routers)
    assert all(flat[k].any() for k in flat if k not in routers)
    with pytest.raises(ValueError):
        builder.build(dict(cfg, training={"router_gradient": "some"}), 3)
    trained = builder.build(dict(cfg, training={"remat": "block"}), 3)
    assert all(m.train_router for m in trained.modules()
               if isinstance(m, MoE))


@pytest.mark.parametrize("n_experts,chips", [(8, 4), (128, 8), (128, 16)])
def test_the_deal_gives_every_chip_an_even_share_of_the_picks(n_experts,
                                                             chips):
    """Loads as the cell's tokens make them: Zipf(1.1) ids, every id
    sending all its picks to its own 8 experts (15% of the tokens are one
    id). Ranked by load and dealt one a chip, every other round backwards:
    a permutation, chip c's experts at ``c * n : (c + 1) * n``, the first
    round in rank order; the first chip's share of the picks is within a
    tenth of even on every seed (it holds the hottest expert) where the
    first n ids' share strays by a fifth and more on some."""
    n = n_experts // chips
    p = np.arange(1, 25025, dtype=np.float64) ** -1.1
    dealt, first = [], []
    for seed in range(12):
        rng = _rng(seed)
        ids, count = np.unique(rng.choice(p.size, 8192, p=p / p.sum()),
                               return_counts=True)
        load = np.zeros(n_experts, np.int64)
        picks = np.argsort(rng.random((ids.size, n_experts)), axis=1)[:, :8]
        np.add.at(load, picks, count[:, None])
        order = builder.deal(load, chips)
        assert sorted(order) == list(range(n_experts))
        ranked = np.argsort(-load, kind="stable")
        assert list(order[::n]) == list(ranked[:chips])
        share = load[order].reshape(chips, n).sum(1) / load.sum()
        dealt.append(abs(share[0] * chips - 1))
        first.append(abs(load[:n].sum() / load.sum() * chips - 1))
    if (n_experts, chips) == (128, 8):      # the cell's deal
        assert max(dealt) < 0.1 and max(first) > 0.2


def test_placement_relabels_the_routers_and_changes_no_layer(cut, capfd):
    """``placement`` ``measured_load``: the builder measures every
    router's picks on one seeded sequence of the stream (the system's own
    forward) and relabels the router's outputs so that ids 0 .. n-1 are the
    first chip's of the deal. Against the same seed built without it: only
    the router matrices differ, by that permutation of their columns; the
    loads are measured on the very rows the cell trains on (the
    configuration's ``placement`` repeats the cell's traffic, at the real
    and at the rehearsal size) and count every pick; ``MoE._route`` is the
    class's own again."""
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
    samples = builder.train_samples(cfg, cell, 3)
    rows = np.stack([s.feature for s in samples])
    by_row = builder.measured_loads(plain, rows)
    assert MoE._route is route
    assert by_row.shape == (cell["records_per_epoch"], 2, 8)
    assert (by_row.sum(2) == cell["seq_len"]
            * cfg["num_experts_per_tok"]).all()
    loads = by_row.mean(0)
    a = {jax.tree_util.keystr(k): np.asarray(v) for k, v in
         jax.tree_util.tree_leaves_with_path(plain.parameter_tree())}
    b = {jax.tree_util.keystr(k): np.asarray(v) for k, v in
         jax.tree_util.tree_leaves_with_path(placed.parameter_tree())}
    routers = sorted(k for k in a if "gate_weight" in k)
    assert len(routers) == 2
    for k in a:
        if k not in routers:
            np.testing.assert_array_equal(a[k], b[k])
    for k, load, held in zip(routers, loads, detail["held_picks"]):
        order = builder.deal(load, 4)
        np.testing.assert_array_equal(a[k][:, order], b[k])
        assert held == load[order[:2]].round().astype(int).tolist()
        assert held[0] == round(load.max())
    _, real = harness.load_cell(CELL)
    real_cell = harness.load_cell(CELL)[0]
    assert real["placement"]["by"] == "measured_load"
    for ours, theirs in (("records", "records_per_epoch"),
                         ("seq_len", "seq_len"), ("token_zipf", "token_zipf")):
        assert real["placement"][ours] == real_cell[theirs]
        assert cfg["placement"][ours] == cell[theirs]
    with pytest.raises(ValueError):
        builder.build(dict(cfg, placement=dict(cfg["placement"],
                                               by="guess")), 3)


def test_the_cut_models_loss_and_gradient_norm_match_the_reference(cut):
    from benchmark.kinds import train as kind
    from bigdl_tpu.ops.precision import DtypePolicy
    cell, cfg, model = cut
    data, labels = builder.reference_batch(cfg, cell, 3)
    crit = builder.criterion(cfg)
    s_loss, s_gn = kind.system_loss_and_grad_norm(model, crit, DtypePolicy(),
                                                  data, labels)
    r_loss, r_gn = builder.reference_loss_and_grad_norm(model, cfg, data,
                                                        labels)
    assert abs(s_loss - r_loss) < 1e-5 * r_loss
    assert abs(s_gn - r_gn) < 1e-4 * r_gn
    b_loss, b_gn = kind.system_loss_and_grad_norm(
        model, crit, DtypePolicy.bf16(), data, labels)
    assert abs(b_loss - r_loss) < 0.01 * r_loss
    assert abs(b_gn - r_gn) < 0.05 * r_gn


@pytest.mark.parametrize("layer,kinds", [(0, "W-"), (1, "WE"), (2, "*E")])
def test_each_kind_of_layer_matches_the_reference(cut, layer, kinds):
    """One layer's output (its attention block, then its feed-forward
    block) on a random stream: window + rope with a dense MLP, window with
    experts, full attention without rope with experts."""
    cell, cfg, model = cut
    dec = builder.decoder_of(model)
    assert dec.pattern[2 * layer:2 * layer + 2] == kinds
    x = _normal(_rng(layer), 2, 40, cfg["hidden_size"])
    got = x
    for i in (2 * layer, 2 * layer + 1):
        got = dec._modules[f"layer{i}"].forward(got)
    want, _ = reference.layer(builder.reference_params(model), layer, x, cfg)
    _close(got, want, tol=1e-5)


def test_the_embedding_is_scaled_by_the_root_of_the_width(cut):
    _, cfg, model = cut
    ids = jnp.asarray([[1.0, 7.0, 256.0]])
    got = model[1].forward(model[0].forward(ids))
    want = reference.embed(builder.reference_params(model),
                           ids.astype(jnp.int32) - 1, cfg)
    _close(got, want, tol=1e-6)
    assert float(jnp.abs(got).max()) > 4 * float(
        jnp.abs(model[0].forward(ids)).max())        # sqrt(64) = 8


def test_the_reference_in_bf16_is_the_tolerances_second_reading(cut):
    cell, cfg, model = cut
    data, labels = builder.reference_batch(cfg, cell, 3)
    ids, tgt = (jnp.asarray(t, jnp.int32) - 1 for t in (data, labels))
    p = builder.reference_params(model)
    true, gn, _ = reference.loss_and_grad_norm(p, ids, tgt, cfg)
    low, gn_low, _ = reference.loss_and_grad_norm(p, ids, tgt, cfg,
                                                  jnp.bfloat16)
    assert 0 < abs(float(low) - float(true)) < 0.02 * float(true)
    assert 0 < abs(float(gn_low) - float(gn)) < 0.1 * float(gn)


def test_hf_config_maps_to_the_builders_arguments():
    from bigdl_tpu.interop.hf import afmoe_lm_kwargs, afmoe_pattern
    _, cfg = harness.load_cell(CELL)
    kw = afmoe_lm_kwargs(builder.hf_config(cfg), held_experts=range(16))
    assert kw["pattern"] == "W-WEWEWE*E" and kw["embed_dim"] == 2048
    assert kw["vocab_size"] == 25024 and kw["post_norm"]
    assert kw["norm_eps"] == 1e-5 and kw["embed_scale"] == 2048 ** 0.5
    full = dict(num_heads=32, num_kv_heads=4, head_dim=128, with_bias=False,
                qk_norm=True, qk_norm_eps=1e-5, gated=True)
    assert kw["attention"] == full
    assert kw["window_attention"] == dict(full, rope=True, rope_theta=1e4,
                                          window=2048)
    assert kw["mlp"] == dict(hidden_size=6144)
    assert kw["moe"] == dict(
        hidden_size=1024, n_experts=128, k=8, activation="swiglu",
        dispatch="held", held=tuple(range(16)), bias=False,
        shared_hidden=1024, route_scale=2.826, train_router=True)
    assert not afmoe_lm_kwargs(builder.hf_config(cfg),
                               train_router=False)["moe"]["train_router"]
    pub = cfg["published"]
    whole = afmoe_pattern(pub["layer_types"], pub["num_dense_layers"])
    assert whole == "W-W-" + "WE*E" + "WEWEWE*E" * 7
    for bad in (dict(n_group=2), dict(num_hidden_layers=4),
                dict(score_func="softmax"), dict(route_norm=False),
                dict(hidden_act="gelu"), dict(tie_word_embeddings=True),
                dict(rope_scaling={"type": "yarn"}),
                dict(num_shared_experts=2),
                dict(layer_types=["chunked_attention"] * 5)):
        with pytest.raises(ValueError):
            afmoe_lm_kwargs(dict(builder.hf_config(cfg), **bad))


def _sizes(e, h, kv, d, dense, f, router, vocab):
    attn = e * (2 * h + 2 * kv) * d + h * d * e + 2 * d     # + q/k norms
    norms = 4 * e
    return dict(attn=attn, dense=3 * e * dense, router=e * router,
                expert=3 * e * f, norms=norms, vocab=2 * vocab * e + e)


def test_the_published_model_has_the_published_size(cut):
    """The builder's shapes at the PUBLISHED depth, experts and vocabulary
    give 26.1B parameters, and the cut gives 705.5M; the same count at the
    rehearsal's sizes is what the builder builds."""
    _, cfg = harness.load_cell(CELL)
    pub = cfg["published"]
    z = _sizes(2048, 32, 4, 128, 6144, 1024, 128, pub["vocab_size"])
    assert round(z["attn"] / 1e6, 2) == 27.26
    moe_layer = lambda held: z["router"] + (1 + held) * z["expert"]
    n_dense = pub["num_dense_layers"]
    whole = pub["num_hidden_layers"] * (z["attn"] + z["norms"]) \
        + n_dense * z["dense"] \
        + (pub["num_hidden_layers"] - n_dense) * moe_layer(128) + z["vocab"]
    assert round(whole / 1e9, 1) == 26.1
    here = 5 * (z["attn"] + z["norms"]) + z["dense"] + 4 * moe_layer(16) \
        + 2 * 25024 * 2048 + 2048
    assert here == 705_473_792 and round(here / 1e6, 1) == 705.5
    _, small, model = cut
    s = _sizes(small["hidden_size"], small["num_attention_heads"],
               small["num_key_value_heads"], small["head_dim"],
               small["intermediate_size"], small["moe_intermediate_size"],
               small["published"]["num_experts"], small["vocab_size"])
    built = sum(int(np.prod(leaf.shape)) for leaf in
                jax.tree_util.tree_leaves(model.parameter_tree()))
    assert built == 3 * (s["attn"] + s["norms"]) + s["dense"] \
        + 2 * (s["router"] + 3 * s["expert"]) + s["vocab"]


def test_block_remat_is_honoured_and_changes_no_gradient(cut):
    cell, cfg, model = cut
    dec = builder.decoder_of(model)
    assert dec.remat_blocks            # the config's training.remat
    x = _normal(_rng(8), 1, 24, cfg["hidden_size"])

    def grads():
        def f(p):
            return jnp.sum(jnp.square(functional_apply(
                dec, p, dec.buffer_tree(), x, training=True)[0]))
        return jax.jit(jax.grad(f))(dec.parameter_tree())

    kept = grads()
    dec.remat_blocks = False
    try:
        for a, b in zip(jax.tree_util.tree_leaves(grads()),
                        jax.tree_util.tree_leaves(kept)):
            _close(a, b, tol=1e-5)
    finally:
        dec.remat_blocks = True


# --------------------------------------------------- the cell and its gate

@pytest.fixture(scope="module")
def controlled():
    """``benchmark.controls`` at the rehearsal size in float32 (there the
    sound system is the reference to 1e-7), 32 tokens past a window of
    16."""
    from benchmark import controls
    cell, cfg = harness.load_cell(CELL, rehearse=True)
    cell = dict(cell, precision="fp32",
                reference=dict(cell["reference"], loss_rtol=1e-6,
                               grad_norm_rtol=1e-5))
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
    the test that holds a fault the chip's bf16 limits cannot see
    (the cell file's ``reference.why``)."""
    got, sound = controlled[fault], controlled["sound"]
    assert got["reference_loss"] == sound["reference_loss"]
    assert got["system_loss"] != sound["system_loss"]
    assert not got["ok"]


def test_a_planted_fault_is_taken_out_again(cut):
    _, _, model = cut
    before = [(m.window, m.rope, m.gated) for m in model.modules()
              if isinstance(m, nn.MultiHeadAttention)]
    for fault in builder.FAULTS:
        with builder.planted(model, fault):
            pass
    assert before == [(m.window, m.rope, m.gated) for m in model.modules()
                      if isinstance(m, nn.MultiHeadAttention)]
    assert before == [(16, True, True)] * 2 + [(None, False, True)]
    assert all(m.route_scale == 2.826 for m in model.modules()
               if isinstance(m, MoE))
    assert model[1].scalar == 8.0
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

def test_scopes_of_the_gated_expert_layer(cut):
    from bigdl_tpu.telemetry import get_registry, instruments
    cell, cfg, model = cut
    dec = builder.decoder_of(model)
    ins = instruments(get_registry())
    held0 = ins.moe_dispatch_total.labels(path="held").value
    x = _normal(_rng(8), 1, 24, cfg["hidden_size"])

    def f(p):
        return jnp.sum(jnp.square(functional_apply(
            dec, p, dec.buffer_tree(), x, training=True)[0]))

    hlo = jax.jit(jax.grad(f)).lower(dec.parameter_tree()).compile().as_text()
    assert ins.moe_dispatch_total.labels(path="held").value > held0
    for scope in ("moe_route", "moe_experts", "moe_shared"):
        assert timeline.scope_instructions(hlo, scope), scope
    whiles = [n for n in timeline.scope_instructions(hlo, "moe_experts")
              if n.startswith("while")]
    assert len(whiles) >= 2     # the backward's loop is under the scope too


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_the_grouped_counter_reads_the_form_the_layer_took(form, monkeypatch):
    """``bigdl_moe_grouped_total`` counts once a trace, under the form that
    ``takes_kernel`` chose; both forms' backward stays under the forward's
    scope."""
    from bigdl_tpu.telemetry import get_registry, instruments
    other = {"xla": "kernel", "kernel": "xla"}[form]
    if form == "kernel":
        _grouped_form(monkeypatch, form, 8)
    fam = instruments(get_registry()).moe_grouped_total
    before = {f: fam.labels(form=f).value for f in (form, other)}
    m = _moe((2, 5, 7))
    u = _normal(_rng(4), 2, 21, 32)
    hlo = jax.jit(jax.grad(lambda p: jnp.sum(jnp.square(_apply(m, p, u))))) \
        .lower(m.parameter_tree()).compile().as_text()
    assert fam.labels(form=form).value == before[form] + 1
    assert fam.labels(form=other).value == before[other]
    whiles = [n for n in timeline.scope_instructions(hlo, "moe_experts")
              if n.startswith("while")]
    assert len(whiles) >= 2


def test_the_form_rule_reads_the_backend_the_widths_and_the_biases():
    """The kernels on a TPU for experts without biases whose widths are
    whole lane tiles (the Trinity and JoyAI cells' (2048, 1024) and (2048,
    768)); the XLA loops everywhere else, Nemotron's 1,856 among it."""
    bf = jnp.bfloat16

    def w(d, h, **more):
        return dict({"w1": jax.ShapeDtypeStruct((16, d, h), bf),
                     "w2": jax.ShapeDtypeStruct((16, h, d), bf)}, **more)

    x = jax.ShapeDtypeStruct((8192, 2048), bf)
    assert expert.takes_kernel("tpu", w(2048, 1024, wg=None), x)
    assert expert.takes_kernel("tpu", w(2048, 768), x)
    assert not expert.takes_kernel("cpu", w(2048, 1024), x)
    assert not expert.takes_kernel("tpu", w(2048, 1024, b1=None), x)
    assert not expert.takes_kernel("tpu", w(2688, 1856), x)
    assert not expert.takes_kernel("tpu", w(2000, 1024), x)
    assert not expert.takes_kernel(
        "tpu", w(2048, 1024), jax.ShapeDtypeStruct((8, 2048), jnp.float16))


def test_a_traced_step_counts_band_and_full_once_a_layer(monkeypatch):
    """On a TPU backend, at a sequence the kernels take and longer than
    the window: tracing the stack's training loss counts ``form=band``
    once for each sliding layer and ``form=full`` once for each full
    layer, and the jaxpr holds the kernels under both sets of names."""
    from bigdl_tpu.interop.hf import afmoe_lm_kwargs
    from bigdl_tpu.models.hybrid import build_hybrid_lm
    from bigdl_tpu.ops import flash_attention as fa
    from bigdl_tpu.telemetry import get_registry, instruments
    _, cfg = harness.load_cell(CELL, rehearse=True)
    cfg = dict(builder.hf_config(cfg), head_dim=64, num_attention_heads=2,
               num_key_value_heads=1, sliding_window=256)
    model = build_hybrid_lm(**afmoe_lm_kwargs(cfg, held_experts=(0, 1)))
    dec = builder.decoder_of(model)
    dec.remat_blocks = True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    real = fa._flash_lse
    monkeypatch.setattr(fa, "_flash_lse", lambda *a: real(
        *a[:7], True, a[8]))            # the kernels in the interpreter
    x = jnp.zeros((1, 1024, cfg["hidden_size"]))
    ins = instruments(get_registry())
    before = {f: ins.flash_attention_total.labels(form=f).value
              for f in ("band", "full")}
    edges0 = {e: ins.flash_band_edges_total.labels(edges=e).value
              for e in ("strips", "masked")}

    def f(p):
        return jnp.sum(functional_apply(dec, p, dec.buffer_tree(), x,
                                        training=True)[0])

    jaxpr = jax.make_jaxpr(jax.grad(f))(dec.parameter_tree())
    rise = {f: ins.flash_attention_total.labels(form=f).value - before[f]
            for f in before}
    assert rise == {"band": dec.pattern.count("W"),
                    "full": dec.pattern.count("*")}
    # at this size the window, 256, is half the default 512-tile, so each
    # banded layer keeps the all-masked loop (the cell's 2,048 is four
    # whole tiles and counts `strips`: tests/test_smallthinker_lm.py holds
    # that form at a window of one whole tile)
    assert {e: ins.flash_band_edges_total.labels(edges=e).value - edges0[e]
            for e in edges0} == {"strips": 0, "masked": dec.pattern.count("W")}
    text = str(jaxpr)
    for name in ("flash_band_fwd", "flash_band_bwd_dkv",
                 "flash_fwd", "flash_bwd_dkv"):
        assert f"name={name}" in text, name
    assert "bwd_dq" not in text     # dQ leaves the dK/dV call
    # and no (T, T) mask or score tensor outside the kernels' own tiles

    def outside(jx):
        for e in jx.eqns:
            if e.primitive.name == "pallas_call":
                continue
            yield from (v.aval.shape for v in e.outvars)
            for val in e.params.values():
                for item in (val if isinstance(val, (list, tuple))
                             else (val,)):
                    inner = getattr(item, "jaxpr", item)
                    if hasattr(inner, "eqns"):
                        yield from outside(inner)

    assert not [sh for sh in outside(jaxpr.jaxpr) if sh[-2:] == (1024, 1024)]
