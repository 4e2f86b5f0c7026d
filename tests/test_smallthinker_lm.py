"""The SmallThinker family (full, unrotated and sliding-window, rotated GQA
layers; a router that reads the LAYER'S input, ahead of the attention, and
weighs its picks by a softmax over their logits; ReGLU experts of which a
share is held) against its plain reference
(``benchmark/reference/smallthinker.py``), at small sizes on the CPU; the
share arithmetic of the expert layer; the configuration's sizes; its cell's
rehearsal and negative controls."""

import json
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, timeline
from benchmark.builders import smallthinker as builder
from benchmark.reference import smallthinker as reference
from bigdl_tpu import nn
from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.parallel import expert
from bigdl_tpu.parallel.expert import MoE, expert_param_specs

logging.getLogger("bigdl_tpu.optim").setLevel(logging.WARNING)

CELL = "smallthinker-21b-a3b-train-s16384"
CFG = dict(hidden_size=32, moe_ffn_hidden_size=24,
           moe_num_active_primary_experts=3)


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


# ------------------------------------------------------- the expert layer

def _moe(held, n_experts=8, seed=5, **kw):
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(seed)
    np.random.seed(seed)
    return MoE(32, 24, n_experts=n_experts, k=3, activation="reglu",
               dispatch="held", held=held, bias=False,
               score="softmax_picked", router_input="given", **kw)


def _named(p, order=None):
    router = p["gate_weight"] if order is None else p["gate_weight"][:, order]
    return {"e.primary_router.weight": router,
            "e.experts.gate_proj": p["wg"], "e.experts.up_proj": p["w1"],
            "e.experts.down_proj": p["w2"]}


def _plain_layer(p, m, h, cfg, held, order=None):
    """The reference's expert half on the normed stream ``m``, routed from
    ``h``: the held experts are the router's first columns after
    ``order``."""
    q = _named(p, order)
    picked, w = reference.route(q, "e.", h, cfg)
    return reference.experts(q, "e.", m, picked, w, held)


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
def test_reglu_experts_routed_from_a_given_stream_match_the_reference(
        held, block_rows, form, monkeypatch):
    """Top-3 of 8 logits of the GIVEN stream, a softmax over the three
    picked logits, ReGLU experts of three matrices; rows in blocks of 8
    and in one block, through the XLA loops and through the grouped
    kernels (in Pallas' interpreter). Output and every gradient, the
    router's and the routed-from stream's among them, to 2e-4."""
    _grouped_form(monkeypatch, form, block_rows)
    m = _moe(held)
    assert sorted(m._parameters) == ["gate_weight", "w1", "w2", "wg"]
    cfg = dict(CFG, moe_num_primary_experts=len(held))
    rng = _rng(2)
    u, h, probe = (_normal(rng, 2, 21, 32) for _ in range(3))
    params = m.parameter_tree()
    order = list(held) + [e for e in range(8) if e not in held]

    def plain(p, u, h):
        return _plain_layer(p, u, h, cfg, range(len(held)), order)

    _close(_apply(m, params, (u, h)), plain(params, u, h))
    got = jax.grad(lambda p, u, h: jnp.sum(_apply(m, p, (u, h)) * probe),
                   argnums=(0, 1, 2))(params, u, h)
    want = jax.grad(lambda p, u, h: jnp.sum(plain(p, u, h) * probe),
                    argnums=(0, 1, 2))(params, u, h)
    assert np.asarray(want[2]).any()        # through the softmax weights
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _close(a, b)


def test_the_softmax_over_the_picks_is_the_softmax_over_all_renormalised():
    m = _moe(tuple(range(8)))
    h = _normal(_rng(1), 40, 32)
    picked, w = m._route(h)
    probs = jax.nn.softmax(h @ m.gate_weight, axis=-1)
    top = jnp.take_along_axis(probs, picked, axis=-1)
    _close(w, top / top.sum(-1, keepdims=True), tol=1e-5)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(picked),
                                  np.asarray(jax.lax.top_k(probs, 3)[1]))


def test_the_shares_of_the_layer_add_up_to_the_uncut_layer():
    """THE SHARE TEST. 64 routed experts over 8 chips, 8 a chip, top-6, as
    the configuration cuts it: the routed parts the 8 shares compute equal
    what the uncut reference gives for the whole 64-expert layer (there is
    no shared expert to count once)."""
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(11)
    np.random.seed(11)
    kw = dict(activation="reglu", dispatch="held", bias=False,
              score="softmax_picked", router_input="given")
    whole = MoE(32, 24, n_experts=64, k=6, **kw)
    params = whole.parameter_tree()
    rng = _rng(3)
    u, h = _normal(rng, 3, 17, 32), _normal(rng, 3, 17, 32)
    cfg = dict(CFG, moe_num_active_primary_experts=6,
               moe_num_primary_experts=64)
    want = _plain_layer(params, u, h, cfg, range(64))
    picked, _ = reference.route(_named(params), "e.", h, cfg)
    total = 0.0
    for chip in range(8):
        held = tuple(range(8 * chip, 8 * chip + 8))
        share = MoE(32, 24, n_experts=64, k=6, held=held, **kw)
        p = dict(params, **{k: params[k][jnp.asarray(held)]
                            for k in ("w1", "wg", "w2")})
        total = total + _apply(share, p, (u, h))
    _close(total, want)
    assert len(np.unique(np.asarray(picked))) > 32


@pytest.mark.parametrize("held", [(0, 1), (2, 5, 7)])
def test_a_router_that_is_not_trained_takes_no_gradient(held):
    """``train_router=False`` under the softmax rule: the same output, no
    gradient to the router nor to the stream it reads; the reference under
    ``training.router_gradient`` ``"none"`` gives the same."""
    full, fixed = _moe(held), _moe(held, train_router=False)
    cfg = dict(CFG, moe_num_primary_experts=len(held),
               training={"router_gradient": "none"})
    rng = _rng(4)
    u, h, probe = (_normal(rng, 2, 21, 32) for _ in range(3))
    params = full.parameter_tree()
    order = list(held) + [e for e in range(8) if e not in held]

    def grads(out):
        return jax.grad(lambda p, u, h: jnp.sum(out(p, u, h) * probe),
                        argnums=(0, 1, 2))(params, u, h)

    np.testing.assert_array_equal(_apply(fixed, params, (u, h)),
                                  _apply(full, params, (u, h)))
    gp, gu, gh = grads(lambda p, u, h: _apply(fixed, p, (u, h)))
    wp, wu, wh = grads(lambda p, u, h: _plain_layer(
        p, u, h, cfg, range(len(held)), order))
    assert not np.asarray(gp["gate_weight"]).any()
    assert not np.asarray(gh).any() and not np.asarray(wh).any()
    _close(gu, wu)
    for k in params:
        _close(gp[k], wp[k])


def test_the_new_options_belong_to_the_held_layer():
    for bad in (dict(activation="reglu"),
                dict(activation="reglu", dispatch="held", bias=True),
                dict(score="softmax_picked"), dict(router_input="given"),
                dict(dispatch="held", score="softmax"),
                dict(dispatch="held", router_input="ahead")):
        with pytest.raises(ValueError):
            MoE(8, 8, n_experts=4, **bad)
    specs = expert_param_specs(_moe((0, 1)))
    assert specs["wg"] == specs["w1"] != specs["gate_weight"]


def test_the_router_counter_and_the_scope_say_what_the_layer_is():
    """``bigdl_moe_router_total{score, input}`` counts once a trace; the
    routing of a layer that is GIVEN its router's stream runs under
    ``moe_route_ahead`` and not under ``moe_route``."""
    from bigdl_tpu.telemetry import get_registry, instruments
    fam = instruments(get_registry()).moe_router_total
    m = _moe((2, 5, 7))
    own = MoE(32, 24, n_experts=8, k=3, activation="swiglu",
              dispatch="held", held=(2, 5, 7), bias=False)
    u, h = _normal(_rng(4), 2, 21, 32), _normal(_rng(5), 2, 21, 32)
    ahead = fam.labels(score="softmax_picked", input="given")
    plain = fam.labels(score="sigmoid", input="own")
    before = ahead.value, plain.value
    hlo = jax.jit(jax.grad(lambda p: jnp.sum(jnp.square(
        _apply(m, p, (u, h)))))).lower(m.parameter_tree()).compile().as_text()
    assert (ahead.value, plain.value) == (before[0] + 1, before[1])
    assert timeline.scope_instructions(hlo, "moe_route_ahead")
    assert not timeline.scope_instructions(hlo, "moe_route")
    assert timeline.scope_instructions(hlo, "moe_experts")
    hlo = jax.jit(lambda p: _apply(own, p, u)).lower(
        own.parameter_tree()).compile().as_text()
    assert (ahead.value, plain.value) == (before[0] + 1, before[1] + 1)
    assert timeline.scope_instructions(hlo, "moe_route")
    assert not timeline.scope_instructions(hlo, "moe_route_ahead")


# ------------------------------------------------------------------ the model

@pytest.fixture(scope="module")
def cut():
    cell, cfg = harness.load_cell(CELL, rehearse=True)
    return cell, cfg, builder.build(cfg, 3)


def test_the_rehearsal_keeps_what_the_cell_is_about(cut):
    """A window SHORTER than the compared sequence, the full layer first
    and three window layers, 2 held of 8 experts, the router ahead."""
    cell, cfg, model = cut
    assert cfg["sliding_window_size"] < cell["reference"]["seq_len"] \
        < cell["seq_len"]
    assert builder.decoder_of(model).pattern == "*RWRWRWR"
    assert cfg["moe_num_primary_experts"] == 2
    assert cfg["published"]["moe_num_primary_experts"] == 8
    real_cell, real = harness.load_cell(CELL)
    assert real["sliding_window_size"] < real_cell["reference"]["seq_len"]
    assert real_cell["seq_len"] == real["max_position_embeddings"] == 16384
    assert real["placement"]["by"] == "measured_load"
    for ours, theirs in (("records", "records_per_epoch"),
                         ("seq_len", "seq_len"), ("token_zipf", "token_zipf")):
        assert real["placement"][ours] == real_cell[theirs]
        assert cfg["placement"][ours] == cell[theirs]
    layers = [m for m in model.modules() if isinstance(m, MoE)]
    assert len(layers) == 4 and all(
        (m.score, m.router_input, m.activation, m.train_router, m.pick_rows)
        == ("softmax_picked", "given", "reglu", False, cfg["vocab_size"])
        for m in layers)
    assert cfg["training"]["router_picks"] \
        == real["training"]["router_picks"] == "token_id"


def _system_grads(model, cfg, cell, policy):
    from bigdl_tpu.optim.optimizer import make_training_loss_fn
    data, labels = builder.reference_batch(cfg, cell, 3)
    loss_fn = make_training_loss_fn(
        model, builder.criterion(cfg), policy, (), False,
        model.buffer_tree(), jax.random.PRNGKey(0), jnp.asarray(data),
        jnp.asarray(labels))
    grads, (_, loss) = jax.grad(loss_fn, has_aux=True)(
        model.parameter_tree())
    return loss, grads, data, labels


@pytest.mark.parametrize("picks", ["token_id", "scores"])
@pytest.mark.parametrize("router", ["none", "full"])
def test_the_loss_and_every_gradient_leaf_match_the_reference(router, picks):
    """The program's own training loss in float32 against the plain
    reference on seeded weights: the loss, and each leaf of the gradient
    under the reference's names, with the routers' gradient applied and
    left out, the picks a table's by token id and the live top k."""
    from bigdl_tpu.ops.precision import DtypePolicy
    cell, cfg = harness.load_cell(CELL, rehearse=True)
    cfg = dict(cfg, training=dict(cfg["training"], router_gradient=router,
                                  router_picks=picks))
    model = builder.build(cfg, 3)
    loss, grads, data, labels = _system_grads(model, cfg, cell, DtypePolicy())
    ids, tgt = (jnp.asarray(t, jnp.int32) - 1 for t in (data, labels))
    p = builder.reference_params(model)
    tables = {k: v for k, v in p.items() if k.endswith("pick_table")}
    assert len(tables) == (4 if picks == "token_id" else 0)
    (want, _), want_g = jax.value_and_grad(
        lambda q: reference.loss(dict(q, **tables), ids, tgt, cfg),
        has_aux=True)({k: v for k, v in p.items() if k not in tables})
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    got_g = builder.named(grads, builder.decoder_of(model).pattern)
    assert sorted(got_g) == sorted(want_g)
    for k in want_g:
        _close(got_g[k], want_g[k], tol=2e-4)
    routers = [k for k in want_g if "primary_router" in k]
    assert len(routers) == 4
    assert all(np.asarray(want_g[k]).any() == (router == "full")
               for k in routers)
    assert all(np.asarray(want_g[k]).any() for k in want_g
               if k not in routers)


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


@pytest.mark.parametrize("layer,kinds", [(0, "*R"), (1, "WR")])
def test_each_kind_of_layer_matches_the_reference(cut, layer, kinds):
    """One layer's output (its attention block, then its expert block,
    which is handed the stream that entered the attention block) on a
    random stream: full attention without rotation, a window with it."""
    cell, cfg, model = cut
    dec = builder.decoder_of(model)
    assert dec.pattern[2 * layer:2 * layer + 2] == kinds
    x = _normal(_rng(layer), 2, 40, cfg["hidden_size"])
    ids = _rng(layer).integers(1, cfg["vocab_size"] + 1, (2, 40))
    after = dec._modules[f"layer{2 * layer}"].forward(x)
    with expert.token_ids(jnp.asarray(ids, jnp.float32)):
        got = dec._modules[f"layer{2 * layer + 1}"].forward((after, x))
        # the router reads the layer's INPUT: handed the post-attention
        # stream the block gives another result
        wrong = dec._modules[f"layer{2 * layer + 1}"].forward((after, after))
    want, _ = reference.layer(builder.reference_params(model), layer, x, cfg,
                              jnp.asarray(ids - 1))
    _close(got, want, tol=1e-5)
    assert np.abs(np.asarray(wrong - want)).max() > 1e-3


def test_an_r_block_reads_what_entered_the_block_before_it():
    with pytest.raises(ValueError):
        nn.HybridDecoder("R*", 16, moe={}, attention={})
    moe = dict(hidden_size=8, n_experts=4, k=2, activation="reglu",
               dispatch="held", bias=False, score="softmax_picked")
    dec = nn.HybridDecoder("*R", 16, moe=moe,
                           attention=dict(num_heads=2, with_bias=False))
    assert dec.layer1.routed_ahead and not dec.layer0.routed_ahead
    assert dec.layer1.mixer.router_input == "given"
    x = _normal(_rng(2), 1, 12, 16)
    after = dec.layer0.forward(x)
    _close(dec.stream(x), dec.layer1.forward((after, x)), tol=1e-6)


def test_the_tables_are_the_seeded_routers_own_picks_and_then_stand(cut):
    """``training.router_picks`` ``"token_id"``: after the build row ``t``
    of every router's table is its top k over token ``t``'s embedding row
    (after the placement's relabelling), which for the FIRST layer, whose
    router reads the embedding itself, are its live picks; the weights
    stay the softmax over the live logits of the table's picks; and with
    every parameter moved the system's measured picks are what they were,
    the rows' ids deciding them alone."""
    cell, cfg, model = cut
    rows = jnp.asarray(next(m for m in model.modules()
                            if isinstance(m, nn.LookupTable)).weight)
    routers = [m for m in model.modules() if isinstance(m, MoE)]
    for m in routers:
        assert m.pick_table.shape == (cfg["vocab_size"], m.k)
        _, want = jax.lax.top_k(rows @ m.gate_weight, m.k)
        np.testing.assert_array_equal(
            np.sort(np.asarray(m.pick_table), 1), np.sort(want, 1))
    ids = _rng(2).integers(1, cfg["vocab_size"] + 1, (2, 9))
    first, h = routers[0], rows[ids - 1]
    with expert.token_ids(jnp.asarray(ids, jnp.float32)):
        picked, w = first._route(h.reshape(-1, h.shape[-1]))
    top, live = jax.lax.top_k(h.reshape(-1, h.shape[-1]) @ first.gate_weight,
                              first.k)
    np.testing.assert_array_equal(np.sort(picked, 1), np.sort(live, 1))
    _close(np.sort(w, 1), np.sort(jax.nn.softmax(top, -1), 1), tol=1e-6)
    data = np.stack([s.feature for s in builder.train_samples(cfg, cell, 3)])
    params = model.parameter_tree()
    moved = jax.tree_util.tree_map(
        lambda a: a + 0.5 * _normal(_rng(9), *a.shape), params)
    dec = builder.decoder_of(model)
    dec.remat_blocks = False        # the builder measures before it sets it
    try:
        before = builder.measured_loads(model, data)
        model.load_parameter_tree(moved)
        after = builder.measured_loads(model, data)
    finally:
        model.load_parameter_tree(params)
        dec.remat_blocks = True
    np.testing.assert_array_equal(before, after)
    with pytest.raises(ValueError):
        builder.build(dict(cfg, training=dict(cfg["training"],
                                              router_picks="hash")), 3)


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
    from bigdl_tpu.interop.hf import (smallthinker_lm_kwargs,
                                      smallthinker_pattern)
    _, cfg = harness.load_cell(CELL)
    kw = smallthinker_lm_kwargs(builder.hf_config(cfg),
                                held_experts=range(8))
    assert kw["pattern"] == "*RWRWRWR" and kw["embed_dim"] == 2560
    assert kw["vocab_size"] == 18992 and kw["norm_eps"] == 1e-6
    assert "post_norm" not in kw and "embed_scale" not in kw
    full = dict(num_heads=28, num_kv_heads=4, head_dim=128, with_bias=False,
                rope=False, rope_theta=1.5e6, window=None)
    assert kw["attention"] == full
    assert kw["window_attention"] == dict(full, rope=True, window=4096)
    assert kw["moe"] == dict(
        hidden_size=768, n_experts=64, k=6, activation="reglu",
        dispatch="held", held=tuple(range(8)), bias=False,
        score="softmax_picked", train_router=True, pick_rows=0)
    assert not smallthinker_lm_kwargs(
        builder.hf_config(cfg), train_router=False)["moe"]["train_router"]
    assert smallthinker_lm_kwargs(
        builder.hf_config(cfg), picks_by_token=True)["moe"]["pick_rows"] \
        == 18992
    pub = cfg["published"]
    assert smallthinker_pattern(pub["rope_layout"],
                                pub["sliding_window_layout"]) \
        == "*RWRWRWR" * 13
    # an unknown sibling is refused BY NAME, never run as the softmax
    with pytest.raises(ValueError,
                       match="moe_primary_router_apply_softmax"):
        smallthinker_lm_kwargs(dict(
            builder.hf_config(cfg), moe_primary_router_apply_softmax=False))
    with pytest.raises(ValueError, match="moe_enable_secondary_experts"):
        smallthinker_lm_kwargs(dict(
            builder.hf_config(cfg), moe_enable_secondary_experts=True))
    for bad in (dict(norm_topk_prob=False), dict(num_hidden_layers=5),
                dict(tie_word_embeddings=True),
                dict(rope_scaling={"type": "yarn"}),
                dict(attention_bias=True),
                dict(rope_layout=[0, 1, 1]),
                dict(rope_layout=[0, 1, 0, 1]),     # a window group mixed
                dict(sliding_window_layout=[0, 2, 1, 1])):
        with pytest.raises(ValueError):
            smallthinker_lm_kwargs(dict(builder.hf_config(cfg), **bad))


def test_the_published_model_has_the_published_size(cut):
    """The builder's shapes at the PUBLISHED depth, experts and vocabulary
    give 21.5B parameters, and the cut gives 370.5M; the same count at the
    rehearsal's sizes is what the builder builds."""
    def sizes(e, h, kv, d, f, router, vocab):
        return dict(attn=e * (h + 2 * kv) * d + h * d * e, router=e * router,
                    expert=3 * e * f, norms=2 * e, vocab=2 * vocab * e + e)

    _, cfg = harness.load_cell(CELL)
    pub = cfg["published"]
    z = sizes(2560, 28, 4, 128, 768, 64, pub["vocab_size"])
    assert round(z["attn"] / 1e6, 2) == 20.97
    assert round(z["expert"] / 1e6, 2) == 5.90
    layer = lambda held: z["attn"] + z["norms"] + z["router"] \
        + held * z["expert"]
    whole = pub["num_hidden_layers"] * layer(64) + z["vocab"]
    assert round(whole / 1e9, 1) == 21.5
    here = 4 * layer(8) + 2 * 18992 * 2560 + 2560
    assert here == 370_547_200 and round(here / 1e6, 1) == 370.5
    _, small, model = cut
    s = sizes(small["hidden_size"], small["num_attention_heads"],
              small["num_key_value_heads"], small["head_dim"],
              small["moe_ffn_hidden_size"],
              small["published"]["moe_num_primary_experts"],
              small["vocab_size"])
    built = sum(int(np.prod(leaf.shape)) for leaf in
                jax.tree_util.tree_leaves(model.parameter_tree()))
    assert built == 4 * (s["attn"] + s["norms"] + s["router"]
                         + 2 * s["expert"]) + s["vocab"]


def test_block_remat_carries_two_values_and_changes_nothing(cut):
    """Remat on and off: the same loss and gradients, though an ``R``
    block's checkpoint takes the stream AND the layer's input; with the
    routers trained the second value carries a gradient across the
    boundary too."""
    cell, cfg, _ = cut
    model = builder.build(dict(cfg, training={"remat": "block"}), 3)
    dec = builder.decoder_of(model)
    assert dec.remat_blocks and all(
        m.train_router and not m.pick_rows for m in model.modules()
        if isinstance(m, MoE))
    x = _normal(_rng(8), 1, 24, cfg["hidden_size"])

    ids = jnp.asarray(_rng(8).integers(1, cfg["vocab_size"] + 1, (1, 24)),
                      jnp.float32)

    def loss_and_grads():
        def f(p, x):
            with expert.token_ids(ids):
                return jnp.sum(jnp.square(functional_apply(
                    dec, p, dec.buffer_tree(), x, training=True)[0]))
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(
            dec.parameter_tree(), x)

    kept_loss, kept = loss_and_grads()
    with expert.token_ids(ids):
        jaxpr = jax.make_jaxpr(lambda p: functional_apply(
            dec, p, dec.buffer_tree(), x, training=True)[0])(
                dec.parameter_tree())
    # what crosses each block's boundary that is no parameter: one stream
    # into an attention block, two into an ``R`` block
    streams = [sum(v.aval.shape == x.shape for v in e.invars)
               for e in jaxpr.jaxpr.eqns if e.primitive.name == "remat2"]
    assert streams == [1, 2] * 4
    dec.remat_blocks = False
    try:
        plain_loss, plain = loss_and_grads()
    finally:
        dec.remat_blocks = True
    _close(kept_loss, plain_loss, tol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(kept)):
        _close(a, b, tol=1e-5)


def test_block_remat_keeps_the_routing_tables_of_an_r_block(cut, monkeypatch):
    """The compiled backward of the rematerialised stack sorts as often as
    the plain one (an expert block's argsort; on the CPU its top-k is no
    sort) and twice as often under a policy that keeps nothing: the
    routing's tables stay on ``ops.remat``'s kept list when the router's
    stream comes in through the checkpoint's second argument."""
    from bigdl_tpu.nn import hybrid
    cell, cfg, model = cut
    dec = builder.decoder_of(model)
    x = _normal(_rng(8), 1, 24, cfg["hidden_size"])

    ids = jnp.asarray(_rng(8).integers(1, cfg["vocab_size"] + 1, (1, 24)),
                      jnp.float32)

    def sorts(remat):
        def f(p, ids):      # an argument: closed over, the sorts fold away
            with expert.token_ids(ids):
                return jnp.sum(jnp.square(functional_apply(
                    dec, p, dec.buffer_tree(), x, training=True)[0]))
        dec.remat_blocks = remat
        try:
            return jax.jit(jax.grad(f)).lower(
                dec.parameter_tree(), ids).compile().as_text().count(
                    " sort(")
        finally:
            dec.remat_blocks = True

    kept = sorts(True)
    assert kept == sorts(False) == dec.pattern.count("R")
    monkeypatch.setattr(hybrid, "block_remat_policy",
                        lambda through=None:
                        jax.checkpoint_policies.nothing_saveable)
    assert sorts(True) == 2 * kept


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
                               grad_norm_rtol=1e-5, block_out_rtol=1e-4,
                               block_grad_rtol=1e-4))
    return dict(controls.run(cell, cfg, 3))


def test_the_sound_system_passes_the_reference_check(controlled):
    assert set(controlled) == {"sound", *builder.FAULTS}
    assert controlled["sound"]["ok"]


@pytest.mark.parametrize("fault", builder.FAULTS)
def test_a_fault_planted_in_the_system_goes_through_the_reference_check(
        controlled, fault):
    """Each fault is in the SYSTEM's modules (``reference_bf16``: the
    plain reference in bf16 where the system stood) and goes through the
    comparison that decides ``correct``; the reference is the sound one.
    In float32 at the rehearsal size every one of them fails it: this is
    the test that holds a fault the chip's bf16 limits cannot see (the
    cell file's ``reference.why``)."""
    got, sound = controlled[fault], controlled["sound"]
    assert got["reference_loss"] == sound["reference_loss"]
    assert got["system_loss"] != sound["system_loss"]
    assert not got["ok"]


@pytest.mark.parametrize("fault", ["rope_on_full", "no_band"])
def test_the_attention_blocks_alone_refuse_a_fault_of_the_attention(
        cut, fault, capfd):
    """The block check (one full and one window mixer against the
    reference's at the cell's length) says not ok whatever the loss says:
    the builder then hands the train kind NaN for its two numbers."""
    cell, cfg, model = cut
    fp32 = dict(cell, precision="fp32", reference=dict(
        cell["reference"], block_out_rtol=1e-4, block_grad_rtol=1e-4))
    builder.reference_batch(cfg, fp32, 3)
    sound = builder.attention_blocks(model)
    assert set(sound) == {"full", "window"}
    assert all(r["out"] < 1e-5 and r["grad"] < 1e-5 for r in sound.values())
    assert builder._gated((1.0, 2.0), model) == (1.0, 2.0)
    with builder.planted(model, fault):
        read = builder.attention_blocks(model)
        gated = builder._gated((1.0, 2.0), model)
    hit = "full" if fault == "rope_on_full" else "window"
    other = "window" if hit == "full" else "full"
    assert read[hit]["out"] > 1e-2 and read[other]["out"] < 1e-5
    assert np.isnan(gated).all()
    assert "benchmark detail attention_blocks: " in capfd.readouterr().err


def test_a_planted_fault_is_taken_out_again(cut):
    from benchmark.kinds import train as kind
    _, _, model = cut
    system = kind.system_loss_and_grad_norm

    def state():
        return ([(m.window, m.rope) for m in model.modules()
                 if isinstance(m, nn.MultiHeadAttention)],
                [(m.score, m.activation) for m in model.modules()
                 if isinstance(m, MoE)],
                ["update_output" in m.__dict__ for m in model.modules()
                 if isinstance(m, nn.HybridBlock)])

    before = state()
    for fault in builder.FAULTS:
        with builder.planted(model, fault):
            assert kind.system_loss_and_grad_norm is not system
            assert fault == "reference_bf16" or state() != before
    assert state() == before and kind.system_loss_and_grad_norm is system
    assert before[0] == [(None, False)] + [(16, True)] * 3
    assert before[1] == [("softmax_picked", "reglu")] * 4
    assert not any(before[2])
    with pytest.raises(ValueError):
        with builder.planted(model, "no_such_fault"):
            pass


def test_placement_relabels_the_routers_and_changes_no_layer(cut, capfd):
    """As the Trinity cell's: only the router matrices differ from the
    same seed built without the placement, by the deal's permutation of
    their columns, measured on the very rows the cell trains on."""
    cell, cfg, model = cut
    route = MoE._route
    plain = builder.build({k: v for k, v in cfg.items()
                           if k != "placement"}, 3)
    capfd.readouterr()
    placed = builder.build(cfg, 3)
    line = [ln for ln in capfd.readouterr().err.splitlines()
            if ln.startswith("benchmark detail placement: ")]
    assert len(line) == 1
    detail = json.loads(line[0].split(": ", 1)[1])
    rows = np.stack([s.feature for s in
                     builder.train_samples(cfg, cell, 3)])
    by_row = builder.measured_loads(plain, rows)
    assert MoE._route is route
    assert by_row.shape == (cell["records_per_epoch"], 4, 8)
    assert (by_row.sum(2) == cell["seq_len"]
            * cfg["moe_num_active_primary_experts"]).all()
    a = {jax.tree_util.keystr(k): np.asarray(v) for k, v in
         jax.tree_util.tree_leaves_with_path(plain.parameter_tree())}
    b = {jax.tree_util.keystr(k): np.asarray(v) for k, v in
         jax.tree_util.tree_leaves_with_path(placed.parameter_tree())}
    routers = sorted(k for k in a if "gate_weight" in k)
    assert len(routers) == 4
    for k in a:
        if k not in routers:
            np.testing.assert_array_equal(a[k], b[k])
    for k, load, held in zip(routers, by_row.mean(0), detail["held_picks"]):
        order = builder.deal(load, 4)
        np.testing.assert_array_equal(a[k][:, order], b[k])
        assert held == load[order[:2]].round().astype(int).tolist()
    with pytest.raises(ValueError):
        builder.build(dict(cfg, placement=dict(cfg["placement"],
                                               by="guess")), 3)


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
    blocks = json.loads(next(
        ln for ln in err.splitlines() if ln.startswith(
            "benchmark detail attention_blocks: ")).split(": ", 1)[1])
    assert blocks["ok"] and set(blocks) >= {"full", "window"}


def test_a_traced_step_counts_band_and_full_once_a_layer(monkeypatch):
    """On a TPU backend, at a sequence the kernels take and longer than
    the window: tracing the stack's training loss counts ``form=band``
    once for each window layer and ``form=full`` once for the full one,
    and the jaxpr holds the kernels under both sets of names."""
    from bigdl_tpu.interop.hf import smallthinker_lm_kwargs
    from bigdl_tpu.models.hybrid import build_hybrid_lm
    from bigdl_tpu.ops import flash_attention as fa
    from bigdl_tpu.telemetry import get_registry, instruments
    _, cfg = harness.load_cell(CELL, rehearse=True)
    cfg = dict(builder.hf_config(cfg), head_dim=64, num_attention_heads=2,
               num_key_value_heads=1, sliding_window_size=512)
    model = build_hybrid_lm(**smallthinker_lm_kwargs(cfg,
                                                     held_experts=(0, 1)))
    dec = builder.decoder_of(model)
    dec.remat_blocks = True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(expert, "takes_kernel", lambda *a: False)
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

    text = str(jax.make_jaxpr(jax.grad(f))(dec.parameter_tree()))
    rise = {f: ins.flash_attention_total.labels(form=f).value - before[f]
            for f in before}
    assert rise == {"band": 3, "full": 1}
    # 1,024 tokens under the default 512-tile and a window of 512, one
    # whole tile as the cell's 4,096 is eight: every banded layer runs its
    # edges as strips, as the cell's do
    assert {e: ins.flash_band_edges_total.labels(edges=e).value - edges0[e]
            for e in edges0} == {"strips": 3, "masked": 0}
    for name in ("flash_band_fwd", "flash_band_bwd_dkv",
                 "flash_fwd", "flash_bwd_dkv"):
        assert f"name={name}" in text, name
