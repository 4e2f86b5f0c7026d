"""The LFM2 mixture-of-experts family (a double-gated short convolution in
three of every four layers, full GQA attention with q/k norm and rotation
in the fourth, a dense and sigmoid-routed SwiGLU feed-forwards, the head
tied to the embedding) against its plain reference
(``benchmark/reference/lfm2_moe.py``), at small sizes on the CPU; the share
arithmetic of the expert layer; the configuration's sizes; its cell's
rehearsal and negative controls."""

import json
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, timeline
from benchmark.builders import lfm2_moe as builder
from benchmark.reference import lfm2_moe as reference
from bigdl_tpu import nn
from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.parallel.expert import MoE

logging.getLogger("bigdl_tpu.optim").setLevel(logging.WARNING)

CELL = "lfm2-24b-a2b-train-s8192"
CFG = dict(hidden_size=32, conv_L_cache=3, moe_intermediate_size=24,
           num_experts_per_tok=4)


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


# ------------------------------------------------------------ the mixer alone

@pytest.fixture(scope="module")
def mixer():
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(7)
    return nn.ShortConv(32, kernel=3)


def test_the_mixer_is_the_three_term_sum_between_two_gates(mixer):
    """``nn.ShortConv`` against the reference's ``short_conv`` (the
    convolution written as the three-term sum it is): output and every
    gradient, the input's among them, in float32."""
    assert sorted(mixer._parameters) == ["conv_weight", "in_proj_weight",
                                         "out_proj_weight"]
    assert mixer.in_proj_weight.shape == (96, 32)
    assert mixer.conv_weight.shape == (32, 3)
    u, probe = _normal(_rng(1), 2, 19, 32), _normal(_rng(2), 2, 19, 32)
    params = mixer.parameter_tree()

    def plain(p, u):
        return reference.short_conv(builder.conv_named(p), "", u, CFG)

    _close(_apply(mixer, params, u), plain(params, u), tol=1e-5)
    got = jax.grad(lambda p, u: jnp.sum(_apply(mixer, p, u) * probe),
                   argnums=(0, 1))(params, u)
    want = jax.grad(lambda p, u: jnp.sum(plain(p, u) * probe),
                    argnums=(0, 1))(params, u)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.asarray(b).any()
        _close(a, b, tol=1e-5)


def test_the_mixer_is_causal_and_exact_where_the_sequence_starts(mixer):
    """An input changed at position t moves no output before t; positions
    0 and 1 read zeros where the sequence has no past: ``c_0 = w_2 g_0``,
    ``c_1 = w_1 g_0 + w_2 g_1``."""
    u = _normal(_rng(3), 1, 12, 32)
    y = mixer.forward(u)
    for t in (0, 1, 5, 11):
        moved = mixer.forward(u.at[:, t].add(1.0))
        np.testing.assert_array_equal(np.asarray(moved[:, :t]),
                                      np.asarray(y[:, :t]))
        assert np.abs(np.asarray(moved[:, t] - y[:, t])).max() > 1e-4
    bcx = u @ mixer.in_proj_weight.T
    b, c, x = bcx[..., :32], bcx[..., 32:64], bcx[..., 64:]
    g, w = b * x, jnp.asarray(mixer.conv_weight)
    first = (c[:, 0] * (w[:, 2] * g[:, 0])) @ mixer.out_proj_weight.T
    second = (c[:, 1] * (w[:, 1] * g[:, 0] + w[:, 2] * g[:, 1])) \
        @ mixer.out_proj_weight.T
    _close(y[:, 0], first, tol=1e-6)
    _close(y[:, 1], second, tol=1e-6)


def test_mamba_runs_the_same_convolution_under_its_bias_and_silu():
    from bigdl_tpu.nn.short_conv import causal_depthwise_conv
    m = nn.Mamba2(16, num_heads=2, head_dim=8, state_size=4)
    x = _normal(_rng(4), 2, 9, m.conv_dim)
    want = jax.nn.silu(causal_depthwise_conv(x, m.conv_weight)
                       + m.conv_bias)
    np.testing.assert_array_equal(np.asarray(m._conv(x)), np.asarray(want))
    with pytest.raises(ValueError):
        nn.ShortConv(8, kernel=0)


def test_the_counter_and_the_scopes_say_what_the_mixer_did(mixer):
    """``bigdl_short_conv_total{form=xla}`` counts once a trace; the two
    products land under ``short_conv_proj``, the rest under
    ``short_conv_local``, backward too."""
    from bigdl_tpu.telemetry import get_registry, instruments
    from bigdl_tpu.telemetry.step_partition import classify, instructions
    fam = instruments(get_registry()).short_conv_total.labels(form="xla")
    before = fam.value
    u = _normal(_rng(5), 2, 16, 32)
    hlo = jax.jit(jax.grad(lambda p: jnp.sum(jnp.square(
        _apply(mixer, p, u))))).lower(
            mixer.parameter_tree()).compile().as_text()
    assert fam.value == before + 1
    assert timeline.scope_instructions(hlo, "short_conv_proj")
    assert timeline.scope_instructions(hlo, "short_conv_local")
    where = {}
    for code, op_name in instructions(hlo).values():
        if "ShortConv" in op_name or "short_conv" in op_name:
            where.setdefault(classify(op_name)[0], set()).add(code)
    assert set(where) == {"short_conv_proj", "short_conv_local"}
    assert "dot" in where["short_conv_proj"]
    assert "dot" not in where["short_conv_local"]


# ------------------------------------------------------- the expert layer

def test_the_shares_of_the_layer_add_up_to_the_uncut_layer():
    """THE SHARE TEST. 64 routed experts over 8 chips, 8 a chip, top-4 of
    sigmoid scores over their sum + 1e-6, as the configuration cuts it: the
    routed parts the 8 shares compute equal what the uncut reference gives
    for the whole 64-expert layer (there is no shared expert to count
    once)."""
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(11)
    np.random.seed(11)
    kw = dict(activation="swiglu", dispatch="held", bias=False,
              renorm_eps=1e-6)
    whole = MoE(32, 24, n_experts=64, k=4, **kw)
    params = whole.parameter_tree()
    m = _normal(_rng(3), 3, 17, 32)
    cfg = dict(CFG, num_experts=64)
    q = {"e.gate.weight": params["gate_weight"],
         "e.expert_bias": jnp.zeros((64,)),
         "e.experts.w1": params["wg"], "e.experts.w3": params["w1"],
         "e.experts.w2": params["w2"]}
    picked, w = reference.route(q, "e.", m, cfg)
    want = reference.experts(q, "e.", m, picked, w, range(64))
    total = 0.0
    for chip in range(8):
        held = tuple(range(8 * chip, 8 * chip + 8))
        share = MoE(32, 24, n_experts=64, k=4, held=held, **kw)
        p = dict(params, **{k: params[k][jnp.asarray(held)]
                            for k in ("w1", "wg", "w2")})
        total = total + _apply(share, p, m)
    _close(total, want)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)
    assert len(np.unique(np.asarray(picked))) > 32


def test_the_sum_under_the_picked_scores_is_the_layers_argument():
    """``renorm_eps`` stands under the sum of the picked sigmoid scores:
    1e-20 unless the family's class writes another."""
    m = _normal(_rng(6), 40, 32)
    for eps in (1e-20, 1e-6, 0.5):
        moe = MoE(32, 24, n_experts=8, k=2, activation="swiglu",
                  dispatch="held", bias=False, renorm_eps=eps)
        picked, w = moe._route(m)
        s = jnp.take_along_axis(jax.nn.sigmoid(m @ moe.gate_weight), picked,
                                -1)
        _close(w, s / (s.sum(-1, keepdims=True) + eps), tol=1e-6)
    assert MoE(32, 24, n_experts=8).renorm_eps == 1e-20


# ------------------------------------------------------------------ the model

@pytest.fixture(scope="module")
def cut():
    cell, cfg = harness.load_cell(CELL, rehearse=True)
    return cell, cfg, builder.build(cfg, 3)


def test_the_rehearsal_keeps_what_the_cell_is_about(cut):
    """The dense layer first, then one whole period (attention second),
    2 held of 8 experts, a batch above one, the head tied."""
    cell, cfg, model = cut
    assert builder.decoder_of(model).pattern == "C-*ECECECE"
    assert cfg["num_experts"] == 2 and cfg["published"]["num_experts"] == 8
    assert cell["batch_size"] > 1
    real_cell, real = harness.load_cell(CELL)
    assert (real_cell["batch_size"], real_cell["seq_len"]) == (4, 8192)
    assert real["layer_types"] == real["published"]["layer_types"][1:6]
    assert real["placement"]["by"] == "measured_load"
    for ours, theirs in (("records", "records_per_epoch"),
                         ("seq_len", "seq_len"), ("token_zipf", "token_zipf")):
        assert real["placement"][ours] == real_cell[theirs]
        assert cfg["placement"][ours] == cell[theirs]
    layers = [m for m in model.modules() if isinstance(m, MoE)]
    assert len(layers) == 4 and all(
        (m.score, m.activation, m.train_router, m.pick_rows, m.renorm_eps,
         m.shared_hidden) == ("sigmoid", "swiglu", False, cfg["vocab_size"],
                              1e-6, 0) for m in layers)
    convs = [m for m in model.modules() if isinstance(m, nn.ShortConv)]
    assert len(convs) == 4 and all(m.kernel == 3 for m in convs)
    att, = [m for m in model.modules()
            if isinstance(m, nn.MultiHeadAttention)]
    assert att.qk_norm and att.rope and att.rope_theta == 1e6
    assert isinstance(list(model.modules())[-1], nn.TiedLMHead)


def _system_grads(model, cfg, cell, policy):
    from bigdl_tpu.optim.optimizer import make_training_loss_fn
    data, labels = builder.reference_batch(cfg, cell, 3)

    @jax.jit
    def run(params, buffers, data, labels):
        loss_fn = make_training_loss_fn(
            model, builder.criterion(cfg), policy, (), False, buffers,
            jax.random.PRNGKey(0), data, labels)
        grads, (_, loss) = jax.grad(loss_fn, has_aux=True)(params)
        return loss, grads

    loss, grads = run(model.parameter_tree(), model.buffer_tree(),
                      jnp.asarray(data), jnp.asarray(labels))
    return loss, grads, data, labels


@pytest.mark.parametrize("remat", ["block", None])
@pytest.mark.parametrize("router,picks", [("none", "token_id"),
                                          ("full", "scores")])
def test_the_loss_and_every_gradient_leaf_match_the_reference(router, picks,
                                                              remat):
    """The program's own training loss in float32 against the plain
    reference on seeded weights at batch 2: the loss, and each leaf of the
    gradient under the reference's names, with block remat on and off, the
    routers' gradient left out over a table's picks and applied over the
    live top k."""
    from bigdl_tpu.ops.precision import DtypePolicy
    cell, cfg = harness.load_cell(CELL, rehearse=True)
    cell = dict(cell, reference=dict(cell["reference"], batch=2))
    cfg = dict(cfg, training=dict(cfg["training"], router_gradient=router,
                                  router_picks=picks, remat=remat))
    model = builder.build(cfg, 3)
    assert builder.decoder_of(model).remat_blocks == (remat == "block")
    loss, grads, data, labels = _system_grads(model, cfg, cell, DtypePolicy())
    assert data.shape[0] == 2
    ids, tgt = (jnp.asarray(t, jnp.int32) - 1 for t in (data, labels))
    p = builder.reference_params(model)
    fixed = {k: v for k, v in p.items()
             if k.endswith(("pick_table", "expert_bias"))}
    assert sum(k.endswith("pick_table") for k in fixed) \
        == (4 if picks == "token_id" else 0)
    (want, _), want_g = jax.jit(jax.value_and_grad(
        lambda q: reference.loss(dict(q, **fixed), ids, tgt, cfg),
        has_aux=True))({k: v for k, v in p.items() if k not in fixed})
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    got_g = builder.named(grads, builder.decoder_of(model).pattern)
    assert sorted(got_g) == sorted(want_g)
    for k in want_g:
        _close(got_g[k], want_g[k], tol=2e-4)
    routers = [k for k in want_g if k.endswith("gate.weight")]
    assert len(routers) == 4
    assert all(np.asarray(want_g[k]).any() == (router == "full")
               for k in routers)
    assert all(np.asarray(want_g[k]).any() for k in want_g
               if k not in routers)


def test_the_tied_matrix_gets_one_gradient_the_sum_of_both_uses(cut):
    """Over the sliced vocabulary ONE (V, E) parameter serves the lookup
    and the head; its gradient is the lookup's plus the head's, each taken
    from the reference with the other use held constant."""
    from bigdl_tpu.ops.precision import DtypePolicy
    cell, cfg, model = cut
    leaves = [leaf for leaf in jax.tree_util.tree_leaves(
        model.parameter_tree())
        if leaf.shape == (cfg["vocab_size"], cfg["hidden_size"])]
    assert len(leaves) == 1
    _, grads, data, labels = _system_grads(model, cfg, cell, DtypePolicy())
    ids, tgt = (jnp.asarray(t, jnp.int32) - 1 for t in (data, labels))
    p = builder.reference_params(model)

    def two_uses(lookup, head):
        x, _ = reference.hidden(dict(p, **{"model.embed_tokens.weight":
                                           lookup}), ids, cfg)
        lp = jax.nn.log_softmax(x @ head.T, -1)
        return -jnp.mean(jnp.take_along_axis(lp, tgt[..., None], -1))

    table = p["model.embed_tokens.weight"]
    g_lookup, g_head = jax.jit(jax.grad(two_uses, argnums=(0, 1)))(table,
                                                                   table)
    assert np.asarray(g_lookup).any() and np.asarray(g_head).any()
    _close(grads["0"]["weight"], g_lookup + g_head, tol=2e-4)
    assert np.abs(np.asarray(g_lookup)).max() > 1e-3 * np.abs(
        np.asarray(g_head)).max()


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


@pytest.mark.parametrize("layer,kinds", [(0, "C-"), (1, "*E"), (2, "CE")])
def test_each_kind_of_layer_matches_the_reference(cut, layer, kinds):
    """One layer's output (its mixer block, then its feed-forward block) on
    a random stream of batch 2: the convolution over the dense layer, the
    attention and a convolution over experts."""
    from bigdl_tpu.parallel import expert
    cell, cfg, model = cut
    dec = builder.decoder_of(model)
    assert dec.pattern[2 * layer:2 * layer + 2] == kinds
    x = _normal(_rng(layer), 2, 40, cfg["hidden_size"])
    ids = _rng(layer).integers(1, cfg["vocab_size"] + 1, (2, 40))
    with expert.token_ids(jnp.asarray(ids, jnp.float32)):
        got = dec._modules[f"layer{2 * layer + 1}"].forward(
            dec._modules[f"layer{2 * layer}"].forward(x))
    want, _ = reference.layer(builder.reference_params(model), layer, x, cfg,
                              jnp.asarray(ids - 1))
    _close(got, want, tol=1e-5)


def test_the_reference_in_bf16_is_the_tolerances_second_reading(cut):
    cell, cfg, model = cut
    data, labels = builder.reference_batch(cfg, cell, 3)
    ids, tgt = (jnp.asarray(t, jnp.int32) - 1 for t in (data, labels))
    p = builder.reference_params(model)
    both = jax.jit(lambda p, dtype: reference.loss_and_grad_norm(
        p, ids, tgt, cfg, dtype)[:2], static_argnums=1)
    (true, gn), (low, gn_low) = both(p, jnp.float32), both(p, jnp.bfloat16)
    assert 0 < abs(float(low) - float(true)) < 0.02 * float(true)
    assert 0 < abs(float(gn_low) - float(gn)) < 0.1 * float(gn)


def test_hf_config_maps_to_the_builders_arguments():
    """The published config's keys give the pattern ``C-C-*E CE CE CE ...``
    with the two dense layers first, the keyword groups and a tied head;
    what is not mapped is refused BY NAME."""
    from bigdl_tpu.interop.hf import lfm2_moe_lm_kwargs, lfm2_moe_pattern
    _, cfg = harness.load_cell(CELL)
    pub = dict(builder.hf_config(cfg), **cfg["published"])
    kw = lfm2_moe_lm_kwargs(pub)
    assert kw["pattern"] == "C-C-" + "*ECECECE" * 9 + "*ECE"
    assert kw["pattern"].count("C") == 30 and kw["pattern"].count("*") == 10
    assert lfm2_moe_pattern(cfg["layer_types"], 1) == "C-*ECECECE"
    assert kw["embed_dim"] == 2048 and kw["vocab_size"] == 65536
    assert kw["norm_eps"] == 1e-5 and kw["tie_embeddings"] is True
    assert kw["short_conv"] == dict(kernel=3)
    assert kw["attention"] == dict(
        num_heads=32, num_kv_heads=8, with_bias=False, qk_norm=True,
        qk_norm_eps=1e-5, rope=True, rope_theta=1e6)
    assert kw["mlp"] == dict(hidden_size=11776)
    assert kw["moe"] == dict(
        hidden_size=1536, n_experts=64, k=4, activation="swiglu",
        dispatch="held", held=None, bias=False, route_scale=1.0,
        renorm_eps=1e-6, train_router=True, pick_rows=0)
    here = lfm2_moe_lm_kwargs(builder.hf_config(cfg), held_experts=range(8),
                              train_router=False, picks_by_token=True)
    assert here["pattern"] == "C-*ECECECE" and here["vocab_size"] == 8192
    assert here["moe"]["held"] == tuple(range(8))
    assert not here["moe"]["train_router"]
    assert here["moe"]["pick_rows"] == 8192
    assert not lfm2_moe_lm_kwargs(
        dict(pub, tie_word_embeddings=False))["tie_embeddings"]
    for bad, name in ((dict(conv_bias=True), "conv_bias"),
                      (dict(norm_topk_prob=False), "norm_topk_prob"),
                      (dict(sliding_window=4096), "sliding_window"),
                      (dict(rope_parameters={"rope_theta": 1e6,
                                             "rope_type": "yarn"}),
                       "rope_type"),
                      (dict(layer_types=["conv", "sliding_attention"],
                            num_hidden_layers=2), "sliding_attention"),
                      (dict(num_hidden_layers=39), "num_hidden_layers")):
        with pytest.raises(ValueError, match=name):
            lfm2_moe_lm_kwargs(dict(pub, **bad))


def test_the_published_model_has_the_published_size(cut):
    """The builder's shapes at the PUBLISHED depth, experts and vocabulary
    give 23.84B parameters with the head tied (2.3B active), and the cut
    gives 469.3M; the same count at the rehearsal's sizes is what the
    builder builds."""
    def sizes(e, h, kv, k, dense, f, router, vocab):
        d = e // h
        return dict(conv=e * 3 * e + e * k + e * e,
                    attn=e * (h + 2 * kv) * d + h * d * e + 2 * d,
                    dense=3 * e * dense, router=e * router,
                    expert=3 * e * f, norms=2 * e, vocab=vocab * e + e)

    def count(z, types, dense, held):
        return sum((z["conv"] if t == "conv" else z["attn"]) + z["norms"]
                   + (z["dense"] if i < dense
                      else z["router"] + held * z["expert"])
                   for i, t in enumerate(types)) + z["vocab"]

    _, cfg = harness.load_cell(CELL)
    pub = cfg["published"]
    z = sizes(2048, 32, 8, 3, 11776, 1536, 64, pub["vocab_size"])
    assert round(z["conv"] / 1e6, 2) == 16.78
    assert round(z["attn"] / 1e6, 2) == 10.49
    assert round(z["dense"] / 1e6, 2) == 72.35
    assert round(z["expert"] / 1e6, 3) == 9.437
    whole = count(z, pub["layer_types"], pub["num_dense_layers"], 64)
    assert round(whole / 1e9, 2) == 23.84
    active = whole - 38 * 60 * z["expert"]
    assert round(active / 1e9, 1) == 2.3
    here = count(dict(z, vocab=8192 * 2048 + 2048), cfg["layer_types"],
                 cfg["num_dense_layers"], 8)
    assert here == 469_284_992 and round(here / 1e6, 1) == 469.3
    _, small, model = cut
    s = sizes(small["hidden_size"], small["num_attention_heads"],
              small["num_key_value_heads"], small["conv_L_cache"],
              small["intermediate_size"], small["moe_intermediate_size"],
              small["published"]["num_experts"], small["vocab_size"])
    built = sum(int(np.prod(leaf.shape)) for leaf in
                jax.tree_util.tree_leaves(model.parameter_tree()))
    assert built == count(s, small["layer_types"],
                          small["num_dense_layers"], small["num_experts"])


def test_block_remat_keeps_the_in_projection_of_a_c_block(cut, monkeypatch):
    """The backward of a rematerialised ``C`` block traces as many products
    as the plain one (in, out, and their four gradients) and one more
    under a policy that keeps nothing: the in-projection's output stays on
    ``ops.remat``'s kept list."""
    from bigdl_tpu.nn import hybrid
    from bigdl_tpu.ops import remat
    assert remat.SHORT_CONV_IN_PROJ in remat.BLOCK_SAVED_NAMES
    dec = nn.HybridDecoder("C", 32, short_conv=dict(kernel=3))
    x = _normal(_rng(8), 2, 24, 32)

    def dots(on):
        def f(p, x):
            return jnp.sum(jnp.square(functional_apply(
                dec, p, dec.buffer_tree(), x, training=True)[0]))
        dec.remat_blocks = on
        return str(jax.make_jaxpr(jax.grad(f))(
            dec.parameter_tree(), x)).count("dot_general")

    kept = dots(True)
    assert kept == dots(False)
    monkeypatch.setattr(hybrid, "block_remat_policy",
                        lambda through=None:
                        jax.checkpoint_policies.nothing_saveable)
    assert dots(True) == kept + 1


# --------------------------------------------------- the cell and its gate

#: the block limits in float32, where the sound system is the reference
_TIGHT = {kind: {"out_rtol": 1e-4, "grad_rtol": 1e-4}
          for kind in ("conv", "conv_local", "attention")}


@pytest.fixture(scope="module")
def controlled():
    """``benchmark.controls`` at the rehearsal size in float32 (there the
    sound system is the reference to 1e-6)."""
    from benchmark import controls
    cell, cfg = harness.load_cell(CELL, rehearse=True)
    cell = dict(cell, precision="fp32",
                reference=dict(cell["reference"], loss_rtol=1e-5,
                               grad_norm_rtol=1e-4, blocks=_TIGHT))
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


@pytest.mark.parametrize("fault", builder.CONV_FAULTS
                         + ("no_qk_norm", "no_rope"))
def test_the_mixer_blocks_alone_refuse_a_fault_of_a_mixer(cut, fault, capfd):
    """The block check (one convolution mixer, its local part alone and
    one attention mixer against the reference's at the cell's length) says
    not ok whatever the loss says: the builder then hands the train kind
    NaN for its two numbers."""
    cell, cfg, model = cut
    fp32 = dict(cell, precision="fp32", reference=dict(
        cell["reference"], blocks=_TIGHT))
    builder.reference_batch(cfg, fp32, 3)
    sound = builder.mixer_blocks(model)
    assert set(sound) == {"conv", "conv_local", "attention"}
    assert all(r["out"] < 1e-5 and r["grad"] < 1e-5 for r in sound.values())
    assert builder._gated((1.0, 2.0), model) == (1.0, 2.0)
    with builder.planted(model, fault):
        read = builder.mixer_blocks(model)
        gated = builder._gated((1.0, 2.0), model)
    hit = {"conv", "conv_local"} if fault in builder.CONV_FAULTS \
        else {"attention"}
    for kind, r in read.items():
        assert (r["out"] > 1e-2) == (kind in hit), (kind, r)
    assert np.isnan(gated).all()
    # a block the limits do not name is no pass
    builder.reference_batch(cfg, dict(fp32, reference=dict(
        fp32["reference"], blocks={"conv": _TIGHT["conv"]})), 3)
    assert np.isnan(builder._gated((1.0, 2.0), model)).all()
    builder.reference_batch(cfg, fp32, 3)
    assert "benchmark detail mixer_blocks: " in capfd.readouterr().err


def test_taps_reversed_is_the_convolution_that_looks_ahead(cut):
    """The control's name is what it does: under it an input changed at
    position t moves the outputs BEFORE t and none after."""
    _, _, model = cut
    mixer = next(m for m in model.modules() if isinstance(m, nn.ShortConv))
    u = _normal(_rng(3), 1, 12, mixer.embed_dim)
    with builder.planted(model, "taps_reversed"):
        y = mixer.forward(u)
        moved = mixer.forward(u.at[:, 5].add(1.0))
    delta = np.abs(np.asarray(moved - y)).max(-1)[0]
    assert (delta[3:6] > 1e-5).all() and not delta[6:].any() \
        and not delta[:3].any()


def test_a_planted_fault_is_taken_out_again(cut):
    from benchmark.kinds import train as kind
    _, _, model = cut
    system, local = kind.system_loss_and_grad_norm, nn.ShortConv._local
    head = list(model.modules())[-1]
    table = head.embed_ref

    def state():
        return ([(m.qk_norm, m.rope) for m in model.modules()
                 if isinstance(m, nn.MultiHeadAttention)],
                [m.score for m in model.modules() if isinstance(m, MoE)],
                nn.ShortConv._local is local, head.embed_ref is table)

    before = state()
    for fault in builder.FAULTS:
        with builder.planted(model, fault):
            assert kind.system_loss_and_grad_norm is not system
            assert fault == "reference_bf16" or state() != before
    assert state() == before and kind.system_loss_and_grad_norm is system
    assert before == ([(True, True)], ["sigmoid"] * 4, True, True)
    with pytest.raises(ValueError):
        with builder.planted(model, "no_such_fault"):
            pass


def test_placement_relabels_the_routers_and_changes_no_layer(cut, capfd):
    """As the Trinity cell's: only the router matrices and the tables
    filled from them differ from the same seed built without the placement,
    by the deal's permutation of the routers' columns."""
    cell, cfg, model = cut
    plain = builder.build({k: v for k, v in cfg.items()
                           if k != "placement"}, 3)
    capfd.readouterr()
    placed = builder.build(cfg, 3)
    line = [ln for ln in capfd.readouterr().err.splitlines()
            if ln.startswith("benchmark detail placement: ")]
    assert len(line) == 1
    detail = json.loads(line[0].split(": ", 1)[1])
    assert len(detail["held_picks"]) == 4
    a = {jax.tree_util.keystr(k): np.asarray(v) for k, v in
         jax.tree_util.tree_leaves_with_path(plain.parameter_tree())}
    b = {jax.tree_util.keystr(k): np.asarray(v) for k, v in
         jax.tree_util.tree_leaves_with_path(placed.parameter_tree())}
    routers = sorted(k for k in a if "gate_weight" in k)
    assert len(routers) == 4
    for k in a:
        if k not in routers:
            np.testing.assert_array_equal(a[k], b[k])
    for k in routers:
        assert not np.array_equal(a[k], b[k])
        np.testing.assert_array_equal(np.sort(a[k], 1), np.sort(b[k], 1))
    with pytest.raises(ValueError):
        builder.build(dict(cfg, placement=dict(cfg["placement"],
                                               by="guess")), 3)
    with pytest.raises(ValueError):
        builder.build(dict(cfg, training=dict(cfg["training"],
                                              router_picks="hash")), 3)


def test_the_balanced_deal_gives_every_chip_its_room_and_the_least_load():
    """``balanced_deal``: a permutation, as many experts a chip as the
    others, the hottest expert on chip 0, each expert to the chip whose
    load so far is least; on a skewed load its fullest chip holds less
    than the round-by-round deal's."""
    from benchmark.builders.afmoe import deal
    order = builder.balanced_deal([9, 8, 1, 1, 1, 1, 1, 1], 2)
    # 9 -> chip 0; 8 and a 1 -> chip 1; then by turns, a tie to chip 0
    assert order.tolist() == [0, 3, 5, 7, 1, 2, 4, 6]
    load = np.asarray([4096.0] * 4 + [2048.0] * 4 + list(
        _rng(1).uniform(200, 700, 56)))
    for chips in (8, 4):
        mine, theirs = builder.balanced_deal(load, chips), deal(load, chips)
        assert sorted(mine) == list(range(64)) and mine[0] == 0
        n = 64 // chips
        held = lambda o: [load[o[c * n:(c + 1) * n]].sum()
                          for c in range(chips)]
        assert max(held(mine)) < max(held(theirs))
        assert max(held(mine)) - min(held(mine)) \
            < 0.5 * (max(held(theirs)) - min(held(theirs)))


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
            "benchmark detail mixer_blocks: ")).split(": ", 1)[1])
    assert blocks["ok"] and set(blocks) >= {"conv", "conv_local",
                                            "attention"}


def test_a_traced_step_counts_the_mixers_and_one_full_flash_call(monkeypatch):
    """On a TPU backend, at a sequence the kernels take: tracing the
    stack's training loss at batch 2 counts ``form=xla`` once for each
    convolution mixer and ``form=full`` once for the attention layer, and
    the jaxpr holds the flash kernels under their names."""
    from bigdl_tpu.interop.hf import lfm2_moe_lm_kwargs
    from bigdl_tpu.models.hybrid import build_hybrid_lm
    from bigdl_tpu.ops import flash_attention as fa
    from bigdl_tpu.parallel import expert
    from bigdl_tpu.telemetry import get_registry, instruments
    _, cfg = harness.load_cell(CELL, rehearse=True)
    cfg = dict(builder.hf_config(cfg), hidden_size=128,
               num_attention_heads=2, num_key_value_heads=1)
    model = build_hybrid_lm(**lfm2_moe_lm_kwargs(cfg, held_experts=(0, 1)))
    dec = builder.decoder_of(model)
    dec.remat_blocks = True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(expert, "takes_kernel", lambda *a: False)
    real = fa._flash_lse
    monkeypatch.setattr(fa, "_flash_lse", lambda *a: real(
        *a[:7], True, a[8]))            # the kernels in the interpreter
    x = jnp.zeros((2, 1024, cfg["hidden_size"]))
    ins = instruments(get_registry())
    conv0 = ins.short_conv_total.labels(form="xla").value
    flash0 = {f: ins.flash_attention_total.labels(form=f).value
              for f in ("band", "full")}

    def f(p):
        return jnp.sum(functional_apply(dec, p, dec.buffer_tree(), x,
                                        training=True)[0])

    text = str(jax.make_jaxpr(jax.grad(f))(dec.parameter_tree()))
    assert ins.short_conv_total.labels(form="xla").value - conv0 == 4
    assert {f: ins.flash_attention_total.labels(form=f).value - flash0[f]
            for f in flash0} == {"band": 0, "full": 1}
    for name in ("flash_fwd", "flash_bwd_dkv"):
        assert f"name={name}" in text, name
