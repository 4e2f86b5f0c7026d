"""The Olmo-Hybrid family (gated delta-rule linear attention in three of
every four layers, full MHA with a q/k norm over the whole projection and
no positional term in the fourth, dense SwiGLU feed-forwards, every block
normed on its OUTPUT alone, an untied head) against its plain reference
(``benchmark/reference/olmo_hybrid.py``), at small sizes on the CPU; the
share arithmetic of the attention layer's heads; the configuration's
sizes; its cell's rehearsal and negative controls."""

import json
import logging
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark.builders import olmo_hybrid as builder
from benchmark.reference import olmo_hybrid as reference
from bigdl_tpu import nn
from bigdl_tpu.nn.module import functional_apply

logging.getLogger("bigdl_tpu.optim").setLevel(logging.WARNING)

CELL = "olmo-hybrid-7b-train-s8192"


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


@pytest.fixture(scope="module")
def cut():
    cell, cfg = harness.load_cell(CELL, rehearse=True)
    return cell, cfg, builder.build(cfg, 3)


# ------------------------------------------------------- the configuration

def test_the_rehearsal_keeps_what_the_cell_is_about(cut):
    """One whole period, three linear layers to one full one, each over a
    dense block; every block normed on its output alone; the q/k norm over
    the whole projection, no rotation; an untied head; block remat."""
    cell, cfg, model = cut
    dec = builder.decoder_of(model)
    assert dec.pattern == "D-D-D-*-" and dec.remat_blocks
    real_cell, real = harness.load_cell(CELL)
    assert (real_cell["batch_size"], real_cell["seq_len"]) == (1, 8192)
    assert real["layer_types"] == ["linear_attention"] * 3 \
        + ["full_attention"] == cfg["layer_types"]
    pub = real["published"]
    for key in ("num_attention_heads", "num_key_value_heads",
                "linear_num_key_heads", "linear_num_value_heads"):
        assert 2 * real[key] == pub[key] == 30, key     # heads 2-way
    assert 8 * real["vocab_size"] == pub["vocab_size"]  # vocabulary 8-way
    assert 8 * real["num_hidden_layers"] == pub["num_hidden_layers"]
    assert real["head_dim"] * pub["num_attention_heads"] \
        == real["hidden_size"]
    for i in range(dec.num_layers):
        assert sorted(dec._modules[f"layer{i}"]._modules) \
            == ["mixer", "norm_post"]
    deltas = [m for m in model.modules() if isinstance(m, nn.GatedDeltaNet)]
    assert len(deltas) == 3 and all(
        (m.conv_kernel, m.allow_neg_eigval, m.chunk_size, m.norm_eps)
        == (4, True, 64, 1e-6) for m in deltas)
    att, = [m for m in model.modules()
            if isinstance(m, nn.MultiHeadAttention)]
    assert att.qk_norm == "projection" and not att.rope and att.causal
    assert att.num_kv_heads == att.num_heads and not att.with_bias
    assert att.q_norm.weight.shape == (att.num_heads * att.head_dim,)
    assert type(list(model.modules())[-1]) is nn.LMHead


def _count(cfg, heads, layers, vocab):
    """Parameters of the family from its published shapes, ``heads`` of
    both mixer kinds held."""
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    d, k = cfg["head_dim"], cfg["linear_conv_kernel_dim"]
    linear = e * heads * (2 * dk + 2 * dv + 2) + heads * (2 * dk + dv) * k \
        + 2 * heads + dv + heads * dv * e
    full = e * 3 * heads * d + 2 * heads * d + heads * d * e
    mlp = 3 * e * f
    return sum((linear if kind == "linear_attention" else full) + mlp
               + 2 * e for kind in layers) + 2 * vocab * e + e


def test_the_share_has_the_size_the_file_states(cut):
    """The count from shapes against the built rehearsal model; at the
    file's sizes it is the 766.2M the deployment names, at the published
    ones the model's 7.4B."""
    _, small, model = cut
    built = sum(int(np.prod(leaf.shape)) for leaf in
                jax.tree_util.tree_leaves(model.parameter_tree()))
    assert built == _count(small, small["num_attention_heads"],
                           small["layer_types"], small["vocab_size"])
    _, real = harness.load_cell(CELL)
    held = _count(real, 15, real["layer_types"], real["vocab_size"])
    assert round(held / 1e6, 1) == 766.2
    assert "766.2M" in real["deployment"]
    whole = _count(real, 30, real["layer_types"] * 8,
                   real["published"]["vocab_size"])
    assert 7.3e9 < whole < 7.5e9


def test_a_configuration_the_builder_does_not_map_is_refused(cut):
    _, cfg, _ = cut
    for key, value in (("attention_bias", True),
                       ("tie_word_embeddings", True),
                       ("rope_parameters", {"rope_theta": 10000.0}),
                       ("linear_num_key_heads", 1)):
        with pytest.raises(ValueError):
            builder.build(dict(cfg, **{key: value}), 3)
    with pytest.raises(ValueError):
        builder.build(dict(cfg, training={"remat": "conv"}), 3)
    with pytest.raises(ValueError):
        nn.MultiHeadAttention(32, 2, qk_norm="head")


# ------------------------------------------------- against the reference

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
def test_the_loss_and_every_gradient_leaf_match_the_reference(remat):
    """The program's own training loss in float32 against the plain
    reference on seeded weights at batch 2: the loss, and each leaf of the
    gradient under the reference's names, with block remat on and off."""
    from bigdl_tpu.ops.precision import DtypePolicy
    cell, cfg = harness.load_cell(CELL, rehearse=True)
    cell = dict(cell, reference=dict(cell["reference"], batch=2))
    cfg = dict(cfg, training=dict(cfg["training"], remat=remat))
    model = builder.build(cfg, 3)
    assert builder.decoder_of(model).remat_blocks == (remat == "block")
    loss, grads, data, labels = _system_grads(model, cfg, cell, DtypePolicy())
    assert data.shape[0] == 2
    ids, tgt = (jnp.asarray(t, jnp.int32) - 1 for t in (data, labels))
    want, want_g = jax.jit(jax.value_and_grad(
        lambda q: reference.loss(q, ids, tgt, cfg)))(
            builder.reference_params(model))
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    got_g = builder.named(grads, builder.decoder_of(model).pattern)
    assert sorted(got_g) == sorted(want_g)
    assert len(got_g) == len(jax.tree_util.tree_leaves(grads))
    for k in want_g:
        assert np.asarray(want_g[k]).any(), k
        _close(got_g[k], want_g[k], tol=2e-4)


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
    assert abs(b_gn - r_gn) < 0.1 * r_gn


@pytest.mark.parametrize("layer,kinds", [(0, "D-"), (3, "*-")])
def test_each_kind_of_layer_matches_the_reference(cut, layer, kinds):
    """One layer's output (its mixer block, then its dense block, each
    normed on its output) on a random stream of batch 2."""
    cell, cfg, model = cut
    dec = builder.decoder_of(model)
    assert dec.pattern[2 * layer:2 * layer + 2] == kinds
    x = _normal(_rng(layer), 2, 40, cfg["hidden_size"])
    got = dec._modules[f"layer{2 * layer + 1}"].forward(
        dec._modules[f"layer{2 * layer}"].forward(x))
    want = reference.layer(builder.reference_params(model), layer, x, cfg)
    _close(got, want, tol=1e-5)


def test_the_reference_in_bf16_is_the_tolerances_second_reading(cut):
    cell, cfg, model = cut
    data, labels = builder.reference_batch(cfg, cell, 3)
    ids, tgt = (jnp.asarray(t, jnp.int32) - 1 for t in (data, labels))
    p = builder.reference_params(model)
    both = jax.jit(lambda p, dtype: reference.loss_and_grad_norm(
        p, ids, tgt, cfg, dtype), static_argnums=1)
    (true, gn), (low, gn_low) = both(p, jnp.float32), both(p, jnp.bfloat16)
    assert 0 < abs(float(low) - float(true)) < 0.02 * float(true)
    assert 0 < abs(float(gn_low) - float(gn)) < 0.2 * float(gn)


def test_the_attention_shares_add_up_once_given_the_whole_mean_square():
    """The cut of ``configs/olmo-hybrid-7b.json`` tied to the model, for
    the full-attention layer: 4 heads dealt over two chips, heads 0-1 and
    2-3, each chip's ``nn.MultiHeadAttention`` holding its heads' rows of
    q, k and v, its columns of the two norm weights and of the
    out-projection. The q/k norm's mean square over the WHOLE projection
    is the one statistic of this model that spans heads: a deployment
    all-reduces one scalar a token and projection (here: the mean of the
    two shares' own mean squares, handed to both). With it the two
    outputs add up to the uncut reference's whole layer; with each share's
    OWN mean square, what the one-chip cell computes, they do not."""
    from bigdl_tpu.nn.attention import RMSNorm
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(13)
    h, d, e = 4, 8, 32
    cfg = dict(num_attention_heads=h, num_key_value_heads=h, head_dim=d,
               rms_norm_eps=1e-6)
    kw = dict(head_dim=d, with_bias=False, causal=True, qk_norm="projection")
    whole = nn.MultiHeadAttention(e, h, **kw)
    rng = _rng(3)
    whole.q_norm.weight = 1.0 + 0.3 * _normal(rng, h * d)   # not all ones
    whole.k_norm.weight = 1.0 + 0.3 * _normal(rng, h * d)
    p = whole.parameter_tree()
    u = _normal(rng, 2, 24, e)
    want = reference.attention(builder.attention_named(p), "", u, cfg)
    _close(_apply(whole, p, u), want, tol=1e-5)

    def share_of(first):
        cols = np.arange(first * d, (first + 2) * d)
        rows = np.concatenate([cols, h * d + cols, 2 * h * d + cols])
        share = nn.MultiHeadAttention(e, 2, **kw)
        mine = dict(in_proj_weight=p["in_proj_weight"][rows],
                    out_proj_weight=p["out_proj_weight"][:, cols],
                    q_norm={"weight": p["q_norm"]["weight"][cols]},
                    k_norm={"weight": p["k_norm"]["weight"][cols]})
        return share, mine

    shares = [share_of(0), share_of(2)]
    half = dict(cfg, num_attention_heads=2, num_key_value_heads=2)
    own = [reference.qk_mean_squares(builder.attention_named(mine), "", u,
                                     half) for _, mine in shares]
    all_reduced = tuple(sum(ms) / 2 for ms in zip(*own))

    def given(which):
        """``RMSNorm.update_output`` with the mean square handed in."""
        def forward(norm, x):
            return x * jax.lax.rsqrt(all_reduced[which] + norm.eps) \
                * norm.weight
        return forward

    alone = sum(_apply(share, mine, u) for share, mine in shares)
    assert np.abs(np.asarray(alone - want)).max() \
        > 1e-3 * np.abs(np.asarray(want)).max()
    real = RMSNorm.update_output
    total = 0.0
    try:
        for share, mine in shares:
            # q's norm runs first, then k's (``_heads_in``)
            calls = iter((given(0), given(1)))
            RMSNorm.update_output = lambda norm, x: next(calls)(norm, x)
            total = total + _apply(share, mine, u)
    finally:
        RMSNorm.update_output = real
    _close(total, want, tol=1e-5)
    # and the reference's own share, given the same statistic
    ref = sum(reference.attention(builder.attention_named(mine), "", u, half,
                                  whole=all_reduced) for _, mine in shares)
    _close(ref, want, tol=1e-5)


# --------------------------------------------------------------- block remat

def test_block_remat_keeps_the_in_projection_of_a_d_block(monkeypatch):
    """The backward of a rematerialised ``D`` block traces as many products
    of the mixer's width as the plain one and one more under a policy that
    keeps nothing: the in-projection's output stays on ``ops.remat``'s kept
    list. Either way ONE trace of the block counts its recurrence once:
    the backward's second forward is jax's replay of the traced jaxpr."""
    from bigdl_tpu.nn import hybrid
    from bigdl_tpu.ops import remat
    from bigdl_tpu.telemetry import get_registry, instruments
    assert remat.DELTA_IN_PROJ in remat.BLOCK_SAVED_NAMES
    dec = nn.HybridDecoder("D", 32, delta=dict(
        num_heads=2, key_head_dim=8, value_head_dim=16, chunk_size=8),
        post_norm=True, pre_norm=False)
    wide = dec.layer0.mixer.in_proj_weight.shape[0]
    x = _normal(_rng(8), 2, 24, 32)
    rules = instruments(get_registry()).delta_rule_total.labels(
        form="chunked")

    def in_projections(on):
        def f(p, x):
            return jnp.sum(jnp.square(functional_apply(
                dec, p, dec.buffer_tree(), x, training=True)[0]))
        dec.remat_blocks = on
        before = rules.value
        jaxpr = jax.make_jaxpr(jax.grad(f))(dec.parameter_tree(), x)
        assert rules.value == before + 1
        return len(re.findall(rf":f32\[2,24,{wide}\] = dot_general",
                              str(jaxpr)))

    assert in_projections(True) == in_projections(False) == 1
    monkeypatch.setattr(hybrid, "block_remat_policy",
                        lambda through=None:
                        jax.checkpoint_policies.nothing_saveable)
    assert in_projections(True) == 2


# --------------------------------------------------- the cell and its gate

#: the block limits in float32, where the sound system is the reference
_TIGHT = {kind: {"out_rtol": 1e-4, "grad_rtol": 2e-4}
          for kind in ("delta", "delta_rule", "attention")}


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
    In float32 at the rehearsal size every one of them fails it."""
    got, sound = controlled[fault], controlled["sound"]
    assert got["reference_loss"] == sound["reference_loss"]
    assert got["system_loss"] != sound["system_loss"]
    assert not got["ok"]


#: the blocks that read each fault of a mixer (the block's norm and the
#: whole model's numbers are not theirs to see)
_HIT = {"beta_not_doubled": {"delta"}, "no_decay": {"delta", "delta_rule"},
        "no_delta_term": {"delta", "delta_rule"},
        "k_not_normalised": {"delta"}, "taps_reversed": {"delta"},
        "no_output_gate": {"delta"}, "no_qk_norm": {"attention"},
        "pre_norm_block": set()}


@pytest.mark.parametrize("fault", sorted(_HIT))
def test_the_mixer_blocks_alone_refuse_a_fault_of_a_mixer(cut, fault, capfd):
    """The block check (one linear mixer, the recurrence alone and the
    attention mixer against the reference's at the cell's length) says not
    ok whatever the loss says: the builder then hands the train kind NaN
    for its two numbers. A fault of the BLOCK around the mixers (the norm
    on the wrong side) is the whole model's to see."""
    cell, cfg, model = cut
    fp32 = dict(cell, precision="fp32", reference=dict(
        cell["reference"], blocks=_TIGHT))
    builder.reference_batch(cfg, fp32, 3)
    sound = builder.mixer_blocks(model)
    assert set(sound) == {"delta", "delta_rule", "attention"}
    assert all(r["out"] < 2e-5 and r["grad"] < 1e-4 for r in sound.values())
    assert builder._gated((1.0, 2.0), model) == (1.0, 2.0)
    with builder.planted(model, fault):
        read = builder.mixer_blocks(model)
        gated = builder._gated((1.0, 2.0), model)
    for kind, r in read.items():
        assert (r["out"] > 1e-2) == (kind in _HIT[fault]), (kind, r)
    assert np.isnan(gated).all() == bool(_HIT[fault])
    # a block the limits do not name is no pass
    builder.reference_batch(cfg, dict(fp32, reference=dict(
        fp32["reference"], blocks={"delta": _TIGHT["delta"]})), 3)
    assert np.isnan(builder._gated((1.0, 2.0), model)).all()
    builder.reference_batch(cfg, fp32, 3)
    assert "benchmark detail mixer_blocks: " in capfd.readouterr().err


def test_taps_reversed_is_the_convolution_that_looks_ahead(cut):
    """The control's name is what it does: under it an input changed at
    position t moves outputs BEFORE t (a sound mixer moves none)."""
    _, _, model = cut
    mixer = next(m for m in model.modules()
                 if isinstance(m, nn.GatedDeltaNet))
    u = _normal(_rng(3), 1, 12, mixer.embed_dim)
    with builder.planted(model, "taps_reversed"):
        y = mixer.forward(u)
        moved = mixer.forward(u.at[:, 5].add(1.0))
    delta = np.abs(np.asarray(moved - y)).max(-1)[0]
    assert (delta[2:5] > 1e-6).all() and not delta[:2].any()


def test_a_planted_fault_is_taken_out_again(cut):
    from benchmark.kinds import train as kind
    from bigdl_tpu.nn import gated_delta_net
    from bigdl_tpu.ops import delta_rule
    _, _, model = cut

    def state():
        return ([m.allow_neg_eigval for m in model.modules()
                 if isinstance(m, nn.GatedDeltaNet)],
                [m.qk_norm for m in model.modules()
                 if isinstance(m, nn.MultiHeadAttention)],
                nn.GatedDeltaNet._recurrence_inputs,
                nn.GatedDeltaNet._gated_norm, nn.HybridBlock.update_output,
                gated_delta_net.gated_delta_rule, delta_rule._wy)

    before, system = state(), kind.system_loss_and_grad_norm
    for fault in builder.FAULTS:
        with builder.planted(model, fault):
            assert kind.system_loss_and_grad_norm is not system
            assert fault == "reference_bf16" or state() != before
    assert state() == before and kind.system_loss_and_grad_norm is system
    assert before[:2] == ([True] * 3, ["projection"])
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
    blocks = json.loads(next(
        ln for ln in err.splitlines() if ln.startswith(
            "benchmark detail mixer_blocks: ")).split(": ", 1)[1])
    assert blocks["ok"] and set(blocks) >= {"delta", "delta_rule",
                                            "attention"}


def test_a_traced_step_counts_the_mixers_and_one_full_flash_call(monkeypatch):
    """On a TPU backend, at a sequence the kernels take: tracing the
    stack's training loss at batch 2 under block remat counts each linear
    mixer and its recurrence once and ``form=full`` once for the attention
    layer, and the jaxpr holds the flash kernels under their names."""
    from bigdl_tpu.models.hybrid import build_hybrid_lm
    from bigdl_tpu.ops import flash_attention as fa
    from bigdl_tpu.telemetry import get_registry, instruments
    _, cfg = harness.load_cell(CELL, rehearse=True)
    cfg = dict(cfg, hidden_size=128, num_attention_heads=2,
               num_key_value_heads=2, head_dim=128)
    model = build_hybrid_lm(**builder.lm_kwargs(cfg))
    dec = builder.decoder_of(model)
    dec.remat_blocks = True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    real = fa._flash_lse
    monkeypatch.setattr(fa, "_flash_lse", lambda *a: real(
        *a[:7], True, a[8]))            # the kernels in the interpreter
    x = jnp.zeros((2, 1024, cfg["hidden_size"]))
    ins = instruments(get_registry())
    count = lambda: (ins.gated_delta_net_total.labels().value,
                     ins.delta_rule_total.labels(form="chunked").value,
                     ins.flash_attention_total.labels(form="full").value,
                     ins.flash_attention_total.labels(form="band").value)
    before = count()

    def f(p):
        return jnp.sum(functional_apply(dec, p, dec.buffer_tree(), x,
                                        training=True)[0])

    text = str(jax.make_jaxpr(jax.grad(f))(dec.parameter_tree()))
    assert tuple(a - b for a, b in zip(count(), before)) == (3, 3, 1, 0)
    for name in ("flash_fwd", "flash_bwd_dkv"):
        assert f"name={name}" in text, name
