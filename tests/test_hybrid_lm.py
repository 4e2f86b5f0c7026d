"""The pattern-built hybrid LM (Mamba-2 / mixture of experts / attention)
against its plain reference (``benchmark/reference/nemotron_h.py``: per-token
recurrence, dense expert loop, plain softmax attention), at small sizes on
the CPU; the dropless held-expert layer's share arithmetic; and that what the
existing models build is what the parent built."""

import hashlib
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, timeline
from benchmark.builders import nemotron_h as builder
from benchmark.reference import nemotron_h as reference
from bigdl_tpu import nn
from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.ops import ssd_scan as scan_module
from bigdl_tpu.ops.ssd_scan import ssd_scan
from bigdl_tpu.parallel import expert
from bigdl_tpu.parallel.expert import MoE, expert_param_specs
from bigdl_tpu.utils.rng import manual_seed

logging.getLogger("bigdl_tpu.optim").setLevel(logging.WARNING)

CFG = dict(hidden_size=32, mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
           ssm_state_size=8, conv_kernel=4, chunk_size=8,
           layer_norm_epsilon=1e-5, num_experts_per_tok=3,
           routed_scaling_factor=2.5, norm_topk_prob=True,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16)


def _rng(seed=0):
    return np.random.default_rng(seed)


def _normal(rng, *shape, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32) * scale)


def _digest(tree):
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        h.update(np.asarray(leaf, np.float32).tobytes())
    return h.hexdigest()[:16]


def _close(a, b, tol=2e-4):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol * np.abs(b).max())


def _apply(module, params, x, training=True):
    return functional_apply(module, params, module.buffer_tree(), x,
                            training=training)[0]


# ------------------------------------------------------------------ the scan

def _scan_inputs(length, seed=0):
    rng = _rng(seed)
    x = _normal(rng, 2, length, 4, 8)
    dt = jax.nn.softplus(_normal(rng, 2, length, 4))
    a = -jnp.exp(_normal(rng, 4))
    b = _normal(rng, 2, length, 2, 8)
    c = _normal(rng, 2, length, 2, 8)
    return x, dt, a, b, c


@pytest.mark.parametrize("length", [32, 37])
@pytest.mark.parametrize("chunk", [4, 8, 16, 64])
def test_chunked_scan_matches_the_per_token_recurrence(chunk, length):
    """Forward and every gradient, across chunk sizes, also where the
    sequence is no whole number of chunks (37) or shorter than one (64)."""
    args = _scan_inputs(length)
    probe = _normal(_rng(9), 2, length, 4, 8)

    def chunked(*a):
        return jnp.sum(ssd_scan(*a, chunk=chunk) * probe)

    def per_token(*a):
        return jnp.sum(reference.selective_scan(*a) * probe)

    _close(ssd_scan(*args, chunk=chunk), reference.selective_scan(*args))
    got = jax.grad(chunked, argnums=range(5))(*args)
    want = jax.grad(per_token, argnums=range(5))(*args)
    for g, w in zip(got, want):
        _close(g, w)


def _tile_inputs(length, dtype, seed=0, heads=4, groups=2, head_dim=64,
                 state=128):
    """The cell's tile (chunk 128, state 128, head 64) at two groups of two
    heads; decays fast and slow, operands in ``dtype``."""
    rng = _rng(seed)
    x = _normal(rng, 1, length, heads, head_dim).astype(dtype)
    dt = jax.nn.softplus(_normal(rng, 1, length, heads) - 2.0)
    a = -jnp.exp(_normal(rng, heads, scale=0.5))
    b = _normal(rng, 1, length, groups, state, scale=0.3).astype(dtype)
    c = _normal(rng, 1, length, groups, state, scale=0.3).astype(dtype)
    return x, dt, a, b, c


def _kernel_form(*args):
    return scan_module._ssd_kernel(*args, 128, interpret=True)


def _xla_form(*args):
    return scan_module._ssd_chunked(*args, 128)


@pytest.fixture(scope="module")
def tile_results():
    """{(dtype name, length): forward and five gradients of the kernel form
    (Pallas' interpreter), of the XLA form and of the per-token recurrence
    in float32 on the same (rounded) inputs}, computed once a case."""
    cache = {}

    def get(dtype, length):
        key = (jnp.dtype(dtype).name, length)
        if key not in cache:
            args = _tile_inputs(length, dtype)
            probe = _normal(_rng(9), *args[0].shape)
            wide = [t.astype(jnp.float32) for t in args]
            out = {}
            for name, form, inp in (
                    ("kernel", _kernel_form, args),
                    ("xla", _xla_form, args),
                    ("recurrence", reference.selective_scan, wide)):
                def probed(*t, form=form):
                    return jnp.sum(form(*t).astype(jnp.float32) * probe)
                out[name] = (form(*inp),) + jax.grad(
                    probed, argnums=range(5))(*inp)
            cache[key] = out
        return cache[key]
    return get


@pytest.mark.parametrize("oracle", ["xla", "recurrence"])
@pytest.mark.parametrize("what", ["y", "dx", "ddt", "da", "dB", "dC"])
@pytest.mark.parametrize("length", [256, 300])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_scan_kernels_match_the_xla_form_and_the_recurrence(
        tile_results, dtype, tol, length, what, oracle):
    """The four Mosaic kernels (Pallas' interpreter on a CPU) at the cell's
    tile, a whole number of chunks (256) and not (300): the forward and
    each of the five gradients against the XLA form, which they replace on
    the chip, and against the per-token recurrence."""
    got = tile_results(dtype, length)
    i = ["y", "dx", "ddt", "da", "dB", "dC"].index(what)
    assert got["kernel"][i].dtype == got["xla"][i].dtype
    _close(np.asarray(got["kernel"][i], np.float32),
           np.asarray(got[oracle][i], np.float32), tol=tol)


def test_scan_kernels_run_the_carry_that_is_planted(monkeypatch):
    """The carry stays a seam: with ``_chunk_states`` replaced by name (the
    ``bf16_scan_state`` control) the kernel form runs it, forward and
    backward, and its numbers move (from the third chunk on: the second
    starts from one chunk's own state, which the read-out rounds anyway)."""
    args = _tile_inputs(512, jnp.bfloat16)

    def loss(*t):
        return jnp.sum(_kernel_form(*t).astype(jnp.float32) ** 2)

    sound = jax.grad(loss, argnums=(0, 2))(*args)
    monkeypatch.setattr(scan_module, "_chunk_states",
                        builder._bf16_chunk_states)
    planted = jax.grad(loss, argnums=(0, 2))(*args)
    for s_, p_ in zip(sound, planted):
        assert np.isfinite(np.asarray(p_, np.float32)).all()
        assert np.abs(np.asarray(s_, np.float32)
                      - np.asarray(p_, np.float32)).max() > 0


def _scan_with_bf16_state(x, dt, a, b, c):
    """The per-token recurrence with the state ROUNDED to bf16 after every
    token: what carrying it in the compute dtype would give."""
    h = x.shape[2]
    b, c = jnp.repeat(b, h, axis=2), jnp.repeat(c, h, axis=2)

    def token(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * a)[..., None, None]
                 * state.astype(jnp.float32)
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
                 ).astype(jnp.bfloat16)
        return state, jnp.einsum("bhpn,bhn->bhp",
                                 state.astype(jnp.float32), c_t)

    _, y = jax.lax.scan(
        token, jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:],
                         jnp.bfloat16),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


@pytest.mark.parametrize("form,head_dim,state,chunk", [
    ("chunked", 8, 8, 64), ("kernel", 64, 128, 128)])
def test_scan_accumulates_its_state_in_float32_under_bf16_operands(
        form, head_dim, state, chunk):
    """Slow decays over 2,048 tokens, bf16 x, B and C: small increments to
    a large state. Either form of the chunked scan stays within 1% of the
    float32 recurrence on the same (rounded) inputs; a state carried in bf16
    is several times further off (12x in the maximum, when written)."""
    rng = _rng(3)
    length = 2048
    x, b, c = (_normal(rng, 1, length, *shape).astype(jnp.bfloat16)
               for shape in ((2, head_dim), (1, state), (1, state)))
    dt = jnp.full((1, length, 2), 0.05, jnp.float32)
    a = jnp.asarray([-0.02, -0.05], jnp.float32)
    f32 = [t.astype(jnp.float32) for t in (x, b, c)]
    want = np.asarray(reference.selective_scan(f32[0], dt, a, *f32[1:]))
    if form == "kernel":
        got = scan_module._ssd_kernel(x, dt, a, b, c, chunk, interpret=True)
    else:
        got = ssd_scan(x, dt, a, b, c, chunk=chunk)
    assert got.dtype == jnp.bfloat16
    low = np.asarray(_scan_with_bf16_state(f32[0], dt, a, *f32[1:]))
    err = np.abs(np.asarray(got, np.float32) - want).max()
    err_low = np.abs(low - want).max()
    assert err < 0.01 * np.abs(want).max()
    assert err_low > 5 * err


@pytest.mark.parametrize("backend,chunk,head_dim,state,per_group,kernel", [
    ("tpu", 128, 64, 128, 8, True),     # the Nemotron cell
    ("tpu", 128, 128, 256, 1, True),    # a head that is a whole lane tile
    ("tpu", 128, 64, 128, 2, True),     # the tests' tile
    ("cpu", 128, 64, 128, 8, False),    # the cell's CPU rehearsal, tier-1
    ("gpu", 128, 64, 128, 8, False),
    ("tpu", 8, 8, 8, 2, False),         # tier-1's small shapes
    ("tpu", 64, 64, 128, 8, False),     # a chunk under a lane tile
    ("tpu", 256, 64, 128, 8, False),    # and one over it
    ("tpu", 128, 64, 128, 16, False),   # more heads a group than 8
    ("tpu", 128, 64, 16, 8, False),     # a state of 16
    ("tpu", 128, 32, 128, 8, False),    # a head of 32
    ("tpu", 128, 64, 128, 1, False),    # one head of 64: half a lane tile
])
def test_which_scans_take_the_kernels(backend, chunk, head_dim, state,
                                      per_group, kernel):
    """The path rule as the docstring states it: backend and shapes."""
    assert scan_module.takes_kernel(backend, chunk, head_dim, state,
                                    per_group) is kernel


def test_the_rehearsal_and_a_cpu_take_the_xla_form(monkeypatch):
    """The cell's rehearsal shapes take the XLA form even on a TPU, and on
    this CPU the cell's own tile does: ``form="chunked"`` counts it."""
    from bigdl_tpu.telemetry import get_registry, instruments
    _, cfg = harness.load_cell("nemotron-3-nano-30b-a3b-train-s8192",
                               rehearse=True)
    assert not scan_module.takes_kernel(
        "tpu", cfg["chunk_size"], cfg["mamba_head_dim"],
        cfg["ssm_state_size"], cfg["mamba_num_heads"] // cfg["n_groups"])
    ins = instruments(get_registry())
    before = {f: ins.ssd_scan_total.labels(form=f).value
              for f in ("chunked", "kernel")}
    monkeypatch.setattr(scan_module, "_ssd_kernel", None)   # must not run
    ssd_scan(*_tile_inputs(128, jnp.float32), chunk=128)
    assert ins.ssd_scan_total.labels(form="chunked").value \
        == before["chunked"] + 1
    assert ins.ssd_scan_total.labels(form="kernel").value == before["kernel"]


# ---------------------------------------------------------------- the mixers

def _mamba():
    manual_seed(5)
    return nn.Mamba2(32, num_heads=4, head_dim=8, state_size=8, n_groups=2,
                     conv_kernel=4, chunk_size=8)


def _mamba_reference_params(tree):
    names = {"in_proj_weight": "in_proj.weight",
             "conv_weight": "conv1d.weight", "conv_bias": "conv1d.bias",
             "dt_bias": "dt_bias", "A_log": "A_log", "D": "D",
             "norm_weight": "norm.weight",
             "out_proj_weight": "out_proj.weight"}
    return {"m." + names[k]: v for k, v in tree.items()}


def test_mamba2_initialises_as_the_family_does():
    m = _mamba()
    a = np.exp(np.asarray(m.A_log))
    assert ((a >= 1.0) & (a <= 16.0)).all()
    dt = np.asarray(jax.nn.softplus(m.dt_bias))
    assert ((dt >= 1e-3 * 0.999) & (dt <= 0.1 * 1.001)).all()
    np.testing.assert_array_equal(np.asarray(m.D), 1.0)
    assert m.in_proj_weight.shape == (2 * 32 + 2 * 2 * 8 + 4, 32)
    assert m.conv_weight.shape == (32 + 2 * 2 * 8, 4)


@pytest.mark.parametrize("length", [24, 29])
def test_mamba2_forward_and_gradients_match_the_reference(length):
    m = _mamba()
    rng = _rng(1)
    u = _normal(rng, 2, length, 32)
    probe = _normal(rng, 2, length, 32)
    params = m.parameter_tree()
    # D and the gate matter: move them off their initial 1 / symmetric
    params["D"] = params["D"] + _normal(rng, 4, scale=0.3)

    def system(p, u):
        return jnp.sum(_apply(m, p, u) * probe)

    def plain(p, u):
        return jnp.sum(reference.mamba2(_mamba_reference_params(p), "m.", u,
                                        CFG) * probe)

    _close(_apply(m, params, u),
           reference.mamba2(_mamba_reference_params(params), "m.", u, CFG))
    gp, gu = jax.grad(system, argnums=(0, 1))(params, u)
    wp, wu = jax.grad(plain, argnums=(0, 1))(params, u)
    _close(gu, wu)
    for k in params:
        _close(gp[k], wp[k])


def _moe(held, n_experts=8, **kw):
    manual_seed(6)
    args = dict(hidden_size=24, n_experts=n_experts, k=3, activation="relu2",
                dispatch="held", held=held, bias=False, shared_hidden=40,
                route_scale=2.5)
    args.update(kw)
    return MoE(32, **args)


def _moe_reference_params(m, tree):
    return {"e.gate.weight": tree["gate_weight"],
            "e.gate.e_score_correction_bias": m.select_bias,
            "e.experts.up_proj": tree["w1"],
            "e.experts.down_proj": tree["w2"],
            "e.shared_experts.up_proj.weight": tree["shared_w1"],
            "e.shared_experts.down_proj.weight": tree["shared_w2"]}


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
def test_held_experts_forward_and_gradients_match_the_reference(
        held, block_rows, form, monkeypatch):
    """Sigmoid top-3 of 8, renormalised, x 2.5, relu^2 experts, a shared
    expert; rows in blocks of 8 (several blocks an expert, masked tails)
    and in one block, through the XLA loops and through the grouped
    kernels (in Pallas' interpreter: row tiles of 8, which their runs
    straddle, and one tile)."""
    _grouped_form(monkeypatch, form, block_rows)
    m = _moe(held)
    cfg = dict(CFG, n_routed_experts=len(held))
    rng = _rng(2)
    u = _normal(rng, 2, 21, 32)
    probe = _normal(rng, 2, 21, 32)
    params = m.parameter_tree()

    def plain_out(p, u):
        # the reference holds ids 0..n-1: renumber so that the held experts
        # come first in the router's columns
        order = list(held) + [e for e in range(8) if e not in held]
        q = dict(_moe_reference_params(m, p))
        q["e.gate.weight"] = q["e.gate.weight"][:, order]
        return reference.moe(q, "e.", u, cfg)[0]

    _close(_apply(m, params, u), plain_out(params, u))
    gp, gu = jax.grad(lambda p, u: jnp.sum(_apply(m, p, u) * probe),
                      argnums=(0, 1))(params, u)
    wp, wu = jax.grad(lambda p, u: jnp.sum(plain_out(p, u) * probe),
                      argnums=(0, 1))(params, u)
    _close(gu, wu)
    for k in params:
        _close(gp[k], wp[k])


def test_the_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST. 16 routed experts over 8 chips, 2 a chip: the routed
    parts the 8 shares compute, plus the shared expert counted once, equal
    what the uncut reference gives for the whole 16-expert layer."""
    whole = _moe(tuple(range(16)), n_experts=16)
    params = whole.parameter_tree()
    u = _normal(_rng(3), 3, 17, 32)
    ref = _moe_reference_params(whole, params)
    want, picked = reference.moe(ref, "e.", u,
                                 dict(CFG, n_routed_experts=16))
    shared_once = reference.relu2(u @ params["shared_w1"]) \
        @ params["shared_w2"]
    total = shared_once
    for chip in range(8):
        held = (2 * chip, 2 * chip + 1)
        share = _moe(held, n_experts=16)
        p = dict(params, w1=params["w1"][jnp.asarray(held)],
                 w2=params["w2"][jnp.asarray(held)])
        total = total + (_apply(share, p, u) - shared_once)
    _close(total, want)
    # and the router really spread the picks over the shares
    assert len(np.unique(np.asarray(picked))) > 8


def test_dropless_when_the_router_sends_every_token_to_one_expert():
    """Every token's first pick forced onto expert 0, held here: all T rows
    land on it (far over any capacity a balanced router would size) and
    each gets its full result."""
    m = _moe((0, 1), k=2)
    params = m.parameter_tree()
    gw = np.zeros((32, 8), np.float32)
    gw[0, 0] = 50.0
    gw[0, 5] = 40.0         # the second pick goes to an absent expert
    params["gate_weight"] = jnp.asarray(gw)
    u = jnp.abs(_normal(_rng(4), 1, 300, 32)) + 0.1
    out = _apply(m, params, u)
    s = jax.nn.sigmoid(u @ gw)
    w0 = 2.5 * s[..., 0] / (s[..., 0] + s[..., 5])
    want = w0[..., None] * (reference.relu2(u @ params["w1"][0])
                            @ params["w2"][0]) \
        + reference.relu2(u @ params["shared_w1"]) @ params["shared_w2"]
    _close(out, want)


@pytest.mark.parametrize("activation", ["gelu", "relu"])
def test_held_path_with_biases(activation):
    """The capacity paths' experts (biases, gelu or relu) under the held
    layer's router: the same ``_hidden`` / ``_project`` run them."""
    m = _moe((1, 3), bias=True, activation=activation, route_scale=1.0)
    params = m.parameter_tree()
    rng = _rng(5)
    for name, shape in (("b1", (2, 24)), ("b2", (2, 32)),
                        ("shared_b1", (40,)), ("shared_b2", (32,))):
        params[name] = _normal(rng, *shape, scale=0.1)
    act = jax.nn.gelu if activation == "gelu" else jax.nn.relu
    u = _normal(rng, 19, 32)
    out = _apply(m, params, u)
    s = jax.nn.sigmoid(u @ params["gate_weight"])
    w, picked = jax.lax.top_k(s, 3)
    w = w / jnp.sum(w, -1, keepdims=True)
    want = act(u @ params["shared_w1"] + params["shared_b1"]) \
        @ params["shared_w2"] + params["shared_b2"]
    for j, eid in enumerate((1, 3)):
        y = act(u @ params["w1"][j] + params["b1"][j]) @ params["w2"][j] \
            + params["b2"][j]
        mine = jnp.sum(jnp.where(picked == eid, w, 0.0), -1, keepdims=True)
        want = want + mine * y
    _close(out, want)
    gp = jax.grad(lambda p: jnp.sum(_apply(m, p, u) ** 2))(params)
    assert all(np.abs(np.asarray(gp[k])).max() > 0 for k in params)


def test_moe_refuses_what_it_cannot_mean():
    with pytest.raises(ValueError):
        MoE(8, 8, n_experts=4, held=(0, 1))             # held needs 'held'
    with pytest.raises(ValueError):
        MoE(8, 8, n_experts=4, dispatch="held", held=(0, 4))
    with pytest.raises(ValueError):
        MoE(8, 8, n_experts=4, shared_hidden=16)        # so does a shared one
    with pytest.raises(ValueError):
        MoE(8, 8, n_experts=4, route_scale=2.5)
    with pytest.raises(ValueError):
        MoE(8, 8, n_experts=4, activation="tanh")


def test_expert_param_specs_follow_the_layers_parameters():
    from jax.sharding import PartitionSpec as P
    old = expert_param_specs(MoE(8, 8, n_experts=4))
    assert old == {"gate_weight": P(), "w1": P("expert", None, None),
                   "b1": P("expert", None), "w2": P("expert", None, None),
                   "b2": P("expert", None)}
    new = expert_param_specs(_moe((0, 1)))
    assert set(new) == {"gate_weight", "w1", "w2", "shared_w1", "shared_w2"}
    assert new["w2"] == P("expert", None, None) and new["shared_w1"] == P()


# Parameter digest and loss of the capacity path as the commit before the
# held layer computed them (capacity_factor 1.0, so tokens drop).
_PARENT_MOE = {"params": "115bb208dbce75b2", "loss": 91.81510925292969}


def test_the_capacity_path_is_the_parents():
    manual_seed(7)
    m = MoE(16, 32, n_experts=4, k=2, capacity_factor=1.0, dispatch="sort")
    assert _digest(m.parameter_tree()) == _PARENT_MOE["params"]
    assert set(m.parameter_tree()) == {"gate_weight", "w1", "b1", "w2", "b2"}
    x = _normal(_rng(0), 3, 11, 16)
    loss = lambda p: jnp.sum(_apply(m, p, x) ** 2)
    v, g = jax.value_and_grad(loss)(m.parameter_tree())
    np.testing.assert_allclose(float(v), _PARENT_MOE["loss"], rtol=1e-6)
    assert np.isfinite(np.asarray(g["gate_weight"])).all()


def test_qwen_built_without_a_head_dim_is_the_parents_model():
    """``head_dim=None``: the same draws from the seed in the same order,
    the same shapes, so the parameters are the parent's to the bit."""
    from bigdl_tpu.interop.hf import qwen2_lm_kwargs
    from bigdl_tpu.models.transformer import build_lm
    cfg = dict(hidden_act="silu", hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=2, vocab_size=512,
               max_position_embeddings=512, rms_norm_eps=1e-6, rope_theta=1e6,
               tie_word_embeddings=True, use_sliding_window=False)
    manual_seed(11)
    lm = build_lm(**qwen2_lm_kwargs(cfg)).evaluate_mode()
    assert _digest(lm.parameter_tree()) == "d23c66e161900f35"
    att = lm[1].layer0.self_attn
    assert att.head_dim == 16 and att._e_q == 64
    assert att.in_proj_weight.shape == (64 + 2 * 32, 64)


def test_attention_with_its_own_head_dim_and_no_positional_term():
    manual_seed(9)
    att = nn.MultiHeadAttention(32, 4, with_bias=False, causal=True,
                                num_kv_heads=2, head_dim=16)
    assert att.in_proj_weight.shape == (4 * 16 + 2 * 2 * 16, 32)
    assert att.out_proj_weight.shape == (32, 64)
    u = _normal(_rng(7), 2, 13, 32)
    p = {"a.qkv_proj.weight": att.in_proj_weight,
         "a.o_proj.weight": att.out_proj_weight}
    _close(att.forward(u), reference.attention(p, "a.", u, CFG))


def _conv_stack(tie, seed=13):
    from bigdl_tpu.models.hybrid import build_hybrid_lm
    manual_seed(seed)
    np.random.seed(seed)
    return build_hybrid_lm(
        48, 32, "C-*ECE", tie_embeddings=tie, short_conv=dict(kernel=3),
        mlp=dict(hidden_size=40),
        attention=dict(num_heads=4, num_kv_heads=2, with_bias=False,
                       qk_norm=True, rope=True),
        moe=dict(hidden_size=16, n_experts=4, k=2, activation="swiglu",
                 dispatch="held", bias=False))


@pytest.mark.parametrize("tie", [False, True])
def test_the_pattern_decoder_under_a_tied_or_an_untied_head(tie):
    """``build_hybrid_lm(tie_embeddings=)``: ONE (V, E) matrix for the
    lookup and the head, or two; either way eval gives log-probabilities
    over the vocabulary and the decoder is the same stack."""
    lm = _conv_stack(tie)
    matrices = [leaf for leaf in jax.tree_util.tree_leaves(
        lm.parameter_tree()) if leaf.shape == (48, 32)]
    assert len(matrices) == (1 if tie else 2)
    head = list(lm.modules())[-1]
    assert isinstance(head, nn.TiedLMHead if tie else nn.LMHead)
    if tie:
        assert head.embed_ref is lm[0]
    ids = jnp.asarray(_rng(1).integers(1, 49, (2, 11)), jnp.float32)
    logp = lm.evaluate_mode().forward(ids)
    assert logp.shape == (2, 11, 48)
    np.testing.assert_allclose(np.asarray(jnp.exp(logp).sum(-1)), 1.0,
                               rtol=1e-5)
    assert _digest(_conv_stack(tie).parameter_tree()[
        "1"]) == _digest(lm.parameter_tree()["1"])


def test_a_convolution_block_is_a_kind_of_the_pattern_decoder():
    """Kind ``C``: ``x + ShortConv(norm(x))`` from the ``short_conv``
    group; a batch above one runs each record as it runs alone, through
    every kind of block, with block remat on or off."""
    assert "C" in nn.HybridDecoder.KINDS
    dec = nn.HybridDecoder("C", 16, short_conv=dict(kernel=4))
    assert isinstance(dec.layer0.mixer, nn.ShortConv)
    assert dec.layer0.mixer.kernel == 4
    assert nn.HybridDecoder("C", 16).layer0.mixer.kernel == 3
    x = _normal(_rng(2), 1, 9, 16)
    blk = dec.layer0
    _close(dec.stream(x), x + blk.mixer.forward(blk.norm.forward(x)),
           tol=1e-6)
    with pytest.raises(ValueError):
        nn.HybridDecoder("CX", 16)
    stack = _conv_stack(True)[1]
    x = _normal(_rng(3), 3, 10, 32)
    params = stack.parameter_tree()
    outs = {}
    for remat in (False, True):
        stack.remat_blocks = remat
        outs[remat] = _apply(stack, params, x)
        for i in range(3):
            _close(outs[remat][i], _apply(stack, params, x[i:i + 1])[0],
                   tol=1e-5)
    _close(outs[True], outs[False], tol=1e-6)


# ------------------------------------------------------------------ the model

@pytest.fixture(scope="module")
def cut():
    cell, cfg = harness.load_cell("nemotron-3-nano-30b-a3b-train-s8192",
                                  rehearse=True)
    return cell, cfg, builder.build(cfg, 3)


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


@pytest.fixture(scope="module")
def controlled():
    """``benchmark.controls`` at the rehearsal size in float32 (there the
    sound system is the reference to 1e-7), 64 tokens = 4 chunks."""
    from benchmark import controls
    cell, cfg = harness.load_cell("nemotron-3-nano-30b-a3b-train-s8192",
                                  rehearse=True)
    cell = dict(cell, precision="fp32",
                reference=dict(cell["reference"], seq_len=64,
                               loss_rtol=1e-5, grad_norm_rtol=1e-4))
    return dict(controls.run(cell, cfg, 3))


def test_the_sound_system_passes_the_reference_check(controlled):
    assert set(controlled) == {"sound", *builder.FAULTS}
    assert controlled["sound"]["ok"]


@pytest.mark.parametrize("fault", builder.FAULTS)
def test_a_fault_planted_in_the_system_goes_through_the_reference_check(
        controlled, fault):
    """Each fault is in the SYSTEM's modules and goes through the
    comparison that decides ``correct``; the reference is the sound one.
    A missing term of the mathematics fails it. The scan's state carried in
    bf16 moves the system's numbers and passes even at these limits, a
    hundredth of the cell's: at seeded weights the loss hardly depends on
    the state (2.5e-7 here), so the scan's precision is held by
    ``test_scan_accumulates_its_state_in_float32_under_bf16_operands``."""
    got, sound = controlled[fault], controlled["sound"]
    assert got["reference_loss"] == sound["reference_loss"]
    assert got["system_loss"] != sound["system_loss"]
    assert got["ok"] == (fault == "bf16_scan_state")


def test_a_planted_fault_is_taken_out_again(cut):
    from bigdl_tpu.nn.mamba import Mamba2
    _, _, model = cut
    mixers = [m for m in model.modules() if isinstance(m, (Mamba2, MoE))]
    route, states = MoE._route, scan_module._chunk_states
    for fault in builder.FAULTS:
        with builder.planted(model, fault):
            pass
    assert MoE._route is route and scan_module._chunk_states is states
    for m in mixers:
        if isinstance(m, MoE):
            assert m.route_scale == 2.5
        else:
            np.testing.assert_array_equal(np.asarray(m.D), 1.0)
    with pytest.raises(ValueError):
        with builder.planted(model, "no_such_fault"):
            pass


def test_the_reference_in_bf16_is_the_tolerances_second_reading(cut):
    """``dtype=bfloat16`` computes the whole reference a precision lower
    (the cell file's second reading): it runs, and lands near the float32
    reference but not on it."""
    cell, cfg, model = cut
    data, labels = builder.reference_batch(cfg, cell, 3)
    ids, tgt = (jnp.asarray(t, jnp.int32) - 1 for t in (data, labels))
    p = builder.reference_params(model)
    true, gn, _ = reference.loss_and_grad_norm(p, ids, tgt, cfg)
    low, gn_low, _ = reference.loss_and_grad_norm(p, ids, tgt, cfg,
                                                  jnp.bfloat16)
    assert 0 < abs(float(low) - float(true)) < 0.02 * float(true)
    assert 0 < abs(float(gn_low) - float(gn)) < 0.1 * float(gn)


def test_hf_config_maps_to_the_builders_arguments(cut):
    from bigdl_tpu.interop.hf import nemotron_h_lm_kwargs
    _, cfg = harness.load_cell("nemotron-3-nano-30b-a3b-train-s8192")
    kw = nemotron_h_lm_kwargs(builder.hf_config(cfg), held_experts=range(8))
    assert kw["pattern"] == "MEMEM*EME" and kw["embed_dim"] == 2688
    assert kw["vocab_size"] == 16384
    assert kw["mamba"] == dict(
        num_heads=64, head_dim=64, state_size=128, n_groups=8, conv_kernel=4,
        chunk_size=128, norm_eps=1e-5, dt_min=0.001, dt_max=0.1,
        dt_floor=0.0001)
    assert kw["moe"] == dict(
        hidden_size=1856, n_experts=128, k=6, activation="relu2",
        dispatch="held", held=tuple(range(8)), bias=False,
        shared_hidden=3712, route_scale=2.5)
    assert kw["attention"] == dict(num_heads=32, num_kv_heads=2,
                                   head_dim=128, with_bias=False)
    for bad in (dict(n_group=2), dict(hybrid_override_pattern="ME-" * 3),
                dict(num_hidden_layers=8), dict(sliding_window=4096),
                dict(mlp_hidden_act="silu"), dict(mamba_proj_bias=True),
                dict(use_conv_bias=False), dict(tie_word_embeddings=True)):
        with pytest.raises(ValueError):
            nemotron_h_lm_kwargs(dict(builder.hf_config(cfg), **bad))


def test_the_published_model_has_the_published_size():
    """The builder's shapes at the PUBLISHED depth, experts and vocabulary
    give the published 31.58B parameters, and the cut gives 667.0M."""
    _, cfg = harness.load_cell("nemotron-3-nano-30b-a3b-train-s8192")
    e, f, fs = 2688, 1856, 3712
    mamba = e * (2 * 4096 + 2 * 8 * 128 + 64) + (4096 + 2048) * 5 \
        + 3 * 64 + 4096 + 4096 * e + e
    attn = e * (32 + 2 * 2) * 128 + 32 * 128 * e + e
    moe = lambda held: e * 128 + 128 + 2 * e * fs + held * 2 * e * f + e
    pub = cfg["published"]
    pattern = pub["hybrid_override_pattern"]
    whole = pattern.count("M") * mamba + pattern.count("*") * attn \
        + pattern.count("E") * moe(128) + 2 * pub["vocab_size"] * e + e
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) \
        == (23, 23, 6)
    assert round(whole / 1e9, 2) == 31.58
    here = 4 * mamba + attn + 4 * moe(8) + 2 * 16384 * e + e
    assert round(here / 1e6, 1) == 667.0


def test_block_remat_is_honoured_and_changes_no_gradient(cut):
    from bigdl_tpu.dataset.base import DataSet, Sample
    from bigdl_tpu.optim import Optimizer
    cell, cfg, model = cut
    dec = builder.decoder_of(model)
    assert dec.remat_blocks            # the config's training.remat
    ds = DataSet.array([Sample(np.ones(8, np.float32),
                               np.ones(8, np.float32))])
    opt = Optimizer(model, ds, builder.criterion(cfg))
    opt.set_remat(False)
    assert not dec.remat_blocks
    x = jnp.asarray(_rng(8).integers(1, 257, (1, 24)).astype(np.float32))

    def grads():
        def f(p):
            h = functional_apply(model[1], p, model[1].buffer_tree(),
                                 model[0].forward(x), training=True)[0]
            return jnp.sum(jnp.square(h))
        return jax.jit(jax.grad(f))(model[1].parameter_tree())

    plain = grads()
    opt.set_remat("block")
    assert dec.remat_blocks
    for a, b in zip(jax.tree_util.tree_leaves(grads()),
                    jax.tree_util.tree_leaves(plain)):
        _close(a, b, tol=1e-5)


def test_scopes_and_counters_of_the_new_layers(cut):
    from bigdl_tpu.telemetry import get_registry, instruments
    cell, cfg, model = cut
    ins = instruments(get_registry())
    held0 = ins.moe_dispatch_total.labels(path="held").value
    scan0 = ins.ssd_scan_total.labels(form="chunked").value
    x = jnp.asarray(_rng(8).integers(1, 257, (1, 24)).astype(np.float32))

    def f(p):
        h = functional_apply(model[1], p, model[1].buffer_tree(),
                             model[0].forward(x), training=True)[0]
        return jnp.sum(jnp.square(h))

    hlo = jax.jit(jax.grad(f)).lower(
        model[1].parameter_tree()).compile().as_text()
    assert ins.moe_dispatch_total.labels(path="held").value > held0
    assert ins.ssd_scan_total.labels(form="chunked").value > scan0
    for scope in ("ssd_scan", "moe_route", "moe_experts", "moe_shared"):
        assert timeline.scope_instructions(hlo, scope), scope
    # the backward's loop over row blocks is under the forward's scope
    whiles = [n for n in timeline.scope_instructions(hlo, "moe_experts")
              if n.startswith("while")]
    assert len(whiles) >= 2


def _scan_and_its_backward(x, dt, a, b, c, ct):
    y, back = jax.vjp(lambda *t: ssd_scan(*t, chunk=128), x, dt, a, b, c)
    return y, back(ct)


def test_scope_and_counter_of_the_scan_kernels(monkeypatch):
    """With the kernel path forced on this CPU (the kernels in Pallas'
    interpreter): ``form="kernel"`` counts it, and EVERY instruction of the
    compiled scan and its backward is under the scope ``ssd_scan``, the four
    calls' own included: a ``custom_vjp`` rule is traced outside the
    forward's name stack and enters the scope by hand."""
    from bigdl_tpu.telemetry import get_registry, instruments
    ins = instruments(get_registry())
    before = {f: ins.ssd_scan_total.labels(form=f).value
              for f in ("chunked", "kernel")}
    monkeypatch.setattr(scan_module, "takes_kernel", lambda *a: True)
    args = _tile_inputs(256, jnp.float32)
    hlo = jax.jit(_scan_and_its_backward).lower(
        *args, args[0]).compile().as_text()
    assert ins.ssd_scan_total.labels(form="kernel").value \
        == before["kernel"] + 1
    assert ins.ssd_scan_total.labels(form="chunked").value \
        == before["chunked"]
    found = timeline.scope_instructions(hlo, "ssd_scan")
    # an op_name that is a path; a reducer's body and a bitcast of an
    # argument carry a bare word ("reduce_sum", "dt")
    named = [m for m in map(timeline._INSTR.match, hlo.splitlines())
             if m and m.group(2).startswith("jit(")]
    assert len(named) > 500
    assert [m.group(1) for m in named if m.group(1) not in found] == []
    for call in ("ssd_fwd_state", "ssd_fwd_out", "ssd_bwd_out",
                 "ssd_bwd_state"):
        assert any(f"/{call}/" in m.group(2) for m in named), call


def test_the_scan_kernels_on_a_tpu_are_named_and_no_flash_call(monkeypatch):
    """Lowered for a TPU at the Nemotron cell's shape (no chip needed): four
    Mosaic calls, named ``ssd_*`` (the ledger's ``mosaic:ssd_*``), none
    with an operand or result of the shape by which the flash readers know
    a flash call in that cell (``builders/nemotron_h.flash_shape``)."""
    import re
    cell, cfg = harness.load_cell("nemotron-3-nano-30b-a3b-train-s8192")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bsz, length = cell["batch_size"], cell["seq_len"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    bf16 = jnp.bfloat16
    x = jax.ShapeDtypeStruct((bsz, length, h, p), bf16)
    bc = jax.ShapeDtypeStruct((bsz, length, g, n), bf16)
    text = jax.jit(_scan_and_its_backward).trace(
        x, jax.ShapeDtypeStruct((bsz, length, h), jnp.float32),
        jax.ShapeDtypeStruct((h,), jnp.float32), bc, bc, x
    ).lower(lowering_platforms=("tpu",)).as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert sorted(re.search(r'kernel_name = "(\w+)"', c).group(1)
                  for c in calls) == ["ssd_bwd_out", "ssd_bwd_state",
                                      "ssd_fwd_out", "ssd_fwd_state"]
    fb, fh, fs, fd = builder.flash_shape(cfg, cell)
    for c in calls:
        types = c.rsplit(" : ", 1)[1]
        assert f"tensor<{fb * fh}x{fs}x{fd}x" not in types
        assert f"tensor<{bsz}x{length}x{h * p}xbf16>" in types


# ------------------------------------------------- the optimizer's one copy

def test_training_keeps_no_second_device_copy_of_the_weights():
    """While ``optimize()`` runs the model's own parameter arrays are host
    arrays (the trainer's private copies are the only ones on the device);
    afterwards the model holds the trained values as device arrays, and a
    clone made before training still has the old ones."""
    from bigdl_tpu.dataset.base import DataSet, Sample, SampleToBatch
    from bigdl_tpu.optim import SGD, Optimizer, Trigger
    manual_seed(3)
    model = nn.Sequential().add(nn.Linear(4, 3)).add(nn.LogSoftMax())
    clone = model.clone_module()
    before = np.asarray(model[0].weight).copy()
    rng = _rng(1)
    ds = DataSet.array([Sample(rng.standard_normal(4).astype(np.float32),
                               np.float32(1 + i % 3)) for i in range(16)]) \
        >> SampleToBatch(4)
    seen = []

    def look(state):
        seen.append([type(leaf) for leaf in jax.tree_util.tree_leaves(
            model.parameter_tree())])
        return state["neval"] > 4

    opt = Optimizer(model, ds, nn.ClassNLLCriterion())
    opt.set_optim_method(SGD(learningrate=0.1))
    opt.set_end_when(Trigger(look, "look"))
    opt.optimize()
    assert seen and all(t is np.ndarray for types in seen for t in types)
    after = model.parameter_tree()
    assert all(isinstance(leaf, jax.Array)
               for leaf in jax.tree_util.tree_leaves(after))
    assert np.abs(np.asarray(model[0].weight) - before).max() > 1e-4
    np.testing.assert_array_equal(np.asarray(clone[0].weight), before)


def test_a_failed_training_leaves_the_model_its_device_arrays():
    from bigdl_tpu.dataset.base import DataSet, Sample, SampleToBatch
    from bigdl_tpu.optim import SGD, Optimizer, Trigger
    manual_seed(3)
    model = nn.Sequential().add(nn.Linear(4, 3)).add(nn.LogSoftMax())
    before = np.asarray(model[0].weight).copy()
    ds = DataSet.array([Sample(np.ones(4, np.float32), np.float32(1))] * 8) \
        >> SampleToBatch(4)

    def boom(state):
        raise ValueError("stop here")

    opt = Optimizer(model, ds, nn.ClassNLLCriterion())
    opt.set_optim_method(SGD(learningrate=0.1))
    opt.set_end_when(Trigger(boom, "boom"))
    with pytest.raises(ValueError):
        opt.optimize()
    assert isinstance(model[0].weight, jax.Array)
    np.testing.assert_array_equal(np.asarray(model[0].weight), before)
