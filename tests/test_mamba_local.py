"""The Mamba-2 mixer's local part as Mosaic calls (``ops/mamba_local.py``),
in Pallas' interpreter on the CPU: each of the four calls against the
``jax.numpy`` lines of ``nn/mamba.py`` it replaces, ``nn.Mamba2`` on the
kernel path against the plain reference, the path rule, the counter and the
calls' names and scopes."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import nemotron_h as reference
from bigdl_tpu import nn
from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.nn.short_conv import causal_depthwise_conv
from bigdl_tpu.ops import mamba_local as ml
from bigdl_tpu.telemetry import step_partition as sp
from bigdl_tpu.utils.rng import manual_seed

F32 = jnp.float32
H, P, N = 4, 64, 64             # d_inner 256; G * N = 128 at G = 2


def _geo(k, g=2):
    return ml._Geo(H, P, g, 2 * N // g, k, 64, 1e-5, True)


def _rng(seed=0):
    return np.random.default_rng(seed)


def _normal(rng, *shape, scale=1.0, dtype=F32):
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                       * scale).astype(dtype)


def _close(a, b, tol=1e-5):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol * np.abs(b).max())


def _operands(geo, seed, bsz, length, dtype=F32):
    """zxbcdt and the four parameters, as the module holds them."""
    rng = _rng(seed)
    width = geo.d_inner + geo.conv_dim + geo.h
    zx = _normal(rng, bsz, length, width, dtype=dtype)
    w = _normal(rng, geo.conv_dim, geo.k, scale=0.5)
    bias = _normal(rng, geo.conv_dim, scale=0.3)
    d = 1.0 + _normal(rng, geo.h, scale=0.3)
    nw = 1.0 + _normal(rng, geo.d_inner, scale=0.3)
    return zx, w, bias, d, nw


# ---- the lines of nn/mamba.py, a pass at a time

def _conv_lines(zx, w, bias, geo):
    xbc = zx[..., geo.d_inner:geo.d_inner + geo.conv_dim]
    out = causal_depthwise_conv(xbc, w) + bias.astype(F32)
    out = jax.nn.silu(out).astype(zx.dtype)
    return (out[..., :geo.d_inner],
            out[..., geo.d_inner:geo.d_inner + geo.tc],
            out[..., geo.d_inner + geo.tc:])


def _gate_lines(y, x, zx, d, nw, geo):
    bsz, length, d_inner = y.shape
    z = zx[..., :d_inner]
    y = y.astype(F32).reshape(bsz, length, geo.h, geo.p) \
        + d.astype(F32)[:, None] * x.astype(F32).reshape(bsz, length, geo.h,
                                                         geo.p)
    y = y.reshape(bsz, length, d_inner) * jax.nn.silu(z.astype(F32))
    yg = y.reshape(bsz, length, geo.g, geo.gw)
    yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
                            + geo.eps)
    return yg.reshape(bsz, length, d_inner).astype(zx.dtype) * nw


SHAPES = [(4, 2, 128), (4, 2, 384), (2, 2, 384), (4, 1, 512)]
IDS = ["k4-one-tile", "k4-three-tiles", "k2-three-tiles", "k4-a-512-tile"]


@pytest.mark.parametrize("k,bsz,length", SHAPES, ids=IDS)
def test_the_convolution_call_is_the_lines_it_replaces(k, bsz, length):
    """``mamba_local_conv``: x, B and C from the columns of ``zxbcdt``
    where they lie, the halo from the tile before and zeros at each
    sequence's start (batch 2: a halo that crossed sequences would show in
    the second's first rows)."""
    geo = _geo(k)
    zx, w, bias, d, nw = _operands(geo, 1, bsz, length)
    w_t, b_row, _, _ = ml._operands(w, bias, d, nw, geo)
    got = ml._conv_call(zx, w_t, b_row, geo)
    for a, b in zip(got, _conv_lines(zx, w, bias, geo)):
        _close(a, b)


@pytest.mark.parametrize("k,bsz,length", SHAPES, ids=IDS)
def test_the_convolution_backward_call_is_the_lines_transpose(k, bsz,
                                                              length):
    """``mamba_local_conv_bwd``: the input cotangent into its columns of
    the wide buffer (the other columns as they were), ``d conv_weight`` and
    ``d conv_bias`` summed over the row tiles, the skip's ``D * dy`` added
    to x's cotangent inside."""
    geo = _geo(k)
    zx, w, bias, d, nw = _operands(geo, 2, bsz, length)
    rng = _rng(3)
    dx, dy = (_normal(rng, bsz, length, geo.d_inner) for _ in range(2))
    db, dc = (_normal(rng, bsz, length, geo.tc) for _ in range(2))
    wide = _normal(rng, *zx.shape)
    w_t, b_row, dspread, _ = ml._operands(w, bias, d, nw, geo)
    got, taps = ml._conv_bwd_call(zx, w_t, b_row, dspread, dx, dy, db, dc,
                                  wide, geo)
    _, back = jax.vjp(lambda zx, w, bias: _conv_lines(zx, w, bias, geo),
                      zx, w, bias)
    skip = (d[:, None] * dy.reshape(bsz, length, geo.h, geo.p)
            ).reshape(dx.shape)
    dzx, dw, dbias = back((dx + skip, db, dc))
    lo, hi = geo.d_inner, geo.d_inner + geo.conv_dim
    _close(got[..., lo:hi], dzx[..., lo:hi])
    np.testing.assert_array_equal(np.asarray(got[..., :lo]),
                                  np.asarray(wide[..., :lo]))
    np.testing.assert_array_equal(np.asarray(got[..., hi:]),
                                  np.asarray(wide[..., hi:]))
    taps = jnp.sum(taps[0], axis=1)
    _close(taps[:k].T, dw)
    _close(taps[k], dbias)


@pytest.mark.parametrize("g,bsz,length", [(2, 2, 128), (2, 2, 384),
                                          (1, 2, 256)])
def test_the_gate_call_is_the_lines_it_replaces(g, bsz, length):
    """``mamba_local_gate``: D skip, gate, RMSNorm over each group of lanes
    and the weight, z read from the first columns of ``zxbcdt``."""
    geo = _geo(4, g)
    zx, w, bias, d, nw = _operands(geo, 4, bsz, length)
    rng = _rng(5)
    y, x = (_normal(rng, bsz, length, geo.d_inner) for _ in range(2))
    _, _, dspread, nw_row = ml._operands(w, bias, d, nw, geo)
    _close(ml._gate_call(y, x, zx, dspread, nw_row, geo),
           _gate_lines(y, x, zx, d, nw, geo))


@pytest.mark.parametrize("g,bsz,length", [(2, 2, 128), (2, 2, 384),
                                          (1, 2, 256)])
def test_the_gate_backward_call_is_the_lines_transpose(g, bsz, length):
    """``mamba_local_gate_bwd``: ``dy``, ``dz`` into the first columns of a
    new wide buffer, ``d D`` (before its sum over a head's lanes) and
    ``d norm_weight`` summed over the row tiles; the skip's ``dx`` is
    ``D * dy``."""
    geo = _geo(4, g)
    zx, w, bias, d, nw = _operands(geo, 6, bsz, length)
    rng = _rng(7)
    y, x, dout = (_normal(rng, bsz, length, geo.d_inner) for _ in range(3))
    _, _, dspread, nw_row = ml._operands(w, bias, d, nw, geo)
    dy, wide, sums = ml._gate_bwd_call(y, x, zx, dspread, nw_row, dout, geo)
    _, back = jax.vjp(lambda y, x, zx, d, nw: _gate_lines(y, x, zx, d, nw,
                                                          geo),
                      y, x, zx, d, nw)
    wy, wx, wzx, wd, wnw = back(dout)
    _close(dy, wy)
    _close(dspread * dy, wx)
    _close(wide[..., :geo.d_inner], wzx[..., :geo.d_inner])
    assert wide.shape == zx.shape
    sums = jnp.sum(sums[0], axis=1)
    _close(sums[0], wnw)
    _close(jnp.sum(sums[1].reshape(geo.h, geo.p), axis=1), wd)


# ---- the whole of it, and nn.Mamba2 on the kernel path

def _mamba(k=4, g=2):
    manual_seed(5)
    return nn.Mamba2(48, num_heads=H, head_dim=P, state_size=2 * N // g,
                     n_groups=g, conv_kernel=k, chunk_size=64)


def _apply(module, params, x):
    return functional_apply(module, params, module.buffer_tree(), x,
                            training=True)[0]


def _moved(m, seed, dtype=F32):
    """The module's parameters with D, the norm weight and the rest moved
    off their initial values, so that every term matters."""
    rng = _rng(seed)
    params = m.parameter_tree()
    for name in ("D", "norm_weight", "conv_bias", "dt_bias", "A_log"):
        params[name] = params[name] + _normal(rng, *params[name].shape,
                                              scale=0.3)
    return {k: v.astype(dtype) for k, v in params.items()}


def _mamba_reference_params(tree):
    """The module's parameters under the public modelling code's names, as
    the plain reference reads them."""
    names = {"in_proj_weight": "in_proj.weight",
             "conv_weight": "conv1d.weight", "conv_bias": "conv1d.bias",
             "dt_bias": "dt_bias", "A_log": "A_log", "D": "D",
             "norm_weight": "norm.weight",
             "out_proj_weight": "out_proj.weight"}
    return {"m." + names[k]: v for k, v in tree.items()}


def _on_the_kernel_path(monkeypatch):
    monkeypatch.setattr(ml, "takes_kernel", lambda *a: True)


@pytest.mark.parametrize("k,length", [(4, 128), (4, 384), (2, 256)])
def test_mamba2_on_the_kernel_path_matches_the_reference(k, length,
                                                         monkeypatch):
    """Forward and every gradient of ``nn.Mamba2`` with its local part in
    the four calls (float32 operands, so the comparison is tight), against
    ``benchmark/reference``'s ``mamba2`` as ``test_hybrid_lm.py`` holds the
    XLA path; batch 2, one row tile and several."""
    m = _mamba(k)
    cfg = dict(mamba_num_heads=H, mamba_head_dim=P, n_groups=2,
               ssm_state_size=N, conv_kernel=k, layer_norm_epsilon=1e-5)
    rng = _rng(1)
    u = _normal(rng, 2, length, 48)
    probe = _normal(rng, 2, length, 48)
    params = _moved(m, 2)

    def plain(p, u):
        return jnp.sum(reference.mamba2(_mamba_reference_params(p), "m.", u,
                                        cfg) * probe)

    def system(p, u):
        return jnp.sum(_apply(m, p, u) * probe)

    want = reference.mamba2(_mamba_reference_params(params), "m.", u, cfg)
    wp, wu = jax.grad(plain, argnums=(0, 1))(params, u)
    _on_the_kernel_path(monkeypatch)
    _close(_apply(m, params, u), want, 2e-4)
    gp, gu = jax.grad(system, argnums=(0, 1))(params, u)
    _close(gu, wu, 2e-4)
    for name in params:
        _close(gp[name], wp[name], 2e-4)


def test_the_two_forms_agree_in_bf16(monkeypatch):
    """bf16 operands, as the training policy hands them: the kernel form
    rounds where the XLA lines round, so the outputs agree to a rounding
    and every gradient to a few (k = 4, batch 2, three row tiles)."""
    m = _mamba()
    bf = jnp.bfloat16
    rng = _rng(3)
    u = _normal(rng, 2, 384, 48, dtype=bf)
    probe = _normal(rng, 2, 384, 48, dtype=bf)
    params = _moved(m, 4, bf)

    def loss(p, u):
        return jnp.sum((_apply(m, p, u) * probe).astype(F32))

    want = _apply(m, params, u)
    wp, wu = jax.grad(loss, argnums=(0, 1))(params, u)
    _on_the_kernel_path(monkeypatch)
    got = _apply(m, params, u)
    assert got.dtype == bf
    _close(got, want, 1e-2)
    gp, gu = jax.grad(loss, argnums=(0, 1))(params, u)
    _close(gu, wu, 2e-2)
    for name in params:
        assert gp[name].dtype == bf
        _close(gp[name], wp[name], 3e-2)


def test_a_swapped_d_parameter_reaches_the_kernels(monkeypatch):
    """The benchmark's planted fault ``no_d_skip`` swaps the module's ``D``:
    pass B reads it at call time, so the output moves with it."""
    m = _mamba()
    u = _normal(_rng(1), 1, 128, 48)
    params = _moved(m, 2)
    _on_the_kernel_path(monkeypatch)
    with_skip = _apply(m, params, u)
    without = _apply(m, dict(params, D=jnp.zeros_like(params["D"])), u)
    assert float(jnp.abs(with_skip - without).max()) > 1e-2


# ---- the path rule, the counter, the names

@pytest.mark.parametrize("args,kernel", [
    (("tpu", jnp.bfloat16, 8192, 4096, 1024, 8, 4), True),   # the cell
    (("tpu", jnp.bfloat16, 1024, 4096, 1024, 8, 4), True),   # its check
    (("tpu", jnp.bfloat16, 256, 256, 128, 2, 2), True),
    (("cpu", jnp.bfloat16, 8192, 4096, 1024, 8, 4), False),
    (("tpu", jnp.float32, 8192, 4096, 1024, 8, 4), False),
    (("tpu", jnp.bfloat16, 8200, 4096, 1024, 8, 4), False),  # no row tile
    (("tpu", jnp.bfloat16, 64, 64, 32, 2, 4), False),   # the rehearsal
    (("tpu", jnp.bfloat16, 8192, 4096, 1024, 64, 4), False),  # groups of 64
    (("tpu", jnp.bfloat16, 8192, 4096, 768, 6, 4), False),  # x ends mid-tile
    (("tpu", jnp.bfloat16, 8192, 4096, 1024, 8, 12), False),  # 11 rows back
])
def test_which_mixers_take_the_kernels(args, kernel):
    """The path rule as its docstring states it: backend, dtype, shapes."""
    assert ml.takes_kernel(*args) is kernel


def test_the_counter_has_both_labels(monkeypatch):
    """``bigdl_mamba_local_total{form}`` counts once a trace: ``xla`` on
    this CPU, ``kernel`` with the path forced."""
    from bigdl_tpu.telemetry import get_registry, instruments
    fam = instruments(get_registry()).mamba_local_total
    m = _mamba()
    u = _normal(_rng(1), 1, 128, 48)
    params = m.parameter_tree()
    before = {f: fam.labels(form=f).value for f in ("xla", "kernel")}
    f = jax.jit(lambda p, u: _apply(m, p, u))
    f(params, u)
    f(params, u)                            # one trace, one count
    assert fam.labels(form="xla").value == before["xla"] + 1
    assert fam.labels(form="kernel").value == before["kernel"]
    _on_the_kernel_path(monkeypatch)
    jax.jit(lambda p, u: _apply(m, p, u))(params, u)
    assert fam.labels(form="kernel").value == before["kernel"] + 1
    assert fam.labels(form="xla").value == before["xla"] + 1


def test_the_calls_names_and_scopes(monkeypatch):
    """The four calls carry their names, and the scope ``mamba_local`` in
    the forward AND in the backward (a ``custom_vjp`` rule enters it by
    hand), so the step's partition charges them to the layer and the pass
    they are; the scan inside stays ``ssd_scan``'s."""
    m = _mamba()
    u = _normal(_rng(1), 1, 128, 48)
    params = m.parameter_tree()
    _on_the_kernel_path(monkeypatch)

    def loss(p, u):
        return jnp.sum(_apply(m, p, u))

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, u).compile().as_text()
    names = [op for _, op in sp.instructions(hlo).values()]
    for call, pas in (("mamba_local_conv", "forward"),
                      ("mamba_local_gate", "forward"),
                      ("mamba_local_conv_bwd", "backward"),
                      ("mamba_local_gate_bwd", "backward")):
        mine = [op for op in names if f"/{call}/" in op]
        assert mine, call
        assert {sp.classify(op) for op in mine} == {("mamba_local", pas)}, \
            call
    scan = {sp.classify(op) for op in names if "ssd_scan" in op}
    assert scan == {("ssd_scan", "forward"), ("ssd_scan", "backward")}
    assert not [op for op in names if "mamba_local/mamba_local" in op
                or "mamba_local)/mamba_local" in op]
