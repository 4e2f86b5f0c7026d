"""The gated delta-rule mixer's local part as Mosaic calls
(``ops/delta_local.py``), in Pallas' interpreter on the CPU: each of the
four calls against the ``jax.numpy`` lines of ``nn/gated_delta_net.py`` it
replaces, ``nn.GatedDeltaNet`` on the kernel path against itself on the XLA
path, the path rule, the counter, the calls' names and scopes, and the
controls' seams. Heads of 96 / 192 as published (0.75 and 1.5 lane tiles),
5 of them where the cell holds 15 (q and k end inside a tile, and so does
the value width) and 10 where the model has 30 (both end on one)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.nn.gated_delta_net import L2_EPS
from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.nn.short_conv import causal_depthwise_conv
from bigdl_tpu.ops import delta_local as dl
from bigdl_tpu.telemetry import step_partition as sp
from bigdl_tpu.utils.rng import manual_seed

BF16, F32 = jnp.bfloat16, jnp.float32
DK, DV, EPS = 96, 192, 1e-6


def _geo(h, k):
    return dl._Geo(h, DK, DV, k, L2_EPS, EPS, True)


def _rng(seed=0):
    return np.random.default_rng(seed)


def _normal(rng, *shape, scale=1.0, dtype=F32):
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                       * scale).astype(dtype)


def _close(a, b, tol=1e-5):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol * np.abs(b).max())


def _operands(geo, seed, bsz, length):
    """proj, the taps and the norm's weight, as the module holds them."""
    rng = _rng(seed)
    width = geo.conv_dim + geo.d_value + 2 * geo.h
    return (_normal(rng, bsz, length, width),
            _normal(rng, geo.conv_dim, geo.k, scale=0.5),
            1.0 + _normal(rng, geo.dv, scale=0.3))


# ---- the lines of nn/gated_delta_net.py, a pass at a time

def _conv_lines(proj, w, geo):
    """q, k and v (B, H, L, d), as ``_recurrence_inputs`` makes them and
    the recurrence's calls take them."""
    bsz, length, _ = proj.shape
    d_key = geo.h * geo.dk
    qkv = jax.nn.silu(causal_depthwise_conv(proj[..., :geo.conv_dim], w))
    qk = qkv[..., :2 * d_key].reshape(bsz, length, 2 * geo.h, geo.dk)
    qk = qk * jax.lax.rsqrt(jnp.sum(jnp.square(qk), -1, keepdims=True)
                            + L2_EPS)
    v = qkv[..., 2 * d_key:].reshape(bsz, length, geo.h, geo.dv)
    return tuple(jnp.swapaxes(t, 1, 2).astype(proj.dtype) for t in (
        qk[:, :, :geo.h] * geo.dk ** -0.5, qk[:, :, geo.h:], v))


def _gate_lines(o, z, nw, geo):
    """From ``o`` (B, H, L, d_v), as the recurrence's call gives it."""
    o = jnp.swapaxes(o, 1, 2).astype(F32)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + geo.eps) * nw.astype(F32)
    return (o.reshape(z.shape) * jax.nn.silu(z.astype(F32))).astype(z.dtype)


# taps, batch, length, heads: one row tile of 128, three of them, two of
# 256; batch 2 would show a halo that crossed sequences
SHAPES = [(4, 2, 128, 5), (4, 1, 384, 5), (2, 2, 512, 10)]
IDS = ["k4-one-tile-5-heads", "k4-three-tiles-5-heads",
       "k2-256-tiles-10-heads"]


@pytest.mark.parametrize("k,bsz,length,h", SHAPES, ids=IDS)
def test_the_convolution_call_is_the_lines_it_replaces(k, bsz, length, h):
    """``delta_local_conv``: q, k and v from the columns of ``proj`` where
    they lie, the halo from the tile before and zeros at each sequence's
    start, a head's L2 norm where a head is 0.75 of a lane tile, each head
    moved out of the row's lanes to rows of its own."""
    geo = _geo(h, k)
    proj, w, _ = _operands(geo, 1, bsz, length)
    for got, want in zip(dl._conv_call(proj, w, geo),
                         _conv_lines(proj, w, geo)):
        _close(got, want)


@pytest.mark.parametrize("k,bsz,length,h", SHAPES, ids=IDS)
def test_the_convolution_backward_call_is_the_lines_transpose(k, bsz,
                                                              length, h):
    """``delta_local_conv_bwd``: the input cotangent with the halo's debt
    carried from tile to tile, ``d conv_weight`` summed over the row
    tiles."""
    geo = _geo(h, k)
    proj, w, _ = _operands(geo, 2, bsz, length)
    rng = _rng(3)
    douts = tuple(_normal(rng, bsz, geo.h, length, d)
                  for d in (geo.dk, geo.dk, geo.dv))
    dx, taps = dl._conv_bwd_call(proj, w, *douts, geo)
    _, back = jax.vjp(lambda p, w: _conv_lines(p, w, geo), proj, w)
    dproj, dw = back(douts)
    _close(dx, dproj[..., :geo.conv_dim], 2e-5)
    assert not np.asarray(dproj[..., geo.conv_dim:]).any()
    _close(jnp.sum(taps[0], axis=1).T, dw, 2e-5)


@pytest.mark.parametrize("k,bsz,length,h", SHAPES, ids=IDS)
def test_the_gate_call_is_the_lines_it_replaces(k, bsz, length, h):
    """``delta_local_gate``: the RMSNorm a head of 1.5 lane tiles, its
    weight and the gate, where the row ends inside a tile (5 heads) and
    where it does not (10)."""
    geo = _geo(h, k)
    rng = _rng(4)
    o = _normal(rng, bsz, geo.h, length, geo.dv)
    z = _normal(rng, bsz, length, geo.d_value)
    nw = 1.0 + _normal(rng, geo.dv, scale=0.3)
    _close(dl._gate_call(o, z, nw, geo), _gate_lines(o, z, nw, geo))


@pytest.mark.parametrize("k,bsz,length,h", SHAPES, ids=IDS)
def test_the_gate_backward_call_is_the_lines_transpose(k, bsz, length, h):
    """``delta_local_gate_bwd``: ``do``, ``dz`` and ``d norm_weight``
    summed over the row tiles and the heads."""
    geo = _geo(h, k)
    rng = _rng(5)
    o = _normal(rng, bsz, geo.h, length, geo.dv)
    z, dout = (_normal(rng, bsz, length, geo.d_value) for _ in range(2))
    nw = 1.0 + _normal(rng, geo.dv, scale=0.3)
    do, dz, sums = dl._gate_bwd_call(o, z, nw, dout, geo)
    _, back = jax.vjp(lambda o, z, nw: _gate_lines(o, z, nw, geo), o, z, nw)
    wo, wz, wnw = back(dout)
    _close(do, wo, 2e-5)
    _close(dz, wz, 2e-5)
    _close(jnp.sum(jnp.sum(sums[0], axis=0).reshape(geo.h, geo.dv), axis=0),
           wnw, 2e-5)


# ---- nn.GatedDeltaNet on the kernel path

def _mixer(h=5, k=4, neg=True):
    manual_seed(5)
    return nn.GatedDeltaNet(48, h, DK, DV, conv_kernel=k,
                            allow_neg_eigval=neg, norm_eps=EPS,
                            chunk_size=64)


def _apply(module, params, x):
    return functional_apply(module, params, module.buffer_tree(), x,
                            training=True)[0]


def _moved(m, seed, dtype=F32):
    """The module's parameters with the norm's weight and the decay's off
    their initial values, so that every term matters."""
    rng = _rng(seed)
    params = m.parameter_tree()
    for name in ("norm_weight", "dt_bias", "A_log"):
        params[name] = params[name] + _normal(rng, *params[name].shape,
                                              scale=0.3)
    return {k: v.astype(dtype) for k, v in params.items()}


def _on_the_kernel_path(monkeypatch):
    monkeypatch.setattr(dl, "takes_kernel", lambda *a: True)


@pytest.mark.parametrize("dtype,h,length,tols", [
    (F32, 5, 384, (2e-5, 2e-4, 2e-4)),
    (BF16, 5, 384, (1e-2, 2e-2, 3e-2)),
    (BF16, 10, 128, (1e-2, 2e-2, 3e-2)),
], ids=["float32-5-heads", "bf16-5-heads", "bf16-10-heads"])
def test_the_two_forms_agree_through_the_mixer(dtype, h, length, tols,
                                               monkeypatch):
    """Forward and every gradient of ``nn.GatedDeltaNet`` with its local
    part in the four calls against the same module on its ``jax.numpy``
    lines: float32 operands tightly; bf16 operands, as the training policy
    hands them, to a rounding (the kernel form rounds where the lines
    round)."""
    m = _mixer(h)
    rng = _rng(3)
    u = _normal(rng, 2, length, 48, dtype=dtype)
    probe = _normal(rng, 2, length, 48, dtype=dtype)
    params = _moved(m, 4, dtype)

    def loss(p, u):
        return jnp.sum((_apply(m, p, u) * probe).astype(F32))

    # compiled: XLA's CPU backend runs a bf16 product only so
    want = jax.jit(lambda p, u: _apply(m, p, u))(params, u)
    wp, wu = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, u)
    _on_the_kernel_path(monkeypatch)
    got = jax.jit(lambda p, u: _apply(m, p, u))(params, u)
    assert got.dtype == dtype
    _close(got, want, tols[0])
    gp, gu = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, u)
    _close(gu, wu, tols[1])
    for name in params:
        assert gp[name].dtype == dtype
        _close(gp[name], wp[name], tols[2])


def _ungated(mixer, o, z):
    """``benchmark/builders/olmo_hybrid.planted``'s ``no_output_gate``."""
    o = o.astype(F32)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + mixer.norm_eps) * mixer.norm_weight.astype(F32)
    return o.reshape(z.shape).astype(z.dtype)


@pytest.mark.parametrize("fault", ["k_not_normalised", "taps_reversed",
                                   "no_output_gate", "beta_not_doubled"])
def test_the_controls_still_plant_with_the_kernel_path_forced(fault,
                                                              monkeypatch):
    """The benchmark's ``correct`` gate replaces ``_recurrence_inputs`` and
    ``_gated_norm`` on the CLASS and sets ``allow_neg_eigval`` on the
    instance: ``update_output`` calls the methods by those names and reads
    the flag at trace time whichever form the sound methods would take, so
    each replacement changes the output."""
    from benchmark.builders import olmo_hybrid
    m = _mixer()
    u = _normal(_rng(1), 1, 128, 48)
    params = _moved(m, 2)
    _on_the_kernel_path(monkeypatch)
    sound = _apply(m, params, u)
    if fault in olmo_hybrid.INPUT_FAULTS:
        monkeypatch.setattr(nn.GatedDeltaNet, "_recurrence_inputs",
                            olmo_hybrid._faulty_inputs(fault))
    elif fault == "no_output_gate":
        monkeypatch.setattr(nn.GatedDeltaNet, "_gated_norm", _ungated)
    else:
        monkeypatch.setattr(m, "allow_neg_eigval", False)
    planted = _apply(m, params, u)
    assert float(jnp.abs(sound - planted).max()) \
        > 1e-2 * float(jnp.abs(sound).max())


# ---- the path rule, the counter, the names

@pytest.mark.parametrize("args,kernel", [
    (("tpu", BF16, 8192, 15, 96, 192, 4), True),    # the cell's 15 held
    (("tpu", BF16, 8192, 30, 96, 192, 4), True),    # the published 30
    (("tpu", BF16, 128, 5, 96, 192, 4), True),
    (("tpu", BF16, 8192, 16, 128, 128, 4), True),   # heads that are tiles
    (("cpu", BF16, 8192, 15, 96, 192, 4), False),
    (("tpu", F32, 8192, 15, 96, 192, 4), False),
    (("tpu", BF16, 8200, 15, 96, 192, 4), False),   # no row tile divides it
    (("tpu", BF16, 64, 2, 8, 16, 4), False),        # the rehearsal's
    (("tpu", BF16, 8192, 4, 8, 16, 4), False),      # tier-1's heads
    (("tpu", BF16, 8192, 15, 80, 160, 4), False),   # 37.5 tiles of columns
    (("tpu", BF16, 8192, 15, 96, 160, 4), False),   # a value head of 1.25
    (("tpu", BF16, 8192, 80, 96, 192, 4), False),   # 160 heads of q and k
    (("tpu", BF16, 8192, 15, 96, 192, 12), False),  # 11 rows back
])
def test_which_mixers_take_the_kernels(args, kernel):
    """The path rule as its docstring states it: backend, dtype, shapes."""
    assert dl.takes_kernel(*args) is kernel


def _form_counts():
    from bigdl_tpu.telemetry import get_registry, instruments
    fam = instruments(get_registry()).delta_local_total
    return {f: fam.labels(form=f).value for f in ("xla", "kernel")}


def test_the_counter_has_both_labels(monkeypatch):
    """``bigdl_delta_local_total{form}`` counts once a trace: ``xla`` on
    this CPU, ``kernel`` with the path forced."""
    m = _mixer()
    u = _normal(_rng(1), 1, 128, 48)
    params = m.parameter_tree()
    before = _form_counts()
    f = jax.jit(lambda p, u: _apply(m, p, u))
    f(params, u)
    f(params, u)                            # one trace, one count
    assert _form_counts() == {"xla": before["xla"] + 1,
                              "kernel": before["kernel"]}
    _on_the_kernel_path(monkeypatch)
    jax.jit(lambda p, u: _apply(m, p, u))(params, u)
    assert _form_counts() == {"xla": before["xla"] + 1,
                              "kernel": before["kernel"] + 1}


def test_the_calls_names_and_scopes(monkeypatch):
    """The four calls carry their names, and the scope ``delta_local`` in
    the forward AND in the backward (a ``custom_vjp`` rule enters it by
    hand), so the step's partition charges them to the layer and the pass
    they are; the recurrence between them stays ``delta_rule``'s, and what
    XLA keeps of the layer (beta, the log-decay, the sum of ``proj``'s
    cotangent) is under the scope too."""
    m = _mixer()
    u = _normal(_rng(1), 1, 128, 48)
    params = m.parameter_tree()
    _on_the_kernel_path(monkeypatch)

    def loss(p, u):
        return jnp.sum(_apply(m, p, u))

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, u).compile().as_text()
    names = [op for _, op in sp.instructions(hlo).values()]
    for call, pas in (("delta_local_conv", "forward"),
                      ("delta_local_gate", "forward"),
                      ("delta_local_conv_bwd", "backward"),
                      ("delta_local_gate_bwd", "backward")):
        mine = [op for op in names if f"/{call}/" in op]
        assert mine, call
        assert {sp.classify(op) for op in mine} == {("delta_local", pas)}, \
            call
    rule = {sp.classify(op) for op in names if "delta_rule" in op}
    assert rule == {("delta_rule", "forward"), ("delta_rule", "backward")}
    assert not [op for op in names if "delta_local/delta_local" in op
                or "delta_local)/delta_local" in op]
