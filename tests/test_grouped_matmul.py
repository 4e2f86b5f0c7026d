"""The grouped products of ``ops/grouped_matmul.py`` in Pallas' interpreter
against a per-group ``einsum``: runs that are empty, shorter than a tile or
astride tiles, rows past the last run that hold NaNs, a width off the 128
lanes, both dtypes, the gradients of both operands, and the float32
accumulation over all of a group's rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops import grouped_matmul as gm

ROWS = 192
#: name -> rows a group; tiles are 64 rows, their skipped parts 16
COUNTS = {
    "an_empty_group": (40, 0, 64, 30),
    "a_run_shorter_than_a_tile": (5, 100, 3, 20),
    "a_run_astride_three_tiles": (30, 130, 0, 10),
    "every_row_landed": (64, 64, 32, 32),
    "nothing_landed": (0, 0, 0, 0),
}


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(gm, "ROW_TILE", 64)
    monkeypatch.setattr(gm, "SUB_ROWS", 16)


def _operands(counts, k, n, dtype, seed=0):
    rng = np.random.default_rng(seed)
    landed = sum(counts)
    lhs = rng.normal(size=(ROWS, k)).astype(np.float32)
    lhs[landed:] = np.nan               # what lies past the runs
    rhs = rng.normal(size=(len(counts), k, n)).astype(np.float32)
    return (jnp.asarray(lhs, dtype), jnp.asarray(rhs, dtype),
            jnp.asarray(counts, jnp.int32))


def _group_of_row(counts):
    return np.repeat(np.arange(len(counts)), counts)


def _plain(lhs, rhs, counts):
    """Each landed row times its group's matrix, zeros past the runs."""
    seg = _group_of_row(counts)
    f32 = jnp.float32
    out = jnp.einsum("rk,rkn->rn", lhs[:len(seg)].astype(f32),
                     rhs.astype(f32)[seg])
    return jnp.zeros((lhs.shape[0], rhs.shape[2]), f32).at[:len(seg)].set(out)


def _plain_transposed(lhs, rhs, counts):
    seg = jax.nn.one_hot(_group_of_row(counts), len(counts), dtype=jnp.float32)
    f32 = jnp.float32
    n = seg.shape[0]
    return jnp.einsum("re,rk,rn->ekn", seg, lhs[:n].astype(f32),
                      rhs[:n].astype(f32))


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-1) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("width", [128, 192], ids=["lanes", "off_lanes"])
@pytest.mark.parametrize("case", list(COUNTS))
def test_product_matches_the_einsum_a_group(case, width, dtype):
    """``width`` 192 is 1.5 lane tiles, as 1,856 is 14.5: the contraction
    and the output are then whole-dimension blocks."""
    counts = COUNTS[case]
    lhs, rhs, c = _operands(counts, width, 256 if width == 128 else width,
                            dtype)
    out = gm.grouped_matmul(lhs, rhs, c)
    assert out.dtype == dtype and not bool(jnp.isnan(out).any())
    np.testing.assert_allclose(out.astype(jnp.float32),
                               _plain(lhs, rhs, counts), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(COUNTS))
def test_transposed_right_hand_side_is_read_as_it_lies(case, dtype):
    counts = COUNTS[case]
    lhs, rhs, c = _operands(counts, 128, 256, dtype, seed=1)
    out = gm.grouped_matmul(lhs, jnp.swapaxes(rhs, 1, 2), c, True)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               _plain(lhs, rhs, counts), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("width", [128, 192], ids=["lanes", "off_lanes"])
@pytest.mark.parametrize("case", list(COUNTS))
def test_transposed_product_sums_a_group_s_rows(case, width, dtype):
    """(R, K)^T x (R, N) -> (E, K, N): zeros for a group without rows, no
    NaN from the rows past the runs on either side."""
    counts = COUNTS[case]
    lhs, _, c = _operands(counts, width, 128, dtype, seed=2)
    rhs, _, _ = _operands(counts, 256, 128, dtype, seed=3)
    out, = gm.grouped_transposed(c, lhs, [rhs], dtype)
    assert out.shape == (len(counts), width, 256)
    for e, rows in enumerate(counts):
        if not rows:
            assert not bool(jnp.any(out[e]))
    np.testing.assert_allclose(out.astype(jnp.float32),
                               _plain_transposed(lhs, rhs, counts),
                               **_tol(dtype))


def test_transposed_product_scales_the_right_hand_rows():
    counts = COUNTS["an_empty_group"]
    lhs, _, c = _operands(counts, 128, 128, jnp.float32, seed=4)
    rhs, _, _ = _operands(counts, 128, 128, jnp.float32, seed=5)
    scale = jnp.asarray(np.random.default_rng(6).uniform(
        0.5, 2.0, (ROWS, 1)), jnp.float32)
    out, = gm.grouped_transposed(c, lhs, [rhs], jnp.float32, scale=scale)
    np.testing.assert_allclose(
        out, _plain_transposed(lhs, rhs * scale, counts), rtol=1e-5,
        atol=1e-4)


@pytest.mark.parametrize("transposed", [False, True], ids=["nn", "nt"])
@pytest.mark.parametrize("case", ["an_empty_group",
                                  "a_run_astride_three_tiles"])
def test_gradients_of_both_operands(case, transposed):
    counts = COUNTS[case]
    lhs, rhs, c = _operands(counts, 128, 256, jnp.float32, seed=7)
    probe = jnp.asarray(np.random.default_rng(8).normal(size=(ROWS, 256)),
                        jnp.float32)
    finite = jnp.where(jnp.isnan(lhs), 0.0, lhs)

    def ours(lhs, rhs):
        w = jnp.swapaxes(rhs, 1, 2) if transposed else rhs
        return jnp.sum(gm.grouped_matmul(lhs, w, c, transposed) * probe)

    def plain(lhs, rhs):
        return jnp.sum(_plain(lhs, rhs, counts) * probe)

    got = jax.grad(ours, (0, 1))(lhs, rhs)
    want = jax.grad(plain, (0, 1))(finite, rhs)
    for g, w in zip(got, want):
        assert not bool(jnp.isnan(g).any())
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-3)


def test_accumulation_is_float32_over_all_of_a_group_s_rows():
    """400 rows of ones in bf16 sum to 400 a column; an accumulator in
    bf16 stalls at 256 (its spacing there is 2), and so would one rounded
    between a group's row tiles. The product over a contraction of 384
    alike."""
    bf = jnp.bfloat16
    counts = jnp.asarray([400, 48], jnp.int32)
    ones = jnp.ones((448, 128), bf)
    out, = gm.grouped_transposed(counts, ones, [ones], bf)
    assert float(out[0, 0, 0]) == 400.0 and float(out[1, 5, 7]) == 48.0

    def in_bf16(total, row):
        return (total + row).astype(bf), None

    stalled, _ = jax.lax.scan(in_bf16, jnp.zeros((), bf), jnp.ones((400,), bf))
    assert float(stalled) == 256.0

    wide = gm.grouped_matmul(jnp.ones((64, 384), bf),
                             jnp.ones((1, 384, 128), bf),
                             jnp.asarray([64], jnp.int32))
    assert float(wide[3, 9]) == 384.0


def test_products_share_a_call_and_an_epilogue():
    """Two products of one left-hand side and a transposed third of
    another, a column a row, the epilogue's row sums, and a first result
    added to the rows an index names (two picks of one row add up)."""
    counts = COUNTS["a_run_shorter_than_a_tile"]
    rng = np.random.default_rng(9)
    f32 = jnp.float32
    a, w1, c = _operands(counts, 128, 128, f32, seed=10)
    b, w2, _ = _operands(counts, 128, 128, f32, seed=11)
    col = jnp.asarray(rng.uniform(0.5, 2.0, (ROWS, 1)), f32)
    to_row = jnp.asarray(rng.integers(0, 24, ROWS), jnp.int32)

    def epilogue(prods, cols):
        p, q, r = prods
        return [cols[0] * p, q + r, p * r]

    added, second, sums = gm.grouped_products(
        c, [a, b], [(0, w1, False), (0, w2, False),
                    (1, jnp.swapaxes(w2, 1, 2), True)],
        epilogue, [f32], cols=[col], row_sum=True, add_to=(to_row, 24))
    landed = sum(counts)
    p, q, r = _plain(a, w1, counts), _plain(a, w2, counts), \
        _plain(b, w2, counts)
    np.testing.assert_allclose(second[:landed], (q + r)[:landed], rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(sums[:landed], jnp.sum(p * r, 1)[:landed],
                               rtol=1e-4, atol=1e-2)
    want = jnp.zeros((24, 128), f32).at[to_row[:landed]].add(
        (col * p)[:landed])
    assert not bool(jnp.isnan(added).any())
    np.testing.assert_allclose(added, want, rtol=1e-5, atol=1e-4)


def test_visits_follow_the_rows_that_landed():
    """At most ceil(landed / tile) + groups - 1 visits, none past the last
    run; a group without rows is visited only where its zeros are due."""
    for counts in COUNTS.values():
        c = jnp.asarray(counts, jnp.int32)
        landed = sum(counts)
        (offs, group, tile), n = gm._visits(c, ROWS // 64, 64, empty=False)
        n = int(n)
        assert n <= -(-landed // 64) + sum(1 for r in counts if r) - 1 \
            or n == 0
        seen = set()
        for v in range(n):
            e, m = int(group[v]), int(tile[v])
            assert counts[e] > 0 and m * 64 < landed
            lo, hi = int(offs[e]), int(offs[e + 1])
            seen.update(range(max(lo, m * 64), min(hi, (m + 1) * 64)))
        assert seen == set(range(landed))
        _, n_all = gm._visits(c, ROWS // 64, 64, empty=True)
        assert int(n_all) == n + sum(1 for r in counts if not r)


# ------------------------------ the chip's compiler, without the chip

@pytest.fixture(scope="module")
def one_chip():
    """A described TPU v5e (compile-only): Mosaic and the TPU compiler are
    installed here though no chip is attached."""
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("cell", ["trinity", "joyai", "nemotron"])
def test_the_cells_expert_blocks_compile_for_the_v5e(cell, one_chip,
                                                     monkeypatch, request):
    """Forward and backward of one held expert block at a cell's real
    widths (8,192 tokens; 65,536 sorted picks over 16 SwiGLU experts, or
    49,152 over 8 relu^2 experts of 1,856 = 14.5 lane tiles, which the
    kernels take though the layer's form rule does not send them there)
    through the chip's own compiler: the six Mosaic calls are in the
    program, and what they hold in VMEM fits."""
    import re
    from bigdl_tpu.parallel import expert
    monkeypatch.undo()                  # the real tiles
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without one: keep it out
    from jax.experimental.compilation_cache import compilation_cache
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    request.addfinalizer(lambda: jax.config.update(
        "jax_enable_compilation_cache", cached))
    d, h, e, r = {"trinity": (2048, 1024, 16, 65536),
                  "joyai": (2048, 768, 16, 65536),
                  "nemotron": (2688, 1856, 8, 49152)}[cell]
    t, bf = 8192, jnp.bfloat16
    gated = cell != "nemotron"

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w = {"w1": spec((e, d, h), bf), "w2": spec((e, h, d), bf)}
    if gated:
        w["wg"] = spec((e, d, h), bf)
    assert expert.takes_kernel("tpu", w, spec((t, d), bf)) == gated

    def activate(hid, gate=None):
        if gate is None:
            return jnp.square(jax.nn.relu(hid))
        return jax.nn.silu(gate) * hid

    def step(w, x, gate, tok, counts, probe):
        def loss(w, x, gate):
            return jnp.sum(expert._grouped_kernel(
                activate, 512, w, x, gate, tok, counts) * probe)
        return jax.value_and_grad(loss, (0, 1, 2))(w, x, gate)

    text = jax.jit(step).lower(
        w, spec((t, d), bf), spec((r,), jnp.float32), spec((r,), jnp.int32),
        spec((e,), jnp.int32), spec((t, d), jnp.float32)).compile().as_text()
    assert set(re.findall(r"moe_gmm[a-z_]*", text)) >= {
        "moe_gmm_hidden", "moe_gmm_out", "moe_gmm_bwd", "moe_gmm_t_hidden",
        "moe_gmm_t_out", "moe_gmm_dx"}


@pytest.mark.parametrize("length", [8192, 1024],
                         ids=["the-cell", "its-reference-check"])
def test_the_mamba_local_calls_compile_for_the_v5e(length, one_chip,
                                                   monkeypatch, request):
    """One Mamba-2 mixer at the Nemotron cell's widths, forward and backward
    under ``jax.checkpoint``, through the chip's own compiler (here beside
    the other real-width compiles: one file, one worker, one libtpu): the
    four ``mamba_local_*`` Mosaic calls are in the program with the scan's,
    what they hold in VMEM fits, ``zxbcdt``'s cotangent is ONE buffer (no
    pad, no concatenate of its width), and no copy of that width stands
    between the in-projection and the calls."""
    import re
    from bigdl_tpu import nn
    from bigdl_tpu.nn.module import functional_apply
    from bigdl_tpu.ops import mamba_local
    from bigdl_tpu.utils.rng import manual_seed
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from jax.experimental.compilation_cache import compilation_cache
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    request.addfinalizer(lambda: jax.config.update(
        "jax_enable_compilation_cache", cached))
    manual_seed(1)
    m = nn.Mamba2(2688, num_heads=64, head_dim=64, state_size=128,
                  n_groups=8, conv_kernel=4, chunk_size=128)
    bf = jnp.bfloat16
    assert mamba_local.takes_kernel("tpu", bf, length, m.d_inner, 1024, 8, 4)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, bf, sharding=one_chip),
        m.parameter_tree())
    u = jax.ShapeDtypeStruct((1, length, 2688), bf, sharding=one_chip)

    def loss(p, u):
        mixer = jax.checkpoint(lambda p, u: functional_apply(
            m, p, m.buffer_tree(), u, training=True)[0])
        return jnp.sum(mixer(p, mixer(p, u)).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1))).lower(params, u).compile(
        ).as_text()
    assert set(re.findall(r"mamba_local_[a-z_]*", text)) >= {
        "mamba_local_conv", "mamba_local_gate", "mamba_local_gate_bwd",
        "mamba_local_conv_bwd"}
    assert "ssd_fwd_out" in text and "ssd_bwd_out" in text
    wide = rf"bf16\[(?:1,)?{length},10304\]"
    assert not re.findall(rf"= {wide}\S* (?:pad|concatenate|copy)\(", text)
    # none carries the operand by which the flash readers know a flash call
    # in that cell (``builders/nemotron_h.flash_shape``: 32 heads of 128)
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "mamba_local_" in line]
    assert len(calls) >= 8
    assert not [c for c in calls if f"bf16[32,{length},128]" in c]


@pytest.mark.parametrize("heads,length,d_k,d_v", [
    (15, 8192, 96, 192), (30, 8192, 96, 192), (2, 1024, 32, 64),
    (2, 1024, 128, 256)], ids=["the-cell", "every-head-held",
                               "the-rules-least", "the-rules-most"])
def test_the_delta_rule_calls_compile_for_the_v5e(heads, length, d_k, d_v,
                                                  one_chip, monkeypatch,
                                                  request):
    """The gated delta rule as ``nn.GatedDeltaNet`` calls it at the
    Olmo-Hybrid cell's shape (15 heads of 96 / 192 over 8,192 tokens), with
    all 30 published heads held and at the least and the largest heads
    ``ops.delta_rule.takes_kernel`` admits, forward and backward through
    the chip's own compiler (here beside the other real-width compiles:
    one file, one worker, one libtpu): ``delta_rule_fwd`` and
    ``delta_rule_bwd`` are in the program, a head of 96 tiles, and what a
    grid cell holds in VMEM fits."""
    from bigdl_tpu.ops import delta_rule
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from jax.experimental.compilation_cache import compilation_cache
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    request.addfinalizer(lambda: jax.config.update(
        "jax_enable_compilation_cache", cached))
    bf, f32 = jnp.bfloat16, jnp.float32
    assert delta_rule.takes_kernel("tpu", bf, f32, 64, d_k, d_v)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, g, beta):
        return jnp.sum(delta_rule.gated_delta_rule(q, k, v, g, beta, 64)
                       .astype(f32))

    qk, gate = spec((1, length, heads, d_k), bf), spec((1, length, heads), f32)
    text = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        qk, qk, spec((1, length, heads, d_v), bf), gate, gate).compile(
        ).as_text()
    assert "delta_rule_fwd" in text and "delta_rule_bwd" in text
    # the one residual beside the inputs: each chunk's start state
    assert f"f32[1,{heads},{length // 64},{d_k},{d_v}]" in text


@pytest.mark.parametrize("heads", [15, 30],
                         ids=["the-cell", "every-head-held"])
def test_the_delta_local_calls_compile_for_the_v5e(heads, one_chip,
                                                   monkeypatch, request):
    """One gated delta-rule mixer at the Olmo-Hybrid cell's widths (15
    heads of 96 / 192 over 8,192 tokens, and all 30 published), forward
    and backward under ``jax.checkpoint``, through the chip's own compiler
    (here beside the other real-width compiles: one file, one worker, one
    libtpu): the four ``delta_local_*`` Mosaic calls are in the program
    with the recurrence's two, what they hold in VMEM fits, and NOTHING is
    copied between them: the calls write and read (B, H, L, d) and XLA
    cancels the transposed views that carry the methods' (B, L, H, d)
    against ``ops.delta_rule``'s own, so no array of a head-major shape is
    the result of a copy, a transpose or a fusion."""
    import re
    from bigdl_tpu import nn
    from bigdl_tpu.nn.module import functional_apply
    from bigdl_tpu.ops import delta_local
    from bigdl_tpu.utils.rng import manual_seed
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from jax.experimental.compilation_cache import compilation_cache
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    request.addfinalizer(lambda: jax.config.update(
        "jax_enable_compilation_cache", cached))
    manual_seed(1)
    length, bf = 8192, jnp.bfloat16
    m = nn.GatedDeltaNet(256, heads, 96, 192, conv_kernel=4,
                         allow_neg_eigval=True, chunk_size=64)
    assert delta_local.takes_kernel("tpu", bf, length, heads, 96, 192, 4)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, bf, sharding=one_chip),
        m.parameter_tree())
    u = jax.ShapeDtypeStruct((1, length, 256), bf, sharding=one_chip)

    def loss(p, u):
        mixer = jax.checkpoint(lambda p, u: functional_apply(
            m, p, m.buffer_tree(), u, training=True)[0])
        return jnp.sum(mixer(p, u).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1))).lower(params, u).compile(
        ).as_text()
    assert set(re.findall(r"delta_local_[a-z_]*", text)) >= {
        "delta_local_conv", "delta_local_gate", "delta_local_gate_bwd",
        "delta_local_conv_bwd"}
    assert "delta_rule_fwd" in text and "delta_rule_bwd" in text
    entry = text[text.index("\nENTRY"):]
    head_major = rf"bf16\[1,{heads},{length},(?:96|192)\]"
    assert not re.findall(
        rf"= {head_major}\S* (?:copy|transpose|fusion)\(", entry)
    # nor is the cotangent of the in-projection's output put together in
    # HBM: the backward products read its parts
    wide = rf"bf16\[(?:1,)?{length},{m.conv_dim + m.d_value + 2 * heads}\]"
    assert not re.findall(rf"= {wide}\S* (?:pad|concatenate|copy)\(", entry)
