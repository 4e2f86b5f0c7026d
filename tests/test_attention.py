"""Attention stack tests: torch oracle for MHA/LayerNorm, internal
consistency for the blockwise (flash) formulation and the Pallas kernel in
interpret mode. New capability — no reference analogue (SURVEY §5.7)."""

import numpy as np
import pytest

torch = pytest.importorskip("torch")

import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.ops import attention_core as ac
from bigdl_tpu.ops.flash_attention import flash_attention

RTOL, ATOL = 2e-4, 2e-4


def _rand(*shape):
    return np.random.randn(*shape).astype(np.float32)


class TestLayerNorm:
    def test_forward_vs_torch(self):
        m = nn.LayerNorm(16)
        m.weight = jnp.asarray(_rand(16))
        m.bias = jnp.asarray(_rand(16))
        x = _rand(4, 7, 16)
        t = torch.nn.LayerNorm(16)
        with torch.no_grad():
            t.weight.copy_(torch.from_numpy(np.asarray(m.weight)))
            t.bias.copy_(torch.from_numpy(np.asarray(m.bias)))
        np.testing.assert_allclose(
            np.asarray(m.forward(jnp.asarray(x))),
            t(torch.from_numpy(x)).detach().numpy(), rtol=RTOL, atol=ATOL)


class TestDotProductAttention:
    def test_vs_torch_sdpa(self):
        b, s, n, d = 2, 9, 3, 8
        q, k, v = _rand(b, s, n, d), _rand(b, s, n, d), _rand(b, s, n, d)
        out = ac.dot_product_attention(*map(jnp.asarray, (q, k, v)))
        ref = torch.nn.functional.scaled_dot_product_attention(
            *(torch.from_numpy(x).permute(0, 2, 1, 3) for x in (q, k, v)))
        np.testing.assert_allclose(np.asarray(out),
                                   ref.permute(0, 2, 1, 3).numpy(),
                                   rtol=RTOL, atol=ATOL)

    def test_causal_vs_torch(self):
        b, s, n, d = 2, 11, 2, 8
        q, k, v = _rand(b, s, n, d), _rand(b, s, n, d), _rand(b, s, n, d)
        out = ac.dot_product_attention(*map(jnp.asarray, (q, k, v)),
                                       causal=True)
        ref = torch.nn.functional.scaled_dot_product_attention(
            *(torch.from_numpy(x).permute(0, 2, 1, 3) for x in (q, k, v)),
            is_causal=True)
        np.testing.assert_allclose(np.asarray(out),
                                   ref.permute(0, 2, 1, 3).numpy(),
                                   rtol=RTOL, atol=ATOL)

    def test_mask(self):
        b, s, n, d = 1, 6, 2, 4
        q, k, v = _rand(b, s, n, d), _rand(b, s, n, d), _rand(b, s, n, d)
        mask = np.tril(np.ones((s, s), bool))[None, None]
        masked = ac.dot_product_attention(*map(jnp.asarray, (q, k, v)),
                                          mask=jnp.asarray(mask))
        causal = ac.dot_product_attention(*map(jnp.asarray, (q, k, v)),
                                          causal=True)
        np.testing.assert_allclose(np.asarray(masked), np.asarray(causal),
                                   rtol=1e-6, atol=1e-6)

    def test_fully_masked_row_is_zero(self):
        b, s, n, d = 1, 5, 2, 4
        q, k, v = (jnp.asarray(_rand(b, s, n, d)) for _ in range(3))
        mask = np.ones((1, 1, s, s), bool)
        mask[..., 2, :] = False  # query row 2 attends nothing
        for fn in (lambda: ac.dot_product_attention(q, k, v,
                                                    mask=jnp.asarray(mask)),
                   lambda: ac.blockwise_attention(q, k, v,
                                                  mask=jnp.asarray(mask),
                                                  block_size=2)):
            out = np.asarray(fn())
            np.testing.assert_allclose(out[:, 2], 0.0, atol=1e-6)
            assert np.abs(out[:, 1]).max() > 0

    def test_causal_alignment_consistent_sq_ne_sk(self):
        # All three cores must agree on top-left causal alignment.
        b, sq, sk, n, d = 1, 3, 6, 2, 4
        q = jnp.asarray(_rand(b, sq, n, d))
        k, v = (jnp.asarray(_rand(b, sk, n, d)) for _ in range(2))
        plain = ac.dot_product_attention(q, k, v, causal=True)
        blk = ac.blockwise_attention(q, k, v, causal=True, block_size=2)
        fl = flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                             interpret=True)
        np.testing.assert_allclose(np.asarray(blk), np.asarray(plain),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(fl), np.asarray(plain),
                                   rtol=1e-5, atol=1e-5)


class TestBlockwiseAttention:
    @pytest.mark.parametrize("s,block,causal", [
        (16, 4, False), (17, 4, False), (16, 4, True), (23, 8, True),
        (8, 16, False),  # block > seq
    ])
    def test_matches_plain(self, s, block, causal):
        b, n, d = 2, 2, 8
        q, k, v = (jnp.asarray(_rand(b, s, n, d)) for _ in range(3))
        plain = ac.dot_product_attention(q, k, v, causal=causal)
        blk = ac.blockwise_attention(q, k, v, causal=causal, block_size=block)
        np.testing.assert_allclose(np.asarray(blk), np.asarray(plain),
                                   rtol=1e-5, atol=1e-5)

    def test_grad_matches(self):
        b, s, n, d = 1, 12, 2, 4
        q, k, v = (jnp.asarray(_rand(b, s, n, d)) for _ in range(3))

        def loss_plain(q):
            return jnp.sum(ac.dot_product_attention(q, k, v, causal=True) ** 2)

        def loss_blk(q):
            return jnp.sum(ac.blockwise_attention(
                q, k, v, causal=True, block_size=4) ** 2)

        np.testing.assert_allclose(np.asarray(jax.grad(loss_blk)(q)),
                                   np.asarray(jax.grad(loss_plain)(q)),
                                   rtol=1e-4, atol=1e-4)


class TestFlashKernel:
    @pytest.mark.parametrize("s,causal", [(32, False), (32, True), (40, True)])
    def test_interpret_matches_plain(self, s, causal):
        b, n, d = 2, 2, 8
        q, k, v = (jnp.asarray(_rand(b, s, n, d)) for _ in range(3))
        plain = ac.dot_product_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(plain),
                                   rtol=1e-5, atol=1e-5)

    def test_grad(self):
        b, s, n, d = 1, 16, 1, 8
        q, k, v = (jnp.asarray(_rand(b, s, n, d)) for _ in range(3))

        def loss_flash(q):
            return jnp.sum(flash_attention(q, k, v, causal=True, block_q=8,
                                           block_k=8, interpret=True) ** 2)

        def loss_plain(q):
            return jnp.sum(ac.dot_product_attention(q, k, v, causal=True) ** 2)

        np.testing.assert_allclose(np.asarray(jax.grad(loss_flash)(q)),
                                   np.asarray(jax.grad(loss_plain)(q)),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("s,causal", [(20, False), (20, True)])
    def test_bwd_kernel_ragged_seq_not_block_multiple(self, s, causal):
        # seq NOT a multiple of block_q: padded query rows carry the LSE
        # sentinel and must be masked in the dK/dV kernel — regression for
        # the inf*0=NaN path (round-3 review finding)
        b, n, d = 1, 2, 8
        q, k, v = (jnp.asarray(_rand(b, s, n, d)) for _ in range(3))
        g = jnp.asarray(_rand(b, s, n, d))

        def run(f):
            _, vjp = jax.vjp(f, q, k, v)
            return vjp(g)

        ref = run(lambda q_, k_, v_: ac.dot_product_attention(
            q_, k_, v_, causal=causal))
        got = run(lambda q_, k_, v_: flash_attention(
            q_, k_, v_, causal=causal, block_q=16, block_k=16,
            interpret=True))
        for r, o, name in zip(ref, got, "qkv"):
            assert np.isfinite(np.asarray(o)).all(), f"d{name} has NaN/inf"
            np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"d{name} mismatch")

    @pytest.mark.parametrize("s,causal", [(32, False), (32, True), (40, True),
                                          (24, False)])
    def test_bwd_kernel_all_grads_match_plain(self, s, causal):
        # The Pallas dQ and dK/dV kernels (not the XLA recompute fallback)
        # against autodiff through the plain formulation, ragged seqs incl.
        b, n, d = 2, 2, 8
        q, k, v = (jnp.asarray(_rand(b, s, n, d)) for _ in range(3))
        g = jnp.asarray(_rand(b, s, n, d))

        def run(f):
            _, vjp = jax.vjp(f, q, k, v)
            return vjp(g)

        ref = run(lambda q_, k_, v_: ac.dot_product_attention(
            q_, k_, v_, causal=causal))
        got = run(lambda q_, k_, v_: flash_attention(
            q_, k_, v_, causal=causal, block_q=8, block_k=8, interpret=True))
        for r, o, name in zip(ref, got, "qkv"):
            np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"d{name} mismatch")

    @pytest.mark.parametrize("d,dv", [(64, 64), (96, 64), (192, 128)])
    def test_value_head_may_differ_from_the_query_key_head(self, d, dv):
        """q and k ``d`` wide over a v ``dv`` wide (latent attention's 192
        over 128), causal, on a sequence that is no whole number of tiles:
        output, LSE and all three gradients against the masked XLA core,
        whose scale is the same 1/sqrt(d). Differing sizes carry their own
        kernel names and form; equal ones the names they had."""
        from bigdl_tpu.ops import flash_attention as fa
        from bigdl_tpu.telemetry import get_registry, instruments
        b, s, n = 1, 40, 2
        q, k = (jnp.asarray(_rand(b, s, n, d)) for _ in range(2))
        v, g = (jnp.asarray(_rand(b, s, n, dv)) for _ in range(2))
        gl = jnp.asarray(_rand(b, n, s))

        def kernel(q_, k_, v_):
            return fa.flash_attention_with_lse(
                q_, k_, v_, causal=True, block_q=16, block_k=16,
                interpret=True)

        def run(f):
            out, vjp = jax.vjp(f, q, k, v)
            return out + vjp((g, gl))

        form = "mla" if d != dv else "full"
        count = instruments(get_registry()).flash_attention_total.labels(
            form=form)
        before = count.value
        got = run(kernel)
        assert count.value == before + 1
        assert got[0].shape == (b, s, n, dv) and got[4].shape == v.shape
        for r, o, name in zip(run(lambda *t: _attention_and_lse(*t, True)),
                              got, ("o", "lse", "dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"{name} mismatch")
        names = [e.params["name"] for e in _eqns(jax.make_jaxpr(
            lambda *t: run(kernel))().jaxpr)
            if e.primitive.name == "pallas_call"]
        stem = "flash_mla_" if d != dv else "flash_"
        # one backward call: the dK/dV kernel, which also returns dQ
        assert names == [stem + "fwd", stem + "bwd_dkv"]
        if d != dv:
            with pytest.raises(ValueError, match="window"):
                fa.flash_attention(q, k, v, causal=True, window=8,
                                   interpret=True)

    def test_lse_value_and_cotangent(self):
        from bigdl_tpu.ops.flash_attention import flash_attention_with_lse
        b, s, n, d = 1, 24, 2, 8
        q, k, v = (jnp.asarray(_rand(b, s, n, d)) for _ in range(3))
        scale = 1.0 / d ** 0.5

        def ref_lse(q_):
            logits = jnp.einsum("bqnd,bknd->bnqk", q_, k) * scale
            return jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)

        _, lse = flash_attention_with_lse(q, k, v, block_q=8, block_k=8,
                                          interpret=True)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse(q)),
                                   rtol=1e-5, atol=1e-5)
        # LSE is a first-class differentiable output: a loss through lse
        # alone must match autodiff through the reference logsumexp
        def loss_kernel(q_):
            _, l = flash_attention_with_lse(q_, k, v, block_q=8, block_k=8,
                                            interpret=True)
            return jnp.sum(jnp.sin(l))

        def loss_ref(q_):
            return jnp.sum(jnp.sin(ref_lse(q_)))

        np.testing.assert_allclose(np.asarray(jax.grad(loss_kernel)(q)),
                                   np.asarray(jax.grad(loss_ref)(q)),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("s,with_lse", [(512, False), (768, True)])
    def test_halved_diagonal_tile_matches_plain(self, s, with_lse):
        # Square 256-tiles on an unpadded causal sequence: the tile the
        # diagonal crosses runs as two half-height strips, each against
        # only the keys it can see (the path the on-chip defaults take on
        # an unpadded causal sequence). Output, LSE and all three gradients
        # against autodiff through the plain formulation.
        from bigdl_tpu.ops import flash_attention as fa
        assert fa._halved_diagonal(True, s, s, 256, 256)
        assert not fa._halved_diagonal(True, s + 8, s + 8, 256, 256)
        b, n, d = 1, 2, 8
        q, k, v, g = (jnp.asarray(_rand(b, s, n, d)) for _ in range(4))
        gl = jnp.asarray(_rand(b, n, s) * with_lse)

        def plain(q_, k_, v_):
            return _attention_and_lse(q_, k_, v_, True)

        def kernel(q_, k_, v_):
            return fa.flash_attention_with_lse(
                q_, k_, v_, causal=True, block_q=256, block_k=256,
                interpret=True)

        def run(f):
            out, vjp = jax.vjp(f, q, k, v)
            return out + vjp((g, gl))

        for r, o, name in zip(run(plain), run(kernel),
                              ("o", "lse", "dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"{name} mismatch")


def _attention_and_lse(q, k, v, causal):
    """(o, lse) by the XLA core's recipe in the dtype of q: the oracle (on
    float32 inputs) and the baseline (on bfloat16) of the kernel tests."""
    o = ac.dot_product_attention(q, k, v, causal=causal)
    logits = (jnp.einsum("bqnd,bknd->bnqk", q, k)
              * (1.0 / q.shape[-1] ** 0.5)).astype(jnp.float32)
    if causal:
        keep = jnp.tril(jnp.ones(logits.shape[-2:], bool))
        logits = jnp.where(keep, logits, jnp.finfo(jnp.float32).min)
    return o, jax.nn.logsumexp(logits, axis=-1)


def _sub_jaxprs(eqn):
    for val in eqn.params.values():
        for item in (val if isinstance(val, (list, tuple)) else (val,)):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def _eqns(jaxpr):
    """Every equation of a jaxpr, loops, branches and kernels included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in _sub_jaxprs(eqn):
            yield from _eqns(inner)


@pytest.mark.parametrize("sq,sk,d,itemsize,want", [
    (2048, 2048, 64, 2, 1024),      # the LM cell
    (1024, 1024, 64, 2, 1024),      # chip_smoke's 134M LM
    (8192, 8192, 64, 2, 1024),
    (4096, 4096, 128, 2, 1024),
    (8192, 8192, 128, 2, 512),      # did not compile at 1024: VMEM
    (4096, 4096, 128, 4, 512),      # float32: the same
    (2048, 2048, 256, 2, 512),      # causal did not compile at 1024
    (3000, 3000, 64, 2, 512),       # padded: every tile masked, 11% slower
    (2048, 4096, 64, 2, 512),       # oblong: no halved diagonal
    (8192, 8192, (192, 128), 2, 512),   # latent: q/k 192 over v 128
    (2048, 2048, (192, 128), 2, 512),   # not compiled at 1024: not taken
])
def test_forward_tile_follows_the_call(sq, sk, d, itemsize, want):
    """The forward's default tile is 1024 only where a v5e compile and the
    on-chip times say so (PERF.md section 6, PR 24); everything else keeps
    the 512 the backward kernels and the parent use."""
    from bigdl_tpu.ops import flash_attention as fa
    d, dv = d if isinstance(d, tuple) else (d, d)
    assert fa._fwd_block(sq, sk, d, dv, itemsize) == want


@pytest.mark.parametrize("sq,sk,d,itemsize,causal,window,want", [
    (2048, 2048, 64, 2, True, None, 1024),      # the LM cell
    (1024, 1024, 64, 2, True, None, 1024),      # chip_smoke's 134M LM
    (8192, 8192, 128, 2, True, None, 1024),     # the two hybrid cells
    (8192, 8192, (192, 128), 2, True, None, 1024),   # latent: 64 MiB named
    (8192, 8192, 128, 2, True, 2048, 1024),     # Trinity's band: two tiles
    (16384, 16384, 128, 2, True, 4096, 1024),   # SmallThinker's: four
    (8192, 8192, 128, 2, True, 1024, 512),      # ONE 1024-tile: not measured
    (8192, 8192, 128, 2, True, 2560, 512),      # whole 512s, no whole 1024s
    (8192, 8192, 128, 2, True, 2000, 512),      # an edge at an angle: masked
    (8200, 8200, 128, 2, True, 2048, 512),      # padded: masked
    (65536, 65536, 128, 2, True, 4096, 512),    # VMEM, as without a band
    (8192, 8192, 128, 2, False, None, 512),     # no diagonal to halve
    (3000, 3000, 64, 2, True, None, 512),       # padded: every tile masked
    (2048, 4096, 64, 2, True, None, 512),       # oblong
    (1536, 1536, 64, 2, True, None, 512),       # no whole number of 1024s
    (32768, 32768, 128, 2, True, None, 512),    # VMEM: the row's dQ, q, dO
])
def test_backward_tile_follows_the_call(sq, sk, d, itemsize, causal, window,
                                        want):
    """The backward's default tile is 1024 x 1024 only where the edges run
    as strips at that size (the causal diagonal and, under a band of at
    least two such tiles, its far edge: ``_edge_strips``) and the call's
    VMEM estimate stays under what it may name (PERF.md section 6, PR 37
    and PR 40); else 512 x 512. The limit the call names follows its
    shapes and is never under the 16 MiB default."""
    from bigdl_tpu.ops import flash_attention as fa
    d, dv = d if isinstance(d, tuple) else (d, d)
    assert fa._bwd_block(sq, sk, d, dv, itemsize, causal, window) == want
    named = fa._bwd_vmem(sq, d, dv, want, want, itemsize)
    assert named >= 16 << 20
    if want == 1024:
        assert named <= fa._VMEM_MOST
    # over what lies whole in VMEM alone: q and dO twice, dQ's float32
    # accumulator and its block twice (a head of 192 as 256 lanes)
    whole = sq * (2 * (fa._lanes(d) + fa._lanes(dv)) * itemsize
                  + fa._lanes(d) * (4 + 2 * itemsize))
    assert named > min(whole, (16 << 20) - 1)


@pytest.mark.parametrize("s,d,window,want", [
    (8192, 128, 2048, 1024),        # Trinity's band
    (16384, 128, 4096, 1024),       # SmallThinker's: its limit is named
    (8192, 128, 1024, 512),         # ONE 1024-tile: not measured
    (8192, 128, 2560, 512),         # whole 512s, no whole 1024s
    (8192, 128, 2000, 512),         # an edge at an angle
    (8200, 128, 2048, 512),         # padded
    (1024, 64, 512, 512),           # the rehearsal's size
    (1 << 18, 128, 4096, 512),      # K and V whole would pass the limit
])
def test_banded_forward_tile_follows_the_backwards(s, d, window, want):
    """Under a band the forward's default tile is the backward's: 1024
    where the edges run as strips at it, the window is at least two such
    tiles and the limit named from the shapes is one the call may name
    (PERF.md section 6, PR 40); else 512. So one decision says how a
    call's edges run in both kernels."""
    from bigdl_tpu.ops import flash_attention as fa
    x = jax.ShapeDtypeStruct((1, s, 2, d), jnp.bfloat16)
    assert fa._fwd_tiles(x, x, x, None, None, True, window) == (want, want)
    if s < 1 << 18:
        assert fa._bwd_block(s, s, d, d, 2, True, window) == want
    assert fa._fwd_tiles(x, x, x, 256, 256, True, window) == (256, 256)


class TestFlashKernelDtypeContract:
    """What the three kernels hand the MXU follows the caller's dtype:
    bfloat16 in, bfloat16 operands and float32 accumulators; float32 in,
    float32 throughout. The jaxpr guards the contract and the casts it
    saves, not MXU passes: on the v5e an up-cast tile measured the same
    time and gave the same bits (PERF.md section 6, PR 24), and no run on a
    CPU would show one coming back."""

    HEAD = 8
    # the kernel keeps float32 logits where the XLA core rounds them to
    # bfloat16, so it is usually the closer of the two; 1.5x the core's
    # own error, plus a bfloat16 ulp of the largest value, is the bound
    FACTOR, FLOOR = 1.5, 2.0 ** -8

    @pytest.mark.parametrize("with_lse", [False, True],
                             ids=["out_only", "lse_cotangent"])
    @pytest.mark.parametrize("s,block", [(48, 16), (40, 16), (512, 256)],
                             ids=["block_multiple", "ragged", "on_chip_tiles"])
    @pytest.mark.parametrize("causal", [True, False],
                             ids=["causal", "full"])
    def test_bf16_as_close_to_f32_oracle_as_xla_bf16(self, causal, s, block,
                                                     with_lse):
        from bigdl_tpu.ops.flash_attention import flash_attention_with_lse
        rng = np.random.RandomState(s + 2 * causal + with_lse)
        b, n, d = 2, 2, self.HEAD
        q, k, v, g = (jnp.asarray(rng.randn(b, s, n, d), jnp.bfloat16)
                      for _ in range(4))
        gl = jnp.asarray(rng.randn(b, n, s) * with_lse, jnp.float32)

        def run(attend, *xs):
            (o, lse), vjp = jax.vjp(attend, *xs)
            return (o, lse) + tuple(vjp((g.astype(o.dtype), gl)))

        def kernel(q_, k_, v_):
            return flash_attention_with_lse(
                q_, k_, v_, causal=causal, block_q=block, block_k=block,
                interpret=True)

        def core(q_, k_, v_):
            return _attention_and_lse(q_, k_, v_, causal)

        oracle = run(core, *(x.astype(jnp.float32) for x in (q, k, v)))
        got, base = run(kernel, q, k, v), run(core, q, k, v)
        for name, a, x, ref in zip(("o", "lse", "dq", "dk", "dv"),
                                   got, base, oracle):
            a, x, ref = (np.asarray(t, np.float32) for t in (a, x, ref))
            assert np.isfinite(a).all(), f"{name} has NaN/inf"
            top = np.abs(ref).max()
            err, err_core = np.abs(a - ref).max(), np.abs(x - ref).max()
            assert err <= self.FACTOR * err_core + self.FLOOR * top, (
                f"{name}: kernel {err:.3e} against the XLA core's "
                f"{err_core:.3e} (largest value {top:.3e})")

    @pytest.mark.parametrize("block,s", [(16, 40), (256, 512)],
                             ids=["masked_tiles", "halved_diagonal"])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                             ids=["bf16", "f32"])
    def test_mxu_operands_follow_input_dtype(self, dtype, block, s):
        from bigdl_tpu.ops.flash_attention import flash_attention_with_lse
        b, n, d = 1, 2, self.HEAD
        x = jnp.zeros((b, s, n, d), dtype)

        def grads(q, k, v, g, gl):
            _, vjp = jax.vjp(lambda *a: flash_attention_with_lse(
                *a, causal=True, block_q=block, block_k=block,
                interpret=True), q, k, v)
            return vjp((g, gl))

        jaxpr = jax.make_jaxpr(grads)(
            x, x, x, x, jnp.zeros((b, n, s), jnp.float32)).jaxpr
        calls = {e.params["name"]: e.params["jaxpr"] for e in _eqns(jaxpr)
                 if e.primitive.name == "pallas_call"}
        assert sorted(calls) == ["flash_bwd_dkv", "flash_fwd"]
        # the one backward call: k q^T, v dO^T, ds^T k (dQ), ds q, p dO
        dots = {"flash_fwd": 2, "flash_bwd_dkv": 5}
        for name, body in calls.items():
            eqns = list(_eqns(body))
            found = [e for e in eqns if e.primitive.name == "dot_general"]
            # every traced tile body (plain, masked, half strips) holds all
            assert found and len(found) % dots[name] == 0, (name, len(found))
            for e in found:
                assert [t.aval.dtype for t in e.invars] == [dtype, dtype], (
                    f"{name}: MXU operands "
                    f"{[str(t.aval.dtype) for t in e.invars]}")
                assert e.outvars[0].aval.dtype == jnp.float32
            upcast = [e for e in eqns
                      if e.primitive.name == "convert_element_type"
                      and e.params["new_dtype"] == jnp.float32
                      and e.invars[0].aval.dtype != jnp.float32
                      and e.invars[0].aval.shape[-1:] == (d,)]
            assert not upcast, f"{name}: a (rows, D) tile is up-cast"


class TestMultiHeadAttention:
    def test_self_attention_vs_torch(self):
        e, n, b, s = 16, 4, 2, 7
        m = nn.MultiHeadAttention(e, n)
        t = torch.nn.MultiheadAttention(e, n, batch_first=True)
        with torch.no_grad():
            t.in_proj_weight.copy_(
                torch.from_numpy(np.asarray(m.in_proj_weight)))
            t.in_proj_bias.copy_(torch.from_numpy(np.asarray(m.in_proj_bias)))
            t.out_proj.weight.copy_(
                torch.from_numpy(np.asarray(m.out_proj_weight)))
            t.out_proj.bias.copy_(
                torch.from_numpy(np.asarray(m.out_proj_bias)))
        x = _rand(b, s, e)
        out = np.asarray(m.forward(jnp.asarray(x)))
        ref, _ = t(*(torch.from_numpy(x),) * 3, need_weights=False)
        np.testing.assert_allclose(out, ref.detach().numpy(),
                                   rtol=RTOL, atol=ATOL)

    def test_causal_matches_torch_mask(self):
        e, n, b, s = 8, 2, 1, 5
        m = nn.MultiHeadAttention(e, n, causal=True)
        t = torch.nn.MultiheadAttention(e, n, batch_first=True)
        with torch.no_grad():
            t.in_proj_weight.copy_(
                torch.from_numpy(np.asarray(m.in_proj_weight)))
            t.in_proj_bias.copy_(torch.from_numpy(np.asarray(m.in_proj_bias)))
            t.out_proj.weight.copy_(
                torch.from_numpy(np.asarray(m.out_proj_weight)))
            t.out_proj.bias.copy_(
                torch.from_numpy(np.asarray(m.out_proj_bias)))
        x = _rand(b, s, e)
        am = torch.triu(torch.full((s, s), float("-inf")), diagonal=1)
        ref, _ = t(*(torch.from_numpy(x),) * 3, attn_mask=am,
                   need_weights=False)
        out = np.asarray(m.forward(jnp.asarray(x)))
        np.testing.assert_allclose(out, ref.detach().numpy(),
                                   rtol=RTOL, atol=ATOL)

    def test_cross_attention_table(self):
        from bigdl_tpu.utils.table import T
        e, n = 8, 2
        m = nn.MultiHeadAttention(e, n)
        q, kv = _rand(2, 3, e), _rand(2, 6, e)
        out = m.forward(T(jnp.asarray(q), jnp.asarray(kv), jnp.asarray(kv)))
        assert out.shape == (2, 3, e)

    def test_per_batch_mask_flows_through_input(self):
        # A mask passed in the input Table must vary across jitted calls
        # (set_mask state would be baked in as a trace constant).
        from bigdl_tpu.nn.module import functional_apply
        e, n, b, s = 8, 2, 1, 4
        m = nn.MultiHeadAttention(e, n)
        params, buffers = m.parameter_tree(), m.buffer_tree()
        x = jnp.asarray(_rand(b, s, e))

        @jax.jit
        def f(p, bufs, x, mask):
            y, _ = functional_apply(m, p, bufs, (x, x, x, mask),
                                    training=False)
            return y

        full = np.ones((1, 1, s, s), bool)
        causal = np.tril(full)
        out_full = f(params, buffers, x, jnp.asarray(full))
        out_causal = f(params, buffers, x, jnp.asarray(causal))
        assert np.abs(np.asarray(out_full) - np.asarray(out_causal)).max() > 1e-5
        ref = nn.MultiHeadAttention(e, n, causal=True)
        ref.load_parameter_tree(params)
        np.testing.assert_allclose(np.asarray(out_causal),
                                   np.asarray(ref.forward(x)),
                                   rtol=1e-5, atol=1e-5)


class TestTransformerEncoder:
    def test_shapes_and_jit(self):
        from bigdl_tpu.nn.module import functional_apply
        enc = nn.TransformerEncoder(2, 16, 4, 32, causal=True)
        x = jnp.asarray(_rand(2, 10, 16))
        out = enc.forward(x)
        assert out.shape == (2, 10, 16)
        params, buffers = enc.parameter_tree(), enc.buffer_tree()

        @jax.jit
        def f(p, b, x):
            y, _ = functional_apply(enc, p, b, x, training=False)
            return y

        np.testing.assert_allclose(np.asarray(f(params, buffers, x)),
                                   np.asarray(out), rtol=1e-5, atol=1e-5)

    def test_grad_flows(self):
        from bigdl_tpu.nn.module import functional_apply
        enc = nn.TransformerEncoderLayer(8, 2, 16)
        x = jnp.asarray(_rand(1, 4, 8))
        params, buffers = enc.parameter_tree(), enc.buffer_tree()

        def loss(p):
            y, _ = functional_apply(enc, p, buffers, x, training=False)
            return jnp.sum(y ** 2)

        g = jax.grad(loss)(params)
        leaves = jax.tree_util.tree_leaves(g)
        assert all(np.all(np.isfinite(np.asarray(l))) for l in leaves)
        assert any(float(jnp.abs(l).max()) > 0 for l in leaves)

    def test_positional_encoding(self):
        pe = nn.PositionalEncoding(16, max_len=32)
        x = jnp.zeros((1, 10, 16))
        out = np.asarray(pe.forward(x))
        # position 0: sin(0)=0, cos(0)=1 alternating
        np.testing.assert_allclose(out[0, 0, 0::2], 0.0, atol=1e-6)
        np.testing.assert_allclose(out[0, 0, 1::2], 1.0, atol=1e-6)

    def test_positional_encoding_odd_dim(self):
        pe = nn.PositionalEncoding(15, max_len=8)
        assert pe.forward(jnp.zeros((1, 4, 15))).shape == (1, 4, 15)


class TestMoETransformerLayer:
    def test_moe_ffn_shapes_and_grads(self):
        from bigdl_tpu import nn as _nn
        from bigdl_tpu.nn.module import functional_apply
        layer = _nn.TransformerEncoderLayer(16, 2, 32, moe_experts=4)
        x = jnp.asarray(_rand(2, 8, 16))
        out = layer.forward(x)
        assert out.shape == (2, 8, 16)
        params = layer.parameter_tree()
        assert "moe" in params and "linear1" not in params

        def loss(p):
            y, _ = functional_apply(layer, p, layer.buffer_tree(), x,
                                    training=True)
            return jnp.sum(y ** 2)

        g = jax.grad(loss)(params)
        # routed experts and the gate both receive gradient
        assert float(jnp.abs(g["moe"]["w1"]).max()) > 0
        assert float(jnp.abs(g["moe"]["gate_weight"]).max()) > 0

    def test_moe_lm_builds_and_runs(self):
        from bigdl_tpu.models import transformer
        m = transformer.build_lm(32, embed_dim=16, num_heads=2, ffn_dim=32,
                                 num_layers=1, max_len=16, moe_experts=4)
        out = m.forward(jnp.ones((2, 8)))
        assert out.shape == (2, 8, 32)


class TestAttentionProbDropout:
    """Round-4 fix: dropout applies to the normalised attention
    PROBABILITIES (torch nn.MultiheadAttention semantics), not the output
    projection. Statistical oracle: inverted-scale dropout is unbiased, so
    the MEAN of many training forwards must converge to the eval forward,
    while individual draws must differ."""

    def _mha(self, p):
        from bigdl_tpu.nn.attention import MultiHeadAttention
        from bigdl_tpu.utils.rng import manual_seed
        manual_seed(11)
        return MultiHeadAttention(16, 4, dropout=p, causal=True)

    def test_mean_converges_to_eval_output(self):
        import jax
        import numpy as np
        from bigdl_tpu.nn.module import functional_apply
        m = self._mha(0.5)
        x = np.random.default_rng(0).normal(0, 1, (2, 6, 16)).astype("f4")
        m.evaluate_mode()
        ref = np.asarray(m.forward(x))
        m.training_mode()
        params, buffers = m.functional_state()
        outs = []
        for i in range(400):
            out, _ = functional_apply(m, params, buffers, x, training=True,
                                      rng=jax.random.PRNGKey(i))
            outs.append(np.asarray(out))
        outs = np.stack(outs)
        # draws genuinely differ (dropout active)...
        assert np.abs(outs[0] - outs[1]).max() > 1e-4
        # ...and are unbiased around the eval output: SE ~ sigma/sqrt(400)
        err = np.abs(outs.mean(0) - ref)
        tol = 4 * outs.std(0) / np.sqrt(400) + 1e-4
        assert (err < tol).mean() > 0.98, (
            f"mean-vs-eval deviation beyond 4 SE for "
            f"{(err >= tol).mean():.1%} of outputs")

    def test_eval_mode_is_deterministic_and_dropout_free(self):
        import numpy as np
        m = self._mha(0.5)
        x = np.random.default_rng(1).normal(0, 1, (1, 5, 16)).astype("f4")
        m.evaluate_mode()
        a, b = np.asarray(m.forward(x)), np.asarray(m.forward(x))
        np.testing.assert_array_equal(a, b)

    def test_dropout_rejects_context_parallel(self):
        import pytest
        from bigdl_tpu.nn.attention import MultiHeadAttention
        with pytest.raises(ValueError, match="context-parallel"):
            MultiHeadAttention(16, 4, dropout=0.1, seq_axis="seq")

    def test_grads_flow_through_dropout(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from bigdl_tpu.nn.module import functional_apply
        m = self._mha(0.3)
        m.training_mode()
        params, buffers = m.functional_state()
        x = jnp.asarray(np.random.default_rng(2).normal(0, 1, (1, 4, 16)),
                        jnp.float32)

        def loss(p):
            out, _ = functional_apply(m, p, buffers, x, training=True,
                                      rng=jax.random.PRNGKey(0))
            return jnp.sum(out ** 2)

        g = jax.grad(loss)(params)
        total = sum(float(jnp.abs(leaf).sum())
                    for leaf in jax.tree_util.tree_leaves(g))
        assert np.isfinite(total) and total > 0


# ------------------------------------------------- the band in the kernels

def _banded_and_lse(q, k, v, window):
    """(o, lse) of the masked XLA core under the mask the band stands for:
    query i sees the keys (i - window, i]."""
    s = q.shape[1]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    keep = (j <= i) & (j > i - window)
    o = ac.dot_product_attention(q, k, v, mask=keep, causal=False)
    logits = jnp.einsum("bqnd,bknd->bnqk", q, k) / q.shape[-1] ** 0.5
    logits = jnp.where(keep, logits, jnp.finfo(jnp.float32).min)
    return o, jax.nn.logsumexp(logits, axis=-1)


def _pallas_calls(f, *args):
    return [e.params["name"] for e in _eqns(jax.make_jaxpr(f)(*args).jaxpr)
            if e.primitive.name == "pallas_call"]


class TestFlashBand:
    """The three kernels told of a sliding window, in the interpreter,
    against the masked XLA core: output, LSE and all three gradients."""

    @pytest.mark.parametrize("s,block,window", [
        (48, 16, 8),        # the band's edge inside a tile, shorter than one
        (48, 16, 16),       # the edge on a tile's edge
        (48, 16, 20),       # inside a tile, longer than one
        (64, 16, 32),       # on a tile's edge, two tiles
        (64, 16, 33),
        (40, 16, 7),        # a padded last tile
        (37, 16, 5),
        (48, 8, 17),
        (48, 16, 47),       # one key short of the sequence
        (48, 16, 1),        # a query sees itself alone
        (512, 256, 300),    # the tiles that would halve the diagonal
        # every case above keeps the all-masked loop (an edge that cuts
        # tiles at an angle, a padded call or a tile of no whole lane
        # groups); below, a window of whole lane-group tiles on a square
        # unpadded call: both edge tiles as two strips, the tiles between
        # unmasked. The first `window / block` query tiles have no lower
        # edge and the last `window / block` key tiles no far one.
        (1024, 256, 256),   # window == block: the two edge tiles are neighbours
        (1024, 256, 512),   # one tile between them
        (1536, 256, 1024),  # three; four of six key tiles have no far edge
        (2048, 512, 1024),  # the cells' tile
    ])
    def test_band_matches_the_masked_core(self, s, block, window):
        from bigdl_tpu.ops import flash_attention as fa
        from bigdl_tpu.ops.flash_attention import flash_attention_with_lse
        from bigdl_tpu.telemetry import get_registry, instruments
        strips = block % 256 == 0 and s % block == 0 and window % block == 0
        assert fa._edge_strips(True, s, s, block, block, window) == strips
        edges = instruments(get_registry()).flash_band_edges_total
        before = {e: edges.labels(edges=e).value
                  for e in ("strips", "masked")}
        rng = np.random.RandomState(s + window)
        q, k, v, g = (jnp.asarray(rng.randn(2, s, 2, 8), jnp.float32)
                      for _ in range(4))
        gl = jnp.asarray(rng.randn(2, 2, s), jnp.float32)

        def kernel(q_, k_, v_):
            return flash_attention_with_lse(
                q_, k_, v_, causal=True, block_q=block, block_k=block,
                interpret=True, window=window)

        def run(f):
            out, vjp = jax.vjp(f, q, k, v)
            return out + vjp((g, gl))

        want = run(lambda *t: _banded_and_lse(*t, window))
        got = run(kernel)
        # one call, counted once by how its edges run
        assert {e: edges.labels(edges=e).value - before[e]
                for e in before} == {"strips": int(strips),
                                     "masked": int(not strips)}
        for r, o, name in zip(want, got, ("o", "lse", "dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"{name} mismatch")
        assert sorted(set(_pallas_calls(lambda *t: run(kernel), q))) == [
            "flash_band_bwd_dkv", "flash_band_fwd"]

    @pytest.mark.parametrize("window", [48, 49, 1000])
    def test_a_window_that_cuts_nothing_is_the_full_call(self, window):
        """Equal to the sequence or longer: the same kernels, names and
        bits as the call that names no window."""
        from bigdl_tpu.ops.flash_attention import flash_attention_with_lse
        rng = np.random.RandomState(window)
        q, k, v = (jnp.asarray(rng.randn(1, 48, 2, 8), jnp.float32)
                   for _ in range(3))

        def f(w):
            return lambda *t: flash_attention_with_lse(
                *t, causal=True, block_q=16, block_k=16, interpret=True,
                window=w)

        for a, b in zip(f(window)(q, k, v), f(None)(q, k, v)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert _pallas_calls(f(window), q, k, v) == ["flash_fwd"]

    def test_a_band_skips_the_tiles_below_it(self):
        """Tiles wholly below the band are never read, as those above the
        diagonal are not (a mask alone would let 0 * NaN through): NaN keys
        far below a query's window reach neither its output nor its dQ,
        and NaN queries far past a key's reach neither its dK nor its dV."""
        s, block, window = 64, 16, 16
        rng = np.random.RandomState(0)
        q, k, v, g = (jnp.asarray(rng.randn(1, s, 1, 8), jnp.float32)
                      for _ in range(4))

        def f(q_, k_, v_):
            return flash_attention(q_, k_, v_, causal=True, block_q=block,
                                   block_k=block, interpret=True,
                                   window=window)

        # queries from 48 see keys from 33: key tile [0, 16) is skipped
        out, vjp = jax.vjp(f, q, k.at[:, :16].set(jnp.nan), v)
        assert np.isfinite(np.asarray(out[:, 48:])).all()
        assert np.isfinite(np.asarray(vjp(g)[0][:, 48:])).all()
        # keys before 16 are seen by queries before 31: query tile
        # [48, 64) is skipped
        _, vjp = jax.vjp(f, q.at[:, 48:].set(jnp.nan), k, v)
        _, dk, dv = vjp(g)
        assert np.isfinite(np.asarray(dk[:, :16])).all()
        assert np.isfinite(np.asarray(dv[:, :16])).all()

    def test_strips_read_only_the_keys_they_can_see(self):
        """The aligned twin: where the band's edges run as strips, a strip
        reads neither the half of its edge tile that none of its rows can
        see nor a tile outside the band. Query tile 4 of 256 under a
        window of 512 meets key tile 2 (its lower edge), 3 and 4 (the
        diagonal); key tile 1 meets query tile 1 (the diagonal), 2 and 3
        (its far edge). The LAST row of a query tile sees nothing of its
        lower-edge tile and still reads a strip of it: its p = 1 against
        _NEG there is wiped by the next tile (``update``'s comment)."""
        from bigdl_tpu.ops import flash_attention as fa
        s, block, window = 1536, 256, 512
        h = block // 2
        assert fa._edge_strips(True, s, s, block, block, window)
        rng = np.random.RandomState(1)
        q, k, v, g = (jnp.asarray(rng.randn(1, s, 1, 8), jnp.float32)
                      for _ in range(4))

        def f(q_, k_, v_):
            return flash_attention(q_, k_, v_, causal=True, block_q=block,
                                   block_k=block, interpret=True,
                                   window=window)

        def poisoned(x, *spans):
            for lo, hi in spans:
                x = x.at[:, lo:hi].set(jnp.nan)
            return x

        want = f(q, k, v)
        # query rows, and the keys they may not read: below the band and
        # the blind half of the lower-edge tile, the blind half of the
        # diagonal tile and every tile above it
        for rows, spans in (
                (slice(4 * block, 4 * block + h),       # the upper strips
                 ((0, 2 * block), (4 * block + h, s))),
                (slice(4 * block + h, 5 * block),       # the lower strips
                 ((0, 2 * block + h), (5 * block, s)))):
            out, vjp = jax.vjp(f, q, poisoned(k, *spans),
                               poisoned(v, *spans))
            np.testing.assert_array_equal(np.asarray(out[:, rows]),
                                          np.asarray(want[:, rows]))
            assert np.isfinite(np.asarray(vjp(g)[0][:, rows])).all()
        # the last row of a query tile: all of its lower-edge strip is
        # masked for it, and it is the masked core's row all the same
        ref = _banded_and_lse(q, k, v, window)[0]
        last = np.arange(block - 1, s, block)
        np.testing.assert_allclose(np.asarray(want)[:, last],
                                   np.asarray(ref)[:, last],
                                   rtol=2e-4, atol=2e-4)
        # key rows, and the queries they may not read
        for rows, spans in (
                (slice(block, block + h),               # the first half
                 ((0, block), (3 * block + h, s))),
                (slice(block + h, 2 * block),           # the second half
                 ((0, block + h), (4 * block, s)))):
            _, vjp = jax.vjp(f, poisoned(q, *spans), k, v)
            _, dk, dv = vjp(poisoned(g, *spans))
            assert np.isfinite(np.asarray(dk[:, rows])).all()
            assert np.isfinite(np.asarray(dv[:, rows])).all()

    @staticmethod
    def _five(case, monkeypatch):
        """(jaxpr text digest, digest of o, LSE, dK and dV, then o, LSE,
        dQ, dK and dV in float32) of one banded call, the digests as
        ``test_a_call_without_a_band_is_the_code_it_was`` takes them."""
        import hashlib
        from bigdl_tpu.ops import flash_attention as fa
        monkeypatch.setattr(fa, "keep", lambda value, name: value)
        sq, sk, block, window, dtype, d = case
        rng = np.random.RandomState(sq + sk + block + window)
        q, g = (jnp.asarray(rng.randn(1, sq, 2, d), dtype) for _ in range(2))
        k, v = (jnp.asarray(rng.randn(1, sk, 2, d), dtype) for _ in range(2))
        gl = jnp.asarray(rng.randn(1, 2, sq), jnp.float32)

        def run(q, k, v, g, gl):
            out, vjp = jax.vjp(lambda *t: fa.flash_attention_with_lse(
                *t, causal=True, block_q=block, block_k=block,
                interpret=True, window=window), q, k, v)
            return out + vjp((g, gl))

        def digest(*arrays):
            h = hashlib.sha256()
            for t in arrays:
                h.update(np.asarray(t, np.float32).tobytes())
            return h.hexdigest()[:16]

        text = str(jax.make_jaxpr(run)(q, k, v, g, gl))
        o, lse, g_q, g_k, g_v = run(q, k, v, g, gl)
        return (hashlib.sha256(text.encode()).hexdigest()[:16],
                digest(o, lse, g_k, g_v)) + tuple(
                    np.asarray(t, np.float32) for t in (o, lse, g_q, g_k, g_v))

    @pytest.mark.parametrize("case,jaxpr,kept,dq", [
        ((512, 512, 256, 300, "float32", 8), "5ad3aa81803cacec",
         "a178ef237bd6e8b8", "f11a568ff61bf1ea"),   # an edge at an angle
        ((520, 520, 256, 256, "float32", 8), "5ab138c93aea41d6",
         "47d65ef89c1a89b0", "34e26d7799c996f9"),   # padded
        ((512, 768, 256, 256, "float32", 8), "37c7319af7497a4a",
         "8c31f05d2c497a82", "8066149e3fc8fdbe"),   # more keys than queries
        ((1024, 1024, 128, 256, "bfloat16", 8), "40201c526585cde3",
         "bbcbf703a2267246", "8aabde311e5b9c4e"),   # half a lane group
    ])
    def test_a_band_off_the_strips_is_the_code_it_was(self, case, jaxpr, kept,
                                                      dq, monkeypatch):
        """A banded call whose window is no whole number of its tiles, a
        padded one, one that is not square and one whose tile's halves are
        no whole lane groups keep the all-masked loop: the jaxpr and the
        bits of all five results are those taken at the parent commit (git
        09f11b7, jax 0.9.0; (sq, sk, tile, window, dtype, head))."""
        import hashlib
        from bigdl_tpu.ops import flash_attention as fa
        sq, sk, block, window = case[:4]
        assert not fa._edge_strips(True, sq, sk, block, block, window)
        text, four, _, _, g_q, _, _ = self._five(case, monkeypatch)
        assert (text, four) == (jaxpr, kept)
        assert hashlib.sha256(g_q.tobytes()).hexdigest()[:16] == dq

    @pytest.mark.parametrize("case,jaxpr,kept,dq,within", [
        ((1024, 1024, 256, 512, "float32", 16), "cbd18f744e717a03",
         "7cfdf97c4d0be00a", "32dd7ae864af7c45", 2 ** -22),
        ((1024, 1024, 512, 512, "bfloat16", 16), "4c035afd4c138b4a",
         "bab1b853e49d3d4c", "a45988034a0f1b66", 2 ** -8),
        ((1536, 1536, 256, 1024, "bfloat16", 16), "9166232fca41c193",
         "bb74c149b640ab3d", "52f0e7490b9daebe", 2 ** -8),
    ])
    def test_an_aligned_band_is_within_a_rounding_of_what_it_gave(
            self, case, jaxpr, kept, dq, within, monkeypatch):
        """The digests are the PARENT's (git 09f11b7, the all-masked loop,
        jax 0.9.0), and they are what this tree gives once the strips are
        refused: text and all five results, checked first, so the
        all-masked loop is still the parent's code for an aligned call too
        and stands here for the parent. With the edges as strips every
        result is within a rounding or two of its dtype of the parent's:
        ``within`` times the array's largest value (float32 2^-22: read
        2.7e-7 on a dQ of 1.7, 9.5e-7 on an LSE of 8; bf16 one place,
        2^-8: read 3.9e-3 on a dQ of 1.3, 2.0e-3 on an o of 1.9). Where
        the differences come from: the same visible pairs enter the
        softmax, a masked pair gave p = 0 and is now not computed, but (1)
        the forward takes the lower-edge tile LAST, in one online-softmax
        step with the diagonal's, where the parent took it first and
        alone: a row's maximum, sum and accumulator are rescaled in
        another grouping, so o and the LSE move in the last place, and dK,
        dV and dQ with the LSE they are computed from; (2) an edge tile's
        ``ds k`` enters dQ's float32 accumulator as two sums over half the
        keys each where it was one sum over the tile (alone it moves dQ by
        2.5e-7 of 1.7 and leaves the other four the parent's bits)."""
        import hashlib
        from bigdl_tpu.ops import flash_attention as fa
        sq, sk, block, window = case[:4]
        assert fa._edge_strips(True, sq, sk, block, block, window)
        got = self._five(case, monkeypatch)
        assert got[0] != jaxpr
        monkeypatch.setattr(fa, "_edge_strips", lambda *a: False)
        was = self._five(case, monkeypatch)
        assert was[:2] == (jaxpr, kept)
        assert hashlib.sha256(was[4].tobytes()).hexdigest()[:16] == dq
        for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got[2:],
                              was[2:]):
            assert np.max(np.abs(a - b)) <= within * np.max(np.abs(b)), name

    @pytest.mark.parametrize("case,jaxpr,kept,dq", [
        ((48, 16, True, "float32"), "adb161f16fe780ce", "d45df316299c977b",
         "e0ee15b507af613e"),
        ((40, 16, True, "float32"), "914a5c73ee6ba65b", "d08b4a9443b08d56",
         "b4413983a27f1098"),
        ((48, 16, False, "float32"), "b17090be3bbadd8e", "62da69dffe8bd488",
         "2c06ebd776ff16ec"),
        ((512, 256, True, "bfloat16"), "49ecefa2bd54e948",
         "758369b7ce09ae27", "733d1a73d24ed7a7"),
    ])
    def test_a_call_without_a_band_is_the_code_it_was(self, case, jaxpr,
                                                      kept, dq, monkeypatch):
        """The jaxpr of the band-less forward and backward and the bits
        they give: masked, padded, full and halved-diagonal calls. ``kept``
        is the digest of o, the LSE, dK and dV, the bits the commit before
        the band gave (git a75b99c, jax 0.9.0) and every commit since (read
        again at 52d54b9, whose digest over all five was the one taken at
        a75b99c). The text and dQ's bits were taken anew when the two
        backward calls became one (PR 37: dQ leaves the dK/dV kernel, as
        ds^T k on the transposed tile; the halved-diagonal case still
        gives the dQ kernel's bits). The forward rule's two tags for block
        remat (``ops.remat.keep`` on ``o`` and ``lse``, identities that
        name a value) are taken out of the text."""
        import hashlib
        from bigdl_tpu.ops import flash_attention as fa
        from bigdl_tpu.ops.flash_attention import flash_attention_with_lse
        monkeypatch.setattr(fa, "keep", lambda value, name: value)
        s, block, causal, dtype = case
        rng = np.random.RandomState(s + block + causal)
        q, k, v, g = (jnp.asarray(rng.randn(1, s, 2, 8), dtype)
                      for _ in range(4))
        gl = jnp.asarray(rng.randn(1, 2, s), jnp.float32)

        def run(q, k, v, g, gl):
            out, vjp = jax.vjp(lambda *t: flash_attention_with_lse(
                *t, causal=causal, block_q=block, block_k=block,
                interpret=True), q, k, v)
            return out + vjp((g, gl))

        text = str(jax.make_jaxpr(run)(q, k, v, g, gl))
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == jaxpr
        o, lse, g_q, g_k, g_v = run(q, k, v, g, gl)

        def digest(*arrays):
            h = hashlib.sha256()
            for t in arrays:
                h.update(np.asarray(t, np.float32).tobytes())
            return h.hexdigest()[:16]

        assert digest(o, lse, g_k, g_v) == kept
        assert digest(g_q) == dq

    def test_band_needs_causal_and_a_positive_window(self):
        x = jnp.zeros((1, 16, 1, 8))
        with pytest.raises(ValueError):
            flash_attention(x, x, x, causal=False, window=4, interpret=True)
        with pytest.raises(ValueError):
            flash_attention(x, x, x, causal=True, window=0, interpret=True)

    def test_window_layers_take_the_kernel_and_build_no_mask(self,
                                                             monkeypatch):
        """On a TPU backend a windowed ``MultiHeadAttention`` goes through
        the banded kernels (no (S, S) tensor in its jaxpr), counts
        ``form=band``; an arbitrary mask still takes the XLA core."""
        from bigdl_tpu.ops import flash_attention as fa
        from bigdl_tpu.telemetry import get_registry, instruments
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        real = fa.flash_attention
        monkeypatch.setattr(fa, "flash_attention", lambda *a, **kw: real(
            *a, **dict(kw, interpret=True)))
        s, e = 1024, 128
        m = nn.MultiHeadAttention(e, 2, causal=True, window=256,
                                  with_bias=False, head_dim=64)
        x = jnp.asarray(_rand(1, s, e))
        ins = instruments(get_registry())
        band0 = ins.flash_attention_total.labels(form="band").value
        masked0 = ins.flash_band_edges_total.labels(edges="masked").value
        jaxpr = jax.make_jaxpr(m.forward)(x).jaxpr
        assert ins.flash_attention_total.labels(form="band").value \
            == band0 + 1
        # a window of 256 under the default 512-tile: the all-masked loop
        assert ins.flash_band_edges_total.labels(edges="masked").value \
            == masked0 + 1
        names = [e_.params["name"] for e_ in _eqns(jaxpr)
                 if e_.primitive.name == "pallas_call"]
        assert names == ["flash_band_fwd"]
        top = [v.aval.shape for e_ in jaxpr.eqns for v in e_.outvars]
        assert not [sh for sh in top if sh[-2:] == (s, s)]
        assert fa.use_flash(x.reshape(1, s, 2, 64), None)
        assert not fa.use_flash(x.reshape(1, s, 2, 64),
                                jnp.ones((s, s), bool))
        banded = m.forward(x)
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        np.testing.assert_allclose(np.asarray(banded),
                                   np.asarray(m.forward(x)),
                                   rtol=2e-4, atol=2e-4)


class TestGatedNormedAttention:
    def test_qk_norm_gate_and_rope_by_hand(self):
        """``qk_norm`` norms each head of q and k BEFORE the rotation and
        ``gated`` multiplies the attention's output by the sigmoid of a
        fourth projection before the out-projection."""
        from bigdl_tpu.nn.attention import rope_rotate
        e, h, kv, d, s = 32, 4, 2, 8, 12
        m = nn.MultiHeadAttention(e, h, causal=True, with_bias=False,
                                  num_kv_heads=kv, head_dim=d, rope=True,
                                  qk_norm=True, qk_norm_eps=1e-5, gated=True)
        m.q_norm.weight = jnp.asarray(1.0 + 0.1 * _rand(d))
        m.k_norm.weight = jnp.asarray(1.0 + 0.1 * _rand(d))
        assert m.gate_proj_weight.shape == (h * d, e)
        x = jnp.asarray(_rand(2, s, e))
        w = m.in_proj_weight
        q = (x @ w[:h * d].T).reshape(2, s, h, d)
        k = (x @ w[h * d:(h + kv) * d].T).reshape(2, s, kv, d)
        v = (x @ w[(h + kv) * d:].T).reshape(2, s, kv, d)

        def norm(t, g):
            return t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True)
                                     + 1e-5) * g

        pos = jnp.arange(s)
        q = rope_rotate(norm(q, m.q_norm.weight), pos)
        k = rope_rotate(norm(k, m.k_norm.weight), pos)
        k, v = (jnp.repeat(t, h // kv, axis=2) for t in (k, v))
        ctx = ac.dot_product_attention(q, k, v, causal=True)
        ctx = ctx.reshape(2, s, h * d) \
            * jax.nn.sigmoid(x @ m.gate_proj_weight.T)
        np.testing.assert_allclose(np.asarray(m.forward(x)),
                                   np.asarray(ctx @ m.out_proj_weight.T),
                                   rtol=RTOL, atol=ATOL)

    def test_without_them_the_layer_has_the_parameters_it_had(self):
        m = nn.MultiHeadAttention(16, 4)
        assert sorted(m._parameters) == ["in_proj_bias", "in_proj_weight",
                                         "out_proj_bias", "out_proj_weight"]


# ------------------------------------------- one backward call: dQ, dK, dV

class TestOneBackwardCall:
    """The backward is ONE call, the dK/dV kernel, which accumulates dQ in
    a float32 VMEM buffer over a (batch, head) row's key tiles: zeroed at
    the first, scaled, cast and written at the last. Every case has two
    (batch, head) rows or more (a row's accumulator must not leak into the
    next), a non-zero LSE cotangent, and all but ``one_key_tile`` more than
    one key tile a row (the accumulator is carried)."""

    # as TestFlashKernelDtypeContract: against the float32 oracle no
    # further than 1.5x the bfloat16 XLA core, plus an ulp of the largest
    FACTOR, FLOOR = 1.5, 2.0 ** -8

    CASES = {
        # name: (sq, sk, d, dv, causal, window, block)
        "causal_halved_diagonal": (768, 768, 8, 8, True, None, 256),
        "causal_masked_tiles": (64, 64, 8, 8, True, None, 16),
        "non_causal": (48, 48, 8, 8, False, None, 16),
        "non_causal_oblong": (32, 80, 8, 8, False, None, 16),
        "band": (64, 64, 8, 8, True, 20, 16),
        "latent": (48, 48, 24, 16, True, None, 16),
        "padded_causal": (40, 40, 8, 8, True, None, 16),
        "padded_oblong": (37, 53, 8, 8, False, None, 16),
        "padded_band": (37, 37, 8, 8, True, 9, 16),
        "one_key_tile": (16, 16, 8, 8, True, None, 16),
    }

    PARENT_DKV = {      # case: (float32, bfloat16)
        "band": ("8f7f5f7f4bf1361d", "4707175de53ebc28"),
        "causal_halved_diagonal": ("394d45422e21b798", "9c34b38bd374344c"),
        "causal_masked_tiles": ("b45a1ee814e17f56", "3d7ad317a644fe01"),
        "latent": ("794176a462a4e4b5", "17f68d74aab66ebe"),
        "non_causal": ("648821f3251a662e", "794db281ddf4df6b"),
        "non_causal_oblong": ("82cadc3e8eec54ba", "3aff8aefd8a236fe"),
        "one_key_tile": ("7abfbc2a6c8ad353", "12c83b740ae9929e"),
        "padded_band": ("d656059a8830c61f", "20f81d24c8f4165a"),
        "padded_causal": ("bd689806add9bdb6", "1b12e5477230b0c1"),
        "padded_oblong": ("6389d6157aa2226b", "b5d1c7fc3b9ffb4e"),
    }

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_dq_dk_dv_of_the_one_call(self, case, dtype):
        import hashlib
        from bigdl_tpu.ops import flash_attention as fa
        sq, sk, d, dv, causal, window, block = self.CASES[case]
        rng = np.random.RandomState(sq + sk + d)
        b, n = 2, 2
        q = jnp.asarray(rng.randn(b, sq, n, d), dtype)
        k = jnp.asarray(rng.randn(b, sk, n, d), dtype)
        v = jnp.asarray(rng.randn(b, sk, n, dv), dtype)
        g = jnp.asarray(rng.randn(b, sq, n, dv), dtype)
        gl = jnp.asarray(rng.randn(b, n, sq), jnp.float32)
        scale = 1.0 / d ** 0.5

        def kernel(q_, k_, v_):
            return fa.flash_attention_with_lse(
                q_, k_, v_, causal=causal, block_q=block, block_k=block,
                interpret=True, window=window)

        def core(q_, k_, v_):
            # the masked XLA core and its LSE, in the dtype of q
            i = jnp.arange(sq)[:, None]
            j = jnp.arange(sk)[None, :]
            keep = jnp.ones((sq, sk), bool)
            if causal:
                keep = keep & (j <= i)
            if window is not None:
                keep = keep & (j > i - window)
            o = ac.dot_product_attention(q_, k_, v_, mask=keep, causal=False)
            logits = (jnp.einsum("bqnd,bknd->bnqk", q_, k_)
                      * scale).astype(jnp.float32)
            logits = jnp.where(keep, logits, jnp.finfo(jnp.float32).min)
            return o, jax.nn.logsumexp(logits, axis=-1)

        def grads(f, *xs):
            (o, lse), vjp = jax.vjp(f, *xs)
            return (o, lse) + tuple(vjp((g.astype(o.dtype), gl)))

        got = grads(kernel, q, k, v)
        assert _pallas_calls(lambda *t: grads(kernel, *t), q, k, v) == [
            fa._name("fwd", window, d != dv),
            fa._name("bwd_dkv", window, d != dv)]
        oracle = grads(core, *(x.astype(jnp.float32) for x in (q, k, v)))
        base = grads(core, q, k, v)
        for name, a, x, ref in zip(("o", "lse", "dq", "dk", "dv"),
                                   got, base, oracle):
            assert a.dtype == x.dtype and a.shape == x.shape, name
            a, x, ref = (np.asarray(t, np.float32) for t in (a, x, ref))
            assert np.isfinite(a).all(), f"{name} has NaN/inf"
            if dtype == jnp.float32:
                np.testing.assert_allclose(a, ref, rtol=2e-4, atol=2e-4,
                                           err_msg=f"{name} mismatch")
                continue
            top = np.abs(ref).max()
            err, err_core = np.abs(a - ref).max(), np.abs(x - ref).max()
            assert err <= self.FACTOR * err_core + self.FLOOR * top, (
                f"{name}: kernel {err:.3e} against the XLA core's "
                f"{err_core:.3e} (largest value {top:.3e})")
        # dK and dV to the bit: their products and their order of summation
        # are the two-call kernel's (digests of the parent's dK and dV on
        # these inputs, git 52d54b9, jax 0.9.0, as TestFlashBand's digests
        # are taken). dQ is not held so: its tile is now ds^T k on the
        # transposed (key, query) tile where the dQ kernel took ds k on a
        # (query, key) tile, and the inner order of a product's sum is the
        # backend's to choose (equal bits in 10 of these 20 cases)
        h = hashlib.sha256()
        for t in got[3:]:
            h.update(np.asarray(t, np.float32).tobytes())
        assert h.hexdigest()[:16] == self.PARENT_DKV[case][
            dtype == jnp.bfloat16], "dK, dV are not the two-call kernel's"


# ------------------------------- the forward from 16,384 keys of head 128

class TestFlashForwardPast16kKeys:
    """The forward holds K and V whole in VMEM. At 16,384 keys of head 128
    in bf16 those alone are the 16 MiB a call gets by default, so such a
    call names its limit from its own shapes (``_fwd_vmem``); every call
    the older cells make (2,048 to 8,192 keys) names none, as before."""

    def test_the_limit_is_named_from_the_calls_shapes(self):
        from bigdl_tpu.ops import flash_attention as fa
        # the accepted cells' calls: Qwen's 2,048 x 64 at 1024-tiles, the
        # 8k cells' 8,192 x 128 at 512-tiles, and the float32 tests
        assert fa._fwd_vmem(2048, 64, 64, 1024, 1024, 2) is None
        assert fa._fwd_vmem(8192, 128, 128, 512, 512, 2) is None
        assert fa._fwd_vmem(48, 8, 8, 16, 16, 4) is None
        at16k = fa._fwd_vmem(16384, 128, 128, 512, 512, 2)
        # K and V twice are 16 MiB; tiles, intermediates and a quarter on top
        assert 24 << 20 < at16k < 32 << 20
        at32k = fa._fwd_vmem(32768, 128, 128, 512, 512, 2)
        assert 44 << 20 < at32k < 52 << 20
        # never more than the call may name of the chip's 128 MiB
        assert fa._fwd_vmem(1 << 18, 128, 128, 512, 512, 2) == fa._VMEM_MOST
        # a head of 192 lies as 256 lanes
        assert fa._fwd_vmem(16384, 192, 128, 512, 512, 2) > at16k

    @pytest.mark.parametrize("window", [None, 132],
                             ids=["full", "band"])
    def test_more_key_tiles_than_the_old_limit_held(self, window):
        """33 key tiles a row (the old limit was 32 tiles of 512): output
        and LSE against the masked XLA core, and the gradients."""
        from bigdl_tpu.ops.flash_attention import flash_attention_with_lse
        s, block = 33 * 16, 16
        rng = np.random.RandomState(7)
        q, k, v, g = (jnp.asarray(rng.randn(1, s, 2, 8), jnp.float32)
                      for _ in range(4))
        gl = jnp.asarray(rng.randn(1, 2, s), jnp.float32)

        def kernel(q_, k_, v_):
            return flash_attention_with_lse(
                q_, k_, v_, causal=True, block_q=block, block_k=block,
                interpret=True, window=window)

        def run(f):
            out, vjp = jax.vjp(f, q, k, v)
            return out + vjp((g, gl))

        want = run(lambda *t: _banded_and_lse(*t, window or s))
        for r, o, name in zip(want, run(kernel),
                              ("o", "lse", "dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"{name} mismatch")
        names = ["flash_band_fwd"] if window else ["flash_fwd"]
        assert _pallas_calls(kernel, q, k, v) == names

    @pytest.fixture(scope="class")
    def one_chip(self):
        """A described TPU v5e (compile-only), as
        ``tests/test_grouped_matmul.py`` describes it."""
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

    @pytest.mark.parametrize("window", [None, 4096], ids=["full", "band"])
    def test_16384_keys_of_head_128_compile_for_the_v5e(
            self, window, one_chip, monkeypatch, request):
        """Forward and backward of one SmallThinker attention core at the
        cell's shape (28 heads x 16,384 x 128, bf16) through the chip's own
        compiler, under the names the readers find them by; without the
        named limit the forward is refused (16.25 MiB of 16)."""
        import re
        from bigdl_tpu.ops import flash_attention as fa
        from jax.experimental.compilation_cache import compilation_cache
        cached = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        request.addfinalizer(lambda: jax.config.update(
            "jax_enable_compilation_cache", cached))
        spec = jax.ShapeDtypeStruct((1, 16384, 28, 128), jnp.bfloat16,
                                    sharding=one_chip)

        def step(q, k, v):
            def loss(q, k, v):
                return jnp.sum(fa.flash_attention(
                    q, k, v, causal=True, window=window,
                    interpret=False).astype(jnp.float32))
            return jax.value_and_grad(loss, (0, 1, 2))(q, k, v)

        text = jax.jit(step).lower(spec, spec, spec).compile().as_text()
        band = "band_" if window else ""
        assert set(re.findall(r"flash_[a-z_]*", text)) >= {
            f"flash_{band}fwd", f"flash_{band}bwd_dkv"}
        monkeypatch.setattr(fa, "_fwd_vmem", lambda *a: None)
        with pytest.raises(Exception, match="vmem"):
            jax.jit(lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, window=window, interpret=False)
            ).lower(spec, spec, spec).compile()

    def test_8192_keys_of_head_64_at_batch_4_compile_for_the_v5e(
            self, one_chip, monkeypatch, request):
        """Forward and backward of the LFM2 cell's attention core at its
        shape (4 x 32 heads x 8,192 x 64, bf16: the kernels see (128, 8,192,
        64)) through the chip's own compiler. Both run at 1024-tiles: the
        forward names 23 MiB where Qwen's 2,048 keys of the same head name
        none, the backward 46 of the 100 MiB it may; without the forward's
        named limit the chip refuses it."""
        import re
        from bigdl_tpu.ops import flash_attention as fa
        from jax.experimental.compilation_cache import compilation_cache
        assert fa._fwd_block(8192, 8192, 64, 64, 2) == 1024
        assert fa._bwd_block(8192, 8192, 64, 64, 2, True, None) == 1024
        assert fa._fwd_vmem(2048, 64, 64, 1024, 1024, 2) is None
        assert 20 << 20 < fa._fwd_vmem(8192, 64, 64, 1024, 1024, 2) < 32 << 20
        assert 40 << 20 < fa._bwd_vmem(8192, 64, 64, 1024, 1024, 2) \
            < fa._VMEM_MOST
        cached = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        request.addfinalizer(lambda: jax.config.update(
            "jax_enable_compilation_cache", cached))
        spec = jax.ShapeDtypeStruct((4, 8192, 32, 64), jnp.bfloat16,
                                    sharding=one_chip)

        def step(q, k, v):
            def loss(q, k, v):
                return jnp.sum(fa.flash_attention(
                    q, k, v, causal=True, interpret=False
                ).astype(jnp.float32))
            return jax.value_and_grad(loss, (0, 1, 2))(q, k, v)

        text = jax.jit(step).lower(spec, spec, spec).compile().as_text()
        assert set(re.findall(r"flash_[a-z_]*", text)) >= {
            "flash_fwd", "flash_bwd_dkv"}
        assert "bf16[128,8192,64]" in text
        monkeypatch.setattr(fa, "_fwd_vmem", lambda *a: None)
        with pytest.raises(Exception, match="vmem"):
            jax.jit(lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, interpret=False)
            ).lower(spec, spec, spec).compile()
