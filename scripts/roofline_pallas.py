"""Best-effort HBM streaming roof via Pallas kernels (round 5, VERDICT #1).

Round 4's roofline rested on XLA-generated elementwise chains that reached
only copy 461 / triad 528 / read 623 GB/s — 56-76% of the v5e's ~819 GB/s
paper bandwidth. If the microbenchmark itself leaves that much on the table,
the "ResNet step moves bytes at the roof" cap argument is unsound. This
script measures the roof a hand-written kernel can reach:

1. ``auto``: grid-pipelined Pallas kernels (copy / read / triad). Pallas TPU
   auto-double-buffers block DMA between HBM and VMEM across grid steps, so
   this is already a double-buffered streaming loop; the sweep over block
   sizes finds the DMA-efficiency sweet spot.
2. ``manual``: explicit double-buffered ``make_async_copy`` loop (guide
   pattern, pallas_guide.md "Patterns: Double Buffering") with N in-flight
   buffers, as a cross-check that the auto pipeline isn't the limiter.

Timing uses the dependent-chain + scalar-fetch discipline from
``roofline_ab.py``.

Usage: python scripts/roofline_pallas.py [--gib 1] [--skip auto,manual]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fetch(x):
    import jax
    import jax.numpy as jnp
    leaf = jax.tree_util.tree_leaves(x)[0]
    return float(jnp.sum(leaf.astype(jnp.float32)))


def _timed_chain(fn, feed, *args, iters=5, warmup=2):
    out = fn(args[0], *args[1:])
    for _ in range(warmup - 1):
        out = fn(feed(out), *args[1:])
    _fetch(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(feed(out), *args[1:])
    _fetch(out)
    return (time.perf_counter() - t0) / iters


def _slope_timed(make_fn, feed, *args, k_small=4, k_large=24, iters=2):
    """Per-pass time with the fixed per-dispatch overhead cancelled: time a k_small-pass and a k_large-pass device-side chain and
    take the slope. ``make_fn(k)`` returns a jitted fn running k dependent
    passes."""
    ts = {}
    for k in (k_small, k_large):
        fn = make_fn(k)
        ts[k] = _timed_chain(fn, feed, *args, iters=iters, warmup=2)
    return (ts[k_large] - ts[k_small]) / (k_large - k_small)


def _calibrate():
    """Slope-based: per-matmul ms with RTT cancelled (clean ~6-9 ms), plus
    the fixed overhead itself so the session's RTT is visible."""
    import jax
    import jax.numpy as jnp
    a = jnp.ones((8192, 8192), jnp.bfloat16)

    def make(k):
        return jax.jit(lambda a: jax.lax.fori_loop(
            0, k, lambda i, t: t @ a, a))

    t2 = _timed_chain(make(2), lambda o: o, a, iters=2)
    t10 = _timed_chain(make(10), lambda o: o, a, iters=2)
    per = (t10 - t2) / 8
    fixed = t2 - 2 * per
    return per * 1e3, fixed * 1e3


# ---------------------------------------------------------------- auto grid

def _copy_kernel(in_ref, out_ref):
    out_ref[...] = in_ref[...]


def _read_kernel(seed_ref, in_ref, acc_ref):
    """seed makes each chained pass depend on the previous one, so XLA
    cannot hoist the (otherwise loop-invariant) read out of the timing
    loop."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = seed_ref[...]
    s = jnp.sum(in_ref[...].astype(jnp.float32))
    acc_ref[...] = acc_ref[...] + jnp.full((1, 1), s, jnp.float32)


def _triad_kernel(a_ref, b_ref, out_ref):
    import jax.numpy as jnp
    out_ref[...] = a_ref[...] + b_ref[...] * jnp.bfloat16(2)


def bench_auto(total_bytes, rows, lanes):
    """Grid-pipelined copy/read/triad at one (rows, lanes) bf16 block size."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    block_bytes = rows * lanes * 2
    nblocks = total_bytes // block_bytes
    shape = (nblocks * rows, lanes)
    x = jnp.ones(shape, jnp.bfloat16)
    y = jnp.full(shape, 0.5, jnp.bfloat16)

    spec = pl.BlockSpec((rows, lanes), lambda i: (i, 0))
    seed_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    copy_call = pl.pallas_call(
        _copy_kernel, grid=(nblocks,), in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(shape, jnp.bfloat16))
    read_call = pl.pallas_call(
        _read_kernel, grid=(nblocks,), in_specs=[seed_spec, spec],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32))
    triad_call = pl.pallas_call(
        _triad_kernel, grid=(nblocks,), in_specs=[spec, spec],
        out_specs=spec, out_shape=jax.ShapeDtypeStruct(shape, jnp.bfloat16))

    def make_copy(k):
        return jax.jit(lambda a: jax.lax.fori_loop(
            0, k, lambda i, t: copy_call(t), a))

    def make_read(k):
        return jax.jit(lambda s, a: jax.lax.fori_loop(
            0, k, lambda i, t: read_call(t, a), s))

    def make_triad(k):
        return jax.jit(lambda a, b: jax.lax.fori_loop(
            0, k, lambda i, t: triad_call(t, b), a))

    n = shape[0] * shape[1]
    out = {"block": f"{rows}x{lanes}"}
    for name, thunk, nbytes in (
        ("copy_gbps",
         lambda: _slope_timed(make_copy, lambda o: o, x), 2 * n * 2),
        ("read_gbps",
         lambda: _slope_timed(make_read, lambda o: o,
                              jnp.zeros((1, 1), jnp.float32), x), n * 2),
        ("triad_gbps",
         lambda: _slope_timed(make_triad, lambda o: o, x, y), 3 * n * 2),
    ):
        try:
            out[name] = round(nbytes / thunk() / 1e9, 1)
        except Exception as e:  # noqa: BLE001
            out[name] = "ERR:" + str(e)[:120]
    return out


# ------------------------------------------------------------- manual DMA

def _manual_copy_body(hbm_in, hbm_out, scratch, sems, *, nchunks, rows,
                      lanes, nbuf):
    """Explicit multi-buffered HBM->VMEM->HBM streaming copy."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def in_dma(slot, idx):
        return pltpu.make_async_copy(
            hbm_in.at[pl.ds(idx * rows, rows), :], scratch.at[slot],
            sems.at[slot, 0])

    def out_dma(slot, idx):
        return pltpu.make_async_copy(
            scratch.at[slot], hbm_out.at[pl.ds(idx * rows, rows), :],
            sems.at[slot, 1])

    for s in range(min(nbuf, nchunks)):
        in_dma(s, s).start()

    def loop(idx, _):
        slot = jax.lax.rem(idx, nbuf)
        in_dma(slot, idx).wait()
        out_dma(slot, idx).start()
        # refill this slot only after its drain completes: the refill DMA
        # writes the same VMEM buffer the out DMA is reading
        @pl.when(idx + nbuf < nchunks)
        def _():
            out_dma(slot, idx).wait()
            in_dma(slot, idx + nbuf).start()
        return _

    jax.lax.fori_loop(0, nchunks, loop, None)
    # tail: the last min(nbuf, nchunks) out-DMAs were started but not
    # waited inside the loop (their slot saw no refill)
    for s in range(min(nbuf, nchunks)):
        idx = nchunks - min(nbuf, nchunks) + s
        out_dma(jax.lax.rem(idx, nbuf), idx).wait()


def bench_manual(total_bytes, rows, lanes, nbuf=4):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_bytes = rows * lanes * 2
    nchunks = max(1, total_bytes // block_bytes)
    nbuf = min(nbuf, nchunks)
    shape = (nchunks * rows, lanes)
    x = jnp.ones(shape, jnp.bfloat16)

    def kernel(hbm_in, hbm_out, scratch, sems):
        _manual_copy_body(hbm_in, hbm_out, scratch, sems, nchunks=nchunks,
                          rows=rows, lanes=lanes, nbuf=nbuf)

    call = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(shape, jnp.bfloat16),
        scratch_shapes=[
            pltpu.VMEM((nbuf, rows, lanes), jnp.bfloat16),
            pltpu.SemaphoreType.DMA((nbuf, 2)),
        ],
    )
    def make(k):
        return jax.jit(lambda a: jax.lax.fori_loop(
            0, k, lambda i, t: call(t), a))

    t = _slope_timed(make, lambda o: o, x)
    n = shape[0] * shape[1]
    return {
        "block": f"{rows}x{lanes}", "nbuf": nbuf,
        "copy_gbps": round(2 * n * 2 / t / 1e9, 1),
    }


def bench_hbm_dma(total_bytes, nstreams=4):
    """HBM->HBM direct DMA copy — no VMEM bounce; nstreams concurrent
    engines over disjoint row ranges."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes = 1024
    rows = total_bytes // (2 * lanes)
    rows -= rows % (8 * nstreams)
    shape = (rows, lanes)
    chunk = rows // nstreams
    x = jnp.ones(shape, jnp.bfloat16)

    def kernel(hbm_in, hbm_out, sems):
        dmas = [
            pltpu.make_async_copy(
                hbm_in.at[pl.ds(s * chunk, chunk), :],
                hbm_out.at[pl.ds(s * chunk, chunk), :],
                sems.at[s])
            for s in range(nstreams)
        ]
        for d in dmas:
            d.start()
        for d in dmas:
            d.wait()

    call = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(shape, jnp.bfloat16),
        scratch_shapes=[pltpu.SemaphoreType.DMA((nstreams,))],
    )

    def make(k):
        return jax.jit(lambda a: jax.lax.fori_loop(
            0, k, lambda i, t: call(t), a))

    t = _slope_timed(make, lambda o: o, x)
    n = shape[0] * shape[1]
    return {"nstreams": nstreams,
            "copy_gbps": round(2 * n * 2 / t / 1e9, 1)}


def bench_xla(total_bytes):
    """Round-4's XLA elementwise kernels, re-timed with the slope method
    (their round-4 numbers included one dispatch overhead per 3 chain
    passes)."""
    import jax
    import jax.numpy as jnp
    n = total_bytes // 2
    x = jnp.ones((n,), jnp.bfloat16)
    y = jnp.full((n,), 0.5, jnp.bfloat16)

    def make_copy(k):
        return jax.jit(lambda a: jax.lax.fori_loop(
            0, k, lambda i, t: t + jnp.bfloat16(1), a))

    def make_triad(k):
        return jax.jit(lambda a, b: jax.lax.fori_loop(
            0, k, lambda i, t: t + b * jnp.bfloat16(2), a))

    def make_read(k):
        # carried scalar seeds the sum so the pass can't be hoisted
        return jax.jit(lambda s, a: jax.lax.fori_loop(
            0, k, lambda i, t: t + jnp.sum((a + t.astype(jnp.bfloat16) * 0
                                            ).astype(jnp.float32)), s))

    out = {}
    out["copy_gbps"] = round(
        2 * n * 2 / _slope_timed(make_copy, lambda o: o, x) / 1e9, 1)
    out["triad_gbps"] = round(
        3 * n * 2 / _slope_timed(make_triad, lambda o: o, x, y) / 1e9, 1)
    out["read_gbps"] = round(
        n * 2 / _slope_timed(make_read, lambda o: o,
                             jnp.float32(0), x) / 1e9, 1)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--gib", type=float, default=1.0)
    ap.add_argument("--skip", default="", help="comma list: auto,manual")
    args = ap.parse_args()
    skip = set(args.skip.split(","))
    total = int(args.gib * (1 << 30))

    # wait for a clean window: a busy co-tenant inflates everything ~10x.
    # The slope calibration cancels the fixed per-fetch overhead, which is
    # reported as fixed_overhead_ms.
    for attempt in range(20):
        cal, fixed = _calibrate()
        print(json.dumps({"calibration_matmul_ms": round(cal, 1),
                          "fixed_overhead_ms": round(fixed, 1),
                          "attempt": attempt}), flush=True)
        if cal < 12.0:
            break
        time.sleep(20)
    res = {}
    if "xla" not in skip:
        try:
            res["xla"] = bench_xla(total)
        except Exception as e:  # noqa: BLE001
            res["xla"] = {"error": str(e)[:200]}
        print(json.dumps({"xla": res["xla"]}), flush=True)
    if "hbm_dma" not in skip:
        res["hbm_dma"] = []
        for ns in (1, 2, 4, 8):
            try:
                r = bench_hbm_dma(total, ns)
            except Exception as e:  # noqa: BLE001
                r = {"nstreams": ns, "error": str(e)[:200]}
            res["hbm_dma"].append(r)
            print(json.dumps(r), flush=True)
    if "auto" not in skip:
        res["auto"] = []
        for rows, lanes in [(256, 1024), (512, 1024), (1024, 1024),
                            (2048, 1024), (512, 4096)]:
            try:
                r = bench_auto(total, rows, lanes)
            except Exception as e:  # noqa: BLE001 — report and move on
                r = {"block": f"{rows}x{lanes}", "error": str(e)[:200]}
            res["auto"].append(r)
            print(json.dumps(r), flush=True)
    if "manual" not in skip:
        res["manual"] = []
        for rows, lanes, nbuf in [(512, 1024, 2), (512, 1024, 4),
                                  (1024, 1024, 2), (1024, 1024, 4),
                                  (2048, 1024, 2), (1024, 4096, 2)]:
            try:
                r = bench_manual(total, rows, lanes, nbuf)
            except Exception as e:  # noqa: BLE001
                r = {"block": f"{rows}x{lanes}", "nbuf": nbuf,
                     "error": str(e)[:200]}
            res["manual"].append(r)
            print(json.dumps(r), flush=True)
    print(json.dumps({"roofline_pallas": res}))


if __name__ == "__main__":
    main()
