#!/usr/bin/env python
"""Flash-attention block-size autotune (run on a real TPU).

Sweeps (block_q, block_k) for the benchmarked attention shapes and prints
per-config times plus the winning env setting:

    python scripts/tune_flash.py                      # transformer bench shape
    python scripts/tune_flash.py --b 8 --s 2048 --d 64 --heads 8 --causal

The winner is exported by setting BIGDL_TPU_FLASH_BLOCK_Q/K (consumed by
``ops.flash_attention`` at call time — no code edits). On CPU this runs
interpret mode with tiny defaults purely as a smoke test.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--b", type=int, default=None)
    ap.add_argument("--s", type=int, default=None)
    ap.add_argument("--heads", type=int, default=None)
    ap.add_argument("--d", type=int, default=None)
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    ap.add_argument("--blocks", default="128,256,512",
                    help="comma-separated candidate block sizes")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--scan", action="store_true",
                    help="crossover scan: best-flash vs the XLA attention "
                    "cores across sequence lengths at constant token count "
                    "(informs the use_flash dispatch gate)")
    ap.add_argument("--seqs", default="512,1024,2048,4096,8192",
                    help="sequence lengths for --scan")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.ops.flash_attention import flash_attention

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        print("WARNING: not a TPU backend - interpret-mode smoke only",
              flush=True)
    # defaults: the bench transformer attention shape on TPU, tiny on CPU
    b = args.b or (32 if on_tpu else 1)
    s = args.s or (512 if on_tpu else 64)
    n = args.heads or (4 if on_tpu else 2)
    d = args.d or 64
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    blocks = [int(x) for x in args.blocks.split(",")]
    if not on_tpu:
        blocks = [16, 32]

    rng = np.random.default_rng(0)

    def fetch(out):
        # a device->host scalar fetch ends the timed region: it cannot
        # return before every queued call has run
        leaf = jax.tree_util.tree_leaves(out)[0]
        return float(jnp.sum(leaf.astype(jnp.float32)))

    def timed(f, *xs):
        fetch(f(*xs))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = f(*xs)
        fetch(out)
        return (time.perf_counter() - t0) / args.iters

    if args.scan:
        scan_crossover(args, jax, jnp, rng, n, d, dtype, blocks, timed,
                       on_tpu)
        return

    q, k, v = (jnp.asarray(rng.normal(0, 1, (b, s, n, d)), dtype)
               for _ in range(3))

    results = []
    for bq in blocks:
        for bk in blocks:
            # graftlint: ignore[JG004] -- autotuner: each (bq, bk) config is a distinct program compiled once
            fwd = jax.jit(lambda q, k, v, bq=bq, bk=bk: flash_attention(
                q, k, v, causal=args.causal, block_q=bq, block_k=bk))

            def loss(q, k, v, bq=bq, bk=bk):
                return jnp.sum(flash_attention(
                    q, k, v, causal=args.causal, block_q=bq,
                    block_k=bk).astype(jnp.float32) ** 2)

            # graftlint: ignore[JG004] -- autotuner: each (bq, bk) config is a distinct program compiled once
            bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            try:
                t_f = timed(fwd, q, k, v)
                t_b = timed(bwd, q, k, v)
            except Exception as e:
                print(f"bq={bq:4d} bk={bk:4d}  FAILED: "
                      f"{type(e).__name__}: {str(e)[:90]}", flush=True)
                continue
            # rank by the fwd+bwd-grad time: that IS the training-step
            # attention cost (the jitted grad already re-runs the forward)
            results.append((t_b, bq, bk, t_f))
            print(f"bq={bq:4d} bk={bk:4d}  fwd {t_f * 1e3:8.3f} ms   "
                  f"fwd+bwd-grad {t_b * 1e3:8.3f} ms", flush=True)

    if not results:
        print("no config succeeded")
        sys.exit(1)
    t_b, bq, bk, t_f = min(results)
    print(f"\nbest: BIGDL_TPU_FLASH_BLOCK_Q={bq} BIGDL_TPU_FLASH_BLOCK_K={bk}"
          f"  (fwd {t_f * 1e3:.3f} ms, fwd+bwd-grad {t_b * 1e3:.3f} ms; "
          f"shape b={b} s={s} h={n} d={d} causal={args.causal} "
          f"{args.dtype})")


def scan_crossover(args, jax, jnp, rng, n, d, dtype, blocks, timed, on_tpu):
    """For each seq length (at ~constant token count), time the XLA cores
    (dot-product; blockwise scan) against the best flash block config on the
    fwd+bwd-grad path — the data the ``use_flash`` gate must encode."""
    from bigdl_tpu.ops import attention_core
    from bigdl_tpu.ops.flash_attention import flash_attention

    seqs = [int(x) for x in args.seqs.split(",")]
    tokens = (args.b or 32) * (args.s or 512)
    if not on_tpu:  # interpret-mode smoke: full bench shapes are intractable
        seqs = [64, 128]
        tokens = 128
    rows = []
    for s in seqs:
        b = max(1, tokens // s)
        q, k, v = (jnp.asarray(rng.normal(0, 1, (b, s, n, d)), dtype)
                   for _ in range(3))

        def grad_timer(core):
            def loss(q, k, v):
                return jnp.sum(core(q, k, v).astype(jnp.float32) ** 2)
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        def xla_dot(q, k, v):
            return attention_core.dot_product_attention(
                q, k, v, causal=args.causal)

        def xla_block(q, k, v):
            return attention_core.blockwise_attention(
                q, k, v, causal=args.causal, block_size=512)

        entries = {}
        for name, core in (("xla-dot", xla_dot), ("xla-block", xla_block)):
            try:
                entries[name] = timed(grad_timer(core), q, k, v)
            except Exception as e:
                print(f"s={s} {name}: FAILED {type(e).__name__}", flush=True)
        best = None
        for bq in blocks:
            for bk in blocks:
                if bq > s or bk > s:
                    continue
                core = (lambda q, k, v, bq=bq, bk=bk: flash_attention(
                    q, k, v, causal=args.causal, block_q=bq, block_k=bk))
                try:
                    t = timed(grad_timer(core), q, k, v)
                except Exception:
                    continue
                if best is None or t < best[0]:
                    best = (t, bq, bk)
        if best is None:
            print(f"s={s}: no flash config succeeded", flush=True)
            continue
        if not entries:
            # no XLA core produced a time: flash ran where XLA could not
            # (e.g. OOM) — report it, but NOT as a measured win
            t_flash, bq, bk = best
            print(f"s={s:5d} b={b:3d}  xla FAILED   flash "
                  f"{t_flash * 1e3:8.3f} ms (bq={bq} bk={bk})  "
                  "[no comparison]", flush=True)
            continue
        t_flash, bq, bk = best
        t_xla = min(entries.values())
        rows.append((s, b, t_xla, t_flash, bq, bk))
        print(f"s={s:5d} b={b:3d}  xla {t_xla * 1e3:8.3f} ms   "
              f"flash {t_flash * 1e3:8.3f} ms (bq={bq} bk={bk})  "
              f"flash/xla={t_flash / t_xla:5.2f}", flush=True)
    wins = [s for s, _, tx, tf, _, _ in rows if tf < tx]
    print(f"\nflash wins at seq lengths: {wins or 'none'} "
          f"(causal={args.causal}, {args.dtype}, h={n}, d={d})")


if __name__ == "__main__":
    main()
