"""ResNet-50 north-star disposition evidence (round 4, VERDICT #3).

Three measurements, one JSON line each, run on the real chip:

1. ``bandwidth``: achievable HBM bandwidth from saturating elementwise
   kernels (copy: 2 bytes moved per element-byte; triad a+b*s: 3) — the
   MEASURED roof that replaces the 819 GB/s paper number in the ResNet
   roofline argument.
2. ``layout_ab``: NHWC vs NCHW timed fwd+bwd on the three conv+BN blocks
   that dominate the ResNet-50 step (stage shapes at b=256), plus the
   full-model step in NHWC. XLA canonicalises conv layouts internally,
   so NCHW should cost extra transposes or tie — this pins it down.
3. ``step_bytes``: XLA cost_analysis bytes of the full compiled training
   step (the 90 GB/step figure's source) next to the measured step time,
   so achieved GB/s = bytes/time can be compared against (1).

Usage:  python scripts/roofline_ab.py [--batch N] [--skip bandwidth,layout,step]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fetch(x):
    """Force device completion: a scalar fetch of a result leaf cannot
    return before the work that produced it has run."""
    import jax
    import jax.numpy as jnp
    leaf = jax.tree_util.tree_leaves(x)[0]
    return float(jnp.sum(leaf.astype(jnp.float32)))


def _timed_chain(fn, feed, *args, iters=20, warmup=3):
    """Iterations form a DEPENDENT chain
    (``feed`` maps the previous output to the next first input), so device
    work serialises and the closing scalar fetch times the whole chain."""
    out = fn(args[0], *args[1:])
    for _ in range(warmup - 1):
        out = fn(feed(out), *args[1:])
    _fetch(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(feed(out), *args[1:])
    _fetch(out)
    return (time.perf_counter() - t0) / iters


def _calibrate():
    """Dirty-window detector (PERF.md recipe): an 8192^2 bf16 matmul
    should land ~6-9 ms; tens of ms means a co-tenant is polluting."""
    import jax
    import jax.numpy as jnp
    a = jnp.ones((8192, 8192), jnp.bfloat16)
    f = jax.jit(lambda a: a @ a)
    out = f(a)
    _fetch(out)
    t0 = time.perf_counter()
    out = f(out)
    _fetch(out)
    return (time.perf_counter() - t0) * 1e3


def bench_bandwidth():
    import jax
    import jax.numpy as jnp
    n = 512 * 1024 * 1024  # 512 Mi elements of bf16 = 1 GiB per array
    x = jnp.ones((n,), jnp.bfloat16)
    y = jnp.ones((n,), jnp.bfloat16)

    k = 20  # device-side chain: one dispatch, k dependent passes (the
    #         per-dispatch host cost would otherwise pollute ms-scale kernels)
    copy = jax.jit(lambda a: jax.lax.fori_loop(
        0, k, lambda i, t: t + jnp.bfloat16(1), a))
    triad = jax.jit(lambda a, b: jax.lax.fori_loop(
        0, k, lambda i, t: t + b * jnp.bfloat16(2), a))

    t_copy = _timed_chain(copy, lambda o: o, x, iters=3) / k
    t_triad = _timed_chain(triad, lambda o: o, x, y, iters=3) / k
    # pure read: fold a 2 GiB array into a carried scalar — write traffic
    # is one float, so the rate is the read roof
    xr = jnp.ones((2 * n,), jnp.bfloat16)
    read = jax.jit(lambda a, s: jax.lax.fori_loop(
        0, k, lambda i, t: t + jnp.sum(a.astype(jnp.float32)), s))
    t_read = _timed_chain(lambda s, a: read(a, s), lambda o: o,
                          jnp.float32(0), xr, iters=3) / k
    bytes_copy = 2 * n * 2
    bytes_triad = 3 * n * 2
    return {
        "copy_gbps": round(bytes_copy / t_copy / 1e9, 1),
        "triad_gbps": round(bytes_triad / t_triad / 1e9, 1),
        "read_gbps": round(2 * n * 2 / t_read / 1e9, 1),
    }


def bench_layout_ab(batch: int):
    """fwd+bwd conv+train-BN blocks, NHWC vs NCHW dimension numbers."""
    import jax
    import jax.numpy as jnp

    # the three shapes that dominate ResNet-50's conv time at b=256
    # (stage 2/3/4 3x3 convs)
    shapes = [  # (H, W, Cin, Cout, stride)
        (56, 56, 64, 64, 1),
        (28, 28, 128, 128, 1),
        (14, 14, 256, 256, 1),
    ]
    out = {}
    for layout in ("NHWC", "NCHW"):
        dn = (layout, "HWIO" if layout == "NHWC" else "OIHW", layout)
        total = 0.0
        for h, w, cin, cout, s in shapes:
            if layout == "NHWC":
                x = jnp.ones((batch, h, w, cin), jnp.bfloat16)
                red = (0, 1, 2)
            else:
                x = jnp.ones((batch, cin, h, w), jnp.bfloat16)
                red = (0, 2, 3)
            k_shape = ((3, 3, cin, cout) if layout == "NHWC"
                       else (cout, cin, 3, 3))
            k = jnp.full(k_shape, 0.01, jnp.bfloat16)

            def block(x, k):
                y = jax.lax.conv_general_dilated(
                    x, k, (s, s), "SAME", dimension_numbers=dn)
                yf = y.astype(jnp.float32)
                mean = jnp.mean(yf, red, keepdims=True)
                var = jnp.mean(jnp.square(yf), red, keepdims=True) \
                    - jnp.square(mean)
                yn = (yf - mean) * jax.lax.rsqrt(var + 1e-5)
                return jax.nn.relu(yn).astype(jnp.bfloat16)

            def loss(x, k):
                return jnp.sum(block(x, k).astype(jnp.float32))

            reps = 10
            gfn = jax.grad(loss, argnums=(0, 1))
            # device-side chain (dx has x's shape: s=1, cin==cout), one
            # dispatch per timing — per-dispatch host cost amortised away
            # graftlint: ignore[JG004] -- one compile per benchmarked layout by design (A/B sweep, not a hot loop)
            g = jax.jit(lambda xx, kk: jax.lax.fori_loop(
                0, reps, lambda i, t: gfn(t, kk)[0], xx))
            total += _timed_chain(g, lambda o: o, x, k, iters=3) / reps
        out[layout.lower() + "_ms"] = round(total * 1e3, 2)
    return out


def bench_step_bytes(batch: int):
    """Full ResNet-50 training step: cost_analysis bytes + measured time."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.models import resnet
    from bigdl_tpu.nn.module import functional_apply
    from bigdl_tpu.ops.precision import DtypePolicy
    from bigdl_tpu.optim.methods import SGD

    model = resnet.build(1000, depth=50)
    crit = nn.ClassNLLCriterion()
    policy = DtypePolicy.bf16()
    optim = SGD(learningrate=0.1, momentum=0.9)
    params = model.parameter_tree()
    buffers = model.buffer_tree()
    state = optim.init_state(params)
    x = jnp.ones((batch, 224, 224, 3), jnp.bfloat16)
    y = jnp.ones((batch,), jnp.float32)

    def step(params, buffers, state, x, y):
        def loss_fn(p):
            p_c = policy.cast_params_for_compute(p)
            out, nb = functional_apply(model, p_c, buffers, x, training=True)
            return crit.apply(out, y).astype(jnp.float32), nb

        grads, nb = jax.grad(loss_fn, has_aux=True)(params)
        new_p, new_s = optim.update(grads, state, params)
        return new_p, nb, new_s

    jitted = jax.jit(step, donate_argnums=(0, 1, 2))
    lowered = jitted.lower(params, buffers, state, x, y)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    # param/state outputs feed the next call: a dependent chain
    t = _timed_chain(lambda st: jitted(*st, x, y), lambda o: o,
                     (params, buffers, state), iters=10)
    bytes_step = float(ca.get("bytes accessed", 0.0))
    return {
        "cost_analysis_gb": round(bytes_step / 1e9, 1),
        "flops_tf": round(float(ca.get("flops", 0.0)) / 1e12, 2),
        "step_ms": round(t * 1e3, 1),
        "achieved_gbps_if_bw_bound": round(bytes_step / t / 1e9, 1),
        "img_per_s": round(batch / t, 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--skip", default="",
                    help="comma list: bandwidth,layout,step")
    args = ap.parse_args()
    skip = set(args.skip.split(","))
    res = {"calibration_matmul_ms": round(_calibrate(), 1)}
    print(json.dumps({"calibration_matmul_ms":
                      res["calibration_matmul_ms"]}), flush=True)
    if "bandwidth" not in skip:
        res["bandwidth"] = bench_bandwidth()
        print(json.dumps({"bandwidth": res["bandwidth"]}), flush=True)
    if "layout" not in skip:
        res["layout_ab"] = bench_layout_ab(args.batch)
        print(json.dumps({"layout_ab": res["layout_ab"]}), flush=True)
    if "step" not in skip:
        res["step"] = bench_step_bytes(args.batch)
        print(json.dumps({"step": res["step"]}), flush=True)
    print(json.dumps({"roofline_ab": res}))


if __name__ == "__main__":
    main()
