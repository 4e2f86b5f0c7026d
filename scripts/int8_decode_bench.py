"""B=1 decode bench: fp32 vs bf16-cast vs int8 fused-kernel weights
(round 5, VERDICT #5 "done" evidence).

Same 134M-param GQA target as the PERF.md round-4 decode table (E=768,
L=12, H=12, KV=4, V=32K, rope/swiglu/rms), B=1, greedy. Timing is the
slope method (two generation lengths differenced — cancels the fixed
per-call overhead and the prefill cost; see roofline_pallas.py), after the standard
clean-window calibration.

Target: int8 >= 1.8x fp32 (the bf16 cast measured 1.69x in round 4; at
the weight-read floor int8's 134 MB resident should approach 2x once the
dequant never rematerializes — ops/int8_matmul.py).

Round 10 adds the flight-recorder cost mode: ``--cost-only`` compiles a
decode-shaped forward per variant under ``tracked_jit`` and emits the
program's cost-analysis FLOPs / bytes-accessed per site into the BENCH
JSON (plus the int8 fallback counter, which must stay 0) — runs on CPU,
no calibration needed. ``--config tiny`` keeps the same serving stack
(GQA + rope/swiglu/rms + tied head) at CI size.

Usage: python scripts/int8_decode_bench.py [--tokens 128]
       python scripts/int8_decode_bench.py --cost-only --config tiny \
           --json /tmp/int8_cost.json
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from roofline_pallas import _calibrate, _fetch  # noqa: E402

_CONFIGS = {
    # name -> build_lm kwargs; 134m is the PERF.md round-4/5 decode target
    "134m": dict(vocab=32_000, embed_dim=768, num_heads=12, ffn_dim=3072,
                 num_layers=12, max_len=512, num_kv_heads=4),
    "tiny": dict(vocab=1_000, embed_dim=128, num_heads=4, ffn_dim=256,
                 num_layers=2, max_len=64, num_kv_heads=2),
}


def build_target(config="134m"):
    from bigdl_tpu.models import transformer
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(7)
    cfg = dict(_CONFIGS[config])
    vocab = cfg.pop("vocab")
    return transformer.build_lm(
        vocab, rope=True, activation="swiglu", norm="rms", bias=False,
        tie_embeddings=True, **cfg)


def cost_rows(variants, config):
    """Compile a decode-shaped forward (B=1, one token) per weight
    variant under the flight recorder and return per-site cost-analysis
    rows — the byte accounting behind the int8 floor claims, portable to
    CPU (cost_analysis is a property of the compiled program, not the
    machine's speed)."""
    import jax.numpy as jnp
    from bigdl_tpu.nn.module import functional_apply
    from bigdl_tpu.telemetry import get_registry, instruments
    from bigdl_tpu.telemetry.profiling import tracked_jit

    fallbacks = instruments(get_registry()).int8_fallbacks_total
    before = fallbacks.value
    rows = {}
    for name, mk in variants:
        model = mk().evaluate_mode()
        params, buffers = model.parameter_tree(), model.buffer_tree()
        site = f"int8_decode.{name}"

        def fwd(p, b, x, model=model):
            return functional_apply(model, p, b, x, training=False)[0]

        step = tracked_jit(fwd, site=site)  # graftlint: ignore[JG004] -- one wrapper per weight variant (3 total, distinct sites/models); nothing to hoist
        out = step(params, buffers, jnp.ones((1, 1), jnp.float32))
        out.block_until_ready()
        ev = step.last_event
        rows[name] = {
            "site": site,
            "program_flops": ev.flops if ev else None,
            "program_bytes_accessed": (ev.bytes_accessed if ev else None),
        }
    rows["int8_fallbacks_delta"] = fallbacks.value - before
    rows["config"] = config
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=32,
                    help="small chain length (large = 5x)")
    ap.add_argument("--skip", default="", help="comma list: fp32,bf16,int8")
    ap.add_argument("--config", default="134m", choices=sorted(_CONFIGS))
    ap.add_argument("--cost-only", action="store_true",
                    help="flight-recorder cost rows only (CPU-safe): "
                         "no calibration, no wall-clock timing")
    ap.add_argument("--json", default="", help="write the BENCH JSON here")
    args = ap.parse_args()
    run(args)


def time_decode(model, n_small=16, n_large=None, iters=3):
    """Seconds/token via the slope between two generation lengths."""
    import jax.numpy as jnp
    from bigdl_tpu.models.generation import generate

    n_large = n_large or (n_small * 5)
    prompt = jnp.ones((1, 8), jnp.float32)
    ts = {}
    for n in (n_small, n_large):
        out = generate(model, prompt, n, greedy=True)  # compile + warmup
        _fetch(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = generate(model, prompt, n, greedy=True)
            _fetch(out)
        ts[n] = (time.perf_counter() - t0) / iters
    return (ts[n_large] - ts[n_small]) / (n_large - n_small)


def run(args):
    skip = set(args.skip.split(","))

    from bigdl_tpu.nn.quantized import cast_model, quantize_model
    model = build_target(args.config)
    variants = []
    if "fp32" not in skip:
        variants.append(("fp32", lambda: model))
    if "bf16" not in skip:
        variants.append(("bf16", lambda: cast_model(model)))
    if "int8" not in skip:
        variants.append(("int8", lambda: quantize_model(model)))

    if args.cost_only:
        res = cost_rows(variants, args.config)
        art = {"schema": 1, "kind": "bigdl_tpu_int8_decode_cost",
               "int8_decode_cost": res}
        print(json.dumps(art))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(art, f, indent=1)
        return

    for _ in range(20):
        cal, fixed = _calibrate()
        print(json.dumps({"calibration_matmul_ms": round(cal, 1),
                          "fixed_overhead_ms": round(fixed, 1)}), flush=True)
        if cal < 12.0:
            break
        time.sleep(20)

    res = {}
    for name, mk in variants:
        try:
            spt = time_decode(mk(), n_small=args.tokens)
            res[name] = {"tok_per_s": round(1.0 / spt, 1),
                         "us_per_tok": round(spt * 1e6, 1)}
        except Exception as e:  # noqa: BLE001
            res[name] = {"error": str(e)[:300]}
        print(json.dumps({name: res[name]}), flush=True)
    if "fp32" in res and "tok_per_s" in res.get("fp32", {}):
        for name in ("bf16", "int8"):
            if "tok_per_s" in res.get(name, {}):
                res[name]["vs_fp32"] = round(
                    res[name]["tok_per_s"] / res["fp32"]["tok_per_s"], 2)
    # timed mode also carries the flight-recorder byte accounting so the
    # PERF tables pair every wall-clock row with its cost-analysis terms
    cost = cost_rows(variants, args.config)
    art = {"schema": 1, "kind": "bigdl_tpu_int8_decode_bench",
           "int8_decode_bench": res, "int8_decode_cost": cost}
    print(json.dumps(art))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(art, f, indent=1)


if __name__ == "__main__":
    main()
