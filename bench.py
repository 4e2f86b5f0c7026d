"""Benchmark entry: prints ONE JSON line with the headline metric.

Headline (BASELINE.md): ResNet-50 ImageNet-shape sync-SGD training
throughput, images/sec/chip. The reference publishes no numbers
(``BASELINE.json published: {}``), so ``vs_baseline`` is reported against the
driver's north-star target: 50% MFU of the chip found (on a TPU v5e,
0.5 * 197 TFLOP/s bf16 / 24.6 GFLOP/image fwd+bwd ~= 4004 img/s/chip).
vs_baseline = 1.0 means the north star is met.

A number printed here is a device metric, so it comes from a TPU or not at
all: a worker whose backend is not ``tpu``, or whose device kind the peak
table (``telemetry/profiling.py``) does not list, is an error, and with no
successful attempt the run prints ``bench_failed`` and exits 1.

The parent process NEVER imports jax — a process that has touched jax holds
the chip, and the worker could not have it. Every attempt runs in a budgeted
subprocess (``--worker``) that is killed on timeout; attempts run
largest-first and the first success wins. A worker killed on timeout may not
have released the chip when the next attempt starts: that attempt then fails
at backend start-up rather than measuring anything. Workers stream progress
to stderr and fetch a scalar after every warmup step. The compile cache
follows the package's one rule (``bigdl_tpu.utils.engine.compile_cache_dir``).

The numbers here are SYNTHETIC-INPUT ceilings (no host data path). The
real-data ingest side is benchmarked by ``bigdl_tpu/apps/ingest_bench.py``
— its ``pipeline`` mode A/Bs the serial host chain against the staged
ingest engine (``dataset/ingest/``) and writes ``INGEST_r01.json`` /
``INGEST_r01_trace.json``; comparing its rec/s against this file's
img/s/chip says whether training is chip-bound or host-bound.

Usage: python bench.py                 # full orchestrated run
       python bench.py --model lenet   # restrict to one workload
"""

import argparse
import json
import os
import subprocess
import sys
import time


NORTH_STAR_MFU = 0.5
LENET_BASELINE_RPS = 4.8  # reference's only published throughput (rnn/README.md:105-108)


_T_START = time.monotonic()


def log(msg):
    print(f"[bench +{time.monotonic() - _T_START:.0f}s] {msg}",
          file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# Worker side: one attempt, inside its own (killable) process
# --------------------------------------------------------------------------

def _timed_loop(step, state, budget_s, max_steps, batch, step_hist=None):
    """Run warmup + timed steps under a wall-clock budget; return imgs/sec.

    Warmup forces a device->host scalar fetch after EVERY step so a stuck
    step fails inside the (killable) worker budget rather than silently
    queueing async work. ``step_hist`` (a telemetry Histogram)
    receives per-step wall-clock observations — chunk time / steps, the
    chunk-end force() being the sync point — so the emitted JSON carries a
    step-time distribution, not just the headline mean.
    """
    force = state.pop("_force")
    t_start = time.monotonic()
    log("compiling + warmup step 1")
    state = step(state)
    force(state)
    log(f"step 1 done at +{time.monotonic() - t_start:.1f}s (compile incl.)")
    for i in range(2):
        state = step(state)
        force(state)
    log("warmup done; entering timed loop")

    done = 0
    t0 = time.monotonic()
    chunk = 5
    over_budget = False
    while done < max_steps and not over_budget:
        n = min(chunk, max_steps - done)
        t_chunk = time.monotonic()
        n_chunk = 0
        for _ in range(n):
            state = step(state)
            done += 1
            n_chunk += 1
            # per-dispatch budget check: at large K each dispatch is
            # seconds of device work, so a per-chunk check could commit
            # to minutes past the budget and get the worker killed
            if time.monotonic() - t_start > budget_s:
                over_budget = True
                break
        force(state)
        if step_hist is not None and n_chunk:
            per_step = (time.monotonic() - t_chunk) / n_chunk
            for _ in range(n_chunk):
                step_hist.observe(per_step)
        elapsed = time.monotonic() - t0
        log(f"timed {done}/{max_steps} steps, {elapsed:.1f}s")
        if over_budget:
            log("phase budget reached; stopping early with partial steps")
    elapsed = time.monotonic() - t0
    if done == 0 or elapsed <= 0:
        raise RuntimeError("no timed steps completed inside budget")
    return batch * done / elapsed


# Fwd multiply-accumulate counts per record at the bench input shapes;
# train FLOPs/record = 3 * 2 * MAC (backward ~ 2x forward).
_FWD_MACS = {
    "resnet50": 4.1e9,       # 224x224; He et al. table 1
    "vgg16": 15.47e9,        # 224x224 convs+fcs
    "inception_v1": 1.5e9,   # GoogLeNet paper: "1.5 billion multiply-adds"
}

# BASELINE workload registry (BASELINE.md configs 1-5 + the transformer):
# build() -> (model, criterion, data_fn(rng, batch) -> (data, labels),
#             records_per_batch_factor)
_SEQ_LEN = {"lstm": 128, "transformer": 512}


def _apply_seq_len_override(args):
    """--seq-len (worker only): bench the sequence workloads at other
    lengths (e.g. the long-context transformer crossover, PERF.md)."""
    if args.seq_len:
        _SEQ_LEN["lstm"] = _SEQ_LEN["transformer"] = args.seq_len


def _build_workload(name, batch):
    import jax.numpy as jnp
    import numpy as np
    from bigdl_tpu import nn

    rng = np.random.default_rng(0)

    def img(shape, classes):
        data = jnp.asarray(rng.normal(0, 1, (batch,) + shape)
                           .astype("float32"))
        labels = jnp.asarray(rng.integers(1, classes + 1, (batch,))
                             .astype("float32"))
        return data, labels

    if name == "resnet50":
        from bigdl_tpu.models import resnet
        return (resnet.build(class_num=1000, depth=50),
                nn.ClassNLLCriterion(), *img((224, 224, 3), 1000), 1)
    if name == "vgg16":
        from bigdl_tpu.models import vgg
        return (vgg.build_imagenet(class_num=1000, depth=16),
                nn.ClassNLLCriterion(), *img((224, 224, 3), 1000), 1)
    if name == "inception_v1":
        from bigdl_tpu.models import inception
        return (inception.build(class_num=1000),
                nn.ClassNLLCriterion(), *img((224, 224, 3), 1000), 1)
    if name == "lenet":
        from bigdl_tpu.models import lenet
        return (lenet.build(10), nn.ClassNLLCriterion(),
                *img((28, 28, 1), 10), 1)
    if name == "lstm":
        from bigdl_tpu.models import rnn
        t = _SEQ_LEN["lstm"]
        model = rnn.build_classifier(10000, 128, 256, 20, cell="lstm")
        data = jnp.asarray(rng.integers(1, 10001, (batch, t))
                           .astype("float32"))
        labels = jnp.asarray(rng.integers(1, 21, (batch,)).astype("float32"))
        return model, nn.ClassNLLCriterion(), data, labels, 1
    if name == "transformer":
        from bigdl_tpu.models import transformer
        t = _SEQ_LEN["transformer"]
        # embed 256 / 4 heads -> head dim 64. At the default seq 512 the
        # use_flash gate routes to XLA attention (the measured in-model
        # winner there); --seq-len 1024+ dispatches the Pallas kernel
        # (PERF.md round-3 crossover)
        # fused LM-head CE (nn.LMHead + FusedLMHeadCriterion): the (B,S,V)
        # logits never materialise — measured +23% over the unfused tail on
        # chip at V=32K (PERF.md round 3); loss numerics parity-tested
        model = transformer.build_lm(10000, embed_dim=256, num_heads=4,
                                     ffn_dim=1024, num_layers=4, max_len=t,
                                     fused_head=True)
        data = jnp.asarray(rng.integers(1, 10001, (batch, t))
                           .astype("float32"))
        labels = jnp.asarray(rng.integers(1, 10001, (batch, t))
                             .astype("float32"))
        # scale matches the previous TimeDistributedCriterion(...,
        # size_average=True) tail (flat mean / T) so the SGD step's
        # gradient magnitudes — and hence the measured training dynamics —
        # stay comparable across rounds
        class _ScaledFusedCE(nn.FusedLMHeadCriterion):
            def update_output(self, input, target):
                return super().update_output(input, target) / t

        crit = _ScaledFusedCE()
        return model, crit, data, labels, t
    raise ValueError(name)


def _transformer_flops_per_token(model, seq_len, layers=4, embed=256):
    """~6 FLOPs/param/token for the matmul params (incl. the vocab
    projection — a real matmul) + the attention quadratic (12*S*E per
    layer per token, fwd+bwd). Only the embedding TABLE is excluded: its
    lookup is a gather, not FLOPs — identified by leaf identity (model[0]
    is the LookupTable), never by shape, which would also catch the
    same-shaped LM head."""
    import numpy as np
    tree = model.parameter_tree()
    embed_leaf = tree.get("0", {}).get("weight")
    n_params = 0
    for leaf in _tree_leaves(tree):
        if leaf is embed_leaf:
            continue
        if getattr(leaf, "ndim", 0) >= 2:
            n_params += int(np.prod(leaf.shape))
    return 6 * n_params + 12 * seq_len * embed * layers


def _tree_leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


def worker_train(name, batch, steps, budget_s, precision="bf16"):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.nn.module import functional_apply
    from bigdl_tpu.ops.precision import DtypePolicy, cast_tree
    from bigdl_tpu.optim.methods import SGD
    from bigdl_tpu.utils.rng import manual_seed

    manual_seed(42)
    model, criterion, data, labels, rec_factor = _build_workload(name, batch)
    opt_method = SGD(learningrate=0.1, momentum=0.9)
    policy = DtypePolicy.bf16() if precision == "bf16" else DtypePolicy.fp32()

    params = model.parameter_tree()
    buffers = model.buffer_tree()
    opt_state = opt_method.init_state(params)

    def forward(p, bufs, data):
        p_c = policy.cast_params_for_compute(p)
        out, new_buf = functional_apply(model, p_c, bufs, data,
                                        training=True)
        return out, cast_tree(new_buf, jnp.float32)

    # BIGDL_TPU_BENCH_REMAT=conv|full: remat A/B lever ("conv" saves conv
    # outputs + BN stats, recomputes the elementwise tail in the backward —
    # the bandwidth lever for the BN-bound ResNet step; see PERF.md)
    remat = os.environ.get("BIGDL_TPU_BENCH_REMAT", "")
    if remat == "conv":
        from bigdl_tpu.ops.remat import conv_remat_policy
        forward = jax.checkpoint(forward, policy=conv_remat_policy())
    elif remat == "full":
        forward = jax.checkpoint(forward)
    elif remat:
        log(f"ignoring unknown BIGDL_TPU_BENCH_REMAT={remat!r} "
            "(expected 'conv' or 'full')")

    def step_fn(params, buffers, opt_state, data, labels):
        def loss_fn(p):
            out, new_buf = forward(p, buffers, data)
            loss = criterion.apply(out, labels).astype(jnp.float32)
            return loss, new_buf

        grads, new_buf = jax.grad(loss_fn, has_aux=True)(params)
        new_params, new_opt = opt_method.update(grads, opt_state, params)
        return new_params, new_buf, new_opt

    # K optimizer steps per dispatch: one fori_loop'd program amortizes the
    # fixed per-dispatch host cost. Constant input per step matches the
    # reference harness's constant-data mode (DistriOptimizerPerf.scala:32).
    try:
        K = max(1, int(os.environ.get("BIGDL_TPU_BENCH_K", "") or 60))
    except ValueError:
        K = 60

    def multi_step(params, buffers, opt_state, data, labels):
        def body(_, st):
            return step_fn(*st, data, labels)
        return jax.lax.fori_loop(0, K, body,
                                 (params, buffers, opt_state))

    # compile flight recorder (telemetry/profiling.py): the BENCH JSON
    # carries compile counts and cumulative compile seconds next to the
    # step-time histogram, so runs are regression-diffable on compiles,
    # not just step time. Private registry: single-purpose worker.
    from bigdl_tpu.telemetry import MetricsRegistry, instruments
    from bigdl_tpu.telemetry.profiling import tracked_jit
    bench_registry = MetricsRegistry()
    jstep = tracked_jit(multi_step, site="bench.step",
                        registry=bench_registry, donate_argnums=(0, 1, 2))

    state = {
        "s": (params, buffers, opt_state),
        "_force": lambda st: float(jnp.sum(_tree_leaves(st["s"][0])[0])),
    }

    def step(st):
        p, b, o = st["s"]
        return {"s": jstep(p, b, o, data, labels)}

    # step-time distribution for the BENCH JSON (telemetry is jax-free and
    # cheap: one histogram observe per timed step)
    step_hist = instruments(bench_registry).bench_step_seconds
    rps = _timed_loop(step, state, budget_s, steps, batch * K,
                      step_hist=step_hist)
    summary = step_hist.summary()
    ev = jstep.last_event
    telem = {
        # per-DISPATCH wall-clock summary (each dispatch = K fused steps)
        "step_seconds": summary,
        "steps_per_dispatch": K,
        "records_per_sec": round(rps * rec_factor, 2),
        # compile flight recorder: how many programs this run built, what
        # they cost to build, and what one dispatch accounts for
        "compiles": jstep.compiles,
        "compile_seconds_total": round(
            sum(e.seconds for e in jstep.events), 3),
        "program_flops": ev.flops if ev is not None else None,
        "program_bytes_accessed": (ev.bytes_accessed
                                   if ev is not None else None),
    }
    return rps * rec_factor, model, telem


def run_worker(args):
    """Execute one attempt and print its result JSON (worker protocol:
    last stdout line is the JSON)."""
    name = args.worker
    # first: no TPU in the peak table, no number (before anything compiles)
    from bigdl_tpu.telemetry.profiling import require_tpu
    dev, peak = require_tpu()
    import jax
    n_dev = len(jax.devices())
    log(f"backend up: {dev.device_kind} x{n_dev}")
    rps, model, telem = worker_train(name, args.batch, args.steps,
                                     args.budget,
                                     precision=args.precision)
    if name in _FWD_MACS:
        flops = 6 * _FWD_MACS[name]
        mfu = rps * flops / peak
        out = {
            "metric": f"{name}_imagenet_train_images_per_sec_per_chip",
            "value": round(rps, 2),
            "unit": "images/sec/chip",
            "vs_baseline": round(mfu / NORTH_STAR_MFU, 4),
            "mfu": round(mfu, 4),
            "batch": args.batch,
        }
    elif name == "transformer":
        t = _SEQ_LEN["transformer"]
        flops = _transformer_flops_per_token(model, t)
        mfu = rps * flops / peak
        out = {
            "metric": "transformer_lm_train_tokens_per_sec_per_chip",
            "value": round(rps, 2),
            "unit": "tokens/sec/chip",
            "vs_baseline": round(mfu / NORTH_STAR_MFU, 4),
            "mfu": round(mfu, 4),
            "batch": args.batch,
            "seq_len": t,
        }
    elif name == "lstm":
        out = {
            "metric": "lstm_textclassifier_train_records_per_sec",
            "value": round(rps, 2),
            "unit": "records/sec/chip",
            # only published reference throughput: SimpleRNN 4.8 rec/s
            # (models/rnn/README.md:105-108)
            "vs_baseline": round(rps / LENET_BASELINE_RPS, 2),
            "batch": args.batch,
            "seq_len": _SEQ_LEN["lstm"],
        }
    else:
        out = {
            "metric": "lenet_mnist_train_records_per_sec",
            "value": round(rps, 2),
            "unit": "records/sec/chip",
            "vs_baseline": round(rps / LENET_BASELINE_RPS, 2),
            "batch": args.batch,
        }
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": n_dev}
    # step-time histogram summary + throughput: future rounds read a perf
    # TRAJECTORY with breakdowns, not just headline numbers
    out["telemetry"] = telem
    print(json.dumps(out), flush=True)


# --------------------------------------------------------------------------
# Orchestrator side: jax-free parent, budgeted subprocess per attempt
# --------------------------------------------------------------------------

def _attempt(name, worker, batch, steps, budget_s, precision="bf16",
             grace=90, seq_len=None):
    cmd = [sys.executable, os.path.abspath(__file__),
           "--worker", worker, "--batch", str(batch), "--steps", str(steps),
           "--budget", str(budget_s), "--precision", precision]
    if seq_len:
        cmd += ["--seq-len", str(seq_len)]
    log(f"attempt {name}: {' '.join(cmd[2:])} (timeout {budget_s + grace}s)")
    try:
        proc = subprocess.run(
            cmd, stdout=subprocess.PIPE, stderr=sys.stderr,
            timeout=budget_s + grace)  # interpreter/backend teardown margin
    except subprocess.TimeoutExpired:
        # the killed worker may still hold the chip when the next attempt
        # starts; that attempt then fails at backend start-up
        log(f"attempt {name}: KILLED on timeout")
        return None
    if proc.returncode != 0:
        log(f"attempt {name}: rc={proc.returncode}")
        return None
    for line in reversed(proc.stdout.decode(errors="replace").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                res = json.loads(line)
                log(f"attempt {name}: OK value={res.get('value')}")
                return res
            except json.JSONDecodeError:
                continue
    log(f"attempt {name}: no JSON in output")
    return None


_MODELS = ["resnet50", "vgg16", "inception_v1", "lenet", "lstm",
           "transformer"]

# Per-model TPU attempt ladders, largest-first: (batch, steps, budget_s).
_LADDERS = {
    "resnet50": [(256, 20, 540), (128, 20, 360), (32, 20, 300)],
    "vgg16": [(128, 20, 540), (32, 10, 300)],
    "inception_v1": [(256, 20, 540), (64, 10, 300)],
    "lenet": [(256, 100, 180)],
    "lstm": [(256, 20, 420), (64, 10, 300)],
    "transformer": [(32, 20, 420), (8, 10, 300)],
}


def _model_attempts(model):
    return [(f"{model}-b{b}", model, b, s, bud)
            for b, s, bud in _LADDERS[model]]


def run_all(args):
    """One JSON line per BASELINE workload (PERF.md recording mode)."""
    try:
        total_budget = float(os.environ.get("BENCH_TOTAL_BUDGET") or 7200)
    except ValueError:
        total_budget = 7200.0
    results = []
    for model in (args.model.split(",") if args.model else _MODELS):
        for name, worker, batch, steps, budget in _model_attempts(model):
            rem = total_budget - (time.monotonic() - _T_START)
            if rem < 60:
                log(f"--all: global budget exhausted before {name}")
                break
            res = _attempt(name, worker, args.batch or batch,
                           args.steps or steps,
                           min(args.budget or budget, rem - 30),
                           args.precision, seq_len=args.seq_len)
            if res is not None:
                res["model"] = model
                print(json.dumps(res), flush=True)
                results.append(res)
                break
    if not results:
        print(json.dumps({"metric": "bench_failed", "value": 0.0,
                          "unit": "", "vs_baseline": 0.0,
                          "error": "no workload produced a number"}),
              flush=True)
        sys.exit(1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None, choices=_MODELS)
    ap.add_argument("--all", action="store_true",
                    help="run every BASELINE workload; one JSON line each "
                    "(headline driver mode stays single-line)")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--precision", default="bf16", choices=["bf16", "fp32"])
    ap.add_argument("--budget", type=float, default=None,
                    help="per-attempt wall budget (seconds)")
    ap.add_argument("--seq-len", type=int, default=None,
                    help="override sequence length for lstm/transformer "
                    "(forwarded to workers in driver mode)")
    ap.add_argument("--worker", default=None, choices=_MODELS,
                    help="internal: run one attempt in this process")
    args = ap.parse_args()
    _apply_seq_len_override(args)

    if args.worker:
        dflt_b, dflt_s, _ = _LADDERS[args.worker][0]
        args.batch = args.batch or dflt_b
        args.steps = args.steps or dflt_s
        args.budget = args.budget or 600
        run_worker(args)
        return

    if args.all:
        run_all(args)
        return

    # driver headline: the resnet50 ladder, largest batch first
    attempts = _model_attempts(args.model or "resnet50")
    # user overrides apply to EVERY attempt
    if args.batch:
        attempts = [(f"{w}-b{args.batch}", w, args.batch, s, b)
                    for _, w, _, s, b in attempts]
    if args.steps:
        attempts = [(n, w, bt, args.steps, b) for n, w, bt, _, b in attempts]
    if args.budget:
        attempts = [(n, w, bt, s, args.budget) for n, w, bt, s, _ in attempts]
    seen, uniq = set(), []
    for a in attempts:  # overrides can collapse attempts into duplicates
        key = (a[1], a[2])
        if key not in seen:
            seen.add(key)
            uniq.append(a)
    attempts = uniq

    # Global deadline: the driver kills the whole run, so the ladder must
    # end (with a number or with bench_failed) inside it.
    try:
        total_budget = float(os.environ.get("BENCH_TOTAL_BUDGET") or 1200)
    except ValueError:
        log("ignoring unparseable BENCH_TOTAL_BUDGET")
        total_budget = 1200.0

    # TPU compile alone takes minutes: an attempt whose post-clamp budget
    # would fall under ~4 min can only burn wall-clock, never succeed.
    # grace = subprocess kill margin.
    min_useful, grace = 240, 90
    for name, worker, batch, steps, budget in attempts:
        rem = total_budget - (time.monotonic() - _T_START)
        if rem - grace < min_useful:
            log(f"attempt {name}: SKIPPED ({rem:.0f}s left in "
                "global budget)")
            continue
        budget = min(budget, rem - grace)
        res = _attempt(name, worker, batch, steps, budget,
                       args.precision, grace=grace, seq_len=args.seq_len)
        if res is not None:
            # The fused conv+BN self-A/B that lived here was answered on
            # hardware in round 3: the Pallas fused path LOSES to XLA's
            # native convs (2539 plain vs 1165/1854/1112 img/s for
            # 1x1/3x3/both at b=256) — see PERF.md. The flags remain as
            # manual levers only; spending driver budget re-asking is waste.
            print(json.dumps(res), flush=True)
            return
    # Every attempt failed: still emit a parseable line so the driver
    # records a diagnosis instead of rc=124 with nothing.
    print(json.dumps({
        "metric": "bench_failed",
        "value": 0.0,
        "unit": "",
        "vs_baseline": 0.0,
        "error": "all attempts failed or timed out; see stderr",
    }), flush=True)
    sys.exit(1)


if __name__ == "__main__":
    main()
