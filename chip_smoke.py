"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py        # on a machine with a TPU; ~2 min cold

One process (it starts no child: a process that has touched jax holds the
chip) drives the trainer's main path through the entry points a user calls
— ``build -> Optimizer(...).optimize()``, wired as ``apps/perf.py`` wires it
— at the full width of the models the baseline names, and checks what comes
out by the repo's own means:

1. ``resnet50``: ``models.resnet.build(1000, depth=50)``, 224x224x3, b=256,
   bf16 policy, SGD with momentum, on one chip;
2. ``lm134m``: the ``transformer_134m`` preset (E=768, 12 heads, 12 layers,
   V=32000, s=1024, b=8, fused LM-head criterion) — the step that selects
   the Pallas flash-attention kernels, the forward and the one backward;
3. ``decode``: 16 greedy tokens at B=1 from ``generate(quantize_model(lm))``,
   every projection and the V=32000 head through the int8 Pallas kernel;
4. ``kernels``: flash attention and the int8 matmul against their XLA
   formulations on the same shapes, within the repo's test tolerances;
5. ``timeline``: a 5-step ``set_profiling`` profile of phase 2's step, read
   back with the benchmark's own readers: the loop's ``train.*`` spans as
   annotations beside the runtime's enqueues, the two ``flash_*`` kernel
   names on the Mosaic calls, the ``lm_head_ce`` scope on the head's one
   ``while`` loop — so a jax upgrade that renames any of them fails here,
   cheaply;
6. ``resnet50_mesh``: phase 1 through ``DistriOptimizer`` over every visible
   device, 256 per chip — when there is more than one device.

Every phase asserts: finite losses, the last lower than the first,
parameters on ``tpu`` devices and changed, exactly one compile per site, the
per-iteration ``Throughput is N records/second`` line. The rates are printed
and nothing is asserted about them. Its first act is to fail unless jax's
default backend is ``tpu`` and the peak table knows the device kind; a
failed check raises, so the run cannot end 0 with a phase failed. The last
line of stdout is one JSON object naming the device as jax reports it.
"""

import glob
import json
import logging
import os
import re
import shutil
import sys
import time

import numpy as np

WARMUP, MEASURED = 3, 10          # ResNet iterations: a handful + about ten
LM_ITERS = 8
PROMPT_LEN = NEW_TOKENS = 16
MOSAIC = "tpu_custom_call"        # custom_call_target of a Pallas TPU kernel
PLATFORM = "tpu"                  # tests say "cpu" to run the plumbing off-chip
_LINE = re.compile(r"Throughput is ([\d.]+) records/second\. "
                   r"Loss is (-?\d+\.\d+|nan|inf)")


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(msg):
    print(msg, flush=True)


class _Lines(logging.Handler):
    """Echo the trainer's progress log and keep it for the checks."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.lines = []

    def emit(self, record):
        line = record.getMessage()
        self.lines.append(line)
        say("  | " + line)


def compile_ledger():
    """{site: (compiles, compile seconds)} from the flight recorder."""
    from bigdl_tpu.telemetry import get_registry
    counts, secs = {}, {}
    for fam in get_registry().collect():
        for s in fam["samples"]:
            site = s["labels"].get("site")
            if fam["name"] == "bigdl_compiles_total":
                counts[site] = int(s["value"])
            elif fam["name"] == "bigdl_compile_seconds":
                secs[site] = s["histogram"]["sum"]
    return {k: (counts[k], secs.get(k, 0.0)) for k in counts}


class Phase:
    """Times a phase and reports its compiles by site (ledger deltas)."""

    def __init__(self, name, report):
        self.name, self.report = name, report

    def __enter__(self):
        say(f"\n== {self.name}")
        self.t0, self.before = time.perf_counter(), compile_ledger()
        return self

    def compiles(self):
        out = {}
        for site, (n, s) in compile_ledger().items():
            n0, s0 = self.before.get(site, (0, 0.0))
            if n > n0:
                out[site] = (n - n0, round(s - s0, 2))
        return out

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            wall = round(time.perf_counter() - self.t0, 1)
            comp = self.compiles()
            self.report[self.name] = {"wall_s": wall, "compile_s_by_site":
                                      {k: v[1] for k, v in comp.items()}}
            say(f"== {self.name}: ok, wall {wall}s, compiles by site "
                f"(count, seconds): {comp}")


# ---------------------------------------------------------------- the trainer

def image_samples(n, image, classes, seed):
    """Noise images; labels from a tenth of the classes, so the class prior
    is learnable inside a dozen steps whatever the batch order."""
    from bigdl_tpu.dataset.base import Sample
    rng = np.random.RandomState(seed)
    return [Sample(rng.randn(image, image, 3).astype(np.float32),
                   np.float32(rng.randint(1, classes // 10 + 1)))
            for _ in range(n)]


def token_samples(n, seq, seed, subset=512):
    """Token rows from a small slice of the vocabulary, target = input: the
    embedding -> head alignment is learnable inside a few steps."""
    from bigdl_tpu.dataset.base import Sample
    rng = np.random.RandomState(seed)
    rows = [rng.randint(1, subset + 1, (seq,)).astype(np.float32)
            for _ in range(n)]
    return [Sample(r, r.copy()) for r in rows]


def train(model, criterion, samples, batch, iters, lr, cast, distributed,
          clip=None, profile=None):
    """``apps/perf.py:main``'s wiring: device-resident cache -> Optimizer
    facade -> bf16 policy -> optimize(), then the checks every trainer
    phase shares. ``profile`` is ``set_profiling``'s arguments. Returns
    (optimizer, losses, rates)."""
    import jax
    from bigdl_tpu.dataset import DeviceCachedDataSet
    from bigdl_tpu.dataset.base import DataSet
    from bigdl_tpu.ops.precision import DtypePolicy
    from bigdl_tpu.optim import SGD, Optimizer, Trigger

    ds = DeviceCachedDataSet(DataSet.array(samples, distributed=distributed),
                             batch_size=batch, cast_dtype=cast)
    opt = Optimizer(model, ds, criterion)
    want = "DistriOptimizer" if distributed else "LocalOptimizer"
    check(type(opt).__name__ == want, f"facade built {type(opt).__name__}")
    opt.set_optim_method(SGD(learningrate=lr, momentum=0.9))
    opt.set_precision(DtypePolicy.bf16())   # the default policy is fp32
    if clip:
        opt.set_gradient_clipping_by_l2_norm(clip)
    opt.set_end_when(Trigger.max_iteration(iters))
    if profile:
        opt.set_profiling(*profile)
    before = jax.tree_util.tree_map(np.asarray, model.parameter_tree())

    log, tap = logging.getLogger("bigdl_tpu.optim"), _Lines()
    level = log.level
    log.addHandler(tap)
    log.setLevel(logging.INFO)
    try:
        opt.optimize()
    finally:
        log.removeHandler(tap)
        log.setLevel(level)

    hits = [m for m in map(_LINE.search, tap.lines) if m]
    check(len(hits) == iters,
          f"{len(hits)} 'Throughput is N records/second' lines, not {iters}")
    rates = [float(m.group(1)) for m in hits]
    losses = [float(m.group(2)) for m in hits]
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")

    after = model.parameter_tree()
    leaves = jax.tree_util.tree_leaves(after)
    n_dev = len(jax.devices()) if distributed else 1
    for leaf in leaves:
        devs = leaf.devices()
        check({d.platform for d in devs} == {PLATFORM}
              and len(devs) == n_dev, f"a parameter lives on {devs}, "
              f"wanted {n_dev} {PLATFORM} device(s)")
    moved = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: bool(np.any(a != np.asarray(b))), before, after))
    check(np.mean(moved) > 0.9, f"only {sum(moved)}/{len(moved)} parameter "
          "leaves changed")
    say(f"  losses {losses[0]:.4f} -> {losses[-1]:.4f}; steady rate seen "
        f"(median of the last {len(rates) - WARMUP}) "
        f"{np.median(rates[WARMUP:]):.1f} records/s over {n_dev} device(s); "
        f"{sum(moved)}/{len(moved)} parameter leaves changed, all on "
        f"{PLATFORM}")
    return opt, losses, rates


def step_text(opt):
    """Optimized-HLO text of the one program ``train.step`` ran."""
    texts = getattr(opt.step_fn, "tracked", opt.step_fn).compiled_texts()
    check(len(texts) == 1, f"{len(texts)} train.step programs retained")
    return texts[0]


def mosaic_calls(text):
    """(forward, backward) Mosaic custom calls in optimized-HLO text;
    autodiff names a backward kernel's op ``transpose(jvp(...))``."""
    lines = [line for line in text.splitlines() if MOSAIC in line]
    backward = sum("transpose(jvp" in line for line in lines)
    return len(lines) - backward, backward


def one_compile(phase, site):
    n = phase.compiles().get(site, (0, 0))[0]
    check(n == 1, f"bigdl_compiles_total{{site={site!r}}} rose by {n}, not 1")


def phase_resnet(report, distributed):
    import jax
    from bigdl_tpu import nn
    from bigdl_tpu.models import resnet

    n_dev = len(jax.devices()) if distributed else 1
    batch = 256 * n_dev
    name = "resnet50_mesh" if distributed else "resnet50"
    with Phase(name, report) as ph:
        say(f"  ResNet-50, 224x224x3, global batch {batch} (256 per chip), "
            f"bf16, {WARMUP}+{MEASURED} steps")
        opt, _, _ = train(
            resnet.build(1000, depth=50), nn.ClassNLLCriterion(),
            image_samples(2 * batch, 224, 1000, seed=1), batch,
            WARMUP + MEASURED, lr=0.01, cast="bfloat16",
            distributed=distributed)
        one_compile(ph, "train.step")
        if distributed:
            check_mesh(opt, batch, n_dev)
            used = [d.memory_stats()["bytes_in_use"] for d in jax.devices()]
            check(all(u > 0 for u in used), f"bytes_in_use by device: {used}")
            say(f"  bytes_in_use by device: {used}")


def check_mesh(opt, batch, n_dev):
    """What only several chips can show: the batch's shards on distinct
    devices, and an all-reduce over all of them in the compiled step."""
    from bigdl_tpu.analysis import commcost

    data, _ = opt._place_batch(next(iter(opt.dataset.data(train=True))))
    homes = {s.device for s in data.addressable_shards}
    check(len(homes) == n_dev and all(
        s.data.shape[0] == batch // n_dev for s in data.addressable_shards),
        f"batch shards sit on {homes}")
    # ring all-reduce moves 2B(S-1)/S bytes per device: the ratio names S
    ar = commcost.collective_bytes_from_hlo(step_text(opt))["per_op"].get(
        "all-reduce", {"count": 0, "payload_bytes": 0, "wire_bytes": 0})
    want = 2 * (n_dev - 1) / n_dev
    check(ar["count"] > 0 and ar["payload_bytes"] > 0 and abs(
        ar["wire_bytes"] / ar["payload_bytes"] - want) < 1e-6,
        f"no all-reduce over a group of {n_dev} in the step: {ar}")
    say(f"  batch shards on {len(homes)} distinct devices; {ar['count']} "
        f"all-reduce op(s) over groups of {n_dev}, {ar['payload_bytes']:.0f} "
        "payload bytes")


def phase_lm(report):
    from bigdl_tpu import nn
    from bigdl_tpu.apps.perf import _build_model

    with Phase("lm134m", report) as ph:
        model, (seq,), *_ = _build_model("transformer_134m")
        say(f"  transformer_134m preset, s={seq}, b=8, bf16, fused LM-head "
            f"criterion, {LM_ITERS} steps")
        opt, _, _ = train(model, nn.FusedLMHeadCriterion(),
                          token_samples(16, seq, seed=2), 8, LM_ITERS,
                          lr=0.1, cast=None, distributed=False, clip=1.0)
        one_compile(ph, "train.step")
        # per attention layer: the forward kernel, then the one backward
        # call (dQ, dK and dV)
        fwd, bwd = mosaic_calls(step_text(opt))
        check(fwd >= 1 and bwd >= fwd, f"{fwd} forward + {bwd} backward "
              "Mosaic custom calls in the LM train step: the XLA attention "
              "core stood in for a flash kernel")
        say(f"  Mosaic custom calls in the compiled LM train step: {fwd} "
            f"forward, {bwd} backward")
    return model


LOOP_SPANS = ("train.iteration", "train.data", "train.dispatch",
              "train.sync", "train.log", "train.hooks")
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dkv")      # dQ leaves the second
PROFILE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           ".bench_scratch", "smoke_profile")
KEPT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "chiprun_out")


def own_runs(runs, host, lag):
    """Of the step runs ``rx.program_runs`` found in a profile, those the
    profile's own iterations dispatched. The session opens while the step of
    the iteration before it still runs: the device plane holds that run cut
    off at the session's start, its ``DoEnqueueProgram`` is from before the
    session, and the reader takes the stump for a whole run whenever the
    cut falls on the slice's first operation (5 of 8 profiles, PR 24's call
    32). A run of the profile cannot begin on the device before the
    profile's first ``train.dispatch`` began on the host (``lag`` puts the
    device's clock on the host's)."""
    first = min(s[1] for s in host.named("train.dispatch"))
    return [r for r in runs if r[1] + lag >= first]


def phase_timeline(report):
    """The names the benchmark's readers stand on, in a real profile."""
    from benchmark import reduce_xplane as rx
    from benchmark import timeline
    from bigdl_tpu import nn
    from bigdl_tpu.apps.perf import _build_model
    from bigdl_tpu.ops.lm_head_ce import rows_per_tile

    with Phase("timeline", report):
        model, (seq,), vocab, *_ = _build_model("transformer_134m")
        shutil.rmtree(PROFILE_DIR, ignore_errors=True)
        opt, _, _ = train(model, nn.FusedLMHeadCriterion(),
                          token_samples(16, seq, seed=2), 8, LM_ITERS,
                          lr=0.1, cast=None, distributed=False, clip=1.0,
                          profile=(PROFILE_DIR, 3, 5))
        found = sorted(glob.glob(os.path.join(
            PROFILE_DIR, "plugins", "profile", "*", "*.xplane.pb")))
        check(found, f"set_profiling left no xplane under {PROFILE_DIR}")
        host, trace = timeline.load_host(found[-1]), rx.load(found[-1])
        for name in LOOP_SPANS:
            check(host.named(name), f"no {name!r} annotation in the "
                  "profile's host plane")
        steps = sorted(s[3].get("step_num") for s in
                       host.named("train.iteration") if s[3].get("k"))
        check(steps == [3, 4, 5, 6, 7] and all(
            s[3].get("_r") == 1 for s in host.named("train.iteration")),
            f"train.iteration step markers {steps}, not 3..7")
        dev = trace.devices[0]
        lo, hi = rx.slice_bounds(trace)
        lag = rx.device_clock_lag(trace)
        runs = own_runs(rx.program_runs(dev, lo, hi), host, lag)
        lost = [r[3] for r in runs if host.enqueue_of(r[3], 0) is None]
        if lost:
            os.makedirs(KEPT_DIR, exist_ok=True)
            shutil.copy(found[-1], os.path.join(
                KEPT_DIR, "smoke_timeline_unmatched.xplane.pb"))
        check(len(runs) >= 4 and not lost,
              f"{len(runs)} runs of the step program, those with run_id "
              f"{lost} without their DoEnqueueProgram; the xplane is kept "
              f"under {KEPT_DIR}")
        mosaic = {rx.family(o) for o in dev.ops if o.is_mosaic}
        for kernel in FLASH_KERNELS:
            check(any(kernel in fam for fam in mosaic), f"no Mosaic call "
                  f"named after {kernel!r} on the device: {sorted(mosaic)}")
        scoped = timeline.scope_instructions(step_text(opt), "lm_head_ce")
        whiles = {o.name for o in dev.ops
                  if o.opcode == "while" and o.name in scoped}
        # loss and gradients in one loop over row tiles; none at one tile
        loops = int(rows_per_tile(8 * seq, vocab) < 8 * seq)
        check(scoped and len(whiles) == loops, f"{sorted(whiles)} of the "
              "step's while loops run under the scope 'lm_head_ce', not "
              f"{loops}")
        sec, n = timeline.scope_seconds(trace, scoped, lo, hi)
        say(f"  profile of steps {steps}: {len(host.spans)} train.* "
            f"annotations, {len(host.enqueues)} enqueues ({len(runs)} step "
            f"runs, each with its own); Mosaic calls "
            f"{sorted(mosaic)}; lm_head_ce loops {sorted(whiles)}, "
            f"{1e3 * sec / n:.2f} ms a step of "
            f"{1e3 * (runs[0][2] - runs[0][1]):.2f}; device clock "
            f"{1e3 * lag:.2f} ms behind the host's")


def phase_decode(report, lm):
    import jax.numpy as jnp
    from bigdl_tpu import nn
    from bigdl_tpu.models.generation import generate
    from bigdl_tpu.telemetry import get_registry, instruments

    with Phase("decode", report) as ph:
        fallbacks = instruments(get_registry()).int8_fallbacks_total
        before = fallbacks.value
        qlm = nn.quantize_model(lm)
        prompt = jnp.asarray(
            np.random.RandomState(3).randint(1, 513, (1, PROMPT_LEN)),
            jnp.float32)
        out = np.asarray(generate(qlm, prompt, NEW_TOKENS, greedy=True))
        check(out.shape == (1, PROMPT_LEN + NEW_TOKENS), f"shape {out.shape}")
        check((out[:, :PROMPT_LEN] == np.asarray(prompt)).all(),
              "the prompt did not come back")
        new = out[0, PROMPT_LEN:]
        check(np.isfinite(new).all() and (new >= 1).all()
              and (new <= 32000).all(), f"token ids out of range: {new}")
        check(fallbacks.value == before, "bigdl_int8_fallbacks_total rose by "
              f"{fallbacks.value - before}")
        one_compile(ph, "generation.decode")
        (fn,) = qlm._generate_fns.values()
        n = sum(mosaic_calls(fn.compiled_texts()[0]))
        check(n >= 1, "no Mosaic custom call in the decode program: the "
              "XLA dequant path stood in for the int8 kernel")
        say(f"  {NEW_TOKENS} greedy tokens {new.astype(int).tolist()}; "
            f"{n} Mosaic custom calls in the compiled decode program; "
            "bigdl_int8_fallbacks_total == 0")


def flash_kernel_ms(q, k, v, runs=8, calls=6):
    """Milliseconds a run of each of the two flash kernels alone (the
    forward; the backward call that returns dQ, dK and dV, with the small
    ``delta`` reduction it reads), causal, at the blocks the model runs them
    with. The arrays go in as (B*N, S, 1, D), the kernels' own layout, so no
    transpose runs beside them. One timed call is one jit that runs the
    kernel ``runs`` times, each on the last one's result, so the device is
    never waiting for a dispatch. A timed region is ``calls`` calls ended by
    a device->host fetch; the best of three."""
    import jax
    from bigdl_tpu.ops import flash_attention as fa

    b, s, n, d = q.shape
    q, k, v = (x.transpose(0, 2, 1, 3).reshape(b * n, s, 1, d)
               for x in (q, k, v))
    # causal, the model's scale, each kernel's own default blocks; interpret
    # mode off the chip (tests)
    args = (True, 1.0 / d ** 0.5, None, None, PLATFORM != "tpu")
    o, lse = jax.jit(lambda q, k, v: fa._flash_fwd_lse(q, k, v, *args))(
        q, k, v)

    steps = {
        "flash_fwd": lambda q, k, v: (fa._flash_fwd_lse(q, k, v, *args)[0],
                                      k, v),
        "flash_bwd_dkv": lambda q, k, v: fa._flash_bwd(
            q, k, v, o, lse, o, None, *args),
    }
    out = {}
    for name, step in steps.items():
        def chain(q, k, v, step=step):
            for _ in range(runs):
                q, k, v = step(q, k, v)
            return q, k, v
        fn = jax.jit(chain)
        jax.block_until_ready(fn(q, k, v))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(calls):
                res = fn(q, k, v)
            np.asarray(res[0][0, 0, 0])
            best = min(best,
                       (time.perf_counter() - t0) / (calls * runs) * 1e3)
        out[name] = round(best, 4)
    return out


def phase_kernels(report):
    """Kernel vs XLA formulation on the shapes phases 2-3 ran. Tolerances
    are the repo's own: flash forward 2e-2 abs in bf16 and gradients 5e-2
    of the largest reference gradient (the retired validate script's), int8
    rtol 2e-2 / atol 3e-2 (tests/test_quantized.py)."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.nn.quantized import quantize_array
    from bigdl_tpu.ops import attention_core
    from bigdl_tpu.ops.flash_attention import flash_attention, use_flash
    from bigdl_tpu.ops.int8_matmul import int8_matmul, kernel_applicable

    def f32(x):
        return np.asarray(x.astype(jnp.float32))

    def loss(attend):
        return lambda q, k, v: jnp.sum(
            attend(q, k, v, causal=True).astype(jnp.float32) ** 2)

    def both(attend, q, k, v):
        fn = jax.jit(lambda q, k, v: (
            attend(q, k, v, causal=True),
            jax.grad(loss(attend), argnums=(0, 1, 2))(q, k, v)))
        return fn, fn(q, k, v)

    with Phase("kernels", report):
        rng = np.random.RandomState(4)
        # the 134M LM's attention and the benchmark's LM cell's own
        for shape in ((2, 1024, 12, 64), (2, 2048, 14, 64)):
            q, k, v = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                       for _ in range(3))
            check(use_flash(q, None), f"use_flash rejects {shape}")
            fn, (o_k, g_k) = both(flash_attention, q, k, v)
            fwd, bwd = mosaic_calls(fn.lower(q, k, v).compile().as_text())
            check(fwd >= 1 and bwd == 1, f"{fwd} forward + {bwd} backward "
                  "Mosaic custom calls: flash did not lower to its two "
                  "kernels")
            _, (o_x, g_x) = both(attention_core.dot_product_attention,
                                 q, k, v)
            err = float(np.max(np.abs(f32(o_k) - f32(o_x))))
            check(np.isfinite(f32(o_k)).all() and err < 2e-2,
                  f"flash forward differs from XLA by {err}")
            rels = []
            for name, a, b in zip(("dq", "dk", "dv"), g_k, g_x):
                rel = float(np.max(np.abs(f32(a) - f32(b)))
                            / (np.max(np.abs(f32(b))) + 1e-9))
                check(np.isfinite(f32(a)).all() and rel < 5e-2,
                      f"flash {name} differs from XLA by rel {rel}")
                rels.append(round(rel, 4))
            ms = flash_kernel_ms(q, k, v)
            report.setdefault("flash_kernel_ms", {})[
                "x".join(map(str, shape))] = ms
            say(f"  flash {shape} bf16 causal vs XLA core: forward max err "
                f"{err:.2e}; dq/dk/dv rel err {rels}; op-level ms a run "
                f"(8 dependent runs a call, fetch at the end) {ms}")

        # the 134M decode's matmuls: qkv, out, ffn up/down, the V=32000 head
        # (31 full 1024-row tiles and one quarter tile)
        for kdim, odim in ((768, 2304), (768, 768), (768, 3072), (3072, 768),
                           (768, 32000)):
            check(kernel_applicable(1, kdim, odim), f"gate excludes "
                  f"K={kdim} O={odim}")
            x = jnp.asarray(rng.randn(1, kdim), jnp.float32)
            wq, s = quantize_array(
                jnp.asarray(rng.randn(odim, kdim) * 0.1, jnp.float32), 0)
            got = f32(int8_matmul(x, wq, s))
            want = f32(jnp.matmul(
                x.astype(jnp.bfloat16),
                (wq.astype(jnp.bfloat16) * s.astype(jnp.bfloat16)).T))
            check(got.shape == (1, odim) and np.isfinite(got).all()
                  and np.allclose(got, want, rtol=2e-2, atol=3e-2),
                  f"int8 kernel K={kdim} O={odim} differs from the XLA "
                  f"dequant path by {np.max(np.abs(got - want))}")
        say("  int8 kernel vs XLA dequant path: 5 decode shapes agree, "
            "partial final tile of V=32000 included")


# ----------------------------------------------------------------------- main

def main():
    t0 = time.perf_counter()
    import jax
    import jaxlib

    import bigdl_tpu
    from bigdl_tpu import native
    from bigdl_tpu.telemetry.profiling import require_tpu

    dev, peak = require_tpu()      # first act: no TPU, no smoke
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    n_dev = len(jax.devices())
    say(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{libtpu}; {n_dev} x {dev.device_kind} ({dev.platform}), table peak "
        f"{peak / 1e12:.0f} TFLOP/s bf16; compile cache "
        f"{bigdl_tpu.compile_cache_dir()}")
    say("native library: " + ("built from native/src/*.cc and loaded"
                              if native.is_loaded()
                              else "not built; fell back to numpy"))

    report = {}
    phase_resnet(report, distributed=False)
    lm = phase_lm(report)
    phase_decode(report, lm)
    phase_kernels(report)
    phase_timeline(report)
    if n_dev > 1:
        phase_resnet(report, distributed=True)
    else:
        say("\n== resnet50_mesh: one device visible, the mesh phase did "
            "not run")

    say(f"\nphases: {json.dumps(report)}")
    say(f"total wall {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_dev}}),
        flush=True)


if __name__ == "__main__":
    sys.exit(main())
