"""One timeline (PR 23): the program's spans inside the profiler's trace,
the training iteration spanned end to end, names on the kernels and on the
fused CE, raw per-request times from the continuous server."""

import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.telemetry import tracing

LOOP_CHILDREN = ("train.data", "train.dispatch", "train.sync", "train.log",
                 "train.hooks")


@pytest.fixture
def tracer():
    tracing.disable()
    tracing.clear()
    yield tracing
    tracing.disable()
    tracing.clear()


def _xplane_events(log_dir):
    """{event name: [stats dict, ...]} over the host plane of the newest
    xplane under ``log_dir``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(dict(e.stats))
    return out


# ------------------------------------------------------------ tracing.py

def test_disabled_span_is_the_shared_noop_and_reads_no_clock(
        tracer, monkeypatch):
    def clock():
        raise AssertionError("the disabled path read a clock")
    monkeypatch.setattr(time, "perf_counter", clock)
    a, b = tracing.span("x", k=1), tracing.step_span("y", 3)
    assert a is b is tracing._NOOP
    with a as s:
        s.annotate(z=1)
    tracing.complete_event("q", 0.0, 1.0)
    tracing.async_instant("r", 1)
    monkeypatch.undo()
    assert tracing.events() == []


def test_origin_places_events_on_perf_counter(tracer):
    tracer.enable()
    t0 = time.perf_counter()
    with tracing.span("o"):
        pass
    t1 = time.perf_counter()
    (ev,) = tracing.events()
    start = tracing.origin() + ev["ts"] / 1e6
    assert t0 <= start <= start + ev["dur"] / 1e6 <= t1


def test_enabled_span_is_a_trace_annotation_in_the_profile(tracer, tmp_path):
    tracer.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.step_span("unit.step", 7, epoch=2):
            with tracing.span("unit.inner", neval=7) as s:
                s.annotate(extra="late")
                jnp.ones((8, 8)).sum().block_until_ready()
        tracing.complete_event("unit.retro", time.perf_counter() - 1e-3,
                               time.perf_counter())
    finally:
        jax.profiler.stop_trace()
    found = _xplane_events(tmp_path)
    (inner,) = found["unit.inner"]
    assert inner["neval"] == 7 and inner["extra"] == "late"
    (step,) = found["unit.step"]
    assert step["step_num"] == 7 and step["epoch"] == 2
    assert step["_r"] == 1      # a StepTraceAnnotation: xprof's step marker
    assert "unit.retro" not in found        # ring buffer only
    names = [e["name"] for e in tracing.events()]
    assert sorted(names) == ["unit.inner", "unit.retro", "unit.step"]


# ------------------------------------------------------- the training loop

def _tiny_optimizer(n_samples=48, batch=16, epochs=3):
    from bigdl_tpu.dataset.base import DataSet, Sample, SampleToBatch
    from bigdl_tpu.optim import SGD, Optimizer, Trigger
    rng = np.random.default_rng(0)
    samples = [Sample(rng.normal(size=(8,)).astype("float32"),
                      float(rng.integers(1, 5))) for _ in range(n_samples)]
    model = nn.Sequential()
    model.add(nn.Linear(8, 32)).add(nn.ReLU())
    model.add(nn.Linear(32, 4)).add(nn.LogSoftMax())
    opt = Optimizer(model, DataSet.array(samples) >> SampleToBatch(batch),
                    nn.ClassNLLCriterion())
    opt.set_optim_method(SGD(learningrate=0.01))
    opt.set_end_when(Trigger.max_epoch(epochs))
    return opt


def _inside(child, parent):
    return (parent["ts"] <= child["ts"] and
            child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


def test_training_loop_is_a_partition_of_named_spans(tracer):
    opt = _tiny_optimizer()
    tracer.enable()
    opt.optimize()
    tracer.disable()
    evs = [e for e in tracing.events() if e["name"].startswith("train.")]
    iters = [e for e in evs if e["name"] == "train.iteration"]
    real = [e for e in iters if e["args"]["k"] > 0]
    assert len(real) == 9 and len(iters) == 12     # 3 a epoch + the k=0 pass
    assert [e["args"]["step_num"] for e in real] == list(range(1, 10))
    for e in evs:
        assert "neval" in e["args"] or "step_num" in e["args"], e
    covered = total = 0.0
    for i, it in enumerate(real[1:], 1):    # the first one compiles
        kids = [e for e in evs if e["name"] in LOOP_CHILDREN
                and _inside(e, it)]
        # an epoch's first iteration has nothing in flight to wait for:
        # the epoch's end drained the pipeline
        want = set(LOOP_CHILDREN) - ({"train.sync", "train.log"}
                                     if i % 3 == 0 else set())
        assert {e["name"] for e in kids} == want, it
        # train.sync / train.log may also sit inside train.hooks: count
        # top-level children only
        top = [e for e in kids
               if not any(o is not e and _inside(e, o) for o in kids)]
        covered += sum(e["dur"] for e in top)
        total += it["dur"]
    assert covered >= 0.95 * total, (covered, total)
    dispatched = {}
    for e in sorted(evs, key=lambda e: e["ts"]):
        if e["name"] == "train.dispatch":
            dispatched[e["args"]["neval"]] = e["ts"]
        elif e["name"] == "train.sync":
            assert dispatched[e["args"]["neval"]] < e["ts"], e
    ends = [e for e in evs if e["name"] == "train.epoch_end"]
    assert [e["args"]["epoch"] for e in ends] == [1, 2, 3]
    for end, nxt in zip(ends, (real[3], real[6])):
        # the boundary closes where the next epoch's first iteration opens
        assert end["ts"] + end["dur"] <= nxt["ts"]


def test_set_profiling_puts_the_spans_in_the_profile(tracer, tmp_path):
    from bigdl_tpu.optim import Trigger
    opt = _tiny_optimizer()
    opt.set_end_when(Trigger.max_iteration(6))
    opt.set_profiling(str(tmp_path), start_iteration=2, n_iterations=3)
    opt.optimize()
    assert not tracing.is_enabled()     # on for the profiled window only
    found = _xplane_events(tmp_path)
    steps = sorted(s["step_num"] for s in found["train.iteration"]
                   if s.get("k", 1))
    assert steps[:3] == [2, 3, 4], steps
    for name in LOOP_CHILDREN:
        assert name in found, name


def test_train_compiles_total_is_gone():
    from bigdl_tpu.telemetry import catalogue
    names = {m.name for m in catalogue.METRIC_SPECS}
    assert "bigdl_train_compiles_total" not in names
    assert "bigdl_compiles_total" in names
    spans = {n for n, _ in catalogue.SPAN_SPECS}
    assert {"train.iteration", "train.epoch_end", *LOOP_CHILDREN} <= spans


# ------------------------------------------------------------------- names

def test_flash_and_int8_kernels_carry_their_names():
    from bigdl_tpu.ops import flash_attention as fa
    from bigdl_tpu.ops.int8_matmul import _int8_matmul_pallas
    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True,
                                  interpret=True).sum()
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    for name in ("flash_fwd", "flash_bwd_dkv"):
        assert f"name={name}" in text, name
    assert "name=flash_bwd_dq" not in text   # dQ leaves the dK/dV call
    x = jnp.ones((4, 128), jnp.bfloat16)
    w = jnp.ones((256, 128), jnp.int8)
    scale = jnp.ones((256,), jnp.float32)
    text = str(jax.make_jaxpr(
        lambda x: _int8_matmul_pallas(x, w, scale, interpret=True))(x))
    assert "name=int8_matmul" in text


@pytest.mark.parametrize("chunk,loops", [(8, 1), (None, 0)])
def test_lm_head_ce_scope_names_its_loop_and_products_in_a_tiny_lm_step(
        chunk, loops):
    """The compiled train step of a tiny LM with the fused head: at more
    than one row tile ONE ``while`` (loss and gradients in one pass, traced
    as the custom_vjp's forward rule) whose op_name holds the scope; at one
    tile no loop is left and the head's three products, those with the
    vocabulary among their shapes, carry the scope themselves."""
    from bigdl_tpu.dataset.base import DataSet, Sample, SampleToBatch
    from bigdl_tpu.models import transformer
    from bigdl_tpu.optim import SGD, Optimizer, Trigger
    rng = np.random.RandomState(0)
    rows = [rng.randint(1, 40, size=(16,)).astype(np.float32)
            for _ in range(8)]
    model = transformer.build_lm(40, 16, 2, 32, num_layers=1, max_len=16,
                                 fused_head=True)
    opt = Optimizer(model, DataSet.array([Sample(r, r) for r in rows])
                    >> SampleToBatch(4), nn.FusedLMHeadCriterion(chunk=chunk))
    opt.set_optim_method(SGD(learningrate=0.1))
    opt.set_end_when(Trigger.max_iteration(2))
    from bigdl_tpu.telemetry import get_registry, instruments
    one_pass = instruments(get_registry()).lm_head_ce_total.labels(
        form="one_pass")
    before = one_pass.value
    opt.optimize()
    assert one_pass.value == before + 1     # once a compiled step
    (hlo,) = getattr(opt.step_fn, "tracked", opt.step_fn).compiled_texts()

    def op_names(opcode, shape_part=""):
        return [ln.split('op_name="', 1)[1].split('"', 1)[0]
                for ln in hlo.splitlines()
                if f" {opcode}(" in ln and "op_name=" in ln
                and shape_part in ln.split(f" {opcode}(", 1)[0]]

    scoped = [name for name in op_names("while") if "lm_head_ce" in name]
    assert len(scoped) == loops, scoped
    assert not any("transpose(" in name for name in scoped), scoped
    products = [name for name in op_names("dot") if "lm_head_ce" in name]
    assert len(products) == 3, products         # logits, dh, dW
    wide = op_names("dot", "40")                # V in the output's shape
    assert len(wide) == 2 and set(wide) <= set(products), wide


# ------------------------------------------------- raw per-request times

def _tiny_server(**kw):
    from bigdl_tpu.models import transformer
    from bigdl_tpu.models.serving import ContinuousLMServer
    from bigdl_tpu.telemetry import MetricsRegistry
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(11)
    model = transformer.build_lm(24, 16, 2, 32, num_layers=1, max_len=32,
                                 rope=True, norm="rms")
    return ContinuousLMServer(model, slots=2, max_len=32, greedy=True,
                              decode_block=3, registry=MetricsRegistry(),
                              **kw)


def test_submit_timed_returns_submits_tokens_and_ordered_raw_times(tracer):
    srv = _tiny_server()
    try:
        assert not tracing.is_enabled()
        t0 = time.perf_counter()
        toks, tm = srv.submit_timed([3, 7, 2], max_new_tokens=8, timeout=120)
        t1 = time.perf_counter()
        assert toks == srv.submit([3, 7, 2], max_new_tokens=8, timeout=120)
    finally:
        srv.close()
    assert len(toks) == 8 and len(tm.token_times) == 8
    assert tm.token_times[0] == tm.first_token
    chain = [t0, tm.submitted, tm.admitted, tm.first_token,
             *tm.token_times, tm.done, t1]
    assert chain == sorted(chain), chain
    # decode_block=3: after the admission's token, tokens arrive in blocks
    # of three that share the time their block reached the host
    assert len(set(tm.token_times)) == 4


def test_request_lane_has_first_token_and_blocks_count_tokens(tracer):
    srv = _tiny_server()
    tracer.enable()
    try:
        toks, tm = srv.submit_timed([3, 7, 2], max_new_tokens=5, timeout=120)
    finally:
        tracer.disable()
        srv.close()
    evs = tracing.events()
    lane = [e for e in evs if e["name"] == "serving.request"]
    (begin,) = [e for e in lane if e["ph"] == "b"]
    (first,) = [e for e in lane if e["ph"] == "n"
                and e["args"]["phase"] == "first_token"]
    assert first["id"] == begin["id"]
    at = tracing.origin() + first["ts"] / 1e6
    assert abs(at - tm.first_token) < 1e-3 and at > tm.submitted
    blocks = [e for e in evs if e["name"] == "serving.decode_block"]
    assert [e["args"]["tokens"] for e in blocks] == [[3], [1]]
    assert all(e["args"]["rids"] == [begin["id"]] for e in blocks)


# ------------------------------------------------------- device memory

def test_sample_device_memory_publishes_the_fullest_device(monkeypatch):
    from bigdl_tpu.telemetry import MetricsRegistry, instruments, profiling

    class Dev:
        def __init__(self, in_use, peak):
            self._s = {"bytes_in_use": in_use, "peak_bytes_in_use": peak}

        def memory_stats(self):
            return self._s
    monkeypatch.setattr(profiling, "_mem_unsupported", False)
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [Dev(10, 90), Dev(70, 80), Dev(30, 99)])
    reg = MetricsRegistry()
    assert profiling.sample_device_memory(reg) == 80
    tm = instruments(reg)
    assert tm.device_memory_bytes.labels().value == 70
    assert tm.device_memory_peak_bytes.labels().value == 80
