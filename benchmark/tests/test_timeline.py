"""``benchmark/timeline.py`` and the readers built on it, on hand-built
traces: gaps charged by cause (the host's, the runtime's, inside a program,
no span at all), the epoch boundary, programs enqueued inside a span, the
scope reader on a hand-built HLO, the serve readers on hand-built lanes, and
the rule that the idle entries add up to the device's idle share; then the
same helper on a trace recorded on the v5e.

``data/epoch_boundary_v5e.xplane.pb``: PR 23, ``resnet50-train-1chip
--trace 1 --seed 3300000103``, cut to the 420 ms around one epoch boundary
(four runs of the step program): the device plane's ``XLA Modules`` and
``XLA Ops`` lines (names cut to 48 characters, stats dropped except
``run_id``) and the host plane's ``train.*`` annotations,
``DoEnqueueProgram`` and ``CompleteCallbacks`` events with their stats."""

import os

import pytest

from benchmark import reduce_xplane as rx, timeline
from benchmark.layer_metrics import (data_device_ms, device_idle_share,
                                     epoch_boundary_ms, idle_in_data_ms,
                                     idle_in_loop_ms, idle_runtime_ms,
                                     idle_unattributed_share, itl_p95_ms,
                                     lm_head_ce_roofline, lm_head_ce_share,
                                     ttft_p95_ms)

MS = 1e-3


def device(runs, name="/device:TPU:0"):
    """A device plane from [(program, run_id, [(op name, t0, t1), ...])]:
    a program's run spans its operations."""
    dev = rx.DevicePlane(name)
    for prog, run_id, ops in runs:
        dev.modules.append((f"{prog}(1)", ops[0][1], ops[-1][2], run_id))
        dev.ops += [rx.Op(n, f"%{n} = f32[] fusion()", a, b)
                    for n, a, b in ops]
    dev.ops.sort(key=lambda o: o.t0)
    return dev


def step(run_id, t0, dur=10 * MS):
    return ("jit_step", run_id, [("fusion.1", t0, t0 + dur)])


def loop_spans(t0, data=1.5 * MS, dispatch=1.5 * MS, neval=2):
    """One iteration's spans from ``t0``: data, then dispatch, inside the
    iteration."""
    return [("train.iteration", t0 - 0.5 * MS, t0 + 20 * MS,
             {"step_num": neval}),
            ("train.data", t0, t0 + data, {"neval": neval}),
            ("train.dispatch", t0 + data, t0 + data + dispatch,
             {"neval": neval})]


def approx(x):
    return pytest.approx(x, rel=1e-6, abs=1e-12)


# ------------------------------------------------------------ by cause

def test_host_caused_gap_is_split_over_the_innermost_spans():
    dev = device([step(1, 0.0), step(2, 14 * MS)])
    host = timeline.Host(spans=loop_spans(10 * MS),
                         enqueues={(1, 0): -1 * MS, (2, 0): 13 * MS})
    got = timeline.charge_device(dev, host, 0.0, 24 * MS, lag=0.0)
    # the host's time from the run's end (10) to the enqueue (13) is half
    # data, half dispatch; the WHOLE 4 ms gap is split that way
    assert got == {"train.data": approx(2 * MS),
                   "train.dispatch": approx(2 * MS)}


def test_device_clock_lag_is_used_at_the_one_boundary():
    lag = 1.5 * MS                  # the device's clock runs behind
    dev = device([step(1, 0.0 - lag), step(2, 14 * MS - lag)])
    host = timeline.Host(spans=loop_spans(10 * MS),
                         enqueues={(2, 0): 13 * MS})
    got = timeline.charge_device(dev, host, -lag, 24 * MS - lag, lag=lag)
    assert got == {"train.data": approx(2 * MS),
                   "train.dispatch": approx(2 * MS)}
    # without it the run seems to end at 8.5: a ms of the iteration's own
    # time and half a ms before it would be charged too
    wrong = timeline.charge_device(dev, host, -lag, 24 * MS - lag, lag=0.0)
    assert set(wrong) == {"train.data", "train.dispatch", "train.iteration",
                          timeline.NONE}


def test_gap_before_a_program_already_enqueued_is_the_runtimes():
    dev = device([step(1, 0.0), step(2, 14 * MS)])
    host = timeline.Host(spans=loop_spans(10 * MS),
                         enqueues={(2, 0): 5 * MS})     # while run 1 ran
    assert timeline.charge_device(dev, host, 0.0, 24 * MS, 0.0) == {
        timeline.RUNTIME: approx(4 * MS)}


def test_gap_inside_a_running_program_is_the_runtimes():
    dev = device([("jit_step", 1, [("fusion.1", 0.0, 4 * MS),
                                   ("fusion.2", 5 * MS, 9 * MS)])])
    host = timeline.Host(spans=loop_spans(0.0), enqueues={(1, 0): -1 * MS})
    assert timeline.charge_device(dev, host, 0.0, 9 * MS, 0.0) == {
        timeline.RUNTIME: approx(1 * MS)}


def test_no_span_and_no_enqueue_are_charged_to_none():
    dev = device([step(1, 0.0), step(2, 14 * MS), step(3, 30 * MS)])
    host = timeline.Host(spans=[], enqueues={(2, 0): 13 * MS})
    got = timeline.charge_device(dev, host, 0.0, 40 * MS, 0.0)
    assert got == {timeline.NONE: approx(4 * MS + 6 * MS)}


def test_enqueue_is_found_by_device_ordinal_then_by_run_id():
    host = timeline.Host(enqueues={(7, 0): 1.0, (7, 1): 2.0, (8, None): 3.0})
    assert host.enqueue_of(7, 1) == 2.0
    assert host.enqueue_of(8, 3) == 3.0
    assert host.enqueue_of(9, 0) is None
    assert timeline.ordinal(rx.DevicePlane("/device:TPU:3")) == 3


def test_split_by_span_takes_the_innermost():
    spans = [("outer", 0.0, 10.0, {}), ("inner", 2.0, 4.0, {}),
             ("next", 12.0, 13.0, {})]
    assert timeline.split_by_span(spans, 1.0, 12.5) == {
        "outer": approx(1.0 + 6.0), "inner": approx(2.0),
        timeline.NONE: approx(2.0), "next": approx(0.5)}
    assert timeline.split_by_span(spans, 5.0, 5.0) == {}


# ----------------------------------------------------------- the readers

def train_ctx(devs, host, lo, hi, step_seconds):
    trace = rx.Trace(devs, [])
    busy, window = rx.busy_and_window(trace, lo, hi)
    return {"trace": trace, "lo": lo, "hi": hi, "busy_s": busy,
            "window_s": window, "step_seconds": step_seconds,
            "cell": {"name": "hand-built"}, "_timeline_host": host}


def two_epochs():
    """Three steps of 10 ms on two chips. Between 1 and 2 the host was
    late (data, dispatch); between 2 and 3 an epoch ended and the host was
    late again, and the cache's gather program (0.5 ms, enqueued inside
    ``train.data``) ran inside that gap on chip 0; chip 1's step 2 also
    has a 1 ms hole between two operations."""
    d0 = device([step(1, 0.0), step(2, 14 * MS),
                 ("jit_gather", 5, [("fusion.1", 26 * MS, 26.5 * MS)]),
                 step(3, 29 * MS)])
    d1 = device([step(1, 0.0),
                 ("jit_step", 2, [("fusion.1", 14 * MS, 18 * MS),
                                  ("fusion.2", 19 * MS, 24 * MS)]),
                 step(3, 29 * MS)], name="/device:TPU:1")
    spans = loop_spans(10 * MS) + [
        ("train.epoch_end", 24.5 * MS, 25.5 * MS, {"epoch": 1}),
        ("train.iteration", 25.5 * MS, 40 * MS, {"step_num": 3}),
        ("train.data", 25.6 * MS, 26.6 * MS, {"neval": 3}),
        ("train.dispatch", 26.6 * MS, 28.6 * MS, {"neval": 3})]
    enq = {}
    for n in (0, 1):
        enq.update({(1, n): -1 * MS, (2, n): 13 * MS, (3, n): 28 * MS})
    enq[(5, 0)] = 25.8 * MS
    return [d0, d1], timeline.Host(spans=spans, enqueues=enq)


def test_idle_entries_add_up_to_the_idle_share_times_the_iteration():
    devs, host = two_epochs()
    ctx = train_ctx(devs, host, 0.0, 39 * MS, step_seconds=13 * MS)
    data = idle_in_data_ms.read(ctx)
    loop = idle_in_loop_ms.read(ctx)
    runtime = idle_runtime_ms.read(ctx)
    none_share = idle_unattributed_share.read(ctx)
    idle_ms = device_idle_share.read(ctx) / 100 * 13.0
    none_ms = none_share / 100 * idle_ms
    assert data + loop + runtime + none_ms == pytest.approx(idle_ms,
                                                           rel=1e-9)
    # chip 0 idles 4 + 2 + 2.5 ms, chip 1 idles 4 + 1 + 5: 9.25 a chip over
    # three iterations
    assert idle_ms == pytest.approx(9.25 / 3, rel=1e-9)
    assert runtime == pytest.approx(0.5 / 3, rel=1e-9)  # chip 1's hole / 2
    assert none_share == pytest.approx(0.0, abs=1e-9)
    assert data > 0 and loop > data


def test_epoch_boundary_is_the_idle_between_the_runs_around_the_span():
    devs, host = two_epochs()
    ctx = train_ctx(devs, host, 0.0, 39 * MS, step_seconds=13 * MS)
    # runs 2 and 3 are 5 ms apart; on chip 0 the gather fills 0.5 of them
    assert epoch_boundary_ms.read(ctx) == pytest.approx((4.5 + 5.0) / 2)
    host.spans = [s for s in host.spans if s[0] != "train.epoch_end"]
    assert epoch_boundary_ms.read(ctx) is None


def test_data_device_ms_counts_every_program_beside_the_step():
    devs, host = two_epochs()
    ctx = train_ctx(devs, host, 0.0, 39 * MS, step_seconds=13 * MS)
    # the gather program alone (0.5 ms on one chip of two, 3 iterations),
    # wherever it was enqueued: on a mesh the runtime enqueues late
    assert data_device_ms.read(ctx) == pytest.approx(0.5 / 2 / 3)
    host.enqueues[(5, 0)] = 24.8 * MS           # inside train.epoch_end
    assert data_device_ms.read(ctx) == pytest.approx(0.5 / 2 / 3)


def test_a_program_without_mirrored_spans_reports_nothing(tmp_path,
                                                          monkeypatch):
    devs, _ = two_epochs()
    ctx = train_ctx(devs, None, 0.0, 39 * MS, step_seconds=13 * MS)
    for reader in (idle_in_data_ms, idle_in_loop_ms, idle_runtime_ms,
                   idle_unattributed_share, epoch_boundary_ms):
        assert reader.read(ctx) is None
    assert data_device_ms.read(ctx) > 0     # stands on the device plane
    # and host_of finds nothing where no trace was written
    from benchmark import harness
    monkeypatch.setattr(harness, "SCRATCH", str(tmp_path))
    del ctx["_timeline_host"]
    assert timeline.host_of(ctx) is None
    assert timeline.host_of({"trace": None, "lo": None}) is None


# ------------------------------------------------ a trace from the chip

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "epoch_boundary_v5e.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    trace, host = rx.load(RECORDED), timeline.load_host(RECORDED)
    lo, hi = rx.slice_bounds(trace)
    return trace, host, lo, hi, rx.device_clock_lag(trace)


def test_recorded_trace_holds_the_loops_spans_beside_the_enqueues(recorded):
    trace, host, lo, hi, lag = recorded
    count = {n: len(host.named(n)) for n in (
        "train.iteration", "train.data", "train.dispatch", "train.sync",
        "train.log", "train.hooks", "train.epoch_end")}
    assert count == {"train.iteration": 6, "train.data": 6,
                     "train.dispatch": 5, "train.sync": 4, "train.log": 5,
                     "train.hooks": 6, "train.epoch_end": 1}
    steps = [s[3] for s in host.named("train.iteration")]
    assert all(s["_r"] == 1 and "step_num" in s for s in steps)
    assert sum(s["k"] == 0 for s in steps) == 1     # the exhausted pass
    assert {k[1] for k in host.enqueues} == {0}     # device_ordinal
    runs = rx.program_runs(trace.devices[0], lo, hi)
    # a step is enqueued while its predecessor runs: the first one's
    # enqueue fell before the cut
    assert len(runs) == 4 and host.enqueue_of(runs[0][3], 0) is None
    for a, b in zip(runs, runs[1:]):
        assert a[1] + lag < host.enqueue_of(b[3], 0) < a[2] + lag \
            or b is runs[2]             # the epoch's first: nothing ran
    assert 1.0e-3 < lag < 1.5e-3        # the device's clock ran behind


def test_recorded_trace_charges_its_idle_time_by_cause(recorded):
    trace, host, lo, hi, lag = recorded
    dev = trace.devices[0]
    charge = timeline.charge_device(dev, host, lo, hi, lag)
    busy, window = rx.busy_and_window(trace, lo, hi)
    idle = window - busy
    assert sum(charge.values()) == pytest.approx(idle, rel=1e-9)
    assert idle == pytest.approx(10.08e-3, rel=1e-3)
    # the boundary's exposed host dispatch is most of it; the epoch's end
    # itself, the fetch, the log and the wait for the drain share the rest
    assert charge["train.dispatch"] == pytest.approx(7.01e-3, rel=1e-2)
    assert charge["train.epoch_end"] < 1e-3 and charge["train.data"] < 1e-3
    assert charge[timeline.RUNTIME] < 0.3e-3
    assert charge[timeline.NONE] < 0.01 * idle
    (boundary,) = timeline.epoch_boundaries(trace, host, lo, hi, lag)
    assert boundary == pytest.approx(9.87e-3, rel=1e-2)


def test_recorded_trace_steady_gap_is_the_caches_gather_not_idle(recorded):
    trace, host, lo, hi, lag = recorded
    dev = trace.devices[0]
    runs = rx.program_runs(dev, lo, hi)
    a, b = runs[0], runs[1]             # two steps of one epoch
    assert b[1] - a[2] == pytest.approx(2.70e-3, rel=1e-2)
    idle = (b[1] - a[2]) - rx.total(rx.busy_intervals(dev, a[2], b[1]))
    assert idle < 0.1e-3
    # four gathers of 2.42 ms, each with its label program (0.23 ms) and a
    # dozen index programs, lie wholly in the cut
    assert timeline.other_program_seconds(trace, lo, hi) == pytest.approx(
        4 * 2.72e-3, rel=1e-2)
    # and the gather is enqueued lazily: inside train.dispatch, not
    # train.data
    gather = next(m for m in dev.modules if m[0].startswith("jit_gather")
                  and m[1] > a[2])
    at = host.enqueue_of(gather[3], 0)
    assert any(s[1] <= at < s[2] for s in host.named("train.dispatch"))
    assert not any(s[1] <= at < s[2] for s in host.named("train.data"))


# ------------------------------------------------------------- HLO scopes

HLO = """\
HloModule jit_step

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %dot.7 = f32[8]{0} fusion(%p), kind=kOutput, calls=%fused.1, metadata={op_name="jit(step)/jit(main)/jvp(lm_head_ce)/while/body/dot_general" source_file="/repo/ops/lm_head_ce.py" source_line=52}
}

ENTRY %main () -> f32[] {
  %fusion.3 = f32[8]{0} fusion(), kind=kLoop, calls=%fused.0, metadata={op_name="jit(step)/jit(main)/Linear/add" source_file="/repo/ops/lm_head_ce.py" source_line=9}
  %while.1 = (s32[], f32[8]{0}) while(%t), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/jit(main)/jvp(lm_head_ce)/while" source_file="/repo/ops/lm_head_ce.py" source_line=83}
  %pad.2 = f32[16]{0} pad(%w, %c), padding=0_8, metadata={op_name="jit(step)/jit(main)/transpose(jvp(lm_head_ce))/pad" source_file="/repo/ops/lm_head_ce.py" source_line=43}
  ROOT %while.2 = (s32[], f32[8]{0}) while(%u), condition=%cond.2, body=%body.2, metadata={op_name="jit(step)/jit(main)/transpose(jvp(lm_head_ce))/while" source_file="/repo/ops/lm_head_ce.py" source_line=115}
  %fusion.9 = f32[] fusion(), kind=kLoop, calls=%fused.2, metadata={op_name="jit(step)/jit(main)/my_lm_head_ce_like/mul"}
}
"""


def test_scope_instructions_reads_op_name_not_source_file():
    assert timeline.scope_instructions(HLO, "lm_head_ce") == {
        "dot.7", "while.1", "pad.2", "while.2"}
    assert timeline.scope_instructions(HLO, "no_such_scope") == set()


def scope_ctx():
    ops = [("fusion.3", 0.0, 2 * MS),
           ("while.1", 2 * MS, 5 * MS), ("dot.7", 2.1 * MS, 4.9 * MS),
           ("pad.2", 5 * MS, 5.5 * MS),
           ("while.2", 6 * MS, 9 * MS), ("dot.7", 6.5 * MS, 8.5 * MS),
           ("fusion.9", 9 * MS, 10 * MS)]
    shift = [(n, a + 12 * MS, b + 12 * MS) for n, a, b in ops]
    dev = device([("jit_step", 1, ops),
                  # another program reuses an instruction name
                  ("jit_gather", 2, [("pad.2", 10.5 * MS, 11.5 * MS)]),
                  ("jit_step", 3, shift)])
    ctx = train_ctx([dev], None, 0.0, 22 * MS, step_seconds=12 * MS)
    ctx.update(hlo=HLO, peaks={"bf16_flops_per_s": 197e12,
                               "hbm_bytes_per_s": 819e9},
               cell={"name": "hand-built", "batch_size": 2,
                     "seq_len": 2048},
               config={"hidden_size": 896, "vocab_size": 151936})
    return ctx


def test_scope_time_is_a_union_inside_the_step_program():
    ctx = scope_ctx()
    seconds, runs = timeline.scope_of(ctx, "lm_head_ce")
    # 3 + 0.5 + 3 ms a step: each while once, its body not again, and not
    # the other program's pad.2
    assert (seconds, runs) == (approx(2 * 6.5 * MS), 2)
    busy = ctx["busy_s"]
    assert busy == approx(2 * 9.5 * MS + 1 * MS)
    assert lm_head_ce_share.read(ctx) == pytest.approx(100 * 13 / 20)


def test_lm_head_ce_roofline_from_shapes():
    flops, bytes_ = lm_head_ce_roofline.cost(4096, 896, 151936)
    assert flops == 6 * 4096 * 896 * 151936 == 3345645305856
    # h twice, dh: 3 x 7.3 MB; W twice bf16 + dW fp32: 8 bytes x 136.1M
    assert bytes_ == 3 * 4096 * 896 * 2 + 8 * 151936 * 896 + 4096 * 4
    assert flops / 197e12 == pytest.approx(16.98e-3, rel=1e-3)  # compute-
    assert bytes_ / 819e9 < 1.4e-3                              # bound
    ctx = scope_ctx()
    assert lm_head_ce_roofline.read(ctx) == pytest.approx(
        100 * 16.9826 / 6.5, rel=1e-4)      # a hand-built 6.5 ms a step
    ctx["hlo"] = HLO.replace("lm_head_ce)", "other)")
    assert lm_head_ce_roofline.read(ctx) is None    # no scope: the parent
    assert lm_head_ce_share.read(ctx) is None


# ---------------------------------------------------------- serve readers

def lane(rid, submit, first, blocks):
    """A request's events as the harness hands them over (seconds)."""
    return [{"name": "serving.request", "ph": "b", "id": rid, "t0": submit,
             "dur": 0.0, "args": {}},
            {"name": "serving.request", "ph": "n", "id": rid, "t0": first - 0.01,
             "dur": 0.0, "args": {"phase": "admitted"}},
            {"name": "serving.request", "ph": "n", "id": rid, "t0": first,
             "dur": 0.0, "args": {"phase": "first_token"}}]


def test_ttft_and_itl_from_the_request_lane():
    spans = []
    for rid in range(1, 21):        # first tokens after 10..200 ms
        spans += lane(rid, 1.0, 1.0 + 0.010 * rid, None)
    # one block of 4 tokens for requests 1 and 2 ending at 2.0, a second
    # of 4 and 1 ending at 2.2
    spans += [{"name": "serving.decode_block", "ph": "X", "id": None,
               "t0": 1.9, "dur": 0.1,
               "args": {"live": 2, "rids": [1, 2], "tokens": [4, 4]}},
              {"name": "serving.decode_block", "ph": "X", "id": None,
               "t0": 2.1, "dur": 0.1,
               "args": {"live": 2, "rids": [1, 2], "tokens": [4, 1]}}]
    ctx = {"spans": spans}
    assert ttft_p95_ms.read(ctx) == pytest.approx(190.5)
    times = itl_p95_ms.token_times(ctx)
    assert times[1] == [1.01] + [2.0] * 4 + [2.2] * 4
    assert times[2] == [1.02] + [2.0] * 4 + [2.2]
    assert times[3] == [1.03]
    # 13 gaps: 2 first-to-block (990, 980 ms), 2 block-to-block (200), 9 zeros
    assert itl_p95_ms.read(ctx) == pytest.approx(984.0)
    assert ttft_p95_ms.read({"spans": []}) is None
    assert itl_p95_ms.read({"spans": []}) is None
