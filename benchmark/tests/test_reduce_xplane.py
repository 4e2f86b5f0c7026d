"""The trace reduction, on a trace recorded on the v5e (PR 22: three
iterations of a 2048^2 bf16 matmul+tanh, a flash-attention forward and its
backward at (2,1024,14,64), each iteration ending in a host sync) and on
hand-built cases."""

import os

import pytest

from benchmark import reduce_xplane as rx

DATA = os.path.join(os.path.dirname(__file__), "data", "probe_v5e.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return rx.load(DATA)


def test_recorded_trace_planes_and_programs(trace):
    assert [d.name for d in trace.devices] == ["/device:TPU:0"]
    dev = trace.devices[0]
    assert len(dev.modules) == 9            # 3 programs x 3 iterations
    assert len(dev.ops) == 117
    assert trace.anchor_t0 is not None
    lo, hi = rx.slice_bounds(trace)
    runs = rx.program_runs(dev, lo, hi + 1e-6, "jit_fa_loss")  # a program ends a few ns after its last op
    assert len(runs) == 3
    assert all(0.50e-3 < r[2] - r[1] < 0.54e-3 for r in runs)   # 522 us


def test_recorded_trace_busy_union_and_gaps(trace):
    dev = trace.devices[0]
    lo, hi = rx.slice_bounds(trace)
    busy, window = rx.busy_and_window(trace, lo, hi)
    # nine programs of 103 + 200 + 522 us, three times: 2.47 ms busy in a
    # 27.6 ms slice whose two long gaps are the host's sleep between
    # iterations
    assert busy == pytest.approx(2.4756e-3, rel=2e-3)
    assert window == pytest.approx(27.58e-3, rel=2e-3)
    by_sum = sum(o.dur for o in dev.ops)
    assert busy <= by_sum + 1e-12           # a union never exceeds the sum
    gaps = rx.idle_gaps(dev, lo, hi)
    assert rx.total(gaps) == pytest.approx(window - busy, rel=1e-9)
    assert gaps[0][1] - gaps[0][0] > 10e-3 and gaps[1][1] - gaps[1][0] > 10e-3


def test_recorded_trace_mosaic_kernels_and_names(trace):
    dev = trace.devices[0]
    mosaic = [o for o in dev.ops if o.is_mosaic]
    assert len(mosaic) == 12                # (1 fwd) + (1 fwd + dQ + dKV), x3
    assert {o.opcode for o in mosaic} == {"custom-call"}
    fams = {rx.family(o) for o in mosaic}
    assert fams == {"mosaic:_lambda_", "mosaic:jvp__",
                    "mosaic:transpose_jvp___"}
    sums = rx.op_sums(dev, *rx.slice_bounds(trace), key=rx.family)
    assert sums["convolution_tanh_fusion"][1] == 3
    assert sums["convolution_tanh_fusion"][0] == pytest.approx(274.7e-6,
                                                               rel=1e-3)
    top = rx.top_ops(trace, *rx.slice_bounds(trace), top=3)
    assert top[0][0].startswith("mosaic:")


def test_recorded_trace_clock_lag(trace):
    # the device's clock ran 1.4-1.8 ms behind the host's in this trace
    assert 1.3e-3 < rx.device_clock_lag(trace) < 1.9e-3
    to_perf = rx.to_perf_counter(trace, 100.0)
    assert to_perf(trace.anchor_t0) == pytest.approx(
        100.0 + rx.device_clock_lag(trace))


def test_union_clip_subtract_by_hand():
    iv = rx.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert iv == [(0, 3), (5, 7)]
    assert rx.total(iv) == 5
    assert rx.clip(iv, 2, 6) == [(2, 3), (5, 6)]
    assert rx.subtract([(0, 10)], iv) == [(3, 5), (7, 10)]
    assert rx.subtract([(0, 4), (6, 8)], [(1, 2), (3, 7)]) == [
        (0, 1), (2, 3), (7, 8)]


def _op(name, text, t0, t1):
    return rx.Op(name, text, t0, t1)


def test_exposed_collective_by_hand():
    # 0..4 compute; an async all-reduce in flight 3..8 (start at 3, done
    # waits 6..8); compute again 5..6 and 8..10. In flight 3..8 = 5 s; of
    # those, compute covers 3..4 and 5..6, so 3 s are exposed.
    dev = rx.DevicePlane("/device:TPU:0")
    ar = "f32[8]{0} all-reduce-start(f32[8]{0} %x), replica_groups={{0,1}}"
    dev.ops = [
        _op("fusion.1", "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)", 0, 4),
        _op("all-reduce-start.1", "%all-reduce-start.1 = " + ar, 3, 3.001),
        _op("fusion.2", "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %b)", 5, 6),
        _op("all-reduce-done.1", "%all-reduce-done.1 = f32[8]{0} "
            "all-reduce-done(f32[8]{0} %all-reduce-start.1)", 6, 8),
        _op("fusion.3", "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %c)", 8, 10),
    ]
    dev.async_ops = [_op("all-reduce-start.1",
                         "%all-reduce-start.1 = " + ar, 3, 8)]
    exposed, in_flight = rx.exposed_collective_seconds(dev, 0, 10)
    assert in_flight == pytest.approx(5.0)
    assert exposed == pytest.approx(3.0)
    # a synchronous all-reduce with nothing beside it is all exposed
    dev2 = rx.DevicePlane("/device:TPU:1")
    dev2.ops = [_op("fusion.1", "%fusion.1 = f32[8]{0} fusion(%a)", 0, 1),
                _op("all-reduce.7", "%all-reduce.7 = f32[8]{0} "
                    "all-reduce(f32[8]{0} %g), replica_groups=[1,4]<=[4]",
                    1, 1.5)]
    assert rx.exposed_collective_seconds(dev2, 0, 2) == (
        pytest.approx(0.5), pytest.approx(0.5))


def test_gap_attribution_by_hand():
    spans = [{"name": "train.sync", "ph": "X", "t0": 10.0, "dur": 2.0},
             {"name": "train.dispatch", "ph": "X", "t0": 13.0, "dur": 1.0}]
    gaps = [(0.5, 1.5), (3.2, 3.6), (6.0, 6.1)]
    out = rx.attribute_gaps(gaps, spans, lambda t: t + 10.0,
                            ("train.sync", "train.dispatch"))
    assert out == [["train.sync", pytest.approx(1.0)],
                   ["train.dispatch", pytest.approx(0.4)],
                   ["none", pytest.approx(0.1)]]
