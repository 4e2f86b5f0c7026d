"""Profiling + cost attribution.

Legacy half (reference ``AbstractModule.scala:134-145`` ``getTimes``/
``resetTimes``): eager wall-time accounting via ``enable_timing`` and
always-on ``jax.named_scope`` HLO tags.

PR-14 half (``telemetry/profiling.py`` + ``telemetry/scoreboard.py``):
the tracked_jit compile flight recorder (one event per signature,
oldest-first eviction, cost fields present-or-None on CPU), the live MFU
gauge, per-request trace lifecycles sharing one id across phases, and
the serving scoreboard (golden markdown output, diff regression gate,
Prometheus scrape parsing)."""

import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.nn.module import enable_timing, functional_apply
from bigdl_tpu.telemetry import (MetricsRegistry, get_registry,
                                 instruments, tracing)
from bigdl_tpu.telemetry import profiling, scoreboard


def _model():
    m = nn.Sequential()
    m.add(nn.Linear(8, 32).set_name("fc1")).add(nn.ReLU())
    m.add(nn.Linear(32, 4).set_name("fc2")).add(nn.LogSoftMax())
    return m


def test_get_times_eager():
    m = _model()
    x = jnp.ones((16, 8))
    enable_timing(True)
    try:
        m.reset_times()
        m.forward(x)
        m.backward(x, jnp.ones((16, 4)))
        times = m.get_times()
    finally:
        enable_timing(False)
    by_name = {mod.name: (f, b) for mod, f, b in times}
    assert by_name["fc1"][0] > 0.0
    assert by_name["fc2"][0] > 0.0
    # container forward time includes its children
    seq_f = times[0][1]
    assert seq_f >= by_name["fc1"][0]
    # the container-level backward was timed
    assert times[0][2] > 0.0
    report = m.time_report()
    assert "fc1" in report and "fwd(s)" in report

    m.reset_times()
    assert all(f == 0.0 and b == 0.0 for _, f, b in m.get_times())


def test_timing_disabled_by_default():
    m = _model()
    m.forward(jnp.ones((2, 8)))
    assert all(f == 0.0 for _, f, _ in m.get_times())


def test_named_scope_tags_in_hlo():
    m = _model()
    params, buffers = m.parameter_tree(), m.buffer_tree()

    def fwd(p, b, x):
        out, _ = functional_apply(m, p, b, x)
        return out

    # Lowered.as_text() grew/lost a debug_info kwarg across jax releases;
    # printing the MLIR module with debug info is the stable way to see
    # the jax.named_scope location tags
    import io
    buf = io.StringIO()
    lowered = jax.jit(fwd).lower(params, buffers, jnp.ones((4, 8)))
    lowered.compiler_ir().operation.print(file=buf, enable_debug_info=True)
    hlo = buf.getvalue()
    assert "fc1" in hlo and "fc2" in hlo


def test_optimizer_profile_window(tmp_path):
    from bigdl_tpu.dataset.base import DataSet, Sample, SampleToBatch
    from bigdl_tpu.optim import Optimizer, SGD, Trigger

    rng = np.random.default_rng(0)
    samples = [Sample(rng.normal(size=(8,)).astype("float32"),
                      float(rng.integers(1, 5))) for _ in range(32)]
    ds = DataSet.array(samples) >> SampleToBatch(16)
    opt = Optimizer(_model(), ds, nn.ClassNLLCriterion())
    opt.set_optim_method(SGD(learningrate=0.01))
    opt.set_end_when(Trigger.max_iteration(4))
    opt.set_profiling(str(tmp_path / "trace"), start_iteration=2,
                      n_iterations=2)
    opt.optimize()
    dumped = []
    for root, _, files in os.walk(tmp_path / "trace"):
        dumped.extend(os.path.join(root, f) for f in files)
    assert dumped, "profiler trace produced no files"


# ===========================================================================
# PR 14: compile flight recorder (telemetry/profiling.py)
# ===========================================================================

class TestTrackedJit:
    def _tracked(self, cache_size=8):
        reg = MetricsRegistry()
        tj = profiling.tracked_jit(lambda x, y: x @ y, site="t.site",
                                   registry=reg, cache_size=cache_size)
        return tj, reg

    def test_fires_exactly_once_per_signature(self):
        tj, reg = self._tracked()
        a = jnp.ones((8, 8))
        out1 = tj(a, a)
        out2 = tj(a, a)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))
        assert tj.compiles == 1
        tj(jnp.ones((4, 8)), a)           # new shape -> one more program
        assert tj.compiles == 2
        tm = instruments(reg)
        assert tm.compiles_total.labels(site="t.site").value == 2
        assert tm.compile_seconds.labels(site="t.site").count == 2

    def test_fires_twice_after_eviction(self):
        tj, reg = self._tracked(cache_size=2)
        a = jnp.ones((8, 8))
        tj(a, a)                                   # sig A
        tj(jnp.ones((4, 8)), a)                    # sig B
        tj(jnp.ones((2, 8)), a)                    # sig C -> evicts A
        assert instruments(reg).compile_cache_evictions_total.labels(
            site="t.site").value == 1
        before = tj.compiles
        tj(a, a)                                   # re-seen A: recompiles
        assert tj.compiles == before + 1
        # ONE entry went, not the whole cache: B or C is still warm
        tj(jnp.ones((2, 8)), a)
        assert tj.compiles == before + 1

    def test_cost_fields_present_or_none(self):
        tj, _ = self._tracked()
        tj(jnp.ones((16, 16)), jnp.ones((16, 16)))
        ev = tj.last_event
        assert ev is not None and ev.seconds > 0
        for field in ("flops", "bytes_accessed", "temp_bytes",
                      "output_bytes"):
            v = getattr(ev, field)
            assert v is None or v >= 0
        assert "leaves" in ev.signature

    def test_donation_respected(self):
        reg = MetricsRegistry()
        tj = profiling.tracked_jit(lambda x: x + 1, site="t.donate",
                                   registry=reg, donate_argnums=(0,))
        x = jnp.zeros((32,))
        y = tj(x)
        assert float(y[0]) == 1.0
        assert x.is_deleted()

    def test_tracer_args_fall_back_to_plain_jit(self):
        """A tracked fn called INSIDE another trace (the eval scorer
        calls the tracked forward) must inline, not crash on the
        compiled-executable path."""
        reg = MetricsRegistry()
        inner = profiling.tracked_jit(lambda x: x * 2, site="t.inner",
                                      registry=reg)

        @jax.jit
        def outer(x):
            return inner(x) + 1

        assert float(outer(jnp.asarray(3.0))) == 7.0

    def test_compile_error_propagates_once(self):
        """No second attempt through plain jit: the error comes from the
        one lower().compile(), nothing is recorded or cached."""
        calls = []

        def bad(x):
            calls.append(1)
            raise ValueError("refused")

        tj = profiling.tracked_jit(bad, site="t.bad",
                                   registry=MetricsRegistry())
        with pytest.raises(ValueError, match="refused"):
            tj(jnp.ones((4,)))
        assert len(calls) == 1 and tj.compiles == 0 and not tj._programs

    def test_in_shardings_make_placement_irrelevant(self):
        """A mesh step sees fresh single-device state on its first call
        and its own mesh-committed outputs afterwards: with explicit
        in_shardings that is ONE program (a DistriOptimizer run compiled
        train.step three times before)."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
        rep = NamedSharding(mesh, P())
        bat = NamedSharding(mesh, P("data"))
        tj = profiling.tracked_jit(
            lambda w, x: (w + x.sum(), x * 2), site="t.mesh",
            registry=MetricsRegistry(), in_shardings=(rep, bat),
            out_shardings=(rep, bat))
        w, x = tj(jnp.zeros(()), jnp.ones((8,)))    # uncommitted inputs
        w, x = tj(w, x)                             # committed outputs
        w, x = tj(jnp.zeros(()), x)                 # mixed
        assert tj.compiles == 1
        assert len(x.sharding.device_set) == 4

    def test_pytree_and_scalar_args(self):
        tj, _ = self._tracked()
        reg = MetricsRegistry()
        tj2 = profiling.tracked_jit(
            lambda tree, s: tree["a"] * s, site="t.tree", registry=reg)
        out = tj2({"a": jnp.ones((4,))}, 2.0)
        np.testing.assert_allclose(np.asarray(out), 2.0)
        # same signature, different scalar VALUE: no new program
        out = tj2({"a": jnp.ones((4,))}, 5.0)
        np.testing.assert_allclose(np.asarray(out), 5.0)
        assert tj2.compiles == 1

    def test_lower_delegates(self):
        tj, _ = self._tracked()
        txt = tj.lower(jnp.ones((4, 4)), jnp.ones((4, 4))) \
            .compile().as_text()
        assert "dot" in txt or "fusion" in txt or len(txt) > 0


class TestMfuAndMemory:
    def test_mfu_helper(self, monkeypatch):
        assert profiling.peak_flops() is None      # a CPU has no roof
        assert profiling.mfu(1e9, 0.01) is None
        monkeypatch.setattr(profiling, "peak_flops", lambda: 1e12)
        assert profiling.mfu(1e9, 0.01) == pytest.approx(0.1)
        assert profiling.mfu(None, 0.01) is None
        assert profiling.mfu(1e9, 0.0) is None

    def test_training_loop_sets_mfu_gauge(self, monkeypatch):
        """The live MFU gauge: cost-analysis FLOPs of the dispatched step
        program over wall seconds over the (pinned) peak — sane means
        strictly positive and far below 1 for a toy model on CPU."""
        from bigdl_tpu.dataset.base import DataSet, Sample, SampleToBatch
        from bigdl_tpu.optim import Optimizer, SGD, Trigger
        monkeypatch.setattr(profiling, "peak_flops", lambda: 1e15)
        rng = np.random.default_rng(0)
        samples = [Sample(rng.normal(size=(8,)).astype("float32"),
                          float(rng.integers(1, 5))) for _ in range(32)]
        ds = DataSet.array(samples) >> SampleToBatch(16)
        opt = Optimizer(_model(), ds, nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learningrate=0.01))
        opt.set_end_when(Trigger.max_iteration(4))
        opt.optimize()
        tm = instruments(get_registry())
        mfu = tm.train_mfu.labels(mode="local").value
        assert 0.0 < mfu < 1.0, mfu
        # the step site recorded exactly one compile with its cost gauges
        assert tm.compiles_total.labels(site="train.step").value >= 1
        assert tm.program_flops.labels(site="train.step").value > 0

    def test_peak_table_and_tpu_gate(self):
        assert profiling.kind_peak_flops("TPU v5 lite") == 197e12
        assert profiling.kind_peak_flops("cpu") is None
        with pytest.raises(RuntimeError, match="'cpu'"):
            profiling.require_tpu()

    def test_sample_device_memory_never_raises(self):
        # CPU has no allocator stats: must be a silent None, never a crash
        assert profiling.sample_device_memory(MetricsRegistry()) is None


# ===========================================================================
# PR 14: per-request trace lifecycles (serving.request async events)
# ===========================================================================

VOCAB = 24


def _tiny_lm():
    from bigdl_tpu.models import transformer
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(11)
    return transformer.build_lm(VOCAB, 16, 2, 32, num_layers=1, max_len=32,
                                rope=True, norm="rms")


class TestRequestLifecycle:
    def test_continuous_request_spans_share_one_id(self):
        from bigdl_tpu.models.serving import ContinuousLMServer
        srv = ContinuousLMServer(_tiny_lm(), slots=2, max_len=32,
                                 greedy=True, decode_block=2,
                                 max_new_tokens=8,
                                 registry=MetricsRegistry())
        tracing.disable()
        tracing.clear()
        tracing.enable()
        try:
            out = srv.submit([3, 7, 2], max_new_tokens=4, timeout=120)
            assert len(out) == 4
            evs = tracing.events()
        finally:
            tracing.disable()
            tracing.clear()
            srv.close()
        lifecycle = [e for e in evs if e["name"] == "serving.request"]
        begins = [e for e in lifecycle if e["ph"] == "b"]
        ends = [e for e in lifecycle if e["ph"] == "e"]
        assert begins and ends
        rid = begins[-1]["id"]
        # the full chain lives under ONE id: begin, admitted instant, end
        assert {e["ph"] for e in lifecycle if e["id"] == rid} == \
            {"b", "n", "e"}
        assert any(e["args"].get("tokens") == 4
                   for e in ends if e["id"] == rid)
        # queue-wait attribution + phase spans carry the same rid
        qw = [e for e in evs if e["name"] == "serving.queue_wait"
              and e["args"].get("rid") == rid]
        assert qw and qw[0]["ph"] == "X" and qw[0]["dur"] >= 0
        prefill = [e for e in evs if e["name"] == "serving.prefill"
                   and e.get("args", {}).get("rid") == rid]
        insert = [e for e in evs if e["name"] == "serving.insert"
                  and e.get("args", {}).get("rid") == rid]
        assert prefill and insert
        # decode blocks name the rids they advanced (when any survived
        # past admission; a fully-admission-served request may see none)
        blocks = [e for e in evs if e["name"] == "serving.decode_block"]
        assert all("rids" in e.get("args", {}) for e in blocks)

    def test_lmserver_request_lifecycle(self):
        from bigdl_tpu.models.lm_server import LMServer
        from bigdl_tpu.models import transformer
        from bigdl_tpu.utils.rng import manual_seed
        manual_seed(5)
        lm = transformer.build_lm(VOCAB, 16, 2, 32, num_layers=1,
                                  max_len=32)
        srv = LMServer(lm, greedy=True, max_new_tokens=4,
                       registry=MetricsRegistry())
        tracing.disable()
        tracing.clear()
        tracing.enable()
        try:
            srv.submit([3, 5, 7], timeout=120)
            evs = tracing.events()
        finally:
            tracing.disable()
            tracing.clear()
            srv.close()
        life = [e for e in evs if e["name"] == "lmserver.request"]
        rid = life[0]["id"]
        phases = {e["ph"] for e in life if e["id"] == rid}
        assert {"b", "n", "e"} <= phases
        disp = [e for e in life if e["id"] == rid and e["ph"] == "n"]
        assert disp[0]["args"]["phase"] == "dispatch"
        assert disp[0]["args"]["wait_s"] >= 0


# ===========================================================================
# PR 14: serving scoreboard (telemetry/scoreboard.py)
# ===========================================================================

GOLDEN_ARTIFACT = {
    "schema": 1, "kind": "bigdl_tpu_serving_scoreboard",
    "backend": "tpu",
    "workload": {"requests": 48, "clients": 8, "seed": 0,
                 "zipf": {"lmin": 4, "lmax": 24, "alpha": 1.1},
                 "max_new": 16,
                 "model": {"vocab": 256, "embed": 32, "heads": 2,
                           "ffn": 64, "layers": 2}},
    "rows": [
        {"slots": 8, "prefill_mode": "chunked", "requests": 48,
         "failed": 0, "wall_s": 12.0,
         "tok_s": 64.0, "ttft_p50_s": 0.05, "ttft_p95_s": 0.25,
         "token_latency_s": 0.004, "compiles": 9, "compile_seconds": 4.2,
         "cache_evictions": 0, "peak_memory_bytes": 41943040,
         "errors": []},
        {"slots": 16, "requests": 48, "failed": 0, "wall_s": 8.0,
         "tok_s": 96.0, "ttft_p50_s": 0.1, "ttft_p95_s": 0.5,
         "token_latency_s": 0.005, "compiles": 9, "compile_seconds": 4.4,
         "cache_evictions": 0, "peak_memory_bytes": 52428800,
         "errors": []},
    ],
}

GOLDEN_MARKDOWN = """\
| slots | prefill | tok/s | TTFT p50 (ms) | TTFT p95 (ms) | per-token (ms) | compiles | compile s | evictions | peak mem (MiB) |
|------:|:--------|------:|--------------:|--------------:|---------------:|---------:|----------:|----------:|---------------:|
| 8 | chunked | 64.0 | 50.0 | 250.0 | 4.0 | 9 | 4.2 | 0 | 40.0 |
| 16 | — | 96.0 | 100.0 | 500.0 | 5.0 | 9 | 4.4 | 0 | 50.0 |

<small>backend=tpu, requests=48/slot-count, Zipf(1.1) prompt lengths [4, 24], seed=0</small>"""


class TestScoreboard:
    def test_zipf_workload_is_deterministic_and_mixed(self):
        a = scoreboard.zipf_lengths(64, seed=3, lmin=4, lmax=24)
        b = scoreboard.zipf_lengths(64, seed=3, lmin=4, lmax=24)
        assert a == b
        assert all(4 <= x <= 24 for x in a)
        assert len(set(a)) > 3          # mixed lengths, not one bucket
        cfg = scoreboard.ScoreboardConfig(seed=7, requests=10)
        assert scoreboard.make_prompts(cfg) == scoreboard.make_prompts(cfg)

    def test_golden_markdown(self):
        assert scoreboard.render_markdown(GOLDEN_ARTIFACT) == \
            GOLDEN_MARKDOWN

    def test_diff_clean_and_injected_regression(self):
        assert scoreboard.diff(GOLDEN_ARTIFACT, GOLDEN_ARTIFACT) == []
        bad = json.loads(json.dumps(GOLDEN_ARTIFACT))
        bad["rows"][0]["tok_s"] = 40.0              # -37% throughput
        bad["rows"][1]["compiles"] = 30             # compile storm
        msgs = scoreboard.diff(GOLDEN_ARTIFACT, bad)
        assert len(msgs) == 2
        assert any("tok/s" in m and "slots=8" in m for m in msgs)
        assert any("compiles" in m and "slots=16" in m for m in msgs)

    def test_diff_thresholds_configurable_and_missing_row(self):
        bad = json.loads(json.dumps(GOLDEN_ARTIFACT))
        bad["rows"][0]["tok_s"] = 40.0
        assert scoreboard.diff(GOLDEN_ARTIFACT, bad,
                               {"tok_s_drop": 0.5}) == []
        short = json.loads(json.dumps(GOLDEN_ARTIFACT))
        short["rows"] = short["rows"][:1]
        msgs = scoreboard.diff(GOLDEN_ARTIFACT, short)
        assert any("missing from new" in m for m in msgs)
        # missing metrics never fail the gate
        nulled = json.loads(json.dumps(GOLDEN_ARTIFACT))
        for r in nulled["rows"]:
            r["peak_memory_bytes"] = None
            r["ttft_p95_s"] = None
        assert scoreboard.diff(GOLDEN_ARTIFACT, nulled) == []

    def test_prometheus_parse_roundtrip(self):
        """The scrape mode's parser against OUR exposition renderer."""
        from bigdl_tpu.telemetry import render_prometheus
        reg = MetricsRegistry()
        tm = instruments(reg)
        tm.serving_slots_total.set(8)
        tm.serving_tokens_total.inc(640)
        tm.serving_requests_completed_total.inc(48)
        for v in (0.004, 0.01, 0.02, 0.3):
            tm.serving_ttft_seconds.observe(v)
        tm.compiles_total.labels(site="serving.prefill").inc(5)
        tm.compiles_total.labels(site="serving.step").inc(1)
        # LABELED histogram: sums/counts/buckets must ACCUMULATE across
        # label sets, not keep the last series parsed
        tm.compile_seconds.labels(site="serving.prefill").observe(10.0)
        tm.compile_seconds.labels(site="serving.prefill").observe(0.5)
        tm.compile_seconds.labels(site="serving.step").observe(2.0)
        values, hists = scoreboard._parse_prometheus(
            render_prometheus(reg))
        assert values["bigdl_serving_slots_total"] == 8
        assert values["bigdl_compiles_total"] == 6    # summed over sites
        snap = hists["bigdl_serving_ttft_seconds"]
        assert snap["count"] == 4
        assert scoreboard.quantile_from_snapshot(snap, 0.5) is not None
        comp = hists["bigdl_compile_seconds"]
        assert comp["sum"] == pytest.approx(12.5)
        assert comp["count"] == 3 == comp["inf"]
        assert scoreboard.quantile_from_snapshot(comp, 0.99) >= 10.0

    def test_live_run_tiny(self):
        """End-to-end run mode at toy scale: real server, real workload,
        real registry aggregation — every row field lands."""
        cfg = scoreboard.ScoreboardConfig(
            slots=[2], requests=4, clients=2, seed=0, lmin=3, lmax=6,
            max_new=3, vocab=VOCAB, embed=16, heads=2, ffn=32, layers=1,
            timeout=120)
        artifact = scoreboard.run(cfg)
        (row,) = artifact["rows"]
        assert row["slots"] == 2 and row["requests"] == 4
        assert row["failed"] == 0, row["errors"]
        assert row["tok_s"] > 0
        assert row["ttft_p50_s"] is not None
        assert row["token_latency_s"] > 0
        # the flight recorder saw the step + insert + the O(1) chunked
        # prefill pair — and NOTHING per-length (PR 15: the pre-fix
        # engine minted one program per distinct Zipf prompt length)
        assert 3 <= row["compiles"] <= 4
        assert row["prefill_mode"] == "chunked"
        assert row["compile_seconds"] > 0
        md = scoreboard.render_markdown(artifact)
        assert "| 2 |" in md
