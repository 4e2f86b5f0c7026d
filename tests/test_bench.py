"""Unit tests for bench.py's orchestration logic (the driver-facing
contract: ALWAYS emit one parseable JSON line, respect the global wall
budget, and never print a device metric from anything but a TPU). The worker
side runs on real hardware; here the attempt layer is stubbed.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

_SPEC = importlib.util.spec_from_file_location(
    "bench", os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench.py"))
bench = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(bench)


def run_main(monkeypatch, capsys, argv, attempts_log, results=None,
             env=None, started_ago=0):
    """Drive bench.main() with _attempt stubbed; returns the parsed final
    JSON line."""
    results = results or {}

    def fake_attempt(name, worker, batch, steps, budget, precision="bf16",
                     grace=90, seq_len=None):
        attempts_log.append((name, worker, batch, budget))
        return results.get(name)

    monkeypatch.setattr(bench, "_attempt", fake_attempt)
    for k, v in (env or {}).items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(sys, "argv", ["bench.py"] + argv)
    monkeypatch.setattr(bench, "_T_START",
                        bench.time.monotonic() - started_ago)
    code = 0
    try:
        bench.main()
    except SystemExit as e:
        code = e.code or 0
    out = capsys.readouterr().out.strip().splitlines()
    assert out, "bench printed no JSON line"
    return json.loads(out[-1]), code


def test_first_success_wins(monkeypatch, capsys):
    log = []
    res = {"resnet50-b256": {"metric": "m", "value": 2526.0,
                             "unit": "u", "vs_baseline": 0.63}}
    parsed, code = run_main(monkeypatch, capsys, [], log, results=res)
    assert code == 0 and parsed["value"] == 2526.0
    # first success wins outright (the fused self-A/B was removed after the
    # round-3 on-chip answer: fused loses — see PERF.md)
    assert [a[0] for a in log] == ["resnet50-b256"]


def test_all_fail_emits_diagnostic_json(monkeypatch, capsys):
    log = []
    parsed, code = run_main(monkeypatch, capsys, [], log)
    assert parsed["metric"] == "bench_failed" and code == 1
    assert "vs_baseline" in parsed
    # every configured attempt was tried before giving up
    assert len(log) >= 3


def test_batch_override_dedupes_attempts(monkeypatch, capsys):
    log = []
    run_main(monkeypatch, capsys, ["--batch", "64"], log)
    keys = [(a[1], a[2]) for a in log]
    assert len(keys) == len(set(keys)), f"duplicate attempts: {keys}"
    assert all(a[2] == 64 for a in log)


def test_unparseable_total_budget_ignored(monkeypatch, capsys):
    log = []
    parsed, code = run_main(monkeypatch, capsys, [], log,
                            env={"BENCH_TOTAL_BUDGET": "20m"})
    assert parsed["metric"] == "bench_failed" and len(log) >= 3


def test_exhausted_budget_skips_every_attempt(monkeypatch, capsys):
    # pretend the run started ~18 min ago: no attempt can compile in what
    # is left, so none is started and the run fails with a parseable line
    log = []
    parsed, code = run_main(monkeypatch, capsys, [], log, started_ago=1100)
    assert log == [] and parsed["metric"] == "bench_failed" and code == 1


def test_non_tpu_worker_is_an_error():
    """The real worker under JAX_PLATFORMS=cpu: it must fail before it
    compiles anything and print no metric line — a number from anything
    but a TPU is not a device metric."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, bench.__file__, "--worker", "lenet",
         "--steps", "1", "--budget", "60"],
        capture_output=True, timeout=120, env=env)
    assert r.returncode != 0
    assert r.stdout.strip() == b""
    assert b"default backend is 'cpu'" in r.stderr


def _load_script(name):
    """Import a scripts/ module the way the CLI runs it (scripts/ on
    sys.path so roofline_pallas resolves)."""
    scripts = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(scripts, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_int8_decode_bench_cost_only_emits_cost_keys(monkeypatch, capsys,
                                                     tmp_path):
    """Round-10 CI gate: the --cost-only mode runs on the CPU tier and
    the BENCH JSON carries the flight-recorder cost-analysis keys for
    every weight variant, with zero int8 fallbacks."""
    mod = _load_script("int8_decode_bench")
    out_json = tmp_path / "int8_cost.json"
    monkeypatch.setattr(sys, "argv", [
        "int8_decode_bench.py", "--cost-only", "--config", "tiny",
        "--json", str(out_json)])
    mod.main()
    art = json.loads(out_json.read_text())
    assert art["kind"] == "bigdl_tpu_int8_decode_cost"
    rows = art["int8_decode_cost"]
    for variant in ("fp32", "bf16", "int8"):
        assert rows[variant]["program_flops"] > 0
        assert rows[variant]["program_bytes_accessed"] > 0
        assert rows[variant]["site"] == f"int8_decode.{variant}"
    assert rows["int8_fallbacks_delta"] == 0


def test_moe_ablate_emits_cost_rows_for_all_dispatches(monkeypatch,
                                                       capsys, tmp_path):
    """The moe_ablate mode must produce one cost row per dispatch
    formulation with cost-analysis keys and the structural HLO evidence
    (only the sort path carries HLO sorts)."""
    mod = _load_script("moe_ablate")
    out_json = tmp_path / "moe_ablate.json"
    monkeypatch.setattr(sys, "argv", [
        "moe_ablate.py", "--config", "tiny", "--cost-only",
        "--json", str(out_json)])
    mod.main()
    art = json.loads(out_json.read_text())
    assert art["kind"] == "bigdl_tpu_moe_ablate"
    rows = {r["dispatch"]: r for r in art["rows"]}
    assert set(rows) == {"sort", "scatter", "einsum"}
    for r in rows.values():
        assert r["program_flops"] > 0
        assert r["program_bytes_accessed"] > 0
        assert r["activated_flops_per_step"] > 0
    assert rows["sort"]["hlo_sorts"] > 0
    assert rows["scatter"]["hlo_sorts"] == 0
    assert rows["einsum"]["hlo_sorts"] == 0


def test_all_mode_one_line_per_workload(monkeypatch, capsys):
    # --all emits one JSON line per BASELINE workload, falling down each
    # model's ladder independently (here: every first rung fails)
    log = []
    results = {f"{m}-b{bench._LADDERS[m][-1][0]}":
               {"metric": f"{m}_x", "value": 1.0 + i, "unit": "u",
                "vs_baseline": 0.1}
               for i, m in enumerate(bench._MODELS)}

    def fake_attempt(name, worker, batch, steps, budget, precision="bf16",
                     grace=90, seq_len=None):
        log.append(name)
        return results.get(name)

    monkeypatch.setattr(bench, "_attempt", fake_attempt)
    monkeypatch.setattr(sys, "argv", ["bench.py", "--all"])
    monkeypatch.setattr(bench, "_T_START", bench.time.monotonic())
    code = 0
    try:
        bench.main()
    except SystemExit as e:
        code = e.code or 0
    lines = [json.loads(l) for l in
             capsys.readouterr().out.strip().splitlines()]
    assert code == 0
    assert len(lines) == len(bench._MODELS)
    assert {l["model"] for l in lines} == set(bench._MODELS)
    assert len(log) == sum(len(v) for v in bench._LADDERS.values())
