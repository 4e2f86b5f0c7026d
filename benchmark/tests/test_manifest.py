"""``BENCHMARK.json`` against the contract's rules of form, and against the
files the harness finds by name."""

import json
import os
import re

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
M = harness.load_manifest()


def line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(M)) < 64 * 1024
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert 1 <= len(M["command"]) <= 32 and all(map(line, M["command"]))
    assert 1 <= len(M["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in M["paths"])
    # a full check with the full 24 cells fits the driver's 43200 s
    assert (2 + 14 * 24) * (M["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_keys_follow_the_character_rules():
    names = []
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert PATH.match(c["file"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and line(w["why"])
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and line(m["layer"])
    metrics = M["end_to_end"] + M["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in (names, [w["name"] for w in M["workloads"]],
                  [m["name"] for m in metrics]):
        assert len(group) == len(set(group))
    assert 1 <= len(M["end_to_end"]) <= 16 and 1 <= len(M["per_layer"]) <= 128
    assert 2 <= len(M["workloads"]) <= 24 and 1 <= len(M["configs"]) <= 24


def test_cells_configs_and_metrics_hang_together():
    cells = {w["name"]: w for w in M["workloads"]}
    configs = {c["name"] for c in M["configs"]}
    assert {w["config"] for w in cells.values()} == configs
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in cells.values())
    assert four <= max(1, len(cells) // 4)
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for name in cells:
        mine = {m["name"] for m in harness.cell_e2e_entries(M, name)}
        assert len(mine - {"setup_s"}) >= 1
        layer = [m for m in M["per_layer"]
                 if name in m.get("workloads", [name])]
        assert layer and all(m["moves"] in mine for m in layer), name
    for m in M["end_to_end"] + M["per_layer"]:
        assert set(m.get("workloads", [])) <= set(cells)
    for m in M["per_layer"]:
        assert m["moves"] in e2e
    by_layer = {}
    for m in M["per_layer"]:        # one reader, one layer, letter for letter
        by_layer.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values())


def test_every_name_finds_its_files():
    for c in M["configs"]:
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        cfg = harness.read_json(harness.ROOT, c["file"])
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert os.path.exists(os.path.join(
            harness.HERE, "builders", cfg["family"] + ".py"))
    for w in M["workloads"]:
        cell, _ = harness.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        assert os.path.exists(os.path.join(
            harness.HERE, "kinds", cell["kind"] + ".py"))
    for m in M["per_layer"]:
        reader = m["name"].split(".")[0]
        path = os.path.join(harness.HERE, "layer_metrics", reader + ".py")
        assert os.path.exists(path), reader
        src = open(path, encoding="utf-8").read()
        assert f'LAYER, UNIT = "{m["layer"]}", "{m["unit"]}"' in src, reader
    for root, _, files in os.walk(harness.HERE):
        for f in files:
            if "__pycache__" not in root:
                assert PATH.match(os.path.join(root, f)[len(harness.ROOT) + 1:])


def test_files_no_cell_lists_yet_are_still_whole():
    """The serve cells wait for their knee sweep and two sets of runs
    (PERF.md section 7): their files are in the tree, not in the manifest,
    and have to load all the same."""
    import importlib
    for f in sorted(os.listdir(os.path.join(harness.HERE, "workloads"))):
        cell, cfg = harness.load_cell(f[:-len(".json")])
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        assert line(cell["why"]) and cell["chips"] in (1, 4)
        assert hasattr(harness.load_kind(cell["kind"]), "run")
        assert hasattr(harness.load_builder(cfg["family"]), "build")
        harness.load_cell(cell["name"], rehearse=True)
    for f in sorted(os.listdir(os.path.join(harness.HERE, "layer_metrics"))):
        if f.endswith(".py") and f != "__init__.py":
            mod = importlib.import_module(f"benchmark.layer_metrics.{f[:-3]}")
            assert line(mod.LAYER) and UNIT.match(mod.UNIT) and mod.read
