"""The readers of the step's partition (PR 36): ``step_partition.py`` hands
the reduced trace to the program's ``telemetry.step_partition.partition``
and the ten ``*_share`` readers take their cells of the table. Hand-built
traces; what ``partition`` itself counts is the program's test
(``tests/test_step_partition.py``)."""

import pytest

from benchmark import harness, step_partition
from benchmark.layer_metrics import (attn_proj_share, batchnorm_share,
                                     conv_share, mamba_local_share,
                                     mamba_proj_share, mlp_share, norm_share,
                                     optimizer_share, step_recompute_share,
                                     step_unattributed_share)
from benchmark.tests.test_timeline import MS, device, train_ctx

_F = "jit(step)/jvp(Sequential)/HybridDecoder/HybridBlock/"
_B = ("jit(step)/transpose(jvp(Sequential))/HybridDecoder/checkpoint/"
      "HybridBlock/")
_R = ("jit(step)/transpose(jvp(Sequential))/HybridDecoder/checkpoint/"
      "rematted_computation/HybridBlock/")


def _line(name, op_name, code="fusion"):
    return (f'  %{name} = f32[] {code}(), '
            f'metadata={{op_name="{op_name}"}}\n')


HLO = "HloModule jit_step\n\nENTRY %main () -> f32[] {\n" + "".join([
    _line("fusion.1", _F + "MultiHeadAttention/attn_proj/dot_general"),
    _line("fusion.2", _F + "GatedMLP/mlp/Linear/dot_general"),
    _line("fusion.3", _F + "Mamba2/mamba_proj/dot_general"),
    _line("fusion.4", _R + "Mamba2/mamba_local/mul"),
    _line("fusion.5", _B + "RMSNorm/mul"),
    _line("fusion.6", _F + "Sequential/SpatialConvolution/conv"),
    _line("fusion.7", _B + "Sequential/SpatialBatchNormalization/mul"),
    _line("fusion.8", "jit(step)/optim_update/div"),
    _line("fusion.9", "jit(step)/transpose(jvp(param_cast))/convert"),
    _line("fusion.10", _F + "add"),
    _line("while.1", _F + "MoE/moe_experts/while", code="while"),
]) + "}\n"

MS_OF = {"fusion.1": 4, "fusion.2": 3, "fusion.3": 2, "fusion.4": 5,
         "fusion.5": 1, "fusion.6": 6, "fusion.7": 2, "fusion.8": 3,
         "fusion.9": 1, "fusion.10": 3}          # 30 ms a step


def _ops(t0):
    ops, t = [("while.1", t0, t0 + 30 * MS)], t0
    for name, ms in MS_OF.items():
        ops.append((name, t, t + ms * MS))
        t += ms * MS
    return sorted(ops, key=lambda o: o[1])


def _ctx(hlo=HLO):
    devs = [device([("jit_step", 1, _ops(0.0)),
                    ("jit_gather", 2, [("fusion.1", 31 * MS, 32 * MS)]),
                    ("jit_step", 3, _ops(33 * MS))], f"/device:TPU:{i}")
            for i in range(2)]
    ctx = train_ctx(devs, None, 0.0, 63 * MS, step_seconds=33 * MS)
    ctx["hlo"] = hlo
    return ctx


def test_every_operation_of_a_whole_run_lands_in_one_cell():
    rows = step_partition.rows(_ctx())
    assert sum(rows.values()) == pytest.approx(30 * MS)     # not the loop
    assert rows[("attn_proj", "forward")] == pytest.approx(4 * MS)
    assert rows[("mamba_local", "recompute")] == pytest.approx(5 * MS)
    assert rows[("param_cast", "update")] == pytest.approx(1 * MS)
    assert rows[("unattributed", "forward")] == pytest.approx(3 * MS)


READERS = [(attn_proj_share, 4), (mlp_share, 3), (mamba_proj_share, 2),
           (mamba_local_share, 5), (norm_share, 1), (conv_share, 6),
           (batchnorm_share, 2), (optimizer_share, 4),
           (step_recompute_share, 5), (step_unattributed_share, 3)]


@pytest.mark.parametrize("reader,ms", READERS,
                         ids=[r.__name__.split(".")[-1] for r, _ in READERS])
def test_a_share_is_its_cells_over_the_tables_total(reader, ms):
    assert reader.read(_ctx()) == pytest.approx(100.0 * ms / 30)
    assert (reader.LAYER, reader.UNIT) == ("model step", "%")


def test_the_table_is_computed_once_a_run():
    ctx = _ctx()
    first = step_partition.rows(ctx)
    ctx["hlo"] = ""
    assert step_partition.rows(ctx) is first


@pytest.mark.parametrize("reader,_", READERS,
                         ids=[r.__name__.split(".")[-1] for r, _ in READERS])
def test_a_program_without_the_vocabulary_reads_nothing(reader, _):
    """A commit before PR 36 (no stage scope in the step's text: also what
    a compile cache it filled serves) and a run with no device trace."""
    old = HLO.replace("optim_update", "Adam").replace("param_cast", "cast")
    assert reader.read(_ctx(old)) is None
    assert reader.read({"trace": None, "lo": None, "hlo": HLO}) is None
    ctx = _ctx()
    ctx["hlo"] = ""
    assert reader.read(ctx) is None


def test_the_manifest_lists_the_ten_for_the_cells_that_hold_the_layer():
    manifest = harness.load_manifest()
    cells = {w["name"].split("-")[0]: w["name"]
             for w in manifest["workloads"]}
    want = {"step_unattributed_share": "resnet50 qwen2.5 nemotron trinity "
            "joyai", "step_recompute_share": "nemotron trinity joyai",
            "optimizer_share": "resnet50 qwen2.5 nemotron trinity joyai",
            "attn_proj_share": "qwen2.5 nemotron trinity",
            "mlp_share": "qwen2.5 trinity joyai",
            "mamba_proj_share": "nemotron", "mamba_local_share": "nemotron",
            "norm_share": "qwen2.5 nemotron trinity joyai",
            "conv_share": "resnet50", "batchnorm_share": "resnet50"}
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name, short in want.items():
        m = entries[name]
        assert m["workloads"] == [cells[s] for s in short.split()]
        assert (m["source"], m["moves"], m["better"]) == (
            "device_trace", "train_records_per_s", "lower")
