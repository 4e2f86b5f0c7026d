"""The step's partition (``telemetry/step_partition.py``): what layer and
pass an ``op_name`` belongs to, that the five benchmark cells' step programs
classify with nothing of weight left ``unattributed``, what ``partition``
counts of a profile, and that ``Optimizer.set_profiling`` writes the table.
Small sizes on the CPU; the flash kernels in Pallas' interpreter."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from bigdl_tpu import nn
from bigdl_tpu.dataset.base import DataSet, Sample, SampleToBatch
from bigdl_tpu.ops import flash_attention as fa
from bigdl_tpu.optim import SGD, Optimizer, Trigger
from bigdl_tpu.telemetry import step_partition as sp

# ------------------------------------------------ (a) classify, as data

_BLOCK = ("jit(step)/transpose(jvp(Sequential))/HybridDecoder/"
          "jvp(Sequential)/HybridDecoder/checkpoint/")
CASES = [
    # forward / backward / recompute of one mixer, its scan nested in it
    ("jit(step)/jvp(Sequential)/HybridDecoder/HybridBlock/Mamba2/"
     "mamba_local/mul", "mamba_local", "forward"),
    (_BLOCK + "HybridBlock/Mamba2/mamba_local/jit(silu)/mul",
     "mamba_local", "backward"),
    (_BLOCK + "rematted_computation/HybridBlock/Mamba2/mamba_local/slice",
     "mamba_local", "recompute"),
    (_BLOCK + "rematted_computation/HybridBlock/Mamba2/mamba_proj/"
     "dot_general", "mamba_proj", "recompute"),
    (_BLOCK + "rematted_computation/HybridBlock/Mamba2/ssd_scan/"
     "bclgn,bcgrpn->bclgrp/dot_general", "ssd_scan", "recompute"),
    ("jit(step)/jvp(Sequential)/HybridDecoder/HybridBlock/Mamba2/ssd_scan/"
     "ssd_fwd_out/pallas_call", "ssd_scan", "forward"),
    # a module's class name stands for a layer where no scope is entered
    (_BLOCK + "rematted_computation/HybridBlock/MoE/reshape", "moe_experts",
     "recompute"),
    # ... and where it is none, there is nothing to hold
    ("jit(step)/jvp(Sequential)/HybridDecoder/HybridBlock/Mamba2/mul",
     "unattributed", "forward"),
    # custom_vjp backward rules: under the call's scope, or their own
    (_BLOCK + "HybridBlock/MultiHeadAttention/attn_core/flash_bwd_dkv/"
     "pallas_call", "attn_core", "backward"),
    (_BLOCK + "HybridBlock/MoE/moe_experts/moe_gmm_bwd/pallas_call",
     "moe_experts", "backward"),
    ("jit(step)/transpose(jvp(criterion))/lm_head_ce/mul", "lm_head_ce",
     "backward"),
    ("jit(step)/jvp(criterion)/lm_head_ce/while/body/closed_call/dot_general",
     "lm_head_ce", "forward"),
    ("jit(step)/jvp(criterion)/jit(_take)/gather", "criterion", "forward"),
    # a kernel by name where no scope was entered
    ("jit(f)/MultiHeadAttention/flash_band_fwd/pallas_call", "attn_core",
     "forward"),
    # an entered scope keeps the module classes inside it; a group does not
    ("jit(step)/jvp(_LMWithMTP)/HybridBlock/LatentAttention/mla_proj/"
     "RMSNorm/rsqrt", "mla_proj", "forward"),
    (_BLOCK + "HybridBlock/MultiHeadAttention/attn_proj/RMSNorm/mul",
     "attn_proj", "backward"),
    ("jit(step)/jvp(_LMWithMTP)/mtp/RMSNorm/mul", "norm", "forward"),
    ("jit(step)/jvp(_LMWithMTP)/mtp/HybridDecoder/HybridBlock/"
     "LatentAttention/mla_proj/dot_general", "mla_proj", "forward"),
    ("jit(step)/jvp(_LMWithMTP)/mtp/Linear/dot_general", "linear",
     "forward"),
    ("jit(step)/jvp(_LMWithMTP)/mtp/concatenate", "mtp", "forward"),
    ("jit(step)/jvp(Sequential)/HybridDecoder/HybridBlock/RMSNorm/mul",
     "norm", "forward"),
    ("jit(step)/jvp(Sequential)/LookupTable/jit(_take)/gather", "embed",
     "forward"),
    ("jit(step)/transpose(jvp(Sequential))/Sequential/"
     "SpatialConvolution/conv_general_dilated", "conv", "backward"),
    ("jit(step)/jvp(Sequential)/Sequential/SpatialBatchNormalization/"
     "reduce_sum", "batchnorm", "forward"),
    # the step's own stages: the pass is `update` whatever wraps them
    ("jit(step)/optim_update/div", "optim_update", "update"),
    ("jit(step)/grad_clip/mul", "grad_clip", "update"),
    ("jit(step)/grad_sync/reduce_scatter", "grad_sync", "update"),
    ("jit(step)/jvp(param_cast)/convert_element_type", "param_cast",
     "update"),
    ("jit(step)/transpose(jvp(param_cast))/convert_element_type",
     "param_cast", "update"),
    # XLA's own names, and two source operations joined into one
    ("reduce_sum", "unattributed", "forward"),
    ("", "unattributed", "forward"),
    ("jit(step)/jvp(Sequential)/HybridDecoder/HybridBlock/MoE/moe_route/"
     "reshape;jit(step)/jvp(Sequential)/HybridDecoder/HybridBlock/RMSNorm/"
     "mul", "moe_route", "forward"),
    # a router that reads the stream from ahead of its layer's attention
    (_BLOCK + "rematted_computation/HybridBlock/MoE/moe_route_ahead/"
     "dot_general",
     "moe_route_ahead", "recompute"),
    ("jit(step)/jvp(Sequential)/HybridDecoder/HybridBlock/MoE/"
     "moe_route_ahead/sort", "moe_route_ahead", "forward"),
    # the convolution mixer's two scopes; neither is the convnets' ``conv``
    ("jit(step)/jvp(_LM)/HybridDecoder/HybridBlock/ShortConv/"
     "short_conv_proj/dot_general", "short_conv_proj", "forward"),
    (_BLOCK + "HybridBlock/ShortConv/short_conv_local/pad",
     "short_conv_local", "backward"),
    (_BLOCK + "rematted_computation/HybridBlock/ShortConv/short_conv_local/"
     "mul", "short_conv_local", "recompute"),
    (_BLOCK + "rematted_computation/HybridBlock/ShortConv/short_conv_proj/"
     "dot_general", "short_conv_proj", "recompute"),
    # ... and what the mixer does outside them is no layer's
    ("jit(step)/jvp(_LM)/HybridDecoder/HybridBlock/ShortConv/mul",
     "unattributed", "forward"),
    # the delta-rule mixer's three scopes; its recurrence's own backward
    # rule (the triangular inverse's) enters ``delta_rule`` by hand
    ("jit(step)/jvp(Sequential)/HybridDecoder/HybridBlock/GatedDeltaNet/"
     "delta_proj/dot_general", "delta_proj", "forward"),
    (_BLOCK + "rematted_computation/HybridBlock/GatedDeltaNet/delta_local/"
     "logistic", "delta_local", "recompute"),
    (_BLOCK + "HybridBlock/GatedDeltaNet/delta_rule/while/body/dot_general",
     "delta_rule", "backward"),
    (_BLOCK + "HybridBlock/GatedDeltaNet/delta_rule/dot_general",
     "delta_rule", "backward"),
    # a looped decoder: the stack's layers keep their names inside the
    # loop over the passes; the exit gate's product and distribution are
    # ``loop_exit``, in the model and in the criterion, both ways
    ("jit(step)/jvp(_LoopedLM)/HybridDecoder/while/body/HybridBlock/"
     "GatedMLP/mlp/dot_general", "mlp", "forward"),
    ("jit(step)/transpose(jvp(_LoopedLM))/HybridDecoder/while/body/"
     "checkpoint/rematted_computation/HybridBlock/RMSNorm/mul", "norm",
     "recompute"),
    ("jit(step)/jvp(_LoopedLM)/loop_exit/dot_general", "loop_exit",
     "forward"),
    ("jit(step)/transpose(jvp(criterion))/loop_exit/logistic", "loop_exit",
     "backward"),
    ("jit(step)/jvp(criterion)/loop_exit/cumsum", "loop_exit", "forward"),
]


@pytest.mark.parametrize("op_name,layer,pas", CASES,
                         ids=[f"{c[1]}-{c[2]}-{i}"
                              for i, c in enumerate(CASES)])
def test_classify(op_name, layer, pas):
    assert sp.classify(op_name) == (layer, pas)


# ------------------------------- (c) the five cells' steps, rehearsal size

#: the layers each cell's row of ISSUE 36's table names, and what else its
#: model holds
CELLS = {
    "resnet50-train-dp4":
        {"conv", "batchnorm", "pool", "activation", "linear", "criterion"},
    "qwen2.5-0.5b-train-s2048":
        {"attn_proj", "attn_core", "mlp", "norm", "embed", "lm_head_ce"},
    "nemotron-3-nano-30b-a3b-train-s8192":
        {"attn_proj", "attn_core", "mamba_proj", "mamba_local", "ssd_scan",
         "moe_route", "moe_experts", "moe_shared", "norm", "embed",
         "lm_head_ce"},
    "trinity-mini-train-s8192":
        {"attn_proj", "attn_core", "mlp", "moe_route", "moe_experts",
         "moe_shared", "norm", "embed", "lm_head_ce"},
    "joyai-llm-flash-train-s8192":
        {"mla_proj", "attn_core", "mlp", "moe_route", "moe_experts",
         "moe_shared", "mtp", "norm", "embed", "lm_head_ce", "linear"},
    "smallthinker-21b-a3b-train-s16384":
        {"attn_proj", "attn_core", "moe_route_ahead", "moe_experts", "norm",
         "embed", "lm_head_ce"},
    "lfm2-24b-a2b-train-s8192":
        {"short_conv_proj", "short_conv_local", "attn_proj", "attn_core",
         "mlp", "moe_route", "moe_experts", "norm", "embed", "lm_head_ce"},
    "olmo-hybrid-7b-train-s8192":
        {"delta_proj", "delta_local", "delta_rule", "attn_proj",
         "attn_core", "mlp", "norm", "embed", "lm_head_ce"},
    "ouro-2.6b-train-s4096":
        {"attn_proj", "attn_core", "mlp", "norm", "embed", "lm_head_ce",
         "loop_exit"},
}
UPDATE = {"param_cast", "grad_clip", "optim_update"}
REMAT = {"nemotron-3-nano-30b-a3b-train-s8192", "trinity-mini-train-s8192",
         "joyai-llm-flash-train-s8192",
         "smallthinker-21b-a3b-train-s16384", "lfm2-24b-a2b-train-s8192",
         "olmo-hybrid-7b-train-s8192", "ouro-2.6b-train-s4096"}


def _step_text(cell_name):
    """The compiled text of the cell's ``train.step`` at the rehearsal
    size, built as ``benchmark/kinds/train.py`` builds it (one chip: the
    layers are the mesh's)."""
    from benchmark.kinds.train import _optim_method, _policy
    cell, cfg = harness.load_cell(cell_name, rehearse=True)
    builder = harness.load_builder(cfg["family"])
    model = builder.build(cfg, 3)
    samples = builder.train_samples(cfg, cell, 3)[:cell["batch_size"]]
    ds = DataSet.array(samples) >> SampleToBatch(cell["batch_size"])
    opt = Optimizer(model, ds, builder.criterion(cfg))
    opt.set_optim_method(_optim_method(cell["optim"]))
    opt.set_precision(_policy(cell["precision"]))
    opt.set_gradient_clipping_by_l2_norm(cell.get("clip_l2") or 1.0)
    params, buffers = model.parameter_tree(), model.buffer_tree()
    data = np.stack([np.asarray(s.feature) for s in samples])
    labels = np.stack([np.asarray(s.label) for s in samples])
    if cell.get("cast_dtype"):
        data = data.astype(cell["cast_dtype"])
    return opt._build_step().lower(
        params, buffers, opt._init_opt_state(params), jax.random.PRNGKey(0),
        jnp.asarray(data), jnp.asarray(labels)).compile().as_text()


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_a_cells_step_classifies(cell_name, monkeypatch):
    """No product, convolution or kernel call of the step is
    ``unattributed``, every layer of the cell is there with the step's own
    stages, and ``recompute`` exactly where the cell's blocks are
    rematerialised."""
    monkeypatch.setattr(fa, "use_flash", lambda q, mask: mask is None)
    table = sp.instructions(_step_text(cell_name))
    cells = {name: sp.classify(op) for name, (_, op) in table.items()}
    heavy = {name for name, (code, op) in table.items()
             if code in ("dot", "convolution")
             or code == "custom-call" and "pallas_call" in op}
    assert heavy
    lost = sorted(table[n][1] for n in heavy
                  if cells[n][0] == sp.UNATTRIBUTED)
    assert not lost, lost[:5]
    layers = {layer for layer, _ in cells.values()}
    assert CELLS[cell_name] | UPDATE <= layers, \
        (CELLS[cell_name] | UPDATE) - layers
    passes = {pas for _, pas in cells.values()}
    assert {"forward", "backward", "update"} <= passes
    assert ("recompute" in passes) == (cell_name in REMAT)
    # the scopes the older readers find their instructions by are whole:
    # nothing is entered twice
    assert not [op for _, op in table.values()
                for scope in ("moe_experts", "ssd_scan", "lm_head_ce",
                              "attn_core", "mla_proj")
                if f"{scope}/{scope}" in op or f"{scope})/{scope}" in op]


def _distri(sync_mode, compress=False):
    from bigdl_tpu.models import lenet
    from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
    from bigdl_tpu.parallel.mesh import MeshTopology
    rng = np.random.default_rng(0)
    samples = [Sample(rng.normal(0, 1, (28, 28, 1)).astype("float32"),
                      float(rng.integers(1, 11))) for _ in range(16)]
    ds = DataSet.array(samples, distributed=True) >> SampleToBatch(16)
    opt = DistriOptimizer(lenet.build(10), ds, nn.ClassNLLCriterion(),
                          topology=MeshTopology(data=8),
                          compress_gradients=compress)
    opt.sync_mode = sync_mode
    opt.set_optim_method(SGD(learningrate=0.1))
    opt.set_gradient_clipping_by_l2_norm(1.0)
    return opt


@pytest.mark.parametrize("sync_mode,wanted", [
    ("allreduce", {"grad_clip", "optim_update", "grad_sync"}),
    ("fsdp", {"grad_clip", "optim_update", "grad_sync"}),
    ("sharded", {"grad_clip", "optim_update", "grad_sync"})])
def test_every_step_builder_enters_the_steps_stages(sync_mode, wanted):
    """The mesh's three builders carry the same stage scopes as the local
    one (which the cells above lower), and the collectives a builder
    writes itself are ``grad_sync`` / ``optim_update``."""
    opt = _distri(sync_mode, compress=True)
    step = opt._build_step()
    params, buffers = opt.model.parameter_tree(), opt.model.buffer_tree()
    opt_state = opt._init_opt_state(params)
    params, buffers, opt_state = opt._place_state(params, buffers, opt_state)
    args = (buffers, opt_state, jax.random.key(0),
            jnp.zeros((16, 28, 28, 1)), jnp.ones((16,)))
    if sync_mode == "sharded":
        from jax.flatten_util import ravel_pytree
        flat, _ = ravel_pytree(opt.model.parameter_tree())
        flat = jax.device_put(jnp.pad(flat, (0, opt._pad)), opt._replicated)
        text = step.jitted.lower(flat, *args).compile().as_text()
    else:
        text = step.lower(params, *args).compile().as_text()
    table = sp.instructions(text)
    cells = {name: sp.classify(op) for name, (_, op) in table.items()}
    assert wanted <= {layer for layer, _ in cells.values()}
    if sync_mode == "sharded":
        by_code = {code: cells[name][0] for name, (code, _) in table.items()
                   if code in ("reduce-scatter", "all-gather")}
        assert by_code == {"reduce-scatter": "grad_sync",
                           "all-gather": "optim_update"}


# --------------------------------------------------- (d) partition, by hand

HLO = """HloModule jit_step, is_scheduled=true

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/jvp(Sequential)/Linear/mul"}
  ROOT %a = f32[8]{0} add(%m, %p), metadata={op_name="jit(step)/jvp(Sequential)/ReLU/add"}
}

%body (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %inner.1 = f32[8]{0} negate(%x), metadata={op_name="jit(step)/jvp(criterion)/lm_head_ce/while/body/neg"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a.1 = f32[8]{0} parameter(0), metadata={op_name="params['w']"}
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%a.1)
  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
  %fusion.1 = f32[8]{0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/jvp(Sequential)/Linear/mul"}
  %while.1 = f32[8]{0} while(%fusion.1), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(criterion)/lm_head_ce/while"}
  %fusion.2 = f32[8]{0} fusion(%while.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(Sequential))/checkpoint/rematted_computation/Linear/mul"}
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(Sequential))/Linear/transpose"}
  %other.1 = f32[8]{0} negate(%fusion.3)
  ROOT %fusion.4 = f32[8]{0} fusion(%fusion.3), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/optim_update/sub"}
}
"""


def _run(t0):
    """One run's events from ``t0``: 1 ms of each instruction, the loop's
    event spanning its body's two trips."""
    names = ["copy-start.1", "copy-done.1", "%fusion.1 = f32[8]{0} fusion("
             "%copy-done.1), kind=kLoop", "inner.1", "inner.1", "fusion.2",
             "fusion.3", "fusion.4", "stray.9"]
    ev = [(n, t0 + i * 1e-3, t0 + (i + 1) * 1e-3)
          for i, n in enumerate(names)]
    ev.append(("while.1", t0 + 3e-3, t0 + 5e-3))
    return ev, (t0, t0 + len(names) * 1e-3)


def test_partition_counts_whole_runs_and_leaves_containers_out():
    ev1, run1 = _run(1.0)
    ev2, run2 = _run(2.0)
    cut, _ = _run(0.9955)            # began before the profile: no run
    events = [e for e in cut if e[1] < 1.0] + ev1 + ev2
    rows = sp.partition(HLO, events, [run1, run2])
    ms = {k: round(v * 1e3, 6) for k, v in rows.items()}
    assert ms == {
        # the prefetch pair takes the layer of the fusion it feeds
        ("linear", "forward"): 3.0,
        ("lm_head_ce", "forward"): 2.0,     # the body's trips, not the loop
        ("linear", "recompute"): 1.0,
        ("linear", "backward"): 1.0,
        ("optim_update", "update"): 1.0,
        ("unattributed", "forward"): 1.0,   # an event the text lacks
    }
    # the total is the runs' busy time: 9 events a run of 1 ms
    assert round(sum(rows.values()) * 1e3, 6) == 9.0
    by_instr = sp.instruction_seconds(HLO, events, [run1, run2])
    assert "while.1" not in by_instr and round(
        by_instr["inner.1"] * 1e3, 6) == 2.0


def test_a_stump_at_either_end_of_a_profile_is_no_whole_run():
    runs = [(0.0, 0.04), (0.05, 0.15), (0.16, 0.262), (0.27, 0.37),
            (0.38, 0.41)]
    assert sp.whole_runs(runs) == runs[1:-1]
    steady = [(0.0, 0.1), (0.11, 0.205), (0.21, 0.31)]
    assert sp.whole_runs(steady) == steady
    assert sp.whole_runs(runs[:2]) == runs[:2]     # too few to tell


def test_a_pathless_instruction_takes_its_users_name_then_its_operands():
    table = sp.instructions(HLO)
    assert sp.classify(table["copy-start.1"][1]) == ("linear", "forward")
    assert sp.classify(table["a.1"][1]) == ("linear", "forward")
    # no user: the operand's
    assert sp.classify(table["other.1"][1]) == ("linear", "backward")
    assert table["while.1"][0] == "while"


def test_mixed_fusions_lists_what_a_fusion_spans():
    mixed = sp.mixed_fusions(HLO)
    assert mixed["fusion.1"] == ("activation", "linear")
    assert set(mixed) == {"fusion.1", "fusion.2", "fusion.3", "fusion.4"}


def test_the_module_imports_no_jax():
    import ast
    import inspect
    tree = ast.parse(inspect.getsource(sp))
    top = [n for n in tree.body if isinstance(n, (ast.Import,
                                                  ast.ImportFrom))]
    names = [a.name for n in top if isinstance(n, ast.Import)
             for a in n.names] + [n.module for n in top
                                  if isinstance(n, ast.ImportFrom)]
    assert not [n for n in names if n.split(".")[0] == "jax"]


# ------------------------------- (e) set_profiling writes the table (CPU)

def test_set_profiling_writes_the_steps_partition(tmp_path, caplog):
    rng = np.random.default_rng(0)
    samples = [Sample(rng.standard_normal(16).astype(np.float32),
                      float(rng.integers(1, 5))) for _ in range(64)]
    ds = DataSet.array(samples) >> SampleToBatch(16)
    model = (nn.Sequential().add(nn.Linear(16, 32)).add(nn.ReLU())
             .add(nn.Linear(32, 4)).add(nn.LogSoftMax()))
    opt = Optimizer(model, ds, nn.ClassNLLCriterion())
    opt.set_optim_method(SGD(learningrate=0.01))
    opt.set_remat(True)
    opt.set_end_when(Trigger.max_iteration(8))
    opt.set_profiling(str(tmp_path), start_iteration=3, n_iterations=3)
    with caplog.at_level("INFO", logger="bigdl_tpu.optim"):
        opt.optimize()
    report = json.loads((tmp_path / "step_partition.json").read_text())
    assert report["program"] == "jit_train_step" and report["runs"] >= 1
    assert list(report["passes"]) == list(sp.PASSES)
    assert all(report["passes"][p] > 0 for p in sp.PASSES), report["passes"]
    assert {r["layer"] for r in report["rows"]} >= {
        "linear", "activation", "criterion", "optim_update"}
    assert abs(sum(r["share"] for r in report["rows"]) - 100.0) < 0.1
    assert set(report["copies"]) <= set(report["layers"])
    assert abs(sum(r["ms"] for r in report["rows"])
               - report["step_ms"]) < 1e-2
    assert "step partition of jit_train_step" in caplog.text
