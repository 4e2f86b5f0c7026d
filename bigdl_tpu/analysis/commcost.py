"""Static collective byte model: closed-form bytes-per-step for every
collective the repo's training/serving modes emit, written to
``COMM_MODEL.json`` and cross-checked against measured HLO.

Three layers, cheapest first:

1. **Op algebra** — ring-equivalent wire bytes and HBM touch bytes per
   collective, as expressions in ``B`` (full payload bytes) and ``S``
   (participant group size). These are topology-independent lower bounds
   (bidirectional-ring == bandwidth-optimal for all-reduce family).
2. **Mode models** — per training mode (``dryrun_multichip`` pass names),
   which collectives fire per optimizer step and with what payload, as
   closed-form expressions in mesh-axis sizes and model symbols
   (``P`` = parameter bytes, ``P_flat`` = padded flat-vector bytes, ...).
3. **Site scan** — a static AST walk over the tree recording every
   collective call site (op, mesh axis, file:line) plus every shard_map
   boundary with its in/out spec axes, so the JSON names where each term
   of layer 2 comes from.

The model is validated two ways by ``tests/test_comm_model.py``: the
mode predictions are evaluated against collective bytes parsed out of
the actually-compiled step HLO (``collective_bytes_from_hlo``), and the
HBM side is bounded by the PR-14 flight recorder's
``bigdl_program_bytes_accessed`` gauge. ``tests/test_packaging.py``
pins ``COMM_MODEL.json`` against drift the same way the telemetry
catalogue gate does.
"""

from __future__ import annotations

import ast
import json
import os
import re
from typing import Any, Dict, Iterator, List, Optional, Tuple

# ---------------------------------------------------------------------------
# Layer 1: op algebra.
#
# wire  = bytes crossing links per participating device, bandwidth-optimal
#         (bidirectional ring / recursive halving-doubling equivalent)
# hbm   = bytes the op reads + writes in device memory (operands + results)
#
# B is the FULL logical payload (the gathered / pre-scatter size); S the
# group size along the participating mesh axis.
# ---------------------------------------------------------------------------

OPS: Dict[str, Dict[str, str]] = {
    "all-reduce": {
        "wire": "2*B*(S-1)/S",
        "hbm": "2*B",
        "note": "reduce-scatter + all-gather phases; psum/pmean/pmax/pmin",
    },
    "all-gather": {
        "wire": "B*(S-1)/S",
        "hbm": "B*(S+1)/S",
        "note": "reads the B/S shard, writes the full B; lax.all_gather "
                "and SPMD-inserted parameter gathers (ZeRO-1/3)",
    },
    "reduce-scatter": {
        "wire": "B*(S-1)/S",
        "hbm": "B*(S+1)/S",
        "note": "reads the full B, writes the owned B/S shard; "
                "lax.psum_scatter and sharded-gradient sync",
    },
    "all-to-all": {
        "wire": "B*(S-1)/S",
        "hbm": "2*B",
        "note": "each device keeps 1/S of its shard; MoE dispatch/combine",
    },
    "collective-permute": {
        "wire": "B",
        "hbm": "2*B",
        "note": "point-to-point shift; lax.ppermute (ring attention, "
                "pipeline boundaries)",
    },
}

# jax.lax entry point -> HLO op the model prices it as
LAX_TO_HLO = {
    "psum": "all-reduce", "pmean": "all-reduce", "pmax": "all-reduce",
    "pmin": "all-reduce", "psum_scatter": "reduce-scatter",
    "all_gather": "all-gather", "all_to_all": "all-to-all",
    "pshuffle": "all-to-all", "ppermute": "collective-permute",
}


def wire_bytes(op: str, payload_bytes: float, group_size: int) -> float:
    """Evaluate OPS[op]['wire'] numerically."""
    return _eval_formula(OPS[op]["wire"], B=payload_bytes, S=group_size)


def hbm_bytes(op: str, payload_bytes: float, group_size: int) -> float:
    """Evaluate OPS[op]['hbm'] numerically."""
    return _eval_formula(OPS[op]["hbm"], B=payload_bytes, S=group_size)


def _eval_formula(expr: str, **bindings: float) -> float:
    # formulas are our own arithmetic strings (no names beyond bindings)
    return float(eval(expr, {"__builtins__": {}}, dict(bindings)))


# ---------------------------------------------------------------------------
# Layer 2: mode models. Symbols:
#   S_data/S_tensor/S_pipe/S_seq/S_expert  mesh-axis sizes
#   P       total parameter bytes
#   P_flat  padded flat-vector bytes ((n_params + pad) * 4, ZeRO-1 geometry)
#   P_shd   parameter bytes actually sharded by fsdp_param_specs
#   k_ag    fsdp gathers per step per param (1 fwd; XLA may re-gather for
#           the backward instead of keeping the full weight live: 1..3)
#   A       activation bytes at one tensor-parallel block boundary
#   n_blk   transformer blocks under tensor parallelism
#   T       routed token bytes per MoE layer (dispatch == combine payload)
#   n_moe   MoE layers
#   K       K/V block bytes rotated per ring-attention step
#   n_ring  ring attention invocations per step (fwd + recomputed bwd)
#   M       boundary activation bytes per microbatch
#   n_micro pipeline microbatches
# Each entry prices ONE optimizer step, totaled over the mesh.
# ---------------------------------------------------------------------------

MODES: Dict[str, List[Dict[str, str]]] = {
    "dp-allreduce": [
        {"op": "all-reduce", "axis": "data", "payload": "P",
         "wire": "2*P*(S_data-1)/S_data",
         "note": "one logical gradient all-reduce (XLA may split it)"},
    ],
    "dp-sharded": [
        {"op": "reduce-scatter", "axis": "data", "payload": "P_flat",
         "wire": "P_flat*(S_data-1)/S_data",
         "note": "ZeRO-1 gradient scatter over the padded flat vector"},
        {"op": "all-gather", "axis": "data", "payload": "P_flat",
         "wire": "P_flat*(S_data-1)/S_data",
         "note": "updated-slice re-broadcast (AllReduceParameter exchange)"},
    ],
    "fsdp": [
        {"op": "all-gather", "axis": "data", "payload": "k_ag*P_shd",
         "wire": "k_ag*P_shd*(S_data-1)/S_data",
         "note": "per-layer ZeRO-3 weight gathers, k_ag in [1,3]"},
        {"op": "reduce-scatter", "axis": "data", "payload": "P_shd",
         "wire": "P_shd*(S_data-1)/S_data",
         "note": "gradient sync to the owned shard (may lower as "
                 "all-reduce-keep-shard at small scale: wire 2x this term)"},
    ],
    "tp-megatron": [
        {"op": "all-reduce", "axis": "tensor", "payload": "4*n_blk*A",
         "wire": "8*n_blk*A*(S_tensor-1)/S_tensor",
         "note": "2 fwd + 2 bwd activation reductions per block "
                 "(attention out-proj + MLP down-proj)"},
    ],
    "fsdp x tp": [
        {"op": "all-gather", "axis": "data", "payload": "k_ag*P_shd",
         "wire": "k_ag*P_shd*(S_data-1)/S_data",
         "note": "ZeRO-3 gathers of the tensor-sharded weight shards"},
        {"op": "reduce-scatter", "axis": "data", "payload": "P_shd",
         "wire": "P_shd*(S_data-1)/S_data",
         "note": "gradient sync over data, shard-local in tensor"},
        {"op": "all-reduce", "axis": "tensor", "payload": "4*n_blk*A",
         "wire": "8*n_blk*A*(S_tensor-1)/S_tensor",
         "note": "Megatron activation reductions, unchanged by fsdp"},
    ],
    "dp x ep": [
        {"op": "all-to-all", "axis": "expert", "payload": "2*n_moe*T",
         "wire": "2*n_moe*T*(S_expert-1)/S_expert",
         "note": "token dispatch + combine per MoE layer"},
        {"op": "all-reduce", "axis": "data", "payload": "P",
         "wire": "2*P*(S_data-1)/S_data",
         "note": "dense-parameter gradient sync"},
    ],
    "sp-ring": [
        {"op": "collective-permute", "axis": "seq",
         "payload": "n_ring*(S_seq-1)*K",
         "wire": "n_ring*(S_seq-1)*K",
         "note": "K/V block rotation, S_seq-1 hops per attention pass"},
    ],
    "dp x cp": [
        {"op": "collective-permute", "axis": "seq",
         "payload": "n_ring*(S_seq-1)*K",
         "wire": "n_ring*(S_seq-1)*K",
         "note": "K/V rotation within each data group's seq coset "
                 "(same ring as sp-ring, run S_data times in parallel)"},
        {"op": "all-reduce", "axis": "data", "payload": "P",
         "wire": "2*P*(S_data-1)/S_data",
         "note": "replicated-parameter gradient sync across data groups"},
    ],
    "pp-gpipe": [
        {"op": "collective-permute", "axis": "pipe",
         "payload": "2*n_micro*(S_pipe-1)*M",
         "wire": "2*n_micro*(S_pipe-1)*M",
         "note": "microbatch activations crossing each stage boundary "
                 "fwd + bwd"},
    ],
}

_MODE_DEFAULTS = {"k_ag": 2.0}


def predict_mode(mode: str, **bindings: float) -> Dict[str, Any]:
    """Evaluate one mode's model. Returns per-term and total wire/hbm
    bytes per step. Unbound symbols raise NameError (the caller must
    supply every symbol its mode uses)."""
    env = dict(_MODE_DEFAULTS)
    env.update(bindings)
    terms = []
    for t in MODES[mode]:
        payload = _eval_formula(t["payload"], **env)
        s = env[f"S_{t['axis']}"]
        terms.append({
            "op": t["op"], "axis": t["axis"],
            "payload_bytes": payload,
            "wire_bytes": wire_bytes(t["op"], payload, int(s)),
            "hbm_bytes": hbm_bytes(t["op"], payload, int(s)),
        })
    return {"mode": mode, "terms": terms,
            "wire_bytes": sum(t["wire_bytes"] for t in terms),
            "hbm_bytes": sum(t["hbm_bytes"] for t in terms)}


# ---------------------------------------------------------------------------
# Layer 3: static collective-site scan.
# ---------------------------------------------------------------------------

# mesh.py axis constants: resolvable without executing the tree
_WELL_KNOWN_AXIS = {"DATA_AXIS": "data", "TENSOR_AXIS": "tensor",
                    "PIPELINE_AXIS": "pipe", "SEQUENCE_AXIS": "seq",
                    "EXPERT_AXIS": "expert"}
_AXIS_ARG_POS = {name: 1 for name in LAX_TO_HLO}
_SHARD_MAP_LASTS = {"shard_map"}
_PSPEC_LASTS = {"P", "PartitionSpec"}


def _module_str_constants(tree: ast.Module) -> Dict[str, str]:
    out = dict(_WELL_KNOWN_AXIS)
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Constant) \
                and isinstance(node.value.value, str):
            out[node.targets[0].id] = node.value.value
    return out


def _axis_of(node: Optional[ast.expr], consts: Dict[str, str]) -> str:
    if node is None:
        return "<dynamic>"
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name):
        return consts.get(node.id, "<dynamic>")
    if isinstance(node, (ast.Tuple, ast.List)):
        parts = [_axis_of(e, consts) for e in node.elts]
        return "+".join(parts)
    if isinstance(node, ast.Attribute):
        return consts.get(node.attr, "<dynamic>")
    return "<dynamic>"


def _spec_axis_names(expr: ast.expr, consts: Dict[str, str]) -> List[str]:
    """Axis names in P(...)/PartitionSpec(...) literals under ``expr``."""
    axes: List[str] = []
    for node in ast.walk(expr):
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func) or ""
        if callee.rsplit(".", 1)[-1] not in _PSPEC_LASTS:
            continue
        for arg in node.args:
            elts = arg.elts if isinstance(arg, (ast.Tuple, ast.List)) \
                else [arg]
            for elt in elts:
                if isinstance(elt, ast.Constant) and elt.value is None:
                    continue
                a = _axis_of(elt, consts)
                if a != "<dynamic>" and a not in axes:
                    axes.append(a)
    return axes


def _dotted(node: ast.expr) -> Optional[str]:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def _param_defaults(fn: ast.AST, consts: Dict[str, str]) -> Dict[str, str]:
    """Function parameters whose default is a resolvable axis name —
    ``def ring(..., axis_name=SEQUENCE_AXIS)`` makes a bare ``axis_name``
    inside the body mean "seq"."""
    out: Dict[str, str] = {}
    args = fn.args
    for params, defaults in ((args.args, args.defaults),
                             (args.kwonlyargs, args.kw_defaults)):
        pad = len(params) - len(defaults)
        for p, d in zip(params[pad:], defaults):
            if d is None:
                continue
            a = _axis_of(d, consts)
            if a != "<dynamic>":
                out[p.arg] = a
    return out


def _scan_file(path: str, rel: str) -> Iterator[Dict[str, Any]]:
    try:
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=path)
    except (OSError, SyntaxError):
        return
    mod_consts = _module_str_constants(tree)
    # innermost enclosing function's resolvable defaults shadow outer ones
    scopes: List[Tuple[ast.AST, Dict[str, str]]] = []

    def consts_at(node: ast.AST) -> Dict[str, str]:
        merged = dict(mod_consts)
        for fn, defaults in scopes:
            if (fn.lineno <= node.lineno
                    and node.lineno <= (fn.end_lineno or node.lineno)):
                merged.update(defaults)
        return merged

    for fn in ast.walk(tree):
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            d = _param_defaults(fn, mod_consts)
            if d:
                scopes.append((fn, d))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func) or ""
        last = callee.rsplit(".", 1)[-1]
        if last in LAX_TO_HLO and (
                callee == last or ".lax" in callee
                or callee.startswith("lax.")):
            pos = _AXIS_ARG_POS[last]
            axis_node = node.args[pos] if len(node.args) > pos else None
            if axis_node is None:
                for kw in node.keywords:
                    if kw.arg in ("axis_name", "axis"):
                        axis_node = kw.value
            op = LAX_TO_HLO[last]
            yield {"file": rel, "line": node.lineno, "call": last,
                   "op": op, "axis": _axis_of(axis_node, consts_at(node)),
                   "wire": OPS[op]["wire"]}
        elif last in _SHARD_MAP_LASTS:
            here = consts_at(node)
            in_axes: List[str] = []
            out_axes: List[str] = []
            for kw in node.keywords:
                if kw.arg == "in_specs":
                    in_axes = _spec_axis_names(kw.value, here)
                elif kw.arg == "out_specs":
                    out_axes = _spec_axis_names(kw.value, here)
            yield {"file": rel, "line": node.lineno, "call": "shard_map",
                   "op": "shard_map-boundary",
                   "axes_in": in_axes, "axes_out": out_axes,
                   "axes_consumed": [a for a in in_axes
                                     if a not in out_axes],
                   "wire": "0",
                   "note": "manual region: body collectives are separate "
                           "sites; consumed axes imply a body reduction"}


def default_scan_roots(repo_root: Optional[str] = None) -> Tuple[str, List[str]]:
    """(repo_root, files): the stable product tree the model covers."""
    if repo_root is None:
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    files: List[str] = []
    pkg = os.path.join(repo_root, "bigdl_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        files.extend(os.path.join(dirpath, f)
                     for f in sorted(filenames) if f.endswith(".py"))
    entry = os.path.join(repo_root, "__graft_entry__.py")
    if os.path.exists(entry):
        files.append(entry)
    return repo_root, files


def scan_sites(repo_root: Optional[str] = None) -> List[Dict[str, Any]]:
    root, files = default_scan_roots(repo_root)
    sites: List[Dict[str, Any]] = []
    for path in files:
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        sites.extend(_scan_file(path, rel))
    sites.sort(key=lambda s: (s["file"], s["line"]))
    return sites


# ---------------------------------------------------------------------------
# Model assembly + rendering.
# ---------------------------------------------------------------------------

MODEL_VERSION = 1


def build_model(repo_root: Optional[str] = None) -> Dict[str, Any]:
    return {
        "version": MODEL_VERSION,
        "conventions": {
            "B": "full logical payload bytes (gathered / pre-scatter size)",
            "S": "participant group size along the collective's mesh axis",
            "wire": "bytes crossing links per participating device, "
                    "bandwidth-optimal ring equivalent",
            "hbm": "device-memory bytes read + written by the op",
            "symbols": "see MODES notes; S_<axis> = mesh axis size, "
                       "P = param bytes, P_flat = padded flat-vector "
                       "bytes, P_shd = fsdp-sharded param bytes",
        },
        "ops": OPS,
        "modes": MODES,
        "sites": scan_sites(repo_root),
    }


def write_model(path: str, repo_root: Optional[str] = None) -> Dict[str, Any]:
    model = build_model(repo_root)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(model, f, indent=2, sort_keys=True)
        f.write("\n")
    return model


def render_perf_table() -> str:
    """Markdown byte-model table for PERF.md."""
    lines = ["| mode | collective | axis | wire bytes / step |",
             "|---|---|---|---|"]
    for mode in MODES:
        for t in MODES[mode]:
            lines.append(f"| {mode} | {t['op']} | {t['axis']} "
                         f"| `{t['wire']}` |")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Measured side: collective bytes out of compiled HLO text.
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
                "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8}
_SHAPE_RE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
# the result type is everything between "= " and the op name: TPU layouts
# nest parentheses inside a tuple type ("(f32[64]{0:T(128)S(1)}, ...)")
_OP_RE = re.compile(
    r"=\s+(.+?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start)?\(")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")


def _result_bytes(result_type: str, is_start: bool) -> int:
    """Output bytes of an HLO result type. For async-start tuples
    ``(operand, result)`` the LAST element is the op's true output; a
    plain op with a tuple result is variadic (one all-reduce over every
    gradient), so its output is the SUM of the elements."""
    sizes = []
    for dtype, dims in _SHAPE_RE.findall(result_type):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        sizes.append(n * _DTYPE_BYTES.get(dtype, 4))
    if not sizes:
        return 0
    return sizes[-1] if is_start else sum(sizes)


def _group_size(line: str) -> Optional[int]:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return None


def collective_bytes_from_hlo(txt: str,
                              default_group: int = 1) -> Dict[str, Any]:
    """Parse compiled HLO text into per-op payload/wire/hbm byte totals.

    Counts plain and ``-start`` forms (skipping ``-done``). Payload B is
    the full logical size: the output for all-reduce / all-gather /
    collective-permute / all-to-all, output*S for reduce-scatter."""
    per_op: Dict[str, Dict[str, float]] = {}
    for line in txt.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        result_type, op = m.group(1), m.group(2)
        out_bytes = _result_bytes(result_type, bool(m.group(3)))
        s = _group_size(line) or default_group
        payload = out_bytes * s if op == "reduce-scatter" else out_bytes
        d = per_op.setdefault(op, {"count": 0, "payload_bytes": 0.0,
                                   "wire_bytes": 0.0, "hbm_bytes": 0.0})
        d["count"] += 1
        d["payload_bytes"] += payload
        d["wire_bytes"] += wire_bytes(op, payload, s)
        d["hbm_bytes"] += hbm_bytes(op, payload, s)
    return {"per_op": per_op,
            "wire_bytes": sum(d["wire_bytes"] for d in per_op.values()),
            "hbm_bytes": sum(d["hbm_bytes"] for d in per_op.values())}
