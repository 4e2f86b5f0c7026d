"""Collective calls and bytes out of a compiled step's HLO text. A copy of
``bigdl_tpu/analysis/commcost.py::collective_bytes_from_hlo`` (sound since
PR 21), kept here so that no later PR can change the yardstick.

Counts plain and ``-start`` forms and skips ``-done``. Payload is the full
logical size: the output for all-reduce / all-gather / collective-permute /
all-to-all, output x group for reduce-scatter.
"""

from __future__ import annotations

import re

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


def _result_bytes(result_type, is_start):
    """Async-start tuples are (operand, result): the LAST element is the
    output; a plain op with a tuple result is variadic: the SUM."""
    sizes = []
    for dtype, dims in _SHAPE_RE.findall(result_type):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        sizes.append(n * _DTYPE_BYTES.get(dtype, 4))
    if not sizes:
        return 0
    return sizes[-1] if is_start else sum(sizes)


def _group_size(line):
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    return len(m.group(1).split(",")) if m else None


def collectives(hlo_text):
    """{op: {"count", "payload_bytes", "groups": sorted group sizes}}."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        op = m.group(2)
        size = _group_size(line) or 1
        nbytes = _result_bytes(m.group(1), bool(m.group(3)))
        d = out.setdefault(op, {"count": 0, "payload_bytes": 0,
                                "groups": set()})
        d["count"] += 1
        d["payload_bytes"] += nbytes * size if op == "reduce-scatter" \
            else nbytes
        d["groups"].add(size)
    for d in out.values():
        d["groups"] = sorted(d["groups"])
    return out
