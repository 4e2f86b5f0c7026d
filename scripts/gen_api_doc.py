#!/usr/bin/env python
"""Regenerate docs/API.md — one line per public class/function.

Run from the repo root: python scripts/gen_api_doc.py
"""

import importlib
import inspect
import io
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODS = [
    ("bigdl_tpu.tensor", "Tensor core"),
    ("bigdl_tpu.nn", "Layers, containers, criterions"),
    ("bigdl_tpu.dataset", "Data pipeline"),
    ("bigdl_tpu.dataset.image", "Image transformers"),
    ("bigdl_tpu.dataset.text", "Text transformers"),
    ("bigdl_tpu.dataset.shards", "Packed-shard ingest"),
    ("bigdl_tpu.optim", "Optimizers, triggers, validation"),
    ("bigdl_tpu.parallel", "Distribution (mesh, DP/TP/PP/SP/EP)"),
    ("bigdl_tpu.models.transformer", "Causal-LM builder"),
    ("bigdl_tpu.models.hybrid", "Causal-LM builder over a pattern decoder"),
    ("bigdl_tpu.models.generation", "Autoregressive decoding"),
    ("bigdl_tpu.ops.flash_attention", "Flash attention (Pallas)"),
    ("bigdl_tpu.ops.lm_head_ce", "Fused LM-head cross-entropy"),
    ("bigdl_tpu.ops.precision", "Mixed-precision policy"),
    ("bigdl_tpu.interop", "Torch/Caffe import-export"),
    ("bigdl_tpu.ml", "sklearn-protocol classifier"),
    ("bigdl_tpu.visualization", "TensorBoard summaries"),
    ("bigdl_tpu.utils", "Engine, tables, RNG, IO, graphs"),
    ("bigdl_tpu.native", "C++ runtime bindings"),
    ("bigdl_tpu.analysis", "graftlint static analyzer"),
    ("bigdl_tpu.telemetry", "Runtime observability (metrics, tracing)"),
    ("bigdl_tpu.resilience", "Preemption-aware elastic resume"),
]


def _is_public_callable(obj) -> bool:
    if inspect.isclass(obj) or inspect.isfunction(obj):
        return getattr(obj, "__module__", "").startswith("bigdl_tpu")
    # jitted functions (jax.jit wrappers) are callables with __wrapped__
    inner = getattr(obj, "__wrapped__", None)
    return (callable(obj) and inner is not None
            and getattr(inner, "__module__", "").startswith("bigdl_tpu"))


def main() -> None:
    out = io.StringIO()
    out.write("""# bigdl_tpu API index

Generated by ``scripts/gen_api_doc.py`` — one line per public
class/function; see docstrings for details, `MIGRATION.md` for the
reference-symbol mapping.

""")
    total = 0
    for modname, title in MODS:
        m = importlib.import_module(modname)
        out.write(f"## {modname} — {title}\n\n")
        rows = []
        names = getattr(m, "__all__", None) or sorted(
            n for n in dir(m) if not n.startswith("_"))
        for n in names:
            obj = getattr(m, n)
            if inspect.ismodule(obj) or not _is_public_callable(obj):
                continue
            # keep each name documented once: aggregator packages list
            # their whole re-exported namespace; leaf modules list only
            # what they define
            home = getattr(getattr(obj, "__wrapped__", obj), "__module__", "")
            aggregator = modname.count(".") == 1
            if aggregator:
                if any(home == other for other, _ in MODS if other != modname):
                    continue
            elif home != modname:
                continue
            # first PARAGRAPH, joined — first-physical-line alone commits
            # dangling mid-sentence fragments for wrapped docstrings
            doc = (inspect.getdoc(obj) or "").split("\n\n")[0]
            doc = " ".join(doc.split()).strip()
            if len(doc) > 110:
                cut = doc[:107]
                if " " in cut:  # last word boundary: no mid-word fragments
                    cut = cut[:cut.rfind(" ")]
                if cut.count("``") % 2:  # drop an unterminated literal span
                    cut = cut[:cut.rfind("``")].rstrip()
                doc = cut + " ..."
            kind = "class" if inspect.isclass(obj) else "def"
            rows.append(f"- `{n}` ({kind}) — {doc}")
        total += len(rows)
        out.write("\n".join(rows) + "\n\n")

    # graftlint rule table, generated from the registry docstrings — the
    # single source of truth for rule ids/summaries/rationales
    from bigdl_tpu.analysis import all_rules
    out.write("## graftlint rule catalogue (bigdl_tpu.analysis)\n\n")
    out.write("Run ``./scripts/bigdl-tpu.sh lint`` or ``python -m "
              "bigdl_tpu.analysis``; full guide in ``docs/ANALYSIS.md``.\n\n")
    out.write("| code | summary | rationale |\n|------|---------|"
              "-----------|\n")
    for rule in all_rules():
        doc = " ".join((rule.__doc__ or "").split()).replace("|", "\\|")
        summary = rule.summary.replace("|", "\\|")
        out.write(f"| {rule.code} | {summary} | {doc} |\n")
        total += 1
    out.write("\n")

    # telemetry inventory, generated from the catalogue specs — the single
    # source of truth every instrumented subsystem builds its families from
    from bigdl_tpu.telemetry import METRIC_SPECS, SCOPE_SPECS, SPAN_SPECS
    out.write("## telemetry metric catalogue (bigdl_tpu.telemetry)\n\n")
    out.write("Scrape via ``GET /metrics`` on a serving process or "
              "``./scripts/bigdl-tpu.sh metrics <url>``; guide in "
              "``docs/OBSERVABILITY.md``.\n\n")
    out.write("| name | kind | labels | help |\n|------|------|--------|"
              "------|\n")
    for spec in METRIC_SPECS:
        labels = ", ".join(spec.labels) if spec.labels else "—"
        help_ = " ".join(spec.help.split()).replace("|", "\\|")
        out.write(f"| `{spec.name}` | {spec.kind} | {labels} | {help_} |\n")
        total += 1
    out.write("\n## telemetry span catalogue (bigdl_tpu.telemetry."
              "tracing)\n\n")
    out.write("Enable with ``tracing.enable()`` or "
              "``BIGDL_TPU_TRACE=/path.json``; dumps load in "
              "chrome://tracing / Perfetto.\n\n")
    out.write("| span | fires at |\n|------|----------|\n")
    for name, where in SPAN_SPECS:
        out.write(f"| `{name}` | {' '.join(where.split())} |\n")
        total += 1
    out.write("\n## step layer catalogue (bigdl_tpu.telemetry."
              "step_partition)\n\n")
    out.write("The closed vocabulary of ``jax.named_scope``s by which a "
              "compiled step's device time is split into layer x pass "
              "(``Optimizer.set_profiling`` -> ``step_partition.json``; "
              "guide in ``docs/OBSERVABILITY.md``). An instruction belongs "
              "to the innermost of these in its ``op_name``.\n\n")
    out.write("| layer | entered at | holds | also named by |\n"
              "|-------|------------|-------|---------------|\n")
    for spec in SCOPE_SPECS:
        also = ", ".join([f"`{c}`" for c in spec.classes]
                         + [f"`{k}*` kernels" for k in spec.kernels]) or "—"
        kind = " (group)" if spec.group else \
            " (pass `update`)" if spec.update else ""
        out.write(f"| `{spec.name}` | {' '.join(spec.entered.split())}"
                  f"{kind} | {' '.join(spec.holds.split())} | {also} |\n")
        total += 1
    out.write("\n")
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "API.md")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(out.getvalue())
    print(f"wrote {path}: {total} entries")


if __name__ == "__main__":
    main()
