"""model step: model FLOPs utilisation. FLOPs a record from shapes
(``benchmark/flops.py``: forward + backward, no recomputation) x this run's
records a second, over chips x the table's bf16 peak. An end-to-end
utilisation: it is no kernel's roofline share and says nothing of idle."""
LAYER, UNIT = "model step", "%"

from benchmark import harness


def read(ctx):
    builder = harness.load_builder(ctx["config"]["family"])
    per = builder.train_flops_per_record(ctx["config"], ctx["cell"])
    rate = ctx["values"].get("train_records_per_s")
    if not per or not rate or not ctx["peaks"]:
        return None
    return 100.0 * per * rate / (ctx["chips"]
                                 * ctx["peaks"]["bf16_flops_per_s"])
