"""serving engine: share of the traced slice's wall time the serve loop
spent inside ``serving.prefill`` + ``serving.insert`` spans: admission runs
inline, so no slot decodes meanwhile."""
LAYER, UNIT = "serving engine", "%"


def read(ctx):
    wall = ctx["extra"].get("slice_wall_s")
    spans = [s for s in ctx["spans"] if s["ph"] == "X"
             and s["name"] in ("serving.prefill", "serving.insert")]
    if not wall or not ctx["spans"]:
        return None
    return 100.0 * sum(s["dur"] for s in spans) / wall
