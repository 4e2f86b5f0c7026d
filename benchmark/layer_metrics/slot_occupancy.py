"""serving engine: mean of ``live`` / slots over the ``serving.decode_block``
spans in the traced slice."""
LAYER, UNIT = "serving engine", "%"


def read(ctx):
    live = [s["args"].get("live") for s in ctx["spans"]
            if s["name"] == "serving.decode_block" and s["ph"] == "X"]
    live = [x for x in live if x is not None]
    slots = ctx["extra"].get("slots")
    if not live or not slots:
        return None
    return 100.0 * sum(live) / (len(live) * slots)
