"""kernels: share of the device's busy time in the flash-attention Mosaic
calls (forward, dQ, dK/dV). The kernels carry no name of their own in the
trace (they are named after the enclosing jit: a ``tracing`` issue), so a
call is recognised by what it is: a ``tpu_custom_call`` whose operands have
the cell's (batch*heads, seq, head_dim) attention shape."""
LAYER, UNIT = "kernels", "%"

from benchmark import harness, reduce_xplane as rx


def flash_ops(ctx):
    """[(op, is_forward)] of device 0's flash calls inside the slice."""
    builder = harness.load_builder(ctx["config"]["family"])
    if not hasattr(builder, "flash_shape") or ctx["trace"] is None \
            or ctx["lo"] is None:
        return None
    b, h, s, d = builder.flash_shape(ctx["config"], ctx["cell"])
    operand = f"bf16[{b * h},{s},{d}]"
    lse = f"f32[{b * h},1,{s}]"
    out = []
    for op in ctx["trace"].devices[0].ops:
        if ctx["lo"] <= op.t0 < ctx["hi"] and op.is_mosaic \
                and operand in op.text:
            result = op.text.split(" custom-call(", 1)[0]
            out.append((op, lse in result))     # only forward RETURNS the lse
    return out


def read(ctx):
    ops = flash_ops(ctx)
    if not ops:
        return None
    dev = ctx["trace"].devices[0]
    busy = rx.total(rx.busy_intervals(dev, ctx["lo"], ctx["hi"]))
    return 100.0 * sum(op.dur for op, _ in ops) / busy if busy else None
