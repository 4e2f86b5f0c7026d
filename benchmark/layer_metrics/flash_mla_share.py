"""kernels: share of the device's busy time in the flash-attention Mosaic
calls whose value head differs from their query/key head (forward, dQ,
dK/dV of the latent-attention layers, the prediction module's among
them), recognised by the name the program gives them: ``flash_mla_fwd``,
``flash_mla_bwd_dq``, ``flash_mla_bwd_dkv`` (``ops/flash_attention.py``; a
``pallas_call``'s ``name`` is its HLO instruction's). A program without
such calls (every commit before PR 34) reads nothing."""
LAYER, UNIT = "kernels", "%"

from benchmark import reduce_xplane as rx

KERNELS = ("flash_mla_fwd", "flash_mla_bwd_dq", "flash_mla_bwd_dkv")


def mla_ops(ctx, kernels=KERNELS):
    """Device 0's latent flash calls inside the slice."""
    if ctx.get("trace") is None or ctx.get("lo") is None:
        return []
    return [op for op in ctx["trace"].devices[0].ops
            if ctx["lo"] <= op.t0 < ctx["hi"] and op.is_mosaic
            and op.name.split(".")[0] in kernels]


def read(ctx):
    ops = mla_ops(ctx)
    if not ops:
        return None
    dev = ctx["trace"].devices[0]
    busy = rx.total(rx.busy_intervals(dev, ctx["lo"], ctx["hi"]))
    return 100.0 * sum(op.dur for op in ops) / busy if busy else None
