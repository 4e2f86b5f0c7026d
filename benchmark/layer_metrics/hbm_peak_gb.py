"""device: the run's ``memory_peak_bytes`` (``harness.memory_peak_bytes``):
the allocator's bytes in use on the fullest of the cell's devices, sampled
INSIDE the window, plus the temporaries of the largest program the system
compiled. What the system holds, not the benchmark's reference check. A
guard: speed bought with memory shows here."""
LAYER, UNIT = "device", "GB"


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return peak / 1e9 if peak else None
