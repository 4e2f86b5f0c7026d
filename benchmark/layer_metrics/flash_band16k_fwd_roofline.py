"""kernels: the BANDED flash-attention forward's share of its roofline at
the cell's length (a window of 4,096 over 16,384 keys of head 128 in the
cell that lists it): as ``flash16k_fwd_roofline``, for the calls named
``flash_band_fwd`` at the pairs the band of ``sliding_window_size`` holds
(58.7M a head of the full layer's 134.2M). Every tile such a call meets is
masked and the tiles on the band's two edges are partly empty (9 of 32 key
tiles a query tile at 512-tiles hold 4,608 keys for the 4,096 a query
sees), so at equal kernel quality it reads below the full call's.
``flash_band_fwd_roofline`` reads the window from another family's key
(``sliding_window``) and finds nothing here."""
LAYER, UNIT = "kernels", "%"

from benchmark.layer_metrics.flash16k_fwd_roofline import forward_roofline


def read(ctx):
    return forward_roofline(ctx, "flash_band_fwd", "sliding_window_size")
