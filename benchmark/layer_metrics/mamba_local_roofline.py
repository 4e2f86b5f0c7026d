"""kernels: the Mamba-2 mixers' local part's share of its roofline: the
least time the chip could take for one step's local parts (every ``M``
block; the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s,
forward + backward, no recomputation, from shapes: ``local_cost``) over the
device time a step of the layer ``mamba_local`` in the step's partition
(all passes, so what block remat runs again is in the time and not in the
cost). Bound by BYTES: forward the convolution reads the ``conv_dim``
columns of the in-projection's output and writes x, B and C, and the gate
and norm read y, x and z and write one ``d_inner``-wide array; backward the
gate and norm read four such arrays and write three, and the convolution
reads its input and the cotangent of its output and writes its input's:
151,552 bytes a token a layer in bf16 at the published widths against
0.0005 MFLOP. Reckoned from shapes and selected by scope, so it reads the
same work whatever implements it (XLA's fusions before PR 43, the
``mamba_local_*`` Mosaic calls since)."""
LAYER, UNIT = "kernels", "%"

from benchmark import step_partition


def local_cost(cfg, tokens, bytes_per_el=2):
    """(FLOPs, bytes) the local part of ONE Mamba-2 mixer needs for
    ``tokens`` tokens, forward + backward, no recomputation. Elements a
    token: forward 2 ``conv_dim`` (the convolution's input read, x, B and C
    written) + 4 ``d_inner`` (y, x and z read, the output written);
    backward 3 ``conv_dim`` (the input and the output's cotangent read, the
    input's written) + 7 ``d_inner`` (y, x, z and the output's cotangent
    read; dy, dz and the skip's dx written). The (tokens, heads) step sizes
    and the parameters' own bytes are left out (under 1%). FLOPs, a token:
    k multiply-adds, the bias and SiLU (4) a convolution channel, and the
    skip, gate, square, mean, scale and weight (12) an inner channel,
    forward; the backward twice that."""
    d_inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv_dim = d_inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    k = cfg["conv_kernel"]
    flops = 3 * tokens * (conv_dim * (2 * k + 5) + d_inner * 12)
    bytes_ = tokens * (5 * conv_dim + 11 * d_inner) * bytes_per_el
    return flops, bytes_


def cost(cfg, tokens):
    """(FLOPs, bytes) of one step's local parts: ``local_cost`` an ``M``
    block."""
    blocks = cfg["hybrid_override_pattern"].count("M")
    f, b = local_cost(cfg, tokens)
    return blocks * f, blocks * b


def read(ctx):
    cell, cfg = ctx["cell"], ctx["config"]
    if "mamba_num_heads" not in cfg or "seq_len" not in cell \
            or not ctx["peaks"]:
        return None
    table = step_partition.rows(ctx)
    seconds = sum(sec for (layer, _), sec in (table or {}).items()
                  if layer == "mamba_local")
    if not seconds:
        return None
    need_f, need_b = cost(cfg, cell["batch_size"] * cell["seq_len"])
    least = max(need_f / ctx["peaks"]["bf16_flops_per_s"],
                need_b / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
