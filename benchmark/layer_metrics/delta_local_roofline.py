"""kernels: the gated delta-rule mixers' local part's share of its roofline:
the least time the chip could take for one step's local parts (every
``linear_attention`` layer; the larger of FLOPs over peak FLOP/s and bytes
over peak bytes/s, forward + backward, no recomputation, from shapes:
``local_cost``) over the device time a step of the layer ``delta_local``
in the step's partition (all passes, so what block remat runs again is in
the time and not in the cost). Bound by BYTES: forward the convolution
reads the ``conv_dim`` columns of the in-projection's output and writes q,
k and v, and the gated norm reads o and z and writes one ``d_value``-wide
array; backward the gated norm reads o, z and the output's cotangent and
writes two, and the convolution reads its input and the cotangents of q, k
and v and writes its input's: 103,680 bytes a token a layer in bf16 at the
15 heads of 96 / 192 a chip holds against 0.0004 MFLOP. Reckoned from
shapes and selected by scope, so it reads the same work whatever
implements it (XLA's fusions over float32 (tokens, conv_dim) before PR 47,
the ``delta_local_*`` Mosaic calls of ``ops/delta_local.py`` since, with
the (tokens, heads)-sized beta and log-decay XLA's beside them)."""
LAYER, UNIT = "kernels", "%"

from benchmark import step_partition


def local_cost(cfg, tokens, bytes_per_el=2):
    """(FLOPs, bytes) the local part of ONE gated delta-rule mixer needs
    for ``tokens`` tokens, forward + backward, no recomputation. Elements a
    token: forward 2 ``conv_dim`` (the convolution's input read, q, k and v
    written) + 3 ``d_value`` (o and z read, the output written); backward
    3 ``conv_dim`` (the input and the cotangents of q, k and v read, the
    input's written) + 5 ``d_value`` (o, z and the output's cotangent read;
    do and dz written). The (tokens, heads) b, a, g and beta and the
    parameters' own bytes are left out (under 1%). FLOPs, a token: k
    multiply-adds and SiLU (4) a convolution channel, the square, sum and
    scale (4) a q or k channel, and the square, mean, scale, weight and
    gate (9) a value channel, forward; the backward twice that."""
    heads = cfg["linear_num_value_heads"]
    d_key = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    d_value = heads * cfg["linear_value_head_dim"]
    conv_dim = 2 * d_key + d_value
    k = cfg["linear_conv_kernel_dim"]
    flops = 3 * tokens * (conv_dim * (2 * k + 4) + 2 * d_key * 4
                          + d_value * 9)
    bytes_ = tokens * (5 * conv_dim + 8 * d_value) * bytes_per_el
    return flops, bytes_


def cost(cfg, tokens):
    """(FLOPs, bytes) of one step's local parts: ``local_cost`` a
    ``linear_attention`` layer."""
    blocks = list(cfg["layer_types"]).count("linear_attention")
    f, b = local_cost(cfg, tokens)
    return blocks * f, blocks * b


def read(ctx):
    cell, cfg = ctx["cell"], ctx["config"]
    if "linear_key_head_dim" not in cfg or "seq_len" not in cell \
            or not ctx["peaks"]:
        return None
    table = step_partition.rows(ctx)
    seconds = sum(sec for (layer, _), sec in (table or {}).items()
                  if layer == "delta_local")
    if not seconds:
        return None
    need_f, need_b = cost(cfg, cell["batch_size"] * cell["seq_len"])
    least = max(need_f / ctx["peaks"]["bf16_flops_per_s"],
                need_b / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
