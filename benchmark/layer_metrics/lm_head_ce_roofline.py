"""kernels: the fused LM-head cross-entropy's share of its roofline: the
least time the chip could take for one step's head (the larger of FLOPs
over peak FLOP/s and bytes over peak bytes/s, from shapes: ``cost``) over
the device time a step of the operations under the scope ``lm_head_ce``.
Compute-bound at T=4096, D=896, V=151936 (about 3,000 FLOPs a byte)."""
LAYER, UNIT = "kernels", "%"

from benchmark import timeline


def cost(tokens, hidden, vocab, bytes_per_el=2):
    """(FLOPs, bytes) the head's loss needs for one step, forward and
    backward: three (T, D) x (D, V) products (logits, dh, dW), none
    recomputed; h and W read once each way, dh written in the compute
    dtype, dW accumulated and written in float32, the targets read."""
    flops = 6 * tokens * hidden * vocab
    bytes_ = (2 * tokens * hidden * bytes_per_el        # h, twice
              + 2 * vocab * hidden * bytes_per_el       # W, twice
              + tokens * hidden * bytes_per_el          # dh
              + vocab * hidden * 4                      # dW
              + tokens * 4)                             # targets
    return flops, bytes_


def read(ctx):
    found = timeline.scope_of(ctx, "lm_head_ce")
    cell, cfg = ctx["cell"], ctx["config"]
    if found is None or not ctx["peaks"] or "seq_len" not in cell:
        return None
    seconds, runs = found
    need_f, need_b = cost(cell["batch_size"] * cell["seq_len"],
                          cfg["hidden_size"], cfg["vocab_size"])
    least = max(need_f / ctx["peaks"]["bf16_flops_per_s"],
                need_b / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * runs / seconds
