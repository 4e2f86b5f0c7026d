"""Operations and bytes from shapes: what the algorithm needs, not what the
compiler emitted. Forward + backward = 3 x forward for every matmul and
convolution (one product forward, two backward); recomputation (flash's
backward recompute, remat) is NOT counted; elementwise work, norms and
softmax are left out (under 1% at these widths). A multiply-add is 2 FLOPs.

Fixed file; ``tests/test_flops.py`` holds the hand counts.
"""

from __future__ import annotations


# --------------------------------------------------------------- Qwen2 / Llama

def decoder_layer_forward_flops(cfg, seq):
    """Forward FLOPs of ONE decoder layer for ONE sequence of ``seq``
    tokens, causal attention counted at half the square."""
    e = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    d = e // h
    f = cfg["intermediate_size"]
    qkv = 2 * seq * e * (h * d + 2 * kv * d)
    out = 2 * seq * (h * d) * e
    attn = 2 * 2 * h * d * seq * seq / 2        # QK^T and PV, causal half
    mlp = 3 * 2 * seq * e * f                   # gate, up, down
    return qkv + out + attn + mlp


def lm_forward_flops(cfg, seq):
    """Forward FLOPs of the whole LM for one sequence, head included (the
    embedding lookup is a gather: no FLOPs)."""
    head = 2 * seq * cfg["hidden_size"] * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * decoder_layer_forward_flops(cfg, seq) \
        + head


def lm_train_flops_per_record(cfg, seq):
    return 3 * lm_forward_flops(cfg, seq)


def flash_forward_cost(batch, heads, seq, head_dim, bytes_per_el=2):
    """(FLOPs, bytes) the causal flash-attention FORWARD needs for one
    call: QK^T and PV over the causal half; q, k, v read once, o written
    once, the fp32 log-sum-exp row written once."""
    flops = 2 * 2 * batch * heads * head_dim * seq * seq / 2
    bytes_ = 4 * batch * heads * seq * head_dim * bytes_per_el \
        + 4 * batch * heads * seq
    return flops, bytes_


# -------------------------------------------------------------------- ResNet

def conv_flops(h_out, w_out, k, c_in, c_out):
    return 2 * h_out * w_out * k * k * c_in * c_out


def bottleneck_forward_flops(hw_in, c_in, mid, stride, expansion=4):
    """One bottleneck block (1x1 -> 3x3/stride -> 1x1, + a 1x1/stride
    projection where the shape changes), for one image."""
    hw_out = hw_in // stride
    c_out = mid * expansion
    total = conv_flops(hw_in, hw_in, 1, c_in, mid)
    total += conv_flops(hw_out, hw_out, 3, mid, mid)
    total += conv_flops(hw_out, hw_out, 1, mid, c_out)
    if stride != 1 or c_in != c_out:
        total += conv_flops(hw_out, hw_out, 1, c_in, c_out)
    return total


def resnet_forward_flops(cfg):
    """Forward FLOPs of the bottleneck ResNet for one image."""
    size = cfg["image_size"]
    stem = cfg["stem"]
    hw = size // stem["stride"]
    total = conv_flops(hw, hw, stem["kernel"], cfg["image_channels"],
                       stem["channels"])
    hw //= 2                                    # the 3x3/2 max-pool
    c_in = stem["channels"]
    exp = cfg["expansion"]
    for stage, (mid, reps) in enumerate(zip(cfg["stage_widths"],
                                            cfg["stage_blocks"])):
        for i in range(reps):
            stride = 2 if (stage > 0 and i == 0) else 1
            total += bottleneck_forward_flops(hw, c_in, mid, stride, exp)
            hw //= stride
            c_in = mid * exp
    return total + 2 * c_in * cfg["num_classes"]


def resnet_train_flops_per_record(cfg):
    return 3 * resnet_forward_flops(cfg)

