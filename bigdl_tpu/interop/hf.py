"""HuggingFace-layout checkpoint import for GPT-2- and Llama-family LMs.

The reference's defining interop move is loading a FOREIGN framework's
pretrained weights into its own modules by structural mapping
(``utils/CaffeLoader.scala:132`` ``copyParameters`` name-matches caffemodel
blobs; ``utils/TorchFile.scala:67`` maps ~30 Lua ``nn.*`` classes). This
module replays that move for the LM era: the checkpoints a migrating user
actually holds today are HF ``transformers`` state_dicts, and the two
layouts that cover most of them are GPT-2's (fused Conv1D ``c_attn``,
learned ``wpe`` positions, tied head) and Llama's (split q/k/v with GQA,
RoPE, RMSNorm, gated SwiGLU MLP, no biases).

Both importers are NAME + LAYOUT maps onto ``models.transformer.build_lm``:

GPT-2 (``GPT2LMHeadModel``): HF stores every projection as ``Conv1D`` —
weight (in, out), the TRANSPOSE of torch/our Linear (out, in) — so each
``c_attn``/``c_proj``/``c_fc`` weight transposes on the way in; the fused
``c_attn`` columns are already q;k;v-stacked, which after transposition is
exactly our ``in_proj_weight`` row stacking.

Llama (``LlamaForCausalLM``): separate ``q_proj``/``k_proj``/``v_proj``
Linears concatenate row-wise into our GQA ``in_proj_weight``
((E + 2*E_kv, E) — the k/v blocks are the GROUPED size, so grouped-query
checkpoints load without expansion); ``gate_proj`` (inside silu) is our
``linear1``, ``up_proj`` our ``linear_gate``, ``down_proj`` our
``linear2``; RoPE pairing is the same rotate-half convention, so q/k need
no permutation (``nn/attention.py:rope_rotate``).

Token ids stay 1-based on our side: the tables are copied verbatim, so our
id ``k`` denotes the same token as HF id ``k-1`` (shift ids by +1 on the
way in, -1 on the way out — ``to_framework_ids``/``to_hf_ids``).

Model output is LOG-probabilities (the framework's LM tail convention),
= ``log_softmax`` of HF logits; perplexity and greedy/beam sampling are
therefore directly comparable (verified to 1e-4 by
``tests/test_hf_interop.py`` against live ``transformers`` torch models).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional, Tuple

import numpy as np

from bigdl_tpu.interop.state_dict import import_lm_state_dict
from bigdl_tpu.nn.module import Module


def to_framework_ids(ids):
    """HF 0-based token ids -> this framework's 1-based ids."""
    return np.asarray(ids) + 1


def to_hf_ids(ids):
    """This framework's 1-based token ids -> HF 0-based ids."""
    return np.asarray(ids) - 1


def _np(v) -> np.ndarray:
    """Materialise a state_dict value (torch tensor / jax / numpy) as fp32
    numpy without importing torch here."""
    if hasattr(v, "detach"):  # torch.Tensor
        v = v.detach().cpu()
        if hasattr(v, "float"):
            v = v.float()
        v = v.numpy()
    return np.asarray(v, np.float32)


# --------------------------------------------------------------------- GPT-2

def gpt2_lm_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """``build_lm`` kwargs for an HF GPT-2 ``config.json`` dict."""
    e = int(config["n_embd"])
    n_inner = config.get("n_inner") or 4 * e
    act = config.get("activation_function", "gelu_new")
    if act not in ("gelu_new", "gelu_pytorch_tanh", "gelu"):
        raise ValueError(f"unsupported GPT-2 activation {act!r}")
    # math-changing attention variants: refuse, don't corrupt (same policy
    # as the Llama rope_scaling/sliding_window guards below)
    if config.get("scale_attn_by_inverse_layer_idx", False):
        raise ValueError("scale_attn_by_inverse_layer_idx=True divides "
                         "attention scores per layer; not mapped")
    if not config.get("scale_attn_weights", True):
        raise ValueError("scale_attn_weights=False (unscaled attention) "
                         "is not mapped")
    # "gelu" is the exact erf form; gelu_new/gelu_pytorch_tanh the tanh
    # approximation (~1e-3 apart) — map each to its own kernel instead of
    # silently substituting
    return dict(
        vocab_size=int(config["vocab_size"]),
        embed_dim=e,
        num_heads=int(config["n_head"]),
        ffn_dim=int(n_inner),
        num_layers=int(config["n_layer"]),
        max_len=int(config.get("n_positions", 1024)),
        pos="learned",
        tie_embeddings=True,
        activation="gelu_exact" if act == "gelu" else "gelu",
        norm="layer",
        norm_eps=float(config.get("layer_norm_epsilon", 1e-5)),
    )


def gpt2_state_dict_to_lm(hf_sd: Dict[str, Any],
                          num_layers: int) -> Dict[str, np.ndarray]:
    """HF GPT-2 state_dict -> our torch-convention LM state_dict.

    Accepts ``GPT2LMHeadModel`` keys (``transformer.``-prefixed) or bare
    ``GPT2Model`` keys. Ignores the non-weight buffers HF carries
    (``attn.bias`` causal mask, ``attn.masked_bias``) and the tied
    ``lm_head.weight`` duplicate.
    """
    sd = {}
    for k, v in hf_sd.items():
        if k.startswith("transformer."):
            k = k[len("transformer."):]
        sd[k] = v
    out: Dict[str, np.ndarray] = {
        "embedding.weight": _np(sd["wte.weight"]),
        "pos_embedding.weight": _np(sd["wpe.weight"]),
        "encoder.norm.weight": _np(sd["ln_f.weight"]),
        "encoder.norm.bias": _np(sd["ln_f.bias"]),
    }
    for i in range(num_layers):
        src, dst = f"h.{i}", f"encoder.layers.{i}"
        out[f"{dst}.norm1.weight"] = _np(sd[f"{src}.ln_1.weight"])
        out[f"{dst}.norm1.bias"] = _np(sd[f"{src}.ln_1.bias"])
        out[f"{dst}.norm2.weight"] = _np(sd[f"{src}.ln_2.weight"])
        out[f"{dst}.norm2.bias"] = _np(sd[f"{src}.ln_2.bias"])
        # Conv1D (in, out) -> Linear (out, in): transpose
        out[f"{dst}.self_attn.in_proj_weight"] = \
            _np(sd[f"{src}.attn.c_attn.weight"]).T.copy()
        out[f"{dst}.self_attn.in_proj_bias"] = \
            _np(sd[f"{src}.attn.c_attn.bias"])
        out[f"{dst}.self_attn.out_proj.weight"] = \
            _np(sd[f"{src}.attn.c_proj.weight"]).T.copy()
        out[f"{dst}.self_attn.out_proj.bias"] = \
            _np(sd[f"{src}.attn.c_proj.bias"])
        out[f"{dst}.linear1.weight"] = _np(sd[f"{src}.mlp.c_fc.weight"]).T.copy()
        out[f"{dst}.linear1.bias"] = _np(sd[f"{src}.mlp.c_fc.bias"])
        out[f"{dst}.linear2.weight"] = _np(sd[f"{src}.mlp.c_proj.weight"]).T.copy()
        out[f"{dst}.linear2.bias"] = _np(sd[f"{src}.mlp.c_proj.bias"])
    return out


def load_gpt2(config: Dict[str, Any], state_dict: Dict[str, Any]) -> Module:
    """Build a ``build_lm`` model from an HF GPT-2 config + state_dict."""
    from bigdl_tpu.models.transformer import build_lm
    kwargs = gpt2_lm_kwargs(config)
    model = build_lm(**kwargs)
    ours = gpt2_state_dict_to_lm(state_dict, kwargs["num_layers"])
    return import_lm_state_dict(model, ours, strict=True)


# --------------------------------------------------------------------- Llama

def llama_lm_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """``build_lm`` kwargs for an HF Llama-family ``config.json`` dict."""
    if config.get("attention_bias", False) or config.get("mlp_bias", False):
        raise ValueError("biased Llama variants are not mapped (set "
                         "attention_bias/mlp_bias False)")
    act = config.get("hidden_act", "silu")
    if act != "silu":
        raise ValueError(f"unsupported Llama activation {act!r}")
    scaling = config.get("rope_scaling")
    rope_scaling = None
    if scaling:
        rt = scaling.get("rope_type", scaling.get("type"))
        if rt in ("llama3", "linear", "yarn"):
            # implemented frequency rescalings (nn.attention
            # .scale_rope_freqs, each parity-tested against transformers)
            rope_scaling = dict(scaling)
        elif rt != "default":
            # the rest (dynamic NTK, longrope) would silently change every
            # attention score if ignored — refuse, don't corrupt
            raise ValueError(f"rope_scaling {scaling!r} is not supported "
                             "yet (plain/llama3/linear/yarn frequencies)")
    window = config.get("sliding_window")
    heads = int(config["num_attention_heads"])
    return dict(
        # Mistral-style sliding window maps to banded causal attention
        # (query i sees keys (i - window, i]); None = global
        window=int(window) if window else None,
        rope_scaling=rope_scaling,
        vocab_size=int(config["vocab_size"]),
        embed_dim=int(config["hidden_size"]),
        num_heads=heads,
        num_kv_heads=int(config.get("num_key_value_heads", heads)),
        ffn_dim=int(config["intermediate_size"]),
        num_layers=int(config["num_hidden_layers"]),
        max_len=int(config.get("max_position_embeddings", 2048)),
        rope=True,
        rope_theta=float(config.get("rope_theta", 10000.0)),
        activation="swiglu",
        norm="rms",
        norm_eps=float(config.get("rms_norm_eps", 1e-6)),
        bias=False,
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
    )


def llama_state_dict_to_lm(hf_sd: Dict[str, Any],
                           num_layers: int) -> Dict[str, np.ndarray]:
    """HF Llama state_dict -> our torch-convention LM state_dict.

    The q/k/v Linears concatenate row-wise into the GQA ``in_proj_weight``
    ((E + 2*E_kv, E)); everything else is a rename (torch Linear layout on
    both sides). ``rotary_emb.inv_freq`` buffers are ignored.
    """
    sd = dict(hf_sd)
    out: Dict[str, np.ndarray] = {
        "embedding.weight": _np(sd["model.embed_tokens.weight"]),
        "encoder.norm.weight": _np(sd["model.norm.weight"]),
    }
    if "lm_head.weight" in sd:
        out["lm_head.weight"] = _np(sd["lm_head.weight"])
    for i in range(num_layers):
        src, dst = f"model.layers.{i}", f"encoder.layers.{i}"
        out[f"{dst}.norm1.weight"] = _np(sd[f"{src}.input_layernorm.weight"])
        out[f"{dst}.norm2.weight"] = \
            _np(sd[f"{src}.post_attention_layernorm.weight"])
        out[f"{dst}.self_attn.in_proj_weight"] = np.concatenate([
            _np(sd[f"{src}.self_attn.q_proj.weight"]),
            _np(sd[f"{src}.self_attn.k_proj.weight"]),
            _np(sd[f"{src}.self_attn.v_proj.weight"])], axis=0)
        if f"{src}.self_attn.q_proj.bias" in sd:  # Qwen2's qkv-bias layout
            out[f"{dst}.self_attn.in_proj_bias"] = np.concatenate([
                _np(sd[f"{src}.self_attn.q_proj.bias"]),
                _np(sd[f"{src}.self_attn.k_proj.bias"]),
                _np(sd[f"{src}.self_attn.v_proj.bias"])], axis=0)
        out[f"{dst}.self_attn.out_proj.weight"] = \
            _np(sd[f"{src}.self_attn.o_proj.weight"])
        out[f"{dst}.linear1.weight"] = _np(sd[f"{src}.mlp.gate_proj.weight"])
        out[f"{dst}.linear_gate.weight"] = _np(sd[f"{src}.mlp.up_proj.weight"])
        out[f"{dst}.linear2.weight"] = _np(sd[f"{src}.mlp.down_proj.weight"])
    return out


def load_llama(config: Dict[str, Any], state_dict: Dict[str, Any]) -> Module:
    """Build a ``build_lm`` model from an HF Llama config + state_dict."""
    from bigdl_tpu.models.transformer import build_lm
    kwargs = llama_lm_kwargs(config)
    model = build_lm(**kwargs)
    ours = llama_state_dict_to_lm(state_dict, kwargs["num_layers"])
    # tied checkpoints carry no lm_head.weight; untied must have it
    strict = not kwargs["tie_embeddings"]
    return import_lm_state_dict(model, ours, strict=strict)


# -------------------------------------------------------------------- Qwen2

def qwen2_lm_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """``build_lm`` kwargs for an HF Qwen2 ``config.json`` dict — the
    Llama block with biased q/k/v projections (and only those):
    ``qkv_bias=True`` on our side restores exactly that layout."""
    act = config.get("hidden_act", "silu")
    if act != "silu":
        raise ValueError(f"unsupported Qwen2 activation {act!r}")
    # Qwen2's sliding_window key is inert unless use_sliding_window; when
    # active, transformers applies it only to layers with index >=
    # max_window_layers (so max_window_layers == num_hidden_layers — the
    # shape real Qwen2 configs ship — means NO layer slides). We build
    # homogeneous stacks: all-sliding (0) and none-sliding (== n_layers)
    # map cleanly; a genuine mix is refused rather than corrupted.
    window = None
    if config.get("use_sliding_window", False):
        n_layers = int(config["num_hidden_layers"])
        mwl = int(config.get("max_window_layers", 0))
        if mwl == 0:
            window = int(config["sliding_window"])
        elif mwl >= n_layers:
            window = None  # sliding enabled but applies to no layer
        else:
            raise ValueError("Qwen2 mixed sliding-window layers "
                             "(0 < max_window_layers < num_hidden_layers) "
                             "are not mapped")
    base = dict(config)
    base.pop("sliding_window", None)  # handled above (llama semantics differ)
    kwargs = llama_lm_kwargs(base)
    kwargs["window"] = window
    kwargs["qkv_bias"] = True
    return kwargs


def load_qwen2(config: Dict[str, Any], state_dict: Dict[str, Any]) -> Module:
    """Build a ``build_lm`` model from an HF Qwen2 config + state_dict
    (same tensor names as Llama plus q/k/v biases)."""
    from bigdl_tpu.models.transformer import build_lm
    kwargs = qwen2_lm_kwargs(config)
    model = build_lm(**kwargs)
    ours = llama_state_dict_to_lm(state_dict, kwargs["num_layers"])
    strict = not kwargs["tie_embeddings"]
    return import_lm_state_dict(model, ours, strict=strict)


# -------------------------------------------------------------- Nemotron-H

def nemotron_h_lm_kwargs(config: Dict[str, Any],
                         held_experts=None) -> Dict[str, Any]:
    """``models.hybrid.build_hybrid_lm`` kwargs for an HF ``nemotron_h``
    ``config.json`` dict: the per-layer ``hybrid_override_pattern`` of
    Mamba-2 (``M``), mixture-of-experts (``E``) and attention (``*``)
    blocks, each one mixer under a pre-norm residual.

    ``held_experts`` lists the routed experts that live on this chip of an
    expert-parallel deployment (default: all ``n_routed_experts``, which is
    always the router's width); ``vocab_size`` may be a slice.

    Read from the config but not applied, as in the public modelling code:
    ``rope_theta`` / ``partial_rotary_factor`` (the family's attention
    layers take no positional term). Refused rather than guessed: expert
    groups with a group limit (``n_group`` > 1), a ``-`` (dense MLP)
    block, a sliding window, a head tied to the embedding, a bias on the
    Mamba projections or none on their convolution (no published
    configuration of the family has any of the three)."""
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != int(config["num_hidden_layers"]):
        raise ValueError(
            f"hybrid_override_pattern has {len(pattern)} blocks, "
            f"num_hidden_layers says {config['num_hidden_layers']}")
    if set(pattern) - set("ME*"):
        raise ValueError(f"unmapped block kinds in pattern {pattern!r} "
                         "(M, E and * are)")
    if int(config.get("n_group", 1)) != 1 \
            or int(config.get("topk_group", 1)) != 1:
        raise ValueError("group-limited expert routing (n_group > 1) is "
                         "not mapped")
    if config.get("sliding_window"):
        raise ValueError("nemotron_h sliding-window attention is not mapped")
    acts = {"relu2": "relu2", "relu": "relu", "gelu": "gelu"}
    act = config.get("mlp_hidden_act", "relu2")
    if act not in acts or config.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError(f"unsupported nemotron_h activations {act!r} / "
                         f"{config.get('mamba_hidden_act')!r}")
    if not config.get("norm_topk_prob", True):
        raise ValueError("norm_topk_prob=false is not mapped")
    if config.get("mamba_proj_bias", config.get("use_bias", False)) \
            or not config.get("use_conv_bias", True):
        raise ValueError("a bias on the Mamba projections, or none on the "
                         "convolution, is not mapped")
    if config.get("tie_word_embeddings", False):
        raise ValueError("a head tied to the embedding is not mapped")
    eps = float(config.get("layer_norm_epsilon", 1e-5))
    kwargs = dict(
        vocab_size=int(config["vocab_size"]),
        embed_dim=int(config["hidden_size"]),
        pattern=pattern, norm_eps=eps)
    if "M" in pattern:
        kwargs["mamba"] = dict(
            num_heads=int(config["mamba_num_heads"]),
            head_dim=int(config["mamba_head_dim"]),
            state_size=int(config["ssm_state_size"]),
            n_groups=int(config["n_groups"]),
            conv_kernel=int(config["conv_kernel"]),
            chunk_size=int(config.get("chunk_size", 128)), norm_eps=eps,
            dt_min=float(config.get("time_step_min", 1e-3)),
            dt_max=float(config.get("time_step_max", 0.1)),
            dt_floor=float(config.get("time_step_floor", 1e-4)))
    if "E" in pattern:
        if int(config.get("n_shared_experts", 1)) not in (0, 1):
            raise ValueError("more than one shared expert is not mapped")
        kwargs["moe"] = dict(
            hidden_size=int(config["moe_intermediate_size"]),
            n_experts=int(config["n_routed_experts"]),
            k=int(config["num_experts_per_tok"]),
            activation=acts[act], dispatch="held",
            held=None if held_experts is None else tuple(held_experts),
            bias=bool(config.get("mlp_bias", False)),
            shared_hidden=int(config["moe_shared_expert_intermediate_size"])
            * int(config.get("n_shared_experts", 1)),
            route_scale=float(config.get("routed_scaling_factor", 1.0)))
    if "*" in pattern:
        kwargs["attention"] = dict(
            num_heads=int(config["num_attention_heads"]),
            num_kv_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            with_bias=bool(config.get("attention_bias", False)))
    return kwargs


def afmoe_pattern(layer_types, num_dense_layers: int) -> str:
    """The ``HybridDecoder`` pattern of an ``afmoe`` stack: each layer is
    an attention block (``W`` sliding window, ``*`` full) and a
    feed-forward block (``-`` dense in the leading ``num_dense_layers``,
    ``E`` experts after them)."""
    kinds = {"sliding_attention": "W", "full_attention": "*"}
    bad = set(layer_types) - set(kinds)
    if bad:
        raise ValueError(f"unmapped layer_types {sorted(bad)} "
                         f"({sorted(kinds)} are)")
    return "".join(kinds[t] + ("-" if i < num_dense_layers else "E")
                   for i, t in enumerate(layer_types))


def afmoe_lm_kwargs(config: Dict[str, Any], held_experts=None,
                    train_router: bool = True) -> Dict[str, Any]:
    """``models.hybrid.build_hybrid_lm`` kwargs for an HF ``afmoe``
    ``config.json`` dict (Arcee Trinity): per ``layer_types`` a
    sliding-window attention layer that rotates q and k or a full one that
    does not, both with RMSNorm on each head of q and k and a sigmoid
    output gate; a dense SwiGLU feed-forward in the leading
    ``num_dense_layers`` and sigmoid-routed SwiGLU experts with one shared
    expert after them; a norm before AND after every mixer; the embedding
    scaled by ``sqrt(hidden_size)`` under ``mup_enabled``.

    ``held_experts`` lists the routed experts that live on this chip of an
    expert-parallel deployment (default: all ``num_experts``, which is
    always the router's width); ``vocab_size`` may be a slice.
    ``train_router=False`` is ``MoE(train_router=False)`` in every expert
    layer: for such a share trained with no exchange, whose part of the
    router's gradient would pull the picks onto the held experts.

    Not keys of the config but of the family's public model class, and so
    fixed here: the q/k norm, the output gate, the four norms a layer, no
    rotation on full layers, the embedding's multiplier. Refused rather
    than guessed: expert groups with a group limit, a softmax router,
    ``route_norm`` false, rope scaling, a tied head, more than one shared
    expert, an activation other than silu."""
    types = list(config["layer_types"])
    if len(types) != int(config["num_hidden_layers"]):
        raise ValueError(f"layer_types has {len(types)} entries, "
                         f"num_hidden_layers says "
                         f"{config['num_hidden_layers']}")
    if int(config.get("n_group", 1)) != 1 \
            or int(config.get("topk_group", 1)) != 1:
        raise ValueError("group-limited expert routing (n_group > 1) is "
                         "not mapped")
    if config.get("score_func", "sigmoid") != "sigmoid" \
            or not config.get("route_norm", True):
        raise ValueError("afmoe routing other than sigmoid scores with "
                         "route_norm is not mapped")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError(f"unsupported afmoe activation "
                         f"{config.get('hidden_act')!r}")
    if config.get("rope_scaling"):
        raise ValueError("afmoe rope_scaling is not mapped")
    if config.get("tie_word_embeddings", False):
        raise ValueError("a head tied to the embedding is not mapped")
    if int(config.get("num_shared_experts", 1)) not in (0, 1):
        raise ValueError("more than one shared expert is not mapped")
    dense = int(config.get("num_dense_layers", 0))
    pattern = afmoe_pattern(types, dense)
    eps = float(config.get("rms_norm_eps", 1e-5))
    e = int(config["hidden_size"])
    full = dict(num_heads=int(config["num_attention_heads"]),
                num_kv_heads=int(config["num_key_value_heads"]),
                head_dim=int(config["head_dim"]), with_bias=False,
                qk_norm=True, qk_norm_eps=eps, gated=True)
    kwargs = dict(vocab_size=int(config["vocab_size"]), embed_dim=e,
                  pattern=pattern, norm_eps=eps, post_norm=True,
                  embed_scale=e ** 0.5 if config.get("mup_enabled", False)
                  else None)
    if "*" in pattern:
        kwargs["attention"] = full
    if "W" in pattern:
        kwargs["window_attention"] = dict(
            full, rope=True, rope_theta=float(config.get("rope_theta", 1e4)),
            window=int(config["sliding_window"]))
    if "-" in pattern:
        kwargs["mlp"] = dict(hidden_size=int(config["intermediate_size"]))
    if "E" in pattern:
        width = int(config["moe_intermediate_size"])
        kwargs["moe"] = dict(
            hidden_size=width, n_experts=int(config["num_experts"]),
            k=int(config["num_experts_per_tok"]), activation="swiglu",
            dispatch="held",
            held=None if held_experts is None else tuple(held_experts),
            bias=False,
            shared_hidden=width * int(config.get("num_shared_experts", 1)),
            route_scale=float(config.get("route_scale", 1.0)),
            train_router=train_router)
    return kwargs


def joyai_llm_flash_lm_kwargs(config: Dict[str, Any], held_experts=None,
                             train_router: bool = True,
                             mtp_loss_weight: float = 0.3,
                             picks_by_token: bool = False) -> Dict[str, Any]:
    """``models.hybrid.build_hybrid_lm`` kwargs for an HF
    ``joyai_llm_flash`` ``config.json`` dict (JoyAI-LLM-Flash; every key is
    DeepSeek-V3's): latent attention in every layer (``q_lora_rank``,
    ``kv_lora_rank``, the three head sizes, rotation of the rotary parts
    at ``rope_theta``), a dense SwiGLU feed-forward in the leading
    ``first_k_dense_replace`` layers and after them sigmoid-routed SwiGLU
    experts (``noaux_tc``: top ``num_experts_per_tok`` of score + bias,
    the picked scores renormalised and scaled by
    ``routed_scaling_factor``) beside one shared expert; one norm before
    every mixer; ``num_nextn_predict_layers`` multi-token-prediction
    modules (0 or 1) over the shared embedding and head. The pattern is
    ``L-`` for a dense layer and ``LE`` for an expert layer.

    ``held_experts`` lists the routed experts that live on this chip of an
    expert-parallel deployment (default: all ``n_routed_experts``, which
    is always the router's width); ``vocab_size`` may be a slice.
    ``train_router=False`` is ``MoE(train_router=False)`` in every expert
    layer, as ``afmoe_lm_kwargs``. ``mtp_loss_weight`` is the prediction
    module's loss weight: a training setting, no key of the config (0.3,
    the DeepSeek-V3 report's early value). ``picks_by_token`` is
    ``MoE(pick_rows=vocab_size)`` in every expert layer: each token's
    experts from a table by its id, for the builder to fill.

    ``rope_interleave`` says how a CHECKPOINT's rotary columns are ordered
    (pairs side by side); here they are paired by halves, which seeded
    weights cannot tell apart and an import would permute. Refused rather
    than guessed: a group-limited router, scores other than sigmoid,
    ``norm_topk_prob`` false, rope scaling, a plain q projection
    (``q_lora_rank`` null), a tied head, more than one shared expert,
    expert layers that alternate with dense ones (``moe_layer_freq``), an
    activation other than silu, a biased attention, more than one
    prediction module."""
    if int(config.get("n_group", 1)) != 1 \
            or int(config.get("topk_group", 1)) != 1:
        raise ValueError("group-limited expert routing (n_group > 1) is "
                         "not mapped")
    if config.get("scoring_func", "sigmoid") != "sigmoid" \
            or not config.get("norm_topk_prob", True):
        raise ValueError("routing other than sigmoid scores with "
                         "norm_topk_prob is not mapped")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError(f"unsupported activation "
                         f"{config.get('hidden_act')!r}")
    if config.get("rope_scaling"):
        raise ValueError("rope_scaling is not mapped for latent attention")
    if config.get("q_lora_rank") is None:
        raise ValueError("a plain q projection (q_lora_rank null) is not "
                         "mapped")
    if config.get("tie_word_embeddings", False):
        raise ValueError("a head tied to the embedding is not mapped")
    if int(config.get("n_shared_experts", 1)) not in (0, 1):
        raise ValueError("more than one shared expert is not mapped")
    if int(config.get("moe_layer_freq", 1)) != 1:
        raise ValueError("moe_layer_freq other than 1 is not mapped")
    if config.get("attention_bias", False):
        raise ValueError("a biased latent attention is not mapped")
    modules = int(config.get("num_nextn_predict_layers", 0))
    if modules not in (0, 1):
        raise ValueError("more than one multi-token-prediction module is "
                         "not mapped")
    layers = int(config["num_hidden_layers"])
    dense = min(int(config.get("first_k_dense_replace", 0)), layers)
    pattern = "L-" * dense + "LE" * (layers - dense)
    if modules and not pattern.endswith("LE"):
        raise ValueError("a prediction module over a stack with no expert "
                         "layer is not mapped")
    eps = float(config.get("rms_norm_eps", 1e-6))
    width = int(config["moe_intermediate_size"])
    kwargs = dict(
        vocab_size=int(config["vocab_size"]),
        embed_dim=int(config["hidden_size"]), pattern=pattern, norm_eps=eps,
        latent_attention=dict(
            num_heads=int(config["num_attention_heads"]),
            q_lora_rank=int(config["q_lora_rank"]),
            kv_lora_rank=int(config["kv_lora_rank"]),
            qk_nope_head_dim=int(config["qk_nope_head_dim"]),
            qk_rope_head_dim=int(config["qk_rope_head_dim"]),
            v_head_dim=int(config["v_head_dim"]),
            rope_theta=float(config.get("rope_theta", 1e4)), norm_eps=eps))
    if dense:
        kwargs["mlp"] = dict(hidden_size=int(config["intermediate_size"]))
    if layers > dense:
        kwargs["moe"] = dict(
            hidden_size=width, n_experts=int(config["n_routed_experts"]),
            k=int(config["num_experts_per_tok"]), activation="swiglu",
            dispatch="held",
            held=None if held_experts is None else tuple(held_experts),
            bias=False,
            shared_hidden=width * int(config.get("n_shared_experts", 1)),
            route_scale=float(config.get("routed_scaling_factor", 1.0)),
            train_router=train_router,
            pick_rows=int(config["vocab_size"]) if picks_by_token else 0)
    if modules:
        kwargs["mtp"] = dict(loss_weight=float(mtp_loss_weight))
    return kwargs


def smallthinker_pattern(rope_layout, sliding_window_layout) -> str:
    """The ``HybridDecoder`` pattern of a SmallThinker stack: each layer is
    an attention block (``W`` where ``sliding_window_layout`` is 1, ``*``
    where it is 0) and an expert block whose router reads the LAYER'S
    input, the stream that entered the attention block (``R``). The two
    attention groups each rotate or do not: a window group or a full group
    that mixes rotated and unrotated layers is refused."""
    rope, window = list(rope_layout), list(sliding_window_layout)
    if len(rope) != len(window):
        raise ValueError(f"rope_layout has {len(rope)} entries, "
                         f"sliding_window_layout {len(window)}")
    bad = (set(rope) | set(window)) - {0, 1}
    if bad:
        raise ValueError(f"layout entries are 0 or 1, got {sorted(bad)}")
    for w in (0, 1):
        if len({r for r, x in zip(rope, window) if x == w}) > 1:
            raise ValueError(
                f"the layers with sliding_window_layout {w} mix rope_layout "
                f"0 and 1: a third attention group is not mapped")
    return "".join(("W" if w else "*") + "R" for w in window)


#: the ``moe_*`` keys ``smallthinker_lm_kwargs`` reads
_SMALLTHINKER_MOE_KEYS = ("moe_ffn_hidden_size",
                          "moe_num_active_primary_experts",
                          "moe_num_primary_experts",
                          "moe_primary_router_apply_softmax")


def smallthinker_lm_kwargs(config: Dict[str, Any], held_experts=None,
                           train_router: bool = True,
                           picks_by_token: bool = False) -> Dict[str, Any]:
    """``models.hybrid.build_hybrid_lm`` kwargs for a SmallThinker
    ``config.json`` dict (PowerInfer SmallThinker-21BA3B / 4BA0.6B): per
    layer a GQA attention block, full and NOT rotated where
    ``sliding_window_layout`` / ``rope_layout`` are 0, a window of
    ``sliding_window_size`` and rotated at ``rope_theta`` where they are 1,
    without bias, q/k norm or gate; then ``moe_num_primary_experts`` ReGLU
    experts of width ``moe_ffn_hidden_size``, the top
    ``moe_num_active_primary_experts`` of a router that reads the LAYER'S
    INPUT (the stream ahead of the attention, not normed) with a softmax
    over the picked logits as weights
    (``moe_primary_router_apply_softmax`` with ``norm_topk_prob``); no
    shared expert, no selection bias, no scale; one norm before each
    mixer; an untied head.

    ``held_experts`` lists the routed experts that live on this chip of an
    expert-parallel deployment (default: all, which is always the
    router's width); ``vocab_size`` may be a slice. ``train_router=False``
    is ``MoE(train_router=False)`` in every expert layer, as
    ``afmoe_lm_kwargs``; ``picks_by_token`` is ``MoE(pick_rows=vocab_size)``
    in every expert layer, as ``joyai_llm_flash_lm_kwargs``: each token's
    experts from a table by its id, for the builder to fill (the weights
    stay the softmax over the live logits of those picks, read from the
    layer's input).

    Refused rather than guessed, each by its name: a router without the
    softmax (``moe_primary_router_apply_softmax`` false: sigmoid scores),
    ``norm_topk_prob`` false, any other ``moe_*`` key that is set
    (secondary experts, shared experts), rope scaling, a tied head, a
    biased attention."""
    if not config.get("moe_primary_router_apply_softmax", False):
        raise ValueError("moe_primary_router_apply_softmax false (sigmoid "
                         "scores on the primary router) is not mapped")
    if not config.get("norm_topk_prob", True):
        raise ValueError("norm_topk_prob false (a softmax over all the "
                         "router's outputs, not renormalised over the "
                         "picks) is not mapped")
    unknown = sorted(k for k, v in config.items() if k.startswith("moe_")
                     and k not in _SMALLTHINKER_MOE_KEYS and v)
    if unknown:
        raise ValueError(f"unmapped SmallThinker keys {unknown}")
    if config.get("rope_scaling"):
        raise ValueError("SmallThinker rope_scaling is not mapped")
    if config.get("tie_word_embeddings", False):
        raise ValueError("a head tied to the embedding is not mapped")
    if config.get("attention_bias", False):
        raise ValueError("a biased attention is not mapped")
    pattern = smallthinker_pattern(config["rope_layout"],
                                   config["sliding_window_layout"])
    if len(pattern) != 2 * int(config["num_hidden_layers"]):
        raise ValueError(f"rope_layout has {len(pattern) // 2} entries, "
                         f"num_hidden_layers says "
                         f"{config['num_hidden_layers']}")
    theta = float(config.get("rope_theta", 1e4))

    def group(windowed):
        # uniform within a group: smallthinker_pattern refused a mix
        rope = any(r for r, w in zip(config["rope_layout"],
                                     config["sliding_window_layout"])
                   if bool(w) == windowed)
        return dict(num_heads=int(config["num_attention_heads"]),
                    num_kv_heads=int(config["num_key_value_heads"]),
                    head_dim=int(config["head_dim"]), with_bias=False,
                    rope=rope, rope_theta=theta,
                    window=int(config["sliding_window_size"])
                    if windowed else None)

    kwargs = dict(vocab_size=int(config["vocab_size"]),
                  embed_dim=int(config["hidden_size"]), pattern=pattern,
                  norm_eps=float(config.get("rms_norm_eps", 1e-6)))
    if "*" in pattern:
        kwargs["attention"] = group(False)
    if "W" in pattern:
        kwargs["window_attention"] = group(True)
    kwargs["moe"] = dict(
        hidden_size=int(config["moe_ffn_hidden_size"]),
        n_experts=int(config["moe_num_primary_experts"]),
        k=int(config["moe_num_active_primary_experts"]),
        activation="reglu", dispatch="held",
        held=None if held_experts is None else tuple(held_experts),
        bias=False, score="softmax_picked", train_router=train_router,
        pick_rows=int(config["vocab_size"]) if picks_by_token else 0)
    return kwargs


def lfm2_moe_pattern(layer_types, num_dense_layers: int) -> str:
    """The ``HybridDecoder`` pattern of an ``lfm2_moe`` stack: each layer is
    a mixer block (``C`` the gated short convolution, ``*`` full attention)
    and a feed-forward block (``-`` dense in the leading
    ``num_dense_layers``, ``E`` experts after them)."""
    kinds = {"conv": "C", "full_attention": "*"}
    bad = set(layer_types) - set(kinds)
    if bad:
        raise ValueError(f"unmapped layer_types {sorted(bad)} "
                         f"({sorted(kinds)} are)")
    return "".join(kinds[t] + ("-" if i < num_dense_layers else "E")
                   for i, t in enumerate(layer_types))


def lfm2_moe_lm_kwargs(config: Dict[str, Any], held_experts=None,
                       train_router: bool = True,
                       picks_by_token: bool = False) -> Dict[str, Any]:
    """``models.hybrid.build_hybrid_lm`` kwargs for an HF ``lfm2_moe``
    ``config.json`` dict (Liquid AI LFM2-8B-A1B / LFM2-24B-A2B): per
    ``layer_types`` a double-gated short convolution of ``conv_L_cache``
    taps (``nn.ShortConv``) or a full GQA attention layer with RMSNorm on
    each head of q and k and rotation at ``rope_parameters.rope_theta``;
    a dense SwiGLU feed-forward in the leading ``num_dense_layers`` and
    after them sigmoid-routed SwiGLU experts (the top
    ``num_experts_per_tok`` of score + bias, the picked scores over their
    sum + 1e-6, times ``routed_scaling_factor``) with no shared expert; one
    norm before every mixer at ``norm_eps``; the head TIED to the embedding
    unless ``tie_word_embeddings`` says false.

    ``held_experts``, ``train_router`` and ``picks_by_token`` as
    ``joyai_llm_flash_lm_kwargs``; ``vocab_size`` may be a slice.

    Not keys of the config but of the family's public model class, and so
    fixed here: the order ``B, C, x`` of the in-projection's thirds, the q/k
    norm ahead of the rotation, the head's tie. Refused rather than
    guessed, each by its name: ``conv_bias`` true, ``norm_topk_prob``
    false, a sliding window, rope scaling, a ``layer_types`` entry other
    than ``conv`` / ``full_attention``."""
    if config.get("conv_bias", False):
        raise ValueError("conv_bias true (a bias on the short convolution "
                         "and its projections) is not mapped")
    if not config.get("norm_topk_prob", True):
        raise ValueError("norm_topk_prob false (the picked scores not "
                         "renormalised) is not mapped")
    if config.get("sliding_window"):
        raise ValueError("lfm2_moe sliding_window is not mapped")
    rope = dict(config.get("rope_parameters") or {})
    if config.get("rope_scaling") \
            or rope.get("rope_type", "default") != "default":
        raise ValueError("lfm2_moe rope scaling (rope_parameters.rope_type "
                         "other than 'default') is not mapped")
    types = list(config["layer_types"])
    if len(types) != int(config["num_hidden_layers"]):
        raise ValueError(f"layer_types has {len(types)} entries, "
                         f"num_hidden_layers says "
                         f"{config['num_hidden_layers']}")
    dense = int(config.get("num_dense_layers", 0))
    pattern = lfm2_moe_pattern(types, dense)
    eps = float(config.get("norm_eps", 1e-5))
    kwargs = dict(vocab_size=int(config["vocab_size"]),
                  embed_dim=int(config["hidden_size"]), pattern=pattern,
                  norm_eps=eps,
                  tie_embeddings=bool(config.get("tie_word_embeddings",
                                                 True)))
    if "C" in pattern:
        kwargs["short_conv"] = dict(kernel=int(config["conv_L_cache"]))
    if "*" in pattern:
        kwargs["attention"] = dict(
            num_heads=int(config["num_attention_heads"]),
            num_kv_heads=int(config["num_key_value_heads"]),
            with_bias=False, qk_norm=True, qk_norm_eps=eps, rope=True,
            rope_theta=float(rope.get("rope_theta",
                                      config.get("rope_theta", 1e4))))
    if "-" in pattern:
        kwargs["mlp"] = dict(hidden_size=int(config["intermediate_size"]))
    if "E" in pattern:
        kwargs["moe"] = dict(
            hidden_size=int(config["moe_intermediate_size"]),
            n_experts=int(config["num_experts"]),
            k=int(config["num_experts_per_tok"]), activation="swiglu",
            dispatch="held",
            held=None if held_experts is None else tuple(held_experts),
            bias=False,
            route_scale=float(config.get("routed_scaling_factor", 1.0)),
            renorm_eps=1e-6, train_router=train_router,
            pick_rows=int(config["vocab_size"]) if picks_by_token else 0)
    return kwargs


def ouro_lm_kwargs(config: Dict[str, Any],
                   exit_beta: float = 0.1) -> Dict[str, Any]:
    """``models.hybrid.build_hybrid_lm`` kwargs for an HF ``ouro``
    ``config.json`` dict (ByteDance Ouro, the looped language model of
    arXiv:2510.25741): ``num_hidden_layers`` layers of a full-attention
    block and a dense SwiGLU block, each normed on its input AND its output
    (four norms a layer), MHA or GQA with rotation at ``rope_theta``, an
    untied head; the whole stack run ``total_ut_steps`` times over the same
    weights with the final norm between passes, and one exit gate
    (``passes``, ``exit_gate``). ``exit_beta`` is the weight of the exit
    distribution's entropy in the training loss (no key of the config: the
    paper's first stage gives 0.05-0.1).

    Not keys of the config but of the family's public model class, and so
    fixed here: the four norms, the gate's bias, no bias elsewhere.
    Refused rather than guessed: a layer type other than full attention, a
    sliding window, rope scaling, a tied head, an activation other than
    silu, an ``early_exit_threshold`` under 1 (a pass left early is a
    serving path that is not built)."""
    types = list(config.get("layer_types")
                 or ["full_attention"] * int(config["num_hidden_layers"]))
    if len(types) != int(config["num_hidden_layers"]):
        raise ValueError(f"layer_types has {len(types)} entries, "
                         f"num_hidden_layers says "
                         f"{config['num_hidden_layers']}")
    if set(types) != {"full_attention"} or config.get("use_sliding_window") \
            or config.get("sliding_window"):
        raise ValueError("ouro layers other than full attention (a "
                         "sliding window) are not mapped")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError(f"unsupported ouro activation "
                         f"{config.get('hidden_act')!r}")
    if config.get("rope_scaling"):
        raise ValueError("ouro rope_scaling is not mapped")
    if config.get("tie_word_embeddings", False):
        raise ValueError("a head tied to the embedding is not mapped")
    if float(config.get("early_exit_threshold", 1.0)) < 1.0:
        raise ValueError("early_exit_threshold under 1 (leaving a pass "
                         "early) is not mapped")
    e, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    return dict(
        vocab_size=int(config["vocab_size"]), embed_dim=e,
        pattern="*-" * len(types),
        attention=dict(num_heads=heads,
                       num_kv_heads=int(config.get("num_key_value_heads",
                                                   heads)),
                       head_dim=int(config.get("head_dim", e // heads)),
                       with_bias=False, rope=True,
                       rope_theta=float(config.get("rope_theta", 1e4))),
        mlp=dict(hidden_size=int(config["intermediate_size"])),
        norm_eps=float(config.get("rms_norm_eps", 1e-6)), post_norm=True,
        passes=int(config["total_ut_steps"]), exit_gate=True,
        exit_beta=float(exit_beta))


# ------------------------------------------------------------------- export

def export_gpt2_state_dict(model: Module) -> Dict[str, np.ndarray]:
    """Inverse of ``gpt2_state_dict_to_lm``: a GPT-2-shaped ``build_lm``
    model (pos="learned", tied embeddings, LayerNorm, biased) exported as
    an HF ``GPT2LMHeadModel`` state_dict (``transformer.``-prefixed
    Conv1D layout) — so models trained here load straight into
    ``transformers``. The reference's interop is likewise bidirectional
    (``utils/TorchFile.scala:67`` saves as well as loads)."""
    from bigdl_tpu.interop.state_dict import export_lm_state_dict
    ours = export_lm_state_dict(model)
    if "pos_embedding.weight" not in ours:
        raise ValueError("GPT-2 export needs build_lm(pos='learned') "
                         "(a trained wpe table)")
    if "lm_head.weight" in ours:
        raise ValueError("GPT-2 export needs tie_embeddings=True "
                         "(GPT-2 checkpoints carry no separate head)")
    out: Dict[str, np.ndarray] = {
        "transformer.wte.weight": ours["embedding.weight"],
        "transformer.wpe.weight": ours["pos_embedding.weight"],
        "transformer.ln_f.weight": ours["encoder.norm.weight"],
        "transformer.ln_f.bias": ours["encoder.norm.bias"],
        "lm_head.weight": ours["embedding.weight"],  # tied duplicate
    }
    n_layers = 1 + max(int(k.split(".")[2]) for k in ours
                       if k.startswith("encoder.layers."))
    for i in range(n_layers):
        src, dst = f"encoder.layers.{i}", f"transformer.h.{i}"
        out[f"{dst}.ln_1.weight"] = ours[f"{src}.norm1.weight"]
        out[f"{dst}.ln_1.bias"] = ours[f"{src}.norm1.bias"]
        out[f"{dst}.ln_2.weight"] = ours[f"{src}.norm2.weight"]
        out[f"{dst}.ln_2.bias"] = ours[f"{src}.norm2.bias"]
        out[f"{dst}.attn.c_attn.weight"] = \
            ours[f"{src}.self_attn.in_proj_weight"].T.copy()
        out[f"{dst}.attn.c_attn.bias"] = ours[f"{src}.self_attn.in_proj_bias"]
        out[f"{dst}.attn.c_proj.weight"] = \
            ours[f"{src}.self_attn.out_proj.weight"].T.copy()
        out[f"{dst}.attn.c_proj.bias"] = ours[f"{src}.self_attn.out_proj.bias"]
        out[f"{dst}.mlp.c_fc.weight"] = ours[f"{src}.linear1.weight"].T.copy()
        out[f"{dst}.mlp.c_fc.bias"] = ours[f"{src}.linear1.bias"]
        out[f"{dst}.mlp.c_proj.weight"] = ours[f"{src}.linear2.weight"].T.copy()
        out[f"{dst}.mlp.c_proj.bias"] = ours[f"{src}.linear2.bias"]
    return out


def export_llama_state_dict(model: Module) -> Dict[str, np.ndarray]:
    """Inverse of ``llama_state_dict_to_lm``: a Llama-shaped ``build_lm``
    model (rope, rms, swiglu, bias-free) exported under HF
    ``LlamaForCausalLM`` names (q/k/v split back out of the GQA
    in_proj stack)."""
    from bigdl_tpu.interop.state_dict import export_lm_state_dict
    from bigdl_tpu.nn.attention import MultiHeadAttention
    ours = export_lm_state_dict(model)
    mhas = [m for m in model.modules()
            if isinstance(m, MultiHeadAttention)]
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": ours["embedding.weight"],
        "model.norm.weight": ours["encoder.norm.weight"],
    }
    if "lm_head.weight" in ours:
        out["lm_head.weight"] = ours["lm_head.weight"]
    n_layers = 1 + max(int(k.split(".")[2]) for k in ours
                       if k.startswith("encoder.layers."))
    for i in range(n_layers):
        src, dst = f"encoder.layers.{i}", f"model.layers.{i}"
        attn = mhas[i]
        e, ekv = attn.embed_dim, attn._e_kv
        w = ours[f"{src}.self_attn.in_proj_weight"]
        out[f"{dst}.self_attn.q_proj.weight"] = w[:e]
        out[f"{dst}.self_attn.k_proj.weight"] = w[e:e + ekv]
        out[f"{dst}.self_attn.v_proj.weight"] = w[e + ekv:]
        out[f"{dst}.self_attn.o_proj.weight"] = \
            ours[f"{src}.self_attn.out_proj.weight"]
        out[f"{dst}.input_layernorm.weight"] = ours[f"{src}.norm1.weight"]
        out[f"{dst}.post_attention_layernorm.weight"] = \
            ours[f"{src}.norm2.weight"]
        out[f"{dst}.mlp.gate_proj.weight"] = ours[f"{src}.linear1.weight"]
        out[f"{dst}.mlp.up_proj.weight"] = ours[f"{src}.linear_gate.weight"]
        out[f"{dst}.mlp.down_proj.weight"] = ours[f"{src}.linear2.weight"]
    return out


def _lm_geometry(model: Module):
    """(embed, encoder, first MHA, head) of a build_lm-shaped model."""
    from bigdl_tpu.interop.state_dict import _lm_parts
    from bigdl_tpu.nn.attention import MultiHeadAttention
    emb, enc, head = _lm_parts(model)
    mha = enc._modules["layer0"].self_attn
    assert isinstance(mha, MultiHeadAttention)
    return emb, enc, mha, head


def save_hf_checkpoint(model: Module, path: str) -> str:
    """Write ``config.json`` + ``model.safetensors`` so ``transformers``
    loads the directory with ``from_pretrained`` — the full inverse of
    ``load_hf_checkpoint``. The flavour is inferred from the model:
    RoPE + RMSNorm + SwiGLU exports as a Llama config, a learned-position
    LayerNorm/gelu stack as GPT-2. Returns the directory path."""
    from safetensors.numpy import save_file
    emb, enc, mha, head = _lm_geometry(model)
    layer0 = enc._modules["layer0"]
    is_llama = getattr(mha, "rope", False)
    act = getattr(layer0, "activation", None)
    # refuse, don't corrupt (the import-side policy, both directions):
    # the exported config hardcodes the family activation
    if is_llama and act != "swiglu":
        raise ValueError(f"Llama-family export needs activation='swiglu' "
                         f"(model has {act!r})")
    if not is_llama and act != "gelu":
        raise ValueError(f"GPT-2 export needs activation='gelu' "
                         f"(= HF gelu_new; model has {act!r})")
    if is_llama and getattr(mha, "qkv_bias", False):
        # Qwen2-shaped model: the llama export has no home for the q/k/v
        # biases and a llama config would silently drop them
        raise ValueError("Qwen2-family export (qkv_bias=True) is not "
                         "implemented; a Llama config cannot carry the "
                         "q/k/v projection biases")
    os.makedirs(path, exist_ok=True)
    if is_llama:
        sd = export_llama_state_dict(model)
        from bigdl_tpu.nn.linear import TiedLMHead
        window = getattr(mha, "window", None)
        config = {
            # a sliding window makes it a Mistral-shaped checkpoint
            "model_type": "mistral" if window else "llama",
            "architectures": ["MistralForCausalLM" if window
                              else "LlamaForCausalLM"],
            **({"sliding_window": int(window)} if window else {}),
            **({"rope_scaling": dict(mha.rope_scaling)}
               if getattr(mha, "rope_scaling", None) else {}),
            "vocab_size": int(emb.n_index),
            "hidden_size": int(mha.embed_dim),
            "intermediate_size": int(layer0.linear1.output_size),
            "num_hidden_layers": int(enc.num_layers),
            "num_attention_heads": int(mha.num_heads),
            "num_key_value_heads": int(mha.num_kv_heads),
            "max_position_embeddings": int(getattr(model, "lm_max_len",
                                                   2048)),
            "rms_norm_eps": float(layer0.norm1.eps),
            "rope_theta": float(getattr(mha, "rope_theta", 10000.0)),
            "hidden_act": "silu",
            "attention_bias": False,
            "mlp_bias": False,
            "tie_word_embeddings": isinstance(head, TiedLMHead),
            "torch_dtype": "float32",
        }
    else:
        sd = export_gpt2_state_dict(model)
        wpe = sd["transformer.wpe.weight"]
        config = {
            "model_type": "gpt2",
            "architectures": ["GPT2LMHeadModel"],
            "vocab_size": int(emb.n_index),
            "n_positions": int(wpe.shape[0]),
            "n_embd": int(mha.embed_dim),
            "n_layer": int(enc.num_layers),
            "n_head": int(mha.num_heads),
            "n_inner": int(layer0.linear1.output_size),
            "activation_function": "gelu_new",
            "layer_norm_epsilon": float(layer0.norm1.eps),
            "tie_word_embeddings": True,
            "torch_dtype": "float32",
        }
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config, f, indent=2)
    save_file({k: np.ascontiguousarray(v, np.float32)
               for k, v in sd.items()},
              os.path.join(path, "model.safetensors"))
    return path


# ------------------------------------------------------------- directory I/O

def _read_safetensors(fname: str) -> Dict[str, np.ndarray]:
    """One safetensors file -> numpy dict. ``safetensors.numpy`` cannot
    represent bfloat16 — the dominant dtype of real Llama/Mistral
    checkpoints — so files containing non-numpy dtypes route through
    ``safetensors.torch`` (``.float()``) with an ``ml_dtypes`` raw-buffer
    fallback when torch is unavailable."""
    import json as _json
    import struct

    with open(fname, "rb") as f:
        (hdr_len,) = struct.unpack("<Q", f.read(8))
        header = _json.loads(f.read(hdr_len))
    numpy_ok = {"F64", "F32", "F16", "I64", "I32", "I16", "I8", "U8", "BOOL"}
    dtypes = {m.get("dtype") for k, m in header.items()
              if k != "__metadata__"}
    if dtypes <= numpy_ok:
        from safetensors.numpy import load_file
        return dict(load_file(fname))
    # wide-dtype path: parse the (trivial) wire format directly — header
    # gives per-tensor dtype/shape/data_offsets into one contiguous buffer
    import ml_dtypes
    wide = {"BF16": ml_dtypes.bfloat16, "F8_E4M3": ml_dtypes.float8_e4m3fn,
            "F8_E5M2": ml_dtypes.float8_e5m2}
    np_map = {"F64": np.float64, "F32": np.float32, "F16": np.float16,
              "I64": np.int64, "I32": np.int32, "I16": np.int16,
              "I8": np.int8, "U8": np.uint8, "BOOL": np.bool_}
    out = {}
    with open(fname, "rb") as f:
        base = 8 + hdr_len
        for k, meta in header.items():
            if k == "__metadata__":
                continue
            dt = meta["dtype"]
            if dt in wide:
                dtype, cast = wide[dt], np.float32
            elif dt in np_map:
                dtype, cast = np_map[dt], None
            else:
                raise ValueError(f"unsupported safetensors dtype {dt!r}")
            start, stop = meta["data_offsets"]
            f.seek(base + start)
            arr = np.frombuffer(f.read(stop - start), dtype=dtype) \
                .reshape(meta["shape"])
            out[k] = arr.astype(cast) if cast is not None else arr
    return out


def _read_hf_weights(path: str) -> Dict[str, np.ndarray]:
    """Read an HF checkpoint directory's weights (safetensors preferred,
    single- or multi-shard; falls back to ``pytorch_model.bin``)."""
    st = [f for f in sorted(os.listdir(path)) if f.endswith(".safetensors")]
    if st:
        out: Dict[str, np.ndarray] = {}
        for f in st:
            out.update(_read_safetensors(os.path.join(path, f)))
        return out
    bins = [f for f in sorted(os.listdir(path)) if f.endswith(".bin")
            and f.startswith("pytorch_model")]
    if bins:
        import torch
        out = {}
        for f in bins:
            out.update(torch.load(os.path.join(path, f),
                                  map_location="cpu", weights_only=True))
        return out
    raise FileNotFoundError(f"no .safetensors or pytorch_model*.bin in {path}")


def load_hf_checkpoint(path: str) -> Module:
    """Load an HF checkpoint DIRECTORY (config.json + weights) into a
    ``build_lm`` model. Dispatches on ``config.json``'s ``model_type``:
    ``gpt2`` or the Llama family (``llama``/``mistral``-shaped configs
    that satisfy ``llama_lm_kwargs``)."""
    with open(os.path.join(path, "config.json")) as f:
        config = json.load(f)
    sd = _read_hf_weights(path)
    mt = config.get("model_type", "")
    if mt == "gpt2":
        return load_gpt2(config, sd)
    if mt in ("llama", "mistral"):
        return load_llama(config, sd)
    if mt == "qwen2":
        return load_qwen2(config, sd)
    raise ValueError(
        f"unsupported model_type {mt!r} (gpt2/llama/mistral/qwen2)")
