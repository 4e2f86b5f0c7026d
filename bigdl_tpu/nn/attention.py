"""Attention layers — new TPU-native capability.

The reference has no attention (SURVEY §5.7 — sequence modelling stops at
``nn/Recurrent.scala``/``nn/LSTM.scala``); long-context is first-class in the
TPU build, so this module adds the transformer stack the reference lacks:
``LayerNorm``, ``MultiHeadAttention``, ``TransformerEncoderLayer``, a
sinusoidal ``PositionalEncoding``, and a stacked ``TransformerEncoder``.

Compute-path notes (TPU-first):
- projections are single MXU matmuls in the module's compute dtype;
- the attention core lives in ``ops/attention_core.py`` (plain XLA or
  flash-style blockwise ``lax.scan``) and in ``ops/flash_attention.py``
  (Pallas kernel, used automatically on TPU for long sequences);
- with a mesh ``seq`` axis, ``parallel/context.py`` runs the same layer
  ring- or Ulysses-sharded — the module code does not change.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.lax import axis_size

from bigdl_tpu.nn import initialization as init
from bigdl_tpu.nn.module import Module, TensorModule
from bigdl_tpu.ops.precision import match_compute
from bigdl_tpu.ops.remat import ATTN_PROJ, keep


class LayerNorm(TensorModule):
    """Per-feature layer normalisation over the last ``len(shape)`` axes.

    Absent from the reference (which predates transformers; nearest is
    ``nn/BatchNormalization.scala:50``) — required by the attention stack.
    """

    def __init__(self, normalized_shape, eps: float = 1e-5,
                 elementwise_affine: bool = True):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.normalized_shape = tuple(normalized_shape)
        self.eps = eps
        self.elementwise_affine = elementwise_affine
        if elementwise_affine:
            self.register_parameter("weight", init.ones(self.normalized_shape))
            self.register_parameter("bias", init.zeros(self.normalized_shape))

    def update_output(self, input):
        axes = tuple(range(input.ndim - len(self.normalized_shape), input.ndim))
        x = input.astype(jnp.float32)
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.eps)
        y = y.astype(input.dtype)
        if self.elementwise_affine:
            y = y * self.weight + self.bias
        return y

    def __repr__(self):
        return f"LayerNorm({self.normalized_shape})"


class RMSNorm(TensorModule):
    """Root-mean-square normalisation (Zhang & Sennrich) — the Llama-family
    replacement for LayerNorm: no mean subtraction, no bias, one gain.
    fp32 statistics like LayerNorm."""

    def __init__(self, dim: int, eps: float = 1e-6):
        super().__init__()
        self.dim, self.eps = dim, eps
        self.register_parameter("weight", init.ones((dim,)))

    def update_output(self, input):
        x = input.astype(jnp.float32)
        y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                       keepdims=True) + self.eps)
        return y.astype(input.dtype) * self.weight

    def __repr__(self):
        return f"RMSNorm({self.dim})"


class MultiHeadAttention(Module):
    """Multi-head attention with fused qkv projection.

    Input (B, S, E) [self-attention], Table {query, key, value}
    [cross-attention], or Table {query, key, value, mask} — the 4th element
    is a boolean mask broadcastable to (B, N, Sq, Sk), True = attend.

    Per-batch masks MUST flow through the input (4-element Table): a mask set
    via ``set_mask`` is module state, which a traced/jitted forward bakes in
    as a compile-time constant — fine for a fixed structural mask, wrong for
    masks that change per batch.

    Weight layout matches Torch's ``nn.MultiheadAttention`` (in_proj stacked
    q;k;v, each (E, E)) so oracle tests and weight import line up.
    """

    # class attributes (not set in __init__) so checkpoints pickled before
    # decode mode existed still forward correctly after load
    _decode = False
    _decode_prefilled = False

    def __init__(self, embed_dim: int, num_heads: int,
                 dropout: float = 0.0, with_bias: bool = True,
                 causal: bool = False, block_size: int = 0,
                 seq_axis: Optional[str] = None, seq_mode: str = "ring",
                 seq_layout: str = "contiguous", rope: bool = False,
                 num_kv_heads: Optional[int] = None,
                 rope_theta: float = 10000.0,
                 window: Optional[int] = None,
                 rope_scaling: Optional[dict] = None,
                 qkv_bias: bool = False,
                 head_dim: Optional[int] = None,
                 qk_norm: bool = False, qk_norm_eps: float = 1e-6,
                 gated: bool = False):
        super().__init__()
        # head_dim: the size of one head where the model sets it apart
        # from the stream's width (the projections are then
        # (num_heads * head_dim) wide, not embed_dim); default, the usual
        # embed_dim // num_heads
        if head_dim is None:
            assert embed_dim % num_heads == 0, \
                "embed_dim must divide num_heads"
            head_dim = embed_dim // num_heads
        # window: sliding-window (banded causal) attention — query i sees
        # keys (i - window, i], the Mistral convention. Requires causal.
        # The flash kernels take the band as an argument and skip the tiles
        # below it; the XLA cores take it as a mask; the context-parallel
        # paths do not implement it.
        if window is not None:
            if not causal:
                raise ValueError("window (sliding-window attention) "
                                 "requires causal=True")
            if seq_axis is not None:
                raise ValueError("sliding-window attention does not "
                                 "compose with context parallelism yet")
            if window < 1:
                raise ValueError("window must be >= 1")
        self.window = window
        # GQA (grouped-query attention): num_kv_heads < num_heads shares
        # each k/v head across num_heads // num_kv_heads query heads — the
        # KV cache (decode's memory hog) shrinks by that factor. The
        # in_proj weight is (E + 2*E_kv, E): torch nn.MultiheadAttention's
        # 3E stacking only when full MHA, and exactly the row-concat of HF
        # Llama's q/k/v projections in general — real grouped-query
        # checkpoints load via interop/hf.py (parity-tested against
        # transformers in tests/test_hf_interop.py).
        self.num_kv_heads = num_kv_heads or num_heads
        if num_heads % self.num_kv_heads != 0:
            raise ValueError(f"num_kv_heads {self.num_kv_heads} must divide "
                             f"num_heads {num_heads}")
        # rope: rotary position embeddings applied to q/k per head (the
        # model then needs NO additive PositionalEncoding). Rotation uses
        # absolute positions (decode_pos-offset while decoding), so cached
        # keys carry their rotation and the q@k score is relative.
        if rope and head_dim % 2 != 0:
            raise ValueError("rope needs an even head_dim")
        self.rope = rope
        self.rope_theta = rope_theta
        # Llama-3.1-style "llama3" frequency rescaling dict (None = plain)
        self.rope_scaling = rope_scaling
        # seq_axis: mesh axis name for context parallelism. When set, the
        # module must run inside shard_map with activations sharded
        # (B, S/P, E) on that axis; attention goes through
        # parallel/context.py (ring or ulysses). seq_layout="zigzag" is the
        # balanced causal striping — the CALLER permutes the global
        # sequence with context.zigzag_permutation before sharding.
        self.seq_axis = seq_axis
        self.seq_mode = seq_mode
        if seq_axis is not None and seq_layout == "zigzag" \
                and seq_mode != "ring":
            raise ValueError("seq_layout='zigzag' is a ring-attention "
                             "layout; ulysses shards contiguously")
        self.seq_layout = seq_layout
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = head_dim
        # applied to the normalised attention PROBABILITIES in training
        # (torch nn.MultiheadAttention semantics; round-3 misplaced it on
        # the output projection). Excluded from the flash/blockwise paths
        # — they never materialise normalised probabilities — so training
        # with dropout > 0 dispatches the plain XLA core.
        self.dropout_p = dropout
        if dropout and seq_axis is not None:
            raise ValueError("attention dropout does not compose with "
                             "context-parallel attention (the ring/Ulysses "
                             "cores use online softmax); train with "
                             "dropout=0 or drop seq_axis")
        self.with_bias = with_bias
        self.causal = causal
        # 0 = plain XLA attention; >0 = blockwise (flash) with that block.
        self.block_size = block_size
        e_kv = self.num_kv_heads * self.head_dim
        self._e_kv = e_kv
        e_q = self._e_q = num_heads * self.head_dim   # embed_dim by default
        self.register_parameter(
            "in_proj_weight", init.xavier((e_q + 2 * e_kv, embed_dim),
                                          embed_dim, e_q))
        self.register_parameter(
            "out_proj_weight", init.xavier((embed_dim, e_q),
                                           e_q, embed_dim))
        # qkv_bias: bias on the q/k/v projections ONLY (Qwen2's layout:
        # with_bias=False drops the out-proj + FFN biases, qkv_bias=True
        # restores the input-projection one)
        self.qkv_bias = qkv_bias
        if with_bias or qkv_bias:
            self.register_parameter("in_proj_bias",
                                    init.zeros((e_q + 2 * e_kv,)))
        if with_bias:
            self.register_parameter("out_proj_bias", init.zeros((embed_dim,)))
        # qk_norm: RMSNorm over each head of q and of k (one learned
        # (head_dim,) gain each, shared by the heads), BEFORE the rotation;
        # "projection": over the WHOLE q and the whole k projection (a gain
        # a column, one mean square over all the heads here: the OLMo 2
        # family's), before the heads are split
        if qk_norm not in (False, True, "projection"):
            raise ValueError(f"qk_norm {qk_norm!r}: False, True (a head) "
                             f"or 'projection'")
        self.qk_norm = qk_norm
        if qk_norm == "projection":
            self.q_norm = RMSNorm(e_q, eps=qk_norm_eps)
            self.k_norm = RMSNorm(e_kv, eps=qk_norm_eps)
        elif qk_norm:
            self.q_norm = RMSNorm(head_dim, eps=qk_norm_eps)
            self.k_norm = RMSNorm(head_dim, eps=qk_norm_eps)
        # gated: a fourth projection of the QUERY input, as wide as q; the
        # attention's output is multiplied by its sigmoid before the
        # out-projection: (attn * sigmoid(x W_g)) W_o
        self.gated = gated
        if gated:
            self.register_parameter(
                "gate_proj_weight", init.xavier((e_q, embed_dim),
                                                embed_dim, e_q))
        self.attn_mask: Optional[jax.Array] = None

    # ------------------------------------------------------------- decoding
    #: rolling-ring cache mode (enable_decode(rolling=True); requires a
    #: sliding window). Class attr for pickle forward-compat.
    _rolling = False

    #: continuous-batching decode (per-row cache positions); class attr for
    #: pickle forward-compat like _rolling
    _continuous = False

    def enable_decode(self, batch_size: int, max_len: int,
                      rolling: bool = False,
                      continuous: bool = False) -> "MultiHeadAttention":
        """Switch to incremental-decode mode with a (B, max_len) KV cache.

        The cache and write position are registered BUFFERS, so under
        ``functional_apply`` they thread functionally: each traced forward
        returns a new buffer tree with the appended K/V and advanced
        position — exactly the carry a jitted ``lax.scan`` decode loop
        needs (``models/generation.py``). The module object itself is never
        mutated by traced steps.

        ``rolling=True`` (sliding-window models only): the cache is a RING
        of ``window`` slots instead of ``max_len`` — decode memory becomes
        O(window) regardless of generation length. Chunks attend the
        concatenation [ring, fresh k/v] BEFORE the ring is overwritten
        (an in-chunk write could destroy a slot an earlier chunk row still
        needs), then the chunk's last ``window`` entries scatter in.

        ``continuous=True`` (the serving engine's slot mode,
        ``models/serving.py``): ``decode_pos`` becomes PER-ROW (B,) so
        every batch row decodes at its own sequence position — mixed-length
        generations share one program. Steps are single-token; prefill
        happens out-of-band (the engine inserts a b=1 prefilled cache into
        a slot row)."""
        if rolling and continuous:
            raise ValueError("continuous batching does not compose with "
                             "the rolling ring cache yet")
        if self.seq_axis is not None:
            raise ValueError("decode mode is incompatible with "
                             "context-parallel attention (seq_axis)")
        if rolling and not getattr(self, "window", None):
            raise ValueError("rolling cache requires sliding-window "
                             "attention (window=N): an unbounded-context "
                             "model needs every past key")
        dt = self.in_proj_weight.dtype
        cache_len = min(self.window, max_len) if rolling else max_len
        shape = (batch_size, cache_len,
                 getattr(self, "num_kv_heads", self.num_heads),
                 self.head_dim)
        self._decode = True
        self._decode_prefilled = False
        self._rolling = rolling
        self._continuous = continuous
        self.register_buffer("k_cache", jnp.zeros(shape, dt))
        self.register_buffer("v_cache", jnp.zeros(shape, dt))
        self.register_buffer(
            "decode_pos",
            jnp.zeros((batch_size,) if continuous else (), jnp.int32))
        return self

    def disable_decode(self) -> "MultiHeadAttention":
        self._decode = False
        self._rolling = False
        self._continuous = False
        for name in ("k_cache", "v_cache", "decode_pos"):
            self._buffers.pop(name, None)
        return self

    def _attend_decode(self, q, k, v):
        """Append k/v at ``decode_pos`` and attend the new queries.

        A multi-token call on a COLD cache is the prompt prefill: the
        valid keys are exactly the fresh k/v, so attention runs through
        the standard causal path (``_attend``) — keeping the flash-kernel
        dispatch for long prompts and avoiding an (S, max_len) mask.
        Every other call (single-token steady state, or a multi-token
        CHUNK on a warm cache — chunked prefill / speculative
        verification) attends against the whole cache with the position
        mask ``k_pos <= q_pos`` (causal within the chunk, full history
        before it)."""
        from bigdl_tpu.ops import attention_core
        if getattr(self, "_rolling", False):
            return self._attend_decode_rolling(q, k, v)
        if getattr(self, "_continuous", False):
            return self._attend_decode_continuous(q, k, v)
        pos = self.decode_pos
        self.k_cache = jax.lax.dynamic_update_slice(
            self.k_cache, k.astype(self.k_cache.dtype), (0, pos, 0, 0))
        self.v_cache = jax.lax.dynamic_update_slice(
            self.v_cache, v.astype(self.v_cache.dtype), (0, pos, 0, 0))
        s = q.shape[1]
        self.decode_pos = pos + s
        # ANY first call warms the cache — a 1-token prompt's prefill too,
        # or a later multi-token chunk would be mis-read as cold and attend
        # only its own k/v (round-4 review catch, reproduced on-chip)
        first = not self._decode_prefilled
        self._decode_prefilled = True
        if s > 1 and first:
            # cold-cache full-prompt prefill: fresh k/v ARE the whole
            # context — keep the flash-dispatch fast path
            return self._attend(q, self._expand_kv(k), self._expand_kv(v),
                                None)
        k_pos = jnp.arange(self.k_cache.shape[1])[None, :]
        q_pos = pos + jnp.arange(s)[:, None]
        step_mask = k_pos <= q_pos
        if getattr(self, "window", None):
            # sliding window: only the last `window` cache entries are
            # live (cache stays full-length; the rolling-cache memory
            # optimisation is deliberately deferred — correctness first)
            step_mask = step_mask & (k_pos > q_pos - self.window)
        n_kv = self.k_cache.shape[2]
        if n_kv == self.num_heads or s > 1:
            # full MHA, or a GQA multi-token chunk (chunked prefill /
            # speculative verification): expand the cache to full head
            # count for this call — chunks are rare relative to the
            # steady state, which keeps the small-cache einsum below
            return attention_core.dot_product_attention(
                q, self._expand_kv(self.k_cache),
                self._expand_kv(self.v_cache),
                mask=step_mask, causal=False)
        # GQA steady state: grouped einsum reads the cache at its SMALL
        # size (an expand-then-attend would copy the whole cache to full
        # head count every step, forfeiting the bandwidth win)
        b, _, h, d = q.shape
        g = h // n_kv
        q_vec = q.reshape(b, n_kv, g, d)           # s == 1
        logits = jnp.einsum("bkgd,blkd->bkgl", q_vec, self.k_cache)
        logits = (logits * (1.0 / float(d) ** 0.5)).astype(jnp.float32)
        valid = step_mask[0]  # (L,): causal (+ window band when set)
        logits = jnp.where(valid[None, None, None, :], logits,
                           jnp.finfo(jnp.float32).min)
        w = jax.nn.softmax(logits, axis=-1)
        ctx = jnp.einsum("bkgl,blkd->bkgd", w.astype(self.v_cache.dtype),
                         self.v_cache)
        return ctx.reshape(b, 1, h, d)

    def _attend_decode_continuous(self, q, k, v):
        """Decode step with PER-ROW cache positions (continuous batching,
        ``models/serving.py``): row b writes its k/v starting at
        ``decode_pos[b]`` and query i of row b attends keys
        ``<= decode_pos[b] + i`` — every slot lives at its own point in
        its own sequence. ``s == 1`` is the steady-state token step;
        ``s > 1`` is a per-row warm CHUNK — the chunked-verification
        path speculative serving runs (draft proposals + the carried
        token verified in one forward), the continuous twin of
        ``_attend_decode``'s multi-token branch. Prefill rows are still
        inserted out-of-band by the engine."""
        from bigdl_tpu.ops import attention_core
        pos = self.decode_pos                                    # (B,)
        bsz, s = q.shape[0], q.shape[1]
        rows = jnp.arange(bsz)
        if s == 1:
            self.k_cache = self.k_cache.at[rows, pos].set(
                k[:, 0].astype(self.k_cache.dtype))
            self.v_cache = self.v_cache.at[rows, pos].set(
                v[:, 0].astype(self.v_cache.dtype))
        else:
            # chunk scatter: row b's tokens land at pos[b]..pos[b]+s-1
            idx = pos[:, None] + jnp.arange(s)[None, :]          # (B, S)
            self.k_cache = self.k_cache.at[rows[:, None], idx].set(
                k.astype(self.k_cache.dtype))
            self.v_cache = self.v_cache.at[rows[:, None], idx].set(
                v.astype(self.v_cache.dtype))
        self.decode_pos = pos + s
        length = self.k_cache.shape[1]
        n_kv = self.k_cache.shape[2]
        if s > 1:
            # chunk mask: query i of row b admits keys <= pos[b] + i.
            # Kept OFF the steady-state trace: the (B, S, L) rank-3 mask
            # measurably slows the single-token program's fusion, and
            # s == 1 is the path every non-speculative decode token runs
            k_pos = jnp.arange(length)[None, None, :]            # (1,1,L)
            q_pos = pos[:, None] + jnp.arange(s)[None, :]        # (B, S)
            valid = k_pos <= q_pos[:, :, None]                   # (B,S,L)
            if getattr(self, "window", None):
                valid = valid & (k_pos > q_pos[:, :, None] - self.window)
            # expand GQA caches for this call too — chunks are rare
            # relative to the steady state, same trade as
            # ``_attend_decode``'s chunk branch
            return attention_core.dot_product_attention(
                q, self._expand_kv(self.k_cache),
                self._expand_kv(self.v_cache),
                mask=valid[:, None, :, :], causal=False)
        k_pos = jnp.arange(length)[None, :]                      # (1, L)
        valid = k_pos <= pos[:, None]                            # (B, L)
        if getattr(self, "window", None):
            valid = valid & (k_pos > pos[:, None] - self.window)
        if n_kv == self.num_heads:
            return attention_core.dot_product_attention(
                q, self._expand_kv(self.k_cache),
                self._expand_kv(self.v_cache),
                mask=valid[:, None, None, :], causal=False)
        # GQA grouped einsum (same shape trick as the steady-state path,
        # with the per-row mask)
        b, _, h, d = q.shape
        g = h // n_kv
        q_vec = q.reshape(b, n_kv, g, d)
        logits = jnp.einsum("bkgd,blkd->bkgl", q_vec, self.k_cache)
        logits = (logits * (1.0 / float(d) ** 0.5)).astype(jnp.float32)
        logits = jnp.where(valid[:, None, None, :], logits,
                           jnp.finfo(jnp.float32).min)
        w = jax.nn.softmax(logits, axis=-1)
        ctx = jnp.einsum("bkgl,blkd->bkgd", w.astype(self.v_cache.dtype),
                         self.v_cache)
        return ctx.reshape(b, 1, h, d)

    def _attend_decode_rolling(self, q, k, v):
        """Ring-cache decode step: attend [ring, fresh] BEFORE writing
        (an in-chunk ring write could destroy a slot an earlier chunk row
        still needs), then scatter the chunk's last ``ring`` entries in.

        Ring slot ``j`` holds the kv of the LARGEST absolute position
        <= decode_pos-1 congruent to j (mod ring size); the mask admits it
        for query at absolute p iff that position is >= 0 and within the
        window (p - window, p]. NOTE: decode_pos rewinds (speculative
        decoding) are NOT supported on a ring — a rejected chunk's writes
        have already destroyed older slots."""
        from bigdl_tpu.ops import attention_core
        w = self.k_cache.shape[1]
        win = self.window
        pos = self.decode_pos
        s = q.shape[1]
        j = jnp.arange(w)[None, :]
        p_i = pos + jnp.arange(s)[:, None]            # abs position per row
        last = pos - 1
        a_j = last - jnp.mod(last - j, w)             # slot abs positions
        ring_valid = (a_j >= 0) & (a_j > p_i - win)
        t = jnp.arange(s)[None, :]
        fresh_valid = (t <= jnp.arange(s)[:, None]) & ((pos + t) > p_i - win)
        mask = jnp.concatenate([ring_valid, fresh_valid], axis=1)
        keys = jnp.concatenate(
            [self.k_cache, k.astype(self.k_cache.dtype)], axis=1)
        vals = jnp.concatenate(
            [self.v_cache, v.astype(self.v_cache.dtype)], axis=1)
        n_kv = self.k_cache.shape[2]
        if s == 1 and n_kv != self.num_heads:
            # GQA steady state: grouped einsum reads the ring at its SMALL
            # kv size (mirror of the linear-cache path — expand-then-attend
            # would copy the whole ring to full head count every token)
            b, _, h, d = q.shape
            g = h // n_kv
            q_vec = q.reshape(b, n_kv, g, d)
            logits = jnp.einsum("bkgd,blkd->bkgl", q_vec, keys)
            logits = (logits * (1.0 / float(d) ** 0.5)).astype(jnp.float32)
            logits = jnp.where(mask[0][None, None, None, :], logits,
                               jnp.finfo(jnp.float32).min)
            wts = jax.nn.softmax(logits, axis=-1)
            ctx = jnp.einsum("bkgl,blkd->bkgd", wts.astype(vals.dtype),
                             vals).reshape(b, 1, h, d)
        else:
            ctx = attention_core.dot_product_attention(
                q, self._expand_kv(keys), self._expand_kv(vals),
                mask=mask, causal=False)
        if s > w:  # only the chunk's last w entries survive; unique slots
            k_wr, v_wr = k[:, -w:], v[:, -w:]
            wr_idx = jnp.mod(pos + s - w + jnp.arange(w), w)
        else:
            k_wr, v_wr = k, v
            wr_idx = jnp.mod(pos + jnp.arange(s), w)
        self.k_cache = self.k_cache.at[:, wr_idx].set(
            k_wr.astype(self.k_cache.dtype))
        self.v_cache = self.v_cache.at[:, wr_idx].set(
            v_wr.astype(self.v_cache.dtype))
        self.decode_pos = pos + s
        self._decode_prefilled = True
        return ctx

    def set_mask(self, mask: Optional[jax.Array]) -> "MultiHeadAttention":
        """Static structural mask (baked in at trace time — see class doc;
        per-batch masks go in the input Table instead)."""
        self.attn_mask = mask
        return self

    def _split_heads(self, x):
        b, s, e = x.shape
        return x.reshape(b, s, e // self.head_dim, self.head_dim)

    def _expand_kv(self, kv):
        """Repeat kv heads up to num_heads for the attention cores (GQA);
        identity for full MHA."""
        n_kv = kv.shape[2]
        if n_kv == self.num_heads:
            return kv
        return jnp.repeat(kv, self.num_heads // n_kv, axis=2)

    def _project(self, x, w, b):
        y = jnp.matmul(match_compute(x, w), w.T)
        return y + b if b is not None else y

    def _in_projections(self, query, key, value):
        """(q, k, v) pre-head-split — the quantized twin overrides this
        (and ``_out_projection``) to run the fused int8 kernel on the raw
        int8 row-slices instead of dequantizing the full matrix."""
        e = getattr(self, "_e_q", self.embed_dim)
        ekv = getattr(self, "_e_kv", e)
        w = self.in_proj_weight
        wq, wk, wv = w[:e], w[e:e + ekv], w[e + ekv:]
        if self.with_bias or getattr(self, "qkv_bias", False):
            b = self.in_proj_bias
            bq, bk, bv = b[:e], b[e:e + ekv], b[e + ekv:]
        else:
            bq = bk = bv = None
        return (self._project(query, wq, bq), self._project(key, wk, bk),
                self._project(value, wv, bv))

    def _out_projection(self, ctx):
        out = jnp.matmul(match_compute(ctx, self.out_proj_weight),
                         self.out_proj_weight.T)
        if self.with_bias:
            out = out + self.out_proj_bias
        return out

    def update_output(self, input):
        from bigdl_tpu.utils.table import Table
        mask = self.attn_mask
        if isinstance(input, Table):
            query, key, value = input[1], input[2], input[3]
            if len(input) >= 4:
                mask = input[4]
        elif isinstance(input, (tuple, list)):
            query, key, value = input[:3]
            if len(input) >= 4:
                mask = input[3]
        else:
            query = key = value = input

        e = getattr(self, "_e_q", self.embed_dim)
        # everything but the core runs under the scope ``attn_proj``, the
        # core under ``attn_core`` (telemetry/catalogue.SCOPE_SPECS)
        with jax.named_scope("attn_proj"):
            q, k, v = self._heads_in(query, key, value)
            if not self._decode:
                k, v = self._expand_kv(k), self._expand_kv(v)
        with jax.named_scope("attn_core"):
            ctx = self._attend_decode(q, k, v) if self._decode \
                else self._attend(q, k, v, mask)
        with jax.named_scope("attn_proj"):
            b, s, _, _ = ctx.shape
            ctx = ctx.reshape(b, s, e)
            if getattr(self, "gated", False):
                gate = keep(self._project(query, self.gate_proj_weight, None),
                            ATTN_PROJ)
                ctx = ctx * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                    ctx.dtype)
            # read again only by a norm on this output
            # (HybridBlock.norm_post)
            return keep(self._out_projection(ctx), ATTN_PROJ)

    def _heads_in(self, query, key, value):
        """q, k and v per head: the in-projections, q/k norm, rotation."""
        # the projections' outputs are kept across a block's
        # rematerialisation (ops.remat) BEFORE norm and rotation: q/k norm's
        # backward reads the un-normed value, and both are element-wise
        pq, pk, pv = (keep(p, ATTN_PROJ)
                      for p in self._in_projections(query, key, value))
        over = getattr(self, "qk_norm", False)
        if over == "projection":
            pq, pk = self.q_norm.forward(pq), self.k_norm.forward(pk)
        q = self._split_heads(pq)
        k = self._split_heads(pk)
        v = self._split_heads(pv)

        if over is True:
            q, k = self.q_norm.forward(q), self.k_norm.forward(k)

        if getattr(self, "rope", False):
            if k.shape[1] != q.shape[1]:
                raise ValueError(
                    "rope supports self-attention only (q and k positions "
                    "coincide); cross-attention inputs need per-tensor "
                    "positions")
            pos = jnp.arange(q.shape[1])
            if self._decode and getattr(self, "_continuous", False):
                # per-row positions: (B, S) — each slot rotates at its own
                # sequence point
                pos = self.decode_pos[:, None] + pos[None, :]
            elif self._decode:
                pos = pos + self.decode_pos
            elif self.seq_axis is not None:
                # context parallelism: this module sees a SHARD of the
                # sequence inside shard_map; rotations must use GLOBAL
                # positions (the long-context Llama recipe — ring/Ulysses
                # attention cores are position-agnostic, rope is not)
                idx = jax.lax.axis_index(self.seq_axis)
                if self.seq_layout == "zigzag":
                    from bigdl_tpu.parallel.context import _zigzag_positions
                    pos = _zigzag_positions(
                        idx, q.shape[1], axis_size(self.seq_axis))
                else:
                    pos = idx * q.shape[1] + pos
            theta = getattr(self, "rope_theta", 10000.0)
            scaling = getattr(self, "rope_scaling", None)
            q = rope_rotate(q, pos, theta, scaling)
            k = rope_rotate(k, pos, theta, scaling)
        return q, k, v

    def _attend(self, q, k, v, mask):
        from bigdl_tpu.ops import attention_core, flash_attention
        if self.seq_axis is not None:
            from bigdl_tpu.parallel import context
            assert mask is None, (
                "context-parallel attention supports causal masking only")
            if self.seq_mode == "ring":
                return context.ring_attention(
                    q, k, v, axis_name=self.seq_axis, causal=self.causal,
                    layout=self.seq_layout)
            return context.ulysses_attention(q, k, v,
                                             axis_name=self.seq_axis,
                                             causal=self.causal)
        window = getattr(self, "window", None)
        drop = self.dropout_p if (self.training and self.dropout_p) else 0.0
        # prob-dropout needs the plain core (see __init__)
        if not drop and flash_attention.use_flash(q, mask):
            # the band is the kernels' own argument: no (Sq, Sk) mask
            return flash_attention.flash_attention(
                q, k, v, causal=self.causal, window=window)
        if window:
            # banded causal: query i sees keys (i - window, i] (Mistral
            # convention), as a mask for the XLA cores
            sq, sk = q.shape[1], k.shape[1]
            q_pos = jnp.arange(sq)[:, None]
            k_pos = jnp.arange(sk)[None, :]
            band = k_pos > q_pos - window
            mask = band if mask is None else jnp.logical_and(mask, band)
        if not drop:
            if self.block_size:
                return attention_core.blockwise_attention(
                    q, k, v, mask=mask, causal=self.causal,
                    block_size=self.block_size)
        return attention_core.dot_product_attention(
            q, k, v, mask=mask, causal=self.causal, dropout_p=drop,
            dropout_key=self.rng_key() if drop else None)

    def __repr__(self):
        return (f"MultiHeadAttention({self.embed_dim}, heads={self.num_heads}"
                f"{', causal' if self.causal else ''})")


class LatentAttention(Module):
    """Causal multi-head LATENT self-attention (MLA, DeepSeek-V2/V3): the
    queries and the keys and values each pass through a narrow latent with
    a norm on it, and a head's query and key are a content part beside a
    rotary part whose KEY is one head shared by all.

    Input (B, S, E). With ``n = num_heads``, ``dc = qk_nope_head_dim``,
    ``dr = qk_rope_head_dim``, ``dv = v_head_dim``::

        cq        = RMSNorm(x Wqa)                   (q_lora_rank)
        [qc ; qr] = cq Wqb                           n heads of dc + dr
        [ckv ; kr] = x Wkva                          kv_lora_rank + dr
        [kc ; v]  = RMSNorm(ckv) Wkvb                n heads of dc + dv
        q_h = [qc_h ; rot(qr_h)],  k_h = [kc_h ; rot(kr)]
        o_h = softmax(q_h k_h^T / sqrt(dc + dr), causal) v_h
        y   = concat(o_h) Wo

    No bias anywhere. ``rot`` is ``rope_rotate`` (feature i paired with
    i + dr/2; a checkpoint whose rotary columns are interleaved permutes
    them on import). The weights are (out, in), named after the HF
    DeepSeek-V3 projections: ``q_a`` / ``q_b`` (down, up), ``kv_a`` /
    ``kv_b``, ``out_proj``. This is the EXPANDED path, the one training
    takes: the latent is up-projected to per-head keys and values, which
    attend through the flash kernels at a q/k head of dc + dr over a v
    head of dv (``ops/flash_attention.py``: ``flash_mla_*``), nothing
    padded; the XLA core below 1,024 tokens and off the TPU. There is no
    decode mode (serving keeps the latent as the cache and absorbs Wkvb
    into the query and the output: ROADMAP R1). Everything but the
    attention core runs under the scope ``mla_proj``, the core under
    ``attn_core``.
    """

    def __init__(self, embed_dim: int, num_heads: int, q_lora_rank: int,
                 kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int,
                 rope_theta: float = 10000.0, norm_eps: float = 1e-6):
        super().__init__()
        if qk_rope_head_dim % 2:
            raise ValueError("rope needs an even qk_rope_head_dim")
        self.embed_dim, self.num_heads = embed_dim, num_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta = rope_theta
        # of the softmax's logits: the whole q/k head's, not the value's
        self.softmax_scale = (qk_nope_head_dim + qk_rope_head_dim) ** -0.5
        e_q = num_heads * (qk_nope_head_dim + qk_rope_head_dim)
        e_kv = num_heads * (qk_nope_head_dim + v_head_dim)
        e_o = num_heads * v_head_dim
        for name, rows, cols in (
                ("q_a_weight", q_lora_rank, embed_dim),
                ("q_b_weight", e_q, q_lora_rank),
                ("kv_a_weight", kv_lora_rank + qk_rope_head_dim, embed_dim),
                ("kv_b_weight", e_kv, kv_lora_rank),
                ("out_proj_weight", embed_dim, e_o)):
            self.register_parameter(name, init.xavier((rows, cols), cols,
                                                      rows))
        self.q_a_norm = RMSNorm(q_lora_rank, eps=norm_eps)
        self.kv_a_norm = RMSNorm(kv_lora_rank, eps=norm_eps)

    @staticmethod
    def _project(x, w):
        return jnp.matmul(match_compute(x, w), w.T)

    def update_output(self, input):
        from bigdl_tpu.ops import attention_core, flash_attention
        from bigdl_tpu.telemetry import get_registry, instruments
        # trace-time count, as bigdl_ssd_scan_total
        instruments(get_registry()).latent_attention_total.labels(
            path="expanded").inc()
        b, s, _ = input.shape
        n, dc, dr = self.num_heads, self.qk_nope_head_dim, \
            self.qk_rope_head_dim
        with jax.named_scope("mla_proj"):
            # none of the projections' outputs is tagged for block remat
            # to keep (ops.remat): on the v5e at 8,192 tokens keeping the
            # two up-projections' cost 0.95 GB at the step's peak and
            # keeping all five 1.12 GB, for 0.5 ms of 368 at best (PERF.md
            # section 6, PR 34); flash's o and lse are kept by the kernel
            cq = self.q_a_norm.forward(self._project(input, self.q_a_weight))
            q = self._project(cq, self.q_b_weight).reshape(b, s, n, dc + dr)
            ckv = self._project(input, self.kv_a_weight)
            kv = self._project(
                self.kv_a_norm.forward(ckv[..., :self.kv_lora_rank]),
                self.kv_b_weight).reshape(b, s, n, -1)
            pos = jnp.arange(s)
            qr = rope_rotate(q[..., dc:], pos, self.rope_theta)
            kr = rope_rotate(ckv[:, :, None, self.kv_lora_rank:], pos,
                             self.rope_theta)
            q = jnp.concatenate([q[..., :dc], qr], -1)
            k = jnp.concatenate(
                [kv[..., :dc], jnp.broadcast_to(kr, (b, s, n, dr))], -1)
            v = kv[..., dc:]
        with jax.named_scope("attn_core"):
            if flash_attention.use_flash(q, None):
                ctx = flash_attention.flash_attention(
                    q, k, v, causal=True, scale=self.softmax_scale)
            else:
                ctx = attention_core.dot_product_attention(
                    q, k, v, causal=True, scale=self.softmax_scale)
        with jax.named_scope("mla_proj"):
            return self._project(ctx.reshape(b, s, -1), self.out_proj_weight)

    def __repr__(self):
        return (f"LatentAttention({self.embed_dim}, heads={self.num_heads}, "
                f"latent={self.kv_lora_rank})")


class _AddedPositionBase(TensorModule):
    """Shared machinery for additive position encodings: a (max_len, E)
    table added to (B, S, E) input, with the incremental-decode offset
    protocol (positions continue from a buffer-tracked ``decode_pos``,
    threaded functionally by ``functional_apply`` like the KV cache).
    Subclasses store the table (parameter or buffer) and expose it via
    ``pos_table()``."""

    _decode = False  # class attr: see MultiHeadAttention._decode

    def pos_table(self) -> jax.Array:
        raise NotImplementedError

    def enable_decode(self):
        self._decode = True
        self.register_buffer("decode_pos", jnp.zeros((), jnp.int32))
        return self

    def disable_decode(self):
        self._decode = False
        self._buffers.pop("decode_pos", None)
        return self

    def update_output(self, input):
        s = input.shape[1]
        table = self.pos_table()
        if self._decode:
            pos = self.decode_pos
            pe = jax.lax.dynamic_slice(table, (pos, 0), (s, table.shape[1]))
            self.decode_pos = pos + s
        else:
            pe = table[:s]
        return self.dropout.forward(input + pe.astype(input.dtype))


class LearnedPositionalEncoding(_AddedPositionBase):
    """Learned absolute position embeddings — the GPT-2 ``wpe`` table. A
    trained (max_len, E) PARAMETER, unlike the fixed sinusoidal
    ``PositionalEncoding``; required to load GPT-2-family checkpoints
    (``interop/hf.py``). GPT-2-style N(0, 0.02) init drawn from the
    process ``RandomGenerator`` so ``manual_seed`` governs it like every
    other parameter."""

    def __init__(self, embed_dim: int, max_len: int = 1024,
                 dropout: float = 0.0):
        super().__init__()
        from bigdl_tpu.nn.regularization import Dropout
        from bigdl_tpu.utils.rng import RandomGenerator
        self.dropout = Dropout(dropout)
        self.max_len, self.embed_dim = max_len, embed_dim
        self.register_parameter(
            "weight",
            RandomGenerator.RNG().normal(
                0.0, 0.02, (max_len, embed_dim)).astype(np.float32))

    def pos_table(self) -> jax.Array:
        return self.weight

    def __repr__(self):
        return (f"LearnedPositionalEncoding({self.embed_dim}, "
                f"max_len={self.max_len})")


class PositionalEncoding(_AddedPositionBase):
    """Sinusoidal position encoding added to (B, S, E) input."""

    def __init__(self, embed_dim: int, max_len: int = 4096,
                 dropout: float = 0.0):
        super().__init__()
        from bigdl_tpu.nn.regularization import Dropout
        self.dropout = Dropout(dropout)
        pos = np.arange(max_len)[:, None]
        div = np.exp(np.arange(0, embed_dim, 2) * (-np.log(10000.0) / embed_dim))
        pe = np.zeros((max_len, embed_dim), np.float32)
        pe[:, 0::2] = np.sin(pos * div)
        pe[:, 1::2] = np.cos(pos * div[: embed_dim // 2])
        self.register_buffer("pe", pe)

    def pos_table(self) -> jax.Array:
        return self.pe


class TransformerEncoderLayer(Module):
    """Pre-/post-norm transformer block: MHA + FFN with residuals."""

    def __init__(self, embed_dim: int, num_heads: int, ffn_dim: int,
                 dropout: float = 0.0, activation: str = "gelu",
                 pre_norm: bool = True, causal: bool = False,
                 block_size: int = 0, seq_axis: Optional[str] = None,
                 seq_mode: str = "ring", seq_layout: str = "contiguous",
                 moe_experts: int = 0, moe_k: int = 2, rope: bool = False,
                 norm: str = "layer", num_kv_heads: Optional[int] = None,
                 rope_theta: float = 10000.0, bias: bool = True,
                 norm_eps: Optional[float] = None,
                 window: Optional[int] = None,
                 rope_scaling: Optional[dict] = None,
                 qkv_bias: bool = False):
        super().__init__()
        from bigdl_tpu.nn.linear import Linear
        from bigdl_tpu.nn.regularization import Dropout
        self.pre_norm = pre_norm
        self.drop = Dropout(dropout)
        self.activation = activation
        self.moe_experts = moe_experts
        # bias=False drops EVERY affine bias in the block (attention in/out
        # projections and the FFN linears) — the Llama-family convention.
        # Context-parallel attention gets NO prob-dropout (its ring/Ulysses
        # cores use online softmax and never materialise probabilities);
        # the block's residual/FFN dropout still applies, so
        # build_lm(dropout=..., seq_axis=...) stays constructible. Warn so
        # the regularization downgrade is visible (direct MHA with the same
        # combination raises instead).
        if seq_axis and dropout > 0.0:
            import warnings
            warnings.warn(
                "TransformerEncoderLayer: attention-prob dropout is "
                f"disabled under context parallelism (seq_axis={seq_axis!r}"
                "); residual/FFN dropout still applies", stacklevel=2)
        self.self_attn = MultiHeadAttention(embed_dim, num_heads,
                                            dropout=(0.0 if seq_axis
                                                     else dropout),
                                            causal=causal,
                                            block_size=block_size,
                                            seq_axis=seq_axis,
                                            seq_mode=seq_mode,
                                            seq_layout=seq_layout,
                                            rope=rope,
                                            num_kv_heads=num_kv_heads,
                                            rope_theta=rope_theta,
                                            with_bias=bias,
                                            window=window,
                                            rope_scaling=rope_scaling,
                                            qkv_bias=qkv_bias)
        if moe_experts:
            if activation == "swiglu":
                raise ValueError("swiglu FFN does not compose with MoE yet")
            # MoE FFN: top-k routed expert MLPs replace the dense pair;
            # under expert parallelism the stacked expert leaves shard
            # over the mesh 'expert' axis (parallel/expert.py)
            from bigdl_tpu.parallel.expert import MoE
            self.moe = MoE(embed_dim, ffn_dim, n_experts=moe_experts,
                           k=moe_k, activation=activation)
        else:
            self.linear1 = Linear(embed_dim, ffn_dim, with_bias=bias)
            self.linear2 = Linear(ffn_dim, embed_dim, with_bias=bias)
            if activation == "swiglu":
                # Llama-style gated FFN: W2(silu(W1 x) * Wg x); the gate is
                # a third column-parallel projection
                self.linear_gate = Linear(embed_dim, ffn_dim, with_bias=bias)
        if norm == "layer":
            eps = 1e-5 if norm_eps is None else norm_eps
            self.norm1 = LayerNorm(embed_dim, eps=eps)
            self.norm2 = LayerNorm(embed_dim, eps=eps)
        elif norm == "rms":
            eps = 1e-6 if norm_eps is None else norm_eps
            self.norm1 = RMSNorm(embed_dim, eps=eps)
            self.norm2 = RMSNorm(embed_dim, eps=eps)
        else:
            raise ValueError(f"unknown norm {norm!r}: 'layer' or 'rms'")

    def _act(self, x):
        if self.activation == "gelu":
            return jax.nn.gelu(x)  # tanh approximation (GPT-2's gelu_new)
        if self.activation == "gelu_exact":
            return jax.nn.gelu(x, approximate=False)  # erf form (HF "gelu")
        if self.activation == "relu":
            return jax.nn.relu(x)
        raise ValueError(f"unknown activation {self.activation!r}")

    def _drop(self, x):
        return self.drop.forward(x)

    def _ffn(self, x):
        if self.moe_experts:
            return self.moe.forward(x)
        with jax.named_scope("mlp"):
            if self.activation == "swiglu":
                up = self.linear1.forward(x)
                gate = self.linear_gate.forward(x)
                return self.linear2.forward(jax.nn.silu(up) * gate)
            return self.linear2.forward(self._act(self.linear1.forward(x)))

    def update_output(self, input):
        # Megatron sequence-parallel regions: when tagged by
        # parallel.tensor_parallel.enable_sequence_parallel, the residual
        # stream (norm/dropout/residual segments between the column->row
        # matmul sandwiches) is constrained seq-sharded over the tensor
        # axis; GSPMD lowers the boundaries as reduce-scatter/all-gather.
        sp = getattr(self, "_sp", None)
        if sp is not None:
            from bigdl_tpu.parallel.tensor_parallel import sp_constrain
            _c = lambda x: sp_constrain(x, sp)
        else:
            _c = lambda x: x
        x = _c(input)
        if self.pre_norm:
            x = _c(x + self._drop(self.self_attn.forward(self.norm1.forward(x))))
            h = self._ffn(self.norm2.forward(x))
            return _c(x + self._drop(h))
        x = _c(self.norm1.forward(x + self._drop(self.self_attn.forward(x))))
        h = self._ffn(x)
        return _c(self.norm2.forward(x + self._drop(h)))


class TransformerEncoder(Module):
    """Stack of ``TransformerEncoderLayer`` with optional final norm."""

    def __init__(self, num_layers: int, embed_dim: int, num_heads: int,
                 ffn_dim: int, dropout: float = 0.0, activation: str = "gelu",
                 pre_norm: bool = True, causal: bool = False,
                 block_size: int = 0, seq_axis: Optional[str] = None,
                 seq_mode: str = "ring", seq_layout: str = "contiguous",
                 moe_experts: int = 0, moe_k: int = 2, rope: bool = False,
                 norm: str = "layer", num_kv_heads: Optional[int] = None,
                 rope_theta: float = 10000.0, bias: bool = True,
                 norm_eps: Optional[float] = None,
                 window: Optional[int] = None,
                 rope_scaling: Optional[dict] = None,
                 qkv_bias: bool = False):
        super().__init__()
        self.num_layers = num_layers
        for i in range(num_layers):
            self.add_module(f"layer{i}", TransformerEncoderLayer(
                embed_dim, num_heads, ffn_dim, dropout=dropout,
                activation=activation, pre_norm=pre_norm, causal=causal,
                block_size=block_size, seq_axis=seq_axis, seq_mode=seq_mode,
                seq_layout=seq_layout, moe_experts=moe_experts, moe_k=moe_k,
                rope=rope, norm=norm, num_kv_heads=num_kv_heads,
                rope_theta=rope_theta, bias=bias, norm_eps=norm_eps,
                window=window, rope_scaling=rope_scaling,
                qkv_bias=qkv_bias))
        if not pre_norm:
            self.final_norm = None
        elif norm == "rms":
            self.final_norm = RMSNorm(
                embed_dim, eps=1e-6 if norm_eps is None else norm_eps)
        else:
            self.final_norm = LayerNorm(
                embed_dim, eps=1e-5 if norm_eps is None else norm_eps)
        if self.final_norm is not None:
            self.add_module("final_norm", self.final_norm)

    #: Optimizer.set_remat("block") sets this: each block's forward runs
    #: under jax.checkpoint, so the backward holds only per-block BOUNDARY
    #: activations (B*S*E per layer) — the transformer activation-memory
    #: recipe that full-forward remat cannot provide (one outer checkpoint
    #: re-materialises every intermediate during its own replay). Training
    #: only; requires state-free blocks (no decode caches — enable_decode
    #: and remat_blocks are mutually exclusive by construction since decode
    #: runs in eval mode).
    remat_blocks = False

    def update_output(self, input):
        x = input
        ckpt = self.remat_blocks and self.training
        for i in range(self.num_layers):
            layer = self._modules[f"layer{i}"]
            if ckpt:
                x = jax.checkpoint(
                    lambda h, _l=layer: _l.forward(h))(x)
            else:
                x = layer.forward(x)
        if self.final_norm is not None:
            x = self.final_norm.forward(x)
        return x


def llama3_scale_freqs(freqs: jax.Array, scaling: dict) -> jax.Array:
    """Llama-3.1 long-context frequency rescaling (the "llama3" rope_type):
    low frequencies (long wavelengths) slow by ``factor``, high
    frequencies keep, a smooth band interpolates — matching HF
    ``_compute_llama3_parameters`` so scaled checkpoints import with
    logit parity (``tests/test_hf_interop.py``)."""
    factor = float(scaling["factor"])
    low_f = float(scaling.get("low_freq_factor", 1.0))
    high_f = float(scaling.get("high_freq_factor", 4.0))
    orig = float(scaling.get("original_max_position_embeddings", 8192))
    wavelen = 2.0 * np.pi / freqs
    smooth = (orig / wavelen - low_f) / (high_f - low_f)
    smooth = jnp.clip(smooth, 0.0, 1.0)
    return (1.0 - smooth) * freqs / factor + smooth * freqs


def scale_rope_freqs(freqs: jax.Array, theta: float,
                     scaling: dict) -> Tuple[jax.Array, float]:
    """(scaled_freqs, attention_scaling) for an HF ``rope_scaling`` dict.

    - ``linear`` (position interpolation): every angle divided by
      ``factor`` — equivalently freqs/factor.
    - ``yarn``: NTK-by-parts — low frequencies interpolate (freqs/factor),
      high frequencies extrapolate (unchanged), a linear ramp between the
      ``beta_fast``/``beta_slow`` correction dims blends; cos/sin are
      additionally scaled by ``attention_factor`` (default
      ``0.1*ln(factor)+1``), matching HF ``_compute_yarn_parameters``.
    - ``llama3``: wavelength-banded rescaling (``llama3_scale_freqs``).
    """
    rt = scaling.get("rope_type", scaling.get("type"))
    if rt == "llama3":
        return llama3_scale_freqs(freqs, scaling), 1.0
    if rt == "linear":
        return freqs / float(scaling["factor"]), 1.0
    if rt == "yarn":
        import math
        factor = float(scaling["factor"])
        attn = scaling.get("attention_factor")
        if attn is None:
            mscale = scaling.get("mscale")
            attn = (0.1 * math.log(factor) + 1.0 if mscale is None
                    else 0.1 * float(mscale) * math.log(factor) + 1.0)
        beta_fast = float(scaling.get("beta_fast", 32.0))
        beta_slow = float(scaling.get("beta_slow", 1.0))
        orig = float(scaling.get("original_max_position_embeddings", 4096))
        half = freqs.shape[0]
        dim = 2 * half

        def correction_dim(rot):
            return (dim * math.log(orig / (rot * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = math.floor(correction_dim(beta_fast))
        high = math.ceil(correction_dim(beta_slow))
        low, high = max(low, 0), min(high, dim - 1)
        ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        extrap_mask = 1.0 - ramp  # 1 where frequencies extrapolate
        scaled = (freqs / factor) * (1.0 - extrap_mask) + freqs * extrap_mask
        return scaled, float(attn)
    raise ValueError(f"unsupported rope_scaling type {rt!r} "
                     "(llama3/linear/yarn)")


def rope_rotate(x: jax.Array, positions: jax.Array,
                theta: float = 10000.0,
                scaling: Optional[dict] = None) -> jax.Array:
    """Rotary position embedding (RoPE, Su et al.): rotate feature pairs of
    ``x`` (B, S, H, D) by angles proportional to absolute ``positions``
    (S,). Because rotations compose, q@k between positions i and j depends
    only on i - j — the relative-position property that makes RoPE the
    modern LM standard. Applied to q/k BEFORE attention (and before the KV
    cache write, so cached keys carry their absolute rotation).

    The pairing convention is HF-Llama's "rotate_half" (pair feature i
    with i + D/2), so Llama-family checkpoints import without any q/k
    permutation (``interop/hf.py``). ``theta`` is the frequency base:
    10000 for Llama-1/2-era models, 500000 for Llama-3. ``scaling`` is
    an optional Llama-3.1-style rope_scaling dict (``llama3_scale_freqs``)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    att_scale = 1.0
    if scaling is not None:
        freqs, att_scale = scale_rope_freqs(freqs, theta, scaling)
    positions = positions.astype(jnp.float32)
    angles = positions[..., None] * freqs          # (S, half) or (B, S, half)
    if angles.ndim == 2:                           # shared positions
        angles = angles[None]
    # attention_factor (yarn): HF multiplies cos/sin, scaling q and k each
    # by it -> attention scores by its square
    cos = jnp.cos(angles)[:, :, None, :] * att_scale
    sin = jnp.sin(angles)[:, :, None, :] * att_scale
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)
