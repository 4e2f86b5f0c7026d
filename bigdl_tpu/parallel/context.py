"""Sequence/context parallelism: ring attention and Ulysses (all-to-all).

New TPU-native capability — the reference has none (SURVEY §5.7: "Sequence
dim is never sharded across workers"; its long-sequence story stops at
pad-to-max batching, ``dataset/Transformer.scala:105-275``). Here the
sequence axis of attention is sharded over the mesh ``seq`` axis so context
length scales with the number of chips:

- **Ring attention** (`ring_attention`): every device keeps its query shard
  resident and streams key/value shards around the ICI ring with
  ``lax.ppermute``, folding each hop's partial attention into an
  online-softmax accumulator. Peak memory per chip is O(S/P); the ring
  overlaps compute with neighbor-to-neighbor ICI traffic, the layout
  collective-free XLA can't derive itself. On TPU each hop's partial runs
  the Pallas flash kernel (``flash_attention_with_lse`` — the LSE output
  plus its differentiable cotangent is exactly the statistic the
  cross-device combine needs); elsewhere the XLA ``attention_partial``
  path is used (``use_kernel`` overrides).
- **Ulysses** (`ulysses_attention`): two ``lax.all_to_all``s re-shard
  (seq-sharded -> head-sharded), run ordinary full-sequence attention
  locally per head group, and shard back. Cheaper for moderate S with
  enough heads (head count must divide by the axis size).

Both are called INSIDE ``shard_map`` bodies (the per-device view), with
arrays sharded (B, S/P, N, D) on the named axis. ``ring_self_attention``
wraps the whole thing in ``shard_map`` for single-call use and tests.

Causal layouts: with ``layout="contiguous"`` shards are consecutive
sequence chunks, so later devices do more causal work than earlier ones
and the ring serialises on the last. ``layout="zigzag"`` gives every
device an (early, late) chunk pair — chunk ``i`` and chunk ``2P-1-i`` —
balancing per-hop FLOPs across the ring (the standard striped fix).
Zigzag composes with the kernel hops too: each hop runs the flash kernel
on the 4 contiguous half-chunk pairs and folds them with the LSE
combine; the XLA partial path instead masks with explicit global
position vectors.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.lax import axis_size, pcast

from bigdl_tpu.ops.attention_core import (
    attention_partial, finalize_partial, online_softmax_combine)

_NEG = float(jnp.finfo(jnp.float32).min)


def _lse_combine(o_a, l_a, o_b, l_b):
    """Merge two (output, logsumexp) attention partials over disjoint key
    sets. o: (B, S, N, D) f32; l: (B, N, S) f32 with the finite ``_NEG``
    sentinel (not -inf) on all-masked rows, keeping this NaN-free."""
    m = jnp.maximum(l_a, l_b)
    ca = jnp.exp(l_a - m)
    cb = jnp.exp(l_b - m)
    s = ca + cb
    l_new = m + jnp.log(s)
    ca, cb = ca / s, cb / s
    o_new = (o_a * ca.transpose(0, 2, 1)[..., None]
             + o_b * cb.transpose(0, 2, 1)[..., None])
    return o_new, l_new


def _ring_hop_kernel(q, kc, vc, scale, kv_chunk, q_chunk, causal, interpret):
    """One (q chunk, kv chunk) pair's (o, lse) partial via the Pallas flash
    kernel.

    Causal classification: kv chunks strictly in the past are unmasked, the
    diagonal chunk runs the kernel's causal path, future chunks contribute
    the empty partial — all three as ``lax.switch`` branches since the
    chunk ids are traced. Chunks must be CONTIGUOUS sequence spans (the
    kernel's causal mask is positional within the pair); zigzag callers
    pass each contiguous half separately.
    """
    from bigdl_tpu.ops.flash_attention import flash_attention_with_lse

    def full(_):
        o, l = flash_attention_with_lse(q, kc, vc, causal=False, scale=scale,
                                        interpret=interpret)
        return o.astype(jnp.float32), l

    if not causal:
        return full(None)

    def diag(_):
        o, l = flash_attention_with_lse(q, kc, vc, causal=True, scale=scale,
                                        interpret=interpret)
        return o.astype(jnp.float32), l

    def skip(_):
        o = (q * 0.0).astype(jnp.float32)
        l = jnp.sum(o, axis=-1).transpose(0, 2, 1) + _NEG
        return o, l

    idx = jnp.where(kv_chunk < q_chunk, 0,
                    jnp.where(kv_chunk == q_chunk, 1, 2))
    return lax.switch(idx, [full, diag, skip], None)


def _zigzag_hop_kernel(q, kc, vc, scale, src, my, p, causal, interpret):
    """One zigzag hop's (o, lse) partial: the local shard is the
    contiguous-chunk pair (my, 2P-1-my) and the kv shard is the pair
    (src, 2P-1-src); run the flash kernel on the 4 contiguous half-chunk
    combinations and fold the kv halves per q half."""
    if not causal:
        # position-independent: one full-chunk launch, no split/fold cost
        return _ring_hop_kernel(q, kc, vc, scale, 0, 0, False, interpret)
    c2 = q.shape[1] // 2
    halves_q = ((q[:, :c2], my), (q[:, c2:], 2 * p - 1 - my))
    halves_kv = ((kc[:, :c2], vc[:, :c2], src),
                 (kc[:, c2:], vc[:, c2:], 2 * p - 1 - src))
    outs = []
    for qh, qid in halves_q:
        o, l = None, None
        for kh, vh, kid in halves_kv:
            oh, lh = _ring_hop_kernel(qh, kh, vh, scale, kid, qid, causal,
                                      interpret)
            if o is None:
                o, l = oh, lh
            else:
                o, l = _lse_combine(o, l, oh, lh)
        outs.append((o, l))
    return (jnp.concatenate([outs[0][0], outs[1][0]], axis=1),
            jnp.concatenate([outs[0][1], outs[1][1]], axis=2))


def zigzag_permutation(seq_len: int, p: int) -> np.ndarray:
    """Index permutation putting the zigzag layout into contiguous shards:
    after ``x[:, perm]`` a P-way contiguous split hands device ``i`` the
    global chunks ``(i, 2P-1-i)``. Requires ``seq_len % (2*p) == 0``."""
    assert seq_len % (2 * p) == 0, \
        f"zigzag needs seq ({seq_len}) divisible by 2*devices ({2 * p})"
    c2 = seq_len // (2 * p)
    idx = []
    for i in range(p):
        idx.extend(range(i * c2, (i + 1) * c2))
        j = 2 * p - 1 - i
        idx.extend(range(j * c2, (j + 1) * c2))
    return np.asarray(idx, dtype=np.int32)


def zigzag_inverse(seq_len: int, p: int) -> np.ndarray:
    perm = zigzag_permutation(seq_len, p)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(seq_len, dtype=np.int32)
    return inv


def _zigzag_positions(dev, chunk, p):
    """Global positions of a device's zigzag shard (device ``dev`` holds
    chunks ``dev`` and ``2P-1-dev``, each of ``chunk // 2``)."""
    c2 = chunk // 2
    ar = jnp.arange(c2)
    return jnp.concatenate([dev * c2 + ar, (2 * p - 1 - dev) * c2 + ar])


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str, causal: bool = False,
                   scale: Optional[float] = None,
                   use_kernel: Optional[bool] = None,
                   interpret: Optional[bool] = None,
                   layout: str = "contiguous") -> jax.Array:
    """Ring attention over the named mesh axis (call inside shard_map).

    q, k, v: the local shard, (B, S/P, N, D); global sequence = P shards in
    axis-index order (``layout="contiguous"``) or the zigzag striping
    (``layout="zigzag"``, see ``zigzag_permutation``). Returns the local
    (B, S/P, N, D) output shard — the same math as full attention on the
    gathered sequence.
    """
    if scale is None:
        scale = 1.0 / float(q.shape[-1]) ** 0.5
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown ring layout {layout!r}")
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    p = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    chunk = q.shape[1]

    # Start with the local chunk, then pull each neighbour's around the ring.
    perm = [(i, (i + 1) % p) for i in range(p)]  # shard s lives on dev s+t at hop t

    b, s_loc, n, d = q.shape

    if use_kernel:
        def hop(t, carry):
            o, lse, kc, vc = carry
            src = (my - t) % p
            if layout == "zigzag":
                oh, lh = _zigzag_hop_kernel(q, kc, vc, scale, src, my, p,
                                            causal, interpret)
            else:
                oh, lh = _ring_hop_kernel(q, kc, vc, scale, src, my,
                                          causal, interpret)
            o, lse = _lse_combine(o, lse, oh, lh)
            kc = lax.ppermute(kc, axis_name, perm)
            vc = lax.ppermute(vc, axis_name, perm)
            return o, lse, kc, vc

        # Derive zero carries from q so they inherit its device-varying
        # type under shard_map's vma checking.
        o0 = (q * 0.0).astype(jnp.float32)
        l0 = jnp.sum(o0, axis=-1).transpose(0, 2, 1) + _NEG
        o, lse, _, _ = lax.fori_loop(0, p, hop, (o0, l0, k, v))
        return o.astype(q.dtype)

    if layout == "zigzag":
        q_pos = _zigzag_positions(my, chunk, p)
    else:
        q_pos = my * chunk + jnp.arange(chunk)

    def hop(t, carry):
        acc, rsum, rmax, kc, vc = carry
        src = (my - t) % p  # which global chunk we hold at hop t
        if layout == "zigzag":
            k_pos = _zigzag_positions(src, chunk, p)
        else:
            k_pos = src * chunk + jnp.arange(chunk)
        pa, ps, pm = attention_partial(q, kc, vc, scale, k_offset=0,
                                       q_offset=0, causal=causal,
                                       q_pos=q_pos, k_pos=k_pos)
        acc, rsum, rmax = online_softmax_combine(acc, rsum, rmax, pa, ps, pm)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return acc, rsum, rmax, kc, vc

    acc = jnp.zeros((b, s_loc, n, d), jnp.float32)
    rsum = jnp.zeros((b, n, s_loc), jnp.float32)
    rmax = jnp.full((b, n, s_loc), _NEG, jnp.float32)
    # Mark the zero-init carries as device-varying over the ring axis —
    # required by shard_map's vma typing (the loop outputs vary over 'seq').
    acc, rsum, rmax = (pcast(x, (axis_name,), to="varying")
                       for x in (acc, rsum, rmax))
    acc, rsum, rmax, _, _ = lax.fori_loop(
        0, p, hop, (acc, rsum, rmax, k, v))
    return finalize_partial(acc, rsum).astype(q.dtype)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str, causal: bool = False,
                      scale: Optional[float] = None) -> jax.Array:
    """Ulysses sequence parallelism (call inside shard_map).

    all_to_all turns the seq-sharded (B, S/P, N, D) into head-sharded
    (B, S, N/P, D), runs full attention locally, and reverses. Requires
    num_heads % axis_size == 0.
    """
    from bigdl_tpu.ops.attention_core import blockwise_attention
    p = axis_size(axis_name)
    n = q.shape[2]
    assert n % p == 0, f"heads {n} must divide seq axis size {p}"

    def to_heads(x):   # (B, S/P, N, D) -> (B, S, N/P, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def to_seq(x):     # (B, S, N/P, D) -> (B, S/P, N, D)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    out = blockwise_attention(qh, kh, vh, causal=causal, scale=scale,
                              block_size=max(128, qh.shape[1] // 8))
    return to_seq(out)


def _wrap_shard_map(fn, mesh, axis_name):
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    spec = P(None, axis_name, None, None)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)


def ring_self_attention(q, k, v, mesh, axis_name: str = "seq",
                        causal: bool = False,
                        scale: Optional[float] = None,
                        mode: str = "ring",
                        use_kernel: Optional[bool] = None,
                        interpret: Optional[bool] = None,
                        layout: str = "contiguous") -> jax.Array:
    """Whole-array convenience: shards (B, S, N, D) over ``axis_name`` of
    ``mesh``, runs ring/Ulysses attention, returns the full array view.

    ``layout="zigzag"`` permutes the sequence into the balanced striping
    before sharding and permutes the output back — callers see normal
    sequence order in and out.
    """
    if mode == "ring":
        impl = functools.partial(ring_attention, use_kernel=use_kernel,
                                 interpret=interpret, layout=layout)
    else:
        impl = ulysses_attention
    fn = functools.partial(impl, axis_name=axis_name, causal=causal,
                           scale=scale)
    wrapped = _wrap_shard_map(fn, mesh, axis_name)
    if mode == "ring" and layout == "zigzag":
        s = q.shape[1]
        p = mesh.shape[axis_name]
        fwd = jnp.asarray(zigzag_permutation(s, p))
        inv = jnp.asarray(zigzag_inverse(s, p))
        out = wrapped(jnp.take(q, fwd, axis=1), jnp.take(k, fwd, axis=1),
                      jnp.take(v, fwd, axis=1))
        return jnp.take(out, inv, axis=1)
    return wrapped(q, k, v)
