"""Selective state-space recurrence of Mamba-2 in its chunked SSD form.

The recurrence, a head ``h`` reading group ``g = h // (H // G)``::

    H_t = exp(dt_t,h * a_h) * H_{t-1} + dt_t,h * x_t,h (x) B_t,g     (P x N)
    y_t,h = H_t @ C_t,g                                      H_0 = 0

is computed a chunk of ``Q`` positions at a time (Dao & Gu 2024, "state
space duality"): inside a chunk the positions see each other through one
masked (Q, Q) product, ``(C B^T * decay) @ (dt x)``, the attention-like
dual; between chunks only the (P, N) state at each chunk's end is carried,
by a ``lax.scan`` over the chunks. The per-token loop of L steps becomes
four batched matrix products and L / Q elementwise steps.

Plain ``jax.numpy``: XLA einsums, differentiable by autodiff, one form (no
option selects another). The products take their operands in the compute
dtype (bf16 under the training policy) and accumulate in float32; the
decays (``exp`` of the cumulated ``dt * a``) and the carried state are
float32 throughout. The D skip and the gate belong to ``nn.Mamba2``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_scan(x, dt, a, b, c, chunk: int = 128):
    """``x`` (B, L, H, P), ``dt`` (B, L, H) after its softplus, ``a`` (H,)
    negative, ``b`` and ``c`` (B, L, G, N) with G dividing H -> ``y``
    (B, L, H, P) in ``x``'s dtype. L need not be a multiple of ``chunk``:
    the tail is padded with ``dt = 0`` (decay 1, no input), which leaves
    the state and every real output as they are."""
    from bigdl_tpu.telemetry import get_registry, instruments
    # trace-time count, as bigdl_moe_dispatch_total: the one form there is
    instruments(get_registry()).ssd_scan_total.labels(form="chunked").inc()
    with jax.named_scope("ssd_scan"):
        return _ssd_chunked(x, dt, a, b, c, chunk)


def _chunk_states(whole, local):
    """Between chunks: the state at each chunk's START, from the decay over
    each whole chunk (B, nc, G, R) and what the chunk adds by its own end
    (B, nc, G, R, P, N). Float32, one elementwise step a chunk, from a
    zero state."""
    def carry_on(state, inp):
        keep, add = inp
        return keep[..., None, None] * state + add, state

    _, before = jax.lax.scan(
        carry_on, jnp.zeros(local.shape[:1] + local.shape[2:], jnp.float32),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(local, 1, 0)))
    return jnp.moveaxis(before, 0, 1)


def _ssd_chunked(x, dt, a, b, c, q):
    bsz, length, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g                              # heads reading one group
    cd = x.dtype
    f32 = jnp.float32
    pad = (-length) % q
    if pad:
        widen = lambda t: jnp.pad(t, [(0, 0), (0, pad)]
                                  + [(0, 0)] * (t.ndim - 2))
        x, dt, b, c = widen(x), widen(dt), widen(b), widen(c)
    nc = (length + pad) // q

    dt = dt.astype(f32).reshape(bsz, nc, q, g, r)
    da = dt * a.astype(f32).reshape(g, r)
    cs = jnp.cumsum(da, axis=2)             # log-decay from the chunk's start
    xd = (x.reshape(bsz, nc, q, g, r, p).astype(f32) * dt[..., None])
    b = b.reshape(bsz, nc, q, g, n)
    c = c.reshape(bsz, nc, q, g, n)

    # inside a chunk: position l reads s <= l at decay exp(cs_l - cs_s)
    cs_t = jnp.moveaxis(cs, 2, -1)          # (B, nc, G, R, Q)
    diff = cs_t[..., :, None] - cs_t[..., None, :]
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))   # (B,nc,G,R,Q,Q)
    cb = jnp.einsum("bclgn,bcsgn->bcgls", c, b,
                    preferred_element_type=f32)
    m = (cb[:, :, :, None] * decay).astype(cd)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", m, xd.astype(cd),
                   preferred_element_type=f32)

    # what each chunk adds to the state by its own end
    to_end = jnp.exp(cs[:, :, -1:] - cs)    # (B, nc, Q, G, R)
    local = jnp.einsum("bcsgn,bcsgrp->bcgrpn", b,
                       (xd * to_end[..., None]).astype(cd),
                       preferred_element_type=f32)

    before = _chunk_states(jnp.exp(cs[:, :, -1]), local)

    y_off = jnp.einsum("bclgn,bcgrpn->bclgrp", c, before.astype(cd),
                       preferred_element_type=f32)
    y = y + y_off * jnp.exp(cs)[..., None]
    y = y.reshape(bsz, nc * q, h, p)[:, :length]
    return y.astype(cd)
