"""Named remat policies: what a rematerialised forward KEEPS.

A ``jax.checkpoint`` with ``save_only_these_names`` runs its forward a
second time in the backward, but for the values a producer tagged with
``checkpoint_name`` under a name on the policy's list. The tags live at the
producer sites and this is the ONE place each save-list is spelled, so a
tag rename cannot silently diverge from its policy (a stale name in
``save_only_these_names`` saves nothing and degenerates to full remat with
no error; ``tests/test_block_remat.py`` holds the two together). Outside a
checkpoint a tag is the identity and lowers to nothing.

**Block remat** (``Optimizer.set_remat("block")`` on a ``nn.HybridDecoder``:
each block under ``jax.checkpoint(..., policy=block_remat_policy())``)
keeps what is dear to recompute and cheap to hold, each in the dtype and
the very value the forward went on to use. In the order of milliseconds
saved a GB held (bytes a token a block that holds the value, in bf16
training; E the stream's width):

======================  ===============================================  ==========================================
name                    value, producer                                  bytes a token
======================  ===============================================  ==========================================
``MOE_ROUTE_TABLES``    the held layer's pick ids and their float32      ``16 k`` (int32 and float32, each
                        scores, the sorted pick order, the sorted        twice): 128 at top-8
                        picks' weights and the rows an expert
                        (``parallel/expert.MoE._route``,
                        ``_held_forward``)
``MOE_ROUTED_OUT``      the held experts' summed output (same place)     ``2 E``
``FLASH_OUT``           flash attention's ``o`` and ``lse``, tagged      ``2 * heads * head_dim + 4 * heads``
                        INSIDE the ``custom_vjp``'s forward rule
                        (``ops/flash_attention._flash_lse_vjp_fwd``):
                        both are its residuals as well as its outputs
``ATTN_PROJ``           the q, k, v and gate projections' outputs        ``2 * (2 * heads + 2 * kv_heads)
                        BEFORE q/k norm and rotation, and the            * head_dim + 2 E``
                        out-projection's output
                        (``nn.MultiHeadAttention.update_output``;
                        NOT ``nn.LatentAttention``'s: kept, its
                        low-rank projections' outputs bought 0.5 ms
                        of 368 for 1 GB at the peak, PR 34)
``MLP_PROJ``            ``nn.GatedMLP``'s gate, up and down outputs      ``4 * hidden + 2 E``
``MAMBA_IN_PROJ``       ``nn.Mamba2``'s in-projection output             ``2 * (2 * d_inner + 2 * groups * state
                        ``[z | xBC | dt]``                               + heads)``
``SHORT_CONV_IN_PROJ``  ``nn.ShortConv``'s in-projection output          ``6 E``
                        ``[B | C | x]``
``DELTA_IN_PROJ``       ``nn.GatedDeltaNet``'s in-projection output     ``2 * (2 * heads * d_k + 2 * heads * d_v
                        ``[q | k | v | z | b | a]``                      + 2 * heads)``
``MOE_SHARED_HID``      the shared expert's float32 first products       ``4 * shared_hidden``, twice for
                        (``MoE._hidden``), before the activation         SwiGLU
======================  ===============================================  ==========================================

A kept value that no backward reads (an out-projection's or the routed
experts' output with no norm behind it) is pruned by ``jax.checkpoint``
itself and costs nothing. What a block still runs twice: norms, rotation,
gates, the convolutions, the scan, the delta rule's recurrence, the
router's product, the shared expert's activation and second product; a
mixer's local part in the form it took (``nn.Mamba2``'s and
``nn.GatedDeltaNet``'s as the forward calls of ``ops/mamba_local.py`` and
``ops/delta_local.py`` where their path rules say so: the calls keep no
residual but the kept in-projection output and the recurrence's, and
their backward calls make the pre-activation and the norms again).
Keeping changes the jaxpr's arithmetic nowhere; XLA compiles the forward around what must reach HBM,
so on the chip in bf16 a loss moves in its sixth digit (PERF.md section 6,
PR 31). On the v5e at 1 x 8,192 tokens the list holds 253 MB an attention
block of the Trinity-Mini cell, 235 MB its dense block, 169 MB a Mamba-2
block and 122 MB an expert block's shared expert of the Nemotron cell,
and took 31 and 25 ms off steps of 286 and 287 ms for 0.77 and 0.30 GB
more at the step's peak (PERF.md section 6, PR 31).
"""

from __future__ import annotations

import jax
from jax.ad_checkpoint import checkpoint_name

MOE_ROUTE_TABLES = "moe_route_tables"
MOE_ROUTED_OUT = "moe_routed_out"
FLASH_OUT = "flash_out"
ATTN_PROJ = "attn_proj"
MLP_PROJ = "mlp_proj"
MAMBA_IN_PROJ = "mamba_in_proj"
SHORT_CONV_IN_PROJ = "short_conv_in_proj"
DELTA_IN_PROJ = "delta_in_proj"
MOE_SHARED_HID = "moe_shared_hid"

#: what block remat keeps (module docstring), dearest to recompute a byte
#: held first: the order to drop names in, from the end, on a chip that
#: runs out
BLOCK_SAVED_NAMES = (MOE_ROUTE_TABLES, MOE_ROUTED_OUT, FLASH_OUT, ATTN_PROJ,
                     MLP_PROJ, MAMBA_IN_PROJ, SHORT_CONV_IN_PROJ,
                     DELTA_IN_PROJ, MOE_SHARED_HID)


def _listed(name: str) -> int:
    """``name``'s place on block remat's list."""
    if name not in BLOCK_SAVED_NAMES:
        raise ValueError(f"{name!r} is not on block remat's list "
                         f"{BLOCK_SAVED_NAMES}")
    return BLOCK_SAVED_NAMES.index(name)


def block_remat_policy(through=None):
    """Per-block checkpointing of a decoder: recompute everything inside a
    block but ``BLOCK_SAVED_NAMES``, or but the leading part of it that
    ends with the name ``through`` (the tuple is in dropping order: a
    decoder that has no room for all of it,
    ``nn.HybridDecoder.remat_keep_through``, drops from the end)."""
    kept = BLOCK_SAVED_NAMES if through is None \
        else BLOCK_SAVED_NAMES[:_listed(through) + 1]
    return jax.checkpoint_policies.save_only_these_names(*kept)


def keep(value, name: str):
    """Tag ``value`` for block remat to keep under ``name``, which must be
    on its list. Counted at trace time (``bigdl_remat_kept_total{name}``:
    the tags a compiled program MET; whether a checkpoint honoured them is
    in the lowered program)."""
    _listed(name)
    from bigdl_tpu.telemetry import get_registry, instruments
    instruments(get_registry()).remat_kept_total.labels(name=name).inc()
    return checkpoint_name(value, name)
