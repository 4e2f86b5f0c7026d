"""Named remat policy for bandwidth-bound conv/BN models.

The tags live at the producer sites — ``nn/conv.py`` wraps conv outputs in
``checkpoint_name(out, "conv_out")`` and ``ops/batch_norm.py`` tags the BN
statistics ``"bn_stats"`` — and this is the ONE place the save-list is
spelled, so a tag rename cannot silently diverge from the policy (a stale
name in ``save_only_these_names`` saves nothing and degenerates to full
remat with no error). Consumed by ``Optimizer.set_remat("conv")`` and
bench.py's ``BIGDL_TPU_BENCH_REMAT=conv`` lever.

Measured on a real v5e (PERF.md round 3): for ResNet-50 this policy LOSES
~7% vs no remat — XLA's backward fusions already recompute the elementwise
tail — so it is an explicit memory/HBM knob, not a default.
"""

from __future__ import annotations

import jax

REMAT_SAVED_NAMES = ("conv_out", "bn_stats")


def conv_remat_policy():
    """Save conv outputs + BN statistics; recompute the elementwise tail."""
    return jax.checkpoint_policies.save_only_these_names(*REMAT_SAVED_NAMES)


#: ``checkpoint_name`` of a held-expert layer's routed output
#: (``parallel/expert.py``): block remat keeps it, so the loop over the
#: experts' row blocks is not run a second time in the backward.
MOE_ROUTED_OUT = "moe_routed_out"


def block_remat_policy():
    """Per-block checkpointing of a decoder: recompute everything inside a
    block but the routed experts' output."""
    return jax.checkpoint_policies.save_only_these_names(MOE_ROUTED_OUT)
