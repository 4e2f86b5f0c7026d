"""Where a ``custom_vjp``'s rules land in the step's partition.

The step's device time is split by the ``jax.named_scope`` a compiled
instruction was written under (``telemetry/step_partition.py``; the
vocabulary is ``telemetry/catalogue.SCOPE_SPECS``). Autodiff carries a
scope from the forward to its transpose by itself; a ``custom_vjp`` rule is
a function of its own, and jax 0.9.0 traces it under the name stack of the
CALL (what the caller had entered), not under a scope the primal function
enters inside itself. So a rule whose kernel is called under its leaf scope
already has it, and one whose scope lives inside the primal does not.
``under_scope`` is the one way both are written: the rule runs under the
scope exactly once, whichever the case.
"""

from __future__ import annotations

import functools
import re

import jax

_PART = re.compile(r"[^/()]+")


def _entered(name: str) -> bool:
    """Whether the name stack a rule is being traced under holds ``name``
    as one component (also wrapped: ``transpose(jvp(name))``)."""
    try:
        from jax._src import source_info_util
        stack = str(source_info_util.current_name_stack())
    except Exception:   # a jax without it: enter again, which names twice
        return False
    return name in _PART.findall(stack)


def under_scope(name: str):
    """Decorator for a ``custom_vjp`` rule (or the primal): its operations
    carry ``name`` in their ``op_name`` once, entered here unless the call
    was made under it."""
    def wrap(rule):
        @functools.wraps(rule)
        def scoped(*args, **kwargs):
            if _entered(name):
                return rule(*args, **kwargs)
            with jax.named_scope(name):
                return rule(*args, **kwargs)
        return scoped
    return wrap
