"""Composable training-loop triggers (reference ``optim/Trigger.scala:26``).

A trigger is a predicate over the driver-side state Table (keys ``epoch``,
``neval``, ``trainingLoss`` ... — same vocabulary as the reference).
"""

from __future__ import annotations

from typing import Callable

from bigdl_tpu.utils.table import Table


class Trigger:
    """Composable training-state predicate (reference ``optim/Trigger.scala:26``).

    Examples::

        >>> from bigdl_tpu.utils.table import T
        >>> Trigger.max_epoch(5)(T(epoch=6, neval=1))
        True
        >>> Trigger.several_iteration(10)(T(neval=20))
        True
        >>> both = Trigger.and_(Trigger.max_epoch(2), Trigger.max_iteration(9))
        >>> both(T(epoch=3, neval=5))
        False
    """

    def __init__(self, fn: Callable[[Table], bool], name: str = "trigger",
                 uses_loss: bool = False):
        self._fn = fn
        self.name = name
        # loss-sensitive triggers force the training loop to drain its
        # one-step loss pipeline before each end_when check, so they see
        # the CURRENT iteration's loss, not the previous one
        self.uses_loss = uses_loss

    def __call__(self, state: Table) -> bool:
        return bool(self._fn(state))

    # -- factories (reference Trigger object methods) -----------------------
    @staticmethod
    def every_epoch() -> "Trigger":
        """Fires at each epoch *boundary* (when the epoch counter advances
        past the first value seen — so never mid-first-epoch)."""
        box = {"last": None}

        def fn(state: Table) -> bool:
            e = int(state["epoch"])
            if box["last"] is None:
                box["last"] = e
                return False
            if e > box["last"]:
                box["last"] = e
                return True
            return False

        return Trigger(fn, "everyEpoch")

    @staticmethod
    def several_iteration(interval: int) -> "Trigger":
        def fn(state: Table) -> bool:
            return int(state["neval"]) % interval == 0

        return Trigger(fn, f"severalIteration({interval})")

    @staticmethod
    def max_epoch(maximum: int) -> "Trigger":
        def fn(state: Table) -> bool:
            return int(state["epoch"]) > maximum

        return Trigger(fn, f"maxEpoch({maximum})")

    @staticmethod
    def max_iteration(maximum: int) -> "Trigger":
        def fn(state: Table) -> bool:
            return int(state["neval"]) > maximum

        return Trigger(fn, f"maxIteration({maximum})")

    @staticmethod
    def max_score(maximum: float) -> "Trigger":
        def fn(state: Table) -> bool:
            return float(state.get("score", float("-inf"))) > maximum

        return Trigger(fn, f"maxScore({maximum})")

    @staticmethod
    def min_loss(minimum: float) -> "Trigger":
        def fn(state: Table) -> bool:
            return float(state.get("trainingLoss", float("inf"))) < minimum

        return Trigger(fn, f"minLoss({minimum})", uses_loss=True)

    @staticmethod
    def and_(*triggers: "Trigger") -> "Trigger":
        return Trigger(lambda s: all(t(s) for t in triggers), "and",
                       uses_loss=any(t.uses_loss for t in triggers))

    @staticmethod
    def or_(*triggers: "Trigger") -> "Trigger":
        return Trigger(lambda s: any(t(s) for t in triggers), "or",
                       uses_loss=any(t.uses_loss for t in triggers))
