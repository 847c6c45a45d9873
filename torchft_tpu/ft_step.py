"""The fault-tolerant step, written once: quorum, gradients, the replica
allreduce, the fenced commit gate, and the update only if the gate said yes."""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from torchft_tpu.manager import Manager


class FTStep:
    """``step(batch) -> (committed, loss, metrics)``, or its four stages one
    by one for a caller that puts its own spans or blocks between them. Owns
    ``params`` and ``opt_state`` from construction on and registers the heal
    contract with the Manager. It imports no model: ``grad_step(params,
    batch) -> (loss, metrics, to_reduce)`` and ``apply_step(params,
    opt_state, reduced) -> (params, opt_state)`` are the caller's
    (``parallel/train.py``), and what ``to_reduce`` holds is theirs alone.

    ``reducer``: a ``ManagedMesh`` or ``DistributedDataParallel``;
    ``quantize_bits``: its wire width, None for fp32. ``sharded_heal``:
    ``state_dict()`` hands out the sharded device leaves (for a sharded
    ``PGTransport``), not host numpy; both load onto the first shardings."""

    def __init__(
        self,
        manager: Manager,
        reducer: Any,
        grad_step: Callable[[Any, Any], Tuple[Any, Dict[str, Any], Any]],
        apply_step: Callable[[Any, Any, Any], Tuple[Any, Any]],
        params: Any,
        opt_state: Any,
        quantize_bits: Optional[int] = None,
        sharded_heal: bool = False,
    ) -> None:
        self.manager = manager
        self.params, self.opt_state = params, opt_state
        self._reducer, self._quantize_bits = reducer, quantize_bits
        self._grad_step, self._apply_step = grad_step, apply_step
        self._sharded_heal = sharded_heal
        self._shardings = jax.tree_util.tree_map(
            lambda x: x.sharding, (params, opt_state)
        )
        manager.set_state_dict_fns(self.load_state_dict, self.state_dict)

    def state_dict(self) -> Dict[str, Any]:
        state = {"params": self.params, "opt_state": self.opt_state}
        if self._sharded_heal:
            return state
        return jax.tree_util.tree_map(np.asarray, state)

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.params, self.opt_state = jax.device_put(
            (state["params"], state["opt_state"]), self._shardings
        )

    def begin(self) -> None:
        self.manager.start_quorum()

    def grads(self, batch: Any) -> Tuple[Any, Dict[str, Any], Any]:
        return self._grad_step(self.params, batch)

    def reduce(self, to_reduce: Any) -> Any:
        bits = self._quantize_bits
        return self._reducer.allreduce_grads(
            to_reduce, should_quantize=bits is not None, quantize_bits=bits
        )

    def commit(self, reduced: Any) -> bool:
        # Fenced: the commit decision and the update are one critical
        # section against concurrent checkpoint sends (async quorum), or a
        # healing peer snapshots a torn (params, step).
        with self.manager.fenced_state_dict():
            committed = self.manager.should_commit()
            if committed:
                self.params, self.opt_state = self._apply_step(
                    self.params, self.opt_state, reduced
                )
        return committed

    def __call__(self, batch: Any) -> Tuple[bool, Any, Dict[str, Any]]:
        self.begin()
        loss, metrics, to_reduce = self.grads(batch)
        return self.commit(self.reduce(to_reduce)), loss, metrics
