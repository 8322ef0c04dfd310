"""The ``fit`` window for a token-level training cell: ``fit(ids, next_ids)``
with labels of rank 2, one a token.  Everything of ``drivers/fit.py`` holds
(the clock, the compared steps, the window, the checks); this driver
replaces what such a cell needs:

- the labels are handed to ``fit`` as ``Rows`` over the pool, as the inputs
  are: a data set of 2.4 M rows of 4,096 labels is 40 GB as an array;
- the seed's weights are not kept beside the program's through the compared
  steps (a model that fills the chip has no room for a second float32
  copy): they are derived from the seed again for the change's norm, and the
  reference's steps give their buffers to the next step;
- the notes print the program's kernel-selection and token counters, and
  what the configuration's attention kernels are asked for a step.

``run`` is ``fit.run`` itself with this session in its session's place;
``PERF.md`` section 7 records the fold-back.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .. import compare, optim
from . import fit
from .fit import Rows, StepHook

NOTED_COUNTERS = ("ops_kernel_selected_total", "train_tokens_total")


class Session(fit.Session):
    def load_weights(self) -> None:
        """The program gets the seed's weights themselves; none is kept."""
        shapes = [(2,) + a.shape[1:] for a in self.pool]
        self.net.set_initial_weights(self.cell.config_mod.to_program(
            self._weights(), self.net, shapes))
        self.p0 = None

    def _fit(self, n: int, hook: Callable[[int], None],
             asked: List[np.ndarray], epochs: int):
        xs = [Rows(a, n, asked if i == 0 else None)
              for i, a in enumerate(self.pool)]
        few = ([a[:self.batch] for a in self.pool], self.pool_y[:self.batch])
        hist = self.net.fit(xs, Rows(self.pool_y, n), batch_size=self.batch,
                            nb_epoch=epochs,
                            shuffle=bool(self.cell.traffic["shuffle"]),
                            validation_data=few,
                            validation_trigger=StepHook(hook), verbose=False)
        # only the compared steps' call returns (the window's ends in
        # WindowClosed): the seed's weights again, for the change's norm
        self.p0 = self._weights()
        return hist

    def reference(self, asked: Optional[List[np.ndarray]] = None,
                  rows: Optional[slice] = None, config=None, **kw
                  ) -> Dict[str, Any]:
        """``fit.Session.reference`` with each step's weights and state
        given to the next (donated), and the change taken against weights
        derived from the seed once more.  ``config`` puts another
        configuration in the cell's place (a planted fault)."""
        import jax

        if asked is None:
            asked = [np.arange(k * self.batch, (k + 1) * self.batch)
                     for k in range(compare.STEPS)]
        if rows is not None:
            asked = [np.resize(idx[rows], len(idx)) for idx in asked]
        cfg = config or self.cfg
        p, batches, (whole, split) = self.reference_inputs(asked)
        step = jax.jit(compare.reference_step(self.cell.reference, cfg, **kw),
                       in_shardings=(whole, whole, split, split),
                       out_shardings=whole, donate_argnums=(0, 1))
        with jax.default_matmul_precision("highest"):
            # jitted: every moment a buffer of its own, to be donated
            state = jax.jit(lambda t: optim.init_state(
                cfg["deployment"]["optimizer"], t))(p)
            losses, grad_norms = [], None
            for xs, y in batches[:compare.STEPS]:
                p, state, loss, gn = step(p, state, tuple(xs), y)
                losses.append(float(loss))
                if grad_norms is None:
                    grad_norms = np.asarray(gn)
            state = None
            delta = np.asarray(jax.jit(lambda a, b: compare.leaf_norms(
                jax.tree_util.tree_map(lambda u, v: u - v, a, b)))(
                    p, self._weights()))
        return {"losses": losses, "grad_norms": grad_norms,
                "delta_norms": delta}


def noted_counters(registry: Dict[str, Any]) -> Dict[str, float]:
    """Of a registry's delta, the program's counters that the notes print."""
    return {k: v for k, v in registry.get("counters", {}).items()
            if k.startswith(NOTED_COUNTERS)}


def run(cell, seed: int, seconds: float, trace: bool, t0: float
        ) -> Dict[str, Any]:
    """One run of a cell: ``fit.run``, which makes its session by the name
    ``Session`` of its module, with this module's session under that name
    for the length of the call."""
    from analytics_zoo_tpu.observe.metrics import METRICS

    before = METRICS.snapshot()
    theirs, fit.Session = fit.Session, Session
    try:
        out = fit.run(cell, seed, seconds, trace, t0)
    finally:
        fit.Session = theirs
    # the kernels are selected when the step is traced, before the window
    out["notes"]["program counters"] = noted_counters(METRICS.delta(before))
    out["notes"]["attention kernels asked for a step"] = (
        cell.config_mod.attention_kernel_work(cell.config,
                                              out["run"]["batch"]))
    return out
