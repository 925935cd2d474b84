"""The GA→serving handoff that never leaves HBM (the Keel payoff).

The classic path from "the GA finished" to "the winner serves" is a
full host round trip: snapshot the best genome, write an npz, package
it with Forge, spawn (or point) a hive at the package, re-upload the
params, and recompile the dispatchers — seconds of wall, dominated by
compile.  But the final generation's trained members are ALREADY
stacked on device in the cohort engine's member axis, and the serving
tier already has the HBM-to-HBM adoption primitive
(``ResidencyManager.swap_params``, the Evergreen promotion move —
measured 6.9ms vs 0.63s against its reload oracle, ~91x).  This
module extends that move to the GA:

1. the serving scaffold — a registered :class:`HostedModel` with a
   compiled (and optionally warmed) :class:`EnsembleEvalEngine` — is
   built AHEAD of the final generation from the cohort's shared init
   params, off the handoff's critical path;
2. the handoff itself is one jitted member-axis gather (top-K members
   sliced out of the cohort stack, device-to-device) plus one
   ``swap_params`` attribute store;
3. the host member copies the spill/restore machinery needs refresh
   AFTER serving starts (:meth:`GAServingHandoff.refresh_host`), the
   same off-critical-path contract the online promotion uses.

tests/test_engine_core.py pins that the handoff writes no npz and
serves params bitwise-equal to the trained ones; the time from the
last generation to the first served request has not been measured on
the chip.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np

from veles_tpu import events, telemetry
from veles_tpu.logger import Logger
from veles_tpu.serve.residency import HostedModel, ResidencyManager


class GAServingHandoff(Logger):
    """One model's pre-built serving scaffold + the HBM-to-HBM adopt.

    Construct it BEFORE (or while) the final generation trains: the
    engine build — stacking placeholder params, tracing the serving
    dispatchers, the optional warm-up dispatch — overlaps with
    training, so :meth:`adopt` pays only the gather + swap."""

    def __init__(self, manager: ResidencyManager, name: str,
                 forwards: List[Any],
                 member_params: List[Dict[str, Dict[str, Any]]],
                 meta: Optional[Dict[str, Any]] = None,
                 sample_shape=None, warm_rows: int = 1) -> None:
        self.manager = manager
        self.name = name
        self.k = len(member_params)
        model = HostedModel(name, forwards, member_params, meta=meta,
                            sample_shape=sample_shape)
        manager.register(model)
        #: the pre-built serving engine (compiled, resident, serving
        #: the placeholder params until the first adopt lands)
        self.engine = manager.ensure(name)
        self.engine.attach_batcher(manager.max_batch,
                                   manager.max_wait_s, label=name,
                                   sample_shape=model.sample_shape)
        self._slice = None
        if warm_rows and sample_shape is not None:
            self.warm(warm_rows, sample_shape)

    def warm(self, rows: int, sample_shape) -> None:
        """Push one dummy request through the serving facade so the
        fixed-shape dispatch is compiled before the handoff — the
        whole point is that the first REAL request after adopt pays a
        dispatch, not a trace."""
        dummy = np.zeros((int(rows),) + tuple(sample_shape),
                         np.float32)
        self.engine.submit(dummy).result()

    # -- the handoff ---------------------------------------------------

    def top_k(self, fitness: np.ndarray) -> np.ndarray:
        """The member indices to slice: the K best (lowest — min
        validation n_err for supervised cohorts, min mean quantization
        error for SOM cohorts; every engine's fitness is
        lower-is-better) members, stable order so ties keep the
        cohort's member order, exactly like the per-genome GA's
        sort."""
        order = np.argsort(np.asarray(fitness, np.float64),
                           kind="stable")
        return np.ascontiguousarray(order[:self.k].astype(np.int32))

    def _gather(self, stacked_params: Any, idx: np.ndarray):
        """The jitted member-axis slice (compiled once; the index
        vector is a traced argument, so every adopt reuses the same
        executable)."""
        core = self.engine._core
        if self._slice is None:
            import jax

            def gather(tree, idx):
                import jax.numpy as jnp
                return jax.tree_util.tree_map(
                    lambda a: jnp.take(a, idx, axis=0), tree)

            if self.engine.member_sharded:
                out = core.member_axis_sharding
            elif core.on_mesh:
                out = core.replicated
            else:
                out = None
            self._slice = core.jit(gather, out_shardings=out)
        # a member-sharded engine's stack is padded to a whole
        # per-device tile; pad the gather the same way (repeating the
        # best member — padding rows are never read by the fixed-order
        # member mean)
        pad = self.engine._n_stacked - self.k
        if pad:
            idx = np.concatenate([idx, np.full(pad, idx[0],
                                               np.int32)])
        return self._slice(stacked_params,
                           core.put_replicated(idx))

    def prewarm(self, cohort_engine: Any) -> None:
        """Compile the adopt gather against the LIVE cohort stack —
        callable any time after the cohort engine exists, so the
        trace+compile overlaps training like the rest of the
        scaffold and the timed adopt pays only a dispatch.  The
        gathered placeholder tree is discarded."""
        import jax

        stacked = cohort_engine._params
        if stacked is None:
            raise RuntimeError(
                "cohort engine has no live stacked params to "
                "prewarm the gather against")
        out = self._gather(stacked,
                           np.arange(self.k, dtype=np.int32))
        for leaf in jax.tree_util.tree_leaves(out):
            leaf.block_until_ready()

    def adopt(self, stacked_params: Any,
              member_indices: np.ndarray):
        """Slice ``member_indices`` out of a cohort-stacked param tree
        (device-to-device, one jitted gather — compiled once, the
        index vector is a traced argument) and swap the sliced tree
        into the serving engine.  Returns the engine, already serving
        the trained members; NOTHING touches the host on this path."""
        t0 = time.perf_counter()
        idx = np.asarray(member_indices, np.int32)
        if len(idx) != self.k:
            raise ValueError(
                f"handoff needs exactly {self.k} members (the "
                f"pre-built engine's stack), got {len(idx)}")
        sliced = self._gather(stacked_params, idx)
        engine = self.manager.swap_params(self.name, sliced)
        dt = time.perf_counter() - t0
        telemetry.event(events.EV_GA_HANDOFF, model=self.name,
                        members=self.k, seconds=round(dt, 5))
        self.info("GA handoff: %d members adopted HBM-to-HBM into "
                  "%r in %.2fms", self.k, self.name, 1000.0 * dt)
        return engine

    def adopt_cohort(self, cohort_engine: Any,
                     fitness: np.ndarray):
        """The whole move for a just-trained cohort: top-K by fitness,
        gather, swap.  ``cohort_engine`` is any engine exposing the
        member-stacked ``_params`` tree — ``PopulationTrainEngine``
        (supervised nets AND CD-k RBM cohorts, whose step body the
        shared Keel builders already trace) or
        :class:`~veles_tpu.ops.kohonen.SOMPopulationEngine` — whose
        :meth:`run` returned ``fitness``; its stacked params must
        still be live (adopt BEFORE ``release()``)."""
        stacked = cohort_engine._params
        if stacked is None:
            raise RuntimeError(
                "cohort engine already released its stacked params; "
                "adopt_cohort must run before release()")
        return self.adopt(stacked, self.top_k(fitness))

    def refresh_host(self) -> None:
        """Fetch host member copies of the served params and hand them
        to the residency manager (the spill/restore source of truth) —
        called OFF the handoff critical path, after serving started."""
        stacked = self.engine.stacked_params
        members: List[Dict[str, Dict[str, np.ndarray]]] = []
        for i in range(self.k):
            members.append({
                fn: {pn: np.asarray(arr[i])
                     for pn, arr in d.items()}
                for fn, d in stacked.items()})
        self.manager.refresh_host_params(self.name, members)
