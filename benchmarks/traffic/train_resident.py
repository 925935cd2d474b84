"""Traffic kind ``train_resident``: train a ``layers`` configuration on
a dataset that lives in device memory, through the program's normal
loop — ``StandardWorkflow.run()``: loader -> fused step -> decision,
with the program's own epoch-end metric fetch at its natural rate.

The harness's interventions are instance-attribute wrappers round unit
``run`` methods (``Unit.fire()`` calls ``self.run()``):

- ``decision.run`` (every run): after the program's own firing it
  captures the first dispatch's feed and state (set-up), opens the
  window at the first train class end behind a barrier, and closes it
  at the first firing after ``seconds`` behind the same barrier;
- ``loader.run`` / ``fused.run`` (traced run only): host timers and
  ``TraceAnnotation`` spans on the profiler's clock.

Weights and dataset are the benchmark's, made from the seed
(``lib/seeded.py``) and handed to the program before its first step.
"""

from __future__ import annotations

import gc
import statistics
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ..lib import check, flops, seeded

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _seeded_loader_class():
    from veles_tpu.loader.base import TEST, TRAIN, VALID
    from veles_tpu.loader.fullbatch import FullBatchLoader

    class SeededResidentLoader(FullBatchLoader):
        """The benchmark's dataset, born in device memory, handed to
        the program's full-batch loader machinery (shuffle, superstep
        index blocks, in-step gather stay the program's)."""

        def __init__(self, workflow=None, make_data=None, **kw):
            super().__init__(workflow, **kw)
            self._make_data = make_data

        def load_data(self) -> None:
            data, labels = self._make_data(self.device)
            self.class_lengths[TEST] = 0
            self.class_lengths[VALID] = 0
            self.class_lengths[TRAIN] = int(data.shape[0])
            self.original_data.devmem = data
            self.original_labels.devmem = labels

    return SeededResidentLoader


def _placement(device):
    """Where arrays of this engine live: the replicated sharding of a
    mesh device, else the one chip."""
    return getattr(device, "_repl", None) or device.jax_device


class Cell:
    """One run of a ``train_resident`` cell."""

    def __init__(self, mix: Dict[str, Any], cfg: Dict[str, Any],
                 seed: int, seconds: float, trace: bool,
                 device=None, t_start: Optional[float] = None,
                 chip_start_s: float = 0.0) -> None:
        self.mix, self.cfg = mix, cfg
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.device = device
        self.t_start = time.time() if t_start is None else t_start
        #: seconds of ``t_start`` .. now that the chip's runtime took
        #: to start; no part of ``setup_s``
        self.chip_start_s = chip_start_s
        self.rows = flops.layer_shapes(cfg["layers"], cfg["input_shape"])
        self.samples: Dict[str, List[float]] = {
            "loader.run": [], "fused.run": [], "decision.run": [],
            "decision.epoch_end": []}
        self.compile_events: List[float] = []
        self.window: Dict[str, Any] = {}
        self.traced: Dict[str, Any] = {}
        self.first: Dict[str, Any] = {}
        self.trace_dir: Optional[str] = None
        self.losses: List[float] = []
        self._firing = 0
        self._state = "warm"
        #: (label, seconds since process start) through set-up
        self.marks: List[Any] = []

    def mark(self, label: str) -> None:
        self.marks.append((label, round(time.time() - self.t_start, 3)))

    # -- set-up ----------------------------------------------------------

    def build(self) -> None:
        import jax

        from veles_tpu import prng
        from veles_tpu.backends import make_device
        from veles_tpu.ops.standard_workflow import StandardWorkflow

        mix, cfg = self.mix, self.cfg
        chips = int(mix.get("chips", 1))
        self.mark("imports")
        if self.device is None:
            self.device = make_device("tpu")
        self.mark("device")
        prng.seed_all(self.seed % (2 ** 63))
        # the dropout stream's seed is the benchmark's, so the
        # reference can state the masks without asking the program
        # (re-initialised in place: ``seed_all`` re-creates a stream
        # that already exists with its derived seed)
        prng.get("fused").__init__("fused",
                                   seeded.dropout_seed(self.seed))
        ds = cfg["dataset"]

        def make_data(device):
            data, labels = seeded.dataset(
                self.seed, int(mix["n_train"]), cfg["input_shape"],
                cfg["n_classes"], ds["noise"], ds["max_shift"])
            out = jax.device_put((data, labels), _placement(device))
            jax.block_until_ready(out)
            self.mark("dataset")
            return out

        loader_cls = _seeded_loader_class()
        w = StandardWorkflow(
            loader_factory=lambda wf: loader_cls(
                wf, name="loader", make_data=make_data,
                minibatch_size=int(mix["minibatch"])),
            layers=cfg["layers"], loss_function=cfg["loss"],
            decision_config={"max_epochs": 10 ** 9},
            superstep=int(mix["superstep"]), name="Bench")
        w.evaluator.compute_confusion = False
        if chips > 1:
            from veles_tpu.parallel import DataParallel
            self.device = DataParallel(
                w, chips,
                devices=jax.devices(self.device.platform)).install()
        w.initialize(device=self.device)
        self.mark("initialize")
        self.w = w
        # the benchmark's weights replace the program's fill
        params = jax.device_put(seeded.init_params(self.seed, self.rows),
                                _placement(self.device))
        for f, p in zip(w.forwards, params):
            vecs = f.param_vectors()
            assert set(vecs) == set(p), (f.name, set(vecs), set(p))
            for name, vec in vecs.items():
                assert tuple(vec.shape) == tuple(p[name].shape), \
                    (f.name, name, vec.shape, p[name].shape)
                vec.devmem = p[name]
        del params
        self.mark("weights")
        self._wrap()

    def _wrap(self) -> None:
        w = self.w
        orig_decision = w.decision.run
        annotate = self._annotation()

        def timed(name, fn):
            def run():
                t0 = time.perf_counter()
                with annotate("bench:" + name):
                    fn()
                self.samples[name].append(time.perf_counter() - t0)
            return run

        def decision_run():
            t0 = time.perf_counter()
            with annotate("bench:decision.run"):
                orig_decision()
            dt = time.perf_counter() - t0
            if self._state != "warm":
                self.samples["decision.run"].append(dt)
                if bool(w.loader.class_ended):
                    self.samples["decision.epoch_end"].append(dt)
            self._after_firing()

        w.decision.run = decision_run
        if self.trace:
            w.loader.run = timed("loader.run", w.loader.run)
            w.fused.run = timed("fused.run", w.fused.run)

    def _annotation(self):
        if not self.trace:
            import contextlib
            return lambda name: contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation

    # -- the window ------------------------------------------------------

    def _barrier(self) -> float:
        """Wait for everything dispatched: the donated parameter tree
        and the metric carry are outputs of the last step program."""
        import jax
        fused = self.w.fused
        jax.block_until_ready((fused._params, fused._opt, fused._acc))
        np.asarray(fused._acc)   # the data-dependent fetch, as bench.py
        return time.perf_counter()

    def _after_firing(self) -> None:
        w, fused = self.w, self.w.fused
        self._firing += 1
        if self._firing == 1:
            self.mark("first_dispatch_submitted")
            self._capture_first()
            self.mark("first_call_read")
        if self._state == "warm":
            if bool(w.loader.class_ended):
                t = self._barrier()
                self.window = {"t_open": t,
                               "images_open": float(fused.processed_images),
                               "firing_open": self._firing,
                               "setup_s": time.time() - self.t_start
                               - self.chip_start_s}
                self._state = "open"
                self.mark("window_open")
            return
        if self._state != "open":
            return
        now = time.perf_counter()
        if self.trace:
            self._trace_control(now)
        if now - self.window["t_open"] >= self.seconds:
            if self.traced.get("on"):
                self._trace_stop()
            t = self._barrier()
            self.window.update(
                t_close=t, images_close=float(fused.processed_images),
                firing_close=self._firing)
            self._state = "closed"
            w.decision.complete.set(True)

    def _capture_first(self) -> None:
        """Set-up, after the first dispatch: what was fed, and the
        per-leaf norms of the state it left — the program's side of
        the comparison that decides ``correct``."""
        import jax
        import jax.numpy as jnp
        w, fused = self.w, self.w.fused
        ld = w.loader
        idx = np.array(ld.superstep_indices, copy=True)
        mask = np.array(ld.superstep_mask, copy=True)
        w0 = seeded.init_params(self.seed, self.rows)
        w0 = jax.device_put(w0, _placement(self.device))
        params = [fused._params[f.name] for f in w.forwards]
        opt = [fused._opt.get(g.name, {}) if g is not None else {}
               for g in w.gds]

        @jax.jit
        def norms(params, opt, w0):
            n = lambda a: jnp.sqrt(jnp.sum(jnp.square(  # noqa: E731
                a.astype(jnp.float32))))
            upd = [{k: n(p[k] - z[k]) for k in p}
                   for p, z in zip(params, w0)]
            mom = [{k: n(v) for k, v in o.items()} for o in opt]
            return upd, mom

        upd, mom = jax.device_get(norms(params, opt, w0))
        del w0, params, opt
        acc = np.asarray(fused._acc, dtype=np.float64)
        flat = lambda t: {f"{i}.{k}": float(v)  # noqa: E731
                          for i, d in enumerate(t) for k, v in d.items()}
        self.first = {"indices": idx, "mask": mask,
                      "loss_sum": float(acc[1]), "count": float(acc[2]),
                      "n_err": float(acc[0]),
                      "update": flat(upd), "momentum": flat(mom)}

    # -- the traced sub-window -------------------------------------------

    def _trace_control(self, now: float) -> None:
        import jax
        tr = self.traced
        if not tr and self._firing >= self.window["firing_open"] + 2:
            import tempfile
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            t = self._barrier()
            jax.profiler.start_trace(self.trace_dir)
            self._window_span = jax.profiler.TraceAnnotation(
                "bench:traced_window")
            self._window_span.__enter__()
            tr.update(on=True, t0=time.perf_counter(), barrier_t0=t,
                      firing0=self._firing,
                      images0=float(self.w.fused.processed_images))
        elif tr.get("on") and now - tr["t0"] >= float(
                self.mix.get("trace_seconds", 3.0)) and \
                self._firing - tr["firing0"] >= int(
                    self.mix.get("trace_firings", 6)):
            self._trace_stop()

    def _trace_stop(self) -> None:
        import jax
        tr = self.traced
        t = self._barrier()
        self._window_span.__exit__(None, None, None)
        tr.update(on=False, t1=t, firings=self._firing - tr["firing0"],
                  images=float(self.w.fused.processed_images)
                  - tr["images0"])
        jax.profiler.stop_trace()

    # -- drive -----------------------------------------------------------

    def run(self, sabotage=None) -> None:
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_event)
        self.build()
        if sabotage is not None:
            sabotage(self)
        self.w.run()
        if self._state != "closed":
            raise RuntimeError(
                f"the workflow ended in state {self._state!r} before "
                f"the window closed")
        self.losses = [float(h["loss"]) for h in self.w.decision.history]

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.compile_events.append(time.perf_counter())

    def compiles_in_window(self) -> int:
        wdw = self.window
        return sum(1 for t in self.compile_events
                   if wdw["t_open"] <= t <= wdw["t_close"])

    def memory_peak_bytes(self) -> Optional[int]:
        devs = list(self.device.mesh.devices.flat) \
            if getattr(self.device, "mesh", None) is not None \
            else [self.device.jax_device]
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devs]
        peaks = [p for p in peaks if p is not None]
        return int(max(peaks)) if peaks else None

    def release(self) -> None:
        """Free the program's device state (parameters, momentum,
        dataset) before the reference runs."""
        w = self.w
        w.fused.release_device_state()
        for v in (w.loader.original_data, w.loader.original_labels):
            v.drop_devmem()
        w.stop()
        self.w = None
        gc.collect()

    # -- what the run reports ---------------------------------------------

    def end_to_end(self) -> Dict[str, float]:
        """ALL images the step counted over ALL the time between the
        two barriers that bracket the window."""
        wdw = self.window
        return {"setup_s": wdw["setup_s"],
                "train_images_per_s":
                    (wdw["images_close"] - wdw["images_open"])
                    / (wdw["t_close"] - wdw["t_open"])}

    def attempted(self):
        """(attempted, failed): images trained in the window; a step
        that fails ends the run."""
        wdw = self.window
        return wdw["images_close"] - wdw["images_open"], 0

    def summary(self) -> Dict[str, Any]:
        wdw = self.window
        return {"window_s": wdw["t_close"] - wdw["t_open"],
                "firings": wdw["firing_close"] - wdw["firing_open"],
                **self.end_to_end()}

    def judge(self):
        """(correct, compared, detail) — the reference follows the
        first call's steps; see lib/check.py for what is compared."""
        mix, first = self.mix, self.first
        bad = check.feed_faults(first, int(mix["n_train"]))
        ref = check.follow_reference(
            self.cfg, self.seed, first["indices"],
            block_rows=int(mix.get("reference_block_rows", 0)))
        numbers = check.gaps(first, ref)
        ok, compared = check.judge(numbers, mix["limits"])
        losses = self.losses
        finite = all(abs(x) < float("inf") for x in losses)  # no NaN
        compared["feed_faults"] = {"value": float(len(bad)),
                                   "limit": 0.0}
        compared["nonfinite_epoch_losses"] = {
            "value": float(0 if finite else 1), "limit": 0.0}
        detail = {"at": numbers["at"], "leaves": numbers["leaves"],
                  "norms": numbers["norms"], "feed": bad,
                  "loss_sum": [first["loss_sum"], ref["loss_sum"]],
                  "epoch_losses": losses[:3] + losses[-2:]}
        return ok and not bad and finite, compared, detail

    # -- what the metric readers see --------------------------------------

    def context(self) -> Dict[str, Any]:
        wdw = self.window
        return {"mix": self.mix, "cfg": self.cfg,
                "samples": self.samples, "window": wdw,
                "traced": self.traced, "trace_dir": self.trace_dir,
                "compiles_in_window": self.compiles_in_window(),
                "chips": int(self.mix.get("chips", 1)),
                "median_ms": lambda name: (
                    1e3 * statistics.median(self.samples[name])
                    if self.samples.get(name) else None)}
