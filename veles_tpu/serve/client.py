"""Line-protocol client for the Hive serving process.

Spawns (or attaches to) a ``--serve-models`` subprocess and multiplexes
CONCURRENT callers over its stdin/stdout: every request draws a wire
id under a lock, a single reader thread routes response lines back to
per-id waiters, and heartbeats/garbage are tolerated as proof of life
(the ChipEvaluatorPool discipline).  This is the surface the serving
tests, the FleetRouter's replicas (veles_tpu/serve/fleet.py), and
operator smoke probes share.

Death semantics: when the replica's stdout reaches EOF (process exit,
SIGKILL, pipe loss), EVERY pending waiter fails immediately with
:class:`ReplicaDied` — never by waiting out its own timeout.  The
fleet router catches exactly that error to retry the request on a
healthy peer.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

from veles_tpu import events, telemetry, trace
from veles_tpu.analysis import witness


class ReplicaDied(RuntimeError):
    """The serving subprocess died (EOF/exit) with requests pending.

    Distinguishable from :class:`TimeoutError` by construction: a
    caller blocked on a dead replica is failed the moment the reader
    thread sees EOF, so failover can retry on a peer immediately
    instead of burning the request timeout.  ``rc`` carries the
    replica's exit code when known (None while it is still dying).
    """

    def __init__(self, msg: str, rc: Optional[int] = None) -> None:
        super().__init__(msg)
        self.rc = rc


class HiveClient:
    """Own one serving subprocess; thread-safe request fan-in."""

    def __init__(self, models: Dict[str, str],
                 backend: str = "cpu",
                 max_batch: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 hbm_budget: Optional[int] = None,
                 heartbeat_every: Optional[float] = None,
                 metrics_dir: Optional[str] = None,
                 install_dir: Optional[str] = None,
                 env: Optional[Dict[str, str]] = None,
                 cwd: Optional[str] = None,
                 online: bool = False,
                 mesh: int = 0,
                 start_timeout: float = 120.0) -> None:
        cmd = [sys.executable, "-m", "veles_tpu", "--serve-models"]
        cmd += [f"{name}={path}" for name, path in models.items()]
        cmd += ["-b", backend]
        if online:
            cmd += ["--online"]
        if mesh and mesh > 1:
            cmd += ["--mesh", str(int(mesh))]
        if max_batch is not None:
            cmd += ["--max-batch", str(max_batch)]
        if max_wait_ms is not None:
            cmd += ["--max-wait-ms", str(max_wait_ms)]
        if hbm_budget is not None:
            cmd += ["--hbm-budget", str(hbm_budget)]
        if heartbeat_every is not None:
            cmd += ["--heartbeat-every", str(heartbeat_every)]
        if metrics_dir is not None:
            cmd += ["--metrics-dir", metrics_dir]
        if install_dir is not None:
            # a respawned replica reuses its predecessor's install dir,
            # so the checksum-verified package unpack is warm
            cmd += ["--install-dir", install_dir]
        run_env = dict(os.environ)
        if backend == "cpu":
            # a CPU replica never even probes a chip the machine may
            # have (its parent, or a sibling, may own it); every other
            # backend sees the environment as the operator left it —
            # a default here would put `-b tpu` replicas on the CPU
            run_env["JAX_PLATFORMS"] = "cpu"
        if env:
            run_env.update(env)
        if mesh and mesh > 1 and \
                run_env.get("JAX_PLATFORMS", "") == "cpu" and \
                "--xla_force_host_platform_device_count" not in \
                run_env.get("XLA_FLAGS", ""):
            # a CPU-backed mesh replica needs N virtual devices the
            # same way dryrun_multichip pins them (a real TPU backend
            # already enumerates its chips)
            run_env["XLA_FLAGS"] = (
                run_env.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={int(mesh)}"
            ).strip()
        self.proc = subprocess.Popen(
            cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, bufsize=1, env=run_env, cwd=cwd)
        self._wlock = witness.lock("client.wire")
        self._cond = witness.condition("client.results")
        self._results: Dict[int, Dict[str, Any]] = {}
        #: async collectors (wire id -> callback) — the canary-mirror
        #: path records telemetry without parking a thread per request
        self._callbacks: Dict[int, Callable[[Optional[Dict[str, Any]],
                                             Optional[BaseException]],
                                            None]] = {}
        #: wire ids whose waiter gave up (timeout cleanup / hedge
        #: loser): the late response is dropped + counted
        #: ``fleet.stale_response`` instead of leaking into _results
        self._cancelled: set = set()
        self._next_id = 0
        self._eof = False
        self.exit_rc: Optional[int] = None
        self.hello: Optional[Dict[str, Any]] = None
        self.heartbeats = 0
        #: monotonic time of the last stdout line (ANY line is proof of
        #: life — the pool/fleet heartbeat-deadline discipline)
        self.last_line_ts = time.monotonic()
        self._reader = threading.Thread(target=self._read_loop,
                                        daemon=True,
                                        name="hive-client-reader")
        self._reader.start()
        deadline = time.monotonic() + start_timeout
        with self._cond:
            while self.hello is None and not self._eof:
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                self._cond.wait(min(left, 0.5))
        if self.hello is None:
            rc = self.proc.poll()
            self.close(kill=True)
            raise RuntimeError(
                f"hive did not come up (rc={rc})")

    # -- wire ----------------------------------------------------------

    @property
    def pid(self) -> Optional[int]:
        return self.proc.pid

    @property
    def dead(self) -> bool:
        """True once the reader saw EOF or the process exited."""
        return self._eof or self.proc.poll() is not None

    def _read_loop(self) -> None:
        for line in self.proc.stdout:
            line = line.strip()
            self.last_line_ts = time.monotonic()
            if not line:
                continue
            try:
                msg = json.loads(line)
            except ValueError:
                continue   # non-protocol noise is proof of life
            cb = None
            stale = False
            with self._cond:
                if msg.get("ready"):
                    self.hello = msg
                elif "hb" in msg:
                    self.heartbeats += 1
                elif msg.get("id") is not None:
                    mid = msg["id"]
                    if mid in self._cancelled:
                        # a hedge loser / timed-out waiter's late
                        # answer: drop it — parking it in _results
                        # would leak it into another waiter forever
                        self._cancelled.discard(mid)
                        stale = True
                    elif not isinstance(mid, int) \
                            or mid > self._next_id:
                        stale = True   # an id this client never drew
                    else:
                        cb = self._callbacks.pop(mid, None)
                        if cb is None:
                            self._results[mid] = msg
                self._cond.notify_all()
            if stale:
                telemetry.counter(
                    events.CTR_FLEET_STALE_RESPONSES).inc()
            if cb is not None:
                self._run_callback(cb, msg, None)
        # EOF: the replica is gone — fail EVERY pending waiter and
        # async collector NOW (a caller must never wait out its own
        # timeout against a dead replica)
        rc = self.proc.poll()
        err = ReplicaDied(
            f"hive pid {self.proc.pid} closed its pipe (rc={rc})",
            rc=rc)
        with self._cond:
            self._eof = True
            self.exit_rc = rc
            callbacks = list(self._callbacks.values())
            self._callbacks.clear()
            self._cancelled.clear()   # nothing late can arrive now
            self._cond.notify_all()
        for cb in callbacks:
            self._run_callback(cb, None, err)

    @staticmethod
    def _run_callback(cb, msg, err) -> None:
        try:
            cb(msg, err)
        except Exception:  # noqa: BLE001 — a collector must not kill
            pass           # the reader thread

    def _send(self, obj: Dict[str, Any]) -> None:
        try:
            with self._wlock:
                self.proc.stdin.write(json.dumps(obj) + "\n")
                self.proc.stdin.flush()
        except (BrokenPipeError, OSError, ValueError) as e:
            raise ReplicaDied(
                f"hive pid {self.proc.pid} stdin is gone ({e})",
                rc=self.proc.poll()) from e

    def _draw_id(self) -> int:
        with self._wlock:
            self._next_id += 1
            return self._next_id

    def _wait(self, jid: int, timeout: float) -> Dict[str, Any]:
        deadline = time.monotonic() + timeout
        with self._cond:
            while jid not in self._results:
                if self._eof:
                    raise ReplicaDied(
                        f"hive pid {self.proc.pid} died before "
                        f"answering request {jid}", rc=self.exit_rc)
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(
                        f"no response for request {jid} in {timeout}s")
                self._cond.wait(min(left, 0.5))
            return self._results.pop(jid)

    # -- API -----------------------------------------------------------

    def submit(self, model: str, rows: Any,
               deadline_ms: Optional[float] = None,
               label: Optional[Any] = None,
               ctx: Optional[trace.TraceContext] = None) -> int:
        """Fire one request without waiting; returns its wire id
        (collect with :meth:`wait_for` or :meth:`collect_async`).
        ``deadline_ms`` (absolute unix-epoch milliseconds) rides the
        wire: the hive batcher drops the request unanswered once it
        expires instead of computing for an absent waiter.
        ``label`` (per-row ground truth) feeds an ``--online`` hive's
        learning tap.  ``ctx`` (a sampled Flightline span — usually
        the router's per-leg child) stamps the trace-propagation wire
        fields so the hive's spans join the caller's trace."""
        jid = self._draw_id()
        msg = {"id": jid, "model": model,
               "rows": np.asarray(rows, np.float32).tolist()}
        if deadline_ms is not None:
            msg["deadline_ms"] = float(deadline_ms)
        if label is not None:
            msg["label"] = np.asarray(label).tolist()
        trace.to_wire(msg, ctx)
        self._send(msg)
        return jid

    def send_label(self, jid: int, label: Any) -> None:
        """Deliver late ground truth for an earlier request by wire
        id (fire-and-forget; an ``--online`` hive joins it into the
        replay buffer, anything else ignores it)."""
        self._send({"label_of": jid,
                    "label": np.asarray(label).tolist()})

    def learn(self, timeout: float = 60.0) -> Dict[str, Any]:
        """The online learner's per-model introspection rows
        (op=learn): {} when the hive is not learning."""
        jid = self._draw_id()
        self._send({"op": "learn", "id": jid})
        return self._wait(jid, timeout)["learn"]

    def learner_ctl(self, suspend: bool,
                    timeout: float = 60.0) -> Dict[str, Any]:
        """Suspend or resume the hive's online learner (the elastic
        fleet's first degradation rung — under pressure there are no
        idle gaps to scavenge).  Ack: ``{"suspended": bool,
        "online": bool}``; ``online`` False means no learner is armed
        and the op was a no-op."""
        jid = self._draw_id()
        self._send({"op": "learner_suspend" if suspend
                    else "learner_resume", "id": jid})
        return self._wait(jid, timeout)["learner_ctl"]

    def cancel(self, jid: int) -> bool:
        """Abandon interest in request ``jid`` — the timeout-cleanup /
        hedge-loser path.  Returns True when the response had already
        arrived (it is dropped now); False when it is still pending,
        in which case its eventual arrival is dropped and counted
        ``fleet.stale_response`` instead of leaking into another
        waiter."""
        with self._cond:
            if jid in self._results:
                self._results.pop(jid)
                return True
            self._callbacks.pop(jid, None)
            if not self._eof:
                self._cancelled.add(jid)
            return False

    def collect_async(self, jid: int,
                      callback: Callable[[Optional[Dict[str, Any]],
                                          Optional[BaseException]],
                                         None]) -> None:
        """Route request ``jid``'s response to ``callback(msg, err)``
        on the reader thread instead of a blocking waiter (exactly one
        of msg/err is set; err is :class:`ReplicaDied` when the
        replica dies first).  The callback must be quick and must not
        raise — the fleet's canary mirror records telemetry here."""
        ready = None
        with self._cond:
            if jid in self._results:
                ready = self._results.pop(jid)
            elif self._eof:
                ready = ReplicaDied(
                    f"hive pid {self.proc.pid} died before answering "
                    f"request {jid}", rc=self.exit_rc)
            else:
                self._callbacks[jid] = callback
        if isinstance(ready, ReplicaDied):
            self._run_callback(callback, None, ready)
        elif ready is not None:
            self._run_callback(callback, ready, None)

    def wait_for(self, jid: int,
                 timeout: float = 60.0) -> Dict[str, Any]:
        return self._wait(jid, timeout)

    def request(self, model: str, rows: Any,
                timeout: float = 60.0) -> Dict[str, Any]:
        """One round trip: returns the response dict ({"pred",
        "probs"} or {"error"})."""
        return self.wait_for(self.submit(model, rows), timeout)

    def stats(self, timeout: float = 60.0) -> Dict[str, Any]:
        """The serving process's live telemetry snapshot."""
        jid = self._draw_id()
        self._send({"op": "stats", "id": jid})
        return self._wait(jid, timeout)["stats"]

    def sigterm(self) -> None:
        self.proc.send_signal(signal.SIGTERM)

    def kill(self) -> None:
        self.proc.kill()

    def wait(self, timeout: float = 60.0) -> int:
        return self.proc.wait(timeout=timeout)

    def close(self, kill: bool = False) -> None:
        if self.proc.poll() is None:
            try:
                if not kill:
                    self._send({"op": "shutdown"})
                    self.proc.wait(timeout=15)
            except Exception:  # noqa: BLE001 — cleanup must not raise
                pass
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=10)

    def __enter__(self) -> "HiveClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
