"""Hive: device-resident multi-model serving with dynamic
micro-batching.

The persistent serving tier of the north star's "heavy traffic" half:
one process owns the chip (the proven ``worker.py --serve`` topology
— hello line, stdin JSONL jobs, heartbeats), keeps many Forge-packaged
models HBM-resident under an LRU residency budget, and coalesces
concurrent requests into fixed-shape mask-padded micro-batches so warm
steady state runs with ZERO recompiles.

- :mod:`veles_tpu.serve.batcher` — the dynamic micro-batching loop
  (``submit(rows) -> Future``; flushes at ``$VELES_SERVE_MAX_BATCH``
  rows or after ``$VELES_SERVE_MAX_WAIT_MS``);
- :mod:`veles_tpu.serve.residency` — the multi-model HBM residency
  manager (budget accounting + LRU spill-to-host);
- :mod:`veles_tpu.serve.hive` — the serving process
  (``python -m veles_tpu --serve-models NAME=PKG ...``);
- :mod:`veles_tpu.serve.client` — the line-protocol client used by
  tests, the fleet router, and operators' smoke probes;
- :mod:`veles_tpu.serve.fleet` — replica lifecycle (spawn / monitor /
  respawn) and the model placement policy;
- :mod:`veles_tpu.serve.router` — Swarm, the SLO-aware fleet router
  (``python -m veles_tpu --serve-fleet N NAME=PKG ...``): N hive
  replicas, placement-aware least-loaded routing, once-on-a-peer
  failover, canary traffic mirroring, and admission-control shedding;
- :mod:`veles_tpu.serve.sentinel` — gray-failure defense: per-request
  deadlines, budget-capped hedging, response-integrity verification,
  and outlier ejection with probe-based reinstatement.
"""

from veles_tpu.serve.batcher import (DeadlineExpired,  # noqa: F401
                                     MicroBatcher)
from veles_tpu.serve.client import ReplicaDied  # noqa: F401
from veles_tpu.serve.fleet import PlacementPolicy  # noqa: F401
from veles_tpu.serve.residency import ResidencyManager  # noqa: F401
from veles_tpu.serve.sentinel import Sentinel  # noqa: F401
