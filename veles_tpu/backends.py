"""Device backends.

Reference parity: veles/backends.py — a ``Device`` base with OpenCL /
CUDA / numpy engines, context management, and a per-device capability
database for autotuned kernel block sizes.

TPU-first design: two engines survive — ``NumpyDevice`` (the pure-host
golden path, reference's "numpy backend") and ``JaxDevice`` (TPU, or
XLA:CPU for tests).  There is no block-size autotuning database: tiling
onto the MXU is XLA's job.  ``JaxDevice`` owns the jit cache and the
compute dtype policy (bfloat16 matmuls by default on TPU — the MXU's
native format — with float32 params).
"""

from __future__ import annotations

import functools
import os
from typing import Any, Callable, Dict, Optional

import numpy as np

from veles_tpu.logger import Logger


class Device(Logger):
    """Base device."""

    is_jax = False
    backend_name = "base"

    def __init__(self) -> None:
        self.compute_dtype = np.float32
        #: cumulative host->device bytes shipped through ``put`` —
        #: the transfer-accounting hook the quantized-ingest tests and
        #: bench read.  ``put`` is dtype-preserving by contract: a
        #: uint8 upload must stay 1 byte/element in HBM (the 4x
        #: residency win), never silently widen to the compute dtype.
        self.h2d_bytes = 0

    def put(self, array: np.ndarray, where: Any = None) -> Any:
        return array

    def get(self, buf: Any) -> np.ndarray:
        return np.asarray(buf)

    def zeros(self, shape, dtype=np.float32) -> Any:
        """A zero buffer in this device's memory (host numpy here; jax
        devices generate it on-device — no host array, no transfer)."""
        return np.zeros(shape, dtype)

    def compile(self, fn: Callable, **jit_kwargs: Any) -> Callable:
        return fn

    def synchronize(self) -> None:
        pass

    def describe(self) -> Dict[str, Any]:
        """What this engine runs on — the facts every run record
        carries (launcher line, first-dispatch event, serving hello)."""
        return {"platform": self.backend_name}

    def __repr__(self) -> str:
        return f"<{type(self).__name__}>"


class NumpyDevice(Device):
    """Pure-host execution; the bit-reproducible golden backend
    (reference: veles/backends.py NumpyDevice)."""

    backend_name = "numpy"


#: where compiled executables persist when the operator names no
#: directory: ONE fixed path inside the checkout, derived from this
#: package's own location.  Never from ``~``, a temp name, a pid, the
#: time or a version — a cache that moves between runs is never warm,
#: and a tool that copies the tree must carry it along.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def compile_cache_dir(platform: str) -> Optional[str]:
    """The persistent XLA compile cache directory an engine on
    ``platform`` uses: ``$JAX_COMPILATION_CACHE_DIR`` when the operator
    set it (JAX reads the variable itself), else
    :data:`COMPILE_CACHE_DIR` — or None for XLA:CPU, which never turns
    the in-checkout cache on (see below)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    return None if platform == "cpu" else COMPILE_CACHE_DIR


def _enable_persistent_compile_cache(platform: str) -> None:
    """Keep compiled executables on disk across processes.

    An externally placed cache wins: with ``JAX_COMPILATION_CACHE_DIR``
    set this function does nothing at all — JAX reads the variable at
    import, and a ``jax.config.update`` here would only be a second
    owner of the same setting.  Unset, a TPU engine keeps its cache in
    the one fixed in-checkout directory.

    An XLA:CPU engine leaves it off.  Re-established on jaxlib 0.9.0
    (PR 21): warm XLA:CPU loads are numerically sound now (ten warm
    reruns, one and eight devices, bit-identical to the cold run), but
    the entries are host machine code — XLA's own loader logs "could
    lead to execution errors such as SIGILL" on every load — and this
    directory travels with the tree to machines with other CPUs.  CPU
    compiles here take about a second; the cache buys nothing for that
    risk.  An operator who sets the variable gets what they asked for.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    path = compile_cache_dir(platform)
    if path:
        import jax
        jax.config.update("jax_compilation_cache_dir", path)


class JaxDevice(Device):
    """An XLA device (TPU in production; CPU for tests/simulation).

    ``compile`` wraps ``jax.jit`` with buffer donation support — donated
    inputs are the rebind targets that give Vectors in-place update
    semantics in HBM.
    """

    is_jax = True
    backend_name = "jax"
    #: devices this engine drives (a mesh device overrides it)
    n_devices = 1

    def __init__(self, platform: Optional[str] = None,
                 ordinal: int = 0, compute_dtype: Any = None) -> None:
        super().__init__()
        import jax
        self._jax = jax
        # LOCAL devices: in a multihost run jax.devices() enumerates
        # every process's chips and ordinal 0 would be process 0's —
        # non-addressable from its peers, so their first upload died
        # with "Cannot copy array to non-addressable device".  A
        # single-device engine must own one of ITS OWN chips; global
        # enumeration belongs to mesh construction (parallel/mesh.py).
        devices = jax.local_devices(backend=platform) if platform \
            else jax.local_devices()
        self.jax_device = devices[ordinal]
        self.platform = self.jax_device.platform
        _enable_persistent_compile_cache(self.platform)
        if compute_dtype is None:
            import jax.numpy as jnp
            compute_dtype = jnp.bfloat16 if self.platform == "tpu" \
                else jnp.float32
        self.compute_dtype = compute_dtype
        self._jit_cache: Dict[Any, Callable] = {}

    def put(self, array: np.ndarray, where: Any = None) -> Any:
        # ``where``: a placement of this device other than its default
        # one (a ``Format``: the device layout a resident store keeps,
        # see ``FullBatchLoader.reside_as``).
        # Copy before upload: device_put may alias host memory (XLA:CPU
        # is zero-copy) or defer the H2D transfer, and the map/unmap
        # protocol lets callers mutate the host buffer right after
        # unmap() while async-dispatched steps still read it.  The copy
        # makes uploads value-snapshots, restoring the reference's
        # enqueue-time semantics.  dtype-preserving: uint8 stays uint8
        # in HBM (quantized ingest's 4x residency cut depends on it).
        arr = np.array(array, copy=True)
        self.h2d_bytes += arr.nbytes
        from veles_tpu.engine import core as engine_core
        return engine_core.put(
            arr, self.jax_device if where is None else where)

    def get(self, buf: Any) -> np.ndarray:
        return np.asarray(buf)

    def zeros(self, shape, dtype=np.float32) -> Any:
        import jax.numpy as jnp

        from veles_tpu.engine import core as engine_core
        with self._jax.default_device(self.jax_device):
            # born on the device, then COMMITTED to it (no copy): an
            # uncommitted first-call argument lowers a different
            # executable than the committed step output that replaces
            # it on the second call (ops/fused.py run())
            return engine_core.put(jnp.zeros(shape, dtype),
                                   self.jax_device)

    def compile(self, fn: Callable, **jit_kwargs: Any) -> Callable:
        return self._jax.jit(fn, **jit_kwargs)

    def cached_compile(self, key: Any, make_fn: Callable[[], Callable],
                       **jit_kwargs: Any) -> Callable:
        """Memoized jit: units ask for their step function by key so
        re-initialization reuses the compiled executable."""
        if key not in self._jit_cache:
            self._jit_cache[key] = self.compile(make_fn(), **jit_kwargs)
        return self._jit_cache[key]

    def synchronize(self) -> None:
        from veles_tpu.engine import core as engine_core
        (engine_core.put(0.0, self.jax_device) + 0).block_until_ready()

    def describe(self) -> Dict[str, Any]:
        from importlib import metadata

        import jaxlib
        jax = self._jax
        return {"platform": self.platform,
                "device_kind": self.jax_device.device_kind,
                # as JAX reports them: every device this process sees,
                # and how many of them this engine drives
                "device_count": len(jax.devices(self.platform)),
                "engine_devices": self.n_devices,
                "compute_dtype": np.dtype(self.compute_dtype).name,
                "jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": metadata.version("libtpu"),
                "compile_cache_dir": compile_cache_dir(self.platform)}

    def __repr__(self) -> str:
        return f"<JaxDevice {self.jax_device}>"


def device_bytes_limit(jax_device: Any) -> Optional[int]:
    """The memory limit a jax device reports, in bytes.  A TPU reports
    one (``memory_stats()["bytes_limit"]``), so a missing limit THERE
    is an error, never a guessed capacity; XLA:CPU reports no stats at
    all and gets None — callers fall to their declared host defaults."""
    limit = int((jax_device.memory_stats() or {}).get("bytes_limit", 0))
    if limit:
        return limit
    if jax_device.platform == "tpu":
        raise RuntimeError(
            f"{jax_device} reports no bytes_limit in memory_stats(); "
            f"refusing to assume a capacity for a TPU")
    return None


@functools.lru_cache(maxsize=None)
def make_device(backend: str = "auto") -> Device:
    """Factory.  ``numpy`` is the host golden path, ``cpu`` is XLA:CPU,
    and ``tpu`` is a TPU or an exception — a request for the chip never
    lands on another platform.  ``auto`` is the announced convenience:
    JAX's own default platform (the TPU when it has one, else XLA:CPU),
    for callers that log what they got (launcher.py).  An import or
    backend error propagates from all of them."""
    if backend == "numpy":
        return NumpyDevice()
    if backend == "auto":
        return JaxDevice()
    if backend in ("tpu", "cpu"):
        return JaxDevice(platform=backend)
    raise ValueError(f"unknown backend {backend!r}")
