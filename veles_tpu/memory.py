"""Vector: the twin host/device buffer abstraction.

Reference parity: veles/memory.py — ``Vector`` holds a numpy host array
(``.mem``) and a device buffer (``.devmem``), kept coherent through an
explicit protocol: ``map_read()`` (host needs to read), ``map_write()``
(host will read+write), ``map_invalidate()`` (host will fully
overwrite), ``unmap()`` (device needs the latest data).

TPU-first design: ``devmem`` is a ``jax.Array`` in HBM.  Unlike OpenCL
mapped pointers, JAX arrays are immutable — so "device writes" happen by
REBINDING ``devmem`` to a step function's output (with the input buffer
donated, giving in-place update semantics in HBM; SURVEY.md §7 "in-place
weight updates").  The map/unmap protocol survives as the host-coherence
contract, and its invariant checks catch stale-host-read bugs that the
reference's assertions caught.

The device mirror may be NARROWER than the host array: a resident
dataset sits in HBM in the dtype (and row layout) the fused step reads
(``retype_devmem``, asked for by ``FullBatchLoader.reside_as``) while
its host copy stays float32.  ``unmap()`` after a host write then
re-uploads in the mirror's form, cast on the host.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import numpy as np

HOST = 1
DEVICE = 2


class Vector:
    """Host numpy array + optional device ``jax.Array``, explicitly
    synchronized."""

    def __init__(self, data: Optional[np.ndarray] = None,
                 name: str = "") -> None:
        self.name = name
        self._mem: Optional[np.ndarray] = None
        self._devmem: Any = None
        #: (dtype, placement) of a device mirror that ``retype_devmem``
        #: made narrower than the host array; None = the mirror is the
        #: host array as uploaded
        self._mirror: Optional[Tuple[np.dtype, Any]] = None
        self._valid = 0
        self.device = None
        if data is not None:
            self.mem = data

    # -- allocation ----------------------------------------------------

    @property
    def mem(self) -> Optional[np.ndarray]:
        return self._mem

    @mem.setter
    def mem(self, value: Optional[np.ndarray]) -> None:
        self._mirror = None     # a new host array: a new vector
        if value is None:
            self._mem = None
            self._valid = 0
            return
        self._mem = np.ascontiguousarray(value)
        self._valid = HOST

    def reset(self, new_mem: Optional[np.ndarray] = None) -> None:
        self._devmem = None
        self.mem = new_mem

    def drop_devmem(self) -> None:
        """Free the HBM copy only.  Any VALID host copy survives; if
        the device held the only valid copy the vector truly reads as
        unallocated — the stale host array is dropped too, so nothing
        (pickling, plotters, __bool__ guards) can serve outdated
        values.  Callers that need the data must map_read() first."""
        self._devmem = None
        self._valid &= HOST
        if not self._valid:
            self._mem = None

    def __bool__(self) -> bool:
        return self._mem is not None or self._devmem is not None

    @property
    def shape(self) -> Tuple[int, ...]:
        if self._mem is not None:
            return self._mem.shape
        if self._devmem is not None:
            return tuple(self._devmem.shape)
        raise AttributeError(f"Vector '{self.name}' not allocated")

    @property
    def dtype(self):
        if self._mem is not None:
            return self._mem.dtype
        return np.dtype(self._devmem.dtype)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape))

    @property
    def nbytes(self) -> int:
        """Bytes of the freshest buffer — what residency budgeting and
        transfer accounting charge.  Dtype-preserving end to end: a
        quantized uint8 dataset reports 1 byte/element here, uploads
        at 1 byte/element (``Device.put``), and sits in HBM at 1
        byte/element — a quarter of its float32 view."""
        if self._mem is not None:
            return int(self._mem.nbytes)
        if self._devmem is not None:
            return int(np.dtype(self._devmem.dtype).itemsize
                       * int(np.prod(tuple(self._devmem.shape))))
        return 0

    @property
    def sample_size(self) -> int:
        """Elements per leading-axis sample (reference: Vector.sample_size)."""
        s = self.shape
        return int(np.prod(s[1:])) if len(s) > 1 else 1

    def __len__(self) -> int:
        return self.shape[0]

    # -- device attach -------------------------------------------------

    def initialize(self, device, upload: bool = True) -> None:
        """Attach to a device; pushes host data to HBM on jax devices.

        ``upload=False`` attaches WITHOUT the eager host->device push —
        for scratch buffers (unit outputs, err_inputs, host minibatch
        staging) that every consumer either rebinds (``devmem = step
        output``) or overwrites before reading.  Correctness is
        unchanged (``unmap()`` still uploads on demand); what it avoids
        is uploading gigabytes of just-allocated zeros at initialize
        time."""
        self.device = device
        if upload and device is not None and device.is_jax \
                and self._mem is not None:
            self.unmap()

    def upload_row_sharded(self, device) -> None:
        """Attach + upload with the leading axis row-sharded 1/N per
        mesh device (``MeshJaxDevice.put_sharded``: rows zero-padded
        to a whole per-device tile).  The host copy STAYS valid —
        ``map_read`` keeps serving the unpadded host rows and
        snapshots carry them — while ``unmap``/``current`` hand
        consumers the sharded (padded) device buffer.  For read-only
        buffers (resident datasets): a later host write + ``unmap``
        would re-upload REPLICATED through the normal path."""
        self.device = device
        self._devmem = device.put_sharded(self._mem)
        self._valid = HOST | DEVICE

    @property
    def devmem(self) -> Any:
        return self._devmem

    @devmem.setter
    def devmem(self, value: Any) -> None:
        """Rebind the device buffer (a jitted step's output) and mark the
        host copy stale — the TPU analogue of a device-side write."""
        self._devmem = value
        self._mirror = None
        self._valid = DEVICE if value is not None else (self._valid & HOST)

    def retype_devmem(self, value: Any, where: Any = None) -> None:
        """Rebind the device buffer to the SAME values in a narrower
        dtype (``value``: the old buffer cast on the device).  Not a
        device write: a valid host copy stays valid and keeps its own
        dtype.  From here on ``unmap()`` after a host write uploads in
        ``value``'s dtype, cast on the host, to ``where`` (a placement
        ``Device.put`` takes; None = the device's own) — a stale
        mirror never comes back wider than the one it replaces.  Holds
        until the host array or the device buffer is replaced."""
        self._devmem = value
        self._mirror = (np.dtype(value.dtype), where)

    # -- coherence protocol -------------------------------------------

    def map_read(self) -> np.ndarray:
        """Host is about to read: copy device->host if host is stale."""
        if not self._valid & HOST:
            if self._devmem is None:
                raise RuntimeError(f"Vector '{self.name}': nothing valid")
            self._mem = np.asarray(self._devmem)
            self._valid |= HOST
        return self._mem

    def map_write(self) -> np.ndarray:
        """Host will read and write: sync down, then device is stale."""
        m = self.map_read()
        self._valid = HOST
        return m

    def map_invalidate(self) -> np.ndarray:
        """Host will fully overwrite: no sync down, device is stale."""
        if self._mem is None:
            if self._devmem is None:
                raise RuntimeError(f"Vector '{self.name}': nothing valid")
            self._mem = np.empty(self.shape, self.dtype)
        self._valid = HOST
        return self._mem

    def current(self) -> Any:
        """Freshest buffer without forcing a transfer: the device array
        when one is bound (possibly an un-fetched step output), else the
        host array.  Callers that need numpy use ``np.asarray`` on the
        result (that is the sync point)."""
        return self._devmem if self._devmem is not None else self._mem

    def unmap(self) -> Any:
        """Device is about to compute: push host->device if device stale.
        Returns the device buffer (or host mem on numpy devices)."""
        if self.device is None or not self.device.is_jax:
            return self._mem
        if not self._valid & DEVICE:
            if self._mem is None:
                raise RuntimeError(f"Vector '{self.name}': nothing valid")
            if self._mirror is None:
                self._devmem = self.device.put(self._mem)
            else:
                dtype, where = self._mirror
                # only a floating host array is ever narrowed on its
                # way up: integer rows (token ids) upload as they are
                host = self._mem.astype(dtype) \
                    if self._mem.dtype.kind not in "iub" else self._mem
                self._devmem = self.device.put(host, where)
            self._valid = HOST | DEVICE
        return self._devmem

    # -- snapshot support ---------------------------------------------

    def __getstate__(self) -> dict:
        if self._valid and not (self._valid & HOST):
            self.map_read()
        return {"name": self.name, "mem": self._mem}

    def __setstate__(self, state: dict) -> None:
        self.name = state["name"]
        self._mem = state["mem"]
        self._devmem = None
        self._mirror = None
        self.device = None
        self._valid = HOST if self._mem is not None else 0

    def __repr__(self) -> str:
        shape = None
        try:
            shape = self.shape
        except AttributeError:
            pass
        return f"Vector('{self.name}', shape={shape}, valid={self._valid})"
