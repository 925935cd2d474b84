"""DataParallel: install SPMD data parallelism on a StandardWorkflow.

Replaces the reference's master--slave gradient aggregation
(veles/server.py: apply_data_from_slave summing weight diffs into
canonical weights) with a single-controller sharded jit: the fused
step's minibatch ``indices``/``mask`` are sharded over the mesh's
``data`` axis, parameters stay replicated, and XLA inserts the gradient
allreduce over ICI (a layer whose activations are fewer bytes than its
gradient gathers them instead: engine/core.py ``GradExchange``).
Semantics are synchronous SGD on the GLOBAL
minibatch — numerically the same training trajectory as the
single-device fused step (the tests assert this on a virtual CPU mesh).
"""

from __future__ import annotations

from typing import Any, Optional

from veles_tpu.backends import JaxDevice
from veles_tpu.logger import Logger
from veles_tpu.parallel.mesh import make_mesh, replicated_sharding


class MeshJaxDevice(JaxDevice):
    """A JaxDevice whose buffers live on a mesh.

    ``put`` uploads host arrays with a fully-replicated NamedSharding so
    Vectors initialized through the normal ``Vector.initialize(device)``
    path are immediately consumable by the sharded step without a
    resharding transfer.  ``put_sharded`` is the capacity placement:
    the leading axis split 1/N per device (row-sharded residency,
    member-sharded cohorts).

    Transfer/residency accounting is PER-DEVICE-HONEST: a replicated
    put physically lands one copy on EVERY device, so it charges
    ``nbytes * n_devices`` against ``h2d_bytes``; a sharded put lands
    ``total/N`` per device and charges the padded total once.  (The
    original accounting charged replicated uploads at 1x — an 8-device
    mesh looked as cheap as one chip while burning 8x HBM.)
    """

    backend_name = "mesh"

    def __init__(self, mesh, compute_dtype: Any = None) -> None:
        import jax

        self.mesh = mesh
        self._repl = replicated_sharding(mesh)
        self._zeros_fn = None
        self._zeros_sharded_fn = None
        platform = mesh.devices.flat[0].platform
        super().__init__(platform=platform, compute_dtype=compute_dtype)
        self._jax = jax

    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size)

    def put(self, array, where: Any = None) -> Any:
        import numpy as np
        # dtype-preserving like JaxDevice.put: a quantized loader's
        # uint8 dataset replicates at 1 byte/element per device, and
        # the sharded streaming path ships uint8 superstep batches
        # (each device receives only its slice of every minibatch)
        arr = np.array(array, copy=True)
        # replicated = one physical copy PER device
        self.h2d_bytes += arr.nbytes * self.n_devices
        from veles_tpu.engine import core as engine_core
        return engine_core.put(
            arr, self._repl if where is None else where)

    def put_sharded(self, array) -> Any:
        """Upload with the leading axis split 1/N per device (rows
        zero-padded to a whole per-device tile).  Total HBM across the
        mesh is the padded array ONCE — per-device cost total/N — and
        that is what ``h2d_bytes`` charges."""
        import numpy as np

        from veles_tpu.parallel import mesh as mesh_helpers
        arr = np.asarray(array)
        buf, _ = mesh_helpers.put_row_sharded(self.mesh, arr)
        self.h2d_bytes += int(buf.nbytes)
        return buf

    def zeros(self, shape, dtype=None, sharded: bool = False) -> Any:
        import numpy as np
        if self._zeros_fn is None:
            import jax.numpy as jnp
            from veles_tpu.parallel.mesh import row_sharding
            # one jitted fn per placement with static (shape, dtype):
            # momentum allocation calls this once per parameter and a
            # fresh lambda per call would defeat jit's cache
            self._zeros_fn = self._jax.jit(
                lambda shape, dtype: jnp.zeros(shape, dtype),
                static_argnums=(0, 1), out_shardings=self._repl)
            self._zeros_sharded_fn = self._jax.jit(
                lambda shape, dtype: jnp.zeros(shape, dtype),
                static_argnums=(0, 1),
                out_shardings=row_sharding(self.mesh))
        dtype = np.dtype(dtype if dtype is not None else np.float32)
        if isinstance(shape, (int, np.integer)):
            shape = (shape,)
        fn = self._zeros_sharded_fn if sharded else self._zeros_fn
        return fn(tuple(int(s) for s in shape), dtype)

    def synchronize(self) -> None:
        # a replicated scalar lands on EVERY mesh device, so the wait
        # covers them all (the single-chip form waits on chip 0 only)
        from veles_tpu.engine import core as engine_core
        (engine_core.put(0.0, self._repl) + 0).block_until_ready()

    def __repr__(self) -> str:
        n = self.mesh.devices.size
        return f"<MeshJaxDevice {n}x{self.platform} axes={self.mesh.axis_names}>"


class DataParallel(Logger):
    """Wires a mesh into a StandardWorkflow's fused step.

    Usage (what Launcher does for ``--dp=N``)::

        dp = DataParallel(workflow, n)
        device = dp.install()          # BEFORE workflow.initialize
        workflow.initialize(device=device)

    After ``install()`` the workflow's ``FusedStepRunner`` jits its
    train/eval steps with mesh shardings; everything else (Decision,
    Snapshotter, plotters) is unchanged — they observe replicated
    Vectors exactly as in the single-device run.
    """

    def __init__(self, workflow, dp: Optional[int] = None,
                 axis_name: str = "data", mesh=None, devices=None,
                 compute_dtype: Any = None) -> None:
        self.workflow = workflow
        self.mesh = mesh if mesh is not None \
            else make_mesh(dp, axis_name, devices=devices)
        self.device = MeshJaxDevice(self.mesh, compute_dtype=compute_dtype)

    @property
    def num_devices(self) -> int:
        return int(self.mesh.devices.size)

    def install(self) -> MeshJaxDevice:
        fused = getattr(self.workflow, "fused", None)
        if fused is None:
            raise ValueError(
                "DataParallel needs a StandardWorkflow with a fused step "
                "(the numpy/eager path has no sharded execution)")
        n = self.num_devices
        loader = self.workflow.loader
        mb = loader.minibatch_size
        if mb % n:
            raise ValueError(
                f"minibatch_size {mb} not divisible by mesh size {n}")
        fused.mesh = self.mesh
        self.info("data parallel over %d devices (%s), global minibatch "
                  "%d -> %d per device", n, self.mesh.axis_names[0], mb,
                  mb // n)
        return self.device
