"""Device-mesh execution layer for population-scale banded relaxations.

The population engine's per-tick DP work is a stack of independent banded
relaxation chains — one (L-1, N, G+1) chain per dirty cohort state (or per
user when no two users share a quantized state).  That is embarrassingly
data-parallel over the leading axis, so the mesh layer shards it the same
way serving-oriented systems shard heavy multi-user traffic: a 1-D jax
mesh over a ``"users"`` axis, the stacked (D, L-1, N, N) tensors laid out
``PartitionSpec("users")`` on dim 0, and the jitted relaxation program
running one shard per device with the distance grid carried on-device
across the layer scan (nothing round-trips through the host between
layers).

The per-shard engine is the jitted jnp layer scan
(``bellman_ford._banded_relax_scan_jnp``), not the banded Pallas kernel:
on CPU the mesh is host-platform devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before importing
jax — see the README scaling quickstart), on TPU the same XLA program lands
on the chips.  (The Pallas kernels behind ``backend="pallas"`` compile on
TPU and are interpreted only on CPU — ``kernels/platform.py``.)  Like the
``jnp``/``pallas`` backends, the mesh engine relaxes in float32 —
``Population`` widens its exit-prune guard accordingly
(``tolerances.DIST_RTOL_F32``); the float64 numpy fallback
(``backend="minplus"``) remains the bit-exact reference.

Multi-host: when the mesh spans devices of several ``jax.distributed``
processes (launch each with ``JAX_COORDINATOR_ADDRESS`` /
``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` or call
``jax.distributed.initialize`` yourself, then build
``population_mesh()``), every host keeps its OWN user shard: the stacked
per-host chains are assembled into one global array with
``jax.make_array_from_process_local_data``, the same jitted program runs
SPMD across hosts, and each host reads back only its addressable shards.
Cohort signature dedupe stays host-local; nothing but the per-shard
relaxed grids ever crosses hosts — the banded DP has no cross-scenario
term, so hosts only synchronize on shard sizes (one tiny allgather per
relax) and on the jit dispatch itself.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.bellman_ford import (_banded_relax_scan_jnp, bucket_rows,
                                     pad_relax_rows)

__all__ = ["population_mesh", "MeshRelaxer"]


def population_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the ``"users"`` axis (default: every visible device,
    across every ``jax.distributed`` process when one was initialized).

    Start the process with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` to expose K host
    devices on CPU-only machines.
    """
    devs = jax.devices()
    if n_devices is not None:
        if jax.process_count() > 1:
            raise ValueError(
                "n_devices cannot be trimmed on a multi-process mesh — "
                "every process's devices must participate")
        if n_devices > len(devs):
            raise ValueError(f"requested {n_devices} devices but only "
                             f"{len(devs)} are visible (set XLA_FLAGS="
                             f"--xla_force_host_platform_device_count)")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), axis_names=("users",))


@functools.partial(jax.jit, static_argnames=("lo",))
def _mesh_relax(init: jnp.ndarray, E: jnp.ndarray, st: jnp.ndarray,
                lo: Optional[int]):
    """Jitted chained relaxation: the distance grid is the scan carry, so
    it lives in device memory across the whole layer chain — the only
    host<->device transfers are the stacked inputs in and the
    history/parents out, once per tick."""
    return _banded_relax_scan_jnp(init, E, st, lo)


class MeshRelaxer:
    """Sharded chained banded relaxation over a ``"users"`` mesh axis.

    ``relax`` has the ``bellman_ford.batched_banded_relax_argmin``
    contract: init (D, N, G+1), E/steep (D, L, N, N) -> (hist
    (D, L+1, N, G+1) float64, par (D, L, N, G+1) int64).  D is padded to
    ``bucket_rows`` per device with empty (all-inf) scenarios; each device
    relaxes its shard independently — there is no cross-shard
    communication in the banded DP, so scaling is linear until the
    per-device shard no longer hides dispatch overhead.
    """

    #: dispatch failures the retry/demotion ladder absorbs: collective
    #: timeouts (the watchdog's and simulated stalls) and socket-level host
    #: losses
    RECOVERABLE = (TimeoutError, ConnectionError)
    #: XLA runtime status codes of a lost or stalled peer: a collective
    #: whose peer died errors out as a ``RuntimeError`` (XLA's
    #: ``JaxRuntimeError``) whose message starts with one of these.  Every
    #: other status (INTERNAL, INVALID_ARGUMENT, UNIMPLEMENTED,
    #: RESOURCE_EXHAUSTED, ...) is a compile or programming fault and
    #: propagates instead of shedding the mesh
    RECOVERABLE_STATUS = ("UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED",
                          "CANCELLED")

    @classmethod
    def recoverable(cls, e: BaseException) -> bool:
        """Whether the retry/demotion ladder absorbs dispatch error ``e``."""
        if isinstance(e, cls.RECOVERABLE):
            return True
        return (isinstance(e, RuntimeError)
                and str(e).split(":", 1)[0].strip() in cls.RECOVERABLE_STATUS)

    def __init__(self, mesh: Optional[Mesh] = None, *,
                 timeout_s: Optional[float] = None, max_retries: int = 2,
                 backoff_s: float = 0.25):
        self._build(mesh if mesh is not None else population_mesh())
        #: per-collective dispatch timeout (None = wait forever; a hung
        #: multi-host collective otherwise blocks ``run_arrays`` for good)
        self.timeout_s = timeout_s
        #: bounded retries per mesh rung, with exponential backoff
        self.max_retries = int(max_retries)
        self.backoff_s = float(backoff_s)
        #: test seam: called with the attempt index before every dispatch
        #: (``FaultPlan.stall_hook`` raises simulated stalls through it)
        self.fault_hook: Optional[Callable[[int], None]] = None
        self.retries = 0             # dispatch attempts beyond the first
        self.demotions = 0           # mesh-ladder rungs taken
        #: devices that held the input shards of the last dispatch
        self.last_shard_devices: Tuple = ()

    def _build(self, mesh: Mesh) -> None:
        self.mesh = mesh
        self._sharding = NamedSharding(self.mesh, P("users"))
        procs = {d.process_index for d in self.mesh.devices.flat}
        #: the mesh spans several jax.distributed processes: inputs are
        #: per-host shards assembled into one global array, outputs are
        #: this host's addressable shards only
        self.multihost = len(procs) > 1
        me = jax.process_index()
        self._n_local = sum(1 for d in self.mesh.devices.flat
                            if d.process_index == me)
        if self.multihost and self._n_local == 0:
            raise ValueError("multi-process mesh has no devices on this "
                             "host — every participating process must "
                             "contribute devices")

    def demote(self) -> bool:
        """Take one rung down the mesh demotion ladder.

        multi-host mesh -> this host's local devices; multi-device local
        mesh -> a single device (numerically the single-host numpy-driven
        jit path).  Returns False at the bottom (nothing left to shed).
        Per-scenario relaxation chains are shard-independent, so results
        at every rung are bit-exact with the full mesh — demotion sheds
        capacity, never accuracy.  NOTE: on a multi-host mesh every
        surviving process must demote symmetrically (the straggler vector
        is allgathered for exactly this reason) or the survivors hang in
        the next collective.
        """
        if self.multihost:
            me = jax.process_index()
            local = [d for d in self.mesh.devices.flat
                     if d.process_index == me]
            self._build(Mesh(np.asarray(local), axis_names=("users",)))
        elif self.n_devices > 1:
            keep = list(self.mesh.devices.flat)[:1]
            self._build(Mesh(np.asarray(keep), axis_names=("users",)))
        else:
            return False
        self.demotions += 1
        return True

    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size)

    def relax(self, init: np.ndarray, E: np.ndarray, steep: np.ndarray,
              lo: Optional[int]) -> Tuple[np.ndarray, np.ndarray]:
        if init.ndim != 3:
            raise ValueError(f"init must be (D, N, G+1), got {init.shape}")
        D, N, Gp1 = init.shape
        if E.ndim != 4 or E.shape[0] != D or E.shape[2:] != (N, N) \
                or steep.shape != E.shape:
            raise ValueError(
                f"E/steep must be ({D}, L, {N}, {N}) matching init "
                f"{init.shape}, got E {E.shape}, steep {steep.shape}")
        L = E.shape[1]
        if L == 0:
            return (np.asarray(init)[:, None].astype(np.float64),
                    np.zeros((D, 0, N, Gp1), dtype=np.int64))
        finite = np.isfinite(steep)
        sti = np.where(finite, steep, 0).astype(np.int32)
        Ef = np.where(finite, E, np.inf).astype(np.float32)
        initf = np.asarray(init, np.float32)
        while True:
            try:
                hist, par = self._dispatch(initf, Ef, sti, lo, D)
                break
            except Exception as e:
                # the retry budget at this rung is spent: shed capacity
                # and try the smaller mesh (bit-exact per-scenario), or
                # give up at the bottom of the ladder
                if not self.recoverable(e) or not self.demote():
                    raise
        # layer-0 history: the exact float64 init (parity with the jnp
        # engine, whose callers read hist[0] as the untouched init grid)
        hist[:, 0] = init
        return hist, par

    def _dispatch(self, initf: np.ndarray, Ef: np.ndarray,
                  sti: np.ndarray, lo: Optional[int],
                  D: int) -> Tuple[np.ndarray, np.ndarray]:
        """One mesh rung's dispatch with bounded retry + backoff."""
        last: Optional[BaseException] = None
        for attempt in range(self.max_retries + 1):
            if attempt:
                self.retries += 1
                time.sleep(self.backoff_s * (2 ** (attempt - 1)))
            try:
                if self.fault_hook is not None:
                    self.fault_hook(attempt)
                return self._relax_once(initf, Ef, sti, lo, D)
            except Exception as e:
                if not self.recoverable(e):
                    raise
                last = e
        raise last

    def _relax_once(self, initf: np.ndarray, Ef: np.ndarray,
                    sti: np.ndarray, lo: Optional[int],
                    D: int) -> Tuple[np.ndarray, np.ndarray]:
        if self.timeout_s is not None:
            # run the collective on a watchdog thread: a dead peer host
            # otherwise blocks the allgather/jit dispatch forever.  A
            # fresh single-use thread per dispatch — a hung worker must
            # not poison a shared pool.
            from concurrent.futures import ThreadPoolExecutor
            from concurrent.futures import TimeoutError as FutTimeout
            pool = ThreadPoolExecutor(max_workers=1,
                                      thread_name_prefix="mesh-relax")
            try:
                fut = pool.submit(self._relax_run, initf, Ef, sti, lo, D)
                try:
                    return fut.result(timeout=self.timeout_s)
                except FutTimeout:
                    raise TimeoutError(
                        f"mesh collective exceeded {self.timeout_s}s "
                        f"(suspected dead or straggling host)")
            finally:
                pool.shutdown(wait=False)
        return self._relax_run(initf, Ef, sti, lo, D)

    def _relax_run(self, initf: np.ndarray, Ef: np.ndarray,
                   sti: np.ndarray, lo: Optional[int],
                   D: int) -> Tuple[np.ndarray, np.ndarray]:
        if self.multihost:
            return self._relax_global(initf, Ef, sti, lo, D)
        # pad to a bucket of rows per device with empty (all-inf) chains
        # and strip them from the outputs — callers never pre-shape, and
        # a stream of varying batch sizes compiles only a few programs
        n = self.n_devices
        initf, Ef, sti = pad_relax_rows(initf, Ef, sti,
                                        n * bucket_rows(D, n))
        dev = jax.device_put(jnp.asarray(initf), self._sharding)
        Ed = jax.device_put(jnp.asarray(Ef), self._sharding)
        sd = jax.device_put(jnp.asarray(sti), self._sharding)
        self.last_shard_devices = tuple(s.device
                                        for s in Ed.addressable_shards)
        h, p = _mesh_relax(dev, Ed, sd, lo)
        hist = np.asarray(h, np.float64)[:D]
        par = np.asarray(p).astype(np.int64)[:D]
        return hist, par

    def _relax_global(self, initf: np.ndarray, Ef: np.ndarray,
                      sti: np.ndarray, lo: Optional[int],
                      D: int) -> Tuple[np.ndarray, np.ndarray]:
        """Multi-host relax: every process contributes its own (ragged)
        shard.  Hosts agree on a uniform per-device row count (the max any
        host needs — one tiny allgather), pad their local stacks to it,
        assemble the global sharded arrays without any cross-host data
        movement, run the SPMD program, and read back only their own
        addressable shards."""
        from jax.experimental import multihost_utils
        counts = np.asarray(
            multihost_utils.process_allgather(np.asarray([D])),
            dtype=np.int64).reshape(-1)
        rows = bucket_rows(int(counts.max()), self._n_local)
        initf, Ef, sti = pad_relax_rows(initf, Ef, sti, rows * self._n_local)

        def mk(x):
            return jax.make_array_from_process_local_data(
                self._sharding, x)

        h, p = _mesh_relax(mk(initf), mk(Ef), mk(sti), lo)

        def local(arr, dtype):
            shards = sorted(arr.addressable_shards,
                            key=lambda s: s.index[0].start or 0)
            return np.concatenate(
                [np.asarray(s.data, dtype) for s in shards])[:D]

        return local(h, np.float64), local(p, np.int64).astype(np.int64)
