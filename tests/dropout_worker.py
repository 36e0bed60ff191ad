"""One process of the simulated mesh host-dropout smoke (not a test
module — launched by tests/test_faults_subprocess.py and the CI
fault-tolerance step).

Each process joins a 2-process ``jax.distributed`` cluster, builds a
multi-host ``MeshRelaxer`` with a bounded retry budget, and injects host
stalls through ``FaultPlan.stall_hook`` until the retry budget at the
multi-host rung is spent.  Both processes inject the same schedule, so
both demote to their local devices at the same dispatch — the ladder must
record exactly one demotion, land on the local mesh, and produce results
bit-identical to a never-faulted local relaxer.

Usage: dropout_worker.py <process_id> <num_processes> <coordinator_port>
"""
import os
import sys

pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=2")

import jax  # noqa: E402

jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=nproc, process_id=pid)

import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.core.faults import FaultPlan  # noqa: E402
from repro.sharding.population import MeshRelaxer, population_mesh  # noqa: E402

mr = MeshRelaxer(population_mesh(), max_retries=1, backoff_s=0.0)
assert mr.multihost and mr.n_devices == 2 * nproc

rng = np.random.default_rng(17 + pid)
D, L, N, Gp1 = 3 + 2 * pid, 3, 5, 11       # ragged across hosts
steep = np.where(rng.random((D, L, N, N)) < 0.5,
                 rng.integers(0, 10, (D, L, N, N)).astype(float), np.inf)
E = rng.random((D, L, N, N))
init = np.where(rng.random((D, N, Gp1)) < 0.3,
                rng.random((D, N, Gp1)), np.inf)

# fail every attempt at the multi-host rung (max_retries=1 -> 2 attempts),
# forcing one rung down the ladder; the local-rung attempt then succeeds.
# The first attempt stalls into the watchdog's timeout, the second errors
# out as XLA reports a collective whose peer died
stall = FaultPlan.stall_hook(1)
peer_lost = FaultPlan.stall_hook(
    1, exc=lambda msg: jax.errors.JaxRuntimeError(f"UNAVAILABLE: {msg}"))
mr.fault_hook = lambda attempt: (stall if attempt == 0 else peer_lost)(attempt)
hist, par = mr.relax(init, E, steep, None)
assert mr.demotions == 1, mr.demotions
assert mr.retries == 1, mr.retries
assert not mr.multihost                    # landed on this host's devices

clean = MeshRelaxer(Mesh(np.asarray(jax.local_devices()),
                         axis_names=("users",)))
hc, pc = clean.relax(init, E, steep, None)
assert np.array_equal(hist, hc)
assert np.array_equal(par, pc)
print(f"proc {pid}: D={D} demoted, post-demotion exact", flush=True)
