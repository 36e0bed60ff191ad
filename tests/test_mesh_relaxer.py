"""MeshRelaxer shaping contract: pad-and-strip for ragged scenario counts,
clear ValueErrors for malformed stacks, and f32 agreement with the float64
reference on every branch.

Runs against however many devices are visible; the pad-branch tests need a
multi-device mesh and are exercised with 4 host devices via
``tests/test_stream_subprocess.py`` (the main pytest process keeps the
default single CPU device).
"""
import jax
import numpy as np
import pytest

from repro.core.bellman_ford import batched_banded_relax_minarg
from repro.sharding.population import MeshRelaxer, population_mesh


def _case(D, seed=0, L=3, N=5, Gp1=11):
    rng = np.random.default_rng(seed)
    steep = np.where(rng.random((D, L, N, N)) < 0.5,
                     rng.integers(0, Gp1 - 1, (D, L, N, N)).astype(float),
                     np.inf)
    E = rng.random((D, L, N, N))
    init = np.where(rng.random((D, N, Gp1)) < 0.3,
                    rng.random((D, N, Gp1)), np.inf)
    return init, E, steep


def _check(mr, D, seed=0):
    init, E, steep = _case(D, seed)
    h, p = mr.relax(init, E, steep, None)
    assert h.shape == (D, 4, 5, 11)
    assert p.shape == (D, 3, 5, 11)
    h64, _ = batched_banded_relax_minarg(
        init, np.where(np.isfinite(steep), E, np.inf), steep, None)
    fin = np.isfinite(h64)
    assert np.array_equal(np.isfinite(h), fin)
    np.testing.assert_allclose(h[fin], h64[fin], rtol=1e-6)
    assert np.array_equal(h[:, 0], init)      # exact f64 init row


def test_divisible_counts_no_padding():
    mr = MeshRelaxer(population_mesh())
    _check(mr, 2 * mr.n_devices, seed=1)


def test_ragged_counts_pad_and_strip():
    mr = MeshRelaxer(population_mesh())
    for D in (1, mr.n_devices + 1, 3 * mr.n_devices - 1):
        _check(mr, D, seed=D)


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="pad branch needs a multi-device mesh")
def test_pad_branch_on_multi_device_mesh():
    mr = MeshRelaxer(population_mesh(4))
    assert mr.n_devices == 4
    for D in (1, 3, 5, 7):                    # all force padding
        assert D % mr.n_devices != 0
        _check(mr, D, seed=10 + D)
    _check(mr, 8, seed=99)                    # and the exact-fit branch


def test_malformed_stacks_raise():
    mr = MeshRelaxer(population_mesh())
    init, E, steep = _case(4)
    with pytest.raises(ValueError, match="init must be"):
        mr.relax(init[:, 0], E, steep, None)
    with pytest.raises(ValueError, match="E/steep"):
        mr.relax(init, E[:, :, :4], steep, None)
    with pytest.raises(ValueError, match="E/steep"):
        mr.relax(init, E, steep[:2], None)
    with pytest.raises(ValueError, match="E/steep"):
        mr.relax(init, E[:2], steep[:2], None)


def test_zero_layer_chain_short_circuits():
    mr = MeshRelaxer(population_mesh())
    init, _, _ = _case(3)
    h, p = mr.relax(init, np.empty((3, 0, 5, 5)), np.empty((3, 0, 5, 5)),
                    None)
    assert np.array_equal(h[:, 0], init) and p.shape == (3, 0, 5, 11)


def test_population_mesh_backend_matches_minplus():
    """A population orchestrator on the mesh backend, its relaxations
    sharded over every visible device, keeps exactly the incumbents of the
    float64 ``minplus`` engine under AR(1) fading with hysteresis."""
    from repro.core import ChurnOrchestrator, population_cohorts
    users = 48
    ref, mesh = (ChurnOrchestrator(
        population=population_cohorts(users, n_extra_edge=2, backend=b),
        hysteresis=0.05) for b in ("minplus", "mesh"))
    rng = np.random.default_rng(5)
    q = np.full(users, 0.5)
    for _ in range(3):
        q = np.clip(0.5 + 0.95 * (q - 0.5) + rng.normal(0, 0.15, users),
                    0.3, 1.0)
        ref.step_arrays(quality=q)
        mesh.step_arrays(quality=q)
    for pa, pb in zip(ref.pops, mesh.pops):
        assert pb._mesh().n_devices == jax.device_count()
        assert np.array_equal(pa.inc_found, pb.inc_found)
        f = pa.inc_found
        assert np.array_equal(pa._inc_place[f], pb._inc_place[f])
        assert np.array_equal(pa._inc_exit[f], pb._inc_exit[f])


def test_population_mesh_device_trim_validation():
    with pytest.raises(ValueError, match="visible"):
        population_mesh(jax.device_count() + 1)


# ---------------------------------------------------------------------------
# host-dropout recovery: bounded retry, then the demotion ladder
# ---------------------------------------------------------------------------

def test_retry_within_budget_no_demotion():
    from repro.core.faults import FaultPlan
    mr = MeshRelaxer(population_mesh(), max_retries=2, backoff_s=0.0)
    clean = MeshRelaxer(population_mesh())
    init, E, steep = _case(3, seed=21)
    hc, pc = clean.relax(init, E, steep, None)
    mr.fault_hook = FaultPlan.stall_hook(2)   # fails 2 of 3 attempts
    h, p = mr.relax(init, E, steep, None)
    assert mr.retries == 2 and mr.demotions == 0
    assert np.array_equal(h, hc) and np.array_equal(p, pc)


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="demotion needs a multi-device local mesh")
def test_retry_budget_spent_demotes_bit_exact():
    from repro.core.faults import FaultPlan
    mr = MeshRelaxer(population_mesh(), max_retries=0, backoff_s=0.0)
    n0 = mr.n_devices
    clean = MeshRelaxer(population_mesh())
    init, E, steep = _case(5, seed=22)
    hc, pc = clean.relax(init, E, steep, None)
    mr.fault_hook = FaultPlan.stall_hook(1)   # kill the only attempt
    h, p = mr.relax(init, E, steep, None)
    assert mr.demotions == 1 and mr.n_devices == 1 < n0
    assert np.array_equal(h, hc) and np.array_equal(p, pc)
    # the relaxer stays usable on the demoted rung
    init2, E2, steep2 = _case(2, seed=23)
    h2, _ = mr.relax(init2, E2, steep2, None)
    h2c, _ = clean.relax(init2, E2, steep2, None)
    assert np.array_equal(h2, h2c)


def test_bottom_of_ladder_reraises():
    from repro.core.faults import FaultPlan
    mr = MeshRelaxer(population_mesh(), max_retries=0, backoff_s=0.0)
    n0 = mr.n_devices
    init, E, steep = _case(2, seed=24)
    mr.fault_hook = FaultPlan.stall_hook(10 ** 6)   # never heals
    with pytest.raises(TimeoutError, match="injected host stall"):
        mr.relax(init, E, steep, None)
    # ladder fully taken before giving up
    assert mr.n_devices == 1
    assert mr.demotions == (1 if n0 > 1 else 0)


def test_nonrecoverable_errors_are_not_retried():
    mr = MeshRelaxer(population_mesh(), max_retries=3, backoff_s=0.0)
    init, E, steep = _case(2, seed=25)

    def bomb(attempt):
        raise KeyError("not in RECOVERABLE")

    mr.fault_hook = bomb
    with pytest.raises(KeyError):
        mr.relax(init, E, steep, None)
    assert mr.retries == 0 and mr.demotions == 0


@pytest.mark.parametrize("exc", [
    RuntimeError("INTERNAL: Mosaic failed to compile"),
    jax.errors.JaxRuntimeError("INTERNAL: Mosaic failed to compile"),
    jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: Mosaic ran out of VMEM"),
    jax.errors.JaxRuntimeError("INVALID_ARGUMENT: Mosaic shape mismatch"),
], ids=["runtime", "internal", "resource_exhausted", "invalid_argument"])
def test_compile_errors_propagate_without_demotion(exc):
    """XLA raises compile/programming faults as RuntimeError: those must
    surface, not shed the mesh while the run carries on."""
    mr = MeshRelaxer(population_mesh(), max_retries=3, backoff_s=0.0)
    init, E, steep = _case(2, seed=26)

    def compile_fault(attempt):
        raise exc

    mr.fault_hook = compile_fault
    with pytest.raises(RuntimeError, match="Mosaic"):
        mr.relax(init, E, steep, None)
    assert mr.retries == 0 and mr.demotions == 0


@pytest.mark.parametrize("error", [RuntimeError, jax.errors.JaxRuntimeError],
                         ids=["runtime", "jax_runtime"])
@pytest.mark.parametrize("status", MeshRelaxer.RECOVERABLE_STATUS)
def test_lost_peer_runtime_errors_retry_then_demote(status, error):
    """A collective whose peer died errors out as an XLA RuntimeError with
    a lost-peer status: the ladder retries it, then sheds the mesh
    (bit-exact per scenario) or re-raises at the bottom."""
    mr = MeshRelaxer(population_mesh(), max_retries=1, backoff_s=0.0)
    n0 = mr.n_devices
    init, E, steep = _case(3, seed=27)
    hc, pc = MeshRelaxer(population_mesh()).relax(init, E, steep, None)
    budget = [mr.max_retries + 1]

    def lost_peer(attempt):
        if budget[0]:
            budget[0] -= 1
            raise error(f"{status}: connection reset")

    mr.fault_hook = lost_peer
    if n0 > 1:
        h, p = mr.relax(init, E, steep, None)
        assert mr.demotions == 1 and mr.n_devices == 1
        assert np.array_equal(h, hc) and np.array_equal(p, pc)
    else:
        with pytest.raises(error, match=status):
            mr.relax(init, E, steep, None)
        assert mr.demotions == 0
    assert mr.retries == 1
