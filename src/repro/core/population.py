"""Struct-of-arrays population engine: whole-cohort churn ticks.

``plan.update_uplinks`` / ``plan.solve_plans`` batch the *math* of a churn
tick but keep the *state* in per-user ``Plan`` objects: every tick pays U
Python method calls, U small ``np.stack`` re-packings and U ``_apply_qpack``
scatter loops before any vectorized work starts — which is what caps the
PR-3 churn loop at ~1e4 user-ticks/s.  :class:`Population` inverts the
layout: one cohort of same-shape users (one network topology, one DNN
profile, one requirements triple, one solver parameterization) owns its
batched state as single contiguous arrays —

  * ``(U, N)`` per-user source-link bandwidth vectors,
  * ``(U, N)`` failure bitmaps,
  * ``(U, L)`` / ``(U,)`` incumbent placements, exits and energies,

and the per-tick pipeline — channel ingest -> fused requantize+signature
kernel -> in-cell cache check -> chained banded relaxation ->
argmin/post-pass — runs as whole-array operations with NO per-user Python
on the hot path.  Quantized uplink packs are NOT stored per user: a
user's pack always equals their cohort state's ``stq`` (states are keyed
BY the pack), so the engine keeps one int16 signature row per *state*
(``_stq_enc``) and stale-row requantization compares fresh signatures
against a gather from that table — the ``(U, M, 2L-1, N)`` float64 pack
array (7 GB at 1e7 users) is gone, and re-keying touches exactly the
rows whose encoding moved (``kernels/ee_gate/population.py`` holds the
fused quantize->int16->signature launch, numpy oracle + jitted jnp).

The DP layer exploits that quantization makes the relaxation tensors
piecewise-constant in the channel *across the cohort*, not just across
ticks: users whose quantized packs (and failure masks) coincide share one
*cohort state* — one (M, L-1, N, N) steepness stack, one relaxed DP grid,
one memoized per-exit minimum, one backtracked candidate list.  A tick
relaxes only the cohort states born this tick (chained float64 banded
relaxation, cache-residency chunked via ``bellman_ford.relax_chunk_rows``),
so a million AR(1)-fading users cost a few hundred relaxations, and the
exact per-user post-pass re-reads the *true* bandwidth through the shared
candidates (``fin._best_feasible`` with a per-state candidate cache).

Results are bit-exact vs per-user ``Plan.solve()`` (hence vs cold
``solve_fin``) on the float64 numpy backends: the ingest replicates the
packed requantizer of ``plan.update_uplinks`` elementwise, states
materialize through the same scatter formulas as ``Plan._apply_qpack``,
the relaxation and post-pass are the shared ``bellman_ford`` / ``fin``
code paths, and the rare no-feasible-path tighten loop falls back to a
fresh per-user ``Plan`` (whose warm==cold invariant is property-tested).
``backend="jnp"/"pallas"`` swap in the float32 engines; ``backend="mesh"``
routes the chained relaxation through the device-mesh execution layer
(``repro.sharding.population``), sharding the stacked (D, L-1, N, N)
relaxation over the user axis of a jax mesh.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.kernels.ee_gate.population import QuantConsts, quant_signature

from .bellman_ford import (batched_banded_relax_argmin,
                           batched_banded_relax_minarg, relax_chunk_rows)
from .dnn_profile import DNNProfile
from .feasible_graph import build_feasible_graph
from .fin import (DP_BACKENDS, _BandedArgDP, _backtrack, _best_feasible,
                  _exit_dmin)
from .frontier import (ParetoFrontier, eval_config_users, frontier_from_rows,
                       scan_state_users)
from .plan import Plan, _validate_bps_values, _validate_population_bps
from .problem import AppRequirements, Config, ConfigEval, Solution
from .spans import span
from .system_model import Network
from .tolerances import dist_tol

__all__ = ["Population", "PopulationStats", "TelemetryPolicy"]


@dataclass(frozen=True)
class TelemetryPolicy:
    """What :meth:`Population.ingest` does with corrupt channel readings.

    Without a policy the engine fails LOUDLY: NaN/Inf/negative bandwidth
    raises a ``ValueError`` naming the offending users — garbage must
    never silently key a shared cohort state.  With a policy the reading
    is absorbed instead:

    ``mode="clamp"``       bad *entries* are replaced by the user's
                           current stored value (entry-wise last known
                           good); the rest of the row ingests normally.
    ``mode="quarantine"``  a user with ANY bad entry (or a stuck sensor,
                           below) holds their entire last-known-good
                           uplink vector — they keep serving their
                           incumbent and rejoin automatically on the
                           first clean reading.  Per-tick transitions are
                           counted in ``PopulationStats.quarantines`` /
                           ``recoveries`` (the orchestrator surfaces them
                           on ``TickReport``).
    ``mode="raise"``       the loud default, as a policy object.

    ``stuck_window > 0`` adds frozen-sensor detection to the quarantine
    mode: a user whose raw reading row repeats EXACTLY for that many
    consecutive ingests is quarantined until the reading moves again.
    """

    mode: str = "quarantine"
    stuck_window: int = 0

    def __post_init__(self):
        if self.mode not in ("raise", "clamp", "quarantine"):
            raise ValueError(f"TelemetryPolicy.mode must be raise/clamp/"
                             f"quarantine, got {self.mode!r}")
        if self.stuck_window < 0:
            raise ValueError("TelemetryPolicy.stuck_window must be >= 0")


@dataclass
class PopulationStats:
    """Aggregate engine counters (diagnostics and benches)."""

    ingests: int = 0             # ingest calls
    uplink_updates: int = 0      # user-slots refreshed by ingest
    quant_changed: int = 0       # user-slots whose quantized pack moved
    dp_relaxes: int = 0          # cohort states relaxed
    dp_cache_hits: int = 0       # user-solves served from an existing state
    solves: int = 0              # user-solves issued
    unique_solves: int = 0       # distinct (state, bandwidth) groups solved
    fastpath_states: int = 0     # states served by the shared fast table
    fallbacks: int = 0           # per-user Plan fallbacks (tighten loop)
    state_evictions: int = 0     # cache compactions
    prebuilt_states: int = 0     # contingency states relaxed off-tick
    fused_relaxes: int = 0       # newborn batches relaxed in ONE launch
    chunked_relaxes: int = 0     # newborn batches split by the residency
    #                              budget (REPRO_RELAX_CHUNK_BYTES)
    bounded_relaxes: int = 0     # states relaxed from a parent's layer slice
    layers_skipped: int = 0      # relax layers skipped by bounded resumes
    mask_reuses: int = 0         # masked states served by a parent's grids
    telemetry_bad: int = 0       # corrupt (user, link) readings seen
    telemetry_clamped: int = 0   # entries clamped to last known good
    quarantines: int = 0         # users entering quarantine
    recoveries: int = 0          # users leaving quarantine
    rekeyed_users: int = 0       # user rows passed to the state re-key
    # per-phase wall clock of the program spans (``core/spans.py``;
    # accumulated only when the Population was built with timing=True —
    # zero-cost when disabled).  Span names in brackets.
    t_ingest_ms: float = 0.0     # channel ingest + requantize [pop.ingest]
    t_relax_ms: float = 0.0      # banded relaxation launches [pop.relax]
    t_post_ms: float = 0.0       # exact post-pass [pop.post]
    t_rekey_ms: float = 0.0      # state-table re-key [pop.rekey]
    t_group_ms: float = 0.0      # solve's (state, bandwidth) grouping
    #                              [pop.group]


def _group_runs(keys: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Group equal keys: (uniq, first, order, bounds).

    ``order[bounds[g]:bounds[g + 1]]`` are the positions of group ``g``
    (first-occurrence-stable); ``first[g]`` is its first position.  One
    home for the unique/stable-argsort/searchsorted idiom the solve,
    incumbent-evaluation and frontier paths all share.

    All-equal keys short-circuit without sorting: a cold-start cohort (one
    bandwidth row tiled U times) and steady single-config ticks are the
    common case at scale, and one vectorized compare beats a million-row
    argsort by orders of magnitude.
    """
    n = len(keys)
    if n > 1 and bool((keys == keys[0]).all()):
        return (keys[:1], np.zeros(1, dtype=np.int64),
                np.arange(n, dtype=np.int64),
                np.array([0, n], dtype=np.int64))
    uniq, first, inv = np.unique(keys, return_index=True,
                                 return_inverse=True)
    order = np.argsort(inv, kind="stable")
    bounds = np.searchsorted(inv[order], np.arange(len(uniq) + 1))
    return uniq, first, order, bounds


#: the dense threshold gate peels at most this many incumbent
#: configurations, and this many factor rows of one configuration, before
#: it leaves the rest of a cohort to the exact evaluator
_GATE_CONFIGS = 8
_GATE_FACTOR_ROWS = 4
#: bit pattern of the largest finite float64
_F64_MAX_BITS = 0x7FEFFFFFFFFFFFFF


def _modal_row(exit_all: np.ndarray, place_all: np.ndarray,
               no_inc: np.ndarray) -> int:
    """A row holding the modal incumbent (exit, placement; no incumbent
    counts as one value) of a 32-row stride sample of the cohort."""
    Us = len(exit_all)
    samp = np.arange(0, Us, max(1, Us // 31))
    srows = np.empty((len(samp), 1 + place_all.shape[1]), dtype=np.int32)
    srows[:, 0] = np.where(no_inc[samp], -2, exit_all[samp])
    srows[:, 1:] = place_all[samp]
    sv = np.ascontiguousarray(srows).view(
        np.dtype((np.void, srows.shape[1] * 4))).ravel()
    uniq, counts = np.unique(sv, return_counts=True)
    return int(samp[np.nonzero(sv == uniq[np.argmax(counts)])[0][0]])


def _enc_int16(q: np.ndarray) -> np.ndarray:
    """Checkpoint encoding of the inf-capable integral quantization arrays
    (qpack / state stq): values are either integers in [0, gamma] or +inf
    (gamma < int16 max is a ctor invariant), stored as int16 with -1 for
    inf — 4x smaller than float64 and exactly invertible."""
    e = np.empty(q.shape, dtype=np.int16)
    fin = np.isfinite(q)
    np.copyto(e, q, casting="unsafe", where=fin)
    e[~fin] = -1
    return e


def _dec_int16(e: np.ndarray) -> np.ndarray:
    out = e.astype(np.float64)
    out[e < 0] = np.inf
    return out


class _BwCols:
    """Column-gather view over selected rows of the bandwidth store.

    ``eval_config_users`` touches its bandwidth argument only through
    ``bwv[:, n]`` columns and ``len(bwv)``; gathering one (Us,) column per
    visited link — instead of materializing the whole (Us, N) row gather
    up front — keeps the per-group incumbent re-evaluation's memory
    traffic proportional to the links a configuration actually uses.
    Values are identical to ``bw[rows][:, n]``, so results stay bit-exact.
    """

    __slots__ = ("_bw", "_rows")

    def __init__(self, bw: np.ndarray, rows: np.ndarray):
        self._bw = bw
        self._rows = rows

    def __len__(self) -> int:
        return len(self._rows)

    def __getitem__(self, key) -> np.ndarray:
        s, n = key                       # only the bwv[:, n] access pattern
        assert s == slice(None)
        return self._bw[self._rows, n]


class _LazyBwCols:
    """Column view over the LAZY bandwidth store (see ``_bw_lazy``):
    column ``n`` materializes as ``scale * factors[:, n]`` on demand —
    per-element IEEE multiplies identical to the fused dense product's
    column — without ever writing the (U, N) product.  Supports only the
    ``bwv[:, n]`` / ``len(bwv)`` access pattern of ``eval_config_users``.
    """

    __slots__ = ("_sc", "_fac", "_src")

    def __init__(self, sc: np.ndarray, fac: np.ndarray, src: int):
        self._sc = sc
        self._fac = fac
        self._src = src

    def __len__(self) -> int:
        return len(self._sc)

    def __getitem__(self, key) -> np.ndarray:
        s, n = key
        assert s == slice(None)
        if n == self._src:
            return np.full(len(self._sc), np.inf)
        return self._sc * self._fac[:, n]


class _PendingSolve:
    """In-flight tick handle between ``solve_begin`` and ``solve_finish``:
    the begin-time (state, bandwidth) snapshot, the grouped rows and the
    relax future (None when the relaxation ran synchronously)."""

    __slots__ = ("users", "build_solutions", "t0", "sids", "first",
                 "order", "bounds", "bw", "future")

    def __init__(self, users: np.ndarray, build_solutions: bool,
                 t0: float):
        self.users = users
        self.build_solutions = build_solutions
        self.t0 = t0
        self.sids = None
        self.first = None
        self.order = None
        self.bounds = None
        self.bw = None
        self.future = None


class _CandCache:
    """Per-(mode, exit) energy-ordered candidate cache of a cohort state."""

    __slots__ = ("items", "order", "exhausted")

    def __init__(self):
        self.items: List[Tuple[Config, float]] = []
        self.order = None            # (flat argsort, values, n_finite)
        self.exhausted = False


class _FastTable:
    """The state's shared first-candidate frontier decision (vector path).

    Exact energies are bandwidth-independent, so the scalar post-pass's
    control flow over FIRST candidates — which (quantizer pass, exit)
    pairs get scanned, which exit wins, whether the ceil rescue replaces
    the main pass — is a pure function of the cohort state and is computed
    ONCE at state birth.  A tick then only has to check, per user, that
    every scanned first candidate is exactly feasible (stacked-array
    feasibility flags); when it is — the overwhelmingly common case — the
    cached choice broadcasts to every user of the state, and any state
    where it is not falls back to the general vectorized scan.

    ``scan``   [(mi, k, pos)] the shared flow evaluates, in order;
    ``keys``/``cfgs``  the distinct first-candidate configs (pos-indexed);
    ``choice`` (mi, k, pos, energy, e_comp, e_comm, used_ceil) or None
               (None = the tighten-fallback path).
    """

    __slots__ = ("keys", "cfgs", "scan", "choice")

    def __init__(self, keys, cfgs, scan, choice):
        self.keys = keys
        self.cfgs = cfgs
        self.scan = scan
        self.choice = choice


class _CohortState:
    """One unique (quantized pack, failure mask) DP state of the cohort.

    Everything hanging off the state is shared by every user currently in
    it: the masked steepness stack, the init grid, the relaxed DP grids
    (``dps``), the per-exit distance minima (memoized by ``fin._exit_dmin``
    on the dp objects), the backtracked candidate lists and the
    first-candidate fast table of the vectorized post-pass.
    """

    __slots__ = ("stq", "mask", "steep", "grid", "dps", "cand", "fast",
                 "parent")

    def __init__(self, stq: np.ndarray, mask: np.ndarray,
                 steep: np.ndarray, grid: np.ndarray, parent: int = -1):
        self.stq = stq               # (M, 2L-1, N)
        self.mask = mask             # (N,) bool
        self.steep = steep           # (M, L-1, N, N), masks applied
        self.grid = grid             # (M, N, G+1), masks applied
        self.dps: Optional[List[_BandedArgDP]] = None
        self.cand: Dict[Tuple[int, int], _CandCache] = {}
        self.fast: Optional[_FastTable] = None
        #: state id the first user keyed here came FROM — a bounded
        #: re-relaxation *hint* only: the resume path re-validates the
        #: layer-prefix equality against whatever state currently sits at
        #: this index (compaction may remap it), so a stale hint degrades
        #: to a full relax, never to a wrong result
        self.parent = parent


class _TightenResult:
    """Per-user outcome arrays of one batched tighten loop
    (``Population._tighten_batch``)."""

    __slots__ = ("found", "energy", "latency", "e_comp", "e_comm", "exit",
                 "rounds", "delta_eff", "cfgs")

    def __init__(self, n: int, max_tighten: int):
        self.found = np.zeros(n, dtype=bool)
        self.energy = np.full(n, np.inf)
        self.latency = np.zeros(n)
        self.e_comp = np.zeros(n)
        self.e_comm = np.zeros(n)
        self.exit = np.full(n, -1, dtype=np.int64)
        #: failed-round count == the succeeding round's index (Plan's
        #: ``meta["tighten_rounds"]``); max_tighten+1 when exhausted
        self.rounds = np.full(n, max_tighten + 1, dtype=np.int64)
        self.delta_eff = np.full(n, np.nan)
        self.cfgs: List[Optional[Config]] = [None] * n


class Population:
    """Struct-of-arrays engine for a cohort of same-shape users.

    One cohort shares (network topology, DNN profile, requirements, solver
    parameters); per-user state is the source-link bandwidth vector, the
    quantized uplink pack, the failure bitmap and the incumbent.  Mixed
    populations (several apps / topologies) are lists of cohorts — see
    ``online.population_cohorts``.

    ``backend``: ``minplus``/``banded`` (float64 numpy, bit-exact vs
    ``Plan.solve()``), ``jnp``/``pallas`` (float32 engines), ``mesh``
    (float32, sharded over the user axis of a jax device mesh — every
    visible device unless ``mesh`` names one, see
    ``sharding.population.population_mesh``).
    """

    def __init__(self, network: Network, profile: DNNProfile,
                 req: AppRequirements, n_users: int, *, gamma: int = 10,
                 lam: Optional[int] = None, quantize: str = "floor",
                 max_tighten: int = 6, tighten_factor: float = 0.85,
                 backend: str = "minplus", check_aggregate_load: bool = False,
                 user_ids: Optional[Sequence[int]] = None,
                 max_states: int = 65536, vector_postpass: bool = True,
                 bounded_rerelax: bool = True, timing: bool = False,
                 telemetry: Optional[TelemetryPolicy] = None,
                 fused_ingest: str = "numpy", mesh=None):
        if n_users <= 0:
            raise ValueError(f"n_users must be positive, got {n_users}")
        if backend != "mesh" and DP_BACKENDS.get(backend) is None:
            raise ValueError(f"unknown Population backend {backend!r} "
                             f"(expected mesh or one of "
                             f"{sorted(DP_BACKENDS)})")
        if backend in ("numpy", "dense"):
            raise ValueError("Population requires a banded engine; the "
                             "dense backends exist for equivalence testing "
                             "only (use minplus/banded/jnp/pallas/mesh)")
        if gamma >= np.iinfo(np.int16).max:
            raise ValueError(f"gamma {gamma} overflows the int16 state "
                             f"encoding")
        if mesh is not None and backend != "mesh":
            raise ValueError("mesh= only applies with backend='mesh'")
        if fused_ingest not in ("numpy", "jnp"):
            raise ValueError(f"unknown fused_ingest backend "
                             f"{fused_ingest!r} (expected numpy or jnp)")
        self.backend = backend
        #: backend of the rare per-user Plan fallback (same engine family)
        self._plan_backend = "jnp" if backend == "mesh" else backend
        self._engine = DP_BACKENDS[self._plan_backend]
        self._dist_tol = dist_tol(self._engine)

        # the prototype Plan owns every *shared* stage-1/2 tensor: the
        # pristine extended graph, the packed-requantizer constants and the
        # base quantized steepness stack that per-user states scatter their
        # source-node rows/cols into.  Building it through Plan (rather
        # than duplicating the builders) is what makes population state
        # equal per-plan state by construction.
        self._proto = Plan(network, profile, req, gamma=gamma, lam=lam,
                           quantize=quantize, max_tighten=max_tighten,
                           tighten_factor=tighten_factor, n_best=1,
                           backend=self._plan_backend,
                           check_aggregate_load=check_aggregate_load)
        self.profile = profile
        self.req = req
        self.gamma = gamma
        self.lam = self._proto.lam
        self.quantize = quantize
        self.max_tighten = max_tighten
        self.tighten_factor = tighten_factor
        self.check_aggregate_load = check_aggregate_load
        self.network0 = self._proto.network      # pristine base (live view)
        self.max_states = max_states

        N = self.network0.n_nodes
        L = profile.n_blocks
        self.U = int(n_users)
        self.N, self.L = N, L
        self.M = len(self._proto._modes)
        self.src = self.network0.source_node
        self.user_ids = (np.arange(self.U, dtype=np.int64)
                         if user_ids is None
                         else np.asarray(user_ids, dtype=np.int64))
        assert len(self.user_ids) == self.U

        # per-user SoA state (quantized packs live on the cohort states —
        # a user's pack IS their state's ``stq``, see the module doc)
        base_row = self._proto._bw[self.src].copy()
        base_row[self.src] = np.inf
        self._bw_vec = np.tile(base_row, (self.U, 1))          # (U, N)
        #: lazy bandwidth store: when set to (scale, factors) the DENSE
        #: ``_bw_vec`` contents are stale and the true store is the
        #: deferred product ``scale[:, None] * factors`` (src column inf).
        #: The dense-tick gate reads columns and the resolve subset reads
        #: rows, so the full (U, N) multiply — the single biggest memory
        #: pass of a steady tick — only happens if a dense consumer
        #: (checkpoint, partial ingest, slice reprice) actually shows up.
        #: All accessors (``_bw_dense``/``_bw_rows``/``_bw_cols``) produce
        #: values bit-identical to the eager multiply.
        self._bw_lazy: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._masked = np.zeros((self.U, N), dtype=bool)
        self._stale = np.zeros(self.U, dtype=bool)   # deferred requants
        self._user_state = np.full(self.U, -1, dtype=np.int64)
        self._solved = np.zeros(self.U, dtype=bool)
        self._inc_place = np.full((self.U, L), -1, dtype=np.int32)
        self._inc_exit = np.full(self.U, -1, dtype=np.int32)
        self._inc_energy = np.full(self.U, np.inf)
        self._solutions = np.full(self.U, None, dtype=object)
        #: whether any Solution object is live (lets the incumbent-only
        #: recording path skip the (U,) object-array clear entirely)
        self._any_solutions = False
        #: uniform-incumbent flag: the (exit, placement) every user is
        #: solved with, or None when unknown/mixed — lets the dense
        #: hysteresis gate skip the per-tick grouping key build
        self._inc_single: Optional[Tuple] = None

        # telemetry sanitization (see :class:`TelemetryPolicy`): quarantine
        # flags and frozen-sensor counters are always allocated (cheap);
        # the raw-reading history only when stuck detection is on
        self._telemetry = telemetry
        self._quarantined = np.zeros(self.U, dtype=bool)
        self._stuck_count = np.zeros(self.U, dtype=np.int32)
        self._last_raw = (np.full((self.U, N), np.nan)
                          if telemetry is not None
                          and telemetry.stuck_window > 0 else None)
        #: internal re-ingests (``update_slice`` replaying the stored
        #: bandwidths) must not look like telemetry ticks
        self._suspend_telemetry = False

        # cohort-state table (the cross-user DP dedupe)
        self._states: List[_CohortState] = []
        self._state_ids: Dict[bytes, int] = {}
        #: contingency-prebuilt state ids pinned through compaction
        #: (``core/contingency.py``; cleared when the state table is)
        self._pinned: set = set()
        #: cohort-wide exact-energy memo (energy is bandwidth-independent):
        #: (exit, placement) -> (energy, e_comp, e_comm); cleared with the
        #: state table on compute-slice churn
        self._cfg_energy: Dict[Tuple, Tuple[float, float, float]] = {}
        #: threshold-gate memo (``_gate_entry``): (exit, placement, factor
        #: values it reads) -> (energy, channel-scale threshold); cleared
        #: wherever the compute or backhaul terms move
        self._gate_cache: Dict[Tuple, Tuple[float, float]] = {}
        self._mesh_arg = mesh
        self._mesh_relaxer = None
        self._fallback_plan: Optional[Plan] = None
        #: vectorized frontier post-pass (core/frontier.py): all (candidate,
        #: user) pairs of a cohort state scored as stacked arrays instead of
        #: one scalar ``_best_feasible`` per unique (state, bandwidth) —
        #: bit-exact either way; False keeps the scalar path (the oracle).
        self._vector_postpass = bool(vector_postpass)
        #: bounded re-relaxation (affected-layer-onward resumes and whole-
        #: grid reuse for masked-out unreached nodes); False forces every
        #: newborn state through the full layer chain — the oracle switch
        #: the equivalence tests and benches flip
        self._bounded = bool(bounded_rerelax)
        #: live masked-entry count — lets the hot incumbent gate skip the
        #: (U, N) bitmap scan entirely when no user has a failure
        self._mask_count = 0
        self._timing = bool(timing)
        self._relax_executor = None      # lazy 1-thread pool (streaming)
        #: wall seconds of the most recent relaxation launch — the
        #: streaming pipeline's adaptive-overlap signal (see
        #: ``online.run_arrays``); always recorded, timing flag or not
        self._last_relax_s = 0.0
        self._ingest_backend = fused_ingest
        self._quant_consts: Optional[QuantConsts] = None
        #: tighten-cell dedupe for the batched fallback (see
        #: ``_tighten_batch``): relaxed single-mode states keyed by
        #: (round, signature@delta_eff, mask) plus the per-round base
        #: steepness stack.  Marginal users drift within a handful of
        #: quantization cells, so steady-state ticks hit these caches and
        #: the whole tighten herd costs scans, not relaxations.
        self._tighten_cache: Dict[Tuple[int, bytes, bytes],
                                  _CohortState] = {}
        self._tighten_base: Dict[int, np.ndarray] = {}
        self.stats = PopulationStats()
        # uniform cold start: every user holds the proto pack and an empty
        # failure mask, which is ONE cohort state — register it directly
        # instead of encoding/hashing U identical signature rows (the 1e7
        # cold start used to spend ~50 s here)
        self._enc_w = self.M * (2 * L - 1) * N
        self._stq_enc = np.empty((0, self._enc_w), dtype=np.int16)
        stq0 = self._proto._qpack.copy()
        mask0 = np.zeros(N, dtype=bool)
        self._user_state[:] = self._add_state(self._state_key(stq0, mask0),
                                              stq0, mask0)

    # ------------------------------------------------------------ properties
    @property
    def n_users(self) -> int:
        return self.U

    @property
    def n_states(self) -> int:
        return len(self._states)

    @property
    def depth_window_lo(self) -> Optional[int]:
        return self.gamma - self.lam if self.lam < self.gamma else None

    @property
    def masked_nodes(self) -> List[int]:
        """Nodes masked for EVERY user (the cohort-wide failure set)."""
        return [int(n) for n in np.nonzero(self._masked.all(axis=0))[0]]

    @property
    def inc_found(self) -> np.ndarray:
        """(U,) bool — users whose incumbent is a feasible configuration
        (``_best_feasible`` only ever returns exactly-feasible configs, so
        found == feasible, mirroring ``Solution.feasible``)."""
        return self._inc_exit >= 0

    def incumbents(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Copies of every user's incumbent: (U, L) placement, (U,) exit
        and (U,) energy (exit -1 and energy inf where none is feasible)."""
        return (self._inc_place.copy(), self._inc_exit.copy(),
                self._inc_energy.copy())

    def solution(self, u: int) -> Optional[Solution]:
        return self._solutions[u]

    def solutions(self, users: Optional[Sequence[int]] = None
                  ) -> List[Optional[Solution]]:
        users = range(self.U) if users is None else users
        return [self._solutions[int(u)] for u in users]

    # --------------------------------------------------------------- ingest
    def ingest(self, bps: Union[float, np.ndarray],
               users: Optional[np.ndarray] = None,
               requant: bool = True) -> Optional[np.ndarray]:
        """Per-tick channel ingest: set the selected users' source-link
        bandwidths and requantize their packs as ONE stacked pipeline.

        ``bps`` is a scalar, a (Us,) per-user scalar or a (Us, N)
        per-target matrix (``users`` defaults to the whole cohort).
        Elementwise identical to ``Plan.update_uplink`` per user; returns
        the (Us,) DP-input-changed flags.  Malformed shapes raise a clear
        ``ValueError`` up front (see ``plan._validate_population_bps``).

        ``requant=False`` defers the requantization: the bandwidths land
        now (incumbent re-evaluation reads only the TRUE bandwidth), the
        packs refresh lazily when a user actually re-solves — under
        hysteresis almost no one does, so the scale path skips ~all of the
        quantization work without changing any decision or solution.
        Returns None in that case (the change flags are not yet known).
        """
        with span(self._timing, self.stats, "t_ingest_ms", "pop.ingest"):
            users = (np.arange(self.U) if users is None
                     else np.asarray(users, dtype=np.int64))
            Us = len(users)
            self._bw_dense()  # partial write + last-known-good reads below
            arr = _validate_population_bps(bps, Us, self.N)
            vec = np.empty((Us, self.N))
            vec[:] = arr if arr.ndim == 2 else \
                (np.broadcast_to(np.asarray(arr, dtype=np.float64)
                                 .reshape(-1, 1), (Us, self.N)))
            vec[:, self.src] = np.inf                # self-loop (Sec. II-A)
            if not self._suspend_telemetry:
                self._screen_rows(users, vec)
            self._bw_vec[users] = vec
            self.stats.ingests += 1
            self.stats.uplink_updates += Us
            if not requant:
                self._stale[users] = True
                return None
            changed = self._requant_users(users, vec)
            self._stale[users] = False
            return changed

    def ingest_factors(self, scale: np.ndarray, factors: np.ndarray,
                       requant: bool = True) -> Optional[np.ndarray]:
        """Whole-cohort ingest from a per-user scale and a per-user factor
        row: the new bandwidth matrix is ``scale[:, None] * factors``
        written straight into the SoA store (one fused multiply, no
        intermediate (U, N) staging copy).  ``factors`` encodes the static
        per-user link pattern (attachment edge, detach fraction) so a
        dense channel tick only has to supply the (U,) fading scale.

        Semantically identical to ``ingest(scale[:, None] * factors)``
        over all users; same ``requant`` contract.
        """
        if scale.shape != (self.U,) or factors.shape != (self.U, self.N):
            raise ValueError(
                f"ingest_factors expects scale ({self.U},) and factors "
                f"({self.U}, {self.N}); got {scale.shape} and "
                f"{factors.shape}")
        with span(self._timing, self.stats, "t_ingest_ms", "pop.ingest"):
            if self._telemetry is None or self._telemetry.mode == "raise":
                # loud default: a corrupt fading scale must not reach the
                # store (factors are orchestrator-owned link patterns, not
                # telemetry)
                _validate_bps_values(scale, what="ingest_factors scale")
                if not requant:
                    # defer the (U, N) product: the gate and resolve subset
                    # read through the lazy accessors (see ``_bw_lazy``)
                    self._bw_lazy = (scale, factors)
                else:
                    np.multiply(scale[:, None], factors, out=self._bw_vec)
                    self._bw_vec[:, self.src] = np.inf  # self-loop (II-A)
                    self._bw_lazy = None
            else:
                # screened path: stage the product so quarantined/clamped
                # rows can be substituted before they land in the store —
                # values are bit-identical to the fused multiply
                self._bw_dense()   # substitution reads last-known-good rows
                vec = scale[:, None] * factors
                vec[:, self.src] = np.inf
                self._screen_rows(np.arange(self.U), vec)
                self._bw_vec[:] = vec
            self.stats.ingests += 1
            self.stats.uplink_updates += self.U
            if not requant:
                self._stale[:] = True
                return None
            changed = self._requant_users(np.arange(self.U), self._bw_vec)
            self._stale[:] = False
            return changed

    def _screen_rows(self, users: np.ndarray, vec: np.ndarray) -> None:
        """Telemetry screening over a staging ingest batch (in place).

        ``vec`` is the (Us, N) staging matrix about to be written into the
        bandwidth store (src column already inf).  Corrupt entries are
        NaN/Inf/negative outside the src column.  Without a policy (or in
        ``raise`` mode) any corruption raises a ``ValueError`` naming the
        offending users; ``clamp`` substitutes bad entries with the user's
        stored value; ``quarantine`` substitutes the WHOLE row of any
        offender (incl. stuck sensors) with their stored last-known-good
        vector — the subsequent wholesale store + requantize then treats a
        quarantined user exactly like a user whose channel froze, so no
        cohort state is ever keyed on a corrupt pack and held users keep
        serving their incumbent bit-exactly.
        """
        pol = self._telemetry
        bad_ent = ~np.isfinite(vec) | (vec < 0)
        bad_ent[:, self.src] = False
        any_bad = bool(bad_ent.any())
        if any_bad:
            self.stats.telemetry_bad += int(np.count_nonzero(bad_ent))
        if pol is None or pol.mode == "raise":
            if any_bad:
                _validate_bps_values(None, bad=bad_ent, users=users,
                                     what="ingest bps")
            return
        if pol.mode == "clamp":
            if any_bad:
                np.copyto(vec, self._bw_vec[users], where=bad_ent)
                self.stats.telemetry_clamped += \
                    int(np.count_nonzero(bad_ent))
            return
        # quarantine: row-level hold on corrupt or frozen readings
        bad_user = bad_ent.any(axis=1)
        if pol.stuck_window > 0:
            rep = (vec == self._last_raw[users]).all(axis=1)
            cnt = np.where(rep, self._stuck_count[users] + 1, 0)
            self._stuck_count[users] = cnt
            self._last_raw[users] = vec
            bad_user |= cnt >= pol.stuck_window
        was_q = self._quarantined[users]
        newly = bad_user & ~was_q
        healed = was_q & ~bad_user
        if newly.any():
            self._quarantined[users[newly]] = True
            self.stats.quarantines += int(np.count_nonzero(newly))
        if healed.any():
            self._quarantined[users[healed]] = False
            self.stats.recoveries += int(np.count_nonzero(healed))
        if bad_user.any():
            np.copyto(vec, self._bw_vec[users], where=bad_user[:, None])

    # ---------------------------------------------- lazy bandwidth accessors
    def _bw_dense(self) -> np.ndarray:
        """The dense (U, N) bandwidth store, materializing a pending lazy
        product first (one fused multiply — identical to the eager path)."""
        lz = self._bw_lazy
        if lz is not None:
            sc, fac = lz
            np.multiply(sc[:, None], fac, out=self._bw_vec)
            self._bw_vec[:, self.src] = np.inf
            self._bw_lazy = None
        return self._bw_vec

    def _bw_rows(self, users: np.ndarray) -> np.ndarray:
        """Selected users' bandwidth rows — a gather-then-multiply under a
        pending lazy store (per-element IEEE ops identical to multiplying
        first and gathering after), a plain row gather otherwise."""
        lz = self._bw_lazy
        if lz is None:
            return self._bw_vec[users]
        sc, fac = lz
        out = sc[users][:, None] * fac[users]
        out[:, self.src] = np.inf
        return out

    def _bw_cols(self):
        """Whole-store column view for ``eval_config_users`` (it touches
        only ``bwv[:, n]`` / ``len``): the dense array, or a zero-copy
        column materializer over the lazy (scale, factors) pair."""
        lz = self._bw_lazy
        if lz is None:
            return self._bw_vec
        return _LazyBwCols(lz[0], lz[1], self.src)

    def _refresh_states(self, users: np.ndarray) -> None:
        """Flush deferred requantizations (lazy ingest) for these users."""
        sel = users[self._stale[users]]
        if len(sel):
            with span(self._timing, self.stats, "t_ingest_ms", "pop.ingest"):
                self._requant_users(sel, self._bw_rows(sel))
                self._stale[sel] = False

    def _quant(self) -> QuantConsts:
        """The fused requantizer's constants bundle — snapshots the proto
        packs, so compute-slice repricings must drop it (they rebuild the
        packs); backhaul repricings are bandwidth-only and keep it."""
        c = self._quant_consts
        if c is None:
            p = self._proto
            c = self._quant_consts = QuantConsts(
                bits_pack=p._bits_pack, C_pack=p._C_pack,
                mask_pack=p._mask_pack, load_pack=p._load_pack,
                modes=tuple(p._modes), gamma=self.gamma,
                delta=self.req.delta)
        return c

    def _requant_users(self, users: np.ndarray,
                       vec: np.ndarray) -> np.ndarray:
        """Fused requantize of the given users' bandwidth rows: ONE
        quantize->int16->signature launch (``kernels/ee_gate/population``,
        elementwise identical to ``plan.update_uplinks`` + the signature
        encode), compared against a gather from the per-state signature
        table — users whose encoding moved re-key through
        ``_assign_states`` with the fresh rows, everyone else costs one
        int16 row compare."""
        enc = quant_signature(vec, self._quant(),
                              backend=self._ingest_backend)
        old = self._stq_enc[self._user_state[users]]
        changed = (enc != old).any(axis=1)
        if changed.any():
            self._assign_states(users[changed], enc=enc[changed])
        self.stats.quant_changed += int(np.count_nonzero(changed))
        return changed

    # ------------------------------------------------------------- failures
    def mask_node(self, n: int, users: Optional[Sequence[int]] = None
                  ) -> "Population":
        """Node failure for ``users`` (default: the whole cohort) — same
        semantics as ``Plan.mask_node`` per user."""
        if n == self.src:
            raise ValueError("cannot mask the source-hosting node")
        sel = (np.arange(self.U) if users is None
               else np.asarray(users, dtype=np.int64))
        flip = sel[~self._masked[sel, n]]
        if len(flip):
            self._masked[flip, n] = True
            self._mask_count += len(flip)
            self._assign_states(flip)
        return self

    def unmask_node(self, n: int, users: Optional[Sequence[int]] = None
                    ) -> "Population":
        sel = (np.arange(self.U) if users is None
               else np.asarray(users, dtype=np.int64))
        flip = sel[self._masked[sel, n]]
        if len(flip):
            self._masked[flip, n] = False
            self._mask_count -= len(flip)
            self._assign_states(flip)
        return self

    def update_slice(self, frac: Union[float, np.ndarray]) -> "Population":
        """Cohort-wide compute-slice rescale (``Plan.update_slice`` with
        ``nodes=None`` for every user).  ``frac`` is a scalar or an (N,)
        per-node factor vector (congestion pricing rescales individual
        nodes); either way it applies to every user of the cohort —
        per-user slices would break the cohort's shared energy tensors,
        so model those as separate cohorts.
        """
        self._proto.update_slice(frac)
        with span(self._timing, self.stats, "t_ingest_ms", "pop.ingest"):
            # the proto rebuilt its packs and base tensors in place or
            # replaced them; every cached cohort state quantized against the
            # old compute terms is now stale (incl. fast tables), the
            # memoized exact energies moved with the compute terms, and the
            # fallback plan's compute base as well.  Capture the pre-slice
            # signatures first — the quant_changed counter compares against
            # them, and the table (their backing store) is about to clear.
            old_enc = self._stq_enc[self._user_state]
            self._states = []
            self._state_ids = {}
            self._pinned = set()
            self._cfg_energy = {}
            self._fallback_plan = None
            self._quant_consts = None
            self._gate_cache = {}
            self._tighten_cache = {}
            self._tighten_base = {}
            self._stq_enc = np.empty((0, self._enc_w), dtype=np.int16)
            # requantize every user against the new compute terms in one
            # fused launch and re-key everyone — the stored bandwidths were
            # already screened, so this must not look like a telemetry tick
            # (quarantine/stuck state and counters stay untouched)
            enc = quant_signature(self._bw_dense(), self._quant(),
                                  backend=self._ingest_backend)
            self.stats.ingests += 1
            self.stats.uplink_updates += self.U
            self.stats.quant_changed += \
                int(np.count_nonzero((enc != old_enc).any(axis=1)))
            self._assign_states(np.arange(self.U), enc=enc)
            self._stale[:] = False
        return self

    def update_backhaul(self, scale: Union[float, np.ndarray]
                        ) -> "Population":
        """Cohort-wide backhaul rescale (``Plan.update_backhaul`` for every
        user): non-source links serve ``bw_base * scale`` — the congestion
        pricing delta for shared links.

        The packed uplink requantizer constants are bandwidth-independent,
        so every user's quantized pack keeps its value verbatim — and
        therefore so does the whole (pack, mask) partition: the cohort
        states are rebuilt IN PLACE (fresh steepness/init tensors against
        the repriced base; DP grids, candidate caches and fast tables
        dropped) with their ids, signature keys, user assignment and
        pinned set all preserved.  No per-user pass at all — link
        repricing costs O(states), not O(users), which is what keeps the
        congestion fixed-point loop cheap at population scale.  The
        memoized exact energies survive too — Eq. (2) has no bandwidth
        term.
        """
        self._proto.update_backhaul(scale)
        for s in self._states:
            s.steep, s.grid = self._state_tensors(s.stq, s.mask)
            s.dps = None
            s.cand = {}
            s.fast = None
        self._fallback_plan = None
        self._gate_cache = {}       # repriced links move the thresholds
        # tighten states quantize the repriced non-source links too
        self._tighten_cache = {}
        self._tighten_base = {}
        return self

    # ------------------------------------------------------- state registry
    def _assign_states(self, users: np.ndarray,
                       enc: Optional[np.ndarray] = None) -> None:
        """(Re)key the given users' (quantized pack, mask) signatures into
        cohort states, materializing states never seen before — touching
        ONLY the given rows and merging into the existing table (the
        stale-subset re-key; callers pass exactly the users whose
        signature may have moved).

        ``enc`` is the users' freshly-quantized (Us, M*K2*N) int16 pack
        encoding (the fused ingest kernel's output); None re-keys the
        users' CURRENT packs (mask flips), read back from the per-state
        signature table — per-user packs are never stored, a user's pack
        always equals their state's."""
        Us = len(users)
        if Us == 0:
            return
        self.stats.rekeyed_users += Us
        with span(self._timing, self.stats, "t_rekey_ms", "pop.rekey",
                  users=Us):
            self._rekey(users, enc)

    def _rekey(self, users: np.ndarray, enc: Optional[np.ndarray]) -> None:
        """The body of :meth:`_assign_states` (its ``pop.rekey`` span)."""
        Us = len(users)
        old_sids = self._user_state[users]       # bounded-resume hints
        if enc is None:
            enc = self._stq_enc[old_sids]
        W = self._enc_w
        rows = np.empty((Us, W + self.N), dtype=np.int16)
        rows[:, :W] = enc
        rows[:, W:] = self._masked[users]
        v = rows.view(np.dtype((np.void, rows.shape[1] * 2))).ravel()
        K2 = 2 * self.L - 1

        def materialize(j: int) -> int:
            key = v[j].tobytes()
            sid = self._state_ids.get(key)
            if sid is None:
                stq = _dec_int16(enc[j]).reshape(self.M, K2, self.N)
                sid = self._add_state(key, stq,
                                      self._masked[int(users[j])].copy(),
                                      parent=int(old_sids[j]))
            return sid

        if Us > 1 and bool((v == v[0]).all()):
            # one signature for the whole batch (cold start, uniform
            # scale moves): skip the million-row unique/argsort entirely
            self._user_state[users] = materialize(0)
            if len(self._states) > self.max_states:
                self._compact_states()
            return
        uniq, first, inv = np.unique(v, return_index=True,
                                     return_inverse=True)
        sids = np.empty(len(uniq), dtype=np.int64)
        for i, j in enumerate(first):
            sids[i] = materialize(int(j))
        self._user_state[users] = sids[inv]
        if len(self._states) > self.max_states:
            self._compact_states()

    def _state_key(self, stq: np.ndarray, mask: np.ndarray) -> bytes:
        """The scalar form of ``_assign_states``'s signature encoding —
        byte-identical to the batched path, so an out-of-band caller (the
        contingency prebuilder) can probe/register states a user would be
        keyed into without a user actually holding that (pack, mask)."""
        M, K2, N = self.M, 2 * self.L - 1, self.N
        enc = np.empty(M * K2 * N + N, dtype=np.int16)
        q = np.ascontiguousarray(stq).reshape(-1)
        fin = np.isfinite(q)
        np.copyto(enc[:M * K2 * N], q, casting="unsafe", where=fin)
        enc[:M * K2 * N][~fin] = -1
        enc[M * K2 * N:] = mask
        return enc.tobytes()

    def _state_tensors(self, stq: np.ndarray, mask: np.ndarray,
                       base_steep: Optional[np.ndarray] = None
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """A state's DP input tensors: scatter the pack's source-node
        rows/cols into a copy of the base steepness stack and rebuild the
        init grid — the exact formulas of ``Plan._apply_qpack``, with
        ``Plan._quant_state``'s failure masking folded in.  (Also the
        backhaul-repricing rebuild: the base stack moved, the pack did
        not.)  ``base_steep`` swaps in a different-width base — the
        tighten fallback passes a single-mode delta_eff stack whose pack
        carries only the main quantizer."""
        proto = self._proto
        L, G, src = self.L, self.gamma, self.src
        steep = (proto._steep if base_steep is None
                 else base_steep).copy()             # (M, L-1, N, N) base
        steep[:, :, src, :] = stq[:, :L - 1]
        steep[:, :, :, src] = stq[:, L:]
        grid = np.full((stq.shape[0], self.N, G + 1), np.inf)
        d = stq[:, L - 1, :]                         # (M, N) init depths
        mi_i, n_i = np.nonzero(np.isfinite(d) & (d <= G))
        grid[mi_i, n_i, d[mi_i, n_i].astype(np.int64)] = \
            proto._ext.init_E[n_i]
        if mask.any():
            steep[:, :, mask, :] = np.inf
            steep[:, :, :, mask] = np.inf
            grid[:, mask, :] = np.inf
        return steep, grid

    def _enc_push(self, enc_row: np.ndarray) -> None:
        """Append a state's int16 signature row to the amortized-growing
        ``_stq_enc`` table (valid rows = ``len(self._states)``)."""
        n = len(self._states)
        cap = len(self._stq_enc)
        if n > cap:
            grown = np.empty((max(16, 2 * cap, n), self._enc_w),
                             dtype=np.int16)
            grown[:cap] = self._stq_enc
            self._stq_enc = grown
        self._stq_enc[n - 1] = enc_row

    def _add_state(self, key: bytes, stq: np.ndarray,
                   mask: np.ndarray, parent: int = -1) -> int:
        """Materialize a cohort state (see ``_state_tensors``) and record
        its int16 signature row."""
        steep, grid = self._state_tensors(stq, mask)
        sid = len(self._states)
        self._states.append(_CohortState(stq, mask, steep, grid,
                                         parent=parent))
        self._state_ids[key] = sid
        self._enc_push(_enc_int16(stq).reshape(-1))
        return sid

    def _compact_states(self) -> None:
        """Drop cohort states no user references (bounds cache growth under
        adversarial churn; referenced states and their DP grids survive).
        Contingency-pinned states survive too — evicting a prebuilt state
        would silently turn its failover back into a relaxation."""
        live = np.unique(self._user_state)
        if self._pinned:
            live = np.unique(np.concatenate(
                [live, np.fromiter(self._pinned, dtype=np.int64)]))
        remap = {int(s): i for i, s in enumerate(live)}
        self._states = [self._states[int(s)] for s in live]
        self._stq_enc = self._stq_enc[live]
        self._state_ids = {k: remap[s] for k, s in self._state_ids.items()
                           if s in remap}
        self._user_state = np.searchsorted(live, self._user_state)
        self._pinned = {remap[s] for s in self._pinned if s in remap}
        self.stats.state_evictions += 1

    # ------------------------------------------------------------ relaxation
    def _relax_states(self, sids: Sequence[int], *,
                      prebuilt: bool = False) -> None:
        """Chained banded relaxation of the given (unrelaxed) cohort states.

        Newborns split three ways: states whose validated parent hint
        proves a layer-prefix match resume from the parent's saved grid
        slice (bounded re-relaxation); pure-mask deltas on nodes the
        parent never reached share the parent's relaxed grids outright;
        the rest ride the full chain — ONE fused launch when the whole
        stack fits the cache-residency budget
        (``bellman_ford.relax_chunk_rows``), the chunked fallback when it
        does not.  ``prebuilt`` routes the counter to
        ``stats.prebuilt_states`` (contingency refills relax off the
        failure tick; a covered tick's ``dp_relaxes`` delta stays zero)."""
        states = [self._states[int(s)] for s in sids]
        if not states:
            return
        # the overlap EWMA reads _last_relax_s, timing flag or not
        t0 = time.perf_counter()
        with span(self._timing, self.stats, "t_relax_ms", "pop.relax"):
            self._relax_split(states)
        self._last_relax_s = time.perf_counter() - t0
        if prebuilt:
            self.stats.prebuilt_states += len(states)
        else:
            self.stats.dp_relaxes += len(states)

    def _relax_split(self, states: List[_CohortState]) -> None:
        """The body of :meth:`_relax_states`: bounded resumes, shared
        grids and the full chain."""
        full: List[_CohortState] = []
        resume: Dict[int, List[Tuple[_CohortState, _CohortState]]] = {}
        if self._bounded:
            for s in states:
                hint = self._resume_hint(s)
                if hint is None:
                    full.append(s)
                    continue
                kind, parent, l0 = hint
                if kind == "share":
                    s.dps = [_BandedArgDP(pd.hist, pd.par_n, s.steep[mi])
                             for mi, pd in enumerate(parent.dps)]
                    self.stats.mask_reuses += 1
                else:
                    resume.setdefault(l0, []).append((s, parent))
        else:
            full = states
        if full:
            self._relax_full(full)
        for l0 in sorted(resume):
            pairs = resume[l0]
            self._relax_resume(l0, pairs)
            self.stats.bounded_relaxes += len(pairs)
            self.stats.layers_skipped += l0 * len(pairs)

    def _resume_hint(self, s: _CohortState
                     ) -> Optional[Tuple[str, _CohortState, int]]:
        """Validate a newborn's parent hint (see ``_CohortState.parent``).

        Returns None (full relax), ("share", parent, 0) when the parent's
        relaxed grids serve the state verbatim — a pure mask-add delta on
        nodes the parent's chain never reached (all-inf rows at every
        block, so no finite cell and no backtrack can touch them) — or
        ("resume", parent, l0) when layers < l0 are provably identical.
        The hint is re-validated against whatever state sits at the index
        NOW, so compaction/renumbering can only cost speed, not
        correctness; resumes are float64-engine-only (the f32 engines
        round intermediates in-chain, so a spliced prefix is not an
        identity there)."""
        p = s.parent
        if p < 0 or p >= len(self._states):
            return None
        parent = self._states[p]
        if parent is s or parent.dps is None:
            return None
        L = self.L
        if np.array_equal(s.stq, parent.stq):
            added = s.mask & ~parent.mask
            if not added.any() or (parent.mask & ~s.mask).any():
                return None
            for pd in parent.dps:
                if np.isfinite(pd.hist[:, added, :]).any():
                    return None
            return ("share", parent, 0)
        if self._engine != "banded" or self.backend == "mesh":
            return None
        if not np.array_equal(s.mask, parent.mask):
            return None
        # first affected relax layer: pack row r < L-1 scatters into the
        # layer-r source row, row r >= L into the layer-(r-L) source col;
        # a moved init-depth row (r == L-1) moves the layer-0 input, so
        # nothing can be skipped
        diff = (s.stq != parent.stq).any(axis=(0, 2))          # (2L-1,)
        l0 = L - 1
        for r in np.nonzero(diff)[0]:
            r = int(r)
            layer = 0 if r == L - 1 else (r if r < L - 1 else r - L)
            l0 = min(l0, layer)
        if l0 < 1:
            return None
        return ("resume", parent, l0)

    def _relax_full(self, states: List[_CohortState]) -> None:
        """Full-chain relaxation: one fused launch across every state when
        the (D*M, L-1, N, N) stack fits the residency budget, the chunked
        loop when it does not (``REPRO_RELAX_CHUNK_BYTES`` shrinks the
        budget; tiny values force the fallback — see the chunking tests)."""
        Ms = [s.steep.shape[0] for s in states]   # per-state mode counts
        B = sum(Ms)                               # (tighten states carry 1)
        N, Gp1 = self.N, self.gamma + 1
        steep = np.concatenate([s.steep for s in states])      # (B, ...)
        grid = np.concatenate([s.grid for s in states])
        E = np.broadcast_to(self._proto._ext.E[None],
                            (B,) + self._proto._ext.E.shape)
        lo = self.depth_window_lo
        if self.backend == "mesh":
            hist, par = self._mesh().relax(grid, E, steep, lo)
            self.stats.fused_relaxes += 1
        else:
            chunk = relax_chunk_rows(N * N * Gp1 * 16)
            if B <= chunk:
                hist, par = self._relax_batch(grid, E, steep, lo)
                self.stats.fused_relaxes += 1
            else:
                hists, pars = [], []
                for start in range(0, B, chunk):
                    sl = slice(start, start + chunk)
                    h, p = self._relax_batch(grid[sl], E[sl], steep[sl], lo)
                    hists.append(h)
                    pars.append(p)
                hist = np.concatenate(hists)
                par = np.concatenate(pars)
                self.stats.chunked_relaxes += 1
        off = 0
        for s, m in zip(states, Ms):
            s.dps = [_BandedArgDP(hist[off + mi], par[off + mi],
                                  s.steep[mi]) for mi in range(m)]
            off += m

    def _relax_batch(self, grid: np.ndarray, E: np.ndarray,
                     steep: np.ndarray, lo: Optional[int]
                     ) -> Tuple[np.ndarray, np.ndarray]:
        if self._engine == "banded":
            return batched_banded_relax_minarg(grid, E, steep, lo)
        return batched_banded_relax_argmin(
            grid, np.ascontiguousarray(E), steep, lo, backend=self._engine)

    def _relax_resume(self, l0: int,
                      pairs: List[Tuple[_CohortState, _CohortState]]
                      ) -> None:
        """Bounded re-relaxation: seed a relax over layers ``l0:`` with the
        parents' saved block-``l0`` grid slices and splice the untouched
        hist/par prefixes back in.  Bit-exact vs the full chain because the
        depth-window masking is DEPTH-based, not layer-position-based
        (``bellman_ford._banded_gather_idx``), so the suffix relax applies
        exactly the ops the full chain would from block ``l0`` on."""
        M = self.M
        lo = self.depth_window_lo
        init = np.stack([pr.dps[mi].hist[l0]
                         for s, pr in pairs for mi in range(M)])
        steep = np.concatenate([s.steep[:, l0:] for s, _pr in pairs])
        E_one = self._proto._ext.E[l0:]
        E = np.broadcast_to(E_one[None], (len(init),) + E_one.shape)
        hist, par = batched_banded_relax_minarg(init, E, steep, lo)
        for i, (s, pr) in enumerate(pairs):
            dps = []
            for mi in range(M):
                pd = pr.dps[mi]
                h = np.concatenate([pd.hist[:l0], hist[i * M + mi]])
                pn = np.concatenate([pd.par_n[:l0], par[i * M + mi]])
                dps.append(_BandedArgDP(h, pn, s.steep[mi]))
            s.dps = dps

    def _mesh(self):
        if self._mesh_relaxer is None:
            from repro.sharding.population import MeshRelaxer
            self._mesh_relaxer = MeshRelaxer(self._mesh_arg)
        return self._mesh_relaxer

    # ------------------------------------------------------------- post-pass
    def _exit_candidates(self, state: _CohortState, mi: int, k: int):
        """Lazy energy-ordered candidates at exit ``k`` — the sequence of
        ``fin._iter_configs_at_exit``, cached on the cohort state so every
        user sharing the state shares one backtrack."""
        cache = state.cand.get((mi, k))
        if cache is None:
            cache = state.cand[(mi, k)] = _CandCache()
        i = 0
        while True:
            while i < len(cache.items):
                yield cache.items[i]
                i += 1
            if cache.exhausted:
                return
            self._extend_candidates(state, mi, k, cache)

    def _extend_candidates(self, state: _CohortState, mi: int, k: int,
                           cache: _CandCache) -> None:
        dp = state.dps[mi]
        block = self.profile.exits[k].block
        d = dp.dist[block]                        # (N, G+1, 1)
        if not cache.items:
            # fast path of _iter_configs_at_exit: cheapest state via argmin
            j0 = int(np.argmin(d))
            v0 = float(d.ravel()[j0])
            if not np.isfinite(v0):
                cache.exhausted = True
                return
            n0, g0, r0 = np.unravel_index(j0, d.shape)
            cfg = Config(placement=_backtrack(dp, block, int(n0), int(g0),
                                              int(r0)), final_exit=k)
            cache.items.append((cfg, v0))
            return
        if cache.order is None:
            order = np.argsort(d, axis=None, kind="stable")
            vals = d.ravel()[order]
            cache.order = (order, vals, int(np.searchsorted(vals, np.inf)))
        order, vals, n_finite = cache.order
        j = len(cache.items)
        if j >= n_finite:
            cache.exhausted = True
            return
        n_, g_, r_ = np.unravel_index(int(order[j]), d.shape)
        cfg = Config(placement=_backtrack(dp, block, int(n_), int(g_),
                                          int(r_)), final_exit=k)
        cache.items.append((cfg, float(vals[j])))

    def _candidate(self, state: _CohortState, mi: int, k: int,
                   j: int) -> Optional[Tuple[Config, float]]:
        """Indexed access into the shared per-state candidate frontier:
        the j-th energy-ordered candidate at exit ``k`` (lazily extended),
        or None when the exit's candidates are exhausted."""
        cache = state.cand.get((mi, k))
        if cache is None:
            cache = state.cand[(mi, k)] = _CandCache()
        while len(cache.items) <= j and not cache.exhausted:
            self._extend_candidates(state, mi, k, cache)
        return cache.items[j] if j < len(cache.items) else None

    def _eval_users_factory(self, bwv: np.ndarray):
        """Bind the cohort's shared tensors into a vectorized exact
        evaluator over the given (Us, N) per-user bandwidth rows."""
        prof, req = self.profile, self.req
        nodes = self.network0.nodes
        base_bw = self._proto._bw
        comp = self._proto._compute
        src = self.src
        chk = self.check_aggregate_load

        def ev(cfg: Config, idx: np.ndarray):
            return eval_config_users(prof, req, nodes, base_bw, comp, src,
                                     cfg, bwv[idx],
                                     check_aggregate_load=chk)
        return ev

    def _scan_state_group(self, state: _CohortState, bwv: np.ndarray):
        """``_solve_one``'s control flow vectorized over a whole user batch
        sharing one cohort state: the main-pass scan, the ceil rescue pass
        bounded by the main pass's per-user energies, and the rare
        no-feasible fallback — all (candidate, user) pairs scored as
        stacked arrays (``frontier.scan_state_users``), with per-user
        selections bit-identical to the scalar post-pass.

        Returns (cfgs, energy, lat, e_comp, e_comm, used_ceil, exit_, fb):
        per-user chosen Config references (shared candidate objects, None
        where nothing was found), their exact objective parts, the
        ceil-pass markers and per-user fallback Solutions (None except on
        the tighten path).
        """
        Us = len(bwv)
        adm = self._proto._admissible
        ev = self._eval_users_factory(bwv)
        s0 = scan_state_users(
            state.dps[0], self.profile, adm,
            lambda k, j: self._candidate(state, 0, k, j), ev, Us,
            dist_tol=self._dist_tol)
        cfgs: List[Optional[Config]] = [None] * Us
        fb: List[Optional[Solution]] = [None] * Us
        energy = s0.energy.copy()
        lat = s0.latency.copy()
        e_comp = s0.e_comp.copy()
        e_comm = s0.e_comm.copy()
        exit_ = s0.exit.copy()
        cand_ = s0.cand.copy()
        mi_ = np.zeros(Us, dtype=np.int64)
        used_ceil = np.zeros(Us, dtype=bool)
        fb_mask = ~s0.found & (self.max_tighten > 0)
        fb_idx = np.nonzero(fb_mask)[0]
        no_exit = not adm
        tb = None
        if len(fb_idx):
            # batched Plan.solve tighten loop (round 0 already failed via
            # the s0 scan above — bit-exact, same dp, same scan contract)
            self.stats.fallbacks += len(fb_idx)
            if not no_exit:
                with span(self._timing, None, None, "pop.post.fallback"):
                    tb = self._tighten_batch(bwv[fb_idx], state)
        s1 = None
        if self.quantize != "ceil" and (len(fb_idx) < Us or tb is not None):
            # one ceil rescue scan for everyone: the non-fallback users
            # bounded by their main-pass energies (the old subset scan),
            # the fallback users bounded by their tighten energies —
            # exactly Plan.solve's ``_scan(dps[1], best)``
            bound = np.where(s0.found, s0.energy, np.nan)
            if tb is not None:
                bound[fb_idx] = np.where(tb.found, tb.energy, np.nan)
            s1 = scan_state_users(
                state.dps[1], self.profile, adm,
                lambda k, j: self._candidate(state, 1, k, j),
                ev, Us, dist_tol=self._dist_tol, bound_energy=bound)
            take = s1.found & (~s0.found | (s1.energy < energy)) & ~fb_mask
            t = np.nonzero(take)[0]
            exit_[t] = s1.exit[take]
            cand_[t] = s1.cand[take]
            mi_[t] = 1
            energy[t] = s1.energy[take]
            lat[t] = s1.latency[take]
            e_comp[t] = s1.e_comp[take]
            e_comm[t] = s1.e_comm[take]
            used_ceil[t] = True
        for i in np.nonzero(~fb_mask)[0]:
            if exit_[i] >= 0:
                cfgs[i] = self._candidate(state, int(mi_[i]), int(exit_[i]),
                                          int(cand_[i]))[0]
        if len(fb_idx):
            self._tighten_assemble(fb, fb_idx, tb, s1, state, no_exit)
        return cfgs, energy, lat, e_comp, e_comm, used_ceil, exit_, fb

    def _scan_state(self, state: _CohortState, mi: int, network: Network,
                    bound=None):
        return _best_feasible(
            network, self.profile, self.req, state.dps[mi],
            self._proto._admissible, self.check_aggregate_load,
            oracle=False, bound=bound, dist_tol=self._dist_tol,
            candidates=lambda k: self._exit_candidates(state, mi, k))

    def _user_network(self, bw_row: np.ndarray) -> Network:
        bw = self._proto._bw.copy()
        src = self.src
        bw[src, :] = bw_row
        bw[:, src] = bw_row
        bw[src, src] = np.inf
        return Network(nodes=list(self.network0.nodes), bandwidth=bw,
                       compute=self._proto._compute, source_node=src)

    def _fallback_solve(self, bw_row: np.ndarray,
                        mask: np.ndarray) -> Solution:
        """Exact rare-path solve (tighten loop / no-feasible round 0): one
        persistent warm Plan per cohort replays the user's (bandwidth,
        mask) state and runs the whole ``Plan.solve`` control flow, whose
        warm==cold invariant is property-tested.  Warm deltas on the kept
        plan cost microseconds where a fresh Plan build costs milliseconds
        — and users with no feasible placement hit this path every tick
        they stay dirty."""
        with span(self._timing, None, None, "pop.post.fallback"):
            plan = self._fallback_plan
            if plan is None:
                plan = self._fallback_plan = Plan(
                    self.network0, self.profile, self.req, gamma=self.gamma,
                    lam=self.lam, quantize=self.quantize,
                    max_tighten=self.max_tighten,
                    tighten_factor=self.tighten_factor, n_best=1,
                    backend=self._plan_backend,
                    check_aggregate_load=self.check_aggregate_load)
            plan.update_uplink(bw_row)
            have = plan._masked.copy()
            for n in np.nonzero(mask & ~have)[0]:
                plan.mask_node(int(n))
            for n in np.nonzero(have & ~mask)[0]:
                plan.unmask_node(int(n))
            self.stats.fallbacks += 1
            return plan.solve()

    def _tighten_consts(self, delta_eff: float) -> QuantConsts:
        """Single-mode constants bundle for one tighten round: the same
        bandwidth-independent packs as the base requantizer, quantized
        against ``delta_eff`` with only the main quantizer mode."""
        base = self._quant()
        return QuantConsts(bits_pack=base.bits_pack, C_pack=base.C_pack,
                           mask_pack=base.mask_pack,
                           load_pack=base.load_pack,
                           modes=(self.quantize,), gamma=self.gamma,
                           delta=float(delta_eff))

    def _tighten_state(self, round_: int, enc_row: np.ndarray,
                       mask: np.ndarray, delta_eff: float) -> _CohortState:
        """A (relaxable) single-mode cohort state for one tighten cell:
        non-source steepness from a per-round ``build_feasible_graph`` at
        ``delta_eff`` (shared by every user — those links' bandwidths are
        cohort-wide), source rows/cols and init depths scattered from the
        user pack, exactly ``Plan._feasible``'s tensors.  Cached by
        (round, signature, mask) OUTSIDE the main state table — a
        tightened signature must never collide with a base-delta key."""
        key = (round_, enc_row.tobytes(), mask.tobytes())
        st = self._tighten_cache.get(key)
        if st is not None:
            return st
        base = self._tighten_base.get(round_)
        if base is None:
            self._proto._flush_ext()
            fg = build_feasible_graph(self._proto._ext, self.gamma,
                                      lam=self.lam, quantize=self.quantize,
                                      delta_eff=delta_eff)
            base = self._tighten_base[round_] = fg.steep[None].copy()
        stq = _dec_int16(enc_row).reshape(1, 2 * self.L - 1, self.N)
        steep, grid = self._state_tensors(stq, mask, base_steep=base)
        st = _CohortState(stq, mask, steep, grid)
        if len(self._tighten_cache) >= 8192:   # adversarial-churn bound
            self._tighten_cache.clear()
        self._tighten_cache[key] = st
        return st

    def _tighten_batch(self, bwv_fb: np.ndarray,
                       state: _CohortState) -> "_TightenResult":
        """``Plan.solve``'s tighten loop batched over every no-feasible
        user of one cohort state.  Per round: ONE fused requantize of the
        still-unsolved rows at the round's ``delta_eff``, dedupe into
        tighten cells, ONE fused relaxation of the unseen cells, and one
        vectorized scan per cell — per-user results bit-exact vs the
        scalar per-user ``Plan.solve`` replay (rounds are per-user
        independent, the dp for a signature is unique, and the scan
        contract is the PR-5 one).  Steady-state churn revisits the same
        cells, so the cache turns the whole herd into pure scans."""
        F = len(bwv_fb)
        res = _TightenResult(F, self.max_tighten)
        adm = self._proto._admissible
        alive = np.arange(F)
        delta_eff = self.req.delta
        for r in range(1, self.max_tighten + 1):
            delta_eff *= self.tighten_factor    # Plan's own accumulation
            if not len(alive):
                break
            enc = quant_signature(bwv_fb[alive],
                                  self._tighten_consts(delta_eff),
                                  backend=self._ingest_backend)
            enc = np.ascontiguousarray(enc)
            v = enc.view(np.dtype((np.void,
                                   enc.shape[1] * enc.dtype.itemsize)))
            _uniq, inv = np.unique(v.ravel(), return_inverse=True)
            groups = [np.nonzero(inv == g)[0] for g in range(len(_uniq))]
            sts = [self._tighten_state(r, enc[g[0]], state.mask, delta_eff)
                   for g in groups]
            fresh = [st for st in sts if st.dps is None]
            if fresh:
                self._relax_full(fresh)
            still = []
            for st, g in zip(sts, groups):
                members = alive[g]
                sc = scan_state_users(
                    st.dps[0], self.profile, adm,
                    lambda k, j, st=st: self._candidate(st, 0, k, j),
                    self._eval_users_factory(bwv_fb[members]), len(members),
                    dist_tol=self._dist_tol)
                hit = sc.found
                hu = members[hit]
                res.found[hu] = True
                res.energy[hu] = sc.energy[hit]
                res.latency[hu] = sc.latency[hit]
                res.e_comp[hu] = sc.e_comp[hit]
                res.e_comm[hu] = sc.e_comm[hit]
                res.exit[hu] = sc.exit[hit]
                res.rounds[hu] = r
                res.delta_eff[hu] = delta_eff
                for p, k, c in zip(hu, sc.exit[hit], sc.cand[hit]):
                    res.cfgs[p] = self._candidate(st, 0, int(k),
                                                  int(c))[0]
                still.append(members[~hit])
            alive = (np.concatenate(still) if still
                     else np.empty(0, dtype=np.int64))
        if len(alive):
            # Plan multiplies once more after the last failed round; the
            # ceil rescue (if it lands) reports that final delta_eff
            res.delta_eff[alive] = delta_eff * self.tighten_factor
        return res

    def _tighten_assemble(self, fb: List[Optional[Solution]],
                          fb_idx: np.ndarray,
                          tb: Optional["_TightenResult"], s1,
                          state: _CohortState, no_exit: bool) -> None:
        """Fold the batched tighten results and the shared ceil-rescue
        scan into per-user ``Solution``s shaped like ``Plan.solve``'s
        (config/eval bit-identical; meta carries the same tighten_rounds /
        delta_eff / used_ceil_pass bookkeeping)."""
        base_meta = {"gamma": self.gamma, "quantize": self.quantize,
                     "backend": self._plan_backend, "warm": True,
                     "population": True}
        if no_exit:
            m = {**base_meta, "tighten_rounds": 0,
                 "reason": "no exit meets alpha (3c)"}
            for i in fb_idx:
                fb[i] = Solution(config=None, eval=None, solve_time=0.0,
                                 solver="fin", meta=m)
            return
        sigma = self.req.sigma
        for p, i in enumerate(fb_idx):
            meta = {**base_meta, "tighten_rounds": int(tb.rounds[p])}
            ceil_take = (s1 is not None and s1.found[i]
                         and (not tb.found[p]
                              or s1.energy[i] < tb.energy[p]))
            if ceil_take:
                k = int(s1.exit[i])
                cfg = self._candidate(state, 1, k, int(s1.cand[i]))[0]
                ev = ConfigEval(energy=float(s1.energy[i]),
                                energy_comp=float(s1.e_comp[i]),
                                energy_comm=float(s1.e_comm[i]),
                                latency=float(s1.latency[i]),
                                accuracy=self.profile.accuracy_of(k),
                                feasible=True, violations=[])
                meta["used_ceil_pass"] = True
            elif tb.found[p]:
                k = int(tb.exit[p])
                cfg = tb.cfgs[p]
                ev = ConfigEval(energy=float(tb.energy[p]),
                                energy_comp=float(tb.e_comp[p]),
                                energy_comm=float(tb.e_comm[p]),
                                latency=float(tb.latency[p]),
                                accuracy=self.profile.accuracy_of(k),
                                feasible=True, violations=[])
            else:
                fb[i] = Solution(config=None, eval=None, solve_time=0.0,
                                 solver="fin",
                                 meta={**meta,
                                       "reason": "no feasible path"})
                continue
            ev._energy_rate = sigma * ev.energy
            meta["delta_eff"] = float(tb.delta_eff[p])
            meta["n_feasible_states"] = 1
            fb[i] = Solution(config=cfg, eval=ev, solve_time=0.0,
                             solver="fin", meta=meta)

    def _solve_one(self, state: _CohortState, bw_row: np.ndarray
                   ) -> Tuple[Optional[Config], Optional[ConfigEval], dict]:
        """``Plan.solve``'s control flow against a shared cohort state and
        one user's true bandwidth (the exact post-pass input)."""
        meta = {"gamma": self.gamma, "quantize": self.quantize,
                "tighten_rounds": 0, "backend": self.backend,
                "warm": True, "population": True}
        if not self._proto._admissible:
            return None, None, {**meta, "reason": "no exit meets alpha (3c)"}
        network = self._user_network(bw_row)
        best = self._scan_state(state, 0, network)
        if best is None and self.max_tighten > 0:
            sol = self._fallback_solve(bw_row, state.mask)
            return sol.config, sol.eval, sol.meta
        if self.quantize != "ceil":
            alt = self._scan_state(state, 1, network, bound=best)
            if alt is not None and (best is None
                                    or alt[1].energy < best[1].energy):
                best = alt
                meta["used_ceil_pass"] = True
        if best is None:
            return None, None, {**meta, "reason": "no feasible path"}
        cfg, ev = best
        meta["delta_eff"] = self.req.delta
        meta["n_feasible_states"] = int(np.isfinite(ev.energy))
        return cfg, ev, meta

    # ----------------------------------------------------------------- solve
    def solve(self, users: Optional[np.ndarray] = None,
              build_solutions: bool = True) -> Optional[List[Solution]]:
        """Warm re-solve of the given users (default: whole cohort).

        Relaxes exactly the cohort states born since their last relax, then
        runs the exact post-pass once per unique (state, true-bandwidth)
        group — users with identical channel state share one solve.  With
        the default vectorized post-pass the unique groups of each cohort
        state are scored together as stacked arrays (``frontier.
        scan_state_users``) — per-user selections are bit-identical to the
        scalar per-group path (``vector_postpass=False``), the oracle of
        ``tests/test_frontier.py::test_vector_postpass_bitexact_vs_scalar_seeded``.
        Updates the incumbents in place; returns the per-user Solutions
        when ``build_solutions`` (pass False on million-user ticks to skip
        materializing U Python objects — the incumbent arrays carry the
        results either way).
        """
        return self.solve_finish(
            self.solve_begin(users, build_solutions=build_solutions))

    def attach_many(self, bps: Union[float, np.ndarray, None] = None,
                    users: Optional[np.ndarray] = None, *,
                    build_solutions: bool = False) -> "Population":
        """Bulk cold-start attach: land the given users' source-link
        bandwidths (scalar / (Us,) / (Us, N), like :meth:`ingest`; None
        keeps the base-topology uplink every user is born with) and build
        their signatures, cohort states, fast tables and incumbents in one
        grouped pass — signature hashing runs only over the rows whose
        encoding moved off the shared cold-start state, the newborn states
        relax in one fused launch, and the incumbents land through the
        shared fast tables with no per-user Python.  Defaults to
        ``build_solutions=False`` (the incumbent arrays carry the result;
        at 1e7 users materializing U Solution objects is the cold start).

        Returns ``self`` — ``Population(...).attach_many(rates)`` is the
        whole cold start.
        """
        users = (np.arange(self.U) if users is None
                 else np.asarray(users, dtype=np.int64))
        if bps is not None:
            self.ingest(bps, users=users, requant=False)
        self.solve(users, build_solutions=build_solutions)
        return self

    def solve_begin(self, users: Optional[np.ndarray] = None,
                    build_solutions: bool = True, *,
                    stream: bool = False) -> "_PendingSolve":
        """Phase 1 of a tick's solve: flush deferred requants, snapshot the
        (state, bandwidth) inputs, group identical rows and LAUNCH the
        newborn relaxation.  ``stream=True`` runs the relaxation on a
        background thread so the caller can overlap the NEXT tick's
        numpy-side ingest with this tick's in-flight relax (the streaming
        pipeline); the handle must be redeemed with :meth:`solve_finish`
        before any call that mutates cohort states (ingest with
        ``requant=False`` only touches the bandwidth store and is safe to
        overlap).  Results are bit-identical to :meth:`solve` — the
        post-pass reads this snapshot, not the live bandwidth."""
        t0 = time.perf_counter()
        users = (np.arange(self.U) if users is None
                 else np.asarray(users, dtype=np.int64))
        Us = len(users)
        pend = _PendingSolve(users, build_solutions, t0)
        if Us == 0:
            return pend
        self._refresh_states(users)
        self._last_relax_s = 0.0     # this tick's relax only (EWMA signal)
        sids = self._user_state[users]
        uniq_sids = np.unique(sids)
        need = [int(s) for s in uniq_sids if self._states[int(s)].dps is None]
        if need and stream:
            pend.future = self._executor().submit(self._relax_states, need)
        elif need:
            self._relax_states(need)
        self.stats.dp_cache_hits += Us - len(need)
        self.stats.solves += Us

        # unique (state, bandwidth) groups: identical inputs, one solve
        with span(self._timing, self.stats, "t_group_ms", "pop.group"):
            rows = np.empty((Us, 1 + self.N), dtype=np.float64)
            rows[:, 0] = sids
            rows[:, 1:] = self._bw_rows(users)
            v = np.ascontiguousarray(rows).view(
                np.dtype((np.void, rows.shape[1] * 8))).ravel()
            _, first, order, bounds = _group_runs(v)
        pend.sids = sids
        pend.first, pend.order, pend.bounds = first, order, bounds
        pend.bw = rows[:, 1:]            # the tick's bandwidth snapshot
        return pend

    def solve_finish(self, pend: "_PendingSolve"
                     ) -> Optional[List[Solution]]:
        """Phase 2: join the in-flight relaxation (if streaming) and run
        the exact post-pass against the snapshot taken at begin-time."""
        users = pend.users
        Us = len(users)
        if Us == 0:
            return [] if pend.build_solutions else None
        if pend.future is not None:
            pend.future.result()
            pend.future = None
        first, order, bounds = pend.first, pend.order, pend.bounds
        dt_share = (time.perf_counter() - pend.t0) / Us

        with span(self._timing, self.stats, "t_post_ms", "pop.post"):
            if self._vector_postpass and self._proto._admissible:
                self._solve_vectorized(users, pend.sids, first, order,
                                       bounds, dt_share,
                                       pend.build_solutions, pend.bw)
            else:
                for g, j in enumerate(first):
                    state = self._states[int(pend.sids[j])]
                    cfg, ev, meta = self._solve_one(state, pend.bw[j])
                    members = users[order[bounds[g]:bounds[g + 1]]]
                    self._record_group(members, cfg, ev, meta, dt_share,
                                       pend.build_solutions)
            self.stats.unique_solves += len(first)
        return self.solutions(users) if pend.build_solutions else None

    def _executor(self):
        if self._relax_executor is None:
            from concurrent.futures import ThreadPoolExecutor
            self._relax_executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="pop-relax")
        return self._relax_executor

    def _build_fast(self, state: _CohortState) -> _FastTable:
        """Materialize the state's shared first-candidate decision (see
        :class:`_FastTable`): replay the scalar post-pass's control flow
        over the FIRST candidate of each (quantizer pass, admissible exit)
        using the bandwidth-independent exact energies — one exact
        evaluation per distinct configuration, memoized cohort-wide."""
        adm = self._proto._admissible
        prof = self.profile
        keys: List[Tuple] = []
        cfgs: List[Config] = []
        pos_of: Dict[Tuple, int] = {}

        def cand0(mi: int, k: int) -> Optional[int]:
            item = self._candidate(state, mi, k, 0)
            if item is None:
                return None
            cfg = item[0]
            key = (cfg.final_exit, tuple(cfg.placement))
            p = pos_of.get(key)
            if p is None:
                p = pos_of[key] = len(cfgs)
                keys.append(key)
                cfgs.append(cfg)
            return p

        def energy(p: int) -> Tuple[float, float, float]:
            ent = self._cfg_energy.get(keys[p])
            if ent is None:
                e, ec, em, _lat, _v = eval_config_users(
                    prof, self.req, self.network0.nodes, self._proto._bw,
                    self._proto._compute, self.src, cfgs[p],
                    self._bw_rows(np.arange(1)),
                    check_aggregate_load=self.check_aggregate_load)
                ent = self._cfg_energy[keys[p]] = (e, ec, em)
            return ent

        tol = self._dist_tol
        scan: List[Tuple[int, int, int]] = []
        found = None                    # (energy, mi, k, pos, ec, em)
        for k in adm:
            dmin = _exit_dmin(state.dps[0], prof.exits[k].block)
            if found is not None and dmin > found[0] * (1.0 + tol):
                continue
            p = cand0(0, k)
            if p is None:
                continue
            scan.append((0, k, p))
            e, ec, em = energy(p)
            if found is None or e < found[0]:
                found = (e, 0, k, p, ec, em)
        used_ceil = False
        if self.quantize != "ceil":
            bound = found[0] if found is not None else None
            alt = None
            for k in adm:
                dmin = _exit_dmin(state.dps[1], prof.exits[k].block)
                be = alt[0] if alt is not None else bound
                if be is not None and dmin > be * (1.0 + tol):
                    continue
                p = cand0(1, k)
                if p is None:
                    continue
                scan.append((1, k, p))
                e, ec, em = energy(p)
                if alt is None or e < alt[0]:
                    alt = (e, 1, k, p, ec, em)
            if alt is not None and (found is None or alt[0] < found[0]):
                found = alt
                used_ceil = True
        choice = None
        if found is not None:
            e, mi, k, p, ec, em = found
            choice = (mi, k, p, e, ec, em, used_ceil)
        state.fast = _FastTable(keys, cfgs, scan, choice)
        return state.fast

    def _solve_vectorized(self, users: np.ndarray, sids: np.ndarray,
                          first: np.ndarray, order: np.ndarray,
                          bounds: np.ndarray, dt_share: float,
                          build_solutions: bool,
                          bw: Optional[np.ndarray] = None) -> None:
        """Vectorized frontier post-pass over the unique (state, bandwidth)
        representatives.

        Fast path: the distinct first-candidate configurations of every
        touched state are evaluated ONCE each for ALL representatives as
        stacked feasibility arrays; a state whose scanned first candidates
        are feasible for every representative broadcasts its cached
        ``_FastTable`` choice (exact energies are bandwidth-independent, so
        the selection is shared).  States with any first-candidate
        violation fall back to the general per-state scan
        (``_scan_state_group``); both are bit-identical to the scalar
        per-group post-pass.
        """
        with span(self._timing, None, None, "pop.post.fast"):
            # shared-table machinery: fast-table builds + the stacked
            # first-candidate feasibility evaluations
            reps = users[first]
            rep_sids = sids[first]
            uniq_s, _f, s_order, s_bounds = _group_runs(rep_sids)
            states = [self._states[int(s)] for s in uniq_s]
            tables = [st.fast if st.fast is not None
                      else self._build_fast(st) for st in states]

            # distinct scanned configs across states -> one stacked-
            # feasibility evaluation each, over exactly the representatives
            # of the states that reference the config (cohort states sharing
            # a first candidate share the evaluation; disjoint states do not
            # pay for each other's rows — unevaluated (row, rep) cells are
            # never read)
            key2row: Dict[Tuple, int] = {}
            tasks: List[Config] = []
            task_rpos: List[List[np.ndarray]] = []
            for gi, ft in enumerate(tables):
                rpos = s_order[s_bounds[gi]:s_bounds[gi + 1]]
                for key, cfg in zip(ft.keys, ft.cfgs):
                    r = key2row.get(key)
                    if r is None:
                        r = key2row[key] = len(tasks)
                        tasks.append(cfg)
                        task_rpos.append([])
                    task_rpos[r].append(rpos)
            bw_reps = self._bw_rows(reps) if bw is None else bw[first]
            nR = len(reps)
            violM = np.ones((len(tasks), nR), dtype=bool)
            latM = np.empty((len(tasks), nR))
            for r, cfg in enumerate(tasks):
                cols = (task_rpos[r][0] if len(task_rpos[r]) == 1
                        else np.unique(np.concatenate(task_rpos[r])))
                _e, _ec, _em, lat, viol = eval_config_users(
                    self.profile, self.req, self.network0.nodes,
                    self._proto._bw, self._proto._compute, self.src, cfg,
                    bw_reps[cols],
                    check_aggregate_load=self.check_aggregate_load)
                violM[r, cols] = viol
                latM[r, cols] = lat

        base_meta = {"gamma": self.gamma, "quantize": self.quantize,
                     "tighten_rounds": 0, "backend": self.backend,
                     "warm": True, "population": True}
        fast_meta = {**base_meta, "delta_eff": self.req.delta,
                     "n_feasible_states": 1}
        for gi, (state, ft) in enumerate(zip(states, tables)):
            rpos = s_order[s_bounds[gi]:s_bounds[gi + 1]]
            ids = [key2row[k] for k in ft.keys]
            scan_rows = sorted({ids[p] for _mi, _k, p in ft.scan})
            ok = (not scan_rows
                  or not violM[np.ix_(scan_rows, rpos)].any())
            if ok and ft.choice is not None:
                mi, k, p, e, ec, em, used_ceil = ft.choice
                cfg = ft.cfgs[p]
                self.stats.fastpath_states += 1
                if not build_solutions:
                    members = (users[order[bounds[rpos[0]]:
                                           bounds[rpos[0] + 1]]]
                               if len(rpos) == 1 else
                               np.concatenate(
                                   [users[order[bounds[rp]:bounds[rp + 1]]]
                                    for rp in rpos]))
                    self._record_fast(members, cfg, e)
                    continue
                row = ids[p]
                meta = ({**fast_meta, "used_ceil_pass": True} if used_ceil
                        else dict(fast_meta))
                acc = self.profile.accuracy_of(k)
                for rp in rpos:
                    members = users[order[bounds[rp]:bounds[rp + 1]]]
                    ev = ConfigEval(energy=e, energy_comp=ec,
                                    energy_comm=em,
                                    latency=float(latM[row, rp]),
                                    accuracy=acc, feasible=True,
                                    violations=[])
                    ev._energy_rate = self.req.sigma * e
                    self._record_group(members, cfg, ev, meta, dt_share,
                                       True)
                continue
            if ok and ft.choice is None:
                # no DP candidates at any admissible exit: the tighten
                # fallback (or a no-feasible-path record), per the scalar
                # control flow
                for rp in rpos:
                    members = users[order[bounds[rp]:bounds[rp + 1]]]
                    if self.max_tighten > 0:
                        sol = self._fallback_solve(bw_reps[rp], state.mask)
                        self._record_group(members, sol.config, sol.eval,
                                           sol.meta, dt_share,
                                           build_solutions)
                    else:
                        meta = {**base_meta, "reason": "no feasible path"}
                        self._record_group(members, None, None, meta,
                                           dt_share, build_solutions)
                continue
            # general path: full vectorized scan for this state's reps
            with span(self._timing, None, None, "pop.post.scan"):
                cfgs, energy, lat, e_comp, e_comm, used_ceil_a, exit_, fb = \
                    self._scan_state_group(state, bw_reps[rpos])
            for pi, rp in enumerate(rpos):
                members = users[order[bounds[rp]:bounds[rp + 1]]]
                if fb[pi] is not None:
                    sol = fb[pi]
                    self._record_group(members, sol.config, sol.eval,
                                       sol.meta, dt_share, build_solutions)
                    continue
                cfg = cfgs[pi]
                if cfg is None:
                    meta = {**base_meta, "reason": "no feasible path"}
                    self._record_group(members, None, None, meta, dt_share,
                                       build_solutions)
                    continue
                if build_solutions:
                    ev = ConfigEval(
                        energy=float(energy[pi]),
                        energy_comp=float(e_comp[pi]),
                        energy_comm=float(e_comm[pi]),
                        latency=float(lat[pi]),
                        accuracy=self.profile.accuracy_of(int(exit_[pi])),
                        feasible=True, violations=[])
                    ev._energy_rate = self.req.sigma * ev.energy
                    meta = {**base_meta, "delta_eff": self.req.delta,
                            "n_feasible_states": 1}
                    if used_ceil_a[pi]:
                        meta["used_ceil_pass"] = True
                    self._record_group(members, cfg, ev, meta, dt_share,
                                       True)
                else:
                    self._record_fast(members, cfg, float(energy[pi]))

    def _note_incumbent(self, members: np.ndarray,
                        cfg: Optional[Config]) -> None:
        """Maintain the uniform-incumbent flag across a recording: a
        whole-cohort record (re)establishes uniformity, a partial record
        keeps it only when it installs the same configuration."""
        if cfg is None:
            if len(members) == self.U or self._inc_single is not None:
                self._inc_single = None
            return
        key = (cfg.final_exit, tuple(int(n) for n in cfg.placement))
        if len(members) == self.U:
            self._inc_single = key
        elif self._inc_single is not None and self._inc_single != key:
            self._inc_single = None

    def _record_fast(self, members: np.ndarray, cfg: Config,
                     energy: float) -> None:
        """Incumbent-arrays-only recording (build_solutions=False path)."""
        self._solved[members] = True
        nb = len(cfg.placement)
        self._inc_place[members, :nb] = cfg.placement
        self._inc_place[members, nb:] = -1
        self._inc_exit[members] = cfg.final_exit
        self._inc_energy[members] = energy
        if self._any_solutions:
            self._solutions[members] = None
        self._note_incumbent(members, cfg)

    def _record_group(self, members: np.ndarray, cfg: Optional[Config],
                      ev: Optional[ConfigEval], meta: dict, dt: float,
                      build_solutions: bool) -> None:
        self._solved[members] = True
        if cfg is None:
            self._inc_place[members] = -1
            self._inc_exit[members] = -1
            self._inc_energy[members] = np.inf
        else:
            nb = len(cfg.placement)
            self._inc_place[members, :nb] = cfg.placement
            self._inc_place[members, nb:] = -1
            self._inc_exit[members] = cfg.final_exit
            self._inc_energy[members] = ev.energy
        if build_solutions:
            self._solutions[members] = Solution(
                config=cfg, eval=ev, solve_time=dt, solver="fin",
                meta=meta)
            self._any_solutions = True
        elif self._any_solutions:
            self._solutions[members] = None
        self._note_incumbent(members, cfg)

    # -------------------------------------------------------------- frontier
    def frontiers(self, users: np.ndarray, *,
                  k_per_exit: Optional[int] = 4) -> List[ParetoFrontier]:
        """Per-user k-best Pareto frontiers (core/frontier.py).

        The candidate rows are the per-cohort-state energy-ordered
        backtracks (shared across every user in a state — one backtrack
        per candidate for the whole cohort), exact-evaluated against each
        user's true bandwidth as stacked arrays and dominance-pruned per
        user (latency feasibility is per-user, so so is the frontier).
        Each frontier's ``argmin`` row is exactly the user's
        ``Population.solve`` selection — the orchestrator's frontier
        policy degrades to the argmin policy row by row.
        """
        users = np.asarray(users, dtype=np.int64)
        Us = len(users)
        out: List[Optional[ParetoFrontier]] = [None] * Us
        if Us == 0:
            return []
        if not self._proto._admissible:
            return [ParetoFrontier([], None) for _ in range(Us)]
        self._refresh_states(users)
        sids = self._user_state[users]
        need = [int(s) for s in np.unique(sids)
                if self._states[int(s)].dps is None]
        self._relax_states(need)
        self.stats.solves += Us
        uniq_s, _f, s_order, s_bounds = _group_runs(sids)
        sigma = self.req.sigma
        for gi in range(len(uniq_s)):
            pos = s_order[s_bounds[gi]:s_bounds[gi + 1]]
            state = self._states[int(uniq_s[gi])]
            bwv = self._bw_rows(users[pos])
            cfgs, energy, lat, e_comp, e_comm, _used_ceil, exit_, fb = \
                self._scan_state_group(state, bwv)
            # candidate rows in the solver's scan order (exit asc, quantizer
            # pass asc, graph-energy asc) — identical to Plan.frontier's
            items: List[Config] = []
            for k in self._proto._admissible:
                for mi in range(self.M):
                    j = 0
                    while k_per_exit is None or j < k_per_exit:
                        it = self._candidate(state, mi, k, j)
                        if it is None:
                            break
                        items.append(it[0])
                        j += 1
            evals = [eval_config_users(
                self.profile, self.req, self.network0.nodes,
                self._proto._bw, self._proto._compute, self.src, cfg, bwv,
                check_aggregate_load=self.check_aggregate_load)
                for cfg in items]
            for pi, p_ in enumerate(pos):
                if fb[pi] is not None:
                    sol = fb[pi]
                    am = (sol.config, sol.eval) if sol.feasible else None
                elif cfgs[pi] is not None:
                    ev0 = ConfigEval(
                        energy=float(energy[pi]),
                        energy_comp=float(e_comp[pi]),
                        energy_comm=float(e_comm[pi]),
                        latency=float(lat[pi]),
                        accuracy=self.profile.accuracy_of(int(exit_[pi])),
                        feasible=True, violations=[])
                    ev0._energy_rate = sigma * ev0.energy
                    am = (cfgs[pi], ev0)
                else:
                    am = None
                pairs = []
                for cfg, (e, ec, em, latr, violr) in zip(items, evals):
                    if violr[pi]:
                        continue
                    evr = ConfigEval(
                        energy=e, energy_comp=ec, energy_comm=em,
                        latency=float(latr[pi]),
                        accuracy=self.profile.accuracy_of(cfg.final_exit),
                        feasible=True, violations=[])
                    evr._energy_rate = sigma * e
                    pairs.append((cfg, evr))
                out[p_] = frontier_from_rows(pairs, am)
        return out

    def frontier(self, u: int, *,
                 k_per_exit: Optional[int] = 4) -> ParetoFrontier:
        """One user's Pareto frontier (see :meth:`frontiers`)."""
        return self.frontiers(np.array([int(u)]), k_per_exit=k_per_exit)[0]

    def set_incumbents(self, users: np.ndarray,
                       cfgs: Sequence[Optional[Config]],
                       energies: Sequence[float]) -> None:
        """Install externally chosen configurations as incumbents.

        The orchestrator's frontier policy may keep a slightly-costlier
        frontier row (or the previous incumbent) when the energy delta
        does not pay for the migration; this records those choices so the
        next tick's hysteresis gate and migration accounting run against
        what is actually deployed."""
        users = np.asarray(users, dtype=np.int64)
        self._inc_single = None      # externally mixed incumbents
        for u, cfg, e in zip(users, cfgs, energies):
            self._solved[u] = True
            if cfg is None:
                self._inc_place[u] = -1
                self._inc_exit[u] = -1
                self._inc_energy[u] = np.inf
            else:
                nb = len(cfg.placement)
                self._inc_place[u, :nb] = cfg.placement
                self._inc_place[u, nb:] = -1
                self._inc_exit[u] = cfg.final_exit
                self._inc_energy[u] = float(e)
            self._solutions[int(u)] = None

    # ------------------------------------------------ incumbent re-evaluation
    def evaluate_incumbents(self, users: Optional[np.ndarray] = None
                            ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Vectorized ``Plan.evaluate(incumbent)`` across users.

        Returns (no_incumbent, feasible, energy) — ``feasible``/``energy``
        are meaningful where ``~no_incumbent``.  Users are grouped by
        incumbent configuration; each group evaluates as one vectorized
        pass whose per-user latency accumulation replays ``evaluate_config``
        term by term (bit-identical doubles), with the failure-bitmap
        dead-node check of ``Plan.evaluate`` applied first.

        ``users=None`` evaluates the whole cohort — the dense hysteresis
        gate's hot path.  Under the lazy bandwidth store (an array tick,
        whose ingest defers the (U, N) product) it judges each incumbent
        against one exact channel threshold per (configuration, factor
        values) instead of re-evaluating it (see :meth:`_gate_plan`);
        rows that path does not cover, and every other call, go through
        the exact evaluator (:meth:`_incumbents_exact`).  The span ``pop.gate.threshold``
        carries the rows judged by threshold or constant as its ``users``
        argument, ``pop.gate.exact`` the rows re-evaluated.  Results are
        bit-identical either way.
        """
        if users is None and self._bw_lazy is not None:
            plan = self._gate_plan()
            if plan is not None:
                return self._gate_judge(*plan)
        n = self.U if users is None else len(users)
        with span(self._timing, None, None, "pop.gate.exact", users=n):
            return self._incumbents_exact(users)

    def _gate_cols(self, place: Sequence[int]) -> Tuple[int, ...]:
        """The source-link columns ``eval_config_users`` reads for this
        placement: the input hop off the source and every cut that enters
        or leaves it."""
        src = self.src
        cols = set()
        if place[0] != src:
            cols.add(place[0])
        for a, b in zip(place, place[1:]):
            if a != b and src in (a, b):
                cols.add(b if a == src else a)
        return tuple(sorted(cols))

    def _gate_entry(self, k: int, place: List[int], cols: Tuple[int, ...],
                    row: np.ndarray) -> Optional[Tuple[float, float]]:
        """(energy, threshold) of incumbent (k, place) for users whose
        factor row agrees with ``row`` on ``cols``, memoized; None when
        those factors are not finite and non-negative.

        Energy has no bandwidth term.  Feasibility reads the bandwidth
        ``scale * factor`` only through correctly rounded multiplies,
        divides, adds and compares, each monotone, so with finite
        non-negative factors it is monotone in the (finite, non-negative,
        validated) scale: feasible exactly when ``scale >= t``.  ``t`` is
        the least float64 at which ``eval_config_users`` itself, on a
        one-row lazy store, finds the incumbent feasible — bisected over
        the bit patterns, which order non-negative doubles — or inf when
        none is.  A placement that reads no source link is feasible for
        every scale or none: ``t`` is 0 or inf.
        """
        vals = row[list(cols)]
        if not (np.isfinite(vals).all() and (vals >= 0).all()):
            return None
        key = (k, tuple(place), vals.tobytes())
        ent = self._gate_cache.get(key)
        if ent is not None:
            return ent
        cfg = Config(placement=list(place), final_exit=k)
        one = row[None].copy()

        def feasible(bits: int) -> Tuple[float, bool]:
            s = np.array([bits], dtype=np.int64).view(np.float64)
            e, _lat, viol = self._eval_config_users(
                cfg, _LazyBwCols(s, one, self.src))
            return e, not bool(viol[0])

        e, ok = feasible(0)
        if ok:
            t = 0.0
        elif not cols or not feasible(_F64_MAX_BITS)[1]:
            t = np.inf
        else:
            lo, hi = 0, _F64_MAX_BITS       # infeasible, feasible
            while hi - lo > 1:
                mid = (lo + hi) // 2
                if feasible(mid)[1]:
                    hi = mid
                else:
                    lo = mid
            t = float(np.array([hi], dtype=np.int64).view(np.float64)[0])
        ent = self._gate_cache[key] = (e, t)
        return ent

    def _gate_plan(self):
        """Group the whole cohort's incumbents for the threshold gate,
        without sorting, or None to leave the cohort to the exact path.

        The head group is the uniform incumbent, or else the modal one of
        a 32-row sample, with the factor values its placement reads: rows
        that differ from it in a positional compare (exit, placement
        columns, no incumbent, those factor columns) are peeled again on
        their gather, one (configuration, factor values) group at a time.
        Past ``_GATE_CONFIGS`` configurations, or ``_GATE_FACTOR_ROWS``
        factor rows of one configuration, or factor values that are not
        finite and non-negative, the remaining rows of the peel go to the
        exact evaluator; a cohort with no incumbent at all, or whose head
        factors are not finite and non-negative, goes there whole.
        Returns (no_inc, head, groups, no_inc_rows, exact) for
        :meth:`_gate_judge`."""
        fac = self._bw_lazy[1]
        U, L = self.U, self.L
        exit_all, place_all = self._inc_exit, self._inc_place
        single = self._inc_single
        neq = None
        if single is not None:
            no_inc = np.zeros(U, dtype=bool)
            k, place, j = single[0], list(single[1]), 0
        else:
            no_inc = ~self._solved | (exit_all < 0)
            j = _modal_row(exit_all, place_all, no_inc)
            if no_inc[j]:
                if no_inc.all():
                    return None
                j = int(np.argmin(no_inc))
            k = int(exit_all[j])
            pp = place_all[j]
            neq = exit_all != k
            for i in range(L):
                neq |= place_all[:, i] != pp[i]
            neq |= no_inc
            place = [int(n) for n in pp[:self.profile.exits[k].block + 1]]
        cols = self._gate_cols(place)
        ent = self._gate_entry(k, place, cols, fac[j])
        if ent is None:
            return None
        for n in cols:
            d = fac[:, n] != fac[j, n]
            if neq is None:
                neq = d
            else:
                neq |= d
        head = (place, cols) + ent
        if neq is None:
            return no_inc, head, [], np.empty(0, dtype=np.int64), []
        rest = np.flatnonzero(neq)
        ni = no_inc[rest]
        left = rest[~ni]
        # the peel runs on one gather of the remainder's incumbents
        ex = np.take(exit_all, left)
        pl = np.take(place_all, left, axis=0)
        groups, exact = [], []
        n_cfg = 1
        while len(left):
            if n_cfg >= _GATE_CONFIGS:
                exact.append(left)
                break
            n_cfg += 1
            k = int(ex[0])
            pp = pl[0].copy()
            eq = ex == k
            for i in range(L):
                eq &= pl[:, i] == pp[i]
            sub = left[eq]
            keep = ~eq
            left = left[keep]
            ex = ex[keep]
            pl = np.compress(keep, pl, axis=0)
            place = [int(n) for n in pp[:self.profile.exits[k].block + 1]]
            cols = self._gate_cols(place)
            for _ in range(_GATE_FACTOR_ROWS):
                ent = self._gate_entry(k, place, cols, fac[sub[0]])
                if ent is None:
                    break
                same = np.ones(len(sub), dtype=bool)
                for n in cols:
                    same &= fac[sub, n] == fac[sub[0], n]
                groups.append((sub[same], place, cols) + ent)
                sub = sub[~same]
                if not len(sub):
                    break
            if len(sub):
                exact.append(sub)
        return no_inc, head, groups, rest[ni], exact

    def _gate_judge(self, no_inc: np.ndarray, head, groups, no_inc_rows,
                    exact: List[np.ndarray]
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Feasibility and energy of a :meth:`_gate_plan`: the head group
        over the whole store (one compare against its threshold, none for
        a placement that reads no source link), each peeled group on its
        rows, then the dead-node check exactly as the exact path applies
        it and the exact evaluator for the rows the plan left to it."""
        U = self.U
        sc = self._bw_lazy[0]
        any_mask = self._mask_count > 0
        n_exact = sum(len(x) for x in exact)
        with span(self._timing, None, None, "pop.gate.threshold",
                  users=U - n_exact):
            place, cols, e, t = head
            feas = sc >= t if cols else np.full(U, t == 0.0)
            energy = np.full(U, e)
            if any_mask:
                dead = self._masked[:, place].any(axis=1)
                feas[dead] = False
                energy[dead] = np.inf
            for rows, place, cols, e, t in groups:
                f = sc[rows] >= t if cols else np.full(len(rows), t == 0.0)
                en = np.full(len(rows), e)
                if any_mask:
                    dead = self._masked[rows][:, place].any(axis=1)
                    f[dead] = False
                    en[dead] = np.inf
                feas[rows] = f
                energy[rows] = en
            feas[no_inc_rows] = False
            energy[no_inc_rows] = np.inf
        for rows in exact:
            with span(self._timing, None, None, "pop.gate.exact",
                      users=len(rows)):
                _, f, en = self._incumbents_exact(rows)
            feas[rows] = f
            energy[rows] = en
        return no_inc, feas, energy

    def _incumbents_exact(self, users: Optional[np.ndarray]
                          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """:meth:`evaluate_incumbents` by re-evaluating every incumbent.

        ``users=None`` reads the incumbent columns as views, the grouping
        key is radix-sorted int64 (one all-equal compare in the steady
        single-config state) and a single-group cohort reads the bandwidth
        store with zero per-user gathers.  When the uniform-incumbent flag
        is set (every user solved with one configuration) even the
        grouping-key build is skipped: one stacked evaluation against the
        bandwidth store, results bit-identical to the single-group general
        path.
        """
        if users is None and self._inc_single is not None:
            k, place_t = self._inc_single
            place = list(place_t)
            cfg = Config(placement=place, final_exit=k)
            e_sc, _lat, viol = self._eval_config_users(
                cfg, self._bw_cols())
            feas = ~viol
            energy = np.full(self.U, e_sc)
            if self._mask_count > 0:
                dead = self._masked[:, place].any(axis=1)
                feas[dead] = False
                energy[dead] = np.inf
            return np.zeros(self.U, dtype=bool), feas, energy
        whole = users is None
        if whole:
            exit_all = self._inc_exit
            place_all = self._inc_place
            solved = self._solved
        else:
            users = np.asarray(users, dtype=np.int64)
            exit_all = self._inc_exit[users]
            place_all = self._inc_place[users]
            solved = self._solved[users]
        Us = len(exit_all)
        feas = np.zeros(Us, dtype=bool)
        energy = np.full(Us, np.inf)
        no_inc = ~solved | (exit_all < 0)
        any_no = bool(no_inc.any())
        if any_no and no_inc.all():
            return no_inc, feas, energy
        # pivot-majority fast path (dense gate at scale): sample the modal
        # incumbent, compare positionally (L+1 cheap int passes — no int64
        # key build, no radix sort), evaluate the pivot config ONCE over
        # the full bandwidth store and re-run only the disagreeing rows
        # through the grouped path below via a subset recursion.  Values
        # are elementwise identical to the grouped evaluation: per-user
        # terms never depend on the grouping, only on the (config, row).
        if whole and Us >= 4096:
            pj = _modal_row(exit_all, place_all, no_inc)
            pk = int(exit_all[pj])
            if pk >= 0 and solved[pj]:
                pp = place_all[pj]
                neq = exit_all != pk
                for i in range(self.L):
                    neq |= place_all[:, i] != pp[i]
                neq |= no_inc
                idx = np.nonzero(neq)[0]
                if len(idx) * 8 <= Us:
                    nb = self.profile.exits[pk].block + 1
                    place = [int(n) for n in pp[:nb]]
                    cfg = Config(placement=place, final_exit=pk)
                    e_sc, _lat, viol = self._eval_config_users(
                        cfg, self._bw_cols())
                    feas = ~viol
                    energy = np.full(Us, e_sc)
                    if self._mask_count > 0:
                        dead = self._masked[:, place].any(axis=1)
                        feas[dead] = False
                        energy[dead] = np.inf
                    if len(idx):
                        _, sub_f, sub_e = self._incumbents_exact(idx)
                        feas[idx] = sub_f
                        energy[idx] = sub_e
                    return no_inc, feas, energy
        # group by incumbent configuration; an injective radix-sortable
        # int64 key (digits = shifted exit/placement columns, base N+2
        # covers the -1 padding) replaces the void-row lexsort whenever the
        # profile is narrow enough to fit — the wide-profile fallback keeps
        # the row view.  No-incumbent users collapse into one skipped
        # sentinel group instead of being filtered up front (saves the
        # index/gather round-trip on the common all-solved tick).
        if (self.L + 1) * int(self.N + 2).bit_length() < 63:
            key = exit_all.astype(np.int64) + 1
            for i in range(self.L):
                key *= self.N + 2
                key += place_all[:, i] + 1
            if any_no:
                key[no_inc] = -1
            _, first, order, bounds = _group_runs(key)
        else:
            rows = np.empty((Us, 1 + self.L), dtype=np.int32)
            rows[:, 0] = np.where(no_inc, -2, exit_all) if any_no \
                else exit_all
            rows[:, 1:] = place_all
            v = np.ascontiguousarray(rows).view(
                np.dtype((np.void, rows.shape[1] * 4))).ravel()
            _, first, order, bounds = _group_runs(v)
        any_mask = self._mask_count > 0
        single = len(first) == 1
        for g, j in enumerate(first):
            j = int(j)
            k = int(exit_all[j])
            if k < 0 or not solved[j]:
                continue                 # the no-incumbent sentinel group
            nb = self.profile.exits[k].block + 1
            place = [int(n) for n in place_all[j, :nb]]
            members = None if single else order[bounds[g]:bounds[g + 1]]
            cfg = Config(placement=place, final_exit=k)
            if members is None:
                gl = users if not whole else None
                bwv = (self._bw_cols() if gl is None
                       else self._bw_rows(gl))
            else:
                gl = users[members] if not whole else members
                bwv = self._bw_rows(gl)
            e_sc, lat, viol = self._eval_config_users(cfg, bwv)
            f = ~viol
            en = np.full(Us if members is None else len(members), e_sc)
            if any_mask:
                rows_m = (self._masked if gl is None
                          else self._masked[gl])
                dead = rows_m[:, place].any(axis=1)
                f[dead] = False
                en[dead] = np.inf
            if members is None:
                feas = f
                energy = en
            else:
                feas[members] = f
                energy[members] = en
        return no_inc, feas, energy

    # ---------------------------------------------------------- checkpointing
    def state_dict(self) -> Dict[str, np.ndarray]:
        """Snapshot the full SoA + cohort-state-table state as a flat dict
        of arrays (the checkpoint leaf set — ``runtime/checkpoint.py``
        saves it verbatim).

        DP grids, candidate caches, fast tables and the exact-energy memo
        are NOT saved: they are deterministic functions of the saved
        (pack, mask) signatures and the proto tensors, so
        :meth:`restore_state` rebuilds them bit-exactly on demand.
        ``state_relaxed`` records WHICH states held relaxed grids so the
        restore re-relaxes exactly those — off-tick probes (contingency
        ``coverage``) and the next tick's ``dp_relaxes`` delta then behave
        identically to the uninterrupted run.
        """
        S = len(self._states)
        M, K2, N = self.M, 2 * self.L - 1, self.N
        pinned = np.zeros(S, dtype=bool)
        if self._pinned:
            pinned[list(self._pinned)] = True
        d = {
            "bw_vec": self._bw_dense().copy(),
            # a user's pack equals their state's stq (the table keys BY
            # pack), so the per-user qpack leaf is a signature-table
            # gather — byte-identical to the historical per-user encode,
            # keeping old and new checkpoints interchangeable
            "qpack": self._stq_enc[self._user_state].reshape(
                self.U, M, K2, N),
            "masked": self._masked.copy(),
            "stale": self._stale.copy(),
            "user_state": self._user_state.copy(),
            "solved": self._solved.copy(),
            "inc_place": self._inc_place.copy(),
            "inc_exit": self._inc_exit.copy(),
            "inc_energy": self._inc_energy.copy(),
            "user_ids": self.user_ids.copy(),
            "quarantined": self._quarantined.copy(),
            "stuck_count": self._stuck_count.copy(),
            "state_stq": (_enc_int16(np.stack([s.stq for s in self._states]))
                          if S else np.zeros((0, M, K2, N), dtype=np.int16)),
            "state_mask": (np.stack([s.mask for s in self._states])
                           if S else np.zeros((0, N), dtype=bool)),
            "state_relaxed": np.array([s.dps is not None
                                       for s in self._states], dtype=bool),
            "state_parent": np.array([s.parent for s in self._states],
                                     dtype=np.int64),
            "state_pinned": pinned,
        }
        if self._last_raw is not None:
            d["last_raw"] = self._last_raw.copy()
        return d

    def restore_state(self, d: Dict[str, np.ndarray]) -> "Population":
        """Restore a :meth:`state_dict` snapshot in place.

        The cohort must match the snapshot (same users and solver
        parameterization), and any structural deltas the snapshot was
        taken under (compute-slice / backhaul repricings — e.g. the
        congestion controller's composed price factors) must be re-applied
        BEFORE restoring, so the proto tensors the rebuilt states scatter
        into equal the snapshot-time ones.  The cohort-state table is
        rebuilt in saved order (state ids are preserved verbatim, so
        ``user_state`` and the pinned set stay valid) and the states that
        held relaxed DP grids are re-relaxed in one launch — bit-exact,
        because the grids are deterministic in (pack, mask, proto
        tensors).
        """
        ids = np.asarray(d["user_ids"], dtype=np.int64)
        if ids.shape != self.user_ids.shape or \
                not np.array_equal(ids, self.user_ids):
            raise ValueError("state_dict user_ids do not match this cohort "
                             f"({ids.shape} vs {self.user_ids.shape})")
        U, N = self.U, self.N
        bw = np.asarray(d["bw_vec"], dtype=np.float64)
        if bw.shape != (U, N):
            raise ValueError(f"bw_vec shape {bw.shape} != ({U}, {N})")
        qp_shape = (U, self.M, 2 * self.L - 1, self.N)
        qp = np.asarray(d["qpack"])
        if qp.shape != qp_shape:
            raise ValueError(f"qpack shape {qp.shape} != {qp_shape}")
        # (the values are redundant — user packs are rebuilt from the
        # saved state table + user_state below; the leaf stays in the
        # checkpoint format for compatibility and shape validation)
        self._bw_vec[:] = bw
        self._bw_lazy = None
        self._masked[:] = d["masked"]
        self._mask_count = int(np.count_nonzero(self._masked))
        self._stale[:] = d["stale"]
        self._solved[:] = d["solved"]
        self._inc_place[:] = d["inc_place"]
        self._inc_exit[:] = d["inc_exit"]
        self._inc_energy[:] = d["inc_energy"]
        self._quarantined[:] = d.get("quarantined", False)
        self._stuck_count[:] = d.get("stuck_count", 0)
        if self._last_raw is not None:
            self._last_raw[:] = d.get("last_raw", np.nan)
        self._solutions = np.full(U, None, dtype=object)
        self._any_solutions = False
        # rebuild the cohort-state table in saved order: every state keys
        # through the same scalar signature encoding, so probes against
        # the restored table return the snapshot-time ids
        self._states = []
        self._state_ids = {}
        self._pinned = set()
        self._cfg_energy = {}
        self._fallback_plan = None
        self._tighten_cache = {}
        self._tighten_base = {}
        self._stq_enc = np.empty((0, self._enc_w), dtype=np.int16)
        stq_all = _dec_int16(np.asarray(d["state_stq"]))
        mask_all = np.asarray(d["state_mask"], dtype=bool)
        parent = np.asarray(d["state_parent"], dtype=np.int64)
        for i in range(len(stq_all)):
            key = self._state_key(stq_all[i], mask_all[i])
            sid = self._add_state(key, stq_all[i].copy(),
                                  mask_all[i].copy(),
                                  parent=int(parent[i]))
            if sid != i:
                raise ValueError(f"duplicate cohort-state signature at "
                                 f"snapshot index {i} (got id {sid})")
        us = np.asarray(d["user_state"], dtype=np.int64)
        if len(us) != U or (len(self._states)
                            and us.max(initial=-1) >= len(self._states)):
            raise ValueError("user_state does not index the saved table")
        self._user_state[:] = us
        self._pinned = {int(s) for s in np.nonzero(
            np.asarray(d["state_pinned"], dtype=bool))[0]}
        relaxed = np.nonzero(np.asarray(d["state_relaxed"],
                                        dtype=bool))[0]
        if len(relaxed):
            self._relax_states([int(s) for s in relaxed], prebuilt=True)
        self._inc_single = self._recompute_inc_single()
        return self

    def _recompute_inc_single(self) -> Optional[Tuple]:
        """One O(U) scan re-deriving the uniform-incumbent flag (used on
        checkpoint restore, where the recording history is gone): set iff
        every user is solved with one identical (exit, placement)."""
        if not bool(self._solved.all()):
            return None
        k = int(self._inc_exit[0])
        if k < 0 or bool((self._inc_exit != k).any()):
            return None
        row0 = self._inc_place[0]
        if bool((self._inc_place != row0[None]).any()):
            return None
        nb = self.profile.exits[k].block + 1
        return (k, tuple(int(n) for n in row0[:nb]))

    def _eval_config_users(self, config: Config, bwv: np.ndarray
                           ) -> Tuple[float, np.ndarray, np.ndarray]:
        """Vectorized ``problem.evaluate_config``: one configuration, many
        users differing only in their source-link bandwidth vector.

        Returns (energy, latency (Us,), violated (Us,)) — the shared
        evaluator now lives in ``core/frontier.py`` (it also powers the
        vectorized frontier post-pass); every per-user result is
        bit-identical to ``evaluate_config`` on that user's mutated
        network.
        """
        e, _ec, _em, lat, viol = eval_config_users(
            self.profile, self.req, self.network0.nodes, self._proto._bw,
            self._proto._compute, self.src, config, bwv,
            check_aggregate_load=self.check_aggregate_load)
        return e, lat, viol
