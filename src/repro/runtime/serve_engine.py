"""Split-serving engine: exit-aware continuous batching over a FIN placement.

This is the TPU-native adaptation of the paper's execution model
(DESIGN.md Sec. 3): SPMD cannot stop computing individual batch lanes, so
per-sample early exits are realized as *scheduling*:

  * a request is admitted into a free slot with one prefill of its
    prompt (``transformer.prefill_into_slot``, prompt lengths padded to
    power-of-two buckets), which also gives its first token; every slot
    then decodes at its own position;
  * every decode step runs the full stack once for the active batch;
  * the fused gate (kernels/ee_gate) scores each exit's logits; a sequence
    whose confidence clears its threshold takes THAT exit's token — deeper
    blocks' output for it is discarded.  One device program judges every
    deployed head (``gate_heads``), and the host reads its tokens and exit
    indices at once;
  * finished sequences free their slot immediately and the next queued
    request takes it (continuous batching) — phi-fraction compute saving
    becomes throughput;
  * per-token *tier accounting*: with a FIN placement (blocks -> tiers),
    the engine charges each token only the blocks up to its exit, yielding
    the measured energy the paper's objective (3a) predicts.  The
    profile describes the served model (one exit per model exit,
    ``core.profile_from_arch``), and a token exits no deeper than the
    placement's final exit;
  * fault tolerance: the placement lives in a persistent ``core.Plan`` —
    ``fail_node`` masks the dead node and issues a *warm* re-solve (no
    graph reconstruction; bit-exact vs a cold solve on the reduced
    network), ``recover_node`` unmasks and re-solves; node indices stay
    stable across failures (Sec. V elasticity).  Every failover re-split
    also exposes the scenario's Pareto frontier (``engine.frontier``,
    core/frontier.py), and with ``migration_weight > 0`` the re-split is
    frontier-aware: the engine deploys the frontier row minimizing
    ``energy + migration_weight * migration_bits`` — on recovery that can
    keep the current placement instead of migrating everything back for a
    marginal energy win;
  * O(1) failover (``contingency=True``): a ``core.contingency``
    library precomputes the likely failure masks' solutions/frontiers/
    migration prices around the current state, so a covered ``fail_node``
    / ``recover_node`` installs the precomputed entry — ZERO DP
    relaxations on the critical path, bit-exact vs the warm re-solve —
    and refills the library off the critical path (the next ``step()``);
    uncovered or environment-stale masks fall back to the warm re-solve
    and record the miss;
  * graceful degradation: when no feasible placement survives a failure,
    ``on_infeasible`` picks the policy — ``"raise"`` a typed
    ``NoFeasiblePlacement`` (carries the masked set + last feasible
    frontier), ``"pause"`` park in-flight requests until a recovery, or
    ``"degrade"`` deploy the cheapest row of the last feasible frontier
    avoiding the dead nodes (falls back to pausing when every row routes
    through one);
  * churn-driven serving: ``on_tick`` applies a ``scenarios.churn_trace``
    tick — uplink fades re-split mid-serving behind a hysteresis band,
    failures/recoveries hit the contingency library — and
    ``serve_with_churn`` interleaves ticks with decode steps.
"""
from __future__ import annotations

import functools
import itertools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import (AppRequirements, Config, DNNProfile, Network,
                        ParetoFrontier, Plan, evaluate_config,
                        migration_delta)
from repro.core.contingency import (ContingencyEntry, ContingencyLibrary,
                                    NoFeasiblePlacement)
from repro.core.frontier import frontier_pick
from repro.core.scenarios import MOBILE_UPLINK_BPS, ChurnEvent
from repro.core.spans import span
from repro.kernels.decode_attn.decode_attn import block_len, blocks_read
from repro.kernels.ee_gate.ee_gate import ee_gate_pallas
from repro.models import attention as ATT
from repro.models import transformer as T


@functools.partial(jax.jit, static_argnames=("interpret",))
def gate_heads(heads: Tuple[jnp.ndarray, ...], thresholds: jnp.ndarray, *,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """First-exit-wins over the deployed heads, in one device program.

    ``heads``: the deployed heads' logits, shallowest first, each [rows,
    V]; ``thresholds``: float32, one per early exit (at least ``len(heads)
    - 1``).  The ``ee_gate`` kernel judges each head; a row takes the first
    exit whose confidence clears its threshold, compared in float32, and
    the last head takes every row that clears none.  The number of heads
    and the rows are the program's shapes, so each pair compiles once.
    Returns int32 [2, rows]: the token and the exit index of each row."""
    gated = [ee_gate_pallas(x, interpret=interpret) for x in heads]
    k = len(heads) - 1
    token = gated[k][1]
    exit_idx = jnp.full(token.shape, k, jnp.int32)
    for j in reversed(range(k)):
        conf, arg = gated[j]
        hit = conf >= thresholds[j]
        token = jnp.where(hit, arg, token)
        exit_idx = jnp.where(hit, j, exit_idx)
    return jnp.stack([token, exit_idx])


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    tokens: List[int] = field(default_factory=list)
    exits_taken: List[int] = field(default_factory=list)  # exit idx per token
    done: bool = False


@dataclass
class EngineStats:
    steps: int = 0
    tokens_out: int = 0
    exit_histogram: Dict[int, int] = field(default_factory=dict)
    blocks_executed: int = 0          # tier-charged block executions
    blocks_saved: int = 0             # skipped by early exits
    energy_j: float = 0.0             # placement-model energy (Eq. 2 units)
    replacements: int = 0             # FIN re-solves after failures/recovery
    blocks_migrated: int = 0          # blocks re-hosted by re-placements
    migration_bits: float = 0.0       # state bits moved by re-placements
    contingency_hits: int = 0         # failovers served from the library
    contingency_misses: int = 0       # failovers that warm re-solved
    paused_events: int = 0            # infeasible -> serving parked
    degrades: int = 0                 # infeasible -> degraded frontier row
    admissions: int = 0               # requests prefilled into a slot
    prompt_tokens_prefilled: int = 0  # their prompt tokens (unpadded)
    live_depth_sum: int = 0           # KV entries read: over decode steps,
    #                                   each live slot's position + 1
    kv_blocks_read: int = 0           # K/V blocks the decode_attn kernel
    #                                   reads per attention layer: over
    #                                   decode steps, each live slot's
    #                                   min(pos, T-1) // bt + 1
    gate_reads: int = 0               # the gate's device-to-host reads,
    #                                   one a decode step and an admission
    # program spans (``timing=True``), ms
    t_admit_ms: float = 0.0           # serve.admit: prefills + 1st tokens
    t_decode_ms: float = 0.0          # serve.decode: the decode program
    #                                   (the step's kv_blocks_read rides
    #                                   on it as its ``blocks`` argument)
    t_gate_ms: float = 0.0            # serve.gate: the gate program on
    #                                   every deployed head + its one read
    t_account_ms: float = 0.0         # serve.account: the slot loop

    @property
    def measured_phi(self) -> Dict[int, float]:
        tot = max(1, sum(self.exit_histogram.values()))
        return {k: v / tot for k, v in sorted(self.exit_histogram.items())}


class SplitServeEngine:
    """Decode engine with exit-aware continuous batching.

    A request is admitted with one prefill of its prompt into a free slot,
    which also yields its first token; generation then proceeds with gated
    exits, each slot at its own position.  ``profile``/``network``/``req``
    wire the engine to the paper's placement problem for energy
    accounting; they are optional — without them the engine is a plain
    continuous-batching server.  ``thresholds``: one per early exit.
    ``timing=True`` turns on the program
    spans ``serve.*`` (``core/spans.py``) and their ``EngineStats`` fields.
    """

    def __init__(self, cfg: ArchConfig, params, *, batch_size: int,
                 cache_len: int, thresholds: Optional[Sequence[float]] = None,
                 network: Optional[Network] = None,
                 profile: Optional[DNNProfile] = None,
                 req: Optional[AppRequirements] = None,
                 gamma: int = 10, seed: int = 0,
                 migration_weight: float = 0.0, frontier_k: int = 4,
                 on_infeasible: str = "raise", contingency: bool = True,
                 hysteresis: float = 0.05, timing: bool = False):
        assert cfg.has_decoder
        self.cfg = cfg
        self.params = params
        self.B = batch_size
        self.cache_len = cache_len
        self.n_exits = len(cfg.exit_layer_list) + 1
        self.thresholds = ([0.9] * (self.n_exits - 1) if thresholds is None
                           else list(thresholds))
        if len(self.thresholds) != self.n_exits - 1:
            raise ValueError(f"{len(self.thresholds)} thresholds for "
                             f"{self.n_exits - 1} early exits")
        #: the thresholds on the device, for the gate program
        self._thresholds_dev = jnp.asarray(self.thresholds, jnp.float32)
        if profile is not None and profile.n_exits != self.n_exits:
            raise ValueError(
                f"profile {profile.name!r} has {profile.n_exits} exits, the "
                f"model {self.n_exits}: place the served model's own "
                f"profile (core.profile_from_arch)")
        self.timing = bool(timing)
        self.caches = T.init_caches(cfg, batch_size, cache_len)

        def decode_step(p, c, t, pos):
            return T.decode_step(p, cfg, t, c, pos)

        def prefill_into_slot(p, c, slot, t, n):
            return T.prefill_into_slot(p, cfg, c, slot, t, n)
        #: the jitted step ``(params, caches, tokens [B, 1], pos [B]) ->
        #: (logits, caches, exits)``; it donates the caches, so the step
        #: updates them in place instead of keeping a second copy live.
        #: Its device program is named ``jit_decode_step``.
        self.decode_step = jax.jit(decode_step, donate_argnums=(1,))
        #: the jitted admission ``(params, caches, slot, tokens [S],
        #: length) -> (logits, caches, exits)``, caches donated; program
        #: ``jit_prefill_into_slot``
        self.prefill_into_slot = jax.jit(prefill_into_slot,
                                         donate_argnums=(1,))
        #: prompt lengths are padded to these: powers of two from 64, and
        #: the cache's length; an SSM state needs the exact length (None)
        self.buckets: Optional[List[int]] = None
        T_len = ATT.cache_spec(cfg, batch_size, cache_len).max_len
        if all(s.kind == "attn" for s in cfg.pattern):
            self.buckets = [b for b in (64 << i for i in range(16))
                            if b < T_len] + [T_len]
        #: the decode_attn kernel's (cache length, positions per block),
        #: for the ``kv_blocks_read`` count; None where decode attention
        #: takes the jnp path (no attention layer, or an int8 cache)
        attn = next((f"l{i}" for i, s in enumerate(cfg.pattern)
                     if s.kind == "attn"), None)
        self._kv_blocks = ((T_len, block_len(T_len)) if attn and
                           ATT.reads_in_place(self.caches[attn]) else None)
        self._ring = cfg.sliding_window > 0
        self.slots: List[Optional[Request]] = [None] * batch_size
        self.queue: List[Request] = []
        self.stats = EngineStats()
        self._rid = itertools.count(10_000)
        #: the position each slot decodes next (its context length)
        self._slot_pos = np.zeros(batch_size, np.int32)
        #: the last step's logits on the device, ``{"final": [B, V_pad],
        #: "exit_<l>": ...}``; ``last_decoded[i]`` is ``(request, position)``
        #: of row i in them (None: an empty slot); ``last_admissions`` the
        #: step's admissions, ``(request, position, logits)`` each
        self.last_logits: Optional[Dict[str, jnp.ndarray]] = None
        self.last_decoded: List[Optional[Tuple[Request, int]]] = \
            [None] * batch_size
        self.last_admissions: List[Tuple[Request, int,
                                         Dict[str, jnp.ndarray]]] = []
        # placement integration: a persistent Plan owns the built pipeline
        # state, so failure/recovery re-solves are warm deltas
        self.profile = profile
        self.app_req = req
        self.gamma = gamma
        self.plan: Optional[Plan] = None
        self.placement: Optional[Config] = None
        self.network = network
        if migration_weight < 0:
            raise ValueError(f"migration_weight must be >= 0, got "
                             f"{migration_weight}")
        if frontier_k < 1:
            raise ValueError(f"frontier_k must be >= 1, got {frontier_k}")
        self.migration_weight = float(migration_weight)
        self.frontier_k = int(frontier_k)
        if on_infeasible not in ("raise", "pause", "degrade"):
            raise ValueError(f"on_infeasible must be 'raise', 'pause' or "
                             f"'degrade', got {on_infeasible!r}")
        if hysteresis < 0:
            raise ValueError(f"hysteresis must be >= 0, got {hysteresis}")
        self.on_infeasible = on_infeasible
        self.hysteresis = float(hysteresis)
        #: graceful-degradation state: ``paused`` parks serving (step() is
        #: a no-op) until a topology/channel change restores feasibility;
        #: ``degraded`` flags a placement adopted off the last feasible
        #: frontier instead of a fresh solve
        self.paused = False
        self.degraded = False
        self._ref_energy = np.inf          # hysteresis reference (on_tick)
        self._last_feasible_frontier: Optional[ParetoFrontier] = None
        #: the Pareto frontier of the last (re-)placement — refreshed on
        #: every failover / recovery re-split (core/frontier.py)
        self.frontier: Optional[ParetoFrontier] = None
        #: precomputed-failover library (core/contingency.py), refilled off
        #: the failover critical path; None when placement is not wired or
        #: ``contingency=False``
        self.contingency: Optional[ContingencyLibrary] = None
        self._contingency_dirty = False
        if network is not None and profile is not None and req is not None:
            self.plan = Plan(network, profile, req, gamma=gamma)
            sol = self.plan.solve()
            assert sol.feasible, "no feasible FIN placement"
            self.placement = sol.config
            self.frontier = self.plan.frontier(k_per_exit=self.frontier_k)
            self.network = self.plan.network   # live view of current state
            self._ref_energy = sol.energy
            if len(self.frontier):
                self._last_feasible_frontier = self.frontier
            if contingency:
                self.contingency = ContingencyLibrary(
                    self.plan, k_per_exit=self.frontier_k)
                self.contingency.refill(base_config=self.placement)

    # ------------------------------------------------------------------ API
    def submit(self, prompt: Sequence[int], max_new_tokens: int) -> Request:
        """Queue a request.  Its prompt must fit the cache; without a
        sliding window so must every position it decodes."""
        n = len(prompt)
        if not 1 <= n <= self.cache_len or max_new_tokens < 1:
            raise ValueError(f"prompt of {n} tokens, {max_new_tokens} new "
                             f"tokens, cache of {self.cache_len}")
        if not self._ring and n + max_new_tokens - 1 > self.cache_len:
            raise ValueError(f"{n} prompt + {max_new_tokens} new tokens "
                             f"overflow the {self.cache_len}-entry cache")
        r = Request(rid=next(self._rid), prompt=list(prompt),
                    max_new_tokens=max_new_tokens)
        self.queue.append(r)
        return r

    def _require_plan(self) -> None:
        if self.plan is None:
            raise RuntimeError(
                "engine has no placement plan: construct SplitServeEngine "
                "with network=, profile= and req= to enable failover")

    def _check_node(self, node_idx: int) -> int:
        if not isinstance(node_idx, (int, np.integer)):
            raise ValueError(f"node_idx must be an integer, got "
                             f"{type(node_idx).__name__}")
        n = int(node_idx)
        if not 0 <= n < self.plan.n_nodes:
            raise ValueError(f"node_idx {n} out of range for the "
                             f"{self.plan.n_nodes}-node network")
        return n

    def fail_node(self, node_idx: int) -> None:
        """Node failure: mask the node and re-split.

        The plan keeps its node indexing (the placement simply avoids the
        dead node), so tier accounting and any in-flight references stay
        valid.  With the contingency library covering the resulting mask
        the new placement is *installed* — zero DP relaxations, bit-exact
        vs the warm re-solve; otherwise this is the warm re-solve (cached
        pipeline state; bit-exact vs a cold solve on the reduced
        network), and the miss is recorded."""
        self.fail_nodes([node_idx])

    def fail_nodes(self, node_idxs: Sequence[int]) -> None:
        """Simultaneous (correlated) failure of several nodes: ONE joint
        mask, ONE lookup/re-solve, ONE re-split — a tier-wide outage whose
        joint mask the library covers is as O(1) as a single failure."""
        self._require_plan()
        nodes = [self._check_node(n) for n in node_idxs]
        src = self.plan.network.source_node
        if src in nodes:
            raise ValueError("cannot mask the source-hosting node")
        if not nodes:
            return
        prospective = self.plan._masked.copy()
        prospective[nodes] = True
        entry = (self.contingency.lookup(prospective)
                 if self.contingency is not None else None)
        for n in nodes:
            self.plan.mask_node(n)
        self._after_topology(entry)

    def recover_node(self, node_idx: int) -> None:
        """Node recovery: unmask and re-split (may migrate back) — same
        library-hit / warm-fallback protocol as ``fail_node``."""
        self._require_plan()
        n = self._check_node(node_idx)
        prospective = self.plan._masked.copy()
        prospective[n] = False
        entry = (self.contingency.lookup(prospective)
                 if self.contingency is not None else None)
        self.plan.unmask_node(n)
        self._after_topology(entry)

    def _after_topology(self, entry: Optional[ContingencyEntry]) -> None:
        """Re-split after a mask change: install the library entry (hit:
        zero DP relaxations, migration pre-priced) or warm re-solve
        (miss).  Either way the library is now keyed off a stale base
        mask — mark it dirty; the refill runs OFF this critical path, at
        the next serving step / explicit ``refresh_contingency``."""
        if entry is not None:
            self.stats.contingency_hits += 1
            sol = self.plan.install_solution(entry.solution, dps=entry.dps)
            self._resplit(sol, entry.frontier, priced=entry)
        else:
            if self.contingency is not None:
                self.stats.contingency_misses += 1
            self._replace()
        self._contingency_dirty = True

    def _replace(self) -> None:
        """Warm re-solve + frontier-aware re-split (the library-miss and
        channel-churn path)."""
        sol = self.plan.solve()
        fr = self.plan.frontier(k_per_exit=self.frontier_k)
        self._resplit(sol, fr)

    def _resplit(self, sol, fr: ParetoFrontier,
                 priced: Optional[ContingencyEntry] = None) -> None:
        """Deploy a re-solve result (fresh or library-installed).

        The scenario's Pareto frontier is exposed on every re-split
        (``self.frontier``); with ``migration_weight > 0`` the new
        placement is the option minimizing ``energy + migration_weight *
        migration_bits`` over the frontier rows AND the current placement
        (if it is still feasible — after a recovery, keeping the current
        hosts avoids migrating every block back for a marginal win).
        ``migration_weight=0`` deploys the argmin row.  ``priced`` is the
        library entry whose build-time migration price is reused when the
        deployed transition is exactly the priced one."""
        old = self.placement
        self.frontier = fr
        choice = sol.config
        energy = sol.energy
        if self.migration_weight > 0 and old is not None:
            ev_old = self.plan.evaluate(old)
            choice, energy, _moved, _bits, _kept = frontier_pick(
                fr, old, ev_old.feasible, ev_old.energy, self.profile,
                self.migration_weight)
            if choice is not None and (
                    not sol.feasible
                    or choice.placement != sol.config.placement
                    or choice.final_exit != sol.config.final_exit):
                self.plan.adopt(choice)     # a non-argmin frontier choice
        if choice is None:
            self._handle_infeasible(old)
            return
        self.paused = False
        self.degraded = False
        self.placement = choice
        self._ref_energy = energy
        if len(fr):
            self._last_feasible_frontier = fr
        self.stats.replacements += 1
        if (priced is not None and sol.feasible and old is not None
                and priced.base_config is not None
                and old.placement == priced.base_config.placement
                and old.final_exit == priced.base_config.final_exit
                and choice.placement == sol.config.placement
                and choice.final_exit == sol.config.final_exit):
            moved, bits = priced.moved, priced.bits
        else:
            moved, bits = migration_delta(self.profile, old, choice)
        self.stats.blocks_migrated += moved
        self.stats.migration_bits += bits

    def _handle_infeasible(self, old: Optional[Config]) -> None:
        """No feasible placement under the current mask: apply the
        ``on_infeasible`` policy."""
        masked = self.plan.masked_nodes
        if self.on_infeasible == "degrade":
            lf = self._last_feasible_frontier
            row = lf.cheapest_avoiding(masked) if lf is not None else None
            if row is not None:
                self.placement = row.config
                self.plan.adopt(row.config)
                self.degraded = True
                self.paused = False
                self._ref_energy = row.energy
                self.stats.degrades += 1
                self.stats.replacements += 1
                moved, bits = migration_delta(self.profile, old, row.config)
                self.stats.blocks_migrated += moved
                self.stats.migration_bits += bits
                return
            # every historical row routes through a dead node: park instead
        if self.on_infeasible in ("pause", "degrade"):
            self.paused = True
            self.stats.paused_events += 1
            return
        raise NoFeasiblePlacement(masked, self._last_feasible_frontier)

    # ----------------------------------------------------- contingency admin
    def refresh_contingency(self) -> int:
        """Rebuild the contingency library around the current (mask,
        channel) state; returns the number of entries built.  Runs
        automatically before serving steps when the library is dirty or
        environment-stale — call explicitly to control when the (warm,
        off-critical-path) build cost is paid."""
        if self.contingency is None:
            return 0
        n = self.contingency.refill(base_config=self.placement)
        self._contingency_dirty = False
        return n

    def _maybe_refill(self) -> None:
        if self.contingency is not None and (
                self._contingency_dirty or self.contingency.stale):
            self.refresh_contingency()

    # ------------------------------------------------------------ churn tick
    def on_tick(self, events: Sequence[ChurnEvent], *,
                uplink_bps: float = MOBILE_UPLINK_BPS) -> Dict[str, object]:
        """Apply one ``scenarios.churn_trace`` tick to the serving plan.

        Uplink fades rescale the source links (``value`` is the AR(1)
        quality factor on ``uplink_bps``) and re-split only when the
        incumbent placement leaves the hysteresis band (infeasible, or
        energy above ``(1 + hysteresis) * ref``); failures are applied as
        ONE joint mask (a tier outage covered by the library is a single
        O(1) hit) and recoveries individually, all through the
        contingency protocol.  The engine serves a single user — drive it
        with ``churn_trace(n_users=1, p_move=0.0, ...)``; ``attach``
        events raise.  Returns a per-tick report dict.
        """
        self._require_plan()
        fails: List[int] = []
        recovers: List[int] = []
        chan = False
        for ev in events:
            if ev.kind == "fail":
                fails.append(int(ev.value))
            elif ev.kind == "recover":
                recovers.append(int(ev.value))
            elif ev.kind == "uplink":
                self.plan.update_uplink(uplink_bps * float(ev.value))
                chan = True
            elif ev.kind == "slice":
                self.plan.update_slice(ev.value)
                chan = True
            else:
                raise ValueError(
                    f"unsupported churn event kind {ev.kind!r} for the "
                    f"single-user engine (generate traces with p_move=0)")
        resplit = held = False
        if chan:
            if self.paused:
                self._replace()            # re-attempt under the new channel
                resplit = True
            elif self.placement is not None:
                ev_inc = self.plan.evaluate(self.placement)
                if ev_inc.feasible and ev_inc.energy <= \
                        self._ref_energy * (1.0 + self.hysteresis):
                    held = True
                else:
                    self._replace()
                    resplit = True
            # the channel moved: re-key the library NOW so this tick's own
            # failures can still hit precomputed entries
            self._maybe_refill()
        fails = [n for n in fails if not self.plan._masked[n]]
        recovers = [n for n in recovers if self.plan._masked[n]]
        h0 = self.contingency.stats.hits if self.contingency else 0
        m0 = self.contingency.stats.misses if self.contingency else 0
        if fails:
            self.fail_nodes(fails)
            resplit = True
        for n in recovers:
            self.recover_node(n)
            resplit = True
        if fails or recovers:
            self._maybe_refill()
        return {
            "resplit": resplit, "held": held,
            "n_fail": len(fails), "n_recover": len(recovers),
            "contingency_hits":
                (self.contingency.stats.hits if self.contingency else 0) - h0,
            "contingency_misses":
                (self.contingency.stats.misses if self.contingency else 0)
                - m0,
            "paused": self.paused, "degraded": self.degraded,
        }

    def run(self, *, max_steps: int = 10_000) -> EngineStats:
        while (any(self.slots) or self.queue) and not self.paused \
                and self.stats.steps < max_steps:
            self.step()
        return self.stats

    # ----------------------------------------------------------------- step
    def bucket(self, n: int) -> int:
        """The padded length a prompt of ``n`` tokens is prefilled at."""
        if self.buckets is None:
            return n
        return next(b for b in self.buckets if b >= n)

    def warm(self) -> None:
        """Compile every program serving can reach: each prompt bucket's
        admission, the decode step and the gates at both row counts.  The
        slots' cache rows are left in an undefined state, which admission
        overwrites."""
        for b in self.buckets or ():
            _, self.caches, _ = self.prefill_into_slot(
                self.params, self.caches, jnp.int32(0),
                jnp.zeros(b, jnp.int32), jnp.int32(b))
        logits, self.caches, exits = self.decode_step(
            self.params, self.caches, jnp.zeros((self.B, 1), jnp.int32),
            jnp.asarray(self._slot_pos))
        heads = self._heads(logits, exits)
        for rows in (self.B, 1):
            jax.block_until_ready(gate_heads(
                tuple(x[:rows] for x in heads), self._thresholds_dev))

    def _deployed(self) -> int:
        """Index of the deepest exit a token may take: the placement's
        final exit (deeper blocks are not deployed), else the last."""
        return (self.n_exits - 1 if self.placement is None
                else self.placement.final_exit)

    def _gate(self, heads: List[jnp.ndarray]) -> np.ndarray:
        """First-exit-wins over the deployed heads ``heads`` (exits in
        order, the last one taken by every row that clears no earlier
        threshold): the gate program (``gate_heads``) and one host read of
        its result.  Returns int32 [2, rows]: token and exit index."""
        out = gate_heads(tuple(heads), self._thresholds_dev)
        self.stats.gate_reads += 1
        return np.asarray(out)

    def _heads(self, logits, exits) -> List[jnp.ndarray]:
        """The deployed heads' logits, shallowest first."""
        heads = [exits[f"exit_{p}"] for p in self.cfg.exit_layer_list]
        return (heads + [logits])[:self._deployed() + 1]

    def _emit(self, i: int, r: Request, token: int, exit_idx: int) -> None:
        r.tokens.append(token)
        r.exits_taken.append(exit_idx)
        self.stats.tokens_out += 1
        self._charge(exit_idx)
        if len(r.tokens) >= r.max_new_tokens:
            r.done = True
            self.slots[i] = None   # continuous batching: free the slot

    def _admit(self, i: int, r: Request) -> None:
        """Prefill ``r``'s prompt into slot ``i``; its first token comes
        from the prefill's logits at the prompt's last position."""
        n = len(r.prompt)
        toks = np.zeros(self.bucket(n), np.int32)
        toks[:n] = r.prompt
        logits, self.caches, exits = self.prefill_into_slot(
            self.params, self.caches, jnp.int32(i), jnp.asarray(toks),
            jnp.int32(n))
        self._slot_pos[i] = n
        self.slots[i] = r
        self.stats.admissions += 1
        self.stats.prompt_tokens_prefilled += n
        self.last_admissions.append((r, n - 1, {"final": logits, **exits}))
        token, exit_idx = self._gate(self._heads(logits, exits))[:, 0]
        self._emit(i, r, int(token), int(exit_idx))

    def _fill_slots(self) -> None:
        """Admit queued requests into the free slots, in queue order (again
        while a request admitted with a one-token budget frees its slot)."""
        while True:
            free = [i for i in range(self.B) if self.slots[i] is None]
            take = self.queue[:len(free)]
            if not take:
                return
            del self.queue[:len(take)]
            with span(self.timing, self.stats, "t_admit_ms", "serve.admit",
                      tokens=sum(len(r.prompt) for r in take)):
                for i, r in zip(free, take):
                    self._admit(i, r)

    def _charge(self, exit_idx: int) -> None:
        """Tier accounting for one emitted token at the given exit."""
        st = self.stats
        st.exit_histogram[exit_idx] = st.exit_histogram.get(exit_idx, 0) + 1
        if self.profile is None or self.placement is None:
            return
        prof, place, nw = self.profile, self.placement, self.network
        last_block = prof.exits[exit_idx].block
        for b in range(prof.n_blocks):
            if b > last_block:
                st.blocks_saved += 1
                continue
            st.blocks_executed += 1
            n = place.placement[b]
            st.energy_j += nw.power_active[n] * prof.block_ops_with_exit(
                b, place.final_exit) / nw.compute[n]
            if b < last_block and place.placement[b + 1] != n:
                n2 = place.placement[b + 1]
                st.energy_j += (nw.e_tx[n] + nw.e_rx[n2]) * prof.cut_bits[b]

    def step(self) -> None:
        if self.paused:
            return                # parked until feasibility is restored
        self._maybe_refill()      # background contingency refill (off the
        #                           failover critical path)
        with span(self.timing, self.stats, None, "serve.step"):
            self.last_admissions = []
            self._fill_slots()
            live = [i for i, r in enumerate(self.slots) if r is not None]
            if not live:
                return
            toks = np.zeros((self.B, 1), np.int32)
            for i in live:
                toks[i, 0] = self.slots[i].tokens[-1]
            pos = self._slot_pos.copy()
            blocks = (0 if self._kv_blocks is None else
                      int(blocks_read(pos[live], *self._kv_blocks).sum()))
            with span(self.timing, self.stats, "t_decode_ms",
                      "serve.decode", blocks=blocks):
                logits, self.caches, exits = self.decode_step(
                    self.params, self.caches, jnp.asarray(toks),
                    jnp.asarray(pos))
                if self.timing:
                    # keeps the decode program's time out of serve.gate;
                    # untimed, the gate program queues behind it
                    jax.block_until_ready(logits)
            self.last_logits = {"final": logits, **exits}
            self.last_decoded = [(self.slots[i], int(pos[i]))
                                 if i in live else None
                                 for i in range(self.B)]
            self._slot_pos[live] += 1
            self.stats.steps += 1
            self.stats.live_depth_sum += int(pos[live].sum()) + len(live)
            self.stats.kv_blocks_read += blocks
            with span(self.timing, self.stats, "t_gate_ms", "serve.gate",
                      reads=1):          # the gate's one host read
                token, exit_idx = self._gate(self._heads(logits, exits))
            with span(self.timing, self.stats, "t_account_ms",
                      "serve.account"):
                for i in live:
                    self._emit(i, self.slots[i], int(token[i]),
                               int(exit_idx[i]))


def serve_with_churn(engine: SplitServeEngine,
                     trace: Sequence[Sequence[ChurnEvent]], *,
                     steps_per_tick: int = 1,
                     uplink_bps: float = MOBILE_UPLINK_BPS
                     ) -> List[Dict[str, object]]:
    """Serve through a churn trace: per tick, apply the events
    (``engine.on_tick`` — re-splits, failovers, library refills) then run
    ``steps_per_tick`` decode steps (no-ops while the engine is paused).
    Returns the per-tick reports."""
    if steps_per_tick < 0:
        raise ValueError(f"steps_per_tick must be >= 0, got {steps_per_tick}")
    reports: List[Dict[str, object]] = []
    for events in trace:
        rep = engine.on_tick(events, uplink_bps=uplink_bps)
        for _ in range(steps_per_tick):
            engine.step()
        reports.append(rep)
    return reports
