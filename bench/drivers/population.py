"""Driver of the population cells: a user population re-placed tick by tick.

The system under test is ``ChurnOrchestrator`` over ``population_cohorts``
(one ``Population`` cohort per app).  A channel tick is
``run_arrays(q[None], attach[None])``, one report per call as an operator
receives them; an infrastructure event is ``step([event])``, a tick of its
own.  Each tick's span on the host clock ends when its decisions are back
on the host.

Set-up builds the deployment from the configuration file, solves every
user once, compiles every relaxation bucket the cell can reach
(``warm_buckets``) and runs the traffic's warm-up ticks.  The window then
runs ticks until ``--seconds`` have passed.  The traffic for the next tick
is drawn between ticks, outside every tick's span; its share of the window
is printed as generator lag.  The rate divides by the whole window.  A fixed sample of users, drawn from the seed, has its answer read
after every tick (outside the spans); once the window has closed the plain
reference (``bench/reference/fin_ref.py``) judges every one of them.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench import common
from bench.reference.fin_ref import (Answers, Deployment, FinChecker,
                                     ReferenceSource)


def program_objects(dep: Deployment):
    """The program's input types built from the same numbers as the
    reference: the network, one profile and requirement per app."""
    from repro.core.dnn_profile import DNNProfile, ExitSpec
    from repro.core.problem import AppRequirements
    from repro.core.system_model import Network, NodeSpec
    tiers = dep.cfg["network"]["tiers"]
    nodes = [NodeSpec(name=f"{t}{i}", tier=t,
                      compute_ops=float(tiers[t]["tops"] * 1e12),
                      power_active=float(dep.p_act[i]),
                      power_idle=float(tiers[t]["power_idle_w"]),
                      link_bps=float(dep.link_bps[i]),
                      e_tx=float(dep.e_tx[i]), e_rx=float(dep.e_rx[i]))
             for i, t in enumerate(dep.tiers)]
    network = Network(nodes=nodes, bandwidth=dep.bw.copy(),
                      compute=dep.compute.copy(), source_node=dep.src)
    profiles, reqs = {}, {}
    for name, a in dep.apps.items():
        exits = [ExitSpec(block=a.exit_block[k], ops=a.exit_ops[k],
                          out_bits=a.exit_out_bits[k],
                          accuracy=a.accuracy[k], phi=a.phi[k])
                 for k in range(a.K)]
        profiles[name] = DNNProfile(name=name, input_bits=a.input_bits,
                                    block_ops=list(a.block_ops),
                                    cut_bits=list(a.cut_bits), exits=exits)
        reqs[name] = AppRequirements(alpha=a.alpha, delta=a.delta,
                                     sigma=a.sigma)
    return network, profiles, reqs


class Sample:
    """A fixed sample of users whose answers are read after every tick."""

    def __init__(self, pops, users: np.ndarray, n_apps: int, L: int):
        self.users = users
        self.cohort = users % n_apps
        self.local = users // n_apps
        self.pops = pops
        self.L = L
        for c in np.unique(self.cohort):
            sel = self.cohort == c
            assert (pops[c].user_ids[self.local[sel]] == users[sel]).all()

    def read(self) -> Answers:
        S = len(self.users)
        place = np.full((S, self.L), -1, np.int64)
        ex = np.empty(S, np.int64)
        en = np.empty(S)
        for c in np.unique(self.cohort):
            sel = np.nonzero(self.cohort == c)[0]
            p, loc = self.pops[c], self.local[sel]
            pl = p._inc_place[loc]
            place[sel, :pl.shape[1]] = pl
            ex[sel] = p._inc_exit[loc]
            en[sel] = p._inc_energy[loc]
        return Answers(ex >= 0, place, ex, en)


def warm_relax(pops, buckets: List[int], backend: str) -> int:
    """Compile the device relaxation for every (chain length, bucket) the
    cell can reach by running it once on empty chains."""
    from repro.core.bellman_ford import batched_banded_relax_argmin
    seen = set()
    n = 0
    for p in pops:
        L1, N, Gp1 = p.L - 1, p.N, p.gamma + 1
        if (L1, N, Gp1) in seen or L1 < 1:
            continue
        seen.add((L1, N, Gp1))
        for B in buckets:
            init = np.full((B, N, Gp1), np.inf)
            init[:, 0, 0] = 0.0
            E = np.zeros((B, L1, N, N))
            st = np.zeros((B, L1, N, N))
            if backend == "mesh":
                p._mesh().relax(init, E, st, p.depth_window_lo)
            else:
                batched_banded_relax_argmin(init, E, st, p.depth_window_lo,
                                            backend=backend)
            n += 1
    return n


def run(ctx) -> Dict[str, object]:
    from repro.core import ChurnOrchestrator, population_cohorts
    from repro.core.scenarios import ChurnEvent

    cfg, traffic, args = ctx.config, ctx.traffic, ctx.args
    pop_cfg = cfg["population"]
    U = int(ctx.overrides.get("users", cfg["n_users"]))
    backend = pop_cfg["backend"]
    timing = bool(args.trace)
    dep = Deployment(cfg)
    network, profiles, reqs = program_objects(dep)
    kw = {}
    if backend == "mesh":
        from repro.sharding.population import population_mesh
        kw["mesh"] = population_mesh(ctx.chips)
    sol = cfg["solver"]
    pops = population_cohorts(
        U, apps=reqs, profiles=profiles, network=network,
        gamma=int(sol["gamma"]), backend=backend, timing=timing,
        quantize=sol["quantize"], max_tighten=int(sol["max_tighten"]),
        tighten_factor=float(sol["tighten_factor"]), **kw)
    orch = ChurnOrchestrator(population=pops,
                             hysteresis=float(pop_cfg["hysteresis"]),
                             uplink_bps=dep.uplink_bps,
                             detach_frac=float(pop_cfg["detach_frac"]))
    assert orch._edge_nodes == dep.edge_nodes
    n_warm = warm_relax(pops, [int(b) for b in ctx.overrides.get(
        "warm_buckets", pop_cfg["warm_buckets"])], backend)
    gen = ctx.generator.make(traffic, n_users=U, edge_nodes=dep.edge_nodes,
                             seed=args.seed)
    n_apps = len(dep.apps)
    S = min(int(ctx.overrides.get("sample", traffic["sample_users"])), U)
    users = np.sort(common.rng_for(args.seed, 3).choice(U, S, replace=False))
    L = max(a.L for a in dep.apps.values())
    sample = Sample(pops, users, n_apps, L)
    apps_of = [dep.app_names[u % n_apps] for u in users]
    failed = np.zeros(dep.N, dtype=bool)

    def channel_tick(q, att):
        return orch.run_arrays(q[None], None if att is None else att[None])

    def event_tick(kind, node):
        return orch.step([ChurnEvent(kind, None, int(node))])

    # warm-up ticks: the first moves every user from the base network to
    # its stationary channel, which is where most re-placements happen
    q = att = None
    with common.CompileCounter() as cc_warm:
        for _ in range(int(traffic["warmup_ticks"])):
            q, att, events = gen.next_channel()
            channel_tick(q, att)
            for kind, node in events:
                event_tick(kind, node)
                failed[node] = kind == "fail"
    common.info(f"set-up: {U} users, backend {backend}, {n_warm} relax "
                f"programs warmed, {cc_warm.n} compiles in "
                f"{traffic['warmup_ticks']} warm-up ticks")

    def sample_state():
        return (q[users].copy(),
                (att[users].copy() if att is not None
                 else np.zeros(S, np.int64)),
                failed.copy())

    start_state = sample_state()
    start_answers = sample.read()
    stats0 = _pop_counters(pops)
    relaxed0 = [p.stats.dp_relaxes for p in pops]
    records = []           # (kind, state, answers) per tick
    tick_ms: List[float] = []
    reports = []
    gen_s = rec_s = 0.0
    setup_s = time.perf_counter() - ctx.t0
    trace = ctx.tracer
    trace.start()
    attempted = failed_ticks = 0
    with common.CompileCounter() as cc:
        w0 = time.perf_counter()
        with trace.span("bench.window"):
            while time.perf_counter() - w0 < args.seconds:
                g0 = time.perf_counter()
                with trace.span("bench.gen"):
                    q, att, events = gen.next_channel()
                t0 = time.perf_counter()
                gen_s += t0 - g0
                attempted += 1
                with trace.span("bench.tick.channel"):
                    (rep,) = channel_tick(q, att)
                t1 = time.perf_counter()
                tick_ms.append((t1 - t0) * 1e3)
                reports.append(rep)
                with trace.span("bench.record"):
                    records.append(("channel", sample_state(), sample.read()))
                r1 = time.perf_counter()
                rec_s += r1 - t1
                for kind, node in events:
                    failed[node] = kind == "fail"
                    attempted += 1
                    t0 = time.perf_counter()
                    with trace.span("bench.tick.outage"):
                        rep = event_tick(kind, node)
                    t1 = time.perf_counter()
                    tick_ms.append((t1 - t0) * 1e3)
                    reports.append(rep)
                    with trace.span("bench.record"):
                        records.append((kind, sample_state(), sample.read()))
                    rec_s += time.perf_counter() - t1
            window_s = time.perf_counter() - w0
    trace.stop()
    stats1 = _pop_counters(pops)
    devs = ctx.devices
    device = common.device_record(devs) if devs else {}
    n_ticks = len(tick_ms)
    common.info(f"window: {n_ticks} ticks ({sum(1 for r in records if r[0] == 'channel')} "
                f"channel) in {window_s:.6f} s; generator lag "
                f"{gen_s / window_s:.6f} of the window, answer reads "
                f"{rec_s / window_s:.6f}; {cc.n} compiles inside the window")
    outage_ms = [ms for ms, r in zip(tick_ms, records) if r[0] != "channel"]
    common.info(f"outage ticks (ms): {outage_ms!r}")

    # ---- the plain reference judges every sampled answer
    c0 = time.perf_counter()
    checker = FinChecker(dep, apps_of)
    q0, a0, f0 = start_state
    dead0 = np.broadcast_to(f0, (S, dep.N))
    checker.start(dep.uplink_vectors(q0, a0), dead0, start_answers)
    prev = start_answers
    for t, (kind, (q, att, fl), ans) in enumerate(records):
        vec = dep.uplink_vectors(q, att)
        checker.tick(t, vec, np.broadcast_to(fl, (S, dep.N)), prev, ans)
        prev = ans
    common.info(f"reference: {checker.answers} answers of {S} users judged "
                f"({checker.replaced} re-placements solved) in "
                f"{time.perf_counter() - c0:.3f} s; "
                + "; ".join(checker.examples[:3]))
    control = (_control(dep, apps_of, start_state, records)
               if ctx.overrides.get("control") else None)
    checks = {
        "answer_mismatches": {"value": checker.mismatches,
                              "limit": ctx.limits["answer_mismatches"]},
        "energy_rel_gap": {"value": checker.energy_gap,
                           "limit": ctx.limits["energy_rel_gap"]},
    }
    counters = {k: stats1[k] - stats0[k] for k in stats1}
    relaxed_by_app = {dep.app_names[i]: p.stats.dp_relaxes - r0
                      for i, (p, r0) in enumerate(zip(pops, relaxed0))}
    sums = {f: float(sum(getattr(r, f) for r in reports))
            for f in ("t_ingest_ms", "t_relax_ms", "t_post_ms")}
    e2e = {
        "tick_ms_p95": common.percentile(tick_ms, 95.0),
        "user_ticks_per_s": U * n_ticks / window_s,
        "setup_s": setup_s,
    }
    return {
        "e2e": e2e, "checks": checks, "attempted": attempted,
        "failed": failed_ticks, "device": device, "control": control,
        "layer_ctx": {"kind": "population", "ticks": n_ticks,
                      "counters": counters, "spans_ms": sums,
                      "relaxed_by_app": relaxed_by_app,
                      "window_s": window_s, "config": cfg,
                      "kernels": (("banded_minplus_chain",)
                                  if backend == "pallas" else ())},
    }


def _control(dep: Deployment, apps_of, start_state, records
             ) -> Dict[str, float]:
    """The control: the reference in float32 put in the program's place on
    the same users and ticks, judged like the program."""
    c0 = time.perf_counter()
    S = len(apps_of)
    f32 = ReferenceSource(Deployment(dep.cfg, dtype=np.float32), apps_of)
    checker = FinChecker(dep, apps_of)
    base = np.broadcast_to(dep.base_vector(), (S, dep.N))
    f32.step(base, np.zeros((S, dep.N), bool), first=True)
    q0, a0, f0 = start_state
    vec0 = dep.uplink_vectors(q0, a0)
    dead0 = np.broadcast_to(f0, (S, dep.N))
    prev = f32.step(vec0, dead0)
    checker.start(vec0, dead0, prev)
    for t, (_kind, (q, att, fl), _ans) in enumerate(records):
        vec = dep.uplink_vectors(q, att)
        dead = np.broadcast_to(fl, (S, dep.N))
        ans = f32.step(vec, dead)
        checker.tick(t, vec, dead, prev, ans)
        prev = ans
    common.info(f"control (float32 reference in the program's place): "
                f"answer_mismatches {checker.mismatches} energy_rel_gap "
                f"{checker.energy_gap!r} over {checker.answers} answers in "
                f"{time.perf_counter() - c0:.3f} s; "
                + "; ".join(checker.examples[:2]))
    return {"answer_mismatches": checker.mismatches,
            "energy_rel_gap": checker.energy_gap}


def _pop_counters(pops) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for f in ("dp_relaxes", "dp_cache_hits", "solves", "bounded_relaxes",
              "mask_reuses", "fused_relaxes", "chunked_relaxes"):
        out[f] = float(sum(getattr(p.stats, f) for p in pops))
    return out
