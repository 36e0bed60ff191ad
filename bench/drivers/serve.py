"""Driver of the serving cells: an early-exit LM behind ``SplitServeEngine``.

The system under test is the engine at the configuration's batch and cache
length, serving the registry model the configuration names at its
published widths (checked against the file's ``model`` block) with random
weights the reference module draws from the seed (``qwen3_ref.weights``,
not the program's initialiser; checked against the program's parameter
shapes), under a FIN placement of the model's own profile
(``core.profile_from_arch``) on the paper's network.  A serving tick is one
``step()`` call, from the call to its tokens on the host (the gates read
them back inside the step); a user-tick is one token emitted to a request.
Prompt tokens prefilled are not user-ticks.

Set-up builds the weights, compiles every prompt bucket's admission, the
decode step and the gates (``engine.warm()``), and admits the traffic's
first wave with one step.  The window then runs a closed loop: before each
step the generator's next requests are queued, one for each free slot, so
that a freed slot is refilled at once (outside the step's span; printed as
generator lag).  After every admission, and after every ``every``-th step,
the logits the engine kept on the device are read to the host (outside the
step's span; printed as the answer-read share of the window).

After the window the cache is dropped and the plain reference
(``bench/reference/qwen3_ref.py``), teacher-forced on the emitted tokens,
judges the logits read of each sequence that held a slot.  Once judging has
taken ``judge_seconds`` it stops at a seeded sample of at least
``sample_min`` sequences, ``sample_admitted`` of them admitted in the
window.  ``--control 1`` also judges the reference computed on weights
rounded through float8 e4m3 against the float32 one, on the same rows.
"""
from __future__ import annotations

import gc
import itertools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import common
from bench.reference import qwen3_ref

#: the serving engine's device programs (``SplitServeEngine``'s jits) and
#: the trace line that holds one event per program run
PROGRAMS = ("jit_decode_step", "jit_prefill_into_slot")
MODULES_LINE = "XLA Modules"

#: the config file's model keys and the registry fields they must equal
_WIDTHS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
           "num_attention_heads": "n_heads", "num_key_value_heads":
           "n_kv_heads", "head_dim": "head_dim_", "intermediate_size":
           "d_ff", "vocab_size": "vocab_size", "tie_word_embeddings":
           "tie_embeddings", "rms_norm_eps": "norm_eps", "rope_theta":
           "rope_theta", "qk_norm": "qk_norm"}


def model_block(arch) -> dict:
    """The ``model`` block of an ``ArchConfig`` (for a reduced variant)."""
    return {k: getattr(arch, f) for k, f in _WIDTHS.items()}


def check_widths(arch, model: dict) -> None:
    got = model_block(arch)
    bad = {k: (got[k], model[k]) for k in _WIDTHS if got[k] != model[k]}
    if bad or arch.dtype != model["torch_dtype"]:
        raise SystemExit(f"bench: registry {arch.name} differs from the "
                         f"configuration: {bad or arch.dtype}")


def program_seconds(raw: dict, names=PROGRAMS) -> Optional[Dict[str, float]]:
    """Device seconds of each program in ``names`` inside the window span,
    averaged over the devices, from a loaded trace (``load_xplane``): the
    events of a device's ``XLA Modules`` line whose name starts with the
    program's.  Without that line, the ``XLA Ops`` the gate kernel did not
    run, all under the first name.  None without a window or a device."""
    from bench import trace_reduce as tr
    win = [ev for p in raw["planes"] if p["name"] == tr.HOST_PLANE
           for line in p["lines"] for ev in line["events"]
           if ev[0] == tr.WINDOW_SPAN]
    devs = [p for p in raw["planes"] if p["name"].startswith(tr.DEVICE_PREFIX)]
    if not win or not devs:
        return None
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    out = dict.fromkeys(names, 0.0)
    for dev in devs:
        lines = {line["name"]: line["events"] for line in dev["lines"]}
        if MODULES_LINE in lines:
            for name, st, du in lines[MODULES_LINE]:
                for k in names:
                    if name.startswith(k):
                        out[k] += max(0.0, min(st + du, w1) - max(st, w0))
        else:
            iv = [(max(st, w0), min(st + du, w1))
                  for name, st, du in lines.get(tr.OPS_LINE, ())
                  if not ("custom-call" in name
                          and tr.op_short_name(name).startswith("ee_gate"))]
            out[names[0]] += tr._length(
                tr._union([(s, e) for s, e in iv if e > s]))
    return {k: v * 1e-9 / len(devs) for k, v in out.items()}


def check_layout(params, shapes) -> None:
    """The drawn weights must fill the program's parameter pytree exactly:
    the same keys, shapes and dtypes."""
    def sig(tree):
        return [(jax.tree_util.keystr(p), tuple(x.shape), str(x.dtype))
                for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]]
    got, want = sig(params), sig(shapes)
    if got != want:
        extra, missing = set(got) - set(want), set(want) - set(got)
        raise SystemExit(f"bench: drawn weights {sorted(extra)} do not fit "
                         f"the program's {sorted(missing)}")


class Reads:
    """The logits rows read from the engine, per request and position."""

    def __init__(self, vocab: int):
        self.V = vocab
        self.rows: Dict[int, Dict[int, Dict[str, np.ndarray]]] = {}
        self.requests: Dict[int, object] = {}
        self.admitted_in_window: List[int] = []

    def keep(self, r, pos: int, heads, row: int) -> None:
        self.requests[r.rid] = r
        self.rows.setdefault(r.rid, {})[pos] = {
            h: x[row, :self.V].copy() for h, x in heads.items()}

    def after_step(self, eng, decoded: bool, in_window: bool) -> None:
        """Read the step's admissions, and its decoded rows if
        ``decoded``: each head's whole array to the host at once."""
        for r, pos, heads in eng.last_admissions:
            self.keep(r, pos, _host(heads), 0)
            if in_window:
                self.admitted_in_window.append(r.rid)
        if decoded:
            heads = _host(eng.last_logits)
            for i, rp in enumerate(eng.last_decoded):
                if rp is not None:
                    self.keep(rp[0], rp[1], heads, i)


def _host(heads) -> Dict[str, np.ndarray]:
    return {h: np.asarray(x, np.float32) for h, x in heads.items()}


def run(ctx) -> Dict[str, object]:
    from repro.configs import get
    from repro.core import AppRequirements, profile_from_arch
    from repro.core.scenarios import paper_scenario
    from repro.models import transformer as T
    from repro.runtime.serve_engine import SplitServeEngine

    cfg, args, over = ctx.config, ctx.args, ctx.overrides
    reduced = bool(over.get("reduced"))
    arch = get(cfg["arch"], reduced=reduced)
    model = model_block(arch) if reduced else cfg["model"]
    if not reduced:
        check_widths(arch, model)
    srv = dict(cfg["serving"], **over.get("serving", {}))
    if not reduced and list(arch.exit_layer_list) != srv["exit_layers"]:
        raise SystemExit(f"bench: {arch.name} exits after layers "
                         f"{arch.exit_layer_list}, the configuration "
                         f"{srv['exit_layers']}")
    exits = list(arch.exit_layer_list)
    B, TL = int(srv["batch"]), int(srv["cache_len"])
    pl = cfg["placement"]
    cmp_ = dict(cfg["compare"], **over.get("compare", {}))
    params = qwen3_ref.weights(
        model, exits, arch.padded_vocab,
        int(common.rng_for(args.seed, 0).integers(2**31)),
        jnp.dtype(arch.dtype))
    check_layout(params, jax.eval_shape(
        lambda k: T.init_model(k, arch), jax.random.PRNGKey(0)))
    profile = profile_from_arch(arch, bits=int(pl["cut_bits"]),
                                context=int(pl["context"]),
                                accuracy=float(pl["final_accuracy"]))
    eng = SplitServeEngine(
        arch, params, batch_size=B, cache_len=TL,
        thresholds=srv["thresholds"],
        network=paper_scenario(n_extra_edge=int(pl["n_extra_edge"])),
        profile=profile,
        req=AppRequirements(alpha=float(pl["alpha"]),
                            delta=float(pl["delta_s"])),
        timing=bool(args.trace))
    eng.warm()
    gen = ctx.generator.make(ctx.traffic, vocab=arch.vocab_size,
                             cache_len=TL, seed=args.seed)
    reads = Reads(arch.vocab_size)
    for prompt, budget in gen.first_wave(B):
        eng.submit(prompt, budget)
    every = int(cmp_["every"])
    with common.CompileCounter() as cc_warm:
        eng.step()                         # admits the first wave
        jax.block_until_ready(eng.caches)
    reads.after_step(eng, True, False)
    common.info(f"set-up: {arch.name}, batch {B}, cache {TL}, buckets "
                f"{eng.buckets}, placement {eng.placement.placement} exit "
                f"{eng.placement.final_exit}; first wave depths "
                f"{sorted(len(r.prompt) for r in eng.slots if r)}; "
                f"{cc_warm.n} compiles in its admission step")

    st0 = _engine_counters(eng)
    step_ms: List[float] = []
    prompts: List[int] = []
    step_admits: List[int] = []
    gen_s = rec_s = 0.0
    n_steps = 0
    setup_s = time.perf_counter() - ctx.t0
    common.info(f"setup_s {setup_s!r}")
    trace = ctx.tracer
    trace.start()
    with common.CompileCounter() as cc:
        w0 = time.perf_counter()
        with trace.span("bench.window"):
            while time.perf_counter() - w0 < args.seconds:
                g0 = time.perf_counter()
                with trace.span("bench.gen"):
                    free = sum(r is None for r in eng.slots)
                    while len(eng.queue) < free:
                        eng.submit(*gen.next_request())
                t0 = time.perf_counter()
                gen_s += t0 - g0
                with trace.span("bench.step"):
                    eng.step()
                t1 = time.perf_counter()
                step_ms.append((t1 - t0) * 1e3)
                n_steps += 1
                step_admits.append(len(eng.last_admissions))
                prompts += [len(r.prompt) for r, _p, _h in
                            eng.last_admissions]
                with trace.span("bench.record"):
                    reads.after_step(eng, n_steps % every == 0, True)
                rec_s += time.perf_counter() - t1
            window_s = time.perf_counter() - w0
    trace.stop()
    programs = (program_seconds(trace.trace) if trace.trace is not None
                else None)
    if programs is not None:
        common.info("device seconds of the programs in the window: " + ", "
                    .join(f"{k} {v!r}" for k, v in programs.items()))
    st1 = _engine_counters(eng)
    device = common.device_record(ctx.devices) if ctx.devices else {}
    counters = {k: st1[k] - st0[k] for k in st1}
    common.info(f"window: {n_steps} steps in {window_s:.6f} s, "
                f"{int(counters['tokens_out'])} tokens, "
                f"{int(counters['admissions'])} admissions "
                f"({int(counters['prompt_tokens_prefilled'])} prompt "
                f"tokens); generator lag {gen_s / window_s:.6f} of the "
                f"window, answer reads {rec_s / window_s:.6f}; {cc.n} "
                f"compiles inside the window")
    slow = sorted(range(n_steps), key=lambda i: -step_ms[i])[:5]
    common.info("slowest steps (index, ms, admissions): " + "; ".join(
        f"{i} {step_ms[i]:.3f} {step_admits[i]}" for i in slow))

    # ---- the plain reference judges the rows read
    eng.caches = eng.last_logits = None
    eng.last_admissions = []
    gc.collect()
    judge, control = _judge(params, model, exits, cmp_, reads, args.seed,
                            bool(over.get("control")))
    checks = {
        "logit_rel_rms": {"value": judge.rel_rms,
                          "limit": ctx.limits["logit_rel_rms"]},
        "top1_flips": {"value": judge.flips,
                       "limit": ctx.limits["top1_flips"]},
    }
    e2e = {
        "tick_ms_p95": common.percentile(step_ms, 95.0),
        "user_ticks_per_s": counters["tokens_out"] / window_s,
        "setup_s": setup_s,
    }
    return {
        "e2e": e2e, "checks": checks, "attempted": n_steps, "failed": 0,
        "device": device, "control": control,
        "layer_ctx": {"kind": "serve", "steps": n_steps,
                      "window_s": window_s, "counters": counters,
                      "spans_ms": {k: counters[k] for k in
                                   ("t_admit_ms", "t_decode_ms",
                                    "t_gate_ms", "t_account_ms")},
                      "programs_s": programs,
                      "model": model, "batch": B, "prompts": prompts,
                      "exit_layers": exits,
                      "heads_gated": eng.placement.final_exit + 1,
                      "kernels": ("ee_gate",)},
    }


def _engine_counters(eng) -> Dict[str, float]:
    st = eng.stats
    return {f: float(getattr(st, f)) for f in (
        "steps", "tokens_out", "admissions", "prompt_tokens_prefilled",
        "live_depth_sum", "t_admit_ms", "t_decode_ms", "t_gate_ms",
        "t_account_ms")}


def _judge(params, model: dict, exits: List[int], cmp_: dict, reads: Reads,
           seed: int, control: bool) -> Tuple[object, object]:
    """Judge the sequences read, in a seeded order that takes one admitted
    in the window and one admitted before it in turn, until
    ``judge_seconds`` have passed and the sample holds ``sample_min``
    sequences, ``sample_admitted`` from the window."""
    c0 = time.perf_counter()
    dims = qwen3_ref.dims_from(model, exits)
    rng = common.rng_for(seed, 7)
    inside = [r for r in reads.admitted_in_window if r in reads.rows]
    rest = [r for r in reads.rows if r not in inside]
    a, b = list(rng.permutation(inside)), list(rng.permutation(rest))
    order = [x for pair in itertools.zip_longest(a, b) for x in pair
             if x is not None]
    judge = qwen3_ref.Judge(cmp_["flip_margin"])
    ctl = qwen3_ref.Judge(cmp_["flip_margin"]) if control else None
    judged: List[int] = []
    for rid in order:
        n_in = sum(r in inside for r in judged)
        if (time.perf_counter() - c0 > cmp_["judge_seconds"]
                and len(judged) >= cmp_["sample_min"]
                and n_in >= min(cmp_["sample_admitted"], len(inside))):
            break
        r = reads.requests[rid]
        rows = reads.rows[rid]
        at = sorted(rows)
        seq = (list(r.prompt) + list(r.tokens))[:at[-1] + 1]
        prog = {h: np.stack([rows[p][h] for p in at]) for h in rows[at[0]]}
        ref = qwen3_ref.forward(params, dims, seq, at)
        judge.compare(prog, ref, f"request {rid}")
        if ctl is not None:
            low = qwen3_ref.forward(params, dims, seq, at,
                                    cast=jnp.float8_e4m3fn)
            ctl.compare(low, ref, f"control {rid}")
        judged.append(int(rid))
    common.info(f"reference: {len(judged)} of {len(order)} sequences "
                f"({sum(r in inside for r in judged)} admitted in the "
                f"window), {judge.rows} rows in "
                f"{time.perf_counter() - c0:.3f} s; logit_rel_rms "
                f"{judge.rel_rms!r}, top1_flips {judge.flips}, largest "
                f"logit gap {judge.max_gap!r}; " + "; ".join(judge.examples))
    out = None
    if ctl is not None:
        out = {"logit_rel_rms": ctl.rel_rms, "top1_flips": ctl.flips}
        common.info(f"control (reference on float8 e4m3 weights against "
                    f"float32): logit_rel_rms {ctl.rel_rms!r}, top1_flips "
                    f"{ctl.flips}, largest logit gap {ctl.max_gap!r}")
    return judge, out
