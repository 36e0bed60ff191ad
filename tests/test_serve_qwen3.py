"""Qwen3-4B on the split-serving engine: the published configuration, its
placement profile, one-pass admission and per-slot positions, checked
against the plain float32 reference (``models/dense_ref.py``).

The reduced variant keeps what the chip runs: a head wider than
``d_model / n_heads``, QK-norm, tied embeddings, eps 1e-6, theta 1e6 and
exits at the thirds of the depth."""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get
from repro.core import AppRequirements, Plan, paper_profile, profile_from_arch
from repro.core.scenarios import paper_scenario
from repro.kernels.decode_attn.decode_attn import block_len
from repro.launch.flops import param_count
from repro.models import dense_ref
from repro.models import transformer as T
from repro.runtime.serve_engine import SplitServeEngine

#: both sides compute in float32; they differ only in the order of sums
#: (online-softmax chunks and blocks against one pass, fused projections),
#: which moves a logit by a few float32 ulps of the values summed
REL_RMS = 1e-5


def spread_norms(params, key):
    """Every RMSNorm scale drawn as 1 + 0.1 N(0, 1) (the initialiser sets
    them to 1), so that a scale applied to the wrong tensor, or never,
    shows in the logits."""
    paths, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(key, len(paths))
    return jax.tree.unflatten(tree, [
        1.0 + 0.1 * jax.random.normal(k, x.shape, x.dtype)
        if jax.tree_util.keystr(p).endswith("['scale']") else x
        for k, (p, x) in zip(keys, paths)])


@pytest.fixture(scope="module")
def model():
    cfg = get("qwen3-4b", reduced=True)
    params = spread_norms(T.init_model(jax.random.PRNGKey(7), cfg),
                          jax.random.PRNGKey(8))
    return cfg, params


def _rel_rms(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def _serve(eng):
    """Run the engine to the end; every logits row each request produced,
    ``{rid: {position: {head: row}}}``, from admissions and decode steps."""
    V = eng.cfg.vocab_size
    seen = {}

    def keep(r, pos, heads, row):
        seen.setdefault(r.rid, {})[pos] = {
            h: np.asarray(x[row, :V]) for h, x in heads.items()}

    while any(eng.slots) or eng.queue:
        eng.step()
        for r, pos, heads in eng.last_admissions:
            keep(r, pos, heads, 0)
        for i, rp in enumerate(eng.last_decoded):
            if rp is not None:
                keep(rp[0], rp[1], eng.last_logits, i)
    return seen


def test_registry_qwen3_is_the_published_model():
    cfg = get("qwen3-4b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim_, cfg.d_ff, cfg.vocab_size) == \
        (36, 2560, 32, 8, 128, 9728, 151936)
    assert cfg.tie_embeddings and cfg.qk_norm
    assert (cfg.norm_eps, cfg.rope_theta) == (1e-6, 1e6)
    assert cfg.exit_layer_list == (12, 24)
    small = get("qwen3-4b", reduced=True)
    assert small.head_dim_ != small.d_model // small.n_heads
    assert small.tie_embeddings and small.qk_norm
    assert (small.norm_eps, small.rope_theta) == (1e-6, 1e6)


def test_param_count_is_4_02e9():
    # the published 4.022e9 (3.633e9 in layers, 0.389e9 in the tied table)
    n = param_count(get("qwen3-4b"))
    assert abs(n / 4.022e9 - 1.0) < 0.005, n


def test_profile_from_arch_blocks_and_exits():
    cfg = get("qwen3-4b")
    prof = profile_from_arch(cfg)
    assert prof.n_blocks == 3 and prof.n_exits == 3
    assert [e.block for e in prof.exits] == [0, 1, 2]
    assert prof.cut_bits == [2560 * 16] * 3
    # 12 layers, each 2 FLOPs a parameter (q/k/v/o 2560 x 128 x 48 +
    # 4096 x 2560, SwiGLU 3 x 2560 x 9728, two norms of 2560) plus
    # attention's scores and sum over 2048 positions
    per_layer = 2 * (2560 * 128 * 48 + 4096 * 2560 + 3 * 2560 * 9728
                     + 2 * 2560) + 4 * 32 * 128 * 2048
    assert prof.block_ops[0] == pytest.approx(12 * per_layer)
    assert prof.exits[0].ops == 2 * 2560 * 151936


def test_fin_places_the_profile_on_the_paper_network():
    cfg = get("qwen3-4b")
    nw = paper_scenario(n_extra_edge=2)
    sol = Plan(nw, profile_from_arch(cfg),
               AppRequirements(alpha=1.0, delta=0.05)).solve()
    assert sol.feasible
    # the early exits claim no accuracy: the final head stays deployed
    assert sol.config.final_exit == 2 and len(sol.config.placement) == 3


def test_profile_must_describe_the_served_model(model):
    cfg, params = model
    with pytest.raises(ValueError, match="exits"):
        SplitServeEngine(cfg, params, batch_size=2, cache_len=64,
                         network=paper_scenario(),
                         profile=paper_profile("h5"),
                         req=AppRequirements(alpha=0.5, delta=8e-3))


@pytest.mark.parametrize("prompt_len", [5, 64, 71])
def test_admission_and_decode_match_dense_ref(model, prompt_len):
    """Admission prefill (one bucket below, at and above 64) and the decode
    steps after it give the reference's logits at every head, beside a
    deeper slot: the two rows' live K/V end in different blocks of the
    decode_attn kernel, which reads each to its own depth."""
    cfg, params = model
    eng = SplitServeEngine(cfg, params, batch_size=2, cache_len=1024,
                           thresholds=[1.1, 1.1])
    assert block_len(1024) < 600        # the deep slot's own block
    rng = np.random.default_rng(prompt_len)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (prompt_len, 600)]
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    served = _serve(eng)
    for prompt, r in zip(prompts, reqs):
        seen = served[r.rid]
        n = len(prompt)
        assert sorted(seen) == list(range(n - 1, n + 5))
        ref = dense_ref.forward(params, dense_ref.dims_of(cfg),
                                prompt + r.tokens[:-1], at=sorted(seen))
        for head in ref:
            got = np.stack([seen[p][head] for p in sorted(seen)])
            assert _rel_rms(got, ref[head]) < REL_RMS, (n, head)
            assert (got.argmax(-1) == ref[head].argmax(-1)).all(), (n, head)


def _swap_qk_norm(params, cfg):
    mix = params["layers"]["l0"]["mix"]
    return _replace(params, ("layers", "l0", "mix"),
                    dict(mix, q_norm=mix["k_norm"], k_norm=mix["q_norm"]))


def _exit_takes_final_norm(params, cfg):
    e = f"exit_{cfg.exit_layer_list[0]}"
    return _replace(params, ("exits", e), {"norm": params["final_norm"]})


def _k_norm_unapplied(params, cfg):
    mix = params["layers"]["l0"]["mix"]
    ones = {"scale": jnp.ones_like(mix["k_norm"]["scale"])}
    return _replace(params, ("layers", "l0", "mix"), dict(mix, k_norm=ones))


def _replace(tree, path, value):
    if not path:
        return value
    return dict(tree, **{path[0]: _replace(tree[path[0]], path[1:], value)})


@pytest.mark.parametrize("miswire", [_swap_qk_norm, _exit_takes_final_norm,
                                     _k_norm_unapplied],
                         ids=["swap_qk_norm", "exit_takes_final_norm",
                              "k_norm_unapplied"])
def test_dense_ref_sees_a_miswired_norm(model, miswire):
    """The engine served with one norm scale mis-wired departs from the
    reference on the true weights far beyond ``REL_RMS``: the drawn scales
    differ from 1 and from each other."""
    cfg, params = model
    eng = SplitServeEngine(cfg, miswire(params, cfg), batch_size=2,
                           cache_len=128, thresholds=[1.1, 1.1])
    prompt = np.random.default_rng(5).integers(0, cfg.vocab_size,
                                               20).tolist()
    r = eng.submit(prompt, max_new_tokens=4)
    seen = _serve(eng)[r.rid]
    ref = dense_ref.forward(params, dense_ref.dims_of(cfg),
                            prompt + r.tokens[:-1], at=sorted(seen))
    worst = max(_rel_rms(np.stack([seen[p][h] for p in sorted(seen)]),
                         ref[h]) for h in ref)
    assert worst > 100 * REL_RMS, worst


def test_readmitted_slot_matches_the_request_served_alone(model):
    """A request admitted into a slot freed mid-run sees none of the slot's
    previous occupant: its logits equal those of the same request served
    alone in a fresh engine."""
    cfg, params = model
    rng = np.random.default_rng(3)
    p_a, p_b, p_c = (rng.integers(0, cfg.vocab_size, n).tolist()
                     for n in (40, 9, 7))
    eng = SplitServeEngine(cfg, params, batch_size=2, cache_len=64,
                           thresholds=[1.1, 1.1])
    eng.submit(p_a, 3)                  # slot 0, frees after 2 steps
    eng.submit(p_b, 12)                 # slot 1, decodes throughout
    c = eng.submit(p_c, 5)              # re-admitted into slot 0
    got = _serve(eng)[c.rid]
    alone = SplitServeEngine(cfg, params, batch_size=2, cache_len=64,
                             thresholds=[1.1, 1.1])
    c1 = alone.submit(p_c, 5)
    want = _serve(alone)[c1.rid]
    assert c.tokens == c1.tokens
    assert sorted(got) == sorted(want)
    for pos in want:
        for head in want[pos]:
            np.testing.assert_allclose(got[pos][head], want[pos][head],
                                       rtol=1e-5, atol=1e-5)


def test_scalar_position_is_shared_by_every_row(model):
    cfg, params = model
    toks = jnp.asarray([[3], [5]], jnp.int32)
    a = T.decode_step(params, cfg, toks, T.init_caches(cfg, 2, 16),
                      jnp.int32(4))
    b = T.decode_step(params, cfg, toks, T.init_caches(cfg, 2, 16),
                      jnp.asarray([4, 4], jnp.int32))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("variant", ["int8", "ring"])
def test_prefill_into_slot_with_int8_and_ring_caches(variant):
    """One-pass admission then per-slot decode on an int8 cache and on a
    sliding-window ring equals the full forward at the next position."""
    if variant == "int8":
        cfg = dataclasses.replace(get("qwen3-4b", reduced=True),
                                  kv_cache_dtype="int8")
        tol = 0.05
    else:
        cfg = dataclasses.replace(get("mixtral-8x22b", reduced=True),
                                  capacity_factor=16.0)
        tol = 1e-4
    params = T.init_model(jax.random.PRNGKey(1), cfg)
    S = 12
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, S), 0,
                              cfg.vocab_size)
    full = T.forward_train(params, cfg, {"tokens": toks})["final"][0, -1]
    caches = T.init_caches(cfg, 3, 16)
    padded = jnp.pad(toks[0, :S - 1], (0, 16 - (S - 1)))
    _, caches, _ = T.prefill_into_slot(params, cfg, caches, 2, padded, S - 1)
    step = jnp.zeros((3, 1), jnp.int32).at[2, 0].set(toks[0, S - 1])
    lg, _, _ = T.decode_step(params, cfg, step, caches,
                             jnp.asarray([0, 5, S - 1], jnp.int32))
    a, b = np.asarray(full), np.asarray(lg[2])
    m = np.isfinite(a)
    err = np.abs(a[m] - b[m]).max() / np.abs(a[m]).max()
    assert err < tol, f"{variant}: {err:.2e}"


def test_warm_leaves_nothing_to_compile(model):
    cfg, params = model
    eng = SplitServeEngine(cfg, params, batch_size=2, cache_len=128,
                           network=paper_scenario(),
                           profile=profile_from_arch(cfg),
                           req=AppRequirements(alpha=1.0, delta=0.05))
    assert eng.buckets == [64, 128]
    eng.warm()
    n = [0]

    def count(event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            n[0] += 1
    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        eng.submit(list(range(1, 70)), 4)
        eng.submit([5, 6, 7], 9)
        eng.submit(list(range(9, 30)), 3)
        eng.run()
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    assert n[0] == 0


def test_spans_and_counters(model, tmp_path):
    cfg, params = model
    eng = SplitServeEngine(cfg, params, batch_size=2, cache_len=64,
                           timing=True)
    eng.submit(list(range(1, 11)), 3)
    eng.submit(list(range(1, 6)), 4)
    eng.submit(list(range(1, 4)), 2)
    with jax.profiler.trace(str(tmp_path)):
        st = eng.run()
    assert st.admissions == 3 and st.prompt_tokens_prefilled == 18
    assert st.tokens_out == 9
    # decode steps: 2, 3 and 1 per request (the first token is admission's)
    # at depths 10..11, 5..7 and 3 (KV entries read = position + 1)
    assert st.live_depth_sum == (11 + 12) + (6 + 7 + 8) + 4
    # a 64-entry cache is one block of the decode_attn kernel: one block
    # per live slot a step, carried by the step's serve.decode span
    assert st.kv_blocks_read == 2 + 3 + 1
    assert st.t_admit_ms > 0 and st.t_gate_ms > 0 and st.t_account_ms > 0
    # one host read of the gate a decode step and an admission, each
    # decode step's carried by its serve.gate span
    assert st.gate_reads == st.steps + st.admissions
    (f,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)
    reads = [dict(e.stats).get("reads")
             for p in ProfileData.from_file(f).planes
             if p.name == "/host:CPU" for line in p.lines
             for e in line.events if e.name == "serve.gate"]
    assert reads == [1] * st.steps
    blocks = [dict(e.stats).get("blocks")
              for p in ProfileData.from_file(f).planes
              if p.name == "/host:CPU" for line in p.lines
              for e in line.events if e.name == "serve.decode"]
    assert len(blocks) == st.steps and sum(blocks) == st.kv_blocks_read


def test_kv_blocks_read_counts_each_live_slots_blocks(model):
    """Over decode steps, ``kv_blocks_read`` adds ``min(pos, T-1) // bt + 1``
    for each live slot: the blocks of one layer's cache the decode_attn
    kernel reads for it, here on both sides of a block edge."""
    cfg, params = model
    T_len = 1024
    bt = block_len(T_len)
    eng = SplitServeEngine(cfg, params, batch_size=3, cache_len=T_len,
                           thresholds=[1.1, 1.1])
    # (prompt length, tokens): decode steps at positions n .. n + m - 2,
    # the first token being admission's
    reqs = [(bt - 2, 5), (bt + 188, 3), (3, 2)]
    for n, m in reqs:
        eng.submit(list(range(1, n + 1)), m)
    st = eng.run()
    want = sum(min(p, T_len - 1) // bt + 1
               for n, m in reqs for p in range(n, n + m - 1))
    assert want == (1 + 1 + 2 + 2) + (2 + 2) + 1
    assert st.kv_blocks_read == want


def test_submit_rejects_what_the_cache_cannot_hold(model):
    cfg, params = model
    eng = SplitServeEngine(cfg, params, batch_size=2, cache_len=32)
    with pytest.raises(ValueError):
        eng.submit(list(range(33)), 1)
    with pytest.raises(ValueError):
        eng.submit(list(range(30)), 4)
    eng.submit(list(range(30)), 3)        # 30 + 2 decoded positions
