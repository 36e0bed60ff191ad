"""A new configuration, traffic generator, traffic mix and per-layer
metric need only new files and new entries: a temporary copy of the
benchmark gains all four and runs them, and no file that was there
changes."""
import hashlib
import json
import shutil

from bench.common import ROOT
from bench.run import run_cell
from bench.tests.conftest import POP_TINY


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_config_traffic_and_metric_by_name_only(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)

    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "fin-paper-1m.json").read_text())
    cfg["name"] = "fin-extra"
    cfg["n_users"] = 3000
    (b / "configs" / "fin-extra.json").write_text(json.dumps(cfg))
    (b / "generators" / "uniform_quality.py").write_text(
        "from bench.common import rng_for\n"
        "\n"
        "class Uniform:\n"
        "    def __init__(self, p, n_users, seed):\n"
        "        self.rng, self.p, self.U = rng_for(seed, 1), p, n_users\n"
        "\n"
        "    def next_channel(self):\n"
        "        q = self.rng.uniform(self.p['lo'], self.p['hi'], self.U)\n"
        "        return q, None, []\n"
        "\n"
        "def make(params, *, n_users, edge_nodes, seed):\n"
        "    return Uniform(params, n_users, seed)\n")
    (b / "traffic" / "uniform.json").write_text(json.dumps({
        "generator": "uniform_quality", "lo": 0.3, "hi": 1.0,
        "warmup_ticks": 2, "sample_users": 128}))
    (b / "layer_metrics" / "solves_per_tick.py").write_text(
        "def read(ctx):\n"
        "    if ctx.get('kind') != 'population' or not ctx['ticks']:\n"
        "        return None\n"
        "    return ctx['counters']['solves'] / ctx['ticks']\n")
    bj = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bj["configs"].append({"name": "fin-extra", "source": "x",
                          "file": "bench/configs/fin-extra.json",
                          "reduced": ["n_users"], "why": "test"})
    bj["workloads"].append({"name": "pop-extra", "config": "fin-extra",
                            "traffic": "uniform", "chips": 1,
                            "why": "test"})
    bj["per_layer"].append({"name": "solves_per_tick", "unit": "1/tick",
                            "better": "lower", "source": "program_counter",
                            "layer": "cohort-state table",
                            "moves": "user_ticks_per_s",
                            "workloads": ["pop-extra"]})
    for m in bj["end_to_end"]:
        if m["name"] == "user_ticks_per_s":
            m["workloads"].append("pop-extra")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))
    edited = {p for p in before if str(p) == "BENCHMARK.json"}

    over = dict(POP_TINY, users=3000, sample=128)
    r0 = run_cell(["--workload", "pop-extra", "--seed", "5", "--seconds",
                   "1", "--trace", "0"], require_chip=False, root=tmp_path,
                  overrides=over)
    assert r0["correct"]
    assert set(r0["metrics"]) == {"user_ticks_per_s", "setup_s"}
    r1 = run_cell(["--workload", "pop-extra", "--seed", "5", "--seconds",
                   "1", "--trace", "1"], require_chip=False, root=tmp_path,
                  overrides=over)
    assert r1["correct"]
    assert "solves_per_tick" in r1["metrics"]
    assert r1["metrics"]["solves_per_tick"]["unit"] == "1/tick"
    after = _digests(tmp_path)
    changed = {p for p in before if before[p] != after.get(p)}
    assert changed == edited
