"""Fault-tolerance coverage that needs real processes, run out-of-process.

Two smokes the in-process suites cannot express:

  * a REAL ``SIGKILL`` mid-run (tests/ckpt_kill_worker.py) — no Python
    exception, no cleanup handlers — followed by an in-process resume
    that must be bit-identical to an uninterrupted run;
  * a 2-process ``jax.distributed`` mesh where both hosts exhaust the
    multi-host retry budget and demote to local devices
    (tests/dropout_worker.py), checking the demotion ladder end-to-end
    on an actual multi-host mesh.
"""
import dataclasses
import os
import pathlib
import signal
import socket
import subprocess
import sys

import numpy as np
import pytest

from repro.core.online import TIMING_FIELDS

REPO = pathlib.Path(__file__).resolve().parents[1]


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    return env


@pytest.mark.timeout(600)
def test_sigkill_then_resume_bit_identical(tmp_path):
    ckpt_dir = str(tmp_path / "ck")
    worker = str(REPO / "tests" / "ckpt_kill_worker.py")
    r = subprocess.run([sys.executable, worker, ckpt_dir], env=_env(),
                       cwd=REPO, capture_output=True, text=True,
                       timeout=560)
    # the worker must die from the signal, not exit on its own
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stdout[-500:])
    assert "SIGKILL at tick" in r.stdout

    # resume in THIS process from whatever checkpoints survived the kill
    import importlib.util
    spec = importlib.util.spec_from_file_location("ckpt_kill_worker", worker)
    w = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(w)
    KILL_TICK, T, build, trace = w.KILL_TICK, w.T, w.build, w.trace
    Q, A = trace()
    r_clean = build().run_arrays(Q, A)
    o = build()
    tail = o.resume(ckpt_dir, Q, A)
    pos = T - len(tail)
    assert 0 < pos <= KILL_TICK          # a pre-kill boundary checkpoint
    for ra, rb in zip(r_clean[pos:], tail):
        da, db = dataclasses.asdict(ra), dataclasses.asdict(rb)
        for k in TIMING_FIELDS:
            da.pop(k), db.pop(k)
        assert da == db, (ra.tick,
                          {k: (da[k], db[k]) for k in da if da[k] != db[k]})
    o_ref = build()
    o_ref.run_arrays(Q, A)
    for p, p2 in zip(o.pops, o_ref.pops):
        np.testing.assert_array_equal(p._inc_place, p2._inc_place)
        np.testing.assert_array_equal(p._inc_energy, p2._inc_energy)


@pytest.mark.timeout(600)
def test_two_process_mesh_dropout_demotes_and_agrees():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = _env()
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    worker = str(REPO / "tests" / "dropout_worker.py")
    procs = [subprocess.Popen(
                [sys.executable, worker, str(i), "2", str(port)],
                env=env, cwd=REPO, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True)
             for i in range(2)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=560)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out))
    for i, (rc, out) in enumerate(outs):
        tail = "\n".join(out.splitlines()[-20:])
        assert rc == 0, f"dropout worker {i} failed:\n{tail}"
        assert f"proc {i}:" in out and "post-demotion exact" in out
