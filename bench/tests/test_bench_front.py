"""The four-chip cell ``f64d6.stream-x4`` on the CPU: a ``WorkerFront`` of
four workers and one load generator a worker, at a tiny size; and the
front's per-layer reader ``front.worker_skew_pct`` on a recorded
``WorkerFront.stats()`` pair."""
import copy
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import _faults
from _faults import run_cell, use_mix

DATA = Path(__file__).resolve().parent / "data"


def test_cpu_rehearsal_of_the_four_worker_front(monkeypatch, capsys):
    """8 streams a worker, 4 workers of 8 slots each: streams the kernel
    places on a full worker are admitted on another, so every worker ends
    full; every answer is correct, nothing compiles inside the window, and
    the workers' stepped samples are the STEP answers."""
    import run

    seen = {}
    real = run.measure

    def keep(*args, **kw):
        seen["got"] = got = real(*args, **kw)
        return got

    monkeypatch.setattr(run, "measure", keep)
    use_mix(monkeypatch, "tiny_stream.json")
    for key in ("JAX_COMPILATION_CACHE_DIR",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        monkeypatch.setenv(key, "")
    rc = run.main(["--workload", "f64d6.stream-x4", "--seed", str(2**32 + 17),
                   "--seconds", "2", "--trace", "0", "--rehearse"])
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    result = json.loads(out.out.strip().splitlines()[-1])
    assert result["correct"] is True, out.err[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["count"] == 4
    got = seen["got"]
    assert got["compiles0"] == got["compiles1"]
    workers = sorted(got["stats1"]["per_worker"], key=lambda w: w["index"])
    assert [w["index"] for w in workers] == [0, 1, 2, 3]
    assert [w["active_streams"] for w in workers] == [8, 8, 8, 8]
    assert all(w["device"]["count"] == 1 for w in workers)
    rec = got["answers"]
    steps = int(((rec["op"] == 0) & (rec["phase"] == 1)).sum())
    ctx = {"stats0": got["stats0"], "stats1": got["stats_end"]}
    import readings

    assert sum(readings.worker_counter_deltas(ctx, "pool.stream_steps")) == steps
    # every stream's samples answered in order, once each
    for s in np.unique(rec["key0"]):
        idx = np.sort(rec["key1"][rec["key0"] == s])
        assert np.array_equal(idx, np.arange(len(idx)))


@pytest.mark.parametrize("fault,gap", [
    ("frozen_worker_gateway", None), ("altering_worker_gateway", 0.01)])
def test_a_fault_in_the_workers_is_not_correct(monkeypatch, fault, gap):
    """A pool step that returns its state unchanged, and one answer in
    each worker altered by 1% where it is produced, each planted in the
    front's workers."""
    import replica

    monkeypatch.setattr(replica, "worker_gateway", getattr(_faults, fault))
    got = run_cell(monkeypatch, "f64d6.stream-x4", "tiny_stream.json", 2**31 + 46)
    assert got["correct"] is False
    check = got["checks"]["excess_gap_max"]
    assert check["value"] > check["limit"]
    if gap is not None:
        assert check["value"] == pytest.approx(gap, rel=0.01)


def _skew(ctx):
    import run

    return run.load_reader("front.worker_skew_pct")(ctx)


def test_worker_skew_on_recorded_front_stats():
    """``front_stats.json``: ``WorkerFront.stats()`` at the open and the
    close of a rehearsal's window, each worker's counters."""
    recorded = json.loads((DATA / "front_stats.json").read_text())
    steps = [w1["counters"]["pool.stream_steps"] - w0["counters"]["pool.stream_steps"]
             for w0, w1 in zip(recorded["stats0"]["per_worker"],
                               recorded["stats1"]["per_worker"])]
    mean = sum(steps) / 4
    assert _skew(recorded) == pytest.approx(100 * (max(steps) - mean) / mean)

    even = copy.deepcopy(recorded)
    for w0, w1 in zip(even["stats0"]["per_worker"], even["stats1"]["per_worker"]):
        w1["counters"]["pool.stream_steps"] = w0["counters"]["pool.stream_steps"] + 500
    assert _skew(even) == 0.0

    uneven = copy.deepcopy(even)
    uneven["stats1"]["per_worker"][2]["counters"]["pool.stream_steps"] += 200
    # deltas 500, 500, 700, 500: mean 550, the busiest 150 / 550 above it
    assert _skew(uneven) == pytest.approx(100 * 150 / 550)


def test_worker_skew_reads_nothing_without_a_front():
    one_chip = {"stats0": {"counters": {"pool.stream_steps": 0.0}},
                "stats1": {"counters": {"pool.stream_steps": 900.0}}}
    assert _skew(one_chip) is None


def test_four_chip_cell_without_chips_no_result():
    root = Path(__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         "f64d6.stream-x4", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs 4 TPU chips" in out.stderr
