"""``bench/run.py`` end to end on the CPU at a tiny size: it answers
correctly and reports no metric; without ``--rehearse`` it refuses the
CPU; and a directory that holds only the benchmark cannot run it."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np

from _faults import use_mix

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _run(args, cwd=ROOT, root=ROOT, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"), *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=timeout)


def _rehearse(monkeypatch, capsys, workload, mix_file, seed, seconds="2"):
    """``bench/run.py``'s main in this process, on a tiny mix."""
    import run

    use_mix(monkeypatch, mix_file)
    for key in ("JAX_COMPILATION_CACHE_DIR",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        monkeypatch.setenv(key, "")
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                   seconds, "--trace", "0", "--rehearse"])
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def test_cpu_rehearsal_reports_no_metric(monkeypatch, capsys):
    result, err = _rehearse(monkeypatch, capsys, "f64d6.backfill",
                            "tiny_backfill.json", 2**31 + 99)
    assert result["correct"] is True, err[-3000:]
    assert result["metrics"] == {}
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "checks"
    assert "check excess_gap_max" in err.strip().splitlines()[-2]


def test_cpu_rehearsal_of_a_mixed_open_loop_mix(monkeypatch, capsys):
    """Open and closed loops, STEP frames of three samples, stored windows
    with a priority and Zipf tenants, under the control plane: every
    answer of every group is compared, and the end-to-end readers find
    their numbers in what was recorded."""
    import run

    seen = {}
    real = run.compare

    def keep(cell, rec, params, control):
        seen["rec"] = rec
        return real(cell, rec, params, control)

    monkeypatch.setattr(run, "compare", keep)
    result, err = _rehearse(monkeypatch, capsys, "f64d6.stream", "tiny_mixed.json",
                            2**33 + 5, seconds="3")
    assert result["correct"] is True, err[-3000:]
    rec = seen["rec"]
    assert set(np.unique(rec["op"])) == {0, 1}
    window = rec["phase"] == 1
    # four open-loop streams (period 0.2 s, halved for 0.5 s), two closed
    # ones, three window connections every 0.1 s
    open_steps = window & (rec["op"] == 0) & (rec["key0"] < 4) & rec["first"]
    assert 4 * 16 <= open_steps.sum() <= 4 * 18
    assert ((rec["key1"][window & (rec["op"] == 0) & (rec["key0"] < 4)] - 1) % 3
            ).tolist().count(0) == open_steps.sum()
    assert (window & (rec["op"] == 1)).sum() == 3 * 30
    ctx = {"answers": rec, "t0": rec["t_send"][window].min(), "window_s": 3.0}
    ctx["t1"] = ctx["t0"] + 3.0
    for name in ("steps_per_s", "step_p95_ms", "windows_per_s", "window_p95_ms"):
        assert run.load_reader(name)(ctx) > 0


def test_no_accelerator_no_result():
    out = _run(["--workload", "f64d6.backfill", "--seed", "1", "--seconds", "1",
                "--trace", "0"])
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(["--workload", "f64d6.backfill", "--seed", "1", "--seconds", "1",
                "--trace", "0"], cwd=tmp_path, root=tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
