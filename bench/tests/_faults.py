"""Shared by the fault tests: one in-process rehearsal run of a cell at a
tiny size, with whatever the test has broken underneath."""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))


def use_mix(monkeypatch, mix_file: str) -> None:
    """Every cell of this process runs the mix in ``data/<mix_file>``."""
    import traffic

    mix = json.loads((DATA / mix_file).read_text())
    monkeypatch.setattr(traffic, "load", lambda name: mix)


def run_cell(monkeypatch, workload: str, mix_file: str, seed: int) -> dict:
    import run

    use_mix(monkeypatch, mix_file)

    # the run points the compile cache at the checkout; keep this process's
    # environment as it was for the tests that follow
    for key in ("JAX_COMPILATION_CACHE_DIR",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        monkeypatch.setenv(key, "")
    args = argparse.Namespace(workload=workload, seed=seed, seconds=2.0,
                              trace=0, rehearse=True, control=False)
    return run.run(args)
