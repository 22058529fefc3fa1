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


# Faults planted in the workers of a front.  A test puts one of these in
# the place of ``replica.worker_gateway``; each worker process imports it
# from this module, breaks the program there and builds the gateway.

def frozen_worker_gateway(*args, **kw):
    """A worker whose pool step returns its state unchanged."""
    import replica
    from repro.engine.base import Engine

    real = Engine._masked_stream_step

    def frozen(self, params, x_t, state, mask):
        y_t, _ = real(self, params, x_t, state, mask)
        return y_t, state

    Engine._masked_stream_step = frozen
    return replica.worker_gateway(*args, **kw)


def altering_worker_gateway(*args, **kw):
    """A worker that alters one answer of its 40th pool step by 1%."""
    import replica
    from repro.gateway.pool import SessionPool

    real = SessionPool.step
    calls = [0]

    def altered(self, inputs):
        out = real(self, inputs)
        calls[0] += 1
        if calls[0] == 40:
            sid = next(iter(out))
            out[sid] *= 1.01
        return out

    SessionPool.step = altered
    return replica.worker_gateway(*args, **kw)
