"""The session pool's per-layer reader ``pool.overlap_pct``: the share of
the window's pool steps that were chained, from the program's counters."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

from repro.gateway.telemetry import Telemetry  # noqa: E402


def _overlap(ctx):
    return run.load_reader("pool.overlap_pct")(ctx)


def _ctx(c0: dict, c1: dict) -> dict:
    return {"stats0": {"counters": c0}, "stats1": {"counters": c1}}


def test_overlap_reads_the_counters_growth_over_the_window():
    ctx = _ctx({"pool.steps": 100.0, "pool.steps_chained": 10.0},
               {"pool.steps": 300.0, "pool.steps_chained": 190.0})
    assert _overlap(ctx) == pytest.approx(90.0)
    # a window that opens before the first step has no counters yet
    assert _overlap(_ctx({}, {"pool.steps": 40.0, "pool.steps_chained": 0.0})) == 0.0


def test_overlap_reads_nothing_without_steps_or_the_counter():
    assert _overlap(_ctx({"pool.steps": 50.0, "pool.steps_chained": 5.0},
                         {"pool.steps": 50.0, "pool.steps_chained": 5.0})) is None
    assert _overlap(_ctx({}, {})) is None
    # a program that keeps no such counter (the parent of the change)
    assert _overlap(_ctx({"pool.steps": 10.0}, {"pool.steps": 90.0})) is None


@pytest.mark.parametrize("chained", [[True] * 7, [False] * 7,
                                     [False, True, True, False, True]])
def test_overlap_of_the_programs_counters_cannot_pass_100(chained):
    """Each pool step counts once in ``pool.steps`` and at most once in
    ``pool.steps_chained``."""
    tel = Telemetry()
    tel.record_pool_step(3, 8)
    before = tel.stats()
    for flag in chained:
        tel.record_pool_step(3, 8, flag)
    value = _overlap({"stats0": before, "stats1": tel.stats()})
    assert 0.0 <= value <= 100.0
    assert value == pytest.approx(100.0 * sum(chained) / len(chained))
