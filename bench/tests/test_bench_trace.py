"""The reduction from a profiler trace to device metrics."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import devtrace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _trace(ops, modules, window_s=1e-5):
    return {"window_s": window_s,
            "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}}


def test_overlapping_ops_count_once():
    ops = [["fusion.1", 0, 1000], ["copy.2", 500, 1000],   # overlap: 0..1500
           ["fusion.3", 3000, 500],                        # gap 1500..3000
           ["fusion.4", 3200, 100]]                        # inside the last
    modules = [["jit__pool_step(12)", 0, 1500], ["jit__score_masked(3)", 3000, 500]]
    got = devtrace.reduce(_trace(ops, modules))
    assert got["busy_s"] == pytest.approx(2000e-9)
    assert got["program_s"] == {"_pool_step": pytest.approx(1500e-9),
                                "_score_masked": pytest.approx(500e-9)}
    assert got["idle_gaps"] == [["after _pool_step", pytest.approx(1500e-9)]]
    assert got["device_ops"][:2] == [["_pool_step:fusion.1", pytest.approx(1000e-9)],
                                     ["_pool_step:copy.2", pytest.approx(1000e-9)]]


def test_busy_is_averaged_over_chips():
    one = {"ops": [["a", 0, 1000]], "modules": [["jit_f(1)", 0, 1000]]}
    two = {"ops": [["a", 0, 3000]], "modules": [["jit_f(1)", 0, 3000]]}
    got = devtrace.reduce({"window_s": 1.0,
                           "devices": {"/device:TPU:0": one, "/device:TPU:1": two}})
    assert got["devices"] == 2
    assert got["busy_s"] == pytest.approx(2000e-9)
    assert got["program_s"]["f"] == pytest.approx(4000e-9)


def test_no_device_ops_reads_nothing():
    assert devtrace.reduce({"window_s": 1.0, "devices": {}}) == {}


def test_program_names():
    assert devtrace.program_name("jit__pool_step(1234)") == "_pool_step"
    assert devtrace.program_name("jit_foo") == "foo"
    assert devtrace.program_name("fusion") == "fusion"


def test_recorded_v5e_trace():
    """Two slices recorded on a TPU v5 lite: eight programs of a stream
    step (the pool step and the three small programs that read its errors
    back), and the start of one masked score, whose while loop spans the
    ops of its body."""
    recorded = json.loads((DATA / "trace_v5e.json").read_text())
    stream = devtrace.reduce(recorded["stream"])
    ops = recorded["stream"]["devices"]["/device:TPU:0"]["ops"]
    assert stream["busy_s"] == pytest.approx(sum(o[2] for o in ops) * 1e-9)
    assert stream["busy_s"] < stream["window_s"]
    assert set(stream["program_s"]) == {"_pool_step", "maximum",
                                        "convert_element_type", "true_divide"}
    assert stream["device_ops"][0][0].startswith("_pool_step:%")
    assert stream["idle_gaps"][0][0] == "after true_divide"
    score = devtrace.reduce(recorded["backfill"])
    ops = recorded["backfill"]["devices"]["/device:TPU:0"]["ops"]
    assert score["busy_s"] < sum(o[2] for o in ops) * 1e-9  # nested ops
    assert score["busy_s"] <= score["window_s"] + 1e-12
    assert score["device_ops"][0][0] == "_score_masked:%while.1"
