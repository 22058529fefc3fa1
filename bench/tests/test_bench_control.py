"""The control, the reference computed in bfloat16 (the precision below
the configurations' float32) put in the program's place, fails each
cell's comparison; here at a size a test run holds.  The readings at the
cells' own sizes on the chip are in PERF.md."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import traffic  # noqa: E402

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def _records(mix: dict, seed: int) -> dict:
    """What the load generators would have recorded for a short run: 64
    streams of 48 samples, or 256 answers over the stored windows."""
    if mix["groups"][0]["op"] == "step":
        key0 = np.repeat(np.arange(64), 48)
        key1 = np.tile(np.arange(48), 64)
    else:
        n = len(traffic.group_lengths(mix, seed)[0])
        key0 = traffic.window_id(0, np.arange(256) * 7 % n)
        key1 = np.zeros(256, np.int64)
    op = traffic.OPS[mix["groups"][0]["op"]]
    return {"op": np.full(len(key0), op, np.int8), "key0": key0, "key1": key1}


@pytest.mark.parametrize("name", sorted(c["name"] for c in SPEC["workloads"]))
def test_control_fails_the_cell(name):
    cell = run.load_cell(name)
    cell["seed"] = seed = 2**31 + 3
    cfg = cell["config"]
    import reference

    params = reference.make_params(seed, cfg["input_features"], cfg["depth"])
    rec = _records(cell["traffic"], seed)
    rec["value"] = run.reference_answers(cell, rec, params)("highest")
    program = run.compare(cell, rec, params, control=False)
    control = run.compare(cell, rec, params, control=True)
    limits = cell["limits"]
    assert all(program[k] <= limits[k] for k in limits), program
    assert any(control[k] > limits[k] for k in limits), (control, limits)
