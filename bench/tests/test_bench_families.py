"""A model family is added with new files and entries alone: a
configuration naming ``"family": "counting_stub"`` (an adapter in
``data/families/``) is routed to that adapter by ``load_cell``, the load
generator's frames, the reference and the useful work, and no file of
the harness names it."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))

import families  # noqa: E402
import run  # noqa: E402

STUB = "counting_stub"


@pytest.fixture
def stub_cell(monkeypatch, tmp_path):
    """A checkout whose BENCHMARK.json holds one stub configuration and one
    cell of it on the ``stream`` mix."""
    monkeypatch.setattr(families, "DIRS", families.DIRS + [DATA / "families"])
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "limits").mkdir()
    config = {"name": "stub-w3", "family": STUB, "width": 3, "scale": 0.5,
              "matmul_precision": "default", "control_precision": "low"}
    (tmp_path / "bench" / "configs" / "stub-w3.json").write_text(json.dumps(config))
    (tmp_path / "bench" / "limits" / "stub.stream.json").write_text(
        json.dumps({"excess_gap_max": 0.0}))
    spec = {"configs": [{"name": "stub-w3", "file": "bench/configs/stub-w3.json"}],
            "workloads": [{"name": "stub.stream", "config": "stub-w3",
                           "traffic": "stream", "chips": 1}],
            "end_to_end": [], "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(run, "ROOT", tmp_path)
    monkeypatch.setattr(run, "BENCH", tmp_path / "bench")
    cell = run.load_cell("stub.stream")
    cell["seed"] = 11
    return cell


def test_load_cell_finds_the_adapter(stub_cell):
    assert Path(stub_cell["family_file"]) == DATA / "families" / f"{STUB}.py"
    assert stub_cell["family"].CHUNK == 4
    assert stub_cell["config"]["width"] == 3


def test_the_load_generator_sends_the_adapters_frames(stub_cell):
    import loadgen
    from bp1 import wire

    gen = loadgen.LoadGen({"mix": stub_cell["traffic"], "seed": 11, "chips": 1,
                           "config": stub_cell["config"],
                           "family_file": stub_cell["family_file"],
                           "host": "", "port": 0})
    conn = loadgen._Conn(None, gen.plan[5])
    for t in range(6):  # across a chunk's seam
        opcode, payload, keys = gen._next_request(conn)
        conn.sent += 1
        meta, data = wire.split_payload(payload)
        assert opcode == wire.OP_STEP and meta == {"tokens": 1}
        assert np.frombuffer(data, "<i4").tolist() == [t + 5 + 11 % 5] * 3
        assert keys == [(5, t)]


def test_reference_and_useful_work_come_from_the_adapter(stub_cell):
    params = stub_cell["family"].make_params(11, stub_cell["config"])
    rec = {"op": np.zeros(6, np.int8), "key0": np.array([0, 0, 0, 2, 2, 2]),
           "key1": np.array([0, 1, 2, 0, 1, 2]), "phase": np.ones(6, np.int8)}
    got = run.reference_answers(stub_cell, rec, params)("highest")
    # stream s, sample t holds 3 * (t + s + 1) (seed 11); scale 0.5 + 2
    want = [2.5 * 3 * np.mean([k + s + 1 for k in range(t + 1)])
            for s, t in zip(rec["key0"], rec["key1"])]
    np.testing.assert_allclose(got, want)
    work = run.useful_work(stub_cell, rec, np.ones(6, bool))
    assert work["flops"] == 2 * (1 + 2 + 3) and work["bytes"] == 4 * 6
    assert work["requests"] == work["row_timesteps"] == 6


def test_no_harness_file_names_the_stub():
    harness = [p for p in BENCH.rglob("*")
               if p.is_file() and "tests" not in p.relative_to(BENCH).parts
               and "__pycache__" not in p.parts]
    assert harness
    assert not [p for p in harness if STUB in p.read_text(errors="replace")]
    assert STUB not in (BENCH.parent / "BENCHMARK.json").read_text()
