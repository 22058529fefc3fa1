"""The ``lstm_ae`` family adapter gives the lstm_ae cells exactly what
the harness gave them before family adapters: ``data/golden_lstm_ae.json``
was recorded from that harness (``reference``, ``series``, ``flops``,
``traffic`` and ``loadgen`` called directly), for both configurations,
at seed 0: the weights, the first 64 samples of streams 0 and 511, stored
windows 0 and 2047 of the ``backfill`` mix, the reference's answers to
them at ``highest`` and ``default``, their useful work, each mix's
warm-up windows and the load generator's first three frames on the first
and the last connection of each mix.  Arrays are compared by the SHA-256
of their bytes, answers by value, bit for bit."""
import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import families  # noqa: E402
import traffic  # noqa: E402

GOLDEN = json.loads((Path(__file__).parent / "data" / "golden_lstm_ae.json").read_text())
CONFIGS = sorted(GOLDEN["configs"])
SEED, RATE = GOLDEN["seed"], GOLDEN["anomaly_rate"]
FAMILY = families.load("lstm_ae")


def digest(a) -> str:
    a = np.ascontiguousarray(a)
    return hashlib.sha256(a.tobytes()).hexdigest() + f":{a.dtype.str}:{list(a.shape)}"


def _config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _inputs(cfg: dict) -> tuple:
    samples = [FAMILY.stream_samples(SEED, s, 64, cfg, RATE) for s in GOLDEN["streams"]]
    windows = [FAMILY.window(SEED, w, n, cfg, RATE)
               for w, n in zip(GOLDEN["windows"], GOLDEN["window_lengths"])]
    return samples, windows


def test_window_lengths_are_the_recorded_ones():
    lengths = traffic.group_lengths(traffic.load("backfill"), SEED)[0]
    assert [int(lengths[w]) for w in GOLDEN["windows"]] == GOLDEN["window_lengths"]


@pytest.mark.parametrize("name", CONFIGS)
def test_weights(name):
    params = FAMILY.make_params(SEED, _config(name))
    leaves = [np.asarray(layer[k]) for layer in params["layers"]
              for k in ("wx", "wh", "b")]
    assert [digest(x) for x in leaves] == GOLDEN["configs"][name]["weights"]


@pytest.mark.parametrize("name", CONFIGS)
def test_stream_samples_and_windows(name):
    samples, windows = _inputs(_config(name))
    assert [digest(x) for x in samples] == GOLDEN["configs"][name]["samples"]
    assert [digest(x) for x in windows] == GOLDEN["configs"][name]["window_data"]


@pytest.mark.parametrize("precision", ["highest", "default"])
@pytest.mark.parametrize("name", CONFIGS)
def test_reference_answers(name, precision):
    cfg = _config(name)
    samples, windows = _inputs(cfg)
    params = FAMILY.make_params(SEED, cfg)
    running, scores = FAMILY.reference_answers(params, samples, windows, precision)
    want = GOLDEN["configs"][name]
    assert [r.tolist() for r in running] == want[f"running_{precision}"]
    assert scores.tolist() == want[f"scores_{precision}"]


@pytest.mark.parametrize("name", CONFIGS)
def test_useful_work(name):
    cfg = _config(name)
    want = GOLDEN["configs"][name]["useful_work"]
    position = np.tile(np.arange(64), len(GOLDEN["streams"]))
    assert list(FAMILY.useful_work(cfg, "step", {"position": position})) == want["step"]
    length = np.array(GOLDEN["window_lengths"])
    assert list(FAMILY.useful_work(cfg, "score", {"length": length})) == want["score"]


@pytest.mark.parametrize("name", CONFIGS)
def test_warm_up(name):
    cfg = _config(name)
    for mix, shapes in GOLDEN["configs"][name]["warm"].items():
        got = FAMILY.warm_payloads(cfg, traffic.load(mix), SEED)
        assert [list(x.shape) for x in got] == shapes
        assert all(x.dtype == np.float32 and not x.any() for x in got)


@pytest.mark.parametrize("name", CONFIGS)
def test_load_generator_frames(name):
    import loadgen

    cfg = _config(name)
    for mix, want in GOLDEN["configs"][name]["frames"].items():
        gen = loadgen.LoadGen({"mix": traffic.load(mix), "seed": SEED, "chips": 1,
                               "config": cfg, "family_file": str(families.path("lstm_ae")),
                               "host": "", "port": 0})
        got = []
        for i in (0, len(gen.plan) - 1):
            conn = loadgen._Conn(None, gen.plan[i])
            for _ in range(3):
                opcode, payload, keys = gen._next_request(conn)
                conn.sent += 1
                got.append([opcode, hashlib.sha256(payload).hexdigest(),
                            [list(map(int, k)) for k in keys]])
        assert got == want
