"""The traffic generator: the same seed gives the same stream, every seed
gives the same amount of work, and a mix file alone sets what each
connection sends and when."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import series  # noqa: E402
import traffic  # noqa: E402

SEED = 2**31 + 12345


def test_windows_and_stream_chunks_repeat_for_a_seed():
    a = series.window(SEED, 4, 37, 64, 0.1)
    np.testing.assert_array_equal(a, series.window(SEED, 4, 37, 64, 0.1))
    assert not np.array_equal(a, series.window(SEED + 1, 4, 37, 64, 0.1))
    c = series.stream_chunk(SEED, 9, 2, 32, 0.1)
    np.testing.assert_array_equal(c, series.stream_chunk(SEED, 9, 2, 32, 0.1))
    assert a.dtype == c.dtype == np.float32


def test_stream_samples_join_chunks():
    n = 2 * series.CHUNK + 5
    xs = series.stream_samples(SEED, 3, n, 32, 0.0)
    assert xs.shape == (n, 32)
    np.testing.assert_array_equal(xs[series.CHUNK:2 * series.CHUNK],
                                  series.stream_chunk(SEED, 3, 1, 32, 0.0))
    # one sinusoid per feature across the seam: no jump beyond the noise
    seam = np.abs(xs[series.CHUNK] - xs[series.CHUNK - 1]).max()
    assert seam < 2 * np.pi * 0.45 + 0.5


def test_score_lengths_same_work_for_every_seed():
    spec = traffic.load("backfill")["groups"][0]["windows"]
    a = traffic.score_lengths(spec, SEED)
    b = traffic.score_lengths(spec, 1)
    np.testing.assert_array_equal(a, traffic.score_lengths(spec, SEED))
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(np.sort(a), np.sort(b))
    assert a.min() >= 8 and a.max() <= 512 and len(a) == 2048
    assert 80 <= a.mean() <= 95
    assert 0.03 <= np.mean(a > 256) <= 0.05
    buckets = {next(t for t in (8, 16, 32, 64, 128, 256, 512) if n <= t) for n in a}
    assert len(buckets) == 7


def test_generator_specs_split_the_load():
    stream = traffic.load("stream")
    plan = traffic.connections(stream, chips=1)
    assert [c["stream"] for c in plan] == list(range(512))
    assert {c["op"] for c in plan} == {"step"} and {c["k"] for c in plan} == {1}
    four = traffic.connections(stream, chips=4)
    assert sorted(c["stream"] for c in four) == list(range(2048))
    score = traffic.connections(traffic.load("backfill"), chips=1)
    assert [c["index"] for c in score] == list(range(8))
    assert {c["conns"] for c in score} == {8}
    assert {c["send"]["in_flight"] for c in score} == {32}
    assert json.dumps(plan) == json.dumps(traffic.connections(stream, chips=1))


def _mix(*groups):
    return {"gateway": {}, "groups": list(groups)}


def test_open_loop_schedule_is_data():
    grp = {"op": "step", "connections_per_chip": 4, "samples_per_frame": 16,
           "anomaly_rate": 0.0,
           "send": {"loop": "open", "period_ms": 1000, "start": "spread",
                    "bursts": [{"at_s": 2.0, "for_s": 1.0, "factor": 4}]}}
    plan = traffic.connections(_mix(grp), chips=1)
    assert [c["offset_s"] for c in plan] == [0.0, 0.25, 0.5, 0.75]
    assert {c["k"] for c in plan} == {16}
    aligned = dict(grp, send=dict(grp["send"], start="aligned"))
    assert {c["offset_s"] for c in traffic.connections(_mix(aligned), 1)} == {0.0}
    send = grp["send"]
    assert traffic.period_s(send, 1.9) == 1.0
    assert traffic.period_s(send, 2.5) == 0.25
    assert traffic.period_s(send, 3.0) == 1.0


def test_groups_share_one_numbering_and_carry_their_meta():
    step = {"op": "step", "connections_per_chip": 3, "anomaly_rate": 0.1,
            "send": {"loop": "closed", "in_flight": 1}}
    score = {"op": "score", "connections_per_chip": 5, "anomaly_rate": 0.2,
             "send": {"loop": "closed", "in_flight": 2}, "meta": {"priority": 1},
             "tenants": {"count": 3, "zipf": 1.0},
             "windows": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                         "min": 2, "max": 16, "distinct": 10}}
    plan = traffic.connections(_mix(step, score, step), chips=1)
    assert [c.get("stream") for c in plan if c["op"] == "step"] == list(range(6))
    assert [c["group"] for c in plan] == [0] * 3 + [1] * 5 + [2] * 3
    scored = [c["meta"] for c in plan if c["op"] == "score"]
    assert {m["priority"] for m in scored} == {1}
    assert [m["tenant"] for m in scored] == ["t0", "t0", "t0", "t1", "t2"]
    assert set(traffic.group_lengths(_mix(step, score), 1)) == {1}
    assert traffic.window_id(1, 7) == traffic.WINDOW_STRIDE + 7


@pytest.mark.parametrize("count,zipf,n", [(4, 1.2, 100), (3, 0.0, 7), (1, 2.0, 5)])
def test_tenant_ranks_follow_zipf(count, zipf, n):
    ranks = traffic.tenant_ranks(count, zipf, n)
    assert len(ranks) == n and ranks == sorted(ranks)
    sizes = np.bincount(ranks, minlength=count)
    want = 1.0 / np.arange(1, count + 1) ** zipf
    assert np.abs(sizes - want / want.sum() * n).max() < 1.0


@pytest.mark.parametrize("bad", [
    {"op": "replay"}, {"send": {"loop": "poisson"}},
    {"send": {"loop": "open", "period_ms": 10, "start": "random"}}])
def test_an_unknown_op_or_loop_is_refused(bad):
    grp = dict({"op": "step", "connections_per_chip": 1, "anomaly_rate": 0.0,
                "send": {"loop": "closed", "in_flight": 1}}, **bad)
    with pytest.raises((ValueError, KeyError)):
        traffic.connections(_mix(grp), chips=1)
