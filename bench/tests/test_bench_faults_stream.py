"""A stream cell's comparison fails a broken pool step: one that returns
its state unchanged, and one answer altered where it is produced."""
import pytest

from _faults import run_cell


def test_step_that_returns_its_state_unchanged(monkeypatch):
    from repro.engine.base import Engine

    real = Engine._masked_stream_step

    def frozen(self, params, x_t, state, mask):
        y_t, _ = real(self, params, x_t, state, mask)
        return y_t, state

    monkeypatch.setattr(Engine, "_masked_stream_step", frozen)
    got = run_cell(monkeypatch, "f32d2.stream", "tiny_stream.json", 42)
    assert got["correct"] is False
    assert got["checks"]["excess_gap_max"]["value"] > got["checks"]["excess_gap_max"]["limit"]


def test_one_answer_altered(monkeypatch):
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

    monkeypatch.setattr(SessionPool, "step", altered)
    got = run_cell(monkeypatch, "f32d2.stream", "tiny_stream.json", 43)
    assert calls[0] > 40
    assert got["correct"] is False
    assert got["checks"]["excess_gap_max"]["value"] == pytest.approx(0.01, rel=0.01)
