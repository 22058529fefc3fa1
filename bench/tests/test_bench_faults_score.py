"""The backfill cell's comparison fails a broken score program: half of
each flush left out with the mean of the rest given to it, and one answer
altered where it is produced."""
import jax.numpy as jnp
import numpy as np
import pytest

from _faults import run_cell


def test_half_of_the_batch_left_out(monkeypatch):
    from repro.engine.base import Engine

    real = Engine.score_masked_with

    def half(self, params, batch):
        scores = np.array(real(self, params, batch))
        n = int((np.asarray(batch["lengths"]) > 1).sum())
        if n >= 2:
            scores[n // 2:n] = scores[:n // 2].mean()
        return jnp.asarray(scores)

    monkeypatch.setattr(Engine, "score_masked_with", half)
    got = run_cell(monkeypatch, "f64d6.backfill", "tiny_backfill.json", 44)
    assert got["correct"] is False
    assert got["checks"]["excess_gap_mean"]["value"] > got["checks"]["excess_gap_mean"]["limit"]


def test_one_answer_altered(monkeypatch):
    from repro.engine.base import Engine

    real = Engine.score_masked_with
    calls = [0]

    def altered(self, params, batch):
        scores = np.array(real(self, params, batch))
        calls[0] += 1
        if calls[0] == 20:
            scores[0] *= 1.01
        return jnp.asarray(scores)

    monkeypatch.setattr(Engine, "score_masked_with", altered)
    got = run_cell(monkeypatch, "f64d6.backfill", "tiny_backfill.json", 45)
    assert calls[0] > 20
    assert got["correct"] is False
    assert got["checks"]["excess_gap_max"]["value"] == pytest.approx(0.01, rel=0.01)
