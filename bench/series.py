"""Seeded synthetic telemetry: per-feature sinusoids with noise, and
injected anomalies (spike, level shift, white-noise segment).

A copy of the program's sinusoid and anomaly generator, kept with the
benchmark so that the inputs do not move when the program's own data code
changes.  Numpy only: the load generators that import it never import JAX.

Two shapes of input come out of it:

* ``window(seed, index, length, features, anomaly_rate)``: one stored
  window for one-shot scoring, drawn from ``(seed, index)``.
* ``stream_chunk(seed, stream, chunk, features, anomaly_rate)``: samples
  ``[chunk * CHUNK, (chunk + 1) * CHUNK)`` of one resident stream.  The
  stream's frequencies, phases and amplitudes are drawn once from
  ``(seed, stream)``, so consecutive chunks join without a seam; noise and
  anomalies are drawn per chunk.
"""
from __future__ import annotations

import numpy as np

#: samples per stream chunk
CHUNK = 64

_WINDOW, _STREAM_SHAPE, _STREAM_CHUNK = 1, 2, 3


def _rng(*key: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(k) for k in key]))


def _inject_anomaly(rng: np.random.Generator, x: np.ndarray) -> None:
    """One anomaly of a random kind in a contiguous stretch of ``x`` (T, F),
    in place."""
    t, f = x.shape
    kind = rng.integers(0, 3)
    w0 = rng.integers(0, max(1, t - t // 4))
    w1 = min(t, w0 + rng.integers(max(2, t // 8), max(3, t // 3)))
    feats = rng.choice(f, size=max(1, f // 4), replace=False)
    if kind == 0:    # spike
        x[w0:w1, feats] += rng.uniform(2.0, 4.0)
    elif kind == 1:  # level shift
        x[w0:, feats] += rng.uniform(1.0, 2.0)
    else:            # frequency break: a white-noise segment
        x[w0:w1, feats] = rng.standard_normal(
            (int(w1 - w0), len(feats))).astype(np.float32)


def _sinusoids(rng: np.random.Generator, f: int):
    freq = rng.uniform(0.05, 0.45, size=f)
    phase = rng.uniform(0, 2 * np.pi, size=f)
    amp = rng.uniform(0.5, 1.0, size=f)
    return freq, phase, amp


def window(seed: int, index: int, length: int, features: int,
           anomaly_rate: float) -> np.ndarray:
    """Stored window ``index`` of the seed: (length, features) float32."""
    rng = _rng(seed, _WINDOW, index)
    freq, phase, amp = _sinusoids(rng, features)
    steps = np.arange(length)[:, None]
    x = amp * np.sin(2 * np.pi * freq * steps + phase)
    x = (x + 0.05 * rng.standard_normal((length, features))).astype(np.float32)
    if rng.uniform() < anomaly_rate:
        _inject_anomaly(rng, x)
    return x


def stream_chunk(seed: int, stream: int, chunk: int, features: int,
                 anomaly_rate: float) -> np.ndarray:
    """Chunk ``chunk`` of resident stream ``stream``: (CHUNK, features)."""
    freq, phase, amp = _sinusoids(_rng(seed, _STREAM_SHAPE, stream), features)
    rng = _rng(seed, _STREAM_CHUNK, stream, chunk)
    steps = np.arange(chunk * CHUNK, (chunk + 1) * CHUNK)[:, None]
    x = amp * np.sin(2 * np.pi * freq * steps + phase)
    x = (x + 0.05 * rng.standard_normal((CHUNK, features))).astype(np.float32)
    if rng.uniform() < anomaly_rate:
        _inject_anomaly(rng, x)
    return x


def stream_samples(seed: int, stream: int, count: int, features: int,
                   anomaly_rate: float) -> np.ndarray:
    """The first ``count`` samples of a stream: (count, features)."""
    chunks = -(-count // CHUNK)
    return np.concatenate(
        [stream_chunk(seed, stream, k, features, anomaly_rate)
         for k in range(max(chunks, 1))])[:count]
