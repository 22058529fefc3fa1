"""The paper's LSTM autoencoders (``bench/configs/lstm-ae-*.json``).

A resident stream sends one telemetry sample a STEP and is answered its
running reconstruction error; a stored window is answered its score.  The
inputs come from ``bench/series.py``, the plain forward from
``bench/reference.py`` and the work from ``bench/flops.py``; this file only
maps the configuration's keys onto them.
"""
from __future__ import annotations

import numpy as np

import flops
import series

CHUNK = series.CHUNK


def make_params(seed: int, config: dict):
    from reference import make_params as make

    return make(seed, config["input_features"], config["depth"])


def open_gateway(config: dict, params, knobs: dict):
    """An ``AnomalyService`` on the default schedule serving ``params``."""
    from repro.engine import AnomalyService

    svc = AnomalyService(config["arch"])
    svc.recalibrate(params=params)
    return svc.open_gateway(**knobs)


def warm_payloads(config: dict, mix: dict, seed: int) -> list:
    """One zero window per score bucket the mix's windows fall in: each
    compiles its (lanes, bucket, F) program."""
    import traffic
    from repro.gateway.queue import bucket_for

    buckets = {bucket_for(int(t))
               for lengths in traffic.group_lengths(mix, seed).values()
               for t in lengths}
    return [np.zeros((tb, config["input_features"]), np.float32)
            for tb in sorted(buckets)]


def stream_chunk(seed: int, stream: int, chunk: int, config: dict,
                 anomaly_rate: float) -> np.ndarray:
    return series.stream_chunk(seed, stream, chunk, config["input_features"],
                               anomaly_rate)


def stream_samples(seed: int, stream: int, count: int, config: dict,
                   anomaly_rate: float) -> np.ndarray:
    return series.stream_samples(seed, stream, count, config["input_features"],
                                 anomaly_rate)


def window(seed: int, index: int, length: int, config: dict,
           anomaly_rate: float) -> np.ndarray:
    return series.window(seed, index, length, config["input_features"],
                         anomaly_rate)


def step_frame(samples: np.ndarray) -> tuple:
    """``k`` consecutive samples as one STEP frame."""
    return {"t": len(samples)}, np.ascontiguousarray(samples, "<f4").tobytes()


def score_frame(x: np.ndarray) -> tuple:
    """One window as one SCORE frame."""
    t, f = x.shape
    return {"n": 1, "t": t, "f": f}, np.ascontiguousarray(x, "<f4").tobytes()


def reference_answers(params, samples: list, windows: list,
                      precision: str) -> tuple:
    import reference

    running = reference.running_errors(params, samples, precision) if samples else []
    scores = (reference.window_scores(params, windows, precision) if windows
              else np.zeros(0, np.float64))
    return running, scores


def useful_work(config: dict, kind: str, answers: dict) -> tuple:
    """An LSTM's work a row-timestep does not depend on its position."""
    if kind == "step":
        rows = requests = len(answers["position"])
    else:
        rows, requests = int(np.sum(answers["length"])), len(answers["length"])
    return flops.useful_work(config["input_features"], config["depth"], kind,
                             rows, requests)
