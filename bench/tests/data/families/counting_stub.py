"""A stand-in family for the harness's tests: a stream's answer is the
running mean of its samples' sums times the weight ``scale``; a window's
answer is the mean of its samples' sums times ``scale``.  Its work grows
with the position of a step in its stream, as attention over a cache
would, so that the positions the harness hands it show in the count."""
import numpy as np

CHUNK = 4


def make_params(seed, config):
    return {"scale": float(config["scale"]) + seed % 3}


def open_gateway(config, params, knobs):
    raise NotImplementedError("the stub serves nothing")


def warm_payloads(config, mix, seed):
    return [np.zeros((config["width"],), np.int32)]


def stream_chunk(seed, stream, chunk, config, anomaly_rate):
    start = chunk * CHUNK
    base = np.arange(start, start + CHUNK)[:, None] + stream + seed % 5
    return np.repeat(base, config["width"], axis=1).astype(np.int32)


def stream_samples(seed, stream, count, config, anomaly_rate):
    chunks = [stream_chunk(seed, stream, c, config, anomaly_rate)
              for c in range(-(-count // CHUNK))]
    return np.concatenate(chunks)[:count]


def window(seed, index, length, config, anomaly_rate):
    return np.full((length, config["width"]), index % 7, np.int32)


def step_frame(samples):
    return {"tokens": len(samples)}, np.ascontiguousarray(samples, "<i4").tobytes()


def score_frame(x):
    return {"tokens": len(x)}, np.ascontiguousarray(x, "<i4").tobytes()


def reference_answers(params, samples, windows, precision):
    running = [np.cumsum(s.sum(axis=1)) / np.arange(1, len(s) + 1) * params["scale"]
               for s in samples]
    scores = np.array([w.sum(axis=1).mean() * params["scale"] for w in windows])
    return running, scores


def useful_work(config, kind, answers):
    if kind == "step":
        return int(np.sum(answers["position"] + 1)), 4 * len(answers["position"])
    return int(np.sum(answers["length"])), 0
