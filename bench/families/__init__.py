"""Model-family adapters: what the harness needs to know of one family of
models, found by name as the metric readers are.

A configuration names its family under ``"family"`` (``lstm_ae`` where it
names none); the adapter is the file ``<family>.py`` in one of ``DIRS``.
It defines:

* ``make_params(seed, config)``: the weights of a run, made on the
  default device from the seed;
* ``open_gateway(config, params, knobs)``: the system under test through
  the program's normal path, its gateway opened with the mix's knobs;
* ``warm_payloads(config, mix, seed)``: the one-shot payloads set-up
  scores so that every shape the mix uses is compiled before the window;
* ``CHUNK``, ``stream_chunk(seed, stream, chunk, config, anomaly_rate)``,
  ``stream_samples(seed, stream, count, config, anomaly_rate)`` and
  ``window(seed, index, length, config, anomaly_rate)``: the inputs a
  resident stream and a stored window carry;
* ``step_frame(samples)`` and ``score_frame(window)``: a STEP and a SCORE
  frame's meta and data, as ``(dict, bytes)``;
* ``reference_answers(params, samples, windows, precision)``: a plain
  forward that imports nothing of the program, at ``precision``
  (``"highest"``, the configuration's ``matmul_precision`` or its
  ``control_precision``); returns the running answer after each sample of
  each stream and the answer to each window;
* ``useful_work(config, kind, answers)`` -> ``(flops, bytes)``: the work
  the answers of one kind (``step`` or ``score``) needed.  ``answers``
  holds ``position`` (a ``step`` answer's sample index in its stream) or
  ``length`` (a ``score`` answer's window length).

The inputs, frames and work are numpy and the standard library only: the
load generator (``bench/loadgen.py``) imports the adapter and must never
import JAX, so an adapter imports JAX and the program inside the
functions that need them.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

#: where adapters are looked for, in order
DIRS = [Path(__file__).resolve().parent]

#: the family of a configuration that names none
DEFAULT = "lstm_ae"


def path(name: str) -> Path:
    """The adapter file of family ``name``."""
    for directory in DIRS:
        candidate = directory / f"{name}.py"
        if candidate.is_file():
            return candidate
    raise ValueError(f"no adapter for model family {name!r} in {DIRS}")


def load_file(file: str | Path):
    """The adapter module in ``file`` (loaded once per process)."""
    file = Path(file).resolve()
    key = f"bench_family_{file.stem}"
    module = sys.modules.get(key)
    if module is None:
        spec = importlib.util.spec_from_file_location(key, file)
        module = importlib.util.module_from_spec(spec)
        sys.modules[key] = module
        spec.loader.exec_module(module)
    return module


def load(name: str):
    return load_file(path(name))
