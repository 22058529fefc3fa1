"""What a metric reader is handed, and helpers to read it.

Each metric, end-to-end or per-layer, is a file
``bench/metrics/<metric name>.py`` with one function
``read(ctx) -> float | None``.  ``None`` means the run holds nothing for
it to read, and the harness leaves the metric out of the line.  ``ctx``
is a dict:

* ``setup_s``: process start to the first timed request (host clock);
* ``t0``, ``t1``, ``window_s``: the measured window (host clock);
* ``answers``: every answered value, as arrays ``op`` (``traffic.OPS``),
  ``key0``, ``key1``, ``t_send``, ``t_recv``, ``value``, ``phase`` (0
  warm-up, 1 window) and ``first`` (the frame's first value), on the load
  generator's clock (``bench/loadgen.py``);
* ``stats0``, ``stats1``: the gateway's telemetry snapshots (the bp1
  ``stats`` answer) at the window's start and end; a cell served by a
  worker front has the front's (``WorkerFront.stats()``): counters summed
  and histograms merged over the workers, and each worker's own snapshot
  under ``per_worker``;
* ``work``: useful work answered in the window, ``{"requests",
  "row_timesteps", "flops", "bytes"}`` (FLOPs and bytes from the model
  family's ``useful_work``, ``bench/families/``);
* ``trace``: the reduced profiler trace of the traced part of the window
  (``bench/devtrace.py``), ``{}`` when the run was not traced or no device
  operation was seen; a front's is every worker's trace, each chip a
  device; ``trace_work`` is the useful work answered while the trace ran;
* ``peaks``: the device's entry of ``bench/peaks.json``;
* ``chips``: chips the cell runs on.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from traffic import OPS


def answered_rate(ctx: dict, op: str) -> Optional[float]:
    """Values of ``op`` answered inside the window, per second of it."""
    a = ctx["answers"]
    n = int(((a["op"] == OPS[op]) & (a["phase"] == 1) & (a["t_recv"] >= ctx["t0"])
             & (a["t_recv"] < ctx["t1"])).sum())
    return n / ctx["window_s"] if n else None


def latency_p95_ms(ctx: dict, op: str) -> Optional[float]:
    """95th percentile of send-to-answer time over every ``op`` frame sent
    inside the window (answers after the close included)."""
    a = ctx["answers"]
    sent = ((a["op"] == OPS[op]) & (a["phase"] == 1) & a["first"]
            & (a["t_send"] >= ctx["t0"]) & (a["t_send"] < ctx["t1"]))
    if not sent.any():
        return None
    return float(np.percentile((a["t_recv"][sent] - a["t_send"][sent]) * 1e3, 95))


def counter_delta(ctx: dict, name: str) -> float:
    c0 = ctx["stats0"].get("counters", {})
    c1 = ctx["stats1"].get("counters", {})
    return float(c1.get(name, 0.0)) - float(c0.get(name, 0.0))


def worker_counter_deltas(ctx: dict, name: str) -> list:
    """Growth of counter ``name`` over the window in each worker of a
    front, in worker order; ``[]`` where the cell has no front."""
    before = {w["index"]: w.get("counters", {})
              for w in ctx["stats0"].get("per_worker", ())}
    deltas = []
    for w in sorted(ctx["stats1"].get("per_worker", ()), key=lambda w: w["index"]):
        c0 = before.get(w["index"], {})
        deltas.append(float(w.get("counters", {}).get(name, 0.0))
                      - float(c0.get(name, 0.0)))
    return deltas


def stage_mean_ms(ctx: dict, name: str) -> Optional[float]:
    """Mean of a telemetry stage histogram over the window."""
    h0 = (ctx["stats0"].get("histograms") or {}).get(name) or {}
    h1 = (ctx["stats1"].get("histograms") or {}).get(name) or {}
    n = int(h1.get("count", 0)) - int(h0.get("count", 0))
    if n <= 0:
        return None
    return (float(h1.get("sum", 0.0)) - float(h0.get("sum", 0.0))) / n


def roofline_pct(ctx: dict, program: str) -> Optional[float]:
    """Least time the chip could take for the useful work answered while
    the trace ran (the larger of FLOPs over peak FLOP/s and bytes over
    peak bytes/s), over the time ``program`` ran on the device in the
    trace, in percent."""
    trace, peaks, work = ctx["trace"], ctx["peaks"], ctx.get("trace_work")
    if not trace or not peaks or not work or not work["flops"]:
        return None
    kernel_s = trace["program_s"].get(program, 0.0)
    if kernel_s <= 0:
        return None
    least_s = max(work["flops"] / peaks["flops_per_s"],
                  work["bytes"] / peaks["bytes_per_s"])
    return 100.0 * least_s / kernel_s


def mfu_pct(ctx: dict) -> Optional[float]:
    """Useful model FLOPs answered in the window over window x chips x
    peak, in percent."""
    peaks, work = ctx["peaks"], ctx["work"]
    if not peaks or not work["flops"]:
        return None
    return 100.0 * work["flops"] / (ctx["window_s"] * ctx["chips"]
                                    * peaks["flops_per_s"])


def idle_pct(ctx: dict) -> Optional[float]:
    trace = ctx["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
