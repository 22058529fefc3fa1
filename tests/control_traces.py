"""Seeded arrival traces + a virtual-clock serving simulator for the
control plane's adaptive-vs-static claims (``tests/test_control.py``).

The adaptive-vs-static comparison needs load shapes a wall-clock
micro-benchmark can't reproduce deterministically (bursts, diurnal
swings, an adversarial tenant flood), so this module separates the two
halves the same way the control plane itself does:

* **Traces** — :func:`make_trace` draws arrival offsets, priority
  classes and tenant ids from ``np.random.default_rng(seed)``, so every
  run of a ``(kind, seed)`` pair replays the identical workload on any
  machine.  Rates are expressed relative to ``unit_rps`` (one worker's
  full-fill capacity), so the shapes stay meaningful when the latency
  model recalibrates.
* **Simulator** — :func:`simulate` is a discrete-event loop over a
  virtual clock: admitted requests queue, idle workers flush up to
  ``max_batch`` rows when the batch fills or the oldest request has
  waited ``max_wait_ms``, and every flush occupies its worker for the
  model-derived ``service_ms`` (the compiled step is padded to the full
  lane count, so flush cost is row-independent — exactly the
  ``MicroBatcher`` contract).  The REAL controllers from
  ``repro.control`` run against it unmodified: the admission controller
  gates arrivals (virtual clock injected), the batching controller and
  autoscaler tick on windowed sensors, and scale-down retires a worker
  only after its in-flight flush completes (the zero-drop drain,
  modeled).  No wall-clock time is read anywhere, so results are
  bit-identical across runs and machines — the tests assert behaviour,
  not scheduler noise.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

_INTERVAL_S = 0.1  # rate-profile resolution for arrival generation


def _draw_arrivals(rng, rate_fn, duration_s: float) -> np.ndarray:
    """Piecewise-Poisson arrival offsets for a time-varying rate."""
    times = []
    t = 0.0
    while t < duration_s:
        rate = max(0.0, float(rate_fn(t)))
        n = rng.poisson(rate * _INTERVAL_S)
        if n:
            times.append(t + rng.uniform(0.0, _INTERVAL_S, n))
        t += _INTERVAL_S
    if not times:
        return np.empty(0, np.float64)
    return np.sort(np.concatenate(times))


def make_trace(kind: str, *, unit_rps: float, seed: int = 0,
               duration_s: float = 60.0, classes: int = 3) -> dict:
    """One named workload: ``{"t", "klass", "tenant", "kind",
    "duration_s"}`` arrays sorted by arrival time.

    ``bursty``      — base load of 1.0 unit with 4.0-unit bursts for 6 s
                      of every 24 s period (the SLO-compliance arm).
    ``diurnal``     — sinusoidal 0.3..2.1 units over a 30 s period.
    ``adversarial`` — steady 0.8 units of priority-0/1 traffic from four
                      tenants plus a 3.0-unit priority-2 flood from one
                      tenant ("mallory") — the shed-fairness arm.
    """
    rng = np.random.default_rng(seed)
    u = float(unit_rps)
    if kind == "bursty":
        def rate(t):
            return 4.0 * u if (t % 24.0) < 6.0 else 1.0 * u
        t = _draw_arrivals(rng, rate, duration_s)
        klass = rng.choice(classes, size=t.size, p=_class_weights(classes))
        tenant = np.array([f"t{i}" for i in rng.integers(0, 4, t.size)])
    elif kind == "diurnal":
        def rate(t):
            return u * (1.2 + 0.9 * np.sin(2.0 * np.pi * t / 30.0))
        t = _draw_arrivals(rng, rate, duration_s)
        klass = rng.choice(classes, size=t.size, p=_class_weights(classes))
        tenant = np.array([f"t{i}" for i in rng.integers(0, 4, t.size)])
    elif kind == "adversarial":
        tb = _draw_arrivals(rng, lambda t: 0.8 * u, duration_s)
        kb = rng.choice([0, 1], size=tb.size, p=[0.6, 0.4])
        nb = np.array([f"t{i}" for i in rng.integers(0, 4, tb.size)])
        tf = _draw_arrivals(rng, lambda t: 3.0 * u, duration_s)
        kf = np.full(tf.size, classes - 1)
        nf = np.full(tf.size, "mallory")
        order = np.argsort(np.concatenate([tb, tf]), kind="stable")
        t = np.concatenate([tb, tf])[order]
        klass = np.concatenate([kb, kf])[order]
        tenant = np.concatenate([nb, nf])[order]
    else:
        raise ValueError(f"unknown trace kind {kind!r}")
    return {"kind": kind, "duration_s": float(duration_s), "t": t,
            "klass": klass.astype(np.int64), "tenant": tenant}


def _class_weights(classes: int) -> list:
    if classes == 1:
        return [1.0]
    # a small high-priority head over a best-effort tail
    w = [0.2] + [0.8 / (classes - 1)] * (classes - 1)
    return [x / sum(w) for x in w]


class _VirtualClock:
    __slots__ = ("now",)

    def __init__(self):
        self.now = 0.0

    def __call__(self) -> float:
        return self.now


def simulate(
    trace: dict,
    *,
    lanes: int,
    service_ms: float,
    slo_ms: float,
    workers: int = 1,
    max_batch: Optional[int] = None,
    max_wait_ms: float = 0.25,
    max_queue: int = 64,
    classes: int = 1,
    tenant_rate: Optional[float] = None,
    batching=None,
    autoscaler=None,
    tick_s: float = 0.5,
    spawn_delay_s: float = 1.0,
) -> dict:
    """Run one arm over a trace; returns the scoreboard.

    ``batching`` / ``autoscaler`` are pre-built ``repro.control``
    controllers (None = static knobs / fixed fleet).  Admission always
    runs — ``classes=1`` is exactly the flat gateway limit.  Goodput
    counts completions within ``slo_ms`` that finish inside the trace
    window; ``worker_s`` integrates the fleet size over time, so
    efficiency (goodput per worker-second) is comparable across arms.
    """
    from repro.control import AdmissionController

    clock = _VirtualClock()
    admission = AdmissionController(classes=classes, tenant_rate=tenant_rate,
                                    clock=clock)
    arr_t = trace["t"]
    arr_k = trace["klass"]
    arr_n = trace["tenant"]
    duration = trace["duration_s"]
    mb = int(max_batch if max_batch is not None else lanes)
    mb = min(max(1, mb), lanes)
    wait_ms = float(max_wait_ms)
    svc_s = service_ms / 1e3

    queue: list = []          # (arrival_t, klass) FIFO
    busy: list = [0.0] * int(workers)  # per-worker busy-until (<= t -> idle)
    retiring = 0              # scale-downs pending a drained worker
    lat_done: list = []       # (completion_t, latency_ms, klass)
    tick_lat: list = []       # latencies completing since the last tick
    n_shed = n_admitted = 0
    flushes = rows_flushed = 0
    scale_ups = scale_downs = 0
    worker_s = 0.0
    last_t = 0.0
    next_tick = tick_s
    i = 0
    n = arr_t.size
    INF = float("inf")

    def dispatch(t: float) -> None:
        nonlocal flushes, rows_flushed
        for w in range(len(busy)):
            if busy[w] > t or not queue:
                continue
            full = len(queue) >= mb
            aged = (t - queue[0][0]) * 1e3 >= wait_ms
            if not (full or aged):
                continue
            take = queue[:mb]
            del queue[:mb]
            done = t + svc_s
            busy[w] = done
            flushes += 1
            rows_flushed += len(take)
            for (a, k) in take:
                lat = (done - a) * 1e3
                lat_done.append((done, lat, k))
                tick_lat.append((done, lat))

    seen_prev = 0  # arrivals observed up to the previous tick
    while True:
        # next event: arrival, wait-deadline flush, worker free, tick.
        # Only FUTURE deadlines count — an already-aged queue head is
        # waiting on a worker, whose completion is the real next event.
        now = clock.now
        candidates = [next_tick]
        if i < n:
            candidates.append(arr_t[i])
        if queue:
            deadline = queue[0][0] + wait_ms / 1e3
            if deadline > now:
                candidates.append(deadline)
        pending = [b for b in busy if b > now and b != INF]
        if pending:
            candidates.append(min(pending))
        t = min(candidates)
        if t > duration and i >= n and not queue:
            break
        t = min(t, duration + 10.0 * svc_s)  # bounded drain after the window
        worker_s += (t - last_t) * sum(1 for b in busy if b != INF)
        last_t = t
        clock.now = t

        while i < n and arr_t[i] <= t:
            try:
                admission.admit(depth=len(queue), max_queue=max_queue,
                                priority=int(arr_k[i]), tenant=str(arr_n[i]))
                queue.append((float(arr_t[i]), int(arr_k[i])))
                n_admitted += 1
            except Exception:
                n_shed += 1
            i += 1
        # zero-drop drain: an idle worker leaves instead of taking more
        # work (its in-flight flush, if any, already completed)
        while retiring > 0 and sum(1 for b in busy if b != INF) > 1:
            idle = next((w for w in range(len(busy))
                         if busy[w] <= t and busy[w] != INF), None)
            if idle is None:
                break
            busy[idle] = INF
            retiring -= 1
        while INF in busy:
            busy.remove(INF)
            scale_downs += 1
        dispatch(t)

        if t >= next_tick:
            next_tick += tick_s
            done_now = [l for (c, l) in tick_lat if c <= t]
            tick_lat = [(c, l) for (c, l) in tick_lat if c > t]
            p95 = float(np.percentile(done_now, 95)) if done_now else 0.0
            fill = (rows_flushed / (flushes * mb)) if flushes else 0.0
            seen = n_admitted + n_shed
            arrival_rps = (seen - seen_prev) / tick_s  # last-tick window
            seen_prev = seen
            if batching is not None:
                d = batching.decide(p95_ms=p95, fill=fill, depth=len(queue),
                                    arrival_rps=arrival_rps,
                                    max_batch=mb, max_wait_ms=wait_ms)
                if d["knobs"]:
                    mb = min(max(1, int(d["knobs"].get("max_batch", mb))),
                             lanes)
                    wait_ms = max(0.0,
                                  float(d["knobs"].get("max_wait_ms",
                                                       wait_ms)))
            if autoscaler is not None and t <= duration:
                live = len(busy) - retiring
                a = autoscaler.decide(arrival_rps=arrival_rps,
                                      workers=max(1, live),
                                      queue_depth=len(queue),
                                      max_queue=max_queue)
                if a["delta"] > 0:
                    # model compile warm-up: the new worker joins late
                    busy.append(t + spawn_delay_s)
                    scale_ups += 1
                elif a["delta"] < 0 and live > 1:
                    retiring += 1
            dispatch(t)

    lats = np.array([l for (c, l, k) in lat_done]) if lat_done else \
        np.empty(0)
    in_window = [(c, l, k) for (c, l, k) in lat_done if c <= duration]
    good = sum(1 for (c, l, k) in in_window if l <= slo_ms)
    shed_by_class = admission.describe()["shed_by_class"]
    return {
        "arrivals": int(n),
        "admitted": int(n_admitted),
        "shed": int(n_shed),
        "completed": len(lat_done),
        "good": int(good),
        "goodput_rps": good / duration,
        "p95_ms": float(np.percentile(lats, 95)) if lats.size else 0.0,
        "mean_fill": (rows_flushed / (flushes * lanes)) if flushes else 0.0,
        "flushes": int(flushes),
        "worker_s": worker_s,
        "scale_ups": int(scale_ups),
        "scale_downs": int(scale_downs),
        "shed_by_class": {k: int(v) for k, v in shed_by_class.items()},
        "rate_limited": int(admission.describe()["rate_limited"]),
        "final_max_batch": mb,
        "final_max_wait_ms": wait_ms,
        "batching_actions": batching.actions if batching is not None else 0,
    }
