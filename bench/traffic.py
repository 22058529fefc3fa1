"""The one traffic generator's plan: a mix's data file
(``bench/traffic/<mix>.json``) and a seed give what every connection
sends and when.  Numpy and the standard library only: the load generator
(``bench/loadgen.py``) imports it and never imports JAX.

A mix file holds parameters only:

* ``gateway``: keyword arguments of ``AnomalyService.open_gateway`` for the
  gateway of each chip (``capacity``, ``max_batch``, ``max_wait_ms``,
  ``max_queue``, ``max_seq_len``, ...);
* ``control`` (optional): fields of ``repro.control.ControlConfig``; with
  it the gateway runs under its control plane (``enable_control``);
* ``groups``: one entry per kind of client, each with

  - ``op``: ``step`` (a resident stream: STEP frames of
    ``samples_per_frame`` consecutive samples of its own series, default
    1) or ``score`` (stored windows, one per SCORE frame);
  - ``connections_per_chip``;
  - ``send``: ``{"loop": "closed", "in_flight": n}``, the next frame goes
    out when an answer comes, ``n`` outstanding per connection; or
    ``{"loop": "open", "period_ms": p, "start": "spread" | "aligned",
    "bursts": [{"at_s", "for_s", "factor"}, ...]}``, every connection
    sends a frame each ``p`` ms whatever is outstanding: ``spread``
    staggers the connections evenly over one period, ``aligned`` ticks
    them together, and from ``at_s`` into the window for ``for_s`` the
    period is divided by ``factor``;
  - ``windows`` (``score`` only): ``dist`` ``lognormal`` with ``median``,
    ``sigma``, clipped to ``[min, max]``, over ``distinct`` stored windows;
  - ``meta`` (optional): fields added to every frame's meta, such as
    ``priority``;
  - ``tenants`` (optional): ``{"count": k, "zipf": s}`` gives connection
    ``i`` the ``tenant`` ``t<r>`` in its frames' meta, the connections
    shared over ranks ``r`` in proportion to ``1 / (r + 1) ** s``;
  - ``anomaly_rate``: share of series and windows with an injected anomaly.

Every seed gets the same work: window lengths are fixed quantiles of the
length distribution, send times do not depend on the seed, and the seed
only orders the windows and draws the data.
"""
from __future__ import annotations

import json
import statistics
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"

#: op codes in the load generator's records
OPS = {"step": 0, "score": 1}
#: a stored window's id is ``group * WINDOW_STRIDE + index``
WINDOW_STRIDE = 1 << 20


def load(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def score_lengths(spec: dict, seed: int) -> np.ndarray:
    """Lengths of the stored windows, in the seed's order: the
    ``(k + 0.5) / distinct`` quantiles of the clipped length distribution,
    permuted by the seed."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    n = int(spec["distinct"])
    if not 0 < n <= WINDOW_STRIDE:
        raise ValueError(f"distinct must lie in [1, {WINDOW_STRIDE}]")
    normal = statistics.NormalDist()
    z = np.array([normal.inv_cdf((k + 0.5) / n) for k in range(n)])
    raw = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    lengths = np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)
    order = np.random.default_rng(np.random.SeedSequence([seed, 7])).permutation(n)
    return lengths[order]


def group_lengths(mix: dict, seed: int) -> dict:
    """-> {group index: stored window lengths} of the mix's score groups."""
    return {g: score_lengths(grp["windows"], seed)
            for g, grp in enumerate(mix["groups"]) if grp["op"] == "score"}


def window_id(group: int, index: int) -> int:
    return group * WINDOW_STRIDE + index


def tenant_ranks(count: int, zipf: float, n: int) -> list:
    """Tenant rank of each of ``n`` connections: rank ``r`` gets a share
    of them in proportion to ``1 / (r + 1) ** zipf`` (largest remainders),
    ranks in order."""
    weight = 1.0 / np.arange(1, count + 1) ** float(zipf)
    want = weight / weight.sum() * n
    got = np.floor(want).astype(np.int64)
    for r in np.argsort(-(want - got), kind="stable")[:n - int(got.sum())]:
        got[r] += 1
    return [r for r in range(count) for _ in range(int(got[r]))]


def connections(mix: dict, chips: int) -> list:
    """One entry per connection, in order: its group and op, its index in
    the group, the stream it carries (``step``), the frame's samples
    (``k``), its send schedule and the meta added to its frames."""
    out = []
    stream = 0
    for g, grp in enumerate(mix["groups"]):
        op = grp["op"]
        if op not in OPS:
            raise ValueError(f"unknown op {op!r} in group {g}")
        send = grp["send"]
        if send["loop"] not in ("closed", "open"):
            raise ValueError(f"unknown loop {send['loop']!r} in group {g}")
        n = int(grp["connections_per_chip"]) * chips
        tenants = grp.get("tenants")
        ranks = (tenant_ranks(int(tenants["count"]), tenants["zipf"], n)
                 if tenants else None)
        for i in range(n):
            meta = dict(grp.get("meta", {}))
            if ranks is not None:
                meta["tenant"] = f"t{ranks[i]}"
            entry = {"group": g, "op": op, "index": i, "conns": n,
                     "k": int(grp.get("samples_per_frame", 1)) if op == "step" else 1,
                     "send": send, "meta": meta,
                     "anomaly_rate": float(grp["anomaly_rate"])}
            if op == "step":
                entry["stream"] = stream
                stream += 1
            if send["loop"] == "open":
                period = float(send["period_ms"]) * 1e-3
                entry["offset_s"] = {"spread": period * i / n,
                                     "aligned": 0.0}[send.get("start", "spread")]
            out.append(entry)
    return out


def period_s(send: dict, since_t0: float) -> float:
    """An open-loop connection's period at ``since_t0`` seconds into the
    window (shorter inside a burst)."""
    period = float(send["period_ms"]) * 1e-3
    for burst in send.get("bursts", ()):
        if burst["at_s"] <= since_t0 < burst["at_s"] + burst["for_s"]:
            return period / float(burst["factor"])
    return period
