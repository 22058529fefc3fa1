#!/usr/bin/env python3
"""The program's host spans in a profiler trace, and the device's idle
gaps named by them.

The program writes each stage of its served path into the profiler's
trace as a host annotation named ``<layer>.<stage>`` (``wire.frame``,
``pool.launch``, ``batch.resolve``, ``pump.tick``, ...), nested as the
code nests them.  ``read_host(dir)`` reads those of the newest
``.xplane.pb`` under ``dir`` into a plain structure, one list per thread
line of the host plane:

    {"<line name>": [[name, start_ns, dur_ns], ...], ...}

Reduction works on plain structures only (this one and the device part
``bench/devtrace.py`` reads), so it is checked on small recorded slices:

* ``self_s``: per span name, the summed self time, a span's duration less
  the part its child spans cover;
* ``frames``: the count of ``wire.frame`` spans (one a request);
* ``label_gaps``: every idle gap of the device, named ``<label> after
  <program>``.  The label is the host span that was innermost for most of
  the gap on the serving thread (the line with most spans), or ``loop``
  where that thread was in no span (the event loop, socket waits).

Run as a script, it runs one cell as ``bench/run.py --trace 1`` does, and
prints one JSON line of what the host spans show: the idle split by
label, self time per span, transport self time a request, the share of
the pool step's device executions that began inside a host pool step's
``pool.launch`` .. ``pool.readback``, and the rates inside and outside
the traced stretch.  ``--slice PATH`` also writes a few milliseconds of
the trace, host and device, as a plain structure for the tests
(``bench/tests/data/hostspans_v5e.json``):

    python bench/hostspans.py --workload f32d2.stream --seed 1 --seconds 8
"""
from __future__ import annotations

import bisect
import glob
import os

import devtrace

#: name prefixes of the program's spans: the layers that write them
SPAN_PREFIXES = ("wire.", "pool.", "batch.", "pump.")

#: a gap's label where the serving thread was in no span
LOOP = "loop"

#: milliseconds of trace in a slice (``--slice``): two pool steps
SLICE_MS = 5.5


def read_host(directory: str) -> dict:
    """The program's spans on each thread line of the host plane of the
    newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    host: dict = {}
    if not paths:
        return host
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                     for ev in line.events if ev.name.startswith(SPAN_PREFIXES)]
            if spans:
                host.setdefault(line.name, []).extend(spans)
    return host


def _nested(spans: list) -> list:
    """Spans sorted so that a parent comes before its children."""
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def self_s(host: dict) -> dict:
    """Per span name, the summed duration less what child spans cover, in
    seconds."""
    out: dict = {}
    for spans in host.values():
        stack: list = []        # [end, name, self_ns] of the open spans
        for name, start, dur in _nested(spans) + [[None, float("inf"), 0.0]]:
            while stack and stack[-1][0] <= start:
                end, done, own = stack.pop()
                out[done] = out.get(done, 0.0) + own * 1e-9
            if name is None:
                break
            end = start + dur
            if stack:
                # a child's time is not its parent's own
                stack[-1][2] -= min(end, stack[-1][0]) - start
            stack.append([end, name, dur])
    return out


def frames(host: dict) -> int:
    return sum(s[0] == "wire.frame" for spans in host.values() for s in spans)


def _innermost(spans: list) -> list:
    """``[start, end, name]`` segments of one line: the innermost open span
    over each stretch (stretches in no span are left out)."""
    out: list = []
    stack: list = []            # [end, name] of the open spans
    t = 0.0
    for name, start, dur in _nested(spans) + [[None, float("inf"), 0.0]]:
        while stack and stack[-1][0] <= start:
            end, top = stack.pop()
            if end > t:
                out.append([t, end, top])
                t = end
        if name is None:
            break
        if stack and start > t:
            out.append([t, start, stack[-1][1]])
        stack.append([start + dur, name])
        t = start
    return out


def serving_line(host: dict) -> list:
    """The spans of the thread that serves: the line with most of them."""
    return max(host.values(), key=len) if host else []


def gap_intervals(trace: dict) -> list:
    """Every idle gap of every device in a plain device trace
    (``bench/devtrace.py``): ``[start_ns, end_ns, program before it]``."""
    gaps: list = []
    for entry in trace["devices"].values():
        modules = sorted((s, s + d, devtrace.program_name(n))
                         for n, s, d in entry["modules"])
        starts = [m[0] for m in modules]
        intervals = []
        for _, start, dur in entry["ops"]:
            i = bisect.bisect_right(starts, start) - 1
            owner = modules[i][2] if i >= 0 and start < modules[i][1] else "?"
            intervals.append((start, start + dur, owner))
        merged = devtrace._union(intervals)
        gaps.extend([prev[1], nxt[0], prev[2]] for prev, nxt in zip(merged, merged[1:]))
    return gaps


def label_gaps(trace: dict, host: dict) -> list:
    """Every idle gap as ``[label, seconds]`` (see the module docstring);
    with no host span the names stay ``after <program>``."""
    segments = _innermost(serving_line(host))
    begins = [s[0] for s in segments]
    out = []
    for start, end, program in gap_intervals(trace):
        if not segments:
            out.append([f"after {program}", (end - start) * 1e-9])
            continue
        cover = {LOOP: end - start}
        i = max(0, bisect.bisect_right(begins, start) - 1)
        while i < len(segments) and segments[i][0] < end:
            s, e, name = segments[i]
            part = min(e, end) - max(s, start)
            if part > 0:
                cover[name] = cover.get(name, 0.0) + part
                cover[LOOP] -= part
            i += 1
        label = max(cover, key=cover.get)
        out.append([f"{label} after {program}", (end - start) * 1e-9])
    return out


def idle_split(gaps: list) -> dict:
    """Idle seconds per label (the part of a gap's name before `` after``)."""
    out: dict = {}
    for name, seconds in gaps:
        label = name.split(" after ", 1)[0]
        out[label] = out.get(label, 0.0) + seconds
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def share_inside(trace: dict, host: dict, program: str, first: str,
                 last: str) -> float | None:
    """Share of ``program``'s device executions that began between the
    start of a host span ``first`` and the end of the next ``last`` on the
    same line (None when there is neither)."""
    windows = []
    for spans in host.values():
        opened = None
        for name, start, dur in _nested(spans):
            if name == first:
                opened = start
            elif name == last and opened is not None:
                windows.append((opened, start + dur))
                opened = None
    windows.sort()
    begins = [w[0] for w in windows]
    runs = [s for entry in trace["devices"].values()
            for n, s, _ in entry["modules"]
            if devtrace.program_name(n) == program]
    if not runs or not windows:
        return None
    inside = 0
    for s in runs:
        i = bisect.bisect_right(begins, s) - 1
        inside += i >= 0 and s <= windows[i][1]
    return inside / len(runs)


# -- as a script: one traced run of a cell ------------------------------------

def _rates(answers: dict, t0: float, t1: float, tr0: float, tr1: float) -> dict:
    """Answers a second inside the traced stretch and in the rest of the
    window."""
    t = answers["t_recv"][answers["phase"] == 1]
    inside = ((t >= tr0) & (t < tr1)).sum()
    outside = ((t >= t0) & (t < t1)).sum() - inside
    return {"traced": float(inside / (tr1 - tr0)),
            "untraced": float(outside / ((t1 - t0) - (tr1 - tr0)))}


def _slice(trace: dict, host: dict, ms: float) -> dict:
    """``ms`` milliseconds of the trace, host and device, from the first
    request (``wire.frame``) or flush (``batch.flush``) on the serving
    line: the events wholly inside, times from the slice's start, device
    ops by their HLO instruction's name."""
    t0 = min(s for n, s, _ in serving_line(host)
             if n in ("wire.frame", "batch.flush"))
    t1 = t0 + ms * 1e6

    def keep(events, name=lambda n: n):
        return [[name(n), s - t0, d] for n, s, d in events
                if t0 <= s and s + d <= t1]

    return {"window_s": ms * 1e-3,
            "devices": {p: {"ops": keep(e["ops"], devtrace.op_name),
                            "modules": keep(e["modules"])}
                        for p, e in trace["devices"].items()},
            "host": {line: keep(spans) for line, spans in host.items()
                     if keep(spans)}}


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import sys
    import tempfile

    import run
    from readings import stage_mean_ms

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on any backend (the trace then holds no device)")
    ap.add_argument("--slice", help="write a slice of the trace to this file")
    args = ap.parse_args(argv)
    args.trace, args.control = 1, False

    cell = run.load_cell(args.workload)
    cell["seed"] = args.seed
    run.set_up_environment()
    workdir = tempfile.mkdtemp(prefix="bench-")
    os.makedirs(os.path.join(workdir, "trace"))
    try:
        system = run.OneChipSystem(cell, args.seed, args.rehearse)
        try:
            got = run.measure(args, cell, system, workdir)
            host = read_host(os.path.join(workdir, "trace"))
        finally:
            system.stop()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    trace = got["trace"]
    gaps = label_gaps(trace, host) if trace["devices"] else []
    own = self_s(host)
    n_frames = frames(host)
    wire_s = sum(s for name, s in own.items() if name.startswith("wire."))
    reduced = devtrace.reduce(trace) if trace["devices"] else {}
    out = {
        "device": system.device,
        "busy_s": reduced.get("busy_s"), "window_s": trace["window_s"],
        "idle_split_s": idle_split(gaps),
        "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:10],
        "self_s": dict(sorted(own.items(), key=lambda kv: -kv[1])),
        "frames": n_frames,
        "transport_self_ms": wire_s * 1e3 / n_frames if n_frames else None,
        "pool_step_inside_launch_readback": share_inside(
            trace, host, "_pool_step", "pool.launch", "pool.readback"),
        "stage_mean_ms": {name: stage_mean_ms(got, name)
                          for name in got["stats1"].get("histograms", {})},
        "rates": _rates(got["answers"], got["t0"], got["t1"], got["tr0"],
                        got["tr1"]),
    }
    if args.slice and trace["devices"]:
        with open(args.slice, "w") as f:
            json.dump(_slice(trace, host, SLICE_MS), f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
