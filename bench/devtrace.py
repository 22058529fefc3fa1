"""Profiler trace capture and its reduction to device metrics.

Capture: ``Capture(dir)`` starts ``jax.profiler`` tracing (no Python
tracer) and ``stop()`` ends it; the ``.xplane.pb`` it writes is read back
with ``jax.profiler.ProfileData`` into a plain structure:

    {"window_s": float,
     "devices": {"<plane name>": {"ops": [[name, start_ns, dur_ns], ...],
                                  "modules": [[name, start_ns, dur_ns], ...]}}}

``ops`` are the events of a device plane's ``XLA Ops`` line (the
operations that ran on the device), ``modules`` those of its
``XLA Modules`` line (one event per execution of a compiled program).  A
plane is a device when its name starts with ``/device:`` and it has one
of those lines; the host's CPU plane never is.

Reduction (``reduce``) works on that structure only, so it is checked on
a small recorded trace without a chip:

* ``busy_s``: per device, the union of its op intervals (overlapping ops
  count once), averaged over devices;
* ``program_s``: per compiled program (module name without its ``jit_``
  prefix and ``(id)`` suffix), the summed duration over devices;
* ``device_ops``: the ten ops that took most time, each named
  ``<program>:<HLO instruction>`` (an op inside a loop is counted in the
  loop's time as well);
* ``idle_gaps``: the ten longest gaps between busy intervals, each named
  by the program whose op ran last before it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

_MODULE_ID = re.compile(r"\(\d+\)$")


def program_name(module: str) -> str:
    """``jit__pool_step(123)`` -> ``_pool_step``."""
    name = _MODULE_ID.sub("", module)
    return name[4:] if name.startswith("jit_") else name


class Capture:
    """One traced window in this process."""

    def __init__(self, directory: str):
        import jax

        self.directory = directory
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(directory, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def read(self, window_s: float) -> dict:
        return read_xspace(self.directory, window_s)


def read_xspace(directory: str, window_s: float) -> dict:
    """The newest ``.xplane.pb`` under ``directory`` as the plain structure."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    devices: dict = {}
    if paths:
        data = ProfileData.from_file(paths[-1])
        for plane in data.planes:
            if not plane.name.startswith("/device:"):
                continue
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines and "XLA Modules" not in lines:
                continue
            entry = {}
            for key, line_name in (("ops", "XLA Ops"), ("modules", "XLA Modules")):
                line = lines.get(line_name)
                entry[key] = [] if line is None else [
                    [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                    for ev in line.events]
            if not entry["ops"]:
                entry["ops"] = list(entry["modules"])
            devices[plane.name] = entry
    return {"window_s": float(window_s), "devices": devices}


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for start, end, name in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
                out[-1][2] = name
        else:
            out.append([start, end, name])
    return out


def op_name(event: str) -> str:
    """``%fusion.34 = f32[512,64]{...} fusion(...)`` -> ``%fusion.34``."""
    return event.split(" = ", 1)[0]


def reduce(trace: dict) -> dict:
    """Device metrics of a trace (see the module docstring).  Returns
    ``{}`` when the trace holds no device operation."""
    devices = trace["devices"]
    if not any(d["ops"] for d in devices.values()):
        return {}
    busy_total = 0.0
    program_s: dict = {}
    op_s: dict = {}
    gaps: list = []
    for entry in devices.values():
        # the program running at each instant, from the modules line
        modules = sorted((s, s + d, program_name(n)) for n, s, d in entry["modules"])
        for start, end, name in modules:
            program_s[name] = program_s.get(name, 0.0) + (end - start) * 1e-9
        starts = [m[0] for m in modules]
        intervals = []
        for name, start, dur in entry["ops"]:
            i = bisect.bisect_right(starts, start) - 1
            owner = modules[i][2] if i >= 0 and start < modules[i][1] else "?"
            key = f"{owner}:{op_name(name)}"
            op_s[key] = op_s.get(key, 0.0) + dur * 1e-9
            intervals.append((start, start + dur, owner))
        merged = _union(intervals)
        busy_total += sum(end - start for start, end, _ in merged) * 1e-9
        for prev, nxt in zip(merged, merged[1:]):
            gaps.append((f"after {prev[2]}", (nxt[0] - prev[1]) * 1e-9))
    busy_s = busy_total / len([d for d in devices.values() if d["ops"]])
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, key=lambda g: -g[1])[:10]
    return {"busy_s": busy_s, "window_s": trace["window_s"],
            "devices": len(devices), "program_s": program_s,
            "device_ops": [[n, s] for n, s in top_ops],
            "idle_gaps": [[n, s] for n, s in top_gaps]}
