"""One replica of the system under test, built from a family adapter
(``bench/families/``): the benchmark's weights made on this process's
device from the seed, the gateway opened with the mix's knobs (under its
control plane where the mix has one), and every one-shot shape the mix
uses compiled.

``build`` serves the one-chip cells in ``bench/run.py``'s own process.
``worker_gateway`` is the module-level factory a ``WorkerFront`` calls in
each of its worker processes, one chip each.  Beside the gateway it sets
up what the harness reads of a worker it cannot reach otherwise:

* the worker's ``stats`` answer gains ``"bench": {"memory_peak_bytes",
  "backend_compiles"}``, the device's peak and the backend compiles of the
  process so far;
* with ``trace_dir``, ``SIGUSR1`` starts a profiler trace of the worker
  into ``<trace_dir>/worker-<pid>`` and ``SIGUSR2`` stops it; the worker
  writes ``<trace_dir>/started-<pid>`` once the trace runs and
  ``<trace_dir>/done-<pid>`` once it is written.
"""
from __future__ import annotations

import os
import signal
import threading
from typing import Callable, Optional

import families


def build(family, config: dict, mix: dict, seed: int,
          stage: Callable[[str], None] = lambda _: None) -> tuple:
    """-> ``(gateway, params)``; ``stage`` is told each step as it ends."""
    params = family.make_params(seed, config)
    stage("weights made")
    gw = family.open_gateway(config, params, mix["gateway"])
    if "control" in mix:
        from repro.control import ControlConfig, enable_control

        enable_control(gw, ControlConfig(**mix["control"]))
    stage("gateway open")
    warm = family.warm_payloads(config, mix, seed)
    for payload in warm:
        gw.score([payload])
    stage(f"{len(warm)} score buckets warm")
    return gw, params


class CompileCounter:
    """Backend compiles in this process, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.count = 0

        def listener(event: str, *_args, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listener)


def memory_peak() -> int:
    """The peak bytes in use on this process's device."""
    import jax

    mem = jax.devices()[0].memory_stats() or {}
    return int(mem.get("peak_bytes_in_use", 0))


def worker_gateway(family_file: str, config: dict, mix: dict, seed: int,
                   trace_dir: Optional[str] = None):
    """The gateway of one front worker (see the module docstring)."""
    compiles = CompileCounter()
    gw, _ = build(families.load_file(family_file), config, mix, seed)
    stats = gw.stats

    def stats_with_device() -> dict:
        out = stats()
        out["bench"] = {"memory_peak_bytes": memory_peak(),
                        "backend_compiles": compiles.count}
        return out

    gw.stats = stats_with_device
    if trace_dir is not None:
        _trace_on_signal(trace_dir)
    return gw


def _trace_on_signal(trace_dir: str) -> None:
    """SIGUSR1 / SIGUSR2 start and stop a trace of this process.  The
    handlers only wake a thread, which starts and stops the profiler off
    the serving loop, as the one-chip cells' trace is started."""
    from devtrace import Capture

    pid = os.getpid()
    start, stop = threading.Event(), threading.Event()

    def run() -> None:
        start.wait()
        capture = Capture(os.path.join(trace_dir, f"worker-{pid}"))
        with open(os.path.join(trace_dir, f"started-{pid}"), "w"):
            pass
        stop.wait()
        capture.stop()
        with open(os.path.join(trace_dir, f"done-{pid}"), "w"):
            pass

    signal.signal(signal.SIGUSR1, lambda *_: start.set())
    signal.signal(signal.SIGUSR2, lambda *_: stop.set())
    threading.Thread(target=run, name="bench-trace", daemon=True).start()
