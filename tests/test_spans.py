"""Stage spans (``Telemetry.span``): each times a synchronous block into
its stage histogram and writes it into the profiler's trace as a host
annotation.  The pool step and the flush split into parts that fit inside
them, the flush counts its padded rows, spans stay well nested on every
thread of a served gateway, and a real profiler capture holds them."""
import json
import os
import subprocess
import sys
import textwrap
import threading
from pathlib import Path

import numpy as np
import pytest

from conftest import GATEWAY_ARCH as ARCH, GATEWAY_FEATS as FEATS, gateway_series
from repro.engine import AnomalyService
from repro.gateway import AnomalyGateway, telemetry
from repro.gateway.client import GatewayClient
from repro.gateway.queue import MicroBatcher
from repro.gateway.server import GatewayServer
from repro.gateway.telemetry import Telemetry

ROOT = Path(__file__).resolve().parents[1]


class TickClock:
    """Advances one millisecond every time it is read."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1e-3
        return self.t


class Recorder:
    """Stands in for the profiler's annotation: logs every enter and exit
    per thread, and every exit that does not close the span its thread
    entered last."""

    def __init__(self):
        self.names: list = []
        self.stacks: dict = {}
        self.misnested: list = []
        self._lock = threading.Lock()

    def __call__(self, name: str):
        rec = self

        class _Note:
            def __enter__(self):
                with rec._lock:
                    rec.names.append(name)
                    rec.stacks.setdefault(threading.get_ident(), []).append(name)

            def __exit__(self, *exc):
                with rec._lock:
                    stack = rec.stacks.get(threading.get_ident(), [])
                    top = stack.pop() if stack else None
                    if top != name:
                        rec.misnested.append((top, name))

        return _Note()


@pytest.fixture(scope="module")
def svc():
    return AnomalyService(ARCH, schedule="wavefront")


@pytest.mark.parametrize("detail", [True, False])
def test_span_records_its_stage_only_with_detail(detail):
    tel = Telemetry(clock=TickClock(), detail=detail)
    with tel.span("pool.step", "pool_step_ms") as span:
        pass
    assert span.ms == pytest.approx(1.0)
    assert ("pool_step_ms" in tel.histograms) is detail
    if detail:
        assert tel.histograms["pool_step_ms"].count == 1


def test_span_records_when_the_block_raises():
    tel = Telemetry(clock=TickClock())
    with pytest.raises(ValueError):
        with tel.span("batch.compute", "compute_ms"):
            raise ValueError("engine failure")
    assert tel.histograms["compute_ms"].count == 1


def test_spans_nest(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(telemetry, "_annotation", rec)
    tel = Telemetry(clock=TickClock())
    with tel.span("outer", "outer_ms") as outer:
        with tel.span("inner", "inner_ms") as inner:
            pass
        with tel.span("bare"):
            pass
    assert rec.names == ["outer", "inner", "bare"] and not rec.misnested
    assert inner.ms == pytest.approx(1.0)
    assert outer.ms == pytest.approx(5.0)
    assert set(tel.histograms) == {"outer_ms", "inner_ms"}


def test_pool_step_parts_fit_inside_the_step(svc):
    gw = AnomalyGateway(svc, capacity=4, clock=TickClock())
    gw.admit("a")
    gw.admit("b")
    for t in range(3):
        gw.step({"a": gateway_series(0, 3)[t], "b": gateway_series(1, 3)[t]})
    h = gw.telemetry.histograms
    parts = ("pool_assemble_ms", "pool_launch_ms", "pool_readback_ms")
    assert all(h[name].count == 3 for name in parts + ("pool_step_ms",))
    assert sum(h[name].sum for name in parts) <= h["pool_step_ms"].sum


def test_flush_counts_useful_and_computed_rows(svc):
    tel = Telemetry()
    batcher = MicroBatcher(svc.engine, max_batch=4, max_wait_ms=1e9,
                           telemetry=tel)
    tickets = [batcher.submit(np.ones((t, FEATS), np.float32)) for t in (5, 7)]
    assert batcher.flush() == 2 and all(t.done for t in tickets)
    assert tel.counters["batch.rows_useful"] == 12      # 5 + 7
    assert tel.counters["batch.rows_computed"] == 32    # 4 lanes x bucket 8
    assert tel.histograms["resolve_ms"].count == 1


def test_spans_stay_nested_on_every_thread_of_a_served_gateway(svc, monkeypatch):
    """One bp1 connection steps while another scores, through a served
    gateway: every span exit closes the span its thread entered last."""
    rec = Recorder()
    monkeypatch.setattr(telemetry, "_annotation", rec)
    gw = svc.open_gateway(capacity=4, max_batch=4, max_wait_ms=2.0)
    server = GatewayServer(gw, port=0, pump_interval_ms=1.0)
    host, port = server.start_in_thread()
    errors: list = []

    def stepping():
        with GatewayClient(host, port, protocol="binary") as client:
            for x in gateway_series(0, 6):
                client.step(x)
            client.step_many(gateway_series(1, 4))

    def scoring():
        with GatewayClient(host, port, protocol="binary") as client:
            client.score_many([gateway_series(i, 5 + i) for i in range(6)])

    def guarded(fn):
        try:
            fn()
        except Exception as exc:  # surfaced by the assertion below
            errors.append(exc)

    try:
        threads = [threading.Thread(target=guarded, args=(fn,))
                   for fn in (stepping, scoring)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
        assert not any(th.is_alive() for th in threads)
    finally:
        server.stop_in_thread()
    assert errors == []
    assert rec.misnested == []
    assert {"wire.frame", "wire.decode", "wire.encode", "pool.step",
            "pool.assemble", "pool.launch", "pool.readback", "batch.flush",
            "batch.assemble", "batch.compute", "batch.resolve",
            "pump.tick"} <= set(rec.names)


_CAPTURE = textwrap.dedent("""
    import glob, json, sys
    import jax
    import numpy as np
    from jax.profiler import ProfileData
    from repro.engine import AnomalyService

    gw = AnomalyService("lstm-ae-f32-d2", schedule="wavefront").open_gateway(
        capacity=2)
    gw.admit("a")
    gw.step({"a": np.zeros(32, np.float32)})          # compile before tracing
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(sys.argv[1], profiler_options=opts)
    for _ in range(3):
        gw.step({"a": np.ones(32, np.float32)})
    jax.profiler.stop_trace()
    path = glob.glob(sys.argv[1] + "/**/*.xplane.pb", recursive=True)[0]
    spans = [[ev.name, ev.start_ns, ev.duration_ns]
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name.startswith("pool.")]
    print(json.dumps(spans))
""")


def test_profiler_capture_holds_the_pool_spans(tmp_path):
    """A real ``jax.profiler`` capture around pool steps (in a child of its
    own, bounded in time) finds each part inside its step on the host
    plane."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", _CAPTURE, str(tmp_path)],
                         env=env, capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-3000:]
    spans = json.loads(out.stdout.strip().splitlines()[-1])
    steps = [s for s in spans if s[0] == "pool.step"]
    assert len(steps) == 3
    for name in ("pool.assemble", "pool.launch", "pool.readback"):
        inside = [s for s in spans if s[0] == name]
        assert len(inside) == 3
        for _, start, dur in inside:
            assert any(s <= start and start + dur <= s + d for _, s, d in steps)
