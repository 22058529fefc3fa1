"""Adaptive serving control plane (repro.control): windowed rate
sensing, priority-aware admission (shed bottom class first, legacy
clients untouched), SLO-feedback batching with hysteresis and bounded
steps, the autoscaler's utilization band, and the wiring — in-process
GatewayControl on submit()/pump, supervisor ControlLoop over injected
front stats — including the controller.jsonl decision journal."""
import json

import numpy as np
import pytest

import control_traces
from conftest import (
    GATEWAY_ARCH as ARCH,
    gateway_series as _series,
)
from repro.control import (
    AdmissionController,
    Autoscaler,
    BatchingController,
    CONTROLLER_LOG,
    ControlConfig,
    ControlLoop,
    TokenBucket,
    enable_control,
)
from repro.engine import AnomalyService
from repro.gateway import AnomalyGateway, GatewayOverloadedError
from repro.gateway.telemetry import Telemetry, _RateWindow
from repro.obs.prometheus import render_stats


@pytest.fixture(scope="module")
def svc():
    return AnomalyService(ARCH, schedule="wavefront")


# -- sliding-window rates (the satellite bugfix) ----------------------------


def test_rate_window_tracks_recent_not_lifetime():
    w = _RateWindow(0.0, window_s=10.0, intervals=20)
    for i in range(100):  # 100 events in the first second
        w.add(i / 100.0)
    assert w.rate(1.0) == pytest.approx(100.0, rel=0.05)
    # 60 idle seconds later the lifetime mean is ~1.6/s; the window is 0
    assert w.rate(61.0) == 0.0


def test_rate_window_partial_fill_is_unbiased():
    w = _RateWindow(0.0, window_s=10.0, intervals=20)
    w.add(0.2)
    w.add(0.4)
    # 2 events in 0.5s elapsed: ~4/s, NOT 2/10s — the young ring divides
    # by elapsed time, not the full window span
    assert w.rate(0.5) == pytest.approx(4.0, rel=0.1)


def test_telemetry_windowed_rates_in_stats():
    clock = [0.0]
    tel = Telemetry(clock=lambda: clock[0])
    for i in range(50):
        clock[0] = i * 0.1
        tel.count("queue.submitted")
    clock[0] = 5.0
    s = tel.stats()
    assert s["arrival_rps_window"] == pytest.approx(10.0, rel=0.1)
    assert s["completed_rps_window"] == 0.0
    clock[0] = 100.0  # long idle: windows drain to zero, lifetime would not
    assert tel.stats()["arrival_rps_window"] == 0.0


# -- runtime batching knobs -------------------------------------------------


def test_set_knobs_clamps_to_compiled_lanes(svc):
    gw = AnomalyGateway(svc, capacity=1, max_batch=4, max_wait_ms=5.0)
    lanes = gw.batcher.lanes
    applied = gw.batcher.set_knobs(max_batch=10 * lanes, max_wait_ms=-3.0)
    # max_batch never escapes [1, lanes] (the compiled shapes), wait
    # floors at 0 — a controller can actuate freely without recompiles
    assert applied == {"max_batch": lanes, "max_wait_ms": 0.0}
    assert gw.batcher.set_knobs(max_batch=0)["max_batch"] == 1
    assert gw.batcher.set_knobs(max_wait_ms=2.5) == {
        "max_batch": 1, "max_wait_ms": 2.5}


# -- admission: priority classes + tenant buckets ---------------------------


def test_admission_sheds_bottom_class_first():
    adm = AdmissionController(classes=3, clock=lambda: 0.0)
    # class-2 limit is a third of the queue, class-1 two thirds, class-0
    # the full queue — shedding starts at the bottom and climbs
    assert adm.depth_limit(0, 60) == 60
    assert adm.depth_limit(1, 60) == 40
    assert adm.depth_limit(2, 60) == 20
    adm.admit(depth=19, max_queue=60, priority=2)
    with pytest.raises(GatewayOverloadedError):
        adm.admit(depth=20, max_queue=60, priority=2)
    adm.admit(depth=20, max_queue=60, priority=1)   # p1 still fits
    adm.admit(depth=59, max_queue=60, priority=0)   # p0 keeps the flat limit
    with pytest.raises(GatewayOverloadedError):
        adm.admit(depth=60, max_queue=60, priority=0)
    d = adm.describe()
    assert d["shed_by_class"] == {"0": 1.0, "1": 0.0, "2": 1.0}


def test_admission_none_priority_is_flat_class0():
    """Legacy clients (no ``priority`` field) behave bit-for-bit like the
    flat gateway: admitted to the full queue, shed only at max_queue."""
    adm = AdmissionController(classes=3, clock=lambda: 0.0)
    assert adm.normalize(None) == 0
    assert adm.normalize(99) == 2   # clamped into [0, classes)
    assert adm.normalize(-5) == 0
    adm.admit(depth=59, max_queue=60)           # no priority kwarg at all
    with pytest.raises(GatewayOverloadedError):
        adm.admit(depth=60, max_queue=60)
    assert adm.describe()["shed_by_class"]["0"] == 1.0


def test_token_bucket_refill_and_burst_cap():
    b = TokenBucket(rate=2.0, burst=4.0, now=0.0)
    assert all(b.try_take(0.0) for _ in range(4))   # burst drained
    assert not b.try_take(0.0)
    assert b.try_take(0.5)                          # 0.5s * 2/s = 1 token
    assert not b.try_take(0.5)
    b.try_take(100.0)                               # refill caps at burst
    assert b.tokens == pytest.approx(3.0)


def test_admission_tenant_rate_limit_is_per_tenant():
    clock = [0.0]
    adm = AdmissionController(classes=1, tenant_rate=5.0,
                              clock=lambda: clock[0])
    for _ in range(10):  # burst defaults to 2*rate
        adm.admit(depth=0, max_queue=64, tenant="mallory")
    with pytest.raises(GatewayOverloadedError, match="rate limit"):
        adm.admit(depth=0, max_queue=64, tenant="mallory")
    adm.admit(depth=0, max_queue=64, tenant="alice")  # other tenants fine
    d = adm.describe()
    assert d["rate_limited"] == 1.0
    assert d["tenants_tracked"] == 2


# -- batching controller: feedforward, hysteresis, bounded steps ------------


def _bc(**kw):
    kw.setdefault("slo_p95_ms", 10.0)
    kw.setdefault("floor_ms", 2.0)
    kw.setdefault("lanes", 16)
    return BatchingController(**kw)


def _obs(bc, p95, **kw):
    kw.setdefault("fill", 0.5)
    kw.setdefault("depth", 0)
    kw.setdefault("arrival_rps", 100.0)
    kw.setdefault("max_batch", 8)
    kw.setdefault("max_wait_ms", 4.0)
    return bc.decide(p95_ms=p95, **kw)


def test_batching_prior_spends_quarter_of_budget():
    bc = _bc()  # budget = 8ms -> prior wait 2ms, capped by wait_cap 6.4ms
    knobs = bc.prior_knobs(32, 0.0)
    assert knobs["max_batch"] == 16  # clamped to lanes
    assert knobs["max_wait_ms"] == pytest.approx(2.0)


def test_batching_infeasible_slo_pins_wait_once():
    bc = _bc(slo_p95_ms=1.0, floor_ms=2.0)
    assert not bc.feasible
    first = _obs(bc, 5.0)
    assert first["action"] == "pin_wait"
    assert first["knobs"] == {"max_wait_ms": 0.0}
    # said once — afterwards it holds instead of thrashing
    assert _obs(bc, 5.0)["action"] == "hold"
    assert _obs(bc, 0.5)["reason"] == "slo_infeasible"


def test_batching_hysteresis_needs_patience_then_cools_down():
    bc = _bc(patience=2, cooldown_ticks=2)
    assert _obs(bc, 15.0)["action"] == "hold"       # 1st hot tick: wait
    act = _obs(bc, 15.0)                            # 2nd: act
    assert act["action"] == "shrink_wait"
    assert act["knobs"]["max_wait_ms"] == pytest.approx(2.0)  # bounded /2
    assert _obs(bc, 15.0)["reason"] == "cooldown"   # then quiet
    assert _obs(bc, 15.0)["reason"] == "cooldown"
    assert bc.actions == 1


def test_batching_over_slo_with_full_batches_grows_batch():
    bc = _bc(patience=1)
    d = _obs(bc, 15.0, fill=0.95, max_batch=8)
    assert d["action"] == "grow_batch"
    assert d["knobs"]["max_batch"] == 16  # doubled, clamped to lanes


def test_batching_under_slo_grows_wait_toward_cap():
    bc = _bc(patience=1)
    d = _obs(bc, 1.0, max_wait_ms=4.0)  # far under 0.6*slo
    assert d["action"] == "grow_wait"
    assert d["knobs"]["max_wait_ms"] == pytest.approx(6.4)  # wait_cap
    assert _obs(bc, 7.0)["reason"] in ("cooldown", "in_band")


def test_batching_idle_ticks_hold():
    bc = _bc(patience=1)
    # p95 of 0 means "no traffic this window", not "fast": hold
    assert _obs(bc, 0.0)["action"] == "hold"


# -- autoscaler -------------------------------------------------------------


def test_autoscaler_scales_up_on_sustained_overload():
    a = Autoscaler(min_workers=1, max_workers=4, worker_rps=100.0,
                   patience=2, cooldown_ticks=1)
    assert a.decide(arrival_rps=150.0, workers=1)["delta"] == 0  # patience
    d = a.decide(arrival_rps=150.0, workers=1)
    assert d["delta"] == +1 and d["reason"] == "over_capacity"
    assert d["utilization"] == pytest.approx(1.5)
    assert a.decide(arrival_rps=150.0, workers=2)["reason"] == "cooldown"


def test_autoscaler_scales_down_only_to_min():
    a = Autoscaler(min_workers=1, max_workers=4, worker_rps=100.0,
                   patience=2, cooldown_ticks=0)
    for _ in range(2):
        d = a.decide(arrival_rps=10.0, workers=2)
    assert d["delta"] == -1 and d["reason"] == "under_utilized"
    for _ in range(2):
        d = a.decide(arrival_rps=10.0, workers=1)
    assert d["delta"] == 0 and d["reason"] == "idle_at_min"


def test_autoscaler_depth_saturation_triggers_without_rate():
    a = Autoscaler(min_workers=1, max_workers=4, worker_rps=1e6,
                   patience=1, cooldown_ticks=0)
    d = a.decide(arrival_rps=1.0, workers=1, queue_depth=600, max_queue=1024)
    assert d["delta"] == +1  # depth_frac 0.59 > 0.5 despite idle util


def test_autoscaler_respects_bounds_immediately():
    a = Autoscaler(min_workers=2, max_workers=3, worker_rps=100.0)
    assert a.decide(arrival_rps=0.0, workers=1)["reason"] == "below_min"
    assert a.decide(arrival_rps=9e9, workers=5)["reason"] == "above_max"


# -- in-process plane: gateway.submit() + pump ticks ------------------------


def test_gateway_priority_shed_order_and_counters(svc):
    """Under forced overload p2 sheds first and p0 rides the flat limit;
    the per-class counters land in stats() and /metrics."""
    gw = AnomalyGateway(svc, capacity=1, max_batch=8, max_queue=6,
                        max_wait_ms=1e9)
    enable_control(gw, ControlConfig(priority_classes=3))
    for i in range(4):
        gw.submit(_series(i, 6), priority=0)
    # depth 4 >= class-2 limit (2) and class-1 limit (4): both shed
    with pytest.raises(GatewayOverloadedError):
        gw.submit(_series(90, 6), priority=2)
    with pytest.raises(GatewayOverloadedError):
        gw.submit(_series(91, 6), priority=1)
    gw.submit(_series(92, 6), priority=0)           # p0 still admitted
    gw.submit(_series(93, 6))                       # legacy: class 0
    with pytest.raises(GatewayOverloadedError):
        gw.submit(_series(94, 6), priority=0)       # flat limit reached
    s = gw.stats()
    assert s["counters"]["admission.shed_p2"] == 1
    assert s["counters"]["admission.shed_p1"] == 1
    assert s["counters"]["admission.shed_p0"] == 1
    assert s["counters"]["admission.admitted_p0"] == 6
    assert s["control"]["admission"]["shed_by_class"]["2"] == 1.0
    text = render_stats(s)
    assert "repro_admission_shed_p2_total 1" in text
    assert "repro_control_ticks" in text
    gw.flush()


def test_gateway_without_control_ignores_priority(svc):
    """No control plane attached: the wire fields are inert and the flat
    queue-depth limit is the only admission check (backward compat)."""
    gw = AnomalyGateway(svc, capacity=1, max_batch=8, max_queue=3,
                        max_wait_ms=1e9)
    assert gw.control is None
    for i in range(3):
        gw.submit(_series(i, 6), priority=2, tenant="x")
    with pytest.raises(GatewayOverloadedError):
        gw.submit(_series(9, 6), priority=0)  # priority buys nothing
    assert "admission.shed_p0" not in gw.stats()["counters"]
    assert "control" not in gw.stats()
    gw.flush()


def test_gateway_control_ticks_on_pump_and_journals(tmp_path):
    clock = [0.0]
    svc = AnomalyService(ARCH, schedule="wavefront")
    gw = AnomalyGateway(svc, capacity=1, max_batch=4, max_wait_ms=2.0,
                        clock=lambda: clock[0])
    ctl = enable_control(
        gw,
        ControlConfig(slo_p95_ms=500.0, tick_interval_s=1.0, arch=ARCH,
                      floor_timesteps=16),
        event_dir=str(tmp_path),
    )
    assert ctl.batching is not None and ctl.floor_ms > 0.0
    # the feedforward prior already bounded the wait below the budget
    assert gw.batcher.max_wait_ms <= ctl.batching.wait_cap_ms
    gw.submit(_series(0, 6))
    assert ctl.maybe_tick() is None     # not due yet
    clock[0] = 1.5
    # in production the transport's pump loop drives this (server.py)
    assert ctl.maybe_tick() is not None
    assert ctl.ticks == 1
    clock[0] = 1.7
    assert ctl.maybe_tick() is None     # next tick not due
    assert ctl.ticks == 1
    s = gw.stats()
    assert s["control"]["ticks"] == 1
    assert s["control"]["slo_p95_ms"] == 500.0
    lines = [json.loads(ln) for ln in
             (tmp_path / CONTROLLER_LOG).read_text().splitlines()]
    assert lines and lines[0]["kind"] == "control_tick"
    assert lines[0]["scope"] == "gateway"
    assert lines[0]["tick"] == 1
    assert "action" in lines[0] and "p95_ms" in lines[0]


# -- supervisor plane: ControlLoop over injected front stats ----------------


class _FakeFront:
    """Records actuations; stats are injected per tick, so no workers."""

    def __init__(self):
        self.batching_calls = []
        self.ups = 0
        self.downs = 0
        self.control = None

    def set_batching(self, **kw):
        self.batching_calls.append(kw)
        return {**kw, "workers": 2, "attempted": 2}

    def scale_up(self):
        self.ups += 1
        return {"index": self.ups, "workers": 1 + self.ups}

    def scale_down(self):
        self.downs += 1
        return {"dropped_tickets": 0, "clean": True, "workers": 2}


def _front_stats(p95_bucket_counts, *, arrival=0.0, depth=0, workers=2,
                 filled=0, slots=0):
    return {
        "arrival_rps_window": arrival,
        "queue_depth": depth,
        "max_batch": 8,
        "workers": {"count": workers},
        "counters": {"batch.filled": filled, "batch.slots": slots},
        "histograms": {"request_ms": {"counts": p95_bucket_counts,
                                      "count": sum(p95_bucket_counts.values()),
                                      "sum": 0.0}},
    }


def test_control_loop_ticks_scale_and_journal(tmp_path):
    from repro.config import get_config

    cfg = get_config(ARCH)
    front = _FakeFront()
    loop = ControlLoop(
        front,
        ControlConfig(slo_p95_ms=1e4, autoscale_min=1, autoscale_max=4,
                      worker_rps=100.0, patience=1, arch=ARCH,
                      floor_timesteps=16,
                      extra={"max_wait_ms": 2.0}),
        lanes=8, max_queue=64, model_cfg=cfg.lstm_ae,
        event_dir=str(tmp_path),
    )
    assert front.control is loop    # attached like gateway.control
    assert loop.floor_ms > 0.0
    # tick 1: overload (util 2.5) — patience satisfied at tick 2
    loop.tick(_front_stats({}, arrival=500.0, workers=2))
    d = loop.tick(_front_stats({}, arrival=500.0, workers=2))
    assert d["scale"]["delta"] == +1 and front.ups == 1
    # idle long enough (cooldown 3, patience 2) — eventually drains one
    for _ in range(8):
        d = loop.tick(_front_stats({}, arrival=1.0, workers=3))
    assert front.downs == 1
    assert d["scale"]["delta"] <= 0
    desc = loop.describe()
    assert desc["ticks"] == 10
    assert desc["autoscale"]["actions"] == 2
    lines = [json.loads(ln) for ln in
             (tmp_path / CONTROLLER_LOG).read_text().splitlines()]
    assert len(lines) == 10
    assert all(ln["scope"] == "front" for ln in lines)
    assert lines[1]["scale"]["reason"] == "over_capacity"


def test_control_loop_batching_actuates_through_front(tmp_path):
    front = _FakeFront()
    loop = ControlLoop(
        front,
        ControlConfig(slo_p95_ms=10.0, patience=1, cooldown_ticks=0,
                      min_wait_ms=0.25, extra={"max_wait_ms": 4.0}),
        lanes=8, event_dir=str(tmp_path),
    )
    from repro.obs.histogram import bucket_index

    assert loop.batching is not None
    assert loop.floor_ms == 0.0     # no model_cfg: pure-feedback mode
    hot = {bucket_index(50.0): 10}  # every request far over the 10ms SLO
    loop.tick(_front_stats(hot, arrival=100.0))
    assert front.batching_calls     # shrink_wait fanned out
    assert front.batching_calls[0]["max_wait_ms"] == pytest.approx(2.0)
    assert loop.describe()["knobs"]["max_wait_ms"] == pytest.approx(2.0)
    loop.stop()                     # never started: stop is a clean no-op


# -- adaptive vs static on seeded traces (virtual-clock simulator) ----------
# Capacity is scaled (one worker = 400 req/s at full fill) so a 60 s trace
# holds ~5e4 events; the controllers' whole world is the slo/floor ratio
# and utilization, both kept (service = 1.2x floor, SLO = 5x floor — the
# shape ``serving_floor_ms`` feeds the live plane).

_LANES, _UNIT = 16, 400.0
_SERVICE = _LANES * 1e3 / _UNIT      # ms per flush (scaled time)
_FLOOR = _SERVICE / 1.2              # feedforward floor the plane sees
_SLO = 5.0 * _FLOOR
_SIM = dict(lanes=_LANES, service_ms=_SERVICE, slo_ms=_SLO, max_queue=64)


def test_adaptive_meets_slo_and_beats_best_static_on_bursty_trace():
    """Batching + autoscale 2:5 meets the p95 SLO on the bursty trace
    at >=1.2x the goodput of the best static arm, each static arm on
    the 2-worker fleet provisioned for the mean load."""
    bursty = control_traces.make_trace("bursty", unit_rps=_UNIT, seed=0,
                                       duration_s=60.0)
    best = max(
        control_traces.simulate(bursty, workers=2, max_batch=mb,
                                max_wait_ms=wait, **_SIM)["goodput_rps"]
        for mb, wait in ((16, 0.25 * _FLOOR),      # tight
                         (4, 0.25 * _FLOOR),       # eager
                         (16, 3.0 * _FLOOR)))      # patient
    a = control_traces.simulate(
        bursty, workers=2, max_batch=16, max_wait_ms=0.25 * _FLOOR,
        batching=BatchingController(slo_p95_ms=_SLO, floor_ms=_FLOOR,
                                    lanes=_LANES, min_wait_ms=0.05 * _FLOOR,
                                    patience=1, cooldown_ticks=1),
        autoscaler=Autoscaler(min_workers=2, max_workers=5,
                              worker_rps=0.8 * _UNIT, patience=1,
                              cooldown_ticks=1),
        tick_s=0.5, spawn_delay_s=1.0, **_SIM)
    assert a["p95_ms"] <= _SLO
    assert a["goodput_rps"] >= 1.2 * best


def test_priority_flood_sheds_bottom_class_and_tenant_bucket_rate_limits():
    """Under a priority-2 tenant flood class 0 sheds nothing while class
    2 takes the shedding; a per-tenant token bucket moves the shedding to
    ``rate_limited`` without shedding class 0."""
    adv = control_traces.make_trace("adversarial", unit_rps=_UNIT, seed=1,
                                    duration_s=30.0)
    p = control_traces.simulate(adv, workers=2, max_batch=16,
                                max_wait_ms=0.25 * _FLOOR, classes=3, **_SIM)
    assert p["shed_by_class"]["0"] == 0
    assert p["shed_by_class"]["2"] > 0
    t = control_traces.simulate(adv, workers=2, max_batch=16,
                                max_wait_ms=0.25 * _FLOOR, classes=3,
                                tenant_rate=0.5 * _UNIT, **_SIM)
    assert t["rate_limited"] > 0
    assert t["shed_by_class"]["0"] == 0
    assert t["shed_by_class"]["2"] < p["shed_by_class"]["2"]
