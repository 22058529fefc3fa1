"""Streaming anomaly gateway: micro-batched serving over the execution
engine (ROADMAP follow-up "batched/async request queueing").

One :class:`AnomalyGateway` fronts an :class:`~repro.engine.AnomalyService`
(or a bare bound :class:`~repro.engine.Engine`) with the two serving
surfaces the paper's deployment needs:

* **streaming sessions** — ``admit / step / evict / reset`` on a
  fixed-capacity :class:`~repro.gateway.pool.SessionPool`: up to
  ``capacity`` concurrent streams share ONE compiled masked step over the
  pooled state block, so thousands of logical streams churn through
  without retracing (the software analogue of the paper's always-fed
  datapath).
* **one-shot scoring** — ``submit / pump / score`` on a
  :class:`~repro.gateway.queue.MicroBatcher`: requests are shape-bucketed
  by sequence length, padded to bucket boundaries, flushed on
  ``max_batch``/``max_wait_ms``, and rejected with
  :class:`GatewayOverloadedError` once ``max_queue`` are pending.

``gateway.stats()`` surfaces the shared :class:`Telemetry` (queue depth,
batch-fill ratio, p50/p95 latency, per-schedule throughput).

Both surfaces are placement-aware: under a sharded
:class:`~repro.engine.placement.Placement` (``open_gateway(placement=
Placement.data(N))`` or ``AnomalyGateway(..., placement=N)``) the pool's
slot block distributes over the data mesh (capacity scales to
``slots_per_device x mesh_size``), bucket flushes score data-parallel
padded to a per-device multiple, and ``stats()`` gains a ``placement``
section with per-device slot occupancy and flush fill.  The single
placement is a strict no-op.

A live deployment fronts the gateway with the asyncio JSON-lines
transport in :mod:`repro.gateway.server` (background pump, one pool
session per connection) and refreshes the detector in place via
:meth:`AnomalyGateway.recalibrate` — no drain required.
"""
from __future__ import annotations

import time
from typing import Callable, Hashable, Mapping, Optional, Sequence, Union

import jax
import numpy as np

from repro.engine.base import Engine
from repro.engine.placement import Placement
from repro.engine.schedules import schedule_cache_info
from repro.gateway.pool import PoolFullError, SessionPool, UnknownStreamError
from repro.gateway.queue import GatewayOverloadedError, MicroBatcher, Ticket, bucket_for
from repro.gateway.telemetry import Telemetry
from repro.obs import EventLog, Tracer

_UNSET = object()


class AnomalyGateway:
    """Session pool + micro-batching queue + telemetry over one engine."""

    def __init__(
        self,
        service_or_engine,
        *,
        capacity: int = 32,
        max_batch: int = 32,
        max_wait_ms: float = 5.0,
        max_queue: int = 1024,
        max_seq_len: Optional[int] = None,
        placement: Optional["object"] = None,
        clock: Callable[[], float] = time.monotonic,
        obs_detail: bool = True,
    ):
        engine = getattr(service_or_engine, "engine", service_or_engine)
        if not isinstance(engine, Engine):
            raise TypeError(
                f"expected AnomalyService or Engine, got {type(service_or_engine)!r}"
            )
        engine._require_params()  # fail fast: a gateway serves a bound model
        self.service = service_or_engine if service_or_engine is not engine else None
        if placement is not None:
            if isinstance(placement, int):  # shorthand: N -> Placement.data(N)
                placement = Placement.data(placement)
            if not isinstance(placement, Placement):
                raise TypeError(
                    f"placement must be a Placement or int, got {type(placement)!r}"
                )
            # re-lay the engine's programs out on the requested mesh; a
            # matching placement returns the engine itself (strict no-op).
            # The fronted service keeps its own engine — recalibrate()
            # rebinds both so the two views never diverge.
            engine = engine.with_placement(placement)
        self.engine = engine
        if self.service is not None:
            # let the service rebind this gateway's engine on fit /
            # recalibrate — a placement override gives the gateway its own
            # Engine, which must never serve stale params
            registry = getattr(self.service, "_gateways", None)
            if registry is not None:
                registry.add(self)
        self._threshold: Optional[float] = None  # used when fronting a bare Engine
        # session durability is opt-in: repro.gateway.durability's
        # enable_durability() attaches a DurableSessions coordinator here
        # and the transport/stats pick it up; None keeps PR-5 semantics
        self.durability = None
        # the control plane is opt-in the same way: repro.control's
        # enable_control() attaches a GatewayControl here (priority
        # admission gate on submit(), SLO batching ticks on the pump);
        # None keeps flat admission and static knobs
        self.control = None
        # observability plane: per-stage histograms gate on ``obs_detail``
        # (the obs_overhead benchmark's off arm), the tracer produces
        # spans for requests that opt in with a wire ``trace`` field, and
        # the event log is a no-op until attach_event_log() points it at
        # a JSONL file
        self.telemetry = Telemetry(clock=clock, detail=obs_detail)
        self.events = EventLog(None)
        self.tracer = Tracer(clock=clock, events=self.events)
        self.pool = SessionPool(engine, capacity, telemetry=self.telemetry)
        self.batcher = MicroBatcher(
            engine, max_batch=max_batch, max_wait_ms=max_wait_ms,
            max_queue=max_queue, max_seq_len=max_seq_len,
            telemetry=self.telemetry, clock=clock,
        )

    # -- streaming sessions (pool) ----------------------------------------

    def admit(self, stream_id: Hashable) -> int:
        return self.pool.admit(stream_id)

    def evict(self, stream_id: Hashable) -> float:
        return self.pool.evict(stream_id)

    def reset(self, stream_id: Hashable) -> None:
        self.pool.reset(stream_id)

    def step(self, inputs: Mapping[Hashable, "object"]) -> dict:
        return self.pool.step(inputs)

    # -- one-shot scoring (micro-batcher) ---------------------------------

    def submit(self, series, *, priority=None, tenant=None) -> Ticket:
        """Enqueue one (T, F) window.  ``priority`` (0 = highest) and
        ``tenant`` are consulted only when a control plane is attached —
        without one (or with ``priority=None``) this is exactly the flat
        PR-5 path: first come, first queued, shed at ``max_queue``."""
        if self.control is not None:
            self.control.admit(priority=priority, tenant=tenant)
        return self.batcher.submit(series)

    def pump(self, now: Optional[float] = None) -> int:
        return self.batcher.pump(now)

    def flush(self) -> int:
        return self.batcher.flush()

    def score(self, windows: Sequence) -> "object":
        return self.batcher.score(windows)

    # -- live recalibration ------------------------------------------------

    @property
    def threshold(self) -> Optional[float]:
        """The detector threshold alerts compare against (None before any
        calibration).  Lives on the fronted service when there is one."""
        if self.service is not None:
            return self.service.threshold
        return self._threshold

    def recalibrate(
        self, *, threshold=_UNSET, params: Optional["object"] = None
    ) -> dict:
        """Swap the detection threshold and/or model params in place.

        The swap is atomic from the serving paths' point of view: resident
        pool streams keep their slots, carried ``(h, c)`` state and running
        errors, and queued one-shot requests stay queued — each pool step /
        flush reads the engine's *current* params and each alert decision
        reads the *current* threshold, so new values simply apply from the
        next operation on.  No drain, no eviction (the ROADMAP's
        "threshold/calibration refresh without draining sessions").

        ``threshold`` may be a float or None (disable alerting); omit it to
        leave the threshold untouched.  ``params`` rebinds the engine (and
        the fronted service, keeping the two views consistent).  Returns
        ``{"threshold": ..., "params_swapped": ...}``.
        """
        if params is not None:
            # one swap path for every view: the service's _bind rebinds its
            # own engine AND every registered gateway engine (placement
            # overrides included), so no sibling gateway serves stale params
            binder = getattr(self.service, "_bind", None)
            if binder is not None:
                binder(params)
            else:  # fronting a bare Engine (or a duck-typed service)
                self.engine.bind(params)
                if self.service is not None:
                    self.service.params = params
        if threshold is not _UNSET:
            value = None if threshold is None else float(threshold)
            if self.service is not None:
                self.service.threshold = value
            else:
                self._threshold = value
        self.telemetry.count("gateway.recalibrated")
        self.events.emit(
            "recalibrate",
            threshold=self.threshold,
            params_swapped=params is not None,
        )
        if self.durability is not None:
            # resumption tokens carry the recalibration epoch so a client
            # can tell its scores straddled a swap (state itself is
            # carried through unchanged, same as for live sessions)
            self.durability.epoch += 1
        return {"threshold": self.threshold, "params_swapped": params is not None}

    # -- observability ----------------------------------------------------

    def attach_event_log(self, path) -> EventLog:
        """Point the gateway's JSONL event log (lifecycle events + sampled
        spans) at ``path``; the tracer follows automatically.  Passing
        None detaches (back to the no-op log)."""
        old = self.events
        self.events = EventLog(path)
        self.tracer.events = self.events
        old.close()
        return self.events

    @property
    def placement(self) -> Placement:
        """The device placement the gateway's serving programs run on."""
        return self.engine.placement

    def stats(self) -> dict:
        out = self.telemetry.stats()
        out.update(
            schedule=self.engine.schedule.tag,
            capacity=self.pool.capacity,
            active_streams=self.pool.active,
            queue_depth=self.batcher.queue_depth,
            max_batch=self.batcher.max_batch,
            max_seq_len=self.batcher.max_seq_len,
            features=self.batcher.features,
            threshold=self.threshold,
        )
        # the devices this process serves on: a front's per-worker view
        # shows each worker confined to its own chip (count 1)
        devices = jax.devices()
        out["device"] = {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices)}
        # compile visibility: per-program/per-shape compile counts + wall
        # time from the engine, resolve-cache hit/miss from the registry —
        # recompile storms on the bucket ladder show up here
        out["engine"] = {
            **self.engine.profile_info(),
            "schedule_cache": schedule_cache_info(),
        }
        if self.placement.is_sharded:
            # mesh-layout view: static layout + live per-device residency;
            # the matching per-flush fill history lives in the gauges
            # (queue.device_fill / pool.device_active).  Absent under the
            # single placement so single-device telemetry is unchanged.
            out["placement"] = {
                **self.placement.describe(),
                "slots_per_device": self.pool.slots_per_device,
                "score_lanes": self.batcher.lanes,
                "device_active": self.pool.per_device_active(),
            }
        if self.durability is not None:
            out["durability"] = self.durability.describe()
        if self.control is not None:
            out["control"] = self.control.describe()
        return out

    def __repr__(self) -> str:
        pl = (f", placement={self.placement!r}"
              if self.placement.is_sharded else "")
        return (f"AnomalyGateway(schedule={self.engine.schedule.tag}, "
                f"capacity={self.pool.capacity}, active={self.pool.active}, "
                f"queue_depth={self.batcher.queue_depth}{pl})")


def drive_stream_churn(
    gateway: AnomalyGateway, windows, churn_every: int = 8
) -> tuple[dict, list]:
    """Demo/benchmark driver: stream N logical series through the pool.

    ``windows`` is (N, T, F); up to ``capacity`` streams are admitted, all
    residents step each timestep, and every ``churn_every`` steps the
    oldest resident is evicted for a waiting stream (late admits score
    their series' tail — slot churn, the behaviour under test).  Returns
    ``(finals, unserved)``: {stream index: final running error} for every
    served stream, plus the indices still waiting when the driver ran out
    of timesteps (only capacity + (T-1)//churn_every streams can be
    served) — callers must report those, not drop them silently.  Shared
    by ``launch/serve --gateway`` and ``examples/serve_anomaly_stream.py``;
    a real deployment drives admit/step/evict from its transport instead.
    """
    windows = np.asarray(windows, np.float32)
    n, t_len, _ = windows.shape
    resident = list(range(min(gateway.pool.capacity, n)))
    waiting = list(range(len(resident), n))
    finals: dict = {}
    for sid in resident:
        gateway.admit(sid)
    for t in range(t_len):
        gateway.step({sid: windows[sid, t] for sid in resident})
        if waiting and t and t % churn_every == 0:
            old = resident.pop(0)
            finals[old] = gateway.evict(old)
            nxt = waiting.pop(0)
            gateway.admit(nxt)
            resident.append(nxt)
    for sid in resident:
        finals[sid] = gateway.evict(sid)
    return finals, waiting


__all__ = [
    "AnomalyGateway",
    "drive_stream_churn",
    "GatewayOverloadedError",
    "MicroBatcher",
    "Placement",
    "PoolFullError",
    "SessionPool",
    "Telemetry",
    "Ticket",
    "UnknownStreamError",
    "bucket_for",
]
