"""Serving launcher: ``python -m repro.launch.serve --arch <id>``.

Modes, per model family:
- LSTM-AE: anomaly-detection service (``repro.engine.AnomalyService``) on a
  named execution schedule — ``--schedule sequential|wavefront|pipelined``
  (wavefront is the paper's deployment).
- LSTM-AE with ``--gateway``: the streaming gateway — a ``--capacity``-slot
  session pool with admit/evict churn plus a micro-batched one-shot scoring
  queue (``--max-batch`` / ``--max-wait-ms``); prints gateway telemetry.
- LSTM-AE with ``--http``: the same gateway behind the asyncio socket
  transport (``--host`` / ``--port``; bp1 binary frames with per-connection
  JSON-lines fallback, background pump, graceful drain on SIGINT/SIGTERM)
  — drive it with ``examples/gateway_client.py``.
- LSTM-AE with ``--http --workers N``: the multi-worker front
  (``repro.gateway.workers``) — N worker processes share one
  ``SO_REUSEPORT`` port, each with its own engine (and its own
  ``--mesh data=K`` placement shard); the supervisor respawns crashes and
  coordinates the SIGTERM drain (every worker answers all pending
  tickets; the exit line reports per-worker clean exits and dropped
  tickets).  With ``--store-dir`` both transport modes serve DURABLE
  sessions: snapshots + signed resumption tokens, crash-resume on any
  worker, drain-handoff (README §Durability).  With ``--slo-p95-ms`` /
  ``--priority-classes`` / ``--autoscale MIN:MAX`` either transport mode
  runs the ADAPTIVE control plane (``repro.control``): SLO-driven
  batching-knob tuning, priority-aware admission, and (workers mode)
  drain-based worker autoscaling (README §Control plane).
- LM families: batched prefill + greedy decode of a few tokens (reduced
  configs on CPU; full configs need a pod mesh).
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import get_config, list_archs, reduced_config
from repro.core.latency import PAPER_RH_M
from repro.data import TimeseriesConfig, make_batch
from repro.engine import AnomalyService, EngineConfig, Placement, available_schedules
from repro.models import build_model
from repro.serving import greedy_decode_loop
from repro.utils.compile_cache import enable_compile_cache


def engine_cfg_for(args) -> "object":
    """The engine selection for this invocation: the bare schedule name,
    or a full EngineConfig carrying the ``--mesh`` placement (e.g.
    ``--mesh data=2`` shards pool slots and micro-batch rows 2-way)."""
    if not args.mesh:
        return args.schedule
    return EngineConfig(
        schedule=args.schedule, placement=Placement.from_spec(args.mesh)
    )


def parse_autoscale(spec):
    """``--autoscale MIN:MAX`` -> ``(min, max)`` worker bounds (or None)."""
    if not spec:
        return None
    try:
        lo, hi = (int(p) for p in spec.split(":", 1))
    except ValueError:
        raise SystemExit(f"--autoscale expects MIN:MAX, got {spec!r}")
    if lo < 1 or hi < lo:
        raise SystemExit(f"--autoscale needs 1 <= MIN <= MAX, got {spec!r}")
    return lo, hi


def control_cfg_for(args, *, autoscale=None):
    """The :class:`repro.control.ControlConfig` this invocation asked
    for, or None when no control-plane flag is set (legacy behaviour:
    flat admission, static knobs, fixed fleet)."""
    wants = (args.slo_p95_ms is not None or args.priority_classes > 1
             or args.tenant_rate is not None or autoscale is not None)
    if not wants:
        return None
    from repro.control import ControlConfig

    return ControlConfig(
        slo_p95_ms=args.slo_p95_ms,
        tick_interval_s=args.control_tick_s,
        priority_classes=args.priority_classes,
        tenant_rate=args.tenant_rate,
        autoscale_min=autoscale[0] if autoscale else None,
        autoscale_max=autoscale[1] if autoscale else None,
        floor_timesteps=args.seq_len,
        arch=args.arch,
        extra={"max_wait_ms": args.max_wait_ms},
    )


def serve_lstm_ae(cfg, args) -> None:
    svc = AnomalyService(cfg, schedule=engine_cfg_for(args))
    data_cfg = TimeseriesConfig(features=cfg.lstm_ae.input_features,
                                seq_len=args.seq_len, batch=args.batch,
                                anomaly_rate=0.05)
    if args.train_steps:
        fit_cfg = TimeseriesConfig(features=cfg.lstm_ae.input_features,
                                   seq_len=args.seq_len, batch=64)
        metrics = svc.fit(fit_cfg, args.train_steps)
        svc.calibrate(fit_cfg)
        print(f"[serve] fitted {cfg.name}: mse={metrics['mse']:.4f}, "
              f"threshold={svc.threshold:.4f}")

    series, _ = make_batch(data_cfg, 0)
    jax.block_until_ready(svc.score(series))  # compile
    total_alerts = 0
    t0 = time.perf_counter()
    for i in range(args.requests):
        series, _ = make_batch(data_cfg, i)
        errors = jax.block_until_ready(svc.score(series))
        if svc.threshold is not None:
            total_alerts += int((errors > svc.threshold).sum())
    dt = time.perf_counter() - t0
    steps = args.requests * args.batch * args.seq_len
    print(f"[serve] {cfg.name} [{svc.engine.schedule.tag}]: {args.requests} requests, "
          f"{dt/args.requests*1e3:.2f} ms/request, {steps/dt:,.0f} timesteps/s"
          + (f", alerts={total_alerts}" if svc.threshold is not None else ""))
    if cfg.name in PAPER_RH_M:  # Eq-1 is calibrated only for Table-1 archs
        est = svc.latency_model(args.seq_len)
        print(f"[serve] Eq-1 model ({est.schedule}) for one sequence "
              f"T={args.seq_len}: {est.ms:.3f} ms ({est.cycles} cycles)")


def serve_gateway(cfg, args) -> None:
    """Drive the streaming gateway: pooled sessions with churn + a
    micro-batched one-shot request stream, then print its telemetry."""
    svc = AnomalyService(cfg, schedule=engine_cfg_for(args))
    feats = cfg.lstm_ae.input_features
    if args.train_steps:
        fit_cfg = TimeseriesConfig(features=feats, seq_len=args.seq_len, batch=64)
        svc.fit(fit_cfg, args.train_steps)
        svc.calibrate(fit_cfg)
        print(f"[gateway] fitted {cfg.name}: threshold={svc.threshold:.4f}")

    gw = svc.open_gateway(capacity=args.capacity, max_batch=args.max_batch,
                          max_wait_ms=args.max_wait_ms)
    print(f"[gateway] {gw!r}")

    # --- streaming phase: more logical streams than slots, admit/evict churn
    from repro.gateway import drive_stream_churn

    n_streams = args.streams or 2 * args.capacity
    data_cfg = TimeseriesConfig(features=feats, seq_len=args.seq_len,
                                batch=n_streams, anomaly_rate=0.05, seed=7)
    series, _ = make_batch(data_cfg, 0)
    xs = np.asarray(series)                      # (N, T, F)
    t0 = time.perf_counter()
    finals, unserved = drive_stream_churn(gw, xs)
    dt = time.perf_counter() - t0
    stepped = int(gw.stats()["counters"]["pool.stream_steps"])
    print(f"[gateway] streamed {len(finals)}/{n_streams} logical streams over "
          f"{gw.pool.capacity} slots: {stepped/dt:,.0f} stream-steps/s "
          f"({dt*1e3:.1f} ms wall)"
          + (f", {len(unserved)} still waiting at end" if unserved else ""))

    # --- one-shot phase: micro-batched score requests (mixed lengths)
    lens = [max(4, args.seq_len - (i % 3) * 2) for i in range(args.requests)]
    tickets = []
    for i, L in enumerate(lens):
        tickets.append(gw.submit(xs[i % n_streams, :L]))
        gw.pump()
    gw.flush()
    scores = np.array([t.score for t in tickets])
    # NB: "is not None" — a calibrated threshold of 0.0 is a real threshold
    alerts = int((scores > svc.threshold).sum()) if svc.threshold is not None else 0
    s = gw.stats()
    print(f"[gateway] scored {len(tickets)} one-shot requests "
          f"(fill={s['batch_fill_ratio']:.2f}, "
          f"p50={s['latency_ms']['p50']:.2f}ms, "
          f"p95={s['latency_ms']['p95']:.2f}ms)"
          + (f", alerts={alerts}" if svc.threshold is not None else ""))
    # rates: lifetime averages for the run summary, plus the sliding
    # 10 s window the control plane actually steers on
    print(f"[gateway] stats: schedule={s['schedule']} "
          f"stream_steps_per_s={s['stream_steps_per_s']:,.0f} "
          f"requests_per_s={s['requests_per_s']:,.0f} "
          f"arrival_rps_window={s['arrival_rps_window']:,.0f} "
          f"rejected={s['counters'].get('queue.rejected', 0):.0f}")


def serve_http(cfg, args) -> None:
    """Run the socket transport (``repro.gateway.server``) in front of
    the gateway until SIGINT/SIGTERM, then drain gracefully.  Serves the
    bp1 binary frame protocol to clients that negotiate it and falls
    back to JSON lines per connection.  Clients:
    ``examples/gateway_client.py`` or
    ``repro.gateway.client.GatewayClient``."""
    from repro.gateway.server import GatewayServer

    svc = AnomalyService(cfg, schedule=engine_cfg_for(args))
    if args.train_steps:
        fit_cfg = TimeseriesConfig(features=cfg.lstm_ae.input_features,
                                   seq_len=args.seq_len, batch=64)
        svc.fit(fit_cfg, args.train_steps)
        svc.calibrate(fit_cfg)
        print(f"[http] fitted {cfg.name}: threshold={svc.threshold:.4f}",
              flush=True)
    gw = svc.open_gateway(capacity=args.capacity, max_batch=args.max_batch,
                          max_wait_ms=args.max_wait_ms)
    if args.store_dir:
        from repro.gateway.durability import enable_durability

        enable_durability(gw, args.store_dir,
                          snapshot_interval_ms=args.snapshot_interval_ms)
    if args.event_dir:
        gw.attach_event_log(os.path.join(args.event_dir, "server.jsonl"))
        gw.events.emit("boot", pid=os.getpid())
    ccfg = control_cfg_for(args)
    if ccfg is not None:
        from repro.control import enable_control

        enable_control(gw, ccfg, event_dir=args.event_dir or None)
    metrics = None
    if args.metrics_port is not None:
        from repro.obs import MetricsServer

        metrics = MetricsServer(gw.stats, host=args.host,
                                port=args.metrics_port).start()
    server = GatewayServer(gw, host=args.host, port=args.port)

    def _ready(srv) -> None:
        mesh = (f", mesh={gw.placement.data_shards}x{gw.placement.data_axis}"
                if gw.placement.is_sharded else "")
        durable = f", store={args.store_dir}" if args.store_dir else ""
        control = ""
        if gw.control is not None:
            control = (f", slo_p95_ms={args.slo_p95_ms}, "
                       f"priority_classes={args.priority_classes}")
        scrape = f" metrics_port={metrics.port}" if metrics else ""
        print(f"[http] listening on {srv.host}:{srv.port}{scrape} "
              f"protocols=bp1+json "
              f"(schedule={gw.engine.schedule.tag}, capacity={gw.pool.capacity}, "
              f"max_batch={gw.batcher.max_batch}, "
              f"max_wait_ms={gw.batcher.max_wait_ms}{mesh}{durable}"
              f"{control})", flush=True)

    import asyncio

    asyncio.run(server.run_until_signal(on_ready=_ready))
    if metrics is not None:
        metrics.stop()
    s = gw.stats()
    print(f"[http] drained: {s['counters'].get('queue.completed', 0):.0f} one-shot "
          f"scores ({s['counters'].get('queue.failed', 0):.0f} failed, "
          f"{s['counters'].get('queue.rejected', 0):.0f} rejected), "
          f"{s['counters'].get('pool.stream_steps', 0):.0f} stream-steps over "
          f"{s['counters'].get('pool.admitted', 0):.0f} sessions", flush=True)


def serve_workers(cfg, args) -> None:
    """Run the multi-worker front: ``--workers N`` processes behind one
    ``SO_REUSEPORT`` port, each worker on its own ``--mesh`` placement
    shard, until SIGINT/SIGTERM; then coordinated drain with a per-worker
    summary (smoke asserts every worker exits cleanly, zero dropped).

    The per-worker build is ``workers.default_gateway_factory`` (runs IN
    each worker; with ``--train-steps`` every worker re-fits
    deterministically from the same seed, so all workers serve identical
    params without shipping arrays across processes)."""
    import functools

    from repro.gateway.workers import WorkerFront, default_gateway_factory

    mesh_ways = Placement.from_spec(args.mesh).data_shards if args.mesh else 1
    env = {}
    if mesh_ways > 1 and "XLA_FLAGS" not in os.environ:
        # CPU emulation of a per-worker K-device mesh; on real hardware
        # set XLA_FLAGS yourself and this passthrough stays out of the way
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={mesh_ways}")
    autoscale = parse_autoscale(args.autoscale)
    n_workers = args.workers
    if autoscale:
        # start inside the declared bounds; the autoscaler moves from here
        n_workers = min(max(n_workers, autoscale[0]), autoscale[1])
    front = WorkerFront(
        functools.partial(
            default_gateway_factory, args.arch, args.schedule,
            reduced=args.reduced, train_steps=args.train_steps,
            train_seq_len=args.seq_len, capacity=args.capacity,
            max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
            mesh=mesh_ways, warm_seq_len=args.seq_len,
            priority_classes=args.priority_classes,
            tenant_rate=args.tenant_rate,
        ),
        n_workers=n_workers, host=args.host, port=args.port, env=env,
        store_dir=args.store_dir or None,
        snapshot_interval_ms=args.snapshot_interval_ms,
        event_dir=args.event_dir or None,
        metrics_port=args.metrics_port,
    )
    ccfg = control_cfg_for(args, autoscale=autoscale)
    loop = None
    if ccfg is not None and (ccfg.slo_p95_ms is not None or ccfg.autoscaling):
        from repro.control import ControlLoop

        loop = ControlLoop(front, ccfg, lanes=args.max_batch,
                           model_cfg=cfg.lstm_ae,
                           event_dir=args.event_dir or None)

    def _ready(f) -> None:
        scrape = f" metrics_port={f.metrics.port}" if f.metrics else ""
        control = ""
        if loop is not None:
            loop.start()
            bounds = (f" autoscale={autoscale[0]}:{autoscale[1]}"
                      if autoscale else "")
            control = (f" slo_p95_ms={args.slo_p95_ms}{bounds} "
                       f"priority_classes={args.priority_classes}")
        print(f"[workers] listening on {f.host}:{f.port}{scrape} "
              f"protocols=bp1+json workers={n_workers} mesh={mesh_ways}xdata "
              f"(schedule={args.schedule}, capacity={args.capacity} and "
              f"max_batch={args.max_batch} per worker){control}", flush=True)

    summary = front.run_until_signal(on_ready=_ready)
    c = summary["counters"]
    print(f"[workers] drained: {summary['clean_exits']}/{summary['workers']} "
          f"workers exited cleanly, {summary['dropped_tickets']} dropped "
          f"tickets, {c.get('queue.completed', 0):.0f} one-shot scores "
          f"({c.get('queue.failed', 0):.0f} failed, "
          f"{c.get('queue.rejected', 0):.0f} rejected), "
          f"{c.get('pool.stream_steps', 0):.0f} stream-steps over "
          f"{c.get('pool.admitted', 0):.0f} sessions, "
          f"restarts={summary['restarts']}, "
          f"sessions_migrated={summary.get('sessions_migrated', 0)}, "
          f"sessions_lost={summary['sessions_lost']}", flush=True)


def serve_lm(cfg, args) -> None:
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    b, s = args.batch, args.seq_len
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.family == "whisper":
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(2), (b, cfg.encoder_seq_len, cfg.d_model), jnp.bfloat16)
    if cfg.frontend == "vision_stub":
        batch["image_embeds"] = jax.random.normal(
            jax.random.PRNGKey(3), (b, cfg.vision_patches, cfg.d_model), jnp.bfloat16)

    t0 = time.perf_counter()
    logits, prefill_state = jax.jit(lambda p, bt: api.prefill(p, bt))(params, batch)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    cache = api.init_cache(b, s + args.decode_tokens)
    first = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
    t0 = time.perf_counter()
    out_tokens, _ = jax.jit(
        lambda p, c, f: greedy_decode_loop(api, p, c, f, jnp.int32(s), args.decode_tokens)
    )(params, cache, first)
    jax.block_until_ready(out_tokens)
    t_decode = time.perf_counter() - t0
    print(f"[serve] {cfg.name}: prefill({b}x{s})={t_prefill*1e3:.1f}ms, "
          f"{args.decode_tokens} tokens decoded in {t_decode*1e3:.1f}ms "
          f"({b*args.decode_tokens/t_decode:,.0f} tok/s)")
    print(f"[serve] sample continuation: {out_tokens[0, :8].tolist()}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--decode-tokens", type=int, default=16)
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--schedule", default="wavefront", choices=available_schedules(),
                    help="LSTM-AE execution schedule (engine registry name)")
    ap.add_argument("--mesh", default=None, metavar="data=N",
                    help="device placement, e.g. 'data=2': shard gateway "
                         "pool slots and micro-batch rows N-way over the "
                         "data mesh axis (needs N devices; see README "
                         "§Placement)")
    ap.add_argument("--train-steps", type=int, default=0,
                    help="fit+calibrate the detector before serving (LSTM-AE)")
    ap.add_argument("--gateway", action="store_true",
                    help="serve through the streaming gateway (LSTM-AE): "
                         "session pool + micro-batched one-shot queue")
    ap.add_argument("--http", action="store_true",
                    help="serve the gateway over the socket transport "
                         "(bp1 binary frames, JSON-lines fallback) until "
                         "SIGTERM (LSTM-AE); see README §Transport")
    ap.add_argument("--workers", type=int, default=0,
                    help="fork N gateway worker processes sharing one "
                         "SO_REUSEPORT port (implies --http); each worker "
                         "gets its own engine and --mesh placement shard; "
                         "see README §Workers")
    ap.add_argument("--host", default="127.0.0.1",
                    help="transport bind host (--http)")
    ap.add_argument("--port", type=int, default=0,
                    help="transport bind port; 0 picks an ephemeral port "
                         "(printed on the 'listening on' line)")
    ap.add_argument("--capacity", type=int, default=32,
                    help="gateway session-pool slots")
    ap.add_argument("--max-batch", type=int, default=16,
                    help="gateway micro-batch flush size")
    ap.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="gateway micro-batch max queueing delay")
    ap.add_argument("--streams", type=int, default=0,
                    help="gateway logical streams (default 2x capacity)")
    ap.add_argument("--store-dir", default=None,
                    help="enable durable sessions: snapshot pool state into "
                         "this directory and return signed resumption "
                         "tokens on step responses (--http / --workers; "
                         "see README §Durability)")
    ap.add_argument("--snapshot-interval-ms", type=float, default=1000.0,
                    help="durability snapshot cadence (with --store-dir)")
    ap.add_argument("--slo-p95-ms", type=float, default=None,
                    help="declare a p95 one-shot-latency SLO (ms): the "
                         "control plane tunes max_batch/max_wait_ms each "
                         "tick to meet it (--http / --workers; README "
                         "§Control plane)")
    ap.add_argument("--priority-classes", type=int, default=1,
                    help="admission priority classes (1 = flat legacy "
                         "admission).  Clients tag requests with "
                         "'priority' 0..N-1; under overload the HIGHEST "
                         "class number sheds first")
    ap.add_argument("--tenant-rate", type=float, default=None,
                    help="per-tenant token-bucket admission rate "
                         "(requests/s; clients tag requests with "
                         "'tenant')")
    ap.add_argument("--autoscale", default=None, metavar="MIN:MAX",
                    help="with --workers: let the supervisor's control "
                         "loop scale the fleet between MIN and MAX "
                         "workers from measured arrival rate and queue "
                         "saturation (scale-down is a zero-drop "
                         "snapshot-handoff drain)")
    ap.add_argument("--control-tick-s", type=float, default=1.0,
                    help="control-plane tick interval (seconds)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="expose GET /metrics (Prometheus text) on this "
                         "port; 0 picks an ephemeral port (printed as "
                         "metrics_port= on the 'listening on' line).  With "
                         "--workers N the supervisor serves the "
                         "front-aggregated view here and worker i serves "
                         "its own on port+1+i (README §Observability)")
    ap.add_argument("--event-dir", default=None,
                    help="append lifecycle events + sampled request spans "
                         "as JSONL under this directory (one file per "
                         "process; README §Observability)")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full-config", dest="reduced", action="store_false")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if cfg.family == "lstm_ae":
        if args.workers:
            serve_workers(cfg, args)
        elif args.http:
            serve_http(cfg, args)
        elif args.gateway:
            serve_gateway(cfg, args)
        else:
            serve_lstm_ae(cfg, args)
    else:
        serve_lm(cfg, args)


if __name__ == "__main__":
    main()
