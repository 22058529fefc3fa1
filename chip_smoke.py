#!/usr/bin/env python3
"""Drive the served LSTM-AE path once on a TPU and check it against the
plain reference.

    python chip_smoke.py              # one chip: the main serving path
    python chip_smoke.py --chips 4    # only the paths across four chips

One chip: the published ``lstm-ae-f64-d6`` (widths 64/32/16/8/16/32/64)
is fitted and calibrated through ``AnomalyService``, served by a
``GatewayServer`` in this process and driven over the socket by
``GatewayClient`` (a bp1 ``score_many`` burst over three bucket lengths,
streaming sessions through ``step_many``, one JSON-lines request), then
drained.  The same windows are scored on each one-chip schedule.  Every
answer is compared with ``lstm_ae_sequential`` at ``highest`` matmul
precision.

Four chips: a ``--workers 4`` front (launched before this process
touches JAX, one client per worker), the gateway on ``Placement.data(4)``
against the single-device engine, and ``pipelined`` on 4 stages against
``wavefront``.

The last line of stdout is ``{"ok": ..., "device": {"platform", "kind",
"count"}}``; the exit code is 0 only when every phase passed on a TPU.
Under ``JAX_PLATFORMS=cpu`` every phase runs (the rehearsal) and the
platform check fails.
"""
from __future__ import annotations

import argparse
import json
import os
import queue
import signal
import subprocess
import sys
import threading
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
ARCH = "lstm-ae-f64-d6"
FIT_STEPS = 200
FIT_SEQ_LEN = 32
WINDOW_LENS = (12, 30, 64)      # buckets 16, 32, 64 of the batcher's ladder
WINDOWS_PER_LEN = 8
SESSIONS, SESSION_STEPS = 3, 20
CAPACITY, MAX_BATCH = 16, 16

# Served answers may differ from the reference by this much, relative.
# The served path runs its f32 matmuls at the TPU's default precision (one
# bf16 pass per dot); the reference runs at ``highest``.  Rounding the dot
# operands to bf16 in a CPU run of this fitted model moves window scores
# and running errors by at most 7e-5 relative, so 1e-3 leaves 14x
# headroom.  A score is mostly the window's own energy, so a wrong model
# moves it by less than one might think: the ``control`` line checks that
# the unfitted model misses most windows by more than this tolerance, or
# the comparison could not tell the two models apart.
RTOL = 1e-3


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def phase(name: str, fn, failures: list):
    """Run one phase; a failure is recorded, and the later phases still
    run (so one rehearsal shows every fault)."""
    try:
        return fn()
    except Exception as exc:
        traceback.print_exc()
        failures.append(f"{name}: {type(exc).__name__}: {exc}")
        return None


def max_rel(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.all(np.isfinite(got))), "non-finite served value")
    return float(np.max(np.abs(got - want) / np.abs(want)))


# -- data and reference (shared by both modes) ------------------------------

def fit_config():
    from repro.data import TimeseriesConfig

    return TimeseriesConfig(features=64, seq_len=FIT_SEQ_LEN, batch=64)


def make_windows(seed: int) -> list:
    """Seeded windows at each of WINDOW_LENS (some with anomalies), made
    on the host: the four-chip mode sends them before touching JAX."""
    from repro.data import TimeseriesConfig, make_batch_np

    n = WINDOWS_PER_LEN * len(WINDOW_LENS)
    series, _ = make_batch_np(TimeseriesConfig(
        features=64, seq_len=max(WINDOW_LENS), batch=n, anomaly_rate=0.25,
        seed=seed + 1), 0)
    return [series[i, :WINDOW_LENS[i % len(WINDOW_LENS)]] for i in range(n)]


def padded_batch(windows) -> dict:
    """The windows as one masked-score batch, zero-padded to the longest."""
    import numpy as np

    x = np.zeros((len(windows), max(WINDOW_LENS), windows[0].shape[1]),
                 np.float32)
    for i, w in enumerate(windows):
        x[i, :len(w)] = w
    return {"series": x,
            "lengths": np.array([len(w) for w in windows], np.int32)}


def make_sessions(seed: int, count: int = SESSIONS) -> list:
    from repro.data import TimeseriesConfig, make_batch_np

    series, _ = make_batch_np(TimeseriesConfig(
        features=64, seq_len=SESSION_STEPS, batch=count, seed=seed + 2), 0)
    return list(series)


class Reference:
    """``lstm_ae_sequential`` at ``highest`` precision: per-window scores
    and per-step running errors, one program per window length."""

    def __init__(self, params):
        import jax

        from repro.core.lstm import lstm_ae_sequential

        self.params = params
        self._fwd = jax.jit(lstm_ae_sequential)

    def _sq_err(self, windows):
        """(n, T, F) same-length windows -> (n, T) per-step squared error."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        xs = jnp.swapaxes(jnp.asarray(np.stack(windows)), 0, 1)
        with jax.default_matmul_precision("highest"):
            recon = self._fwd(self.params, xs)
        return np.asarray(jnp.swapaxes(jnp.mean((recon - xs) ** 2, axis=2), 0, 1))

    def scores(self, windows) -> list:
        out = [None] * len(windows)
        for length in sorted({len(w) for w in windows}):
            idx = [i for i, w in enumerate(windows) if len(w) == length]
            sq = self._sq_err([windows[i] for i in idx])
            for i, row in zip(idx, sq):
                out[i] = float(row.mean())
        return out

    def running(self, sessions) -> list:
        import numpy as np

        sq = self._sq_err(sessions)
        steps = np.arange(1, sq.shape[1] + 1)
        return [list(np.cumsum(row) / steps) for row in sq]


def fitted_service():
    """The model every launcher serves: seed-0 init, fitted as
    ``serve --train-steps`` fits it (so worker answers can be checked)."""
    from repro.engine import AnomalyService

    svc = AnomalyService(ARCH)
    metrics = svc.fit(fit_config(), FIT_STEPS)
    threshold = svc.calibrate(fit_config())
    log(f"fit {FIT_STEPS} steps: mse={metrics['mse']:.6f} "
        f"threshold={threshold:.6f}")
    return svc


def check_control(ref: Reference, windows) -> None:
    """The unfitted model must miss the fitted reference by more than
    RTOL on most windows, or a wrong model could pass the comparison."""
    import numpy as np

    from repro.engine import AnomalyService

    unfitted = Reference(AnomalyService(ARCH).params)
    miss = [abs(a - b) / b for a, b in
            zip(unfitted.scores(windows), ref.scores(windows))]
    log(f"control: unfitted model misses the reference by median "
        f"{np.median(miss):.3e}, max {max(miss):.3e} (tolerance {RTOL:.0e})")
    check(np.median(miss) > RTOL, "tolerance too loose to tell models apart")


def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


# -- one chip ---------------------------------------------------------------

def run_one_chip(seed: int, failures: list) -> dict:
    from repro.config import get_config
    from repro.engine import build_engine
    from repro.gateway.client import GatewayClient
    from repro.gateway.server import GatewayServer

    device = device_info()
    log(f"device: {device}")
    cfg = get_config(ARCH)
    log(f"{cfg.name}: widths {cfg.lstm_ae.input_features}/"
        + "/".join(str(h) for h in cfg.lstm_ae.layer_sizes()))

    svc = fitted_service()
    windows = make_windows(seed)
    sessions = make_sessions(seed)
    ref = Reference(svc.params)
    want_scores = ref.scores(windows)
    want_running = ref.running(sessions)
    deviations: dict[str, float] = {}

    # -- served over the socket ---------------------------------------------
    gw = svc.open_gateway(capacity=CAPACITY, max_batch=MAX_BATCH,
                          max_wait_ms=5.0)
    server = GatewayServer(gw, port=0)
    host, port = server.start_in_thread()
    answered = {"n": 0}

    def served():
        try:
            with GatewayClient(host, port) as client:
                check(client.protocol == "bp1", "bp1 did not negotiate")
                scores = client.score_many(windows, windows_per_frame=8)
                answered["n"] += len(scores)
                deviations["bp1 score_many"] = max_rel(scores, want_scores)
            running = []
            clients = [GatewayClient(host, port) for _ in sessions]
            try:
                half = SESSION_STEPS // 2
                for client, xs in zip(clients, sessions):
                    running.append(client.step_many(xs[:half]))
                for client, xs, got in zip(clients, sessions, running):
                    got.extend(client.step_many(xs[half:]))
                    answered["n"] += len(got)
                    client.end_session()
            finally:
                for client in clients:
                    client.close()
            deviations["step_many running errors"] = max_rel(running,
                                                             want_running)
            with GatewayClient(host, port, protocol="json") as client:
                check(client.protocol == "json", "json fallback")
                score = client.score(windows[0])
                answered["n"] += 1
                deviations["json score"] = max_rel([score], want_scores[:1])
        finally:
            server.stop_in_thread()

    phase("serve", served, failures)
    stats = gw.stats()
    counters = stats["counters"]
    expected = len(windows) + SESSIONS * SESSION_STEPS + 1
    log(f"requests answered: {answered['n']}/{expected}")
    log(f"queue.failed: {counters.get('queue.failed', 0):.0f}, "
        f"queue.completed: {counters.get('queue.completed', 0):.0f}")
    profile = gw.engine.profile_info()
    log(f"engine profile ({profile['schedule']}): {profile['compiles']} "
        f"compiles, {profile['compile_ms']:.1f} compile ms")
    # stop_in_thread raises (failing "serve") when the drain times out
    drained = gw.batcher.queue_depth == 0 and gw.pool.active == 0
    log(f"drain: {'clean' if drained else 'INCOMPLETE'} (queue_depth="
        f"{gw.batcher.queue_depth}, active_streams={gw.pool.active})")
    if answered["n"] != expected:
        failures.append(f"answered {answered['n']} of {expected} requests")
    if counters.get("queue.failed", 0):
        failures.append(f"queue.failed={counters['queue.failed']:.0f}")
    if not drained:
        failures.append("drain did not complete")

    # -- every one-chip schedule on the same windows --------------------------
    batch = padded_batch(windows)
    for name in ("sequential", "wavefront", "fused"):
        def scheduled(name=name):
            engine = build_engine(cfg, name, params=svc.params)
            got = engine.score_masked(batch)
            line = f"schedule {engine.schedule.tag}"
            if name == "fused":
                text = engine._score_masked.lower(
                    svc.params, batch["series"], batch["lengths"]
                ).compile().as_text()
                kernel = "tpu_custom_call" in text
                line += f": tpu_custom_call={kernel}"
                if device["platform"] == "tpu":
                    check(kernel, "fused compiled without its Pallas kernel")
            log(line)
            deviations[f"schedule {name}"] = max_rel(got, want_scores)

        phase(f"schedule {name}", scheduled, failures)

    phase("control", lambda: check_control(ref, windows), failures)
    report_deviations(deviations, failures)
    return device


def report_deviations(deviations: dict, failures: list) -> None:
    for name, dev in deviations.items():
        log(f"max rel deviation, {name}: {dev:.3e}")
        if not dev <= RTOL:
            failures.append(f"{name} deviates {dev:.3e} > {RTOL:.0e}")
    if deviations:
        log(f"largest deviation: {max(deviations.values()):.3e} "
            f"(tolerance {RTOL:.0e})")


# -- four chips -------------------------------------------------------------

def run_worker_front(seed: int, n_workers: int) -> dict:
    """Launch ``serve --workers N`` in a subprocess and drive it with one
    client per worker.  Touches no JAX: this process must not hold a chip
    while the workers boot.  Returns what the workers answered."""
    from repro.gateway.client import GatewayClient

    from jax._src.xla_bridge import backends_are_initialized

    env = dict(os.environ, PYTHONPATH=str(SRC))
    cmd = [sys.executable, "-m", "repro.launch.serve", "--arch", ARCH,
           "--full-config", "--http", "--workers", str(n_workers),
           "--capacity", str(CAPACITY), "--max-batch", str(MAX_BATCH),
           "--seq-len", str(FIT_SEQ_LEN), "--train-steps", str(FIT_STEPS)]
    # own process group: the supervisor and its workers go down together
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE, text=True,
                            start_new_session=True)
    lines: queue.Queue = queue.Queue()
    threading.Thread(target=lambda: [lines.put(l) for l in proc.stdout],
                     daemon=True).start()

    def wait_for(prefix: str, timeout: float) -> str:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                line = lines.get(timeout=1.0)
            except queue.Empty:
                check(proc.poll() is None,
                      f"front exited with {proc.returncode} before {prefix!r}")
                continue
            print(line, end="", flush=True)
            if line.startswith(prefix):
                return line
        raise SmokeFailure(f"no {prefix!r} line after {timeout:.0f}s")

    clients: list = []
    try:
        ready = wait_for("[workers] listening on", 900.0)
        host, port = ready.split()[3].rsplit(":", 1)
        sessions = make_sessions(seed, n_workers)
        windows = [w for w in make_windows(seed) if len(w) <= FIT_SEQ_LEN]
        # The kernel picks each connection's worker: keep connecting until
        # every worker holds one session, and note which client is whose.
        mine: dict[int, tuple] = {}  # worker index -> (client, session)
        active: dict[int, int] = {}
        while len(mine) < n_workers and len(clients) < 6 * n_workers:
            client = GatewayClient(host, int(port))
            clients.append(client)
            xs = sessions[len(mine)]
            client.step(xs[0])
            per_worker = client.stats()["per_worker"]
            grew = [w["index"] for w in per_worker
                    if w["active_streams"] > active.get(w["index"], 0)]
            active = {w["index"]: w["active_streams"] for w in per_worker}
            if len(grew) == 1 and grew[0] not in mine:
                mine[grew[0]] = (client, xs)
        check(len(mine) == n_workers,
              f"{len(clients)} connections reached only workers {sorted(mine)}")
        answers = {"scores": [], "windows": windows, "running": [],
                   "sessions": []}
        for index, (client, xs) in sorted(mine.items()):
            # the session's first step went out while finding its worker
            running = [None] + client.step_many(xs[1:])
            answers["running"].append(running)
            answers["sessions"].append(xs)
            answers["scores"].append(client.score_many(windows))
        stats = clients[0].stats()
        answers["workers"] = [{"index": w["index"], "pid": w["pid"],
                               "device": w.get("device")}
                              for w in stats["per_worker"]]
        check(not backends_are_initialized(),
              "this process touched JAX while the workers held the chips")
        for client in clients:
            client.close()
        clients = []
        proc.send_signal(signal.SIGTERM)
        drained = wait_for("[workers] drained:", 300.0)
        check(f"{n_workers}/{n_workers} workers exited cleanly, 0 dropped "
              f"tickets" in drained, f"unclean front drain: {drained.strip()}")
        check(proc.wait(60) == 0, f"front exited with {proc.returncode}")
        return answers
    finally:
        for client in clients:
            client.close()
        if proc.poll() is None:  # failed midway: drain, then force
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(120)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(proc.pid, signal.SIGKILL)  # any worker left behind
        except ProcessLookupError:
            pass
        proc.wait(30)


def run_four_chips(seed: int, failures: list) -> dict:
    answers = phase("workers", lambda: run_worker_front(seed, 4), failures)

    # only now does this process touch a JAX backend
    from repro.config import get_config
    from repro.engine import EngineConfig, Placement, build_engine

    device = device_info()
    log(f"device: {device}")
    cfg = get_config(ARCH)
    svc = fitted_service()
    ref = Reference(svc.params)
    windows = make_windows(seed)
    sessions = make_sessions(seed)
    deviations: dict[str, float] = {}

    def workers():
        for w in answers["workers"]:
            log(f"worker {w['index']} (pid {w['pid']}): {w['device']}")
        if device["platform"] == "tpu":
            devs = [w["device"] for w in answers["workers"]]
            check(len(devs) == 4 and all(
                d and d["platform"] == "tpu" and d["count"] == 1
                for d in devs), "a worker is not confined to one chip")
        want = ref.scores(answers["windows"])
        deviations["workers score_many"] = max(
            max_rel(got, want) for got in answers["scores"])
        want_run = ref.running(answers["sessions"])
        deviations["workers step running errors"] = max(
            max_rel(got[1:], w[1:])
            for got, w in zip(answers["running"], want_run))

    def data4():
        single = svc.open_gateway(capacity=CAPACITY, max_batch=MAX_BATCH)
        sharded = svc.open_gateway(capacity=CAPACITY, max_batch=MAX_BATCH,
                                   placement=Placement.data(4))
        log(f"gateway on {sharded.placement!r}: "
            f"{sharded.stats()['placement']}")
        deviations["Placement.data(4) scores vs single device"] = max_rel(
            sharded.score(windows), single.score(windows))
        runs = []
        for gw in (single, sharded):
            for s in range(len(sessions)):
                gw.admit(s)
            steps = [gw.step({s: xs[t] for s, xs in enumerate(sessions)})
                     for t in range(SESSION_STEPS)]
            runs.append([[step[s] for step in steps]
                         for s in range(len(sessions))])
        deviations["Placement.data(4) running errors vs single device"] = \
            max_rel(runs[1], runs[0])

    def pipelined():
        batch = padded_batch(windows)
        pipe = build_engine(cfg, EngineConfig(schedule="pipelined",
                                              n_stages=4), params=svc.params)
        log(f"schedule {pipe.schedule.tag}")
        check(pipe.schedule.resolved == "pipelined",
              f"pipelined resolved to {pipe.schedule.resolved}")
        wave = build_engine(cfg, "wavefront", params=svc.params)
        deviations["pipelined(4 stages) vs wavefront"] = max_rel(
            pipe.score_masked(batch), wave.score_masked(batch))

    if answers is not None:
        phase("workers check", workers, failures)
    phase("Placement.data(4)", data4, failures)
    phase("pipelined", pipelined, failures)
    report_deviations(deviations, failures)
    return device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the paths across four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the windows and sessions sent")
    args = ap.parse_args()
    if not (SRC / "repro" / "__init__.py").is_file():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.utils.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    failures: list[str] = []
    run = run_four_chips if args.chips == 4 else run_one_chip
    device = phase(f"--chips {args.chips}", lambda: run(args.seed, failures),
                   failures)
    if device is not None and device["platform"] != "tpu":
        failures.append(f"platform is {device['platform']!r}, not 'tpu'")
    if device is not None and device["count"] != args.chips:
        failures.append(f"{device['count']} devices, expected {args.chips}")
    for f in failures:
        log(f"FAILED {f}")
    ok = not failures
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
