#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a model
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``, see ``bench/traffic.py``).  The run:

1. builds the system under test in this process: the benchmark's weights
   made on the chip from ``--seed``, an ``AnomalyService`` on the default
   schedule serving them, its gateway opened with the mix's knobs, and
   ``GatewayServer`` on loopback;
2. warms the shapes the mix uses (the score buckets of its windows; the
   pool step through each stream's first frame) and starts the load
   generator (``bench/loadgen.py``, a process that never imports JAX);
3. measures a window of ``--seconds``; with ``--trace 1`` the profiler
   traces part of it;
4. after the window, compares every answer with the plain reference
   (``bench/reference.py``) and prints one JSON line with the cell's
   end-to-end metrics (``--trace 0``) or per-layer metrics (``--trace
   1``), each read by its own file ``bench/metrics/<metric>.py``
   (``bench/readings.py``).

Off a TPU it refuses to run (exit 1, no result).  ``--rehearse`` runs the
same path on any backend and reports no metric: the CPU rehearsal.
``--control`` compares the bfloat16 control in the program's place (used
to set the limits; the benchmark's own runs do not run it).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import bp1  # noqa: E402
import flops  # noqa: E402
import series  # noqa: E402
import traffic as traffic_mod  # noqa: E402

#: seconds between the start of the window and the start of the trace,
#: and the longest traced stretch (traces of a whole window are large)
TRACE_LEAD_S, TRACE_MAX_S = 1.0, 2.0
#: seconds given to the answers still due after the window closes
DRAIN_S = 60.0


class BenchError(RuntimeError):
    """The run cannot produce a result (exit 1, no result line)."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def log_setup(stage: str) -> None:
    log(f"set-up: {stage} at {time.monotonic() - T_START:.2f} s")


# -- the cell ---------------------------------------------------------------

def load_cell(workload: str) -> dict:
    """Everything the run needs, found by name from ``BENCHMARK.json``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())

    def here(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "name": workload, "chips": int(cell["chips"]), "config": config,
        "traffic": traffic_mod.load(cell["traffic"]),
        "limits": json.loads((BENCH / "limits" / f"{workload}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if here(m)],
        "per_layer": [m for m in bench["per_layer"] if here(m)],
    }


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def set_up_environment() -> None:
    """Compile cache at a fixed path inside the checkout, every compile
    kept (the program's compiles are mostly under JAX's default 1 s
    threshold); the program's sources on the path."""
    if not (ROOT / "src" / "repro" / "__init__.py").is_file():
        raise BenchError(f"no program under {ROOT / 'src'}")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path.insert(0, str(ROOT / "src"))


def load_peaks(kind: str) -> dict | None:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    return table.get(kind)


# -- the system under test ----------------------------------------------------

class _CompileCounter:
    """Backend compiles in this process, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.count = 0

        def listener(event: str, *_args, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listener)


class OneChipSystem:
    """The gateway served by ``GatewayServer`` in this process."""

    def __init__(self, cell: dict, seed: int, rehearse: bool):
        import jax

        devices = jax.devices()
        self.device = {"platform": devices[0].platform,
                       "kind": devices[0].device_kind, "count": len(devices)}
        log_setup("JAX backend up")
        check_device(self.device, cell["chips"], rehearse)
        self.compiles = _CompileCounter()
        from reference import make_params

        from repro.engine import AnomalyService
        from repro.gateway.queue import bucket_for
        from repro.gateway.server import GatewayServer

        cfg, mix = cell["config"], cell["traffic"]
        self.params = make_params(seed, cfg["input_features"], cfg["depth"])
        log_setup("weights made")
        self.svc = AnomalyService(cfg["arch"])
        self.svc.recalibrate(params=self.params)
        self.gw = self.svc.open_gateway(**mix["gateway"])
        if "control" in mix:
            from repro.control import ControlConfig, enable_control

            enable_control(self.gw, ControlConfig(**mix["control"]))
        log_setup("gateway open")
        # one flush per bucket the mix's windows fall in: each compiles its
        # (lanes, bucket, F) program before the window
        buckets = {bucket_for(int(t))
                   for lengths in traffic_mod.group_lengths(mix, seed).values()
                   for t in lengths}
        for tb in sorted(buckets):
            self.gw.score([np.zeros((tb, cfg["input_features"]), np.float32)])
        log_setup(f"{len(buckets)} score buckets warm")
        self.server = GatewayServer(self.gw, port=0)
        self.host, self.port = self.server.start_in_thread()
        log_setup("server listening")
        self._capture = None

    def backend_compiles(self) -> int:
        return self.compiles.count

    def trace_start(self, directory: str) -> None:
        from devtrace import Capture

        self._capture = Capture(directory)

    def trace_stop(self) -> None:
        self._capture.stop()

    def trace_read(self, window_s: float) -> dict:
        return self._capture.read(window_s)

    def memory_peak(self) -> int:
        import jax

        mem = jax.devices()[0].memory_stats() or {}
        return int(mem.get("peak_bytes_in_use", 0))

    def stop(self) -> None:
        self.server.stop_in_thread()
        self.server = self.gw = self.svc = None


def check_device(device: dict, chips: int, rehearse: bool) -> None:
    if rehearse:
        return
    if device["platform"] != "tpu":
        raise BenchError(f"no TPU: JAX runs on {device['platform']!r}")
    if device["count"] < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX sees {device['count']}")
    if load_peaks(device["kind"]) is None:
        raise BenchError(f"device kind {device['kind']!r} is not in bench/peaks.json")


# -- the load generator -----------------------------------------------------------

class Generator:
    """``bench/loadgen.py`` in a child process."""

    def __init__(self, spec: dict, workdir: str):
        self.out = os.path.join(workdir, "answers.npz")
        path = os.path.join(workdir, "loadgen.json")
        Path(path).write_text(json.dumps(dict(spec, out=self.out, drain_s=DRAIN_S)))
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        self.proc = subprocess.Popen(
            [sys.executable, str(BENCH / "loadgen.py"), path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)

    def _expect(self, word: str) -> str:
        line = self.proc.stdout.readline()
        if not line.startswith(word):
            raise BenchError(f"load generator said {line!r}, expected {word}")
        return line[len(word):].strip()

    def ready(self) -> None:
        self._expect("READY")

    def go(self, t0: float, t1: float) -> None:
        self.proc.stdin.write(f"GO {t0!r} {t1!r}\n")
        self.proc.stdin.flush()

    def done(self) -> dict:
        summary = json.loads(self._expect("DONE"))
        self.proc.wait(30)
        return summary

    def answers(self) -> dict:
        with np.load(self.out) as got:
            return {k: got[k] for k in got.files}

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(30)


# -- after the window -----------------------------------------------------------

def engine_compiles(stats: dict) -> int:
    """The engine's compile count in a ``stats`` answer."""
    return int(stats["engine"]["compiles"])


def window_lengths(cell: dict, ids: np.ndarray) -> np.ndarray:
    """True length of each stored window id."""
    lengths = traffic_mod.group_lengths(cell["traffic"], cell["seed"])
    group, index = np.divmod(ids, traffic_mod.WINDOW_STRIDE)
    return np.array([lengths[int(g)][int(w)] for g, w in zip(group, index)],
                    np.int64)


def useful_work(cell: dict, rec: dict, mask: np.ndarray) -> dict:
    """Useful work of the answers in ``mask`` (``bench/flops.py``)."""
    cfg = cell["config"]
    step = mask & (rec["op"] == traffic_mod.OPS["step"])
    score = mask & (rec["op"] == traffic_mod.OPS["score"])
    rows = {"step": int(step.sum()),
            "score": int(window_lengths(cell, rec["key0"][score]).sum())}
    total = {"requests": int(step.sum() + score.sum()),
             "row_timesteps": rows["step"] + rows["score"], "flops": 0, "bytes": 0}
    for op, n in (("step", int(step.sum())), ("score", int(score.sum()))):
        fl, nb = flops.useful_work(cfg["input_features"], cfg["depth"], op,
                                   rows[op], n)
        total["flops"] += fl
        total["bytes"] += nb
    return total


def _anomaly_rates(mix: dict, chips: int) -> dict:
    """-> (op, stream or window group) -> the anomaly rate of its data."""
    rates = {}
    for plan in traffic_mod.connections(mix, chips):
        key = plan.get("stream", plan["group"])
        rates[(plan["op"], key)] = plan["anomaly_rate"]
    return rates


def reference_answers(cell: dict, rec: dict, params) -> callable:
    """-> ``at(precision)``: the reference's answer to every recorded
    value, computed at ``precision`` (see ``bench/reference.py``)."""
    import reference

    cfg, mix, seed = cell["config"], cell["traffic"], cell["seed"]
    feats = cfg["input_features"]
    rates = _anomaly_rates(mix, cell["chips"])
    step = np.flatnonzero(rec["op"] == traffic_mod.OPS["step"])
    score = np.flatnonzero(rec["op"] == traffic_mod.OPS["score"])
    counts: dict = {}
    for s, t in zip(rec["key0"][step], rec["key1"][step]):
        counts[int(s)] = max(counts.get(int(s), 0), int(t) + 1)
    samples = [series.stream_samples(seed, s, n, feats, rates[("step", s)])
               for s, n in counts.items()]
    pos = {s: i for i, s in enumerate(counts)}
    where = [(pos[int(s)], int(t)) for s, t in zip(rec["key0"][step],
                                                   rec["key1"][step])]
    ids = np.unique(rec["key0"][score])
    lengths = window_lengths(cell, ids)
    windows = [series.window(seed, int(w), int(n), feats,
                             rates[("score", int(w) // traffic_mod.WINDOW_STRIDE)])
               for w, n in zip(ids, lengths)]
    index = np.searchsorted(ids, rec["key0"][score])

    def at(precision: str) -> np.ndarray:
        out = np.zeros(len(rec["op"]), np.float64)
        if samples:
            run = reference.running_errors(params, samples, precision)
            out[step] = [run[i][t] for i, t in where]
        if windows:
            out[score] = reference.window_scores(params, windows, precision)[index]
        return out
    return at


def compare(cell: dict, rec: dict, params, control: bool) -> dict:
    """Every answer against the reference -> the compared numbers.

    The reference is the plain forward at ``highest`` precision.  An answer
    may lie as far from it as the same forward computed at the precision
    the configuration states does; what lies further is its excess gap,
    ``(|answer - highest| - |stated - highest|) / highest``.  The numbers
    are the largest and the mean excess gap over every answer; the plain
    relative gaps to the reference are reported beside them.  With
    ``control`` the bfloat16 control's answers stand in the program's place.
    """
    at = reference_answers(cell, rec, params)
    truth = at("highest")
    allowed = np.abs(at(cell["config"]["matmul_precision"]) - truth)
    got = at("bfloat16") if control else rec["value"]
    gap = np.abs(got - truth)
    excess = (gap - allowed) / truth
    if not excess.size:
        excess = gap = truth = np.array([np.inf])
    return {"excess_gap_max": float(excess.max()),
            "excess_gap_mean": float(excess.mean()),
            "max_rel_dev": float((gap / truth).max()),
            "mean_rel_dev": float((gap / truth).mean()),
            "compared": int(excess.size)}


# -- one run ----------------------------------------------------------------------

def measure(args, cell: dict, system, workdir: str) -> dict:
    """The load generator up, the window, and what was read in it."""
    trace_dir = os.path.join(workdir, "trace")
    gen = Generator({"mix": cell["traffic"], "seed": args.seed,
                     "chips": cell["chips"],
                     "features": cell["config"]["input_features"],
                     "host": system.host, "port": system.port}, workdir)
    try:
        gen.ready()
        log_setup("load generator connected and warm")
        got = {"stats0": bp1.stats(system.host, system.port),
               "backend0": system.backend_compiles(),
               "setup_s": time.monotonic() - T_START}
        t0 = got["t0"] = time.monotonic() + 0.2
        t1 = got["t1"] = t0 + args.seconds
        gen.go(t0, t1)
        if args.trace:
            time.sleep(max(0.0, t0 + min(TRACE_LEAD_S, args.seconds / 4)
                           - time.monotonic()))
            system.trace_start(trace_dir)
            tr0 = got["tr0"] = time.monotonic()
            time.sleep(max(0.0, min(tr0 + TRACE_MAX_S, t1 - 0.1) - time.monotonic()))
            got["tr1"] = time.monotonic()
            system.trace_stop()
        time.sleep(max(0.0, t1 - time.monotonic()))
        got["stats1"] = bp1.stats(system.host, system.port)
        got["backend1"] = system.backend_compiles()
        got["summary"] = gen.done()
        got["answers"] = gen.answers()
        got["trace"] = (system.trace_read(got["tr1"] - got["tr0"])
                        if args.trace else {})
    finally:
        gen.stop()
    got["memory_peak"] = system.memory_peak()
    return got


def reading_context(cell: dict, got: dict, peaks) -> dict:
    """What a metric reader is handed (``bench/readings.py``)."""
    from devtrace import reduce

    rec, t0, t1 = got["answers"], got["t0"], got["t1"]
    window = rec["phase"] == 1
    ctx = {"setup_s": got["setup_s"], "t0": t0, "t1": t1, "window_s": t1 - t0,
           "answers": rec, "stats0": got["stats0"], "stats1": got["stats1"],
           "chips": cell["chips"], "peaks": peaks,
           "work": useful_work(cell, rec, window & (rec["t_recv"] >= t0)
                               & (rec["t_recv"] < t1)),
           "trace": reduce(got["trace"]) if got["trace"] else {}}
    if "tr0" in got:
        ctx["trace_work"] = useful_work(cell, rec, window
                                        & (rec["t_recv"] >= got["tr0"])
                                        & (rec["t_recv"] < got["tr1"]))
    return ctx


def read_metrics(defs: list, ctx: dict) -> dict:
    """Each metric by its reader; a reader that finds nothing is left out."""
    metrics = {}
    for m in defs:
        value = load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def run(args) -> dict:
    cell = load_cell(args.workload)
    cell["seed"] = args.seed
    set_up_environment()
    workdir = tempfile.mkdtemp(prefix="bench-")
    os.makedirs(os.path.join(workdir, "trace"))
    try:
        system = OneChipSystem(cell, args.seed, args.rehearse)
        try:
            got = measure(args, cell, system, workdir)
        except BaseException:
            system.stop()
            raise
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    device, params = system.device, system.params
    system.stop()
    del system

    rec, summary = got["answers"], got["summary"]
    log(f"load generator: cpu busy {summary['cpu_busy_pct']:.1f}% of the "
        f"window, {summary['errors']} errors, {summary['unanswered']} unanswered")
    for sample in summary["error_samples"]:
        log(f"  error answer: {sample}")
    log(f"compiles inside the window: engine "
        f"{engine_compiles(got['stats1']) - engine_compiles(got['stats0'])}, "
        f"backend {got['backend1'] - got['backend0']}")
    failed = summary["errors"] + summary["unanswered"]
    log(f"window: {summary['sent']} frames sent in {args.seconds} s, "
        f"{failed} failed")

    t_ref = time.monotonic()
    numbers = compare(cell, rec, params, args.control)
    log(f"reference: {numbers['compared']} answers compared in "
        f"{time.monotonic() - t_ref:.1f} s"
        + (" (bfloat16 control in the program's place)" if args.control else ""))
    log("gaps to the reference: " + ", ".join(
        f"{k} {v!r}" for k, v in numbers.items() if k != "compared"))
    checks = {k: {"value": numbers[k], "limit": cell["limits"][k]}
              for k in sorted(cell["limits"])}
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checks.values())

    metrics: dict = {}
    breakdown = None
    if not args.rehearse:
        ctx = reading_context(cell, got, load_peaks(device["kind"]))
        metrics = read_metrics(cell["per_layer" if args.trace else "end_to_end"], ctx)
        if ctx["trace"]:
            device = dict(device, busy_s=ctx["trace"]["busy_s"],
                          window_s=ctx["trace"]["window_s"])
            breakdown = {"device_ops": ctx["trace"]["device_ops"],
                         "idle_gaps": ctx["trace"]["idle_gaps"]}
    result = {"correct": bool(correct), "attempted": int(summary["sent"]),
              "failed": int(failed), "metrics": metrics,
              "device": dict(device, memory_peak_bytes=got["memory_peak"])}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on any backend and report no metric")
    ap.add_argument("--control", action="store_true",
                    help="compare the bfloat16 control in the program's place")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    try:
        result = run(args)
    except BenchError as exc:
        log(f"FAILED: {exc}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
