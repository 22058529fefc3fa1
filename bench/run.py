#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a model
configuration (``bench/configs/<config>.json``), whose ``family`` (default
``lstm_ae``) names its adapter (``bench/families/<family>.py``), and a
traffic mix (``bench/traffic/<mix>.json``, see ``bench/traffic.py``).
The run:

1. builds the system under test (``bench/replica.py``): the benchmark's
   weights made on the chip from ``--seed``, the family's service serving
   them, its gateway opened with the mix's knobs.  A one-chip cell serves
   it from ``GatewayServer`` in this process; a cell on more chips from a
   ``WorkerFront`` of one one-chip worker a chip behind one port, with
   this process kept off JAX until the workers have ended;
2. warms the shapes the mix uses (the score buckets of its windows; the
   pool step through each stream's first frame) and starts the load
   generators (``bench/loadgen.py``, processes that never import JAX),
   one a chip, each driving its own slice of the mix's connections;
3. measures a window of ``--seconds``; with ``--trace 1`` the profiler
   traces part of it, on every chip;
4. after the window, compares every answer with the family's plain
   reference and prints one JSON line with the cell's end-to-end metrics
   (``--trace 0``) or per-layer metrics (``--trace 1``), each read by its
   own file ``bench/metrics/<metric>.py`` (``bench/readings.py``).

Off a TPU, or with fewer chips than the cell asks for, it refuses to run
(exit 1, no result).  ``--rehearse`` runs the same path on any backend
and reports no metric: the CPU rehearsal.  ``--control`` compares the
control (the reference at the configuration's ``control_precision``) in
the program's place, and logs the program's own numbers beside it (used
to set the limits; the benchmark's own runs do not run it).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import functools  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import bp1  # noqa: E402
import families  # noqa: E402
import replica  # noqa: E402
import traffic as traffic_mod  # noqa: E402

#: seconds between the start of the window and the start of the trace,
#: and the longest traced stretch (traces of a whole window are large)
TRACE_LEAD_S, TRACE_MAX_S = 1.0, 2.0
#: seconds given to the answers still due after the window closes
DRAIN_S = 60.0
#: seconds a front's workers may take to come up (the first run of a cell
#: in a checkout compiles)
FRONT_READY_S = 900.0


class BenchError(RuntimeError):
    """The run cannot produce a result (exit 1, no result line)."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def log_setup(stage: str) -> None:
    log(f"set-up: {stage} at {time.monotonic() - T_START:.2f} s")


def log_after(stage: str) -> None:
    log(f"after the window: {stage} at {time.monotonic() - T_START:.2f} s")


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
    family_file = families.path(config.get("family", families.DEFAULT))

    def here(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "name": workload, "chips": int(cell["chips"]), "config": config,
        "family": families.load_file(family_file), "family_file": str(family_file),
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

class OneChipSystem:
    """The gateway served by ``GatewayServer`` in this process."""

    #: load generator processes
    generators = 1

    def __init__(self, cell: dict, seed: int, rehearse: bool):
        import jax

        devices = jax.devices()
        self.device = {"platform": devices[0].platform,
                       "kind": devices[0].device_kind, "count": len(devices)}
        log_setup("JAX backend up")
        check_device(self.device, cell["chips"], rehearse)
        self.compiles = replica.CompileCounter()
        from repro.gateway.server import GatewayServer

        self.gw, self.params = replica.build(cell["family"], cell["config"],
                                             cell["traffic"], seed, log_setup)
        self.server = GatewayServer(self.gw, port=0)
        self.host, self.port = self.server.start_in_thread()
        log_setup("server listening")
        self._capture = None

    def stats(self) -> dict:
        return bp1.stats(self.host, self.port)

    def compiles_in(self, stats: dict) -> tuple:
        """-> (engine compiles, backend compiles) so far."""
        return int(stats["engine"]["compiles"]), self.compiles.count

    def trace_start(self, directory: str) -> None:
        from devtrace import Capture

        self._capture = Capture(directory)

    def trace_stop(self) -> None:
        self._capture.stop()

    def trace_read(self, window_s: float) -> dict:
        return self._capture.read(window_s)

    def memory_peak(self) -> int:
        return replica.memory_peak()

    def stop(self) -> None:
        self.server.stop_in_thread()
        self.server = self.gw = None

    def reference_params(self):
        return self.params


class FrontSystem:
    """A ``WorkerFront`` of one one-chip worker a chip behind one port, each
    worker's gateway built by ``replica.worker_gateway``.  This process
    stays off JAX until the workers have ended: a chip belongs to one
    process."""

    def __init__(self, cell: dict, seed: int, rehearse: bool,
                 trace_dir: str | None, claims_dir: str):
        from repro.gateway.claims import host_tpu_chips
        from repro.gateway.workers import WorkerFront

        chips = cell["chips"]
        self.offered = [] if rehearse else host_tpu_chips()
        if not rehearse and len(self.offered) < chips:
            raise BenchError(f"the cell needs {chips} TPU chips; the host "
                             f"offers {len(self.offered) or 'no TPU'}")
        self.cell, self.seed, self.generators = cell, seed, chips
        self.trace_dir = trace_dir
        factory = functools.partial(replica.worker_gateway, cell["family_file"],
                                    cell["config"], cell["traffic"], seed,
                                    trace_dir)
        self.front = WorkerFront(factory, n_workers=chips, respawn=False,
                                 device_claims={i: [i] for i in range(chips)},
                                 claims_dir=claims_dir)
        try:
            self.host, self.port = self.front.start(ready_timeout=FRONT_READY_S)
        except RuntimeError as exc:
            raise BenchError(f"the worker front did not start: {exc}") from exc
        workers = self.stats()["per_worker"]
        log_setup(f"{len(workers)} workers serving")
        devices = [w["device"] for w in workers]
        if len(workers) != chips or any(d["count"] != 1 for d in devices):
            self.stop()
            raise BenchError(f"the front's workers see {devices}; the cell "
                             f"needs {chips} workers of one chip each")
        self.device = {"platform": devices[0]["platform"],
                       "kind": devices[0]["kind"], "count": len(devices)}
        try:
            check_device(self.device, chips, rehearse)
        except BenchError:
            self.stop()
            raise

    def stats(self) -> dict:
        return self.front.stats()

    @staticmethod
    def compiles_in(stats: dict) -> tuple:
        workers = stats["per_worker"]
        return (sum(int(w["engine"]["compiles"]) for w in workers),
                sum(int(w["bench"]["backend_compiles"]) for w in workers))

    def _signal_workers(self, sig: int, marker: str) -> None:
        """Send ``sig`` to every worker and wait for its trace marker."""
        pids = self.front.worker_pids()
        for pid in pids:
            os.kill(pid, sig)
        deadline = time.monotonic() + 60.0
        for pid in pids:
            path = os.path.join(self.trace_dir, f"{marker}-{pid}")
            while not os.path.exists(path):
                if time.monotonic() > deadline:
                    raise BenchError(f"worker {pid} did not write {marker}")
                time.sleep(0.005)

    def trace_start(self, directory: str) -> None:
        if directory != self.trace_dir:
            raise BenchError(f"the workers trace into {self.trace_dir}")
        self._signal_workers(signal.SIGUSR1, "started")

    def trace_stop(self) -> None:
        self._signal_workers(signal.SIGUSR2, "done")

    def trace_read(self, window_s: float) -> dict:
        """Every worker's trace, its device planes named by the worker."""
        from devtrace import read_xspace

        devices = {}
        for path in sorted(glob.glob(os.path.join(self.trace_dir, "worker-*"))):
            worker = os.path.basename(path)
            for plane, entry in read_xspace(path, window_s)["devices"].items():
                devices[f"{worker}{plane}"] = entry
        return {"window_s": float(window_s), "devices": devices}

    def memory_peak(self) -> int:
        """The peak of the fullest chip."""
        return max(int(w["bench"]["memory_peak_bytes"])
                   for w in self.stats()["per_worker"])

    def stop(self) -> None:
        if self.front is not None:
            summary = self.front.shutdown()
            log(f"front drained: {summary['clean_exits']} of {summary['workers']} "
                f"workers clean, {summary['dropped_tickets']} tickets dropped")
            self.front = None

    def reference_params(self):
        """The weights every worker made, made again here.  The workers
        have ended, so this process may take a chip; it takes one, which
        the reference needs, and comes up faster than on all of them."""
        if self.offered:
            from repro.gateway.claims import tpu_worker_env

            os.environ.update(tpu_worker_env(self.offered[0], free_port(),
                                             free_port()))
        return self.cell["family"].make_params(self.seed, self.cell["config"])


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def check_device(device: dict, chips: int, rehearse: bool) -> None:
    if rehearse:
        return
    if device["platform"] != "tpu":
        raise BenchError(f"no TPU: JAX runs on {device['platform']!r}")
    if device["count"] < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX sees {device['count']}")
    if load_peaks(device["kind"]) is None:
        raise BenchError(f"device kind {device['kind']!r} is not in bench/peaks.json")


# -- the load generators ----------------------------------------------------------

class Generator:
    """``bench/loadgen.py`` in a child process, driving part ``part`` of
    ``parts`` of the mix's connections."""

    def __init__(self, spec: dict, workdir: str, part: int, parts: int):
        self.out = os.path.join(workdir, f"answers-{part}.npz")
        path = os.path.join(workdir, f"loadgen-{part}.json")
        Path(path).write_text(json.dumps(dict(spec, out=self.out, drain_s=DRAIN_S,
                                              part=part, parts=parts)))
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

def window_lengths(cell: dict, ids: np.ndarray) -> np.ndarray:
    """True length of each stored window id."""
    lengths = traffic_mod.group_lengths(cell["traffic"], cell["seed"])
    group, index = np.divmod(ids, traffic_mod.WINDOW_STRIDE)
    return np.array([lengths[int(g)][int(w)] for g, w in zip(group, index)],
                    np.int64)


def useful_work(cell: dict, rec: dict, mask: np.ndarray) -> dict:
    """Useful work of the answers in ``mask`` (the family's
    ``useful_work``)."""
    family, cfg = cell["family"], cell["config"]
    step = mask & (rec["op"] == traffic_mod.OPS["step"])
    score = mask & (rec["op"] == traffic_mod.OPS["score"])
    lengths = window_lengths(cell, rec["key0"][score])
    total = {"requests": int(step.sum() + score.sum()),
             "row_timesteps": int(step.sum()) + int(lengths.sum()),
             "flops": 0, "bytes": 0}
    for kind, answers in (("step", {"position": rec["key1"][step]}),
                          ("score", {"length": lengths})):
        fl, nb = family.useful_work(cfg, kind, answers)
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
    value, computed at ``precision`` (the family's ``reference_answers``
    on the same inputs the load generators sent)."""
    family, cfg, mix, seed = cell["family"], cell["config"], cell["traffic"], cell["seed"]
    rates = _anomaly_rates(mix, cell["chips"])
    step = np.flatnonzero(rec["op"] == traffic_mod.OPS["step"])
    score = np.flatnonzero(rec["op"] == traffic_mod.OPS["score"])
    counts: dict = {}
    for s, t in zip(rec["key0"][step], rec["key1"][step]):
        counts[int(s)] = max(counts.get(int(s), 0), int(t) + 1)
    samples = [family.stream_samples(seed, s, n, cfg, rates[("step", s)])
               for s, n in counts.items()]
    pos = {s: i for i, s in enumerate(counts)}
    where = [(pos[int(s)], int(t)) for s, t in zip(rec["key0"][step],
                                                   rec["key1"][step])]
    ids = np.unique(rec["key0"][score])
    lengths = window_lengths(cell, ids)
    windows = [family.window(seed, int(w), int(n), cfg,
                             rates[("score", int(w) // traffic_mod.WINDOW_STRIDE)])
               for w, n in zip(ids, lengths)]
    index = np.searchsorted(ids, rec["key0"][score])

    def at(precision: str) -> np.ndarray:
        out = np.zeros(len(rec["op"]), np.float64)
        running, scores = family.reference_answers(params, samples, windows,
                                                   precision)
        if samples:
            out[step] = [running[i][t] for i, t in where]
        if windows:
            out[score] = scores[index]
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
    ``control`` the control's answers (the forward at the configuration's
    ``control_precision``) stand in the program's place.
    """
    cfg = cell["config"]
    at = reference_answers(cell, rec, params)
    truth = at("highest")
    allowed = np.abs(at(cfg["matmul_precision"]) - truth)
    got = at(cfg["control_precision"]) if control else rec["value"]
    gap = np.abs(got - truth)
    excess = (gap - allowed) / truth
    if not excess.size:
        excess = gap = truth = np.array([np.inf])
    return {"excess_gap_max": float(excess.max()),
            "excess_gap_mean": float(excess.mean()),
            "max_rel_dev": float((gap / truth).max()),
            "mean_rel_dev": float((gap / truth).mean()),
            "compared": int(excess.size)}


def merge_answers(parts: list) -> dict:
    """The load generators' records as one."""
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def merge_summaries(parts: list) -> dict:
    out = {"cpu_busy_pct": [p["cpu_busy_pct"] for p in parts],
           "error_samples": [e for p in parts for e in p["error_samples"]]}
    for key in ("sent", "unanswered", "errors"):
        out[key] = sum(int(p[key]) for p in parts)
    return out


# -- one run ----------------------------------------------------------------------

def measure(args, cell: dict, system, workdir: str) -> dict:
    """The load generators up, the window, and what was read in it."""
    trace_dir = os.path.join(workdir, "trace")
    spec = {"mix": cell["traffic"], "seed": args.seed, "chips": cell["chips"],
            "config": cell["config"], "family_file": cell["family_file"],
            "host": system.host, "port": system.port}
    gens: list = []
    try:
        for part in range(system.generators):
            gens.append(Generator(spec, workdir, part, system.generators))
        for gen in gens:
            gen.ready()
        log_setup(f"{len(gens)} load generator(s) connected and warm")
        got = {"stats0": system.stats()}
        got["compiles0"] = system.compiles_in(got["stats0"])
        got["setup_s"] = time.monotonic() - T_START
        t0 = got["t0"] = time.monotonic() + 0.2
        t1 = got["t1"] = t0 + args.seconds
        for gen in gens:
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
        got["stats1"] = system.stats()
        got["compiles1"] = system.compiles_in(got["stats1"])
        got["summary"] = merge_summaries([gen.done() for gen in gens])
        got["answers"] = merge_answers([gen.answers() for gen in gens])
        log_after("every answer in")
        got["stats_end"] = system.stats()
        got["trace"] = (system.trace_read(got["tr1"] - got["tr0"])
                        if args.trace else {})
    finally:
        for gen in gens:
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


def make_system(args, cell: dict, workdir: str):
    if cell["chips"] == 1:
        return OneChipSystem(cell, args.seed, args.rehearse)
    trace_dir = os.path.join(workdir, "trace") if args.trace else None
    return FrontSystem(cell, args.seed, args.rehearse, trace_dir, workdir)


def run(args) -> dict:
    cell = load_cell(args.workload)
    cell["seed"] = args.seed
    set_up_environment()
    workdir = tempfile.mkdtemp(prefix="bench-")
    os.makedirs(os.path.join(workdir, "trace"))
    try:
        system = make_system(args, cell, workdir)
        try:
            got = measure(args, cell, system, workdir)
        finally:
            system.stop()
            log_after("system under test stopped")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    device, params = system.device, system.reference_params()
    del system
    log_after("reference weights at hand")

    rec, summary = got["answers"], got["summary"]
    busy = ", ".join(f"{b:.1f}%" for b in summary["cpu_busy_pct"])
    log(f"load generator(s): cpu busy {busy} of the window, "
        f"{summary['errors']} errors, {summary['unanswered']} unanswered")
    for sample in summary["error_samples"]:
        log(f"  error answer: {sample}")
    (e0, b0), (e1, b1) = got["compiles0"], got["compiles1"]
    log(f"compiles inside the window: engine {e1 - e0}, backend {b1 - b0}")
    failed = summary["errors"] + summary["unanswered"]
    log(f"window: {summary['sent']} frames sent in {args.seconds} s, "
        f"{failed} failed")

    t_ref = time.monotonic()
    if args.control:
        own = compare(cell, rec, params, False)
        log("the program's gaps: " + ", ".join(
            f"{k} {v!r}" for k, v in own.items() if k != "compared"))
    numbers = compare(cell, rec, params, args.control)
    log(f"reference: {numbers['compared']} answers compared in "
        f"{time.monotonic() - t_ref:.1f} s"
        + (" (the control in the program's place)" if args.control else ""))
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
                    help="compare the control in the program's place")
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
