"""The paper's Eq-1 tables, one function each, printed as CSV rows.

Output convention: ``name,us_per_call,derived`` rows (derived carries
the table-specific payload, ';'-separated).

  table1_resources   — paper Table 1: RH_m, balanced reuse factors,
                       multiplier (DSP) demand, steady-state utilization
  table2_latency     — paper Table 2: measured CPU (this machine, jitted
                       JAX) vs the calibrated Eq-1 FPGA model, T=1..64
  table3_energy      — paper Table 3: energy/timestep from the same runs
  schedule_compare   — dataflow (wavefront) vs layer-by-layer on the
                       paper's own cycle model — isolates the temporal-
                       parallelism win from platform effects
  roofline_cells     — §Roofline summary over experiments/dryrun artifacts

``--tables`` selects a subset (default: all).  Run from the repo root
with ``PYTHONPATH=src``::

    PYTHONPATH=src python scripts/paper_tables.py --tables table1_resources
"""
from __future__ import annotations

import argparse
import json
import statistics
import time
from pathlib import Path

import jax


def _timeit(fn, *args, iters: int = 50, warmup: int = 5) -> float:
    """Median wall time per call in microseconds (post-warmup, jitted)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(times)


def table1_resources() -> list[str]:
    from repro.config import get_config
    from repro.core.balancing import balance_model, total_multipliers, utilization
    from repro.core.latency import PAPER_RH_M

    rows = []
    for name, rh_m in PAPER_RH_M.items():
        bal = balance_model(get_config(name).lstm_ae, rh_m)
        rhs = "/".join(str(b.rh) for b in bal)
        rows.append(
            f"table1.{name},0.0,"
            f"RH_m={rh_m};RH_i={rhs};multipliers={total_multipliers(bal):.0f};"
            f"utilization={utilization(bal):.3f};Lat_t={bal[0].lat_t}"
        )
    return rows


_T_STEPS = (1, 2, 4, 6, 16, 64)


def _measure_cpu_lstm_ae(name: str) -> dict[int, float]:
    """Median jitted CPU latency (us) of the full LSTM-AE forward per T."""
    from repro.config import get_config
    from repro.core import init_lstm_ae, lstm_ae_sequential

    cfg = get_config(name)
    params = init_lstm_ae(jax.random.PRNGKey(0), cfg)
    f = cfg.lstm_ae.input_features
    out = {}
    fwd = jax.jit(lambda p, xs: lstm_ae_sequential(p, xs))
    for t in _T_STEPS:
        xs = jax.random.normal(jax.random.PRNGKey(1), (t, 1, f))
        out[t] = _timeit(fwd, params, xs, iters=30, warmup=3)
    return out


def table2_latency() -> list[str]:
    from repro.config import get_config
    from repro.core.latency import PAPER_RH_M, fpga_latency_ms

    rows = []
    for name, rh_m in PAPER_RH_M.items():
        cfg = get_config(name).lstm_ae
        cpu = _measure_cpu_lstm_ae(name)
        for t in _T_STEPS:
            fpga_ms = fpga_latency_ms(cfg, t, rh_m).ms
            cpu_ms = cpu[t] / 1e3
            rows.append(
                f"table2.{name}.T{t},{cpu[t]:.1f},"
                f"fpga_model_ms={fpga_ms:.4f};cpu_ms={cpu_ms:.4f};"
                f"speedup_vs_cpu={cpu_ms / fpga_ms:.1f}x"
            )
    return rows


def table3_energy() -> list[str]:
    from repro.config import get_config
    from repro.core.latency import PAPER_RH_M, energy_per_timestep_mj, fpga_latency_ms

    rows = []
    for name, rh_m in PAPER_RH_M.items():
        cfg = get_config(name).lstm_ae
        cpu = _measure_cpu_lstm_ae(name)
        for t in (1, 64):
            fpga_ms = fpga_latency_ms(cfg, t, rh_m).ms
            e_fpga = energy_per_timestep_mj(fpga_ms, t, "fpga")
            e_cpu = energy_per_timestep_mj(cpu[t] / 1e3, t, "cpu")
            rows.append(
                f"table3.{name}.T{t},{cpu[t]:.1f},"
                f"fpga_mj={e_fpga:.4f};cpu_mj={e_cpu:.3f};"
                f"reduction={e_cpu / e_fpga:.0f}x"
            )
    return rows


def schedule_compare() -> list[str]:
    from repro.config import get_config
    from repro.core.latency import PAPER_RH_M, speedup_table

    rows = []
    for name, rh_m in PAPER_RH_M.items():
        for r in speedup_table(get_config(name).lstm_ae, rh_m, timesteps=(1, 16, 64)):
            rows.append(
                f"schedule.{name}.T{r['timesteps']},0.0,"
                f"dataflow_cyc={r['dataflow_cycles']};seq_cyc={r['sequential_cycles']};"
                f"temporal_speedup={r['speedup']:.2f}x"
            )
    return rows


def roofline_cells(dryrun_dir: str = "experiments/dryrun") -> list[str]:
    rows = []
    d = Path(dryrun_dir)
    if not d.exists():
        return ["roofline.missing,0.0,run `python -m repro.launch.dryrun` first"]
    for f in sorted(d.glob("*__single_pod_16x16.json")):
        r = json.loads(f.read_text())
        if r.get("status") != "ok":
            continue
        total = r["compute_s"] + r["memory_s"] + r["collective_s"]
        frac = r["compute_s"] / total if total else 0.0
        rows.append(
            f"roofline.{r['arch']}.{r['shape']},0.0,"
            f"dominant={r['dominant']};compute_s={r['compute_s']:.3g};"
            f"memory_s={r['memory_s']:.3g};collective_s={r['collective_s']:.3g};"
            f"compute_frac={frac:.3f};flops_ratio={r['flops_ratio']:.3f}"
        )
    return rows

_TABLES = {
    "table1_resources": table1_resources,
    "table2_latency": table2_latency,
    "table3_energy": table3_energy,
    "schedule_compare": schedule_compare,
    "roofline_cells": roofline_cells,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tables", nargs="*", choices=sorted(_TABLES),
                    help="subset of tables to run (default: all)")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    for name in args.tables or list(_TABLES):
        for row in _TABLES[name]():
            print(row, flush=True)


if __name__ == "__main__":
    main()
