"""The program's host spans in a profiler trace: self time, request
count, the device's idle gaps named by the host span they fell in, and
the share of a program's executions inside a pair of spans."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import hostspans  # noqa: E402

# one request on the serving line, then a pump tick that flushes (ns)
SERVING = [
    ["wire.frame", 0, 100],
    ["wire.decode", 5, 10],
    ["pool.step", 20, 60],
    ["pool.assemble", 20, 10],
    ["pool.launch", 30, 20],
    ["pool.readback", 50, 30],
    ["wire.encode", 85, 10],
    ["pump.tick", 200, 20],
    ["batch.flush", 202, 16],
    ["batch.compute", 204, 10],
]
HOST = {"python": SERVING, "other": [["wire.frame", 500, 40]]}


def _device(*events):
    return {"window_s": 1e-6,
            "devices": {"/device:TPU:0": {"ops": [list(e) for e in events],
                                          "modules": [list(e) for e in events]}}}


# the step at 32, its error read at 44 and 70, a flush's program at 206
DEVICE = _device(["jit__pool_step(1)", 32, 10], ["jit_true_divide(2)", 44, 2],
                 ["jit_maximum(3)", 70, 2], ["jit__score_masked(4)", 206, 6])


def test_self_time_is_duration_less_child_spans():
    own = hostspans.self_s(HOST)
    ns = {name: pytest.approx(v * 1e-9) for name, v in {
        "wire.frame": 100 - 10 - 60 - 10 + 40, "wire.decode": 10,
        "pool.step": 0, "pool.assemble": 10, "pool.launch": 20,
        "pool.readback": 30, "wire.encode": 10, "pump.tick": 4,
        "batch.flush": 6, "batch.compute": 10}.items()}
    assert own == ns


def test_frames_count_every_request():
    assert hostspans.frames(HOST) == 2


def test_gaps_are_named_by_the_host_span_they_fell_in():
    gaps = hostspans.label_gaps(DEVICE, HOST)
    assert gaps == [
        # 42..44: inside the launch
        ["pool.launch after _pool_step", pytest.approx(2e-9)],
        # 46..70: 4 ns of launch, 20 of readback
        ["pool.readback after true_divide", pytest.approx(24e-9)],
        # 72..206: 100 ns between the request and the pump tick
        ["loop after maximum", pytest.approx(134e-9)],
    ]
    assert hostspans.idle_split(gaps) == {
        "loop": pytest.approx(134e-9), "pool.readback": pytest.approx(24e-9),
        "pool.launch": pytest.approx(2e-9)}


def test_without_host_spans_gaps_keep_their_program_names():
    gaps = hostspans.label_gaps(DEVICE, {})
    assert [g[0] for g in gaps] == ["after _pool_step", "after true_divide",
                                    "after maximum"]


def test_share_of_executions_inside_launch_to_readback():
    late = _device(["jit__pool_step(1)", 32, 10], ["jit__pool_step(1)", 90, 5])
    assert hostspans.share_inside(late, HOST, "_pool_step", "pool.launch",
                                  "pool.readback") == pytest.approx(0.5)
    assert hostspans.share_inside(late, {}, "_pool_step", "pool.launch",
                                  "pool.readback") is None


_CAPTURE = textwrap.dedent("""
    import json, sys
    import jax, jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    import hostspans

    f = jax.jit(lambda x: x * 2.0)
    x = jnp.ones(8)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(sys.argv[1], profiler_options=opts)
    for _ in range(2):
        with TraceAnnotation("pool.step"):
            with TraceAnnotation("pool.launch"):
                y = f(x)
            with TraceAnnotation("pool.readback"):
                y.block_until_ready()
    with TraceAnnotation("not.a.span"):
        pass
    jax.profiler.stop_trace()
    print(json.dumps(hostspans.read_host(sys.argv[1])))
""")


def test_read_host_finds_the_spans_of_a_capture(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(BENCH))
    out = subprocess.run([sys.executable, "-c", _CAPTURE, str(tmp_path)],
                         env=env, capture_output=True, text=True, timeout=180)
    assert out.returncode == 0, out.stderr[-3000:]
    host = json.loads(out.stdout.strip().splitlines()[-1])
    names = [s[0] for spans in host.values() for s in spans]
    assert sorted(names) == sorted(["pool.step", "pool.launch",
                                    "pool.readback"] * 2)
    own = hostspans.self_s(host)
    assert set(own) == {"pool.step", "pool.launch", "pool.readback"}
    assert all(v >= 0 for v in own.values())


def test_recorded_v5e_slices():
    """Slices recorded on a TPU v5 lite with the program's spans
    (``hostspans.py --slice``): one STEP request of ``f32d2.stream`` and
    the next one's launch, and two flushes of ``f64d6.backfill``."""
    recorded = json.loads((Path(__file__).parent / "data"
                           / "hostspans_v5e.json").read_text())
    stream = recorded["stream"]
    host = stream["host"]
    assert hostspans.frames(host) == 1
    gaps = hostspans.label_gaps(stream, host)
    # the device waits on the host's dispatch of each program of the step
    assert set(hostspans.idle_split(gaps)) == {"pool.launch"}
    # the first step ran inside its launch..readback; the second step's
    # readback ends after the slice does
    assert hostspans.share_inside(stream, host, "_pool_step", "pool.launch",
                                  "pool.readback") == 0.5
    own = hostspans.self_s(host)
    assert own["pool.launch"] > own["pool.readback"] > own["pool.assemble"]
    backfill = recorded["backfill"]
    split = hostspans.idle_split(hostspans.label_gaps(backfill, backfill["host"]))
    assert list(split) == ["batch.compute"]
    assert hostspans.self_s(backfill["host"])["wire.encode"] > 0


def test_cpu_rehearsal_reads_the_program_spans(monkeypatch, capsys):
    """The script end to end on the CPU at a tiny size: the traced stretch
    holds the program's spans (and no device plane to split)."""
    from _faults import use_mix

    use_mix(monkeypatch, "tiny_stream.json")
    for key in ("JAX_COMPILATION_CACHE_DIR",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        monkeypatch.setenv(key, "")
    assert hostspans.main(["--workload", "f32d2.stream", "--seed",
                           str(2**31 + 7), "--seconds", "2", "--rehearse"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["frames"] > 0 and out["transport_self_ms"] > 0
    assert {"wire.frame", "pool.step", "pool.launch",
            "pool.readback"} <= set(out["self_s"])
    assert out["idle_split_s"] == {}
    assert out["stage_mean_ms"]["pool_launch_ms"] > 0
