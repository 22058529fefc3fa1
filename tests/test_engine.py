"""Unified execution-engine API: every registered schedule must produce the
same reconstructions on all four paper configs, the registry must fail
loudly on unknown names, and the AnomalyService lifecycle must hold
together end-to-end."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config
from repro.core import init_lstm_ae, lstm_ae_sequential
from repro.engine import (
    AnomalyService,
    Engine,
    EngineConfig,
    available_schedules,
    build_engine,
)
from repro.models import build_model

PAPER_ARCHS = ["lstm-ae-f32-d2", "lstm-ae-f32-d6", "lstm-ae-f64-d2", "lstm-ae-f64-d6"]
SCHEDULES = ["sequential", "wavefront", "pipelined", "fused"]


def _setup(arch: str, t: int = 9, b: int = 2):
    cfg = get_config(arch)
    params = init_lstm_ae(jax.random.PRNGKey(0), cfg)
    f = cfg.lstm_ae.input_features
    series = jax.random.normal(jax.random.PRNGKey(1), (b, t, f))
    ref = jnp.swapaxes(lstm_ae_sequential(params, jnp.swapaxes(series, 0, 1)), 0, 1)
    return cfg, params, series, ref


@pytest.mark.parametrize("arch", PAPER_ARCHS)
@pytest.mark.parametrize("schedule", SCHEDULES)
def test_schedule_equivalence(arch, schedule):
    """All schedules agree with the layer-by-layer reference on every paper
    config (the paper's core claim: the schedule changes latency, never
    values — padded-matmul accumulation order allows ~1e-7 float drift)."""
    cfg, params, series, ref = _setup(arch)
    engine = build_engine(cfg, schedule, params=params)
    recon = engine.reconstruct({"series": series})
    np.testing.assert_allclose(
        np.asarray(recon), np.asarray(ref), rtol=1e-5, atol=1e-6
    )


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_score_is_reconstruction_mse(schedule):
    cfg, params, series, ref = _setup("lstm-ae-f32-d2")
    engine = build_engine(cfg, schedule, params=params)
    scores = engine.score({"series": series})
    expect = jnp.mean(jnp.square(ref - series), axis=(1, 2))
    np.testing.assert_allclose(np.asarray(scores), np.asarray(expect), rtol=1e-5, atol=1e-6)


def test_unknown_schedule_raises():
    cfg = get_config("lstm-ae-f32-d2")
    with pytest.raises(ValueError, match="unknown schedule 'bogus'.*available"):
        build_engine(cfg, "bogus")


def test_registry_lists_builtin_schedules():
    assert set(SCHEDULES) <= set(available_schedules())


def test_engine_rejects_non_lstm_ae():
    cfg = get_config("tinyllama-1.1b")
    with pytest.raises(ValueError, match="lstm_ae"):
        Engine(cfg, "wavefront")


def test_engine_requires_bound_params():
    cfg, params, series, _ = _setup("lstm-ae-f32-d2")
    engine = build_engine(cfg, "wavefront")
    with pytest.raises(ValueError, match="bind"):
        engine.score({"series": series})
    engine.bind(params)
    assert engine.score({"series": series}).shape == (2,)


def test_build_engine_accepts_model_api():
    cfg = get_config("lstm-ae-f32-d2")
    api = build_model(cfg)
    engine = build_engine(api, "sequential")
    assert engine.cfg is cfg


def test_pipelined_single_device_fallback():
    """On one device the pipelined schedule resolves to wavefront (same
    dataflow semantics, no stage axis) instead of failing."""
    cfg = get_config("lstm-ae-f32-d6")
    engine = build_engine(cfg, "pipelined")
    assert engine.schedule.name == "pipelined"
    assert engine.schedule.resolved == "wavefront"
    assert engine.schedule.tag == "pipelined->wavefront"


def test_pipelined_data_parallel_needs_devices():
    """An explicit data-parallel placement must never silently degrade to
    an unsharded single-device run — and the legacy ``data_parallel=N``
    spelling reaches the same check through the deprecation shim."""
    from repro.engine import Placement

    cfg = get_config("lstm-ae-f32-d6")
    with pytest.raises(ValueError, match=r"Placement.data\(2\).*4 devices"):
        build_engine(
            cfg, EngineConfig(schedule="pipelined", placement=Placement.data(2))
        )
    with pytest.warns(DeprecationWarning, match="data_parallel=2"):
        shim = EngineConfig(schedule="pipelined", data_parallel=2)
    assert shim.placement == Placement.data(2)
    with pytest.raises(ValueError, match=r"Placement.data\(2\)"):
        build_engine(cfg, shim)


def test_fused_schedule_uses_pallas_cell():
    """The fused schedule resolves cleanly (interpreted on CPU) and
    keeps the sequential Eq-1 accounting (layer-major walk)."""
    cfg = get_config("lstm-ae-f32-d2")
    engine = build_engine(cfg, "fused")
    assert engine.schedule.resolved == "fused"
    assert engine.schedule.latency_kind == "sequential"


def test_resolve_cache_keyed_and_capped():
    """Regression (ISSUE 2 + ISSUE 4): EngineConfig fields a schedule
    declares it ignores must not split the resolve cache — EXCEPT the
    placement, which is always part of the key so engines differing only
    in device layout never alias one cached program — and resolving many
    distinct configs must stay within the LRU cap instead of leaking
    executors."""
    from repro.engine import (
        Placement,
        Schedule,
        register_schedule,
        resolve_schedule,
        schedule_cache_info,
        unregister_schedule,
    )
    from repro.engine.schedules import SCHEDULE_CACHE_CAPACITY

    cfg = get_config("lstm-ae-f32-d2")
    s0 = resolve_schedule("wavefront", cfg, EngineConfig(schedule="wavefront"))
    s1 = resolve_schedule(
        "wavefront", cfg,
        EngineConfig(schedule="wavefront", n_stages=5, jit=False),
    )
    assert s0 is s1  # wavefront keys on pwl only
    assert s0 is not resolve_schedule(
        "wavefront", cfg, EngineConfig(schedule="wavefront", pwl=True)
    )
    # placement always keys, even for schedules that ignore it (ISSUE 4:
    # sharded and unsharded compiled programs must never collide); no mesh
    # is built at resolve time, so a 3-way layout resolves on one device
    s2 = resolve_schedule(
        "wavefront", cfg,
        EngineConfig(schedule="wavefront", placement=Placement.data(3)),
    )
    assert s2 is not s0
    info = schedule_cache_info()
    assert "placement" in info["always_keyed"]
    assert any("Placement.data(3" in p for p in info["placements"])

    @register_schedule("_cache_probe")  # no config_fields: keys on everything
    def _probe(cfg, ecfg):
        return Schedule("_cache_probe", "_cache_probe", "sequential",
                        lambda p, xs: xs)

    try:
        for i in range(1, 3 * SCHEDULE_CACHE_CAPACITY):
            resolve_schedule(
                "_cache_probe", cfg,
                EngineConfig(schedule="_cache_probe", n_stages=i),
            )
            assert schedule_cache_info()["size"] <= SCHEDULE_CACHE_CAPACITY
    finally:
        unregister_schedule("_cache_probe")
    assert "_cache_probe" not in available_schedules()


def test_stream_matches_batch_reconstruction():
    cfg, params, series, ref = _setup("lstm-ae-f32-d6", t=7, b=3)
    engine = build_engine(cfg, "wavefront", params=params)
    state = engine.init_stream_state(3)
    outs = []
    for t in range(series.shape[1]):
        y_t, state = engine.stream(series[:, t], state)
        outs.append(y_t)
    np.testing.assert_allclose(
        np.asarray(jnp.stack(outs, axis=1)), np.asarray(ref), rtol=1e-5, atol=1e-6
    )


def test_latency_model_per_schedule():
    """Eq-1 accounting follows the bound schedule: dataflow beats
    layer-by-layer cycles for T >> depth (the paper's headline)."""
    cfg = get_config("lstm-ae-f32-d6")
    seq = build_engine(cfg, "sequential").latency_model(64)
    wav = build_engine(cfg, "wavefront").latency_model(64)
    pipe = build_engine(cfg, "pipelined").latency_model(64)
    assert seq.schedule == "sequential"
    assert wav.schedule == "dataflow" and pipe.schedule == "dataflow"
    assert wav.cycles == pipe.cycles
    assert seq.cycles > 2 * wav.cycles


def test_prefill_delegates_to_schedule_registry():
    """ModelAPI.prefill accepts schedule= and routes through the engine."""
    cfg, params, series, ref = _setup("lstm-ae-f32-d2")
    api = build_model(cfg)
    expect = jnp.mean(jnp.square(ref - series), axis=(1, 2))
    for schedule in ("sequential", "wavefront"):
        scores, _ = api.prefill(params, {"series": series}, schedule=schedule)
        np.testing.assert_allclose(
            np.asarray(scores), np.asarray(expect), rtol=1e-5, atol=1e-6
        )
    with pytest.raises(ValueError, match="unknown schedule"):
        api.prefill(params, {"series": series}, schedule="bogus")


def test_anomaly_service_lifecycle():
    """fit -> calibrate -> score/detect/stream on a tiny model; streaming
    running errors equal batch scores."""
    from repro.data import TimeseriesConfig, make_batch

    svc = AnomalyService("lstm-ae-f32-d2", schedule="wavefront")
    dc = TimeseriesConfig(features=32, seq_len=12, batch=16, anomaly_rate=0.0)
    metrics = svc.fit(dc, steps=5)
    assert "mse" in metrics
    thr = svc.calibrate(dc)
    assert svc.threshold == thr > 0
    series, labels = make_batch(
        TimeseriesConfig(features=32, seq_len=12, batch=8, anomaly_rate=0.5, seed=3), 0
    )
    report = svc.detect(series, labels)
    assert 0.0 <= report.anomaly_rate <= 1.0
    sess = svc.stream_start(8)
    for t in range(series.shape[1]):
        errors, sess = svc.stream_step(series[:, t], sess)
    np.testing.assert_allclose(
        np.asarray(errors), np.asarray(svc.score(series)), rtol=1e-5, atol=1e-6
    )


def test_build_score_step_matches_engine():
    """The serving-step builder wraps an engine's scoring under the usual
    mesh-context machinery (the LSTM-AE analogue of build_prefill_step)."""
    from repro.serving import build_score_step

    cfg, params, series, _ = _setup("lstm-ae-f32-d2")
    engine = build_engine(cfg, "wavefront")
    step = build_score_step(engine)
    scores = step(params, {"series": series})
    np.testing.assert_allclose(
        np.asarray(scores),
        np.asarray(engine.bind(params).score({"series": series})),
        rtol=1e-6,
    )


def test_anomaly_service_seed_governs_fit():
    """Two services with different seeds fit different models; same seed is
    deterministic."""
    from repro.data import TimeseriesConfig

    dc = TimeseriesConfig(features=32, seq_len=8, batch=8, anomaly_rate=0.0)
    series = jnp.ones((2, 8, 32))

    def fitted_scores(seed):
        svc = AnomalyService("lstm-ae-f32-d2", seed=seed)
        svc.fit(dc, steps=2)
        return np.asarray(svc.score(series))

    a, b, a2 = fitted_scores(0), fitted_scores(7), fitted_scores(0)
    np.testing.assert_array_equal(a, a2)
    assert np.abs(a - b).max() > 0


def test_anomaly_service_requires_calibration():
    svc = AnomalyService("lstm-ae-f32-d2")
    with pytest.raises(ValueError, match="calibrate"):
        svc.alerts(jnp.zeros((2, 4, 32)))


_MULTI_DEVICE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.config import get_config
from repro.core import init_lstm_ae, lstm_ae_sequential
from repro.engine import EngineConfig, build_engine

cfg = get_config("lstm-ae-f32-d6")
params = init_lstm_ae(jax.random.PRNGKey(0), cfg)
series = jax.random.normal(jax.random.PRNGKey(1), (4, 11, 32))
ref = jnp.swapaxes(lstm_ae_sequential(params, jnp.swapaxes(series, 0, 1)), 0, 1)
for ecfg in (EngineConfig(schedule="pipelined", n_stages=4),
             EngineConfig(schedule="pipelined", n_stages=4, data_parallel=2)):
    e = build_engine(cfg, ecfg, params=params)
    assert e.schedule.resolved == "pipelined", e.schedule
    np.testing.assert_allclose(np.asarray(e.reconstruct({"series": series})),
                               np.asarray(ref), rtol=1e-4, atol=1e-5)
print("ENGINE_PIPELINE_OK")
"""


def test_pipelined_engine_multi_device():
    """The real pipelined path (internal mesh + stage params traced into
    one program, incl. 2-way data parallelism) on 8 emulated devices in a
    subprocess (device count is process-global)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # emulated host devices, never the chip
    out = subprocess.run(
        [sys.executable, "-c", _MULTI_DEVICE_SCRIPT], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ENGINE_PIPELINE_OK" in out.stdout
