"""The paper's core claim in software: wavefront == layer-by-layer, and the
multi-device pipeline (shard_map + ppermute FIFOs) == both."""
import subprocess
import sys
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # hypothesis or fallback shim

from repro.config import get_config
from repro.config.core import LSTMAEConfig, ModelConfig
from repro.core import (
    init_lstm_ae,
    lstm_ae_sequential,
    schedule_table,
    wavefront_forward,
)


def _random_ae(depth: int, features: int, t: int, b: int, seed: int):
    cfg = ModelConfig(
        name="t", family="lstm_ae",
        num_layers=depth,
        lstm_ae=LSTMAEConfig(input_features=features, depth=depth),
    )
    key = jax.random.PRNGKey(seed)
    params = init_lstm_ae(key, cfg)
    xs = jax.random.normal(jax.random.PRNGKey(seed + 1), (t, b, features))
    return params, xs


@given(
    depth=st.sampled_from([2, 4, 6]),
    features=st.sampled_from([16, 32, 64]),
    t=st.integers(min_value=1, max_value=12),
    b=st.integers(min_value=1, max_value=3),
    seed=st.integers(min_value=0, max_value=1000),
)
@settings(max_examples=25, deadline=None)
def test_wavefront_equals_sequential(depth, features, t, b, seed):
    params, xs = _random_ae(depth, features, t, b, seed)
    seq = lstm_ae_sequential(params, xs)
    wav = wavefront_forward(params, xs)
    np.testing.assert_allclose(np.asarray(wav), np.asarray(seq), rtol=1e-5, atol=1e-6)


def test_wavefront_pwl_mode():
    params, xs = _random_ae(2, 32, 8, 2, 7)
    seq = lstm_ae_sequential(params, xs, pwl=True)
    wav = wavefront_forward(params, xs, pwl=True)
    np.testing.assert_allclose(np.asarray(wav), np.asarray(seq), rtol=1e-5, atol=1e-6)


def test_schedule_table_staggered():
    """At steady state every layer is busy (the paper's Fig. 2)."""
    n, t = 4, 10
    table = schedule_table(n, t)
    assert len(table) == t + n - 1
    # wavefront step k=n-1 .. t-1: all n layers active
    for k in range(n - 1, t):
        assert len(table[k]) == n
        layers = [l for l, _ in table[k]]
        steps = [s for _, s in table[k]]
        assert layers == list(range(n))
        assert steps == [k - i for i in range(n)]  # staggered timesteps
    # fill & drain ramps
    assert len(table[0]) == 1
    assert len(table[-1]) == 1


_PIPELINE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.config import get_config
from repro.core import init_lstm_ae, lstm_ae_sequential
from repro.core.temporal import build_stage_params, pipelined_forward
from repro.launch.mesh import make_host_mesh

cfg = get_config("lstm-ae-f32-d6")
key = jax.random.PRNGKey(0)
params = init_lstm_ae(key, cfg)
xs = jax.random.normal(jax.random.PRNGKey(1), (11, 4, 32))

mesh = make_host_mesh((2, 4), ("data", "model"))
stage_params, counts, assignment = build_stage_params(params, cfg, 4)
ys = pipelined_forward(stage_params, counts, xs, mesh=mesh, cfg=cfg,
                       stage_axis="model", batch_axes=("data",))
ref = lstm_ae_sequential(params, xs)
np.testing.assert_allclose(np.asarray(ys), np.asarray(ref), rtol=1e-4, atol=1e-5)
print("PIPELINE_OK", assignment)
"""


def test_pipelined_forward_multi_device():
    """Run the shard_map pipeline on 8 emulated devices in a subprocess
    (device count is process-global, so tests keep their single device)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # emulated host devices, never the chip
    out = subprocess.run(
        [sys.executable, "-c", _PIPELINE_SCRIPT], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PIPELINE_OK" in out.stdout
