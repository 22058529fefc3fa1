"""The benchmark's own forward pass against the program's plain
layer-by-layer one (``lstm_ae_sequential``) on seeded weights."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import flops  # noqa: E402
import reference  # noqa: E402
import series  # noqa: E402


@pytest.mark.parametrize("arch,features,depth", [
    ("lstm-ae-f64-d6", 64, 6), ("lstm-ae-f32-d2", 32, 2)])
def test_widths_match_the_program(arch, features, depth):
    from repro.config import get_config

    cfg = get_config(arch).lstm_ae
    assert tuple(flops.widths(features, depth)) == cfg.layer_sizes()


@pytest.mark.parametrize("features,depth", [(64, 6), (32, 2)])
def test_scores_and_running_errors_match_lstm_ae_sequential(features, depth):
    from repro.core.lstm import lstm_ae_sequential

    params = reference.make_params(2**31 + 11, features, depth)
    lengths = [5, 17, 40]
    windows = [series.window(3, i, t, features, 0.5) for i, t in enumerate(lengths)]
    got = reference.window_scores(params, windows)
    run = reference.running_errors(params, windows)
    for w, score, running in zip(windows, got, run):
        xs = jnp.asarray(w)[:, None, :]
        with jax.default_matmul_precision("highest"):
            recon = np.asarray(lstm_ae_sequential(params, xs))[:, 0]
        sq = np.mean((recon.astype(np.float64) - w) ** 2, axis=1)
        np.testing.assert_allclose(score, sq.mean(), rtol=2e-6)
        np.testing.assert_allclose(running, np.cumsum(sq) / np.arange(1, len(w) + 1),
                                   rtol=2e-6)


def test_weights_come_from_the_seed_alone():
    a = reference.make_params(7, 32, 2)
    b = reference.make_params(7, 32, 2)
    c = reference.make_params(8, 32, 2)
    for la, lb, lc in zip(a["layers"], b["layers"], c["layers"]):
        np.testing.assert_array_equal(np.asarray(la["wx"]), np.asarray(lb["wx"]))
        assert not np.array_equal(np.asarray(la["wx"]), np.asarray(lc["wx"]))
    bound = 1 / np.sqrt(16)
    assert np.abs(np.asarray(a["layers"][0]["wh"])).max() <= bound


def test_control_is_lower_precision():
    params = reference.make_params(5, 32, 2)
    samples = [series.stream_samples(5, s, 64, 32, 0.1) for s in range(8)]
    ref = reference.running_errors(params, samples)
    ctl = reference.running_errors(params, samples, "bfloat16")
    rel = np.concatenate([np.abs(c - r) / r for c, r in zip(ctl, ref)])
    assert rel.mean() > 1e-6
