"""The ``jamba`` family adapter at a tiny size: its weights take the
program's layout, its plain forward agrees with the test suite's
independent one, its inputs are seeded token streams, and its frames and
useful work are what the harness expects."""
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "tests"))

import families  # noqa: E402

FAMILY = families.load("jamba")
#: the configuration's control: the reference at this precision in the
#: program's place must fail the cell's limits
CONTROL = json.loads((BENCH / "configs" / "jamba2-3b.json").read_text())["control_precision"]
#: the configuration file's keys at the program's test widths
CONFIG = {
    "arch": "jamba2-3b", "family": "jamba", "attn_layer_offset": 7,
    "attn_layer_period": 14, "hidden_size": 64, "intermediate_size": 128,
    "mamba_d_conv": 4, "mamba_d_state": 4, "mamba_dt_rank": 8, "mamba_expand": 2,
    "num_attention_heads": 4, "num_key_value_heads": 1, "num_hidden_layers": 14,
    "rms_norm_eps": 1e-6, "vocab_size": 512, "initializer_range": 0.02,
}


@pytest.fixture(scope="module")
def params():
    return FAMILY.make_params(2**31 + 9, CONFIG)


def test_weights_take_the_programs_layout(params):
    from repro.config import reduced_config
    from repro.models import build_model

    cfg = reduced_config("jamba2-3b")
    program = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    assert jax.tree.structure(params.tree) == jax.tree.structure(program)
    assert [a.shape for a in jax.tree.leaves(params.tree)] == \
        [a.shape for a in jax.tree.leaves(program)]
    mixer = params.tree["positions"][0]["mixer"]
    assert mixer["in_x"]["w"].dtype == np.dtype("bfloat16")
    assert float(np.std(np.asarray(mixer["in_x"]["w"], np.float32))) == \
        pytest.approx(0.02, rel=0.05)
    np.testing.assert_allclose(np.asarray(mixer["a_log"][0, 0]), np.log(np.arange(1, 5)))


def test_the_plain_forward_agrees_with_the_test_suites(params):
    import jamba_reference as tests_ref  # tests/jamba_reference.py

    toks = [FAMILY.stream_samples(5, s, 40, CONFIG, 0.0) for s in range(3)]
    got, _ = FAMILY.reference_answers(params, toks, [], "highest")
    cfg = dict(params.config, period=14)
    for t, g in zip(toks, got):
        np.testing.assert_allclose(g, tests_ref.running_surprisal(params.tree, t, cfg),
                                   rtol=1e-5)
    assert got[0][0] == pytest.approx(np.log(512), rel=1e-6)


def test_the_reference_takes_only_the_weights_it_made(params):
    """Handed a bare tree (no configuration beside it) the reference
    refuses at once instead of guessing head counts from shapes."""
    toks = [FAMILY.stream_samples(5, 0, 4, CONFIG, 0.0)]
    with pytest.raises(TypeError, match="Weights"):
        FAMILY.reference_answers(params.tree, toks, [], "highest")


def test_stated_precision_and_control_move_the_answers_apart(params):
    """The forward at the stated precision lies near the reference; the
    configuration's control (every float32 part in bf16) lies further from
    it, by an excess gap the harness's mean limit can see (the chip
    readings at the cell's size are in PERF.md)."""
    assert CONTROL == "bfloat16"
    toks = [FAMILY.stream_samples(6, s, 64, CONFIG, 0.1) for s in range(4)]
    hi, stated, low = (np.concatenate(FAMILY.reference_answers(params, toks, [], p)[0])
                       for p in ("highest", "default", CONTROL))
    stated_gap = np.abs(stated - hi) / hi
    assert 0 < stated_gap.max() < 1e-2
    excess = np.abs(low - hi) / hi - stated_gap
    assert excess.mean() > 1e-5


def test_token_streams_are_seeded_and_chunked():
    a = FAMILY.stream_samples(7, 3, 300, CONFIG, 0.1)
    assert a.dtype == np.int32 and a.shape == (300,)
    assert (a >= 0).all() and (a < 512).all()
    np.testing.assert_array_equal(a[256:], FAMILY.stream_chunk(7, 3, 1, CONFIG, 0.1)[:44])
    np.testing.assert_array_equal(a, FAMILY.stream_samples(7, 3, 300, CONFIG, 0.1))
    assert not np.array_equal(a, FAMILY.stream_samples(8, 3, 300, CONFIG, 0.1))
    # a stream is anomalous with probability anomaly_rate: odd ids appear
    # in no template
    plain = {int(t) for s in range(40) for t in FAMILY.stream_samples(7, s, 256, CONFIG, 0.0)}
    odd = [s for s in range(40)
           if set(FAMILY.stream_samples(7, s, 256, CONFIG, 1.0).tolist()) - plain]
    assert len(odd) >= 35


def test_frames_and_useful_work():
    meta, data = FAMILY.step_frame(np.array([3, 4], np.int32))
    assert meta == {"t": 2, "dtype": "i4"}
    assert np.frombuffer(data, "<i4").tolist() == [3, 4]
    with pytest.raises(ValueError, match="streams only"):
        FAMILY.score_frame(np.zeros((2, 2)))
    d, v, e = 64, 512, 128
    flops, nbytes = FAMILY.useful_work(CONFIG, "step", {"position": np.array([0, 9])})
    dense = 2 * FAMILY.non_embedding_params(CONFIG) + 2 * d * v
    assert flops == 2 * dense + 4 * d * (1 + 10)
    state = 13 * (e * 4 * 4 + 3 * e * 2) + d * 4
    assert nbytes == 2 * (2 * state + 8) + 2 * 16 * 2 * (9 + 2)
    assert FAMILY.useful_work(CONFIG, "score", {"length": np.array([4])}) == (0, 0)
