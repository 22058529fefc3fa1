"""Compiles for a described TPU v5e: what interpret mode cannot catch.

The kernels run interpreted on the CPU everywhere else in the suite;
here Mosaic and the TPU compiler see them at the served widths, so a
block shape off the tiling or a program that does not fit is refused
without a chip.  Nothing runs: these tests say nothing about values.

The topology is described inside a fixture and never at import time:
only one process may load libtpu, so describing it while collecting
would make the other test workers fail to load it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import get_config
from repro.core import init_lstm_ae
from repro.engine import build_engine
from repro.engine.schedules import _divisor_block
from repro.kernels.lstm_cell import lstm_cell_pallas
from repro.kernels.lstm_seq import lstm_seq_pallas

CFG = get_config("lstm-ae-f64-d6")
LAYERS = list(zip(CFG.lstm_ae.layer_input_sizes(), CFG.lstm_ae.layer_sizes()))
# micro-batch lane counts the gateway compiles (launcher default,
# open_gateway default, and a wide flush)
BATCHES = (16, 32, 64)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the cause, it cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, *dims):
    return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)


def _compile_cell(one_chip, bsz, n_in, hidden, block_b, block_h):
    fn = jax.jit(lambda x, h, c, wx, wh, b: lstm_cell_pallas(
        x, h, c, wx, wh, b, block_b=block_b, block_h=block_h))
    return fn.lower(
        _shape(one_chip, bsz, n_in), _shape(one_chip, bsz, hidden),
        _shape(one_chip, bsz, hidden), _shape(one_chip, 4, n_in, hidden),
        _shape(one_chip, 4, hidden, hidden), _shape(one_chip, 4, hidden),
    ).compile()


@pytest.mark.parametrize("bsz", BATCHES)
@pytest.mark.parametrize("n_in,hidden", LAYERS)
def test_lstm_cell_kernel_compiles(one_chip, bsz, n_in, hidden):
    """The fused schedule's cell kernel, blocked as the schedule blocks it."""
    compiled = _compile_cell(one_chip, bsz, n_in, hidden, _divisor_block(bsz),
                             _divisor_block(hidden, align=128))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bsz", BATCHES)
@pytest.mark.parametrize("n_in,hidden", LAYERS)
def test_lstm_seq_kernel_compiles(one_chip, bsz, n_in, hidden):
    t_len = 64
    fn = jax.jit(lambda xs, h0, c0, wx, wh, b: lstm_seq_pallas(
        xs, h0, c0, wx, wh, b, block_b=bsz))
    compiled = fn.lower(
        _shape(one_chip, t_len, bsz, n_in), _shape(one_chip, bsz, hidden),
        _shape(one_chip, bsz, hidden), _shape(one_chip, 4, n_in, hidden),
        _shape(one_chip, 4, hidden, hidden), _shape(one_chip, 4, hidden),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_divisor_block_aligns_batch_the_compiler_refused(one_chip):
    """A 200-row batch used to get block 100, which is neither a multiple
    of 8 nor the whole dimension; the aligned block compiles."""
    block = _divisor_block(200)
    assert block % 8 == 0 and 200 % block == 0
    n_in, hidden = LAYERS[0]
    _compile_cell(one_chip, 200, n_in, hidden, block, hidden)


def test_wavefront_score_program_compiles(one_chip):
    """The gateway's masked score program on the wavefront schedule, at
    one bucket (32 lanes x 64 steps) of the widest, deepest config."""
    engine = build_engine(CFG, "wavefront")
    params = jax.eval_shape(lambda: init_lstm_ae(jax.random.PRNGKey(0), CFG))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)
    series = _shape(one_chip, 32, 64, CFG.lstm_ae.input_features)
    lengths = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip)
    compiled = engine._score_masked.lower(params, series, lengths).compile()
    assert compiled.memory_analysis() is not None


def test_jamba_pool_step_fits_one_chip(one_chip):
    """The session pool's step for AI21-Jamba2-3B at its served size (256
    slots, KV room 4,096, bf16 matrices): it compiles, its state block is
    donated (every state byte aliased to the output), and weights, state
    and temporaries fit one chip's 16 GB."""
    from repro.gateway.pool import pool_step_program
    from repro.models import build_model

    cfg = get_config("jamba2-3b")
    model = build_model(cfg).stream()
    rows, room = 256, 4096

    def served(path, leaf):
        name = getattr(path[-1], "key", "")
        dtype = jnp.bfloat16 if name in ("w", "table", "conv_w") else jnp.float32
        return jax.ShapeDtypeStruct(leaf.shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map_with_path(
        served, jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0)))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: model.init_state(rows, room)))

    def vec(dtype):
        return jax.ShapeDtypeStruct((rows,), dtype, sharding=one_chip)

    compiled = jax.jit(pool_step_program(model), donate_argnums=(2, 4, 5)).lower(
        params, vec(jnp.int32), state, vec(jnp.bool_), vec(jnp.float32),
        vec(jnp.int32)).compile()
    mem = compiled.memory_analysis()
    state_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
