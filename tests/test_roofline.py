"""The roofline extraction machinery: trip-count-aware HLO cost model and
collective parsing (validated against programs with known exact costs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.extract import active_param_count, model_flops_estimate
from repro.roofline.hlo_cost import analyze_hlo


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_single_matmul_flops_exact():
    a = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    t = analyze_hlo(_compiled_text(lambda x, y: x @ y, a, b))
    assert t.flops == 2 * 256 * 512 * 128


def test_scan_flops_multiplied_by_trip_count():
    def body(h, w):
        return jnp.tanh(h @ w), None

    def scanned(h, ws):
        return jax.lax.scan(body, h, ws)[0]

    h = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((13, 64, 64), jnp.float32)
    t = analyze_hlo(_compiled_text(scanned, h, ws))
    assert t.flops == 13 * 2 * 64**3
    assert not t.notes


def test_nested_scan_flops():
    def inner(h, w):
        return jnp.tanh(h @ w), None

    def outer(h, ws):
        return jax.lax.scan(inner, h, ws)[0], None

    def nested(h, ws):
        return jax.lax.scan(outer, h, ws)[0]

    h = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, 5, 32, 32), jnp.float32)
    t = analyze_hlo(_compiled_text(nested, h, ws))
    assert t.flops == 15 * 2 * 32**3


def test_grad_flops_counts_fwd_and_bwd():
    def loss(w, x):
        return jnp.sum((x @ w) ** 2)

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    t = analyze_hlo(_compiled_text(jax.grad(loss, argnums=(0, 1)), x, x))
    assert t.flops == 3 * 2 * 128**3  # fwd + dW + dX


def test_bytes_scale_with_tensor_size():
    a1 = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    a2 = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    f = lambda x: jnp.tanh(x) * 2.0 + 1.0
    t1 = analyze_hlo(_compiled_text(f, a1))
    t2 = analyze_hlo(_compiled_text(f, a2))
    assert t2.bytes > 10 * t1.bytes  # 16x elements


def test_collective_parse_psum():
    """shard_map psum lowers to all-reduce; payload must be counted."""
    import subprocess, sys, os, json, textwrap
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax.sharding import AxisType
        from repro.roofline.hlo_cost import analyze_hlo
        mesh = jax.make_mesh((4,), ("x",), (AxisType.Auto,))
        def f(a):
            return jax.lax.psum(a, "x")
        g = jax.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P(), check_vma=False)
        text = jax.jit(g).lower(jax.ShapeDtypeStruct((64, 128), jnp.float32)).compile().as_text()
        t = analyze_hlo(text)
        print("COLL", int(t.coll_bytes), t.coll_by_op)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("COLL")][0]
    coll = int(line.split()[1])
    # per-device shard is (16,128) f32 = 8192 bytes; all-reduce payload >= that
    assert coll >= 8192, line
    assert "all-reduce" in line


def test_active_param_count_orders_of_magnitude():
    from repro.config import get_config
    # dense: close to the advertised sizes
    assert 1.0e9 < active_param_count(get_config("tinyllama-1.1b")) < 1.35e9
    assert 0.9e9 < active_param_count(get_config("olmo-1b")) < 1.6e9
    assert 17e9 < active_param_count(get_config("internlm2-20b")) < 23e9
    # MoE: active (not total) params
    moonshot = active_param_count(get_config("moonshot-v1-16b-a3b"))
    assert 2e9 < moonshot < 5e9  # "A3B" = ~3B active
    dbrx = active_param_count(get_config("dbrx-132b"))
    assert 30e9 < dbrx < 45e9    # dbrx ~36B active


def test_model_flops_kinds():
    from repro.config import TRAIN_4K, DECODE_32K, get_config
    cfg = get_config("tinyllama-1.1b")
    train = model_flops_estimate(cfg, TRAIN_4K)
    decode = model_flops_estimate(cfg, DECODE_32K)
    tokens = TRAIN_4K.global_batch * TRAIN_4K.seq_len
    assert train == pytest.approx(6 * active_param_count(cfg) * tokens)
    assert decode == pytest.approx(2 * active_param_count(cfg) * DECODE_32K.global_batch)
