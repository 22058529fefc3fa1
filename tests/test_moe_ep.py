"""Expert-parallel all_to_all MoE (the §Perf dispatch fix) must match the
single-device scatter path bit-for-bit when nothing is dropped."""
import os
import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.config.core import ModelConfig, MoEConfig
from repro.distributed.sharding import mesh_context, rules_for_mesh
from repro.layers.moe import apply_moe, apply_moe_ep, init_moe
from repro.launch.mesh import make_host_mesh

cfg = ModelConfig(
    name="t", family="transformer", num_layers=1, d_model=32, num_heads=4,
    num_kv_heads=4, d_ff=64, vocab_size=64,
    moe=MoEConfig(num_experts=8, top_k=2, capacity_factor=8.0, impl="ep_a2a"),
)
params = init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))

# reference: single-device scatter path (no mesh)
y_ref, aux_ref = apply_moe(params, x, cfg)

mesh = make_host_mesh((2, 4), ("data", "model"))
rules = rules_for_mesh(mesh)

def run(p, xx):
    with mesh_context(mesh, rules):
        return apply_moe_ep(p, xx, cfg)

y_ep, aux_ep = jax.jit(run)(params, x)
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref), rtol=2e-4, atol=2e-5)
np.testing.assert_allclose(float(aux_ep), float(aux_ref), rtol=1e-4)

# gradients flow through the a2a path
def loss(p):
    with mesh_context(mesh, rules):
        y, aux = apply_moe_ep(p, x, cfg)
    return jnp.sum(jnp.square(y)) + 0.01 * aux
g = jax.jit(jax.grad(loss))(params)
gnorm = sum(float(jnp.abs(l).sum()) for l in jax.tree.leaves(g))
assert np.isfinite(gnorm) and gnorm > 0

# decode variant (S=1 -> replicated tokens + psum combine)
x1 = jax.random.normal(jax.random.PRNGKey(2), (8, 1, 32))
y_ref1, aux_ref1 = apply_moe(params, x1, cfg)
y_ep1, aux_ep1 = jax.jit(run)(params, x1)
np.testing.assert_allclose(np.asarray(y_ep1), np.asarray(y_ref1), rtol=2e-4, atol=2e-5)
np.testing.assert_allclose(float(aux_ep1), float(aux_ref1), rtol=1e-4)
print("MOE_EP_OK", gnorm)
"""


def test_moe_ep_matches_scatter():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # emulated host devices, never the chip
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MOE_EP_OK" in out.stdout
