"""First-class Placement API (ISSUE 4): the declarative surface must be
hashable/comparable (it is part of the schedule cache key), the
``data_parallel`` shim must map with a warning, the single placement must
be a strict no-op, and — on a forced 4-host-device mesh in a subprocess —
sharded-pool streaming and data-parallel bucket scores must be
bit-equivalent to the unsharded pool and to solo ``stream_step``, with
admission control at ``capacity = slots_per_device x devices``."""
import os
import subprocess
import sys

import pytest

from conftest import GATEWAY_ARCH as ARCH
from repro.config import get_config
from repro.engine import AnomalyService, EngineConfig, Placement, build_engine
from repro.engine.placement import _mesh_for


# -- declarative surface ---------------------------------------------------


def test_placement_defaults_and_constructors():
    assert Placement() == Placement.single() == Placement.data(1)
    assert not Placement.single().is_sharded
    pl = Placement.data(4)
    assert pl.is_sharded and pl.devices_needed == 4
    assert pl == Placement(data_shards=4)
    assert hash(pl) == hash(Placement(data_shards=4))
    assert "Placement.data(4" in repr(pl)
    assert repr(Placement.single()) == "Placement.single()"


def test_placement_pad_rows_and_row_mapping():
    pl = Placement.data(4)
    assert [pl.pad_rows(n) for n in (1, 4, 5, 8, 30)] == [4, 4, 8, 8, 32]
    assert Placement.single().pad_rows(7) == 7
    # contiguous blocks: rows [d*rows/n, (d+1)*rows/n) live on shard d
    assert [pl.shard_of_row(r, 8) for r in range(8)] == [0, 0, 1, 1, 2, 2, 3, 3]


def test_placement_validation():
    with pytest.raises(ValueError, match="data_shards"):
        Placement(data_shards=0)
    with pytest.raises(ValueError, match="must differ"):
        Placement(data_axis="x", stage_axis="x")


def test_placement_from_spec():
    assert Placement.from_spec("data=4") == Placement.data(4)
    assert Placement.from_spec(" data=2 ,") == Placement.data(2)
    assert Placement.from_spec("") == Placement.single()
    with pytest.raises(ValueError, match="axes supported"):
        Placement.from_spec("model=2")
    with pytest.raises(ValueError, match="not an int"):
        Placement.from_spec("data=two")


def test_placement_mesh_requires_devices():
    """A placement wider than the device pool fails loudly at mesh build
    (engines/pools fail fast at construction, not first call)."""
    with pytest.raises(ValueError, match="devices"):
        _mesh_for(1999, "data")
    with pytest.raises(ValueError, match="devices"):
        build_engine(
            get_config(ARCH),
            EngineConfig(schedule="wavefront", placement=Placement.data(1999)),
        )


# -- deprecation shim ------------------------------------------------------


def test_data_parallel_shim_warns_and_maps():
    with pytest.warns(DeprecationWarning, match=r"Placement.data\(3\)"):
        shim = EngineConfig(schedule="wavefront", data_parallel=3)
    explicit = EngineConfig(schedule="wavefront", placement=Placement.data(3))
    assert shim == explicit and hash(shim) == hash(explicit)
    # the placement is the single source of truth: the legacy int folds in
    # and resets, the axis names mirror the placement
    assert shim.placement == Placement.data(3)
    assert shim.data_parallel is None is explicit.data_parallel
    assert shim.data_axis == "data" and shim.stage_axis == "model"


def test_explicit_placement_wins_over_legacy_fields():
    """Two sharded layouts in one config: the explicit placement wins, but
    never silently."""
    with pytest.warns(UserWarning, match="ignoring data_parallel=9"):
        cfg = EngineConfig(
            schedule="wavefront", data_parallel=9, placement=Placement.data(2)
        )
    assert cfg.placement == Placement.data(2) and cfg.data_parallel is None


def test_dataclasses_replace_data_parallel_still_shims():
    """``dataclasses.replace(cfg, data_parallel=N)`` on an unsharded config
    (a PR 1–3 idiom — the replaced config carries a non-None single
    placement) must map through the shim, not silently unshard."""
    import dataclasses

    base = EngineConfig(schedule="wavefront")
    with pytest.warns(DeprecationWarning, match=r"Placement.data\(4\)"):
        cfg = dataclasses.replace(base, data_parallel=4)
    assert cfg.placement == Placement.data(4) and cfg.data_parallel is None


def test_legacy_unshard_request_is_never_silent():
    """``replace(sharded_cfg, data_parallel=1)`` (the legacy 'unshard'
    spelling) cannot win over an explicit sharded placement, but it must
    say so — the real unshard is placement=Placement.single()."""
    import dataclasses

    sharded = EngineConfig(schedule="wavefront", placement=Placement.data(4))
    with pytest.warns(UserWarning, match="ignoring data_parallel=1"):
        cfg = dataclasses.replace(sharded, data_parallel=1)
    assert cfg.placement == Placement.data(4)


def test_dataclasses_replace_placement_unshards_cleanly():
    """``replace(sharded_cfg, placement=Placement.single())`` must yield an
    unsharded config without warnings — a stale legacy mirror must never
    veto an explicit placement (data_parallel folds to None, so there is
    no mirror to conflict with)."""
    import dataclasses
    import warnings

    sharded = EngineConfig(schedule="wavefront", placement=Placement.data(2))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        cfg = dataclasses.replace(sharded, placement=Placement.single())
    assert cfg.placement == Placement.single()


def test_default_config_carries_single_placement():
    cfg = EngineConfig()
    assert cfg.placement == Placement.single()
    assert cfg.data_parallel is None


# -- single-device no-op guarantee ----------------------------------------


@pytest.fixture(scope="module")
def svc():
    return AnomalyService(ARCH, schedule="wavefront")


def test_single_placement_is_noop(svc):
    engine = svc.engine
    assert engine.placement == Placement.single()
    assert engine._sharded == {}  # no sharded variants, no mesh built
    assert engine.with_placement(Placement.single()) is engine

    gw = svc.open_gateway(capacity=4, max_batch=4)
    assert gw.engine is svc.engine           # no engine re-layout
    assert gw.batcher.lanes == 4             # lanes == max_batch, unchanged
    assert gw.pool.slots_per_device == 4     # one device holds everything
    assert "placement" not in gw.stats()     # telemetry unchanged
    assert gw.pool.per_device_active() == [0]


def test_open_gateway_single_placement_kw(svc):
    gw = svc.open_gateway(capacity=2, placement=Placement.single())
    assert gw.engine is svc.engine and gw.service is svc


def test_gateway_placement_needs_devices(svc):
    from repro.gateway import AnomalyGateway

    with pytest.raises(ValueError, match="devices"):
        AnomalyGateway(svc, capacity=4, placement=Placement.data(1998))
    with pytest.raises(ValueError, match="devices"):
        svc.open_gateway(capacity=4, placement=1998)  # int shorthand
    with pytest.raises(TypeError, match="Placement or int"):
        AnomalyGateway(svc, capacity=4, placement="data=2")


# -- sharded serving on a forced 4-host-device mesh ------------------------

_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import jax, jax.numpy as jnp
from repro.engine import AnomalyService, EngineConfig, Placement

ARCH, FEATS, T = "lstm-ae-f32-d2", 32, 7
pl = Placement.data(4)
svc = AnomalyService(ARCH, schedule="wavefront")
rng = np.random.default_rng(0)

# capacity = slots_per_device x devices, served sharded AND unsharded
cap = 2 * 4
gws = svc.open_gateway(capacity=cap, max_batch=4, placement=pl)
gwu = svc.open_gateway(capacity=cap, max_batch=4)
assert gws.engine is not svc.engine and gws.placement == pl
assert gws.pool.slots_per_device == 2 and gws.batcher.lanes == 4
leaf = jax.tree.leaves(gws.pool._state)[0]
assert len(leaf.sharding.device_set) == 4, leaf.sharding

data = [rng.standard_normal((T, FEATS)).astype(np.float32) for _ in range(cap)]
for i in range(cap):
    gws.admit(i); gwu.admit(i)
# admission control: the sharded pool admits exactly capacity streams
try:
    gws.admit("overflow"); raise SystemExit("overadmitted past capacity")
except Exception as exc:
    assert type(exc).__name__ == "PoolFullError", exc
assert gws.pool.per_device_active() == [2, 2, 2, 2]  # balanced admission

# pooled streaming: sharded == unsharded, stepping irregular subsets
for t in range(T):
    stepping = [i for i in range(cap) if (t + i) % 3 != 2]
    rs = gws.step({i: data[i][t] for i in stepping})
    ru = gwu.step({i: data[i][t] for i in stepping})
    for i in stepping:
        np.testing.assert_array_equal(rs[i], ru[i])

# ... and both equal solo stream_step (the PR-2 oracle), per stream
for i in (0, 3, 7):
    sess = svc.stream_start(1)
    for t in range(T):
        if (t + i) % 3 != 2:
            errs, sess = svc.stream_step(jnp.asarray(data[i][t][None]), sess)
    np.testing.assert_allclose(gws.pool.error_of(i), float(errs[0]),
                               rtol=1e-6, atol=1e-7)

# evict -> slot frees -> readmission balances back onto the same device
final_s, final_u = gws.evict(5), gwu.evict(5)
np.testing.assert_array_equal(final_s, final_u)
gws.admit("fresh")
assert gws.pool.per_device_active() == [2, 2, 2, 2]

# data-parallel bucket scoring: sharded flush (padded to per-device
# multiple) == unsharded flush == direct B=1 scoring
lens = [5, 9, 16, 7, 12, 6, 31, 8]
windows = [rng.standard_normal((L, FEATS)).astype(np.float32) for L in lens]
ss, su = gws.score(windows), gwu.score(windows)
np.testing.assert_array_equal(ss, su)
for w, s in zip(windows[:3], ss[:3]):
    np.testing.assert_allclose(
        s, float(svc.score(jnp.asarray(w[None]))[0]), rtol=1e-6, atol=1e-7)

# telemetry: mesh layout + per-device occupancy and flush fill observable
st = gws.stats()
assert st["placement"]["data"] == 4
assert st["placement"]["slots_per_device"] == 2
assert st["placement"]["device_active"] == [2, 2, 2, 2]
assert len(st["gauge_vecs"]["pool.device_active"]) == 4
assert len(st["gauge_vecs"]["queue.device_fill"]) == 4
assert "placement" not in gwu.stats()

# uneven capacity pads the block but never admits the padding rows
gw6 = svc.open_gateway(capacity=6, placement=pl)
assert gw6.pool._block == 8 and gw6.pool.slots_per_device == 2
for i in range(6):
    gw6.admit(i)
try:
    gw6.admit("pad-row"); raise SystemExit("admitted a padding row")
except Exception as exc:
    assert type(exc).__name__ == "PoolFullError", exc

# the deprecation shim maps to the sharded placement
import warnings
with warnings.catch_warnings():
    warnings.simplefilter("ignore", DeprecationWarning)
    shim_cfg = EngineConfig(schedule="wavefront", data_parallel=4)
assert shim_cfg.placement == pl

# re-laying a sharded engine back onto the single placement must neither
# warn nor drag the old sharded layout along (legacy mirrors reset)
with warnings.catch_warnings():
    warnings.simplefilter("error")
    down = gws.engine.with_placement(Placement.single())
assert down.placement == Placement.single() and down._sharded == {}

# a service-side param swap must reach the placement-override gateway's
# own engine: it never serves stale params (the open-gateway contract)
orig_params = svc.params
other = AnomalyService(ARCH, schedule="wavefront", seed=123)
svc.recalibrate(params=other.params)
assert gws.engine.params is other.params
w0 = windows[0]
np.testing.assert_allclose(
    gws.score([w0])[0], float(other.score(jnp.asarray(w0[None]))[0]),
    rtol=1e-6, atol=1e-7)

# ... and a swap initiated on a SIBLING gateway routes through the
# service's _bind, so the placement-override gateway rebinds too
gwu.recalibrate(params=orig_params)
assert gws.engine.params is orig_params

# non-divisible batches fall back to the unsharded program, same values
e = gws.engine
b5 = jnp.asarray(np.stack([np.pad(w[:5], ((0, 0), (0, 0))) for w in windows[:5]]))
np.testing.assert_array_equal(
    np.asarray(e.score({"series": b5})),
    np.asarray(svc.engine.score({"series": b5})),
)
print("PLACEMENT_SHARDED_OK")
"""


def test_sharded_gateway_multi_device():
    """The real sharded path on 4 emulated host devices in a subprocess
    (device count is process-global): pooled streaming and bucket scores
    bit-equal to the unsharded pool, equivalence with solo stream_step,
    admission control at slots_per_device x devices, balanced admission,
    per-device telemetry, block padding, and the data_parallel shim."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # emulated host devices, never the chip
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PLACEMENT_SHARDED_OK" in out.stdout
