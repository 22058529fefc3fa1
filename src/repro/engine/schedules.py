"""Named execution schedules for the LSTM-AE (paper Section 3).

The paper's contribution is a *schedule* — how the (layer x time) iteration
grid of a recurrent stack is walked — not a new model.  This module turns
each schedule into a first-class, registry-resolved object so every
consumer (serving, benchmarks, examples) selects it by name:

* ``"sequential"`` — layer-by-layer (the CPU/GPU baseline the paper
  compares against): layer i runs over all timesteps before layer i+1.
* ``"wavefront"``  — single-device temporal-parallel dataflow (§3.2): at
  wavefront step k every layer fires concurrently on its own timestep.
* ``"pipelined"``  — multi-device pipeline over a stage mesh axis with
  ppermute FIFOs (§3.1's inter-module queues).  Stage grouping + mesh
  construction are encapsulated here; on a single device it degenerates
  to the wavefront schedule (same dataflow semantics, no stage axis).
* ``"fused"``      — the Pallas fused-cell kernel (kernels/lstm_cell.py:
  MVM_X + MVM_H + gates + element-wise as one MXU kernel) scanned over the
  (layer, time) grid; compiled on TPU, interpreted on CPU.

Third-party backends register with :func:`register_schedule`; see README
§Execution engine for the contract.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Callable, NamedTuple, Optional, TYPE_CHECKING

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.config.core import ModelConfig
from repro.core.lstm import lstm_ae_sequential
from repro.core.temporal import build_stage_params, pipelined_forward, wavefront_forward
from repro.utils import Params

if TYPE_CHECKING:
    from repro.engine.base import EngineConfig

# (params, xs (T, B, F)) -> reconstruction (T, B, F)
ForwardFn = Callable[[Params, jnp.ndarray], jnp.ndarray]


class Schedule(NamedTuple):
    """A resolved schedule: the executor plus its Eq-1 accounting kind."""
    name: str            # requested registry name
    resolved: str        # actual executor after fallbacks (may differ)
    latency_kind: str    # "dataflow" | "sequential" (core.latency Eq-1 mode)
    forward: ForwardFn
    # True when the factory already manages compilation internally (the
    # Engine must NOT wrap forward in its own programs; see _pipelined)
    prejitted: bool = False

    @property
    def tag(self) -> str:
        """Display form: the requested name, plus the resolved executor
        when a fallback rerouted it (e.g. ``pipelined->wavefront``)."""
        return self.name if self.resolved == self.name else f"{self.name}->{self.resolved}"


# name -> factory(cfg, engine_cfg) -> Schedule
_SCHEDULES: dict[str, Callable[[ModelConfig, "EngineConfig"], Schedule]] = {}
# name -> EngineConfig field names the factory actually reads (None = all).
# Used to canonicalise the cache key so configs differing only in fields a
# schedule ignores share one Schedule (and one set of compiled programs).
_SCHEDULE_FIELDS: dict[str, Optional[tuple[str, ...]]] = {}

# Resolve cache: explicit LRU so compiled executors (and, for "pipelined",
# their meshes) cannot accumulate without bound when callers resolve many
# distinct EngineConfigs.  Keys are canonicalised (see _canonical_cfg).
SCHEDULE_CACHE_CAPACITY = 32
_RESOLVE_CACHE: "OrderedDict[tuple, Schedule]" = OrderedDict()
# Monotonic resolve counters (process lifetime, not reset with the cache):
# a miss is a full factory build — possibly a fresh mesh + retrace — so a
# climbing miss count under steady serving is a recompile storm in progress.
_CACHE_STATS = {"hits": 0, "misses": 0}


def register_schedule(name: str, *, config_fields: Optional[tuple[str, ...]] = None):
    """Register a schedule factory under ``name`` (decorator).

    The factory receives ``(model_cfg, engine_cfg)`` and returns a
    :class:`Schedule` whose ``forward`` maps ``(params, xs (T,B,F))`` to the
    reconstruction ``(T,B,F)``.  Registration is how new backends plug in.

    ``config_fields`` optionally names the :class:`EngineConfig` fields the
    factory reads (e.g. ``("pwl",)``); resolutions then cache on those
    fields only, so EngineConfigs differing in irrelevant knobs share one
    compiled executor.  Omit it (the safe default) to key on every field.
    ``placement`` is always part of the key, declared or not — sharded and
    unsharded device layouts never share a cached Schedule.
    """
    def deco(factory):
        _SCHEDULES[name] = factory
        _SCHEDULE_FIELDS[name] = config_fields
        _RESOLVE_CACHE.clear()  # re-registration must not serve stale
        return factory
    return deco


def unregister_schedule(name: str) -> None:
    """Remove a registered schedule and drop its cached resolutions."""
    _SCHEDULES.pop(name, None)
    _SCHEDULE_FIELDS.pop(name, None)
    _RESOLVE_CACHE.clear()


def available_schedules() -> list[str]:
    return sorted(_SCHEDULES)


def schedule_cache_info() -> dict:
    """Resolve-cache occupancy — regression surface for the LRU cap.

    ``always_keyed`` are the EngineConfig fields every cache key includes
    regardless of a schedule's declared ``config_fields``; ``placements``
    lists the distinct device layouts currently cached (sharded and
    unsharded resolutions never alias one entry)."""
    return {
        "size": len(_RESOLVE_CACHE),
        "capacity": SCHEDULE_CACHE_CAPACITY,
        "always_keyed": ("schedule", "placement"),
        "placements": sorted({repr(k[2].placement) for k in _RESOLVE_CACHE}),
        "hits": _CACHE_STATS["hits"],
        "misses": _CACHE_STATS["misses"],
    }


def _canonical_cfg(name: str, engine_cfg: "EngineConfig") -> "EngineConfig":
    """Project ``engine_cfg`` onto the fields schedule ``name`` declares it
    reads; everything else is reset to the EngineConfig default so it cannot
    split the cache key.  ``placement`` is ALWAYS part of the key — a
    prejitted schedule bakes its compiled programs (and mesh) into the
    Schedule object, so two engines differing only in device layout must
    never alias one cached program (the ISSUE-4 aliasing bug)."""
    fields = _SCHEDULE_FIELDS.get(name)
    if fields is None:
        return dataclasses.replace(engine_cfg, schedule=name)
    from repro.engine.base import EngineConfig

    return dataclasses.replace(
        EngineConfig(schedule=name, placement=engine_cfg.placement),
        **{f: getattr(engine_cfg, f) for f in fields},
    )


def resolve_schedule(name: str, cfg: ModelConfig, engine_cfg: "EngineConfig") -> Schedule:
    """Look up ``name`` in the registry and build its executor.

    Resolutions are cached per (name, cfg, canonicalised engine_cfg):
    repeated calls — e.g. ``ModelAPI.prefill`` resolving per request, or
    several Engines on the same config — share one Schedule and hence one
    set of compiled programs instead of rebuilding meshes and retracing
    every time.  The cache is a capped LRU (``SCHEDULE_CACHE_CAPACITY``)
    so many distinct configs cannot leak compiled meshes."""
    if name not in _SCHEDULES:
        raise ValueError(
            f"unknown schedule {name!r}; available schedules: "
            f"{', '.join(available_schedules())}"
        )
    canon = _canonical_cfg(name, engine_cfg)
    key = (name, cfg, canon)
    sched = _RESOLVE_CACHE.get(key)
    if sched is None:
        _CACHE_STATS["misses"] += 1
        sched = _SCHEDULES[name](cfg, canon)
        _RESOLVE_CACHE[key] = sched
        while len(_RESOLVE_CACHE) > SCHEDULE_CACHE_CAPACITY:
            _RESOLVE_CACHE.popitem(last=False)
    else:
        _CACHE_STATS["hits"] += 1
        _RESOLVE_CACHE.move_to_end(key)
    return sched


def resolve_forward(
    name: str, cfg: ModelConfig, *, pwl: bool = False, n_stages: Optional[int] = None
) -> ForwardFn:
    """Convenience: schedule name -> ForwardFn with a default EngineConfig
    (used by ``models.lstm_ae.prefill`` so the ModelAPI delegates here)."""
    from repro.engine.base import EngineConfig

    ecfg = EngineConfig(schedule=name, pwl=pwl, n_stages=n_stages)
    return resolve_schedule(name, cfg, ecfg).forward


@register_schedule("sequential", config_fields=("pwl",))
def _sequential(cfg: ModelConfig, ecfg: "EngineConfig") -> Schedule:
    def forward(params, xs):
        return lstm_ae_sequential(params, xs, pwl=ecfg.pwl)

    return Schedule("sequential", "sequential", "sequential", forward)


@register_schedule("wavefront", config_fields=("pwl",))
def _wavefront(cfg: ModelConfig, ecfg: "EngineConfig") -> Schedule:
    def forward(params, xs):
        return wavefront_forward(params, xs, pwl=ecfg.pwl)

    return Schedule("wavefront", "wavefront", "dataflow", forward)


def _divisor_block(n: int, cap: int = 128, align: int = 8) -> int:
    """Block size for a Pallas grid dimension of extent ``n``.

    Mosaic accepts a block only when it spans the whole dimension or is a
    multiple of the tile (8 sublanes for rows, 128 lanes for the minor
    dimension), so: the whole dimension when it fits under ``cap``, else
    the largest multiple of ``align`` <= cap that divides ``n``, else the
    whole dimension."""
    if n <= cap:
        return n
    for d in range(cap - cap % align, 0, -align):
        if n % d == 0:
            return d
    return n


@register_schedule("fused", config_fields=("pwl",))
def _fused(cfg: ModelConfig, ecfg: "EngineConfig") -> Schedule:
    """Pallas fused-cell schedule (ROADMAP follow-up): scans the fused
    MVM_X+MVM_H+gates kernel of ``kernels/lstm_cell.py`` over the
    (layer, time) grid layer-by-layer — the paper's single-module datapath
    as one MXU kernel per (layer, timestep).  Compiled on TPU; interpreted
    on CPU so CPU CI exercises the same kernel code."""
    from repro.kernels.lstm_cell import lstm_cell_pallas, pack_weights
    from repro.kernels.ops import pallas_interpret

    interpret = pallas_interpret()

    def forward(params, xs):
        ys = xs
        for layer in params["layers"]:
            wx, wh, b = pack_weights(layer)
            bsz = ys.shape[1]
            hidden = wh.shape[1]
            block_b = _divisor_block(bsz)
            block_h = _divisor_block(hidden, align=128)
            h0 = jnp.zeros((bsz, hidden), ys.dtype)
            c0 = jnp.zeros((bsz, hidden), jnp.float32)

            def step(carry, x_t, wx=wx, wh=wh, b=b, bb=block_b, bh=block_h):
                h, c = carry
                h, c = lstm_cell_pallas(
                    x_t, h, c, wx, wh, b, block_b=bb, block_h=bh,
                    pwl=ecfg.pwl, interpret=interpret,
                )
                return (h, c), h

            _, ys = jax.lax.scan(step, (h0, c0), ys)
        return ys

    return Schedule("fused", "fused", "sequential", forward)


@register_schedule("pipelined")  # reads every EngineConfig field: key on all
def _pipelined(cfg: ModelConfig, ecfg: "EngineConfig") -> Schedule:
    if cfg.lstm_ae is None:
        raise ValueError("pipelined schedule requires an lstm_ae config")
    depth = len(cfg.lstm_ae.layer_sizes())
    devices = jax.devices()
    data_par = ecfg.placement.data_shards  # data_parallel=N arrives here too (shim)
    n_stages = ecfg.n_stages or min(len(devices) // data_par, depth)

    if n_stages < 2:
        if data_par > 1:
            # the caller explicitly asked for batch sharding — degrading to
            # an unsharded single-device run must not happen silently
            raise ValueError(
                f"pipelined schedule with Placement.data({data_par}) needs "
                f"at least {2 * data_par} devices (2 stages x {data_par}), "
                f"have {len(devices)}"
            )
        # Single device (or a 1-stage request): the pipeline degenerates to
        # the wavefront schedule — identical dataflow semantics, no stage
        # axis.  Eq-1 accounting stays "dataflow".
        wf = _wavefront(cfg, ecfg)
        return Schedule("pipelined", "wavefront", "dataflow", wf.forward)

    need = data_par * n_stages
    if len(devices) < need:
        raise ValueError(
            f"pipelined schedule needs {need} devices "
            f"({data_par} data x {n_stages} stages), have {len(devices)}"
        )
    mesh = jax.make_mesh(
        (data_par, n_stages), (ecfg.data_axis, ecfg.stage_axis),
        (AxisType.Auto, AxisType.Auto), devices=devices[:need],
    )

    # Stage grouping (balanced DP over per-timestep FLOPs) is encapsulated
    # here — callers never hand-build stage params or meshes.  The program
    # runs on its own (data, stage) mesh, which the Engine's data-placement
    # shardings do not describe, so this Schedule is ``prejitted`` and the
    # Engine must not wrap it in its row-sharded programs.
    def forward(params, xs):
        stage_params, counts, _ = build_stage_params(params, cfg, n_stages)
        return pipelined_forward(
            stage_params, counts, xs, mesh=mesh, cfg=cfg,
            stage_axis=ecfg.stage_axis, batch_axes=(ecfg.data_axis,),
            pwl=ecfg.pwl,
        )

    return Schedule("pipelined", "pipelined", "dataflow",
                    jax.jit(forward) if ecfg.jit else forward, prejitted=True)
