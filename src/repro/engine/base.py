"""The unified execution engine: one surface over every temporal schedule.

An :class:`Engine` binds a model config (or :class:`~repro.models.api.ModelAPI`)
plus parameters to a *named* execution schedule resolved from the registry in
``engine/schedules.py``.  All consumers — serving, benchmarks, examples —
talk to the same four methods regardless of which schedule executes:

    engine = build_engine(cfg, "wavefront", params=params)
    recon  = engine.reconstruct(batch)    # (B, T, F)
    errors = engine.score(batch)          # (B,) per-sequence MSE
    y, st  = engine.stream(x_t, st)       # one timestep, carried state
    est    = engine.latency_model(T)      # Eq-1 accounting for this schedule

Schedule choice is therefore a config knob (``EngineConfig.schedule`` or a
plain string), which is what the paper's sequential-vs-temporal-parallel
comparison needs and what future backends plug into.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from dataclasses import dataclass
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.config.core import ModelConfig
from repro.core.latency import PAPER_RH_M, LatencyEstimate, fpga_latency_ms
from repro.engine.placement import Placement
from repro.engine.schedules import Schedule, resolve_schedule
from repro.utils import Params


@dataclass(frozen=True)
class EngineConfig:
    """Declarative engine selection — everything needed to resolve a schedule.

    ``schedule``       registry name ("sequential" | "wavefront" | "pipelined" | ...)
    ``pwl``            piecewise-linear activations (the paper's HLS numerics)
    ``n_stages``       pipeline stages (pipelined; default: min(devices, depth))
    ``placement``      device placement (:class:`~repro.engine.placement.Placement`):
                       data-mesh ways + axis names for pool slots, micro-batch
                       rows and pipeline stages; defaults to the single-device
                       no-op placement
    ``jit``            wrap the executor in jax.jit (disable for debugging)

    ``data_parallel`` / ``data_axis`` / ``stage_axis`` are the PR 1–3
    placement surface, kept as a deprecation shim: ``data_parallel=N`` maps
    to ``Placement.data(N)`` with a warning — including through
    ``dataclasses.replace(cfg, data_parallel=N)`` on an unsharded config.
    After normalisation ``data_parallel`` is *folded into* the placement
    and reset to None (so the two spellings hash/compare equal, and a
    later ``replace(cfg, placement=...)`` cannot be overridden by a stale
    legacy int), while ``data_axis``/``stage_axis`` mirror the placement's
    axis names.  When an explicitly *sharded* ``placement`` and a legacy
    int disagree, the placement wins with a ``UserWarning`` (never
    silently); read the layout from ``cfg.placement``, not the legacy
    fields.
    """
    schedule: str = "wavefront"
    pwl: bool = False
    n_stages: Optional[int] = None
    # DEPRECATED: use placement=Placement.data(N); None once normalised
    data_parallel: Optional[int] = None
    stage_axis: str = "model"   # DEPRECATED: use placement=Placement(stage_axis=...)
    data_axis: str = "data"     # DEPRECATED: use placement=Placement(data_axis=...)
    jit: bool = True
    placement: Optional[Placement] = None

    def __post_init__(self):
        pl = self.placement
        dp = self.data_parallel
        if pl is None:
            pl = Placement(data_shards=1, data_axis=self.data_axis,
                           stage_axis=self.stage_axis)
        if dp is not None and dp != pl.data_shards:
            if not pl.is_sharded:
                # the deprecated spelling (constructor or
                # dataclasses.replace on an unsharded config): fold it in
                warnings.warn(
                    f"EngineConfig(data_parallel={dp}) is deprecated; use "
                    f"placement=Placement.data({dp})",
                    DeprecationWarning, stacklevel=3,
                )
                pl = dataclasses.replace(pl, data_shards=dp)
            else:
                # the legacy int disagrees with a sharded placement
                # (including data_parallel=1, the legacy 'unshard'): the
                # placement wins, but never silently — unshard with
                # placement=Placement.single()
                warnings.warn(
                    f"EngineConfig: ignoring data_parallel={dp} in favour "
                    f"of the explicit placement {pl!r}",
                    UserWarning, stacklevel=3,
                )
        # the placement is now the single source of truth: the legacy int
        # folds in and resets (so shim and explicit spellings compare
        # equal, and replacing the placement later is never overridden by
        # a stale mirror); the axis names mirror the placement
        object.__setattr__(self, "placement", pl)
        object.__setattr__(self, "data_parallel", None)
        object.__setattr__(self, "data_axis", pl.data_axis)
        object.__setattr__(self, "stage_axis", pl.stage_axis)


def _as_engine_cfg(schedule: Union[str, EngineConfig]) -> EngineConfig:
    if isinstance(schedule, EngineConfig):
        return schedule
    return EngineConfig(schedule=schedule)


class Engine:
    """A model bound to one named temporal schedule.

    Construct via :func:`build_engine`.  ``params`` may be bound at
    construction, later via :meth:`bind`, or supplied per call through the
    ``*_with`` variants (the form ModelAPI/serving steps use).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        engine_cfg: Union[str, EngineConfig] = "wavefront",
        params: Optional[Params] = None,
    ):
        if cfg.family != "lstm_ae" or cfg.lstm_ae is None:
            raise ValueError(
                f"Engine executes the paper's lstm_ae family; got {cfg.family!r}"
            )
        self.cfg = cfg
        self.engine_cfg = _as_engine_cfg(engine_cfg)
        self.schedule: Schedule = resolve_schedule(
            self.engine_cfg.schedule, cfg, self.engine_cfg
        )
        self.params = params
        fwd = self.schedule.forward

        # Whole-request programs (transpose + forward + reduction fused),
        # jitted as one unit unless the schedule manages its own compilation
        # (prejitted, e.g. pipelined — its program runs on its own stage
        # mesh; see schedules.py).
        def _reconstruct(params, series):
            xs = jnp.swapaxes(series, 0, 1)
            return jnp.swapaxes(fwd(params, xs), 0, 1)

        def _score(params, series):
            xs = jnp.swapaxes(series, 0, 1)
            recon = fwd(params, xs)
            return jnp.mean(
                jnp.square(recon.astype(jnp.float32) - xs.astype(jnp.float32)),
                axis=(0, 2),
            )

        def _score_masked(params, series, lengths):
            # Per-sequence MSE over each row's valid prefix only.  The LSTM
            # stack is causal, so zero-padding rows out to a common T does
            # not perturb the valid timesteps — the contract the gateway's
            # shape-bucketed micro-batching relies on.
            xs = jnp.swapaxes(series, 0, 1)                       # (T, B, F)
            recon = fwd(params, xs)
            sq = jnp.mean(
                jnp.square(recon.astype(jnp.float32) - xs.astype(jnp.float32)),
                axis=2,
            )                                                     # (T, B)
            valid = jnp.arange(sq.shape[0])[:, None] < lengths[None, :]
            denom = jnp.maximum(lengths, 1).astype(jnp.float32)
            return jnp.sum(jnp.where(valid, sq, 0.0), axis=0) / denom

        jit_here = self.engine_cfg.jit and not self.schedule.prejitted
        self._reconstruct = jax.jit(_reconstruct) if jit_here else _reconstruct
        self._score = jax.jit(_score) if jit_here else _score
        self._score_masked = jax.jit(_score_masked) if jit_here else _score_masked
        step = self._stream_step
        self._step = jax.jit(step) if self.engine_cfg.jit else step
        mstep = self._masked_stream_step
        self._mstep = jax.jit(mstep) if self.engine_cfg.jit else mstep

        # Placement-aware variants: the same programs jitted with explicit
        # in/out shardings — batch rows (and streaming state rows) laid out
        # over the placement's data axis, params replicated.  Built only for
        # a sharded placement (the single placement is a strict no-op) and
        # dispatched per call when the leading dim divides the mesh; callers
        # that need guaranteed sharding (the gateway) pad to a per-device
        # multiple.  Prejitted schedules (pipelined) manage their own batch
        # sharding, so only the schedule-independent streaming programs get
        # sharded variants there.
        self._sharded: dict[str, "object"] = {}
        pl = self.placement
        if pl.is_sharded and self.engine_cfg.jit:
            rows = pl.row_sharding()   # builds (or fails fast on) the mesh
            repl = pl.replicated_sharding()
            if not self.schedule.prejitted:
                self._sharded["reconstruct"] = jax.jit(
                    _reconstruct, in_shardings=(repl, rows), out_shardings=rows)
                self._sharded["score"] = jax.jit(
                    _score, in_shardings=(repl, rows), out_shardings=rows)
                self._sharded["score_masked"] = jax.jit(
                    _score_masked, in_shardings=(repl, rows, rows),
                    out_shardings=rows)
            self._sharded["step"] = jax.jit(
                step, in_shardings=(repl, rows, rows), out_shardings=(rows, rows))
            self._sharded["mstep"] = jax.jit(
                mstep, in_shardings=(repl, rows, rows, rows),
                out_shardings=(rows, rows))

        # Compile profiling: a jitted program (re)traces+compiles on the
        # first call per input shape, so the first-call wall time per
        # (program, shape) is the compile-cost proxy — that is what makes
        # a recompile storm on the bucket ladder visible in stats().
        self._seen_shapes: set = set()
        self.profile: dict = {"compiles": 0, "compile_ms": 0.0,
                              "per_program": {}}

    # -- placement ---------------------------------------------------------

    @property
    def placement(self) -> Placement:
        """The device placement this engine's programs are laid out on."""
        return self.engine_cfg.placement

    def with_placement(self, placement: Placement) -> "Engine":
        """A new engine on the same model/schedule/params with ``placement``
        (returns self when the placement already matches).  Compiled
        programs are NOT shared — sharded and unsharded programs must
        never collide (the resolve cache keys on placement too)."""
        if placement == self.placement:
            return self
        # data_parallel is always None post-normalisation, so replacing the
        # placement cannot be vetoed by a stale legacy mirror
        ecfg = dataclasses.replace(self.engine_cfg, placement=placement)
        return Engine(self.cfg, ecfg, params=self.params)

    def _row_program(self, key: str, rows: int):
        """The sharded variant of program ``key`` when one exists and the
        leading dim splits evenly over the data mesh; None otherwise (the
        caller falls back to the unsharded program — value-identical, the
        rows are independent)."""
        prog = self._sharded.get(key)
        if prog is not None and rows % self.placement.data_shards == 0:
            return prog
        return None

    # -- profiling ---------------------------------------------------------

    def _run_profiled(self, name: str, prog, shape: tuple, *args):
        """Dispatch ``prog`` and, on the first call per (program, shape),
        record its wall time as that shape's compile cost (tracing and
        compilation happen synchronously inside the first dispatch).
        Steady-state cost is one set lookup."""
        key = (name, shape)
        if key in self._seen_shapes:
            return prog(*args)
        t0 = time.perf_counter()
        out = prog(*args)
        ms = (time.perf_counter() - t0) * 1e3
        self._seen_shapes.add(key)
        self.profile["compiles"] += 1
        self.profile["compile_ms"] += ms
        per = self.profile["per_program"].setdefault(
            name, {"compiles": 0, "compile_ms": 0.0, "shapes": []}
        )
        per["compiles"] += 1
        per["compile_ms"] += ms
        per["shapes"].append(list(shape))
        return out

    def profile_info(self) -> dict:
        """JSON-safe compile profile: total + per-program compile counts,
        first-call wall time, and the shapes (bucket ladder rungs) seen."""
        return {
            "schedule": self.schedule.tag,
            "compiles": self.profile["compiles"],
            "compile_ms": round(self.profile["compile_ms"], 3),
            "per_program": {
                name: {
                    "compiles": d["compiles"],
                    "compile_ms": round(d["compile_ms"], 3),
                    "shapes": list(d["shapes"]),
                }
                for name, d in self.profile["per_program"].items()
            },
        }

    # -- binding ----------------------------------------------------------

    def bind(self, params: Params) -> "Engine":
        """Bind parameters; returns self (compiled executors are reused)."""
        self.params = params
        return self

    def _require_params(self) -> Params:
        if self.params is None:
            raise ValueError("engine has no bound params; call bind(params)")
        return self.params

    # -- batch surface ----------------------------------------------------

    def reconstruct_with(self, params: Params, batch: dict) -> jnp.ndarray:
        """batch {"series": (B, T, F)} -> reconstruction (B, T, F)."""
        series = batch["series"]
        sharded = self._row_program("reconstruct", series.shape[0])
        return self._run_profiled(
            "reconstruct@sharded" if sharded is not None else "reconstruct",
            sharded or self._reconstruct, tuple(series.shape), params, series,
        )

    def score_with(self, params: Params, batch: dict) -> jnp.ndarray:
        """batch {"series": (B, T, F)} -> per-sequence reconstruction MSE (B,)
        — the anomaly score of the paper's application.  Under a sharded
        placement the batch rows are scored data-parallel over the mesh."""
        series = batch["series"]
        sharded = self._row_program("score", series.shape[0])
        return self._run_profiled(
            "score@sharded" if sharded is not None else "score",
            sharded or self._score, tuple(series.shape), params, series,
        )

    def score_masked_with(self, params: Params, batch: dict) -> jnp.ndarray:
        """batch {"series": (B, T, F), "lengths": (B,) int} -> per-sequence
        MSE over each row's first ``lengths[i]`` timesteps.  Rows padded
        beyond their length (and all-padding rows) do not contaminate
        scores — the micro-batching gateway's bucketed-scoring primitive
        (which pads B to a per-device multiple under a sharded placement)."""
        series = batch["series"]
        lengths = jnp.asarray(batch["lengths"], jnp.int32)
        sharded = self._row_program("score_masked", series.shape[0])
        return self._run_profiled(
            "score_masked@sharded" if sharded is not None else "score_masked",
            sharded or self._score_masked, tuple(series.shape),
            params, series, lengths,
        )

    def reconstruct(self, batch: dict) -> jnp.ndarray:
        return self.reconstruct_with(self._require_params(), batch)

    def score(self, batch: dict) -> jnp.ndarray:
        return self.score_with(self._require_params(), batch)

    def score_masked(self, batch: dict) -> jnp.ndarray:
        return self.score_masked_with(self._require_params(), batch)

    # -- streaming surface ------------------------------------------------

    def init_stream_state(self, batch: int, dtype=jnp.float32) -> Params:
        """Zero (h, c) per layer for a streaming session of ``batch`` series."""
        from repro.models.lstm_ae import init_stream_state

        return init_stream_state(self.cfg, batch, dtype)

    def _stream_step(self, params, x_t, state):
        # One timestep through all layers.  A single timestep admits no
        # temporal parallelism (Eq 1 with T=1), so streaming is schedule-
        # independent: every schedule shares the ModelAPI decode cell loop.
        from repro.models.lstm_ae import decode_step

        return decode_step(params, x_t, state, None, self.cfg,
                           pwl=self.engine_cfg.pwl)

    def _masked_stream_step(self, params, x_t, state, mask):
        # Pooled-session streaming: advance only the rows ``mask`` selects.
        # Rows are independent through the cell (batched matmuls), so masked
        # stepping is value-identical to stepping each selected row alone.
        y_t, new_state = self._stream_step(params, x_t, state)
        keep = mask[:, None]
        merged = jax.tree.map(
            lambda new, old: jnp.where(keep, new, old), new_state, state
        )
        return y_t, merged

    def stream_with(
        self, params: Params, x_t: jnp.ndarray, state: Params
    ) -> tuple[jnp.ndarray, Params]:
        """One streaming timestep x_t (B, F) -> (reconstruction (B, F), state)."""
        sharded = self._row_program("step", x_t.shape[0])
        return self._run_profiled(
            "step@sharded" if sharded is not None else "step",
            sharded or self._step, tuple(x_t.shape), params, x_t, state,
        )

    def stream(self, x_t: jnp.ndarray, state: Params) -> tuple[jnp.ndarray, Params]:
        return self.stream_with(self._require_params(), x_t, state)

    def stream_masked_with(
        self, params: Params, x_t: jnp.ndarray, state: Params, mask: jnp.ndarray
    ) -> tuple[jnp.ndarray, Params]:
        """Pooled step: x_t (B, F), mask (B,) bool -> (y_t (B, F), state)
        where only masked rows' (h, c) advance (others carry unchanged).
        The gateway session pool runs thousands of logical streams through
        this one compiled program — slot churn never retraces.  Under a
        sharded placement the slot rows live distributed over the data
        mesh (state in, state out keep the row sharding)."""
        sharded = self._row_program("mstep", x_t.shape[0])
        return self._run_profiled(
            "mstep@sharded" if sharded is not None else "mstep",
            sharded or self._mstep, tuple(x_t.shape), params, x_t, state, mask,
        )

    def stream_masked(
        self, x_t: jnp.ndarray, state: Params, mask: jnp.ndarray
    ) -> tuple[jnp.ndarray, Params]:
        return self.stream_masked_with(self._require_params(), x_t, state, mask)

    # -- analytics --------------------------------------------------------

    def latency_model(
        self, timesteps: int, rh_m: Optional[int] = None, **kw
    ) -> LatencyEstimate:
        """Eq-1 accounting of THIS schedule on the paper's accelerator model.

        ``rh_m`` defaults to the paper's Table-1 bottleneck reuse factor for
        this architecture (1 when the arch is not a paper config).
        """
        if rh_m is None:
            rh_m = PAPER_RH_M.get(self.cfg.name, 1)
        return fpga_latency_ms(
            self.cfg.lstm_ae, timesteps, rh_m,
            schedule=self.schedule.latency_kind, **kw,
        )

    def __repr__(self) -> str:
        pl = f", placement={self.placement!r}" if self.placement.is_sharded else ""
        return (f"Engine({self.cfg.name}, schedule={self.schedule.tag}"
                f"{pl}, bound={self.params is not None})")


def build_engine(
    model: Union[ModelConfig, "object"],
    schedule: Union[str, EngineConfig] = "wavefront",
    params: Optional[Params] = None,
) -> Engine:
    """Build an :class:`Engine` from a ModelConfig or a ModelAPI.

    ``schedule`` is a registry name or a full :class:`EngineConfig`.
    """
    cfg = getattr(model, "cfg", model)  # ModelAPI carries .cfg
    if not isinstance(cfg, ModelConfig):
        raise TypeError(f"expected ModelConfig or ModelAPI, got {type(model)!r}")
    return Engine(cfg, schedule, params=params)
