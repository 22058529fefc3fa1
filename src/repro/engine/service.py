"""AnomalyService: the paper's deployment scenario as one object.

fit (train on benign series) -> calibrate (threshold on a benign split) ->
score / detect (batched windows) -> stream (per-timestep state + running
errors).  This replaces the train/calibrate/score loops that used to be
copy-pasted across ``examples/serve_anomaly_stream.py``, ``launch/serve.py``
and ``examples/quickstart.py``; the execution schedule underneath is a
config knob (any name in the engine registry).
"""
from __future__ import annotations

import weakref
from dataclasses import dataclass
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.config import TrainConfig, get_config
from repro.config.core import ModelConfig
from repro.core.anomaly import DetectionReport, calibrate_threshold, evaluate_detection
from repro.core.latency import LatencyEstimate
from repro.data import TimeseriesConfig, make_batch
from repro.engine.base import Engine, EngineConfig, build_engine
from repro.models.api import build_model
from repro.utils import Params

_UNSET = object()  # distinguishes "not given" from an explicit None


@dataclass
class StreamSession:
    """Carried state of one streaming connection: per-layer (h, c) plus the
    running sum of squared reconstruction error per series."""
    state: Params
    sq_err_sum: jnp.ndarray   # (B,)
    steps: int

    @property
    def errors(self) -> jnp.ndarray:
        """Mean squared reconstruction error so far, per series (B,)."""
        return self.sq_err_sum / max(1, self.steps)


class AnomalyService:
    """End-to-end anomaly detection on a pluggable execution engine.

    >>> svc = AnomalyService("lstm-ae-f32-d2", schedule="wavefront")
    >>> svc.fit(TimeseriesConfig(features=32, seq_len=32, batch=64), steps=100)
    >>> svc.calibrate(TimeseriesConfig(features=32, seq_len=32, batch=64))
    >>> report = svc.detect(series, labels)
    """

    def __init__(
        self,
        model: Union[str, ModelConfig],
        schedule: Union[str, EngineConfig] = "wavefront",
        *,
        seed: int = 0,
        params: Optional[Params] = None,
    ):
        """``params`` serves given weights (a model too large to initialise
        twice on one device); by default they are initialised from ``seed``."""
        cfg = get_config(model) if isinstance(model, str) else model
        self.cfg = cfg
        self.api = build_model(cfg)
        self.engine: Engine = build_engine(cfg, schedule)
        self.seed = seed
        self.params: Params = (self.api.init(jax.random.PRNGKey(seed))
                               if params is None else params)
        self.engine.bind(self.params)
        self.threshold: Optional[float] = None
        # open gateways whose engine is a placement re-layout of ours (see
        # open_gateway): weakly held so a dropped gateway is collectable,
        # rebound on every param swap so they never serve stale params
        self._gateways: "weakref.WeakSet" = weakref.WeakSet()

    def _bind(self, params: Params) -> None:
        """Swap ``params`` onto this service AND every open gateway engine.

        A gateway opened with a different placement carries its own Engine
        (same model, re-laid-out programs); binding only ``self.engine``
        would leave it scoring with stale params — the contract is that
        open gateways always read the params now in effect."""
        self.params = params
        self.engine.bind(params)
        for gw in list(self._gateways):
            if gw.engine is not self.engine:
                gw.engine.bind(params)

    @property
    def features(self) -> int:
        return self.cfg.lstm_ae.input_features

    # -- fit --------------------------------------------------------------

    def fit(
        self,
        data_cfg: TimeseriesConfig,
        steps: int,
        train_cfg: Optional[TrainConfig] = None,
        log_every: int = 0,
    ) -> dict:
        """Train on benign windows drawn from ``data_cfg``; binds the fitted
        params onto the engine.  Returns the final metrics (empty when
        ``steps == 0`` — the service then scores with its init params)."""
        self.engine._require_windows()
        if steps <= 0:
            return {}
        from repro.training import build_train_step, init_train_state

        tc = train_cfg or TrainConfig(
            learning_rate=5e-3, warmup_steps=min(10, steps), total_steps=steps
        )
        # the ctor seed governs training init too, so two services with
        # different seeds fit genuinely different models
        state = init_train_state(self.api, jax.random.PRNGKey(self.seed), tc)
        step = jax.jit(build_train_step(self.api, tc))
        metrics: dict = {}
        for i in range(steps):
            series, _ = make_batch(data_cfg, i)
            state, metrics = step(state, {"series": series})
            if log_every and (i % log_every == 0 or i == steps - 1):
                print(f"step {i:4d}  mse={float(metrics['loss']):.4f}")
        self._bind(state.params)
        return {k: float(v) for k, v in metrics.items()}

    # -- calibrate --------------------------------------------------------

    def calibrate(
        self,
        benign: Union[TimeseriesConfig, jnp.ndarray],
        k_sigma: float = 3.0,
        seed: int = 99_999,
    ) -> float:
        """Threshold = mean + k*std of scores on a benign split.  ``benign``
        is either a series batch (B, T, F) or a TimeseriesConfig to draw one."""
        if isinstance(benign, TimeseriesConfig):
            benign, _ = make_batch(benign, seed)
        self.threshold = calibrate_threshold(self.score(benign), k_sigma=k_sigma)
        return self.threshold

    def recalibrate(
        self,
        benign: Union[TimeseriesConfig, jnp.ndarray, None] = None,
        *,
        threshold=_UNSET,
        params: Optional[Params] = None,
        k_sigma: float = 3.0,
        seed: int = 99_999,
    ) -> Optional[float]:
        """Refresh the live detector in place — no drain, no restart.

        Optionally rebinds ``params`` (e.g. a freshly fitted model) onto
        the engine, then swaps the threshold: either ``threshold``
        directly (an explicit None disables alerting — same semantics as
        :meth:`AnomalyGateway.recalibrate`; omit it to leave the threshold
        alone), or re-derived from a ``benign`` split (after the param
        swap, so the new threshold reflects the new model).  Streaming
        sessions and open gateways keep serving throughout — both read the
        engine's current params and this threshold per operation.  Returns
        the threshold now in effect.
        """
        if params is not None:
            self._bind(params)
        if threshold is not _UNSET:
            self.threshold = None if threshold is None else float(threshold)
        elif benign is not None:
            self.calibrate(benign, k_sigma=k_sigma, seed=seed)
        return self.threshold

    # -- batch scoring ----------------------------------------------------

    def score(self, series: jnp.ndarray) -> jnp.ndarray:
        """(B, T, F) -> per-sequence reconstruction errors (B,)."""
        return self.engine.score({"series": series})

    def alerts(self, series: jnp.ndarray) -> jnp.ndarray:
        """(B, T, F) -> boolean alert mask (B,); requires calibration."""
        return self.score(series) > self._require_threshold()

    def detect(self, series: jnp.ndarray, labels: jnp.ndarray) -> DetectionReport:
        """Score + evaluate against ground-truth labels (B,)."""
        return evaluate_detection(self.score(series), labels, self._require_threshold())

    def _require_threshold(self) -> float:
        if self.threshold is None:
            raise ValueError("service is not calibrated; call calibrate(...) first")
        return self.threshold

    # -- streaming --------------------------------------------------------

    def stream_start(self, batch: int) -> StreamSession:
        return StreamSession(
            state=self.engine.init_stream_state(batch),
            sq_err_sum=jnp.zeros((batch,), jnp.float32),
            steps=0,
        )

    def stream_step(
        self, x_t: jnp.ndarray, session: StreamSession
    ) -> tuple[jnp.ndarray, StreamSession]:
        """One timestep x_t (B, F); returns (running errors (B,), session)."""
        y_t, state = self.engine.stream(x_t, session.state)
        sq = jnp.mean(
            jnp.square(y_t.astype(jnp.float32) - x_t.astype(jnp.float32)), axis=-1
        )
        session = StreamSession(
            state=state, sq_err_sum=session.sq_err_sum + sq, steps=session.steps + 1
        )
        return session.errors, session

    # -- gateway ----------------------------------------------------------

    def open_gateway(
        self,
        *,
        capacity: int = 32,
        max_batch: int = 32,
        max_wait_ms: float = 5.0,
        max_queue: int = 1024,
        max_seq_len: Optional[int] = None,
        placement: Optional["object"] = None,
        **kw,
    ) -> "object":
        """Open a streaming/micro-batching gateway over this service.

        Returns a :class:`repro.gateway.AnomalyGateway`: a ``capacity``-slot
        session pool (admit/step/evict over one compiled masked step) plus a
        shape-bucketed one-shot scoring queue (flush on ``max_batch`` or
        ``max_wait_ms``, reject past ``max_queue`` pending or ``max_seq_len``
        timesteps).  ``placement`` (a
        :class:`~repro.engine.placement.Placement`, or an int as shorthand
        for ``Placement.data(n)``) shards the gateway's serving programs
        over a data mesh — pool-slot state distributes over the mesh so
        ``capacity`` can exceed what one device holds, and bucket flushes
        score data-parallel; it defaults to this engine's own placement.
        See README §Gateway / §Placement; front it with
        :class:`repro.gateway.server.GatewayServer` for socket serving.
        """
        from repro.gateway import AnomalyGateway  # lazy: gateway imports engine

        # the gateway registers itself in self._gateways, so future param
        # swaps (fit / recalibrate) rebind its engine too
        return AnomalyGateway(
            self, capacity=capacity, max_batch=max_batch,
            max_wait_ms=max_wait_ms, max_queue=max_queue,
            max_seq_len=max_seq_len, placement=placement, **kw,
        )

    # -- analytics --------------------------------------------------------

    def latency_model(self, timesteps: int, **kw) -> LatencyEstimate:
        """Eq-1 accounting of the bound schedule (paper accelerator model)."""
        return self.engine.latency_model(timesteps, **kw)
