"""Uniform model API: one entry point per family, plus the dry-run
``input_specs`` (ShapeDtypeStruct stand-ins; no device allocation).

``build_model(cfg)`` returns a :class:`ModelAPI` with:

- init(key) -> params
- param_specs() -> logical-axis spec tree (mirrors params)
- loss(params, batch) -> (scalar, metrics)        [train shapes]
- prefill(params, batch) -> (logits/scores, cache) [prefill shapes]
- decode(params, token, cache, cache_len) -> (logits, cache) [decode shapes]
- init_cache(batch, max_len) / cache_specs()       [decode state]
- stream() -> StreamModel, for a streams-only family the session pool serves
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.core import ModelConfig, ShapeConfig
from repro.core.lstm import init_lstm_ae, lstm_ae_specs
from repro.models import jamba as jamba_m
from repro.models import lstm_ae as lstm_ae_m
from repro.models import rwkv6 as rwkv6_m
from repro.models import transformer as tf_m
from repro.models import whisper as whisper_m
from repro.utils import Params


class StreamModel(NamedTuple):
    """What the session pool needs of a model family's streams.

    ``sample_shape`` / ``sample_dtype``: one sample of a stream (the LSTM-AE:
    ``(F,)`` float32; Jamba: one int32 token, ``()``).
    ``init_state(rows, max_len)``: the zero slot state of ``rows`` streams,
    a tree whose every leaf is rows-first; ``max_len`` bounds a cache.
    ``step(params, x, state, mask, steps)``: one sample for the rows
    ``mask`` selects (``steps``: samples each row has taken) -> (new state,
    unselected rows unchanged; per-row loss), the loss the pool averages
    into a stream's answer.
    ``check(samples)``: raise ValueError on samples the model cannot take
    (None: any sample of the right shape).
    ``cached``: a slot's state holds a cache that ``max_len`` bounds.
    """
    sample_shape: tuple
    sample_dtype: np.dtype
    init_state: Callable
    step: Callable
    check: Optional[Callable] = None
    cached: bool = False


@dataclass(frozen=True)
class ModelAPI:
    cfg: ModelConfig
    init: Callable[[jax.Array], Params]
    param_specs: Callable[[], Params]
    loss: Callable[..., tuple[jnp.ndarray, dict]]
    prefill: Callable[..., tuple[jnp.ndarray, Params]]
    decode: Optional[Callable[..., tuple[jnp.ndarray, Params]]]
    init_cache: Optional[Callable[[int, int], Params]]
    cache_specs: Optional[Callable[[], Params]]
    #: ``stream()`` -> the :class:`StreamModel` of a family the Engine
    #: serves without window programs; None where it brings none (the
    #: LSTM-AE's is the Engine's own masked step, ``engine/base.py``)
    stream: Optional[Callable[[], StreamModel]] = None


def build_model(cfg: ModelConfig) -> ModelAPI:
    if cfg.family == "transformer":
        return ModelAPI(
            cfg=cfg,
            init=lambda key: tf_m.init_transformer(key, cfg),
            param_specs=lambda: tf_m.transformer_specs(cfg),
            loss=lambda p, b, **kw: tf_m.train_loss(p, b, cfg, **kw),
            prefill=lambda p, b, **kw: tf_m.prefill(p, b, cfg, **kw),
            decode=lambda p, t, c, n: tf_m.decode_step(p, t, c, n, cfg),
            init_cache=lambda batch, max_len: tf_m.init_decode_cache(cfg, batch, max_len),
            cache_specs=lambda: tf_m.decode_cache_specs(cfg),
        )
    if cfg.family == "rwkv6":
        return ModelAPI(
            cfg=cfg,
            init=lambda key: rwkv6_m.init_rwkv6(key, cfg),
            param_specs=lambda: rwkv6_m.rwkv6_specs(cfg),
            loss=lambda p, b, **kw: rwkv6_m.train_loss(p, b, cfg, **kw),
            prefill=lambda p, b, **kw: rwkv6_m.prefill(p, b, cfg, **kw),
            decode=lambda p, t, c, n: rwkv6_m.decode_step(p, t, c, n, cfg),
            init_cache=lambda batch, max_len: rwkv6_m.init_state(cfg, batch),
            cache_specs=lambda: rwkv6_m.state_specs(),
        )
    if cfg.family == "jamba":
        return ModelAPI(
            cfg=cfg,
            init=lambda key: jamba_m.init_jamba(key, cfg),
            param_specs=lambda: jamba_m.jamba_specs(cfg),
            loss=lambda p, b, **kw: jamba_m.train_loss(p, b, cfg, **kw),
            prefill=lambda p, b, **kw: jamba_m.prefill(p, b, cfg, **kw),
            decode=lambda p, t, c, n: jamba_m.decode_step(p, t, c, n, cfg),
            init_cache=lambda batch, max_len: jamba_m.init_states(cfg, batch, max_len),
            cache_specs=lambda: jamba_m.state_specs(cfg),
            # one int32 token a sample, answered its surprisal
            stream=lambda: StreamModel(
                sample_shape=(), sample_dtype=np.dtype(np.int32),
                init_state=lambda rows, max_len: jamba_m.init_stream_state(
                    cfg, rows, max_len),
                step=lambda p, x, s, m, n: jamba_m.stream_step(p, x, s, m, n, cfg),
                check=lambda samples: jamba_m.check_tokens(samples, cfg),
                cached=True,
            ),
        )
    if cfg.family == "whisper":
        return ModelAPI(
            cfg=cfg,
            init=lambda key: whisper_m.init_whisper(key, cfg),
            param_specs=lambda: whisper_m.whisper_specs(cfg),
            loss=lambda p, b, **kw: whisper_m.train_loss(p, b, cfg, **kw),
            prefill=lambda p, b, **kw: whisper_m.prefill(p, b, cfg, **kw),
            decode=lambda p, t, c, n: whisper_m.decode_step(p, t, c, n, cfg),
            init_cache=lambda batch, max_len: whisper_m.init_decode_cache(cfg, batch, max_len),
            cache_specs=lambda: whisper_m.decode_cache_specs(cfg),
        )
    if cfg.family == "lstm_ae":
        # prefill delegates to the execution-engine registry (repro.engine):
        # pass schedule="sequential" | "wavefront" | "pipelined" through kw.
        return ModelAPI(
            cfg=cfg,
            init=lambda key: init_lstm_ae(key, cfg),
            param_specs=lambda: lstm_ae_specs(cfg),
            loss=lambda p, b, **kw: lstm_ae_m.train_loss(p, b, cfg, **kw),
            prefill=lambda p, b, **kw: lstm_ae_m.prefill(p, b, cfg, **kw),
            decode=lambda p, t, c, n: lstm_ae_m.decode_step(p, t, c, n, cfg),
            init_cache=lambda batch, max_len: lstm_ae_m.init_stream_state(cfg, batch),
            cache_specs=None,
        )
    raise ValueError(f"unknown family {cfg.family!r}")


# ---------------------------------------------------------------------------
# Dry-run input specs (ShapeDtypeStruct: weak-type-correct, no allocation)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict[str, Any]:
    """Model inputs for a given (arch x shape) dry-run cell.

    train/prefill: the token/series batch (+ modality stubs);
    decode: one token + cache_len (the cache itself comes from
    ``cache_struct``).
    """
    b, s = shape.global_batch, shape.seq_len
    if cfg.family == "lstm_ae":
        return {"series": _sds((b, s, cfg.lstm_ae.input_features), "float32")}

    if cfg.family == "whisper":
        if shape.kind == "train":
            return {
                "frames": _sds((b, cfg.encoder_seq_len, cfg.d_model), cfg.compute_dtype),
                "tokens": _sds((b, s), "int32"),
                "labels": _sds((b, s), "int32"),
            }
        if shape.kind == "prefill":
            return {
                "frames": _sds((b, cfg.encoder_seq_len, cfg.d_model), cfg.compute_dtype),
                "tokens": _sds((b, s), "int32"),
            }
        return {"token": _sds((b, 1), "int32"), "cache_len": _sds((), "int32")}

    if cfg.frontend == "vision_stub" and shape.kind != "decode":
        p = cfg.vision_patches
        text = s - p
        assert text > 0
        spec = {
            "tokens": _sds((b, text), "int32"),
            "image_embeds": _sds((b, p, cfg.d_model), cfg.compute_dtype),
        }
        if shape.kind == "train":
            spec["labels"] = _sds((b, text), "int32")
        return spec

    if shape.kind == "train":
        return {"tokens": _sds((b, s), "int32"), "labels": _sds((b, s), "int32")}
    if shape.kind == "prefill":
        return {"tokens": _sds((b, s), "int32")}
    return {"token": _sds((b, 1), "int32"), "cache_len": _sds((), "int32")}


def cache_struct(api: ModelAPI, batch: int, max_len: int) -> Params:
    """ShapeDtypeStruct tree of the decode cache (no allocation)."""
    return jax.eval_shape(lambda: api.init_cache(batch, max_len))


def param_struct(api: ModelAPI) -> Params:
    """ShapeDtypeStruct tree of the parameters (no allocation)."""
    return jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
