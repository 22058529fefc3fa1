"""Jamba [arXiv:2403.19887]: hybrid Mamba + attention with MLP or MoE FFNs.

Layer ``i`` mixes with attention iff ``i % attn_every == attn_offset``
(HF ``JambaConfig``'s ``attn_layer_period`` / ``attn_layer_offset``) and
with a Mamba block otherwise; its FFN is a MoE where ``cfg.moe`` places one
and a dense SiLU-gated MLP elsewhere (``num_experts`` 1 means no ``moe``).
Params are stacked per position of one period of the layer pattern (the
least common multiple of the attention and MoE periods) and the stack is
scanned over periods, keeping HLO compact.  A tied unembedding reads the
embedding table.  Jamba has no positional encoding: the SSM layers carry
position.

Projections round their input to the weight's dtype and accumulate in
float32 (``apply_linear_f32``), so bf16 weights serve at bf16 matmul inputs
with float32 accumulation; norms, softmax and the SSM are float32.

:func:`stream_step` is the family's step for the session pool (its
``StreamModel`` in ``models/api.py``): one token per stream per step, the
answer being the token's surprisal ``-ln p(x_t | x_<t)`` under the slot's
last hidden state.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.core import ModelConfig
from repro.distributed.sharding import constrain
from repro.layers.attention import (
    apply_attention,
    attention_specs,
    decode_attention,
    init_attention,
    init_kv_cache,
    kv_cache_specs,
)
from repro.layers.embeddings import (
    chunked_xent_loss,
    embed_tokens,
    embedding_specs,
    init_embedding,
    init_unembed,
    unembed_logits,
    unembed_specs,
)
from repro.layers.linear import apply_linear_f32
from repro.layers.mamba import (
    apply_mamba,
    apply_mamba_step,
    init_mamba,
    init_mamba_state,
    mamba_specs,
    mamba_state_specs,
)
from repro.layers.mlp import init_mlp, mlp_specs
from repro.layers.moe import apply_moe, init_moe, moe_specs
from repro.layers.norms import apply_norm, init_norm, norm_specs
from repro.models.transformer import _stack_specs
from repro.utils import Params, split_keys


def period(cfg: ModelConfig) -> int:
    """Layers in one period of the layer pattern."""
    every = cfg.moe.every if cfg.moe is not None else 1
    return math.lcm(cfg.attn_every, every)


def _n_periods(cfg: ModelConfig) -> int:
    p = period(cfg)
    assert cfg.num_layers % p == 0, f"jamba layer count must be a multiple of {p}"
    return cfg.num_layers // p


def _layer_kind(cfg: ModelConfig, j: int) -> tuple[str, str]:
    """(mixer, ffn) for layer (or period position) j."""
    mixer = "attn" if cfg.is_attn_layer(j) else "mamba"
    ffn = "moe" if cfg.is_moe_layer(j) else "mlp"
    return mixer, ffn


def init_position(key: jax.Array, cfg: ModelConfig, j: int) -> Params:
    mixer, ffn = _layer_kind(cfg, j)
    keys = split_keys(key, ["mixer", "ffn"])
    p = {"ln1": init_norm(cfg.norm, cfg.d_model), "ln2": init_norm(cfg.norm, cfg.d_model)}
    p["mixer"] = (
        init_attention(keys["mixer"], cfg) if mixer == "attn" else init_mamba(keys["mixer"], cfg)
    )
    p["ffn"] = init_moe(keys["ffn"], cfg) if ffn == "moe" else init_mlp(keys["ffn"], cfg)
    return p


def position_specs(cfg: ModelConfig, j: int) -> Params:
    mixer, ffn = _layer_kind(cfg, j)
    return {
        "ln1": norm_specs(cfg.norm),
        "ln2": norm_specs(cfg.norm),
        "mixer": attention_specs(cfg) if mixer == "attn" else mamba_specs(cfg),
        "ffn": moe_specs(cfg) if ffn == "moe" else mlp_specs(cfg),
    }


def init_jamba(key: jax.Array, cfg: ModelConfig) -> Params:
    n_p, per = _n_periods(cfg), period(cfg)
    keys = split_keys(key, ["embed", "layers", "unembed"])
    period_keys = jax.random.split(keys["layers"], n_p * per).reshape(n_p, per, 2)
    positions = []
    for j in range(per):
        stacked = jax.vmap(lambda k, j=j: init_position(k, cfg, j))(period_keys[:, j])
        positions.append(stacked)
    params = {
        "embed": init_embedding(keys["embed"], cfg.vocab_size, cfg.d_model),
        "positions": tuple(positions),
        "ln_f": init_norm(cfg.norm, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = init_unembed(keys["unembed"], cfg.d_model, cfg.vocab_size)
    return params


def jamba_specs(cfg: ModelConfig) -> Params:
    specs = {
        "embed": embedding_specs(),
        "positions": tuple(_stack_specs(position_specs(cfg, j))
                           for j in range(period(cfg))),
        "ln_f": norm_specs(cfg.norm),
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = unembed_specs()
    return specs


def _norm(p: Params, h: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    return apply_norm(p, h, cfg.norm, cfg.norm_eps)


def _mlp(p: Params, h: jnp.ndarray) -> jnp.ndarray:
    """Dense SiLU-gated MLP, float32 result."""
    g = apply_linear_f32(p["gate"], h)
    u = apply_linear_f32(p["up"], h)
    return apply_linear_f32(p["down"], jax.nn.silu(g) * u)


def _ffn(lp: Params, h: jnp.ndarray, cfg: ModelConfig, j: int):
    _, ffn = _layer_kind(cfg, j)
    if ffn == "moe":
        if cfg.moe.impl == "ep_a2a":
            from repro.layers.moe import apply_moe_ep
            return apply_moe_ep(lp["ffn"], h, cfg)
        return apply_moe(lp["ffn"], h, cfg)
    return _mlp(lp["ffn"], h).astype(h.dtype), jnp.float32(0.0)


def _unembed_w(params: Params) -> jnp.ndarray:
    """(D, V) output projection: the tied embedding's transpose, or its own."""
    if "unembed" in params:
        return params["unembed"]["w"]
    return params["embed"]["table"].T


def init_states(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> Params:
    """Decode state tree: tuple over period positions, stacked over periods.
    Attention positions hold a KV cache; mamba positions hold (ssm, conv)."""
    n_p = _n_periods(cfg)
    states = []
    for j in range(period(cfg)):
        mixer, _ = _layer_kind(cfg, j)
        one = (
            init_kv_cache(cfg, batch, max_len, dtype)
            if mixer == "attn"
            else init_mamba_state(cfg, batch, dtype)
        )
        states.append(jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n_p,) + x.shape), one))
    return tuple(states)


def state_specs(cfg: ModelConfig) -> Params:
    from repro.distributed.sharding import map_specs

    out = []
    for j in range(period(cfg)):
        mixer, _ = _layer_kind(cfg, j)
        base = kv_cache_specs() if mixer == "attn" else mamba_state_specs()
        out.append(map_specs(lambda axes: (None,) + axes, base))
    return tuple(out)


def forward(
    params: Params,
    h: jnp.ndarray,
    cfg: ModelConfig,
    *,
    remat: bool = True,
    kv_chunk: int = 1024,
    q_chunks: int = 1,
    collect_state: bool = False,
):
    """h: (B, S, D) -> (h, aux, states|None).  Jamba has no positional
    embedding — the SSM layers carry position information."""

    def period_fn(carry, lp_all):
        h, aux = carry
        new_sts = []
        for j in range(period(cfg)):
            lp = lp_all[j]
            mixer, _ = _layer_kind(cfg, j)
            hn = _norm(lp["ln1"], h, cfg)
            if mixer == "attn":
                y, kv = apply_attention(
                    lp["mixer"], hn, cfg=cfg, causal=True, use_rope=False,
                    kv_chunk=kv_chunk, q_chunks=q_chunks, return_kv=True,
                    linear=apply_linear_f32,
                )
                y = y.astype(h.dtype)
                new_st = {"k": kv[0].astype(h.dtype), "v": kv[1].astype(h.dtype)}
            else:
                y, new_st = apply_mamba(lp["mixer"], hn, cfg)
            h = constrain(h + y, ("batch", "sp", None))
            hn = _norm(lp["ln2"], h, cfg)
            f, aux_l = _ffn(lp, hn, cfg, j)
            h = constrain(h + f, ("batch", "sp", None))
            aux = aux + aux_l
            new_sts.append(new_st)
        return (h, aux), (tuple(new_sts) if collect_state else None)

    body = jax.checkpoint(period_fn) if remat else period_fn
    (h, aux), collected = jax.lax.scan(body, (h, jnp.float32(0.0)), params["positions"])
    return h, aux, (collected if collect_state else None)


def train_loss(params: Params, batch: dict, cfg: ModelConfig, *, remat: bool = True,
               loss_chunk: int = 2048, kv_chunk: int = 1024, q_chunks: int = 1,
               aux_weight: float = 0.01, **_) -> tuple[jnp.ndarray, dict]:
    dtype = jnp.dtype(cfg.compute_dtype)
    h = embed_tokens(params["embed"], batch["tokens"], dtype)
    h, aux, _ = forward(params, h, cfg, remat=remat, kv_chunk=kv_chunk, q_chunks=q_chunks)
    h = _norm(params["ln_f"], h, cfg)
    loss = chunked_xent_loss(_unembed_w(params), h, batch["labels"], chunk=loss_chunk)
    total = loss + aux_weight * aux
    return total, {"xent": loss, "aux": aux}


def prefill(params: Params, batch: dict, cfg: ModelConfig, *, kv_chunk: int = 1024,
            q_chunks: int = 1, **_) -> tuple[jnp.ndarray, Params]:
    dtype = jnp.dtype(cfg.compute_dtype)
    h = embed_tokens(params["embed"], batch["tokens"], dtype)
    h, _, states = forward(
        params, h, cfg, remat=False, kv_chunk=kv_chunk, q_chunks=q_chunks,
        collect_state=True,
    )
    h = _norm(params["ln_f"], h, cfg)
    logits = unembed_logits(_unembed_w(params), h[:, -1:, :])
    return logits, states


def decode_step(params: Params, token: jnp.ndarray, states: Params,
                cache_len: jnp.ndarray, cfg: ModelConfig) -> tuple[jnp.ndarray, Params]:
    """One-token decode.  token: (B,1); states from :func:`init_states`."""
    dtype = jnp.dtype(cfg.compute_dtype)
    h = embed_tokens(params["embed"], token, dtype)[:, 0, :]  # (B, D)

    def period_fn(h, inp):
        lp_all, st_all = inp
        new_sts = []
        for j in range(period(cfg)):
            lp, st = lp_all[j], st_all[j]
            mixer, _ = _layer_kind(cfg, j)
            hn = _norm(lp["ln1"], h, cfg)
            if mixer == "attn":
                y3, new_st = decode_attention(
                    lp["mixer"], hn[:, None, :], st, cache_len, cfg=cfg, use_rope=False,
                    linear=apply_linear_f32,
                )
                y = y3[:, 0, :].astype(h.dtype)
            else:
                y, new_st = apply_mamba_step(lp["mixer"], hn, cfg, st)
            h = h + y
            hn = _norm(lp["ln2"], h, cfg)
            f, _ = _ffn(lp, hn[:, None, :], cfg, j)
            h = h + f[:, 0, :]
            new_sts.append(new_st)
        return h, tuple(new_sts)

    h, new_states = jax.lax.scan(period_fn, h, (params["positions"], states))
    h = _norm(params["ln_f"], h, cfg)
    logits = unembed_logits(_unembed_w(params), h[:, None, :])
    return logits, new_states


# -- the stream contract (session pool) ------------------------------------

def init_stream_state(cfg: ModelConfig, rows: int, max_len: int) -> Params:
    """Slot state of ``rows`` token streams, every leaf rows-first: per
    layer a Mamba ``(ssm, conv)`` state or a KV cache with room for
    ``max_len`` tokens, and ``h``, the last hidden state (zero on
    admission: the first token's prediction is uniform).  The cache
    length of a slot is the pool's per-slot step count."""
    dtype = jnp.dtype(cfg.compute_dtype)
    layers = tuple(
        init_kv_cache(cfg, rows, max_len, dtype) if cfg.is_attn_layer(i)
        else init_mamba_state(cfg, rows, dtype)
        for i in range(cfg.num_layers)
    )
    return {"layers": layers, "h": jnp.zeros((rows, cfg.d_model), jnp.float32)}


def token_surprisal(params: Params, h: jnp.ndarray, tokens: jnp.ndarray,
                    cfg: ModelConfig) -> jnp.ndarray:
    """``-ln p(token | h)`` per row, from the final norm and the unembedding
    of the last hidden state ``h`` (B, D); float32."""
    hn = _norm(params["ln_f"], h, cfg)
    w = _unembed_w(params)
    logits = jnp.dot(hn.astype(w.dtype), w, preferred_element_type=jnp.float32)
    gold = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return jax.nn.logsumexp(logits, axis=-1) - gold


def stream_step(params: Params, tokens: jnp.ndarray, state: Params,
                mask: jnp.ndarray, steps: jnp.ndarray,
                cfg: ModelConfig) -> tuple[Params, jnp.ndarray]:
    """One token for the rows ``mask`` selects: tokens (B,) int32, steps
    (B,) the tokens each row has taken (its KV length).  Returns the new
    state (unselected rows unchanged) and each row's surprisal."""
    with jax.named_scope("unembed"):
        loss = token_surprisal(params, state["h"], tokens, cfg)
    h = params["embed"]["table"][tokens].astype(jnp.float32)
    s_max = next(st["k"].shape[1] for st in state["layers"] if "k" in st)
    # an unselected row writes no KV (its write position is out of range)
    kv_len = jnp.where(mask, steps, s_max)
    keep = mask[:, None]
    per = period(cfg)
    new_layers = []
    for i, st in enumerate(state["layers"]):
        lp = jax.tree.map(lambda x, p=i // per: x[p], params["positions"][i % per])
        hn = _norm(lp["ln1"], h, cfg)
        if "k" in st:
            with jax.named_scope("attention"):
                y, new_st = decode_attention(
                    lp["mixer"], hn[:, None, :], st, kv_len, cfg=cfg,
                    use_rope=False, linear=apply_linear_f32)
                y = y[:, 0, :]
        else:
            with jax.named_scope("mamba"):
                y, new_st = apply_mamba_step(lp["mixer"], hn, cfg, st)
                new_st = jax.tree.map(
                    lambda new, old: jnp.where(keep[..., None], new, old), new_st, st)
        h = h + y
        with jax.named_scope("mlp"):
            f, _ = _ffn(lp, _norm(lp["ln2"], h, cfg), cfg, i)
        h = h + f
        new_layers.append(new_st)
    h = jnp.where(keep, h, state["h"])
    return {"layers": tuple(new_layers), "h": h}, loss


def check_tokens(samples: np.ndarray, cfg: ModelConfig) -> None:
    """Refuse token ids outside the vocabulary."""
    bad = (samples < 0) | (samples >= cfg.vocab_size)
    if bad.any():
        raise ValueError(
            f"token id {int(samples[bad][0])} is outside the vocabulary "
            f"[0, {cfg.vocab_size})")
