"""Grouped-query attention: training/prefill (memory-bounded blocked softmax),
decode (KV cache, flash-decode-style partial-softmax combine), and whisper
cross-attention.

Design notes
------------
* **Blocked causal attention** (`blocked_attention`): an online-softmax scan
  over KV chunks.  Scores for (all-q x one-kv-chunk) are materialised per
  step, so peak memory is O(S * chunk) instead of O(S^2) — this is what lets
  the 32k prefill shapes fit HBM in the dry-run.  It is also the jnp oracle
  for the Pallas flash kernel (kernels/flash_attention.py).
* **Wedge skip** (`q_chunks > 1`): splits queries into chunks and lets chunk
  i attend only kv-chunks <= i, recovering the ~2x triangular FLOP saving at
  the cost of a slightly larger HLO.  This is one of the §Perf hillclimb
  levers.
* **Decode** uses one fused step over the full cache with a max-subtracted
  softmax; sharding: batch over ``batch``, kv-sequence over ``tp`` with a
  partial-softmax combine left to XLA's reduce (see serving/decode.py for
  the shard_map flash-decode used at scale).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config.core import ModelConfig
from repro.distributed.sharding import constrain
from repro.layers.linear import apply_linear, init_linear, linear_specs
from repro.layers.rotary import apply_rope
from repro.utils import Params, split_keys

NEG_INF = -1e30


def init_attention(key: jax.Array, cfg: ModelConfig, cross: bool = False) -> Params:
    hd = cfg.resolved_head_dim()
    keys = split_keys(key, ["q", "k", "v", "o"])
    return {
        "q": init_linear(keys["q"], cfg.d_model, cfg.num_heads * hd, bias=cfg.qkv_bias),
        "k": init_linear(keys["k"], cfg.d_model, cfg.num_kv_heads * hd, bias=False),
        "v": init_linear(keys["v"], cfg.d_model, cfg.num_kv_heads * hd, bias=cfg.qkv_bias),
        "o": init_linear(keys["o"], cfg.num_heads * hd, cfg.d_model, bias=cfg.qkv_bias),
    }


def attention_specs(cfg: ModelConfig) -> Params:
    return {
        "q": linear_specs("fsdp", "tp", bias=cfg.qkv_bias),
        "k": linear_specs("fsdp", "tp", bias=False),
        "v": linear_specs("fsdp", "tp", bias=cfg.qkv_bias),
        "o": linear_specs("tp", "fsdp", bias=cfg.qkv_bias),
    }


def _project_qkv(params: Params, x_q: jnp.ndarray, x_kv: jnp.ndarray, cfg: ModelConfig,
                 linear=apply_linear):
    hd = cfg.resolved_head_dim()
    bq, sq, _ = x_q.shape
    bk, sk, _ = x_kv.shape
    q = linear(params["q"], x_q).reshape(bq, sq, cfg.num_heads, hd)
    k = linear(params["k"], x_kv).reshape(bk, sk, cfg.num_kv_heads, hd)
    v = linear(params["v"], x_kv).reshape(bk, sk, cfg.num_kv_heads, hd)
    q = constrain(q, ("batch", None, "tp", None))
    k = constrain(k, ("batch", None, "tp", None))
    v = constrain(v, ("batch", None, "tp", None))
    return q, k, v


def _expand_kv(k: jnp.ndarray, num_heads: int) -> jnp.ndarray:
    """Broadcast kv heads to query heads: (B,S,Hkv,d) -> (B,S,Hq,d)."""
    b, s, hkv, d = k.shape
    group = num_heads // hkv
    if group == 1:
        return k
    return jnp.repeat(k, group, axis=2)


def blocked_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool,
    kv_chunk: int = 1024,
    q_chunks: int = 1,
    q_offset: int = 0,
) -> jnp.ndarray:
    """Online-softmax attention, O(S*chunk) memory.

    q: (B, Sq, H, d); k/v: (B, Sk, H, d) (kv heads already expanded).
    ``q_chunks > 1`` enables the causal wedge skip (chunk i of queries only
    scans kv chunks that intersect its causal window).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(d)

    if q_chunks > 1 and causal and sq == sk and q_offset == 0:
        assert sq % q_chunks == 0
        cq = sq // q_chunks
        outs = []
        for i in range(q_chunks):
            qi = q[:, i * cq : (i + 1) * cq]
            hi = (i + 1) * cq  # causal horizon for this q chunk
            outs.append(
                blocked_attention(
                    qi,
                    k[:, :hi],
                    v[:, :hi],
                    causal=True,
                    kv_chunk=min(kv_chunk, hi),
                    q_chunks=1,
                    q_offset=i * cq,
                )
            )
        return jnp.concatenate(outs, axis=1)

    kv_chunk = min(kv_chunk, sk)
    pad = (-sk) % kv_chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = (sk + pad) // kv_chunk
    kc = k.reshape(b, n_chunks, kv_chunk, h, d)
    vc = v.reshape(b, n_chunks, kv_chunk, h, d)

    q_pos = q_offset + jnp.arange(sq)

    def step(carry, blk):
        m_prev, l_prev, o_prev = carry
        k_blk, v_blk, blk_idx = blk
        kv_pos = blk_idx * kv_chunk + jnp.arange(kv_chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk, preferred_element_type=jnp.float32)
        s = s * scale
        valid = kv_pos[None, :] < sk  # mask zero padding
        if causal:
            valid = valid & (kv_pos[None, :] <= q_pos[:, None])
        s = jnp.where(valid[None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        o_new = o_prev * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32
        )
        return (m_new, l_new, o_new), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    blk_ids = jnp.arange(n_chunks)
    (m, l, o), _ = jax.lax.scan(
        step, (m0, l0, o0), (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), blk_ids)
    )
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # (B, Sq, H, d)


def apply_attention(
    params: Params,
    x: jnp.ndarray,
    *,
    cfg: ModelConfig,
    causal: bool,
    positions: Optional[jnp.ndarray] = None,
    use_rope: bool = True,
    x_kv: Optional[jnp.ndarray] = None,
    kv_chunk: int = 1024,
    q_chunks: int = 1,
    return_kv: bool = False,
    linear=apply_linear,
):
    """Full-sequence attention (train / prefill). x: (B, S, D).

    With ``return_kv`` also returns the (post-RoPE, un-expanded) K/V for KV
    cache population at prefill.  ``linear`` as in :func:`decode_attention`.
    """
    x_kv = x if x_kv is None else x_kv
    q, k, v = _project_qkv(params, x, x_kv, cfg, linear)
    if use_rope:
        if positions is None:
            positions = jnp.arange(x.shape[1])
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    kv = (k, v) if return_kv else None
    k = _expand_kv(k, cfg.num_heads)
    v = _expand_kv(v, cfg.num_heads)
    out = blocked_attention(q, k, v, causal=causal, kv_chunk=kv_chunk, q_chunks=q_chunks)
    out = constrain(out, ("batch", None, "tp", None))
    y = linear(params["o"], out.reshape(x.shape[0], x.shape[1], -1))
    y = constrain(y, ("batch", "sp", None))
    if return_kv:
        return y, kv
    return y


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> Params:
    hd = cfg.resolved_head_dim()
    return {
        "k": jnp.zeros((batch, max_len, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, max_len, cfg.num_kv_heads, hd), dtype),
    }


def kv_cache_specs() -> Params:
    # batch over data, kv sequence over the model axis (flash-decode layout)
    return {"k": ("batch", "tp", None, None), "v": ("batch", "tp", None, None)}


def decode_attention(
    params: Params,
    x: jnp.ndarray,
    cache: Params,
    cache_len: jnp.ndarray,
    *,
    cfg: ModelConfig,
    use_rope: bool = True,
    update_cache: bool = True,
    linear=apply_linear,
) -> tuple[jnp.ndarray, Params]:
    """One-token decode: x (B, 1, D) against cache (B, S_max, Hkv, hd).

    ``cache_len`` is one length for the whole batch (a scalar) or one per
    row ``(B,)``: each row takes its token at its own position, writes it
    there and attends to positions ``<= its length``; a row whose length is
    ``S_max`` or more writes nothing.  ``linear`` computes the projections
    (``apply_linear``, or ``apply_linear_f32`` for float32 accumulation).

    Returns (y, new_cache).  The softmax over the cached sequence is computed
    in fp32 with explicit masking of positions >= cache_len + 1.
    """
    b, one, _ = x.shape
    assert one == 1
    hd = cfg.resolved_head_dim()
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    q, k_new, v_new = _project_qkv(params, x, x, cfg, linear)
    if use_rope:
        q = apply_rope(q, lens[:, None], cfg.rope_theta)
        k_new = apply_rope(k_new, lens[:, None], cfg.rope_theta)

    if update_cache:
        rows = jnp.arange(b)
        k_cache = cache["k"].at[rows, lens].set(
            k_new[:, 0].astype(cache["k"].dtype), mode="drop")
        v_cache = cache["v"].at[rows, lens].set(
            v_new[:, 0].astype(cache["v"].dtype), mode="drop")
    else:
        k_cache, v_cache = cache["k"], cache["v"]
    k_cache = constrain(k_cache, ("batch", "tp", None, None))
    v_cache = constrain(v_cache, ("batch", "tp", None, None))

    s_max = k_cache.shape[1]
    group = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(b, cfg.num_kv_heads, group, hd)  # (B, Hkv, G, d) (Sq==1 folded)
    scores = jnp.einsum(
        "bhgd,bshd->bhgs", qg.astype(k_cache.dtype), k_cache,
        preferred_element_type=jnp.float32,
    ) / math.sqrt(hd)
    valid = jnp.arange(s_max)[None, :] <= lens[:, None]  # includes the new token
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum(
        "bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache, preferred_element_type=jnp.float32
    )
    out = out.reshape(b, 1, cfg.num_heads * hd).astype(x.dtype)
    y = linear(params["o"], out)
    y = constrain(y, ("batch", None, None))
    new_cache = {"k": k_cache, "v": v_cache} if update_cache else cache
    return y, new_cache
