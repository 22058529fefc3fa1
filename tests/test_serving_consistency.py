"""Serving-path consistency: for every family, decoding token t against
prefilled state must reproduce the teacher-forced forward at position t.
This is the invariant batched serving relies on (cache correctness)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import reduced_config
from repro.models import build_model


def _tokens(cfg, b, s, key):
    return jax.random.randint(key, (b, s), 0, cfg.vocab_size, jnp.int32)


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "moonshot-v1-16b-a3b"])
def test_transformer_decode_consistent_with_prefill(arch):
    cfg = reduced_config(arch)
    if cfg.moe is not None:
        # capacity-based MoE routing is batch-dependent (prefill routes B*S
        # tokens jointly; decode routes B) — give ample capacity so nothing
        # drops and the paths are comparable
        import dataclasses
        cfg = cfg.with_overrides(moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    b, s = 2, 10
    toks = _tokens(cfg, b, s + 1, jax.random.PRNGKey(1))

    # teacher-forced logits at the last position
    full_logits, _ = api.prefill(params, {"tokens": toks})

    # prefill the prefix, stitch its cache into a decode cache, decode last
    _, prefix_cache = api.prefill(params, {"tokens": toks[:, :-1]})
    cache = api.init_cache(b, s + 1)
    cache = {
        "k": cache["k"].at[:, :, :s].set(prefix_cache["k"].astype(cache["k"].dtype)),
        "v": cache["v"].at[:, :, :s].set(prefix_cache["v"].astype(cache["v"].dtype)),
    }
    dec_logits, _ = api.decode(params, toks[:, -1:], cache, jnp.int32(s))
    if cfg.moe is not None:
        # top-k routing is a discrete boundary: assert the serving-relevant
        # invariant (greedy token identity) instead of elementwise closeness
        np.testing.assert_array_equal(
            np.asarray(jnp.argmax(dec_logits[:, -1], -1)),
            np.asarray(jnp.argmax(full_logits[:, -1], -1)),
        )
    else:
        np.testing.assert_allclose(
            np.asarray(dec_logits.astype(jnp.float32)),
            np.asarray(full_logits.astype(jnp.float32)),
            rtol=6e-2, atol=6e-2,  # bf16 cache round-trip
        )


def test_transformer_decode_unroll_equals_scan():
    """The §Perf unrolled decode loop matches scan (bf16 fusion-order tol)."""
    cfg = reduced_config("tinyllama-1.1b")
    api_scan = build_model(cfg)
    api_unroll = build_model(cfg.with_overrides(decode_loop="unroll"))
    params = api_scan.init(jax.random.PRNGKey(2))
    b = 2
    cache = api_scan.init_cache(b, 16)
    tok = jnp.ones((b, 1), jnp.int32)
    l1, c1 = api_scan.decode(params, tok, cache, jnp.int32(3))
    l2, c2 = api_unroll.decode(params, tok, api_unroll.init_cache(b, 16), jnp.int32(3))
    np.testing.assert_allclose(np.asarray(l1, np.float32), np.asarray(l2, np.float32),
                               rtol=6e-2, atol=6e-2)
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(l1[:, -1], -1)), np.asarray(jnp.argmax(l2[:, -1], -1))
    )
    # unroll uses a tuple-of-layers cache; stack it for comparison
    c2_stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *c2)
    for a, b_ in zip(jax.tree.leaves(c1), jax.tree.leaves(c2_stacked)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b_, np.float32),
                                   rtol=6e-2, atol=6e-2)


# jamba2-3b's served stream keeps its residual stream in float32 (its 14
# layers in bf16 drift past this tolerance), so its paths are held there
@pytest.mark.parametrize("arch,dtype", [("jamba-v0.1-52b", "bfloat16"),
                                        ("jamba2-3b", "float32")])
def test_jamba_decode_consistent_with_prefill(arch, dtype):
    cfg = reduced_config(arch).with_overrides(compute_dtype=dtype)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(3))
    b, s = 2, 9
    toks = _tokens(cfg, b, s + 1, jax.random.PRNGKey(4))
    full_logits, _ = api.prefill(params, {"tokens": toks})

    _, states = api.prefill(params, {"tokens": toks[:, :-1]})
    # stitch prefill states into decode layout: KV caches padded to s+1
    dec_states = []
    for j, st in enumerate(states):
        if "k" in st:  # attention position
            tmpl = jax.tree.map(
                lambda x: x, api.init_cache(b, s + 1)[j]
            )
            dec_states.append({
                "k": tmpl["k"].at[:, :, :s].set(st["k"].astype(tmpl["k"].dtype)),
                "v": tmpl["v"].at[:, :, :s].set(st["v"].astype(tmpl["v"].dtype)),
            })
        else:
            dec_states.append(st)
    dec_logits, _ = api.decode(params, toks[:, -1:], tuple(dec_states), jnp.int32(s))
    np.testing.assert_allclose(
        np.asarray(dec_logits.astype(jnp.float32)),
        np.asarray(full_logits.astype(jnp.float32)),
        rtol=5e-2, atol=5e-2,
    )


def test_whisper_decode_consistent_with_prefill():
    cfg = reduced_config("whisper-large-v3")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(5))
    b, s = 2, 8
    toks = _tokens(cfg, b, s + 1, jax.random.PRNGKey(6))
    frames = jax.random.normal(jax.random.PRNGKey(7),
                               (b, cfg.encoder_seq_len, cfg.d_model), jnp.float32)
    full_logits, _ = api.prefill(params, {"tokens": toks, "frames": frames})

    _, pre = api.prefill(params, {"tokens": toks[:, :-1], "frames": frames})
    cache = api.init_cache(b, s + 1)
    cache = {
        "k": cache["k"].at[:, :, :s].set(pre["k"].astype(cache["k"].dtype)),
        "v": cache["v"].at[:, :, :s].set(pre["v"].astype(cache["v"].dtype)),
        "ck": pre["ck"].astype(cache["ck"].dtype),
        "cv": pre["cv"].astype(cache["cv"].dtype),
    }
    dec_logits, _ = api.decode(params, toks[:, -1:], cache, jnp.int32(s))
    np.testing.assert_allclose(
        np.asarray(dec_logits.astype(jnp.float32)),
        np.asarray(full_logits.astype(jnp.float32)),
        rtol=5e-2, atol=5e-2,
    )


def test_greedy_decode_loop_runs():
    from repro.serving import greedy_decode_loop
    cfg = reduced_config("olmo-1b")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(8))
    b = 2
    cache = api.init_cache(b, 24)
    first = jnp.ones((b, 1), jnp.int32)
    toks, _ = greedy_decode_loop(api, params, cache, first, jnp.int32(0), 8)
    assert toks.shape == (b, 8)
    assert int(toks.max()) < cfg.vocab_size
