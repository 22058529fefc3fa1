"""Jamba language models (``bench/configs/jamba2-3b.json``) scoring token
streams: a resident stream sends one token a STEP (``"dtype": "i4"``) and
is answered the running mean surprisal of its tokens, the model's
anomaly score for a log stream (DeepLog, Du et al., CCS 2017).  The family
serves streams only: no stored windows.

Inputs are log-like token streams.  Each seed draws ``TEMPLATES`` message
templates of 8-32 token ids from a Zipf law over the vocabulary (through a
seeded permutation, so frequent ids are spread over it); a stream is a
seeded walk over the templates, each emitted whole, the templates a stream
uses drawn from a Zipf law of its own.  A stream is anomalous with
probability ``anomaly_rate``: a share ``ODD_SHARE`` of its tokens are then
replaced by ids drawn uniformly from the vocabulary, outside any template.
Each chunk of ``CHUNK`` tokens is drawn from its own seed.

The weights follow HF Jamba's initializer: every matrix and the embedding
normal with std ``initializer_range`` (0.02), biases zero, norm weights 1,
``A_log = log(1..d_state)`` and ``D = 1`` in every Mamba mixer; matrices
in bfloat16, the rest float32 (the served precision).  ``make_params``
returns them as :class:`Weights`, the program's tree beside the
configuration keys the plain forward (``bench/reference_jamba.py``) reads,
so that ``reference_answers``, handed the weights alone, has both.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

CHUNK = 256
TEMPLATES = 64
ZIPF = 1.1
ODD_SHARE = 0.05


@dataclass(frozen=True)
class Weights:
    """A run's weights: ``tree`` in the program's layout, and ``config``,
    the configuration keys the plain forward needs besides their shapes
    (the head counts, the norms' eps, the layer pattern)."""
    tree: dict
    config: dict


def _shapes(config: dict) -> dict:
    """The program's parameter layout (``models/jamba.py``) as a tree of
    ``(shape, kind)``: kind ``w`` a bf16 matrix, ``one``/``zero`` a float32
    vector, ``a_log`` Mamba's A."""
    d, v = config["hidden_size"], config["vocab_size"]
    e = config["mamba_expand"] * d
    n, k, r = config["mamba_d_state"], config["mamba_d_conv"], config["mamba_dt_rank"]
    f = config["intermediate_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = d // heads
    per = config["attn_layer_period"]
    stack = config["num_hidden_layers"] // per

    def s(*shape, kind="w"):
        return ((stack,) + shape, kind)

    mlp = {"gate": {"w": s(d, f)}, "up": {"w": s(d, f)}, "down": {"w": s(f, d)}}
    mamba = {
        "in_x": {"w": s(d, e)}, "in_z": {"w": s(d, e)},
        "conv_w": s(k, e), "conv_b": s(e, kind="zero"),
        "x_proj": {"w": s(e, r + 2 * n)},
        "dt_norm": {"scale": s(r, kind="one")}, "b_norm": {"scale": s(n, kind="one")},
        "c_norm": {"scale": s(n, kind="one")},
        "dt_proj": {"w": s(r, e), "b": s(e, kind="zero")},
        "a_log": s(e, n, kind="a_log"), "d_skip": s(e, kind="one"),
        "out": {"w": s(e, d)},
    }
    attn = {"q": {"w": s(d, heads * hd)}, "k": {"w": s(d, kv * hd)},
            "v": {"w": s(d, kv * hd)}, "o": {"w": s(heads * hd, d)}}
    positions = tuple(
        {"ln1": {"scale": s(d, kind="one")}, "ln2": {"scale": s(d, kind="one")},
         "mixer": attn if j == config["attn_layer_offset"] else mamba, "ffn": mlp}
        for j in range(per))
    return {"embed": {"table": ((v, d), "w")}, "positions": positions,
            "ln_f": {"scale": ((d,), "one")}}


def _reference_config(config: dict) -> dict:
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "attn_layer_period", "attn_layer_offset",
            "mamba_d_state", "mamba_d_conv", "mamba_dt_rank", "rms_norm_eps")
    return {key: config[key] for key in keys}


def make_params(seed: int, config: dict) -> Weights:
    """The weights of a run, made leaf by leaf on the default device."""
    import jax
    import jax.numpy as jnp
    from reference import seed_key
    from repro.config import get_config

    # a program that does not know the architecture fails here, before
    # 6 GB of weights are made for it
    get_config(config["arch"])

    std = float(config["initializer_range"])

    @partial(jax.jit, static_argnums=1)
    def normal(key, shape):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)

    base = seed_key(seed)
    leaves, treedef = jax.tree.flatten(_shapes(config), is_leaf=_is_leaf)
    out = []
    for i, (shape, kind) in enumerate(leaves):
        if kind == "w":
            out.append(normal(jax.random.fold_in(base, i), shape))
        elif kind == "a_log":
            a = np.log(np.arange(1, shape[-1] + 1, dtype=np.float32))
            out.append(jnp.asarray(np.broadcast_to(a, shape)))
        else:
            out.append(jnp.full(shape, 1.0 if kind == "one" else 0.0, jnp.float32))
    tree = jax.block_until_ready(jax.tree.unflatten(treedef, out))
    return Weights(tree, _reference_config(config))


def open_gateway(config: dict, params: Weights, knobs: dict):
    """An ``AnomalyService`` serving ``params`` (given at construction: the
    service initialises no weights of its own)."""
    from repro.engine import AnomalyService

    svc = AnomalyService(config["arch"], params=params.tree)
    return svc.open_gateway(**knobs)


def warm_payloads(config: dict, mix: dict, seed: int) -> list:
    """None: the pool step compiles through each stream's first frame."""
    return []


def _templates(seed: int, vocab: int) -> list:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 101]))
    ranks = 1.0 / np.arange(1, vocab + 1) ** ZIPF
    ids = rng.permutation(vocab)
    return [ids[rng.choice(vocab, size=int(rng.integers(8, 33)), p=ranks / ranks.sum())]
            for _ in range(TEMPLATES)]


_TEMPLATE_CACHE: dict = {}


def stream_chunk(seed: int, stream: int, chunk: int, config: dict,
                 anomaly_rate: float) -> np.ndarray:
    """Tokens ``chunk * CHUNK ..`` of a stream, (CHUNK,) int32."""
    vocab = int(config["vocab_size"])
    key = (seed, vocab)
    if key not in _TEMPLATE_CACHE:
        _TEMPLATE_CACHE[key] = _templates(seed, vocab)
    templates = _TEMPLATE_CACHE[key]
    odd = np.random.default_rng(
        np.random.SeedSequence([seed, stream, 1 << 30])).random() < anomaly_rate
    pref = np.random.default_rng(np.random.SeedSequence([seed, stream, 1 << 31]))
    weights = 1.0 / np.arange(1, TEMPLATES + 1) ** ZIPF
    order = pref.permutation(TEMPLATES)
    rng = np.random.default_rng(np.random.SeedSequence([seed, stream, chunk]))
    out = []
    while sum(len(t) for t in out) < CHUNK:
        out.append(templates[order[rng.choice(TEMPLATES, p=weights / weights.sum())]])
    tokens = np.concatenate(out)[:CHUNK].astype(np.int32)
    if odd:
        swap = rng.random(CHUNK) < ODD_SHARE
        tokens[swap] = rng.integers(0, vocab, int(swap.sum()))
    return tokens


def stream_samples(seed: int, stream: int, count: int, config: dict,
                   anomaly_rate: float) -> np.ndarray:
    chunks = [stream_chunk(seed, stream, c, config, anomaly_rate)
              for c in range(-(-count // CHUNK))]
    return np.concatenate(chunks)[:count] if chunks else np.zeros(0, np.int32)


def window(seed: int, index: int, length: int, config: dict,
           anomaly_rate: float) -> np.ndarray:
    raise ValueError("the jamba family serves streams only: no stored windows")


def step_frame(samples: np.ndarray) -> tuple:
    """``k`` consecutive tokens as one STEP frame."""
    return ({"t": len(samples), "dtype": "i4"},
            np.ascontiguousarray(samples, "<i4").tobytes())


def score_frame(x: np.ndarray) -> tuple:
    raise ValueError("the jamba family serves streams only: no SCORE frames")


def reference_answers(params: Weights, samples: list, windows: list,
                      precision: str) -> tuple:
    import reference_jamba

    if windows:
        raise ValueError("the jamba family serves streams only: no stored windows")
    if not isinstance(params, Weights):
        raise TypeError("the jamba reference takes the Weights make_params "
                        f"made, not {type(params).__name__}")
    running = (reference_jamba.running_answers(params.tree, samples, params.config,
                                               precision)
               if samples else [])
    return running, np.zeros(0, np.float64)


def non_embedding_params(config: dict) -> int:
    """Parameters outside the embedding (norm weights and biases included)."""
    total = 0
    for shape, _ in _flat(_shapes(config)["positions"]):
        total += int(np.prod(shape))
    return total + config["hidden_size"]  # ln_f


def _is_leaf(node) -> bool:
    return isinstance(node, tuple) and len(node) == 2 and isinstance(node[1], str)


def _flat(tree):
    if _is_leaf(tree):
        yield tree
    elif isinstance(tree, dict):
        for value in tree.values():
            yield from _flat(value)
    else:
        for value in tree:
            yield from _flat(value)


def useful_work(config: dict, kind: str, answers: dict) -> tuple:
    """A token's work: FLOPs ``2 x`` the non-embedding parameters, the tied
    unembedding's ``2 D V`` and, in each attention layer, scores and values
    over the ``position + 1`` tokens it attends to (``4 D`` a token).
    Bytes: the SSM state (float32) and conv state (bf16) of every Mamba
    layer and the last hidden state (float32) read and written, the KV
    cache (bf16) read for the ``position`` tokens before it and one token
    written in every attention layer, the token in and the answer out.  The weights are not
    counted (they are read once per step, whatever its rows)."""
    if kind != "step":
        return 0, 0
    pos = np.asarray(answers["position"], np.int64)
    d, v = config["hidden_size"], config["vocab_size"]
    e = config["mamba_expand"] * d
    n, k = config["mamba_d_state"], config["mamba_d_conv"]
    hd = d // config["num_attention_heads"]
    kv = config["num_key_value_heads"]
    layers = config["num_hidden_layers"]
    attn = layers // config["attn_layer_period"]
    mamba = layers - attn
    tokens = len(pos)
    flops = tokens * (2 * non_embedding_params(config) + 2 * d * v) \
        + attn * 4 * d * int(np.sum(pos + 1))
    state = mamba * (e * n * 4 + (k - 1) * e * 2) + d * 4
    kv_bytes = attn * 2 * kv * hd * 2 * (int(np.sum(pos)) + tokens)
    nbytes = tokens * (2 * state + 4 + 4) + kv_bytes
    return int(flops), int(nbytes)
