"""Jamba token streams served through the session pool, compared with the
plain float32 reference (``tests/jamba_reference.py``) on seeded weights.

The size is one period of the layer pattern (14 layers: 13 Mamba, 1
attention at offset 7), ``d_model`` 64, ``d_state`` 4, 4 query heads and 1
KV head, a vocabulary of 512.  The program runs in float32 here (weights,
conv state and KV cache), so it and the reference differ only in the order
of float32 sums: the tolerance ``TOL`` on the relative gap of every
running answer is 2e-5, twenty times the largest gap measured (~1e-6).
Each planted fault (the SSM state in bf16, one Mamba layer's state not
carried, one KV length shared by every row) moves answers by 1e-3 or more
and must fail it."""
import dataclasses
import sys
import threading
import time
from contextlib import contextmanager
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import jamba_reference as ref  # noqa: E402
from repro.config import reduced_config  # noqa: E402
from repro.engine import AnomalyService  # noqa: E402
from repro.gateway import wire  # noqa: E402
from repro.gateway.client import GatewayClient  # noqa: E402
from repro.gateway.pool import SequenceFullError  # noqa: E402
from repro.gateway.server import GatewayServer  # noqa: E402
from repro.models import build_model, jamba  # noqa: E402

CFG = dataclasses.replace(reduced_config("jamba2-3b"), compute_dtype="float32")
REF = ref.config_dict(CFG)
VOCAB = CFG.vocab_size
#: relative gap allowed between a served answer and the reference (see above)
TOL = 2e-5


@pytest.fixture(scope="module")
def svc():
    return AnomalyService(CFG, seed=3)


def tokens(stream: int, n: int) -> np.ndarray:
    """The first ``n`` (at most 128) tokens of stream ``stream``."""
    rng = np.random.default_rng(np.random.SeedSequence([11, stream]))
    return rng.integers(0, VOCAB, 128).astype(np.int32)[:n]


@contextmanager
def held(server):
    """Block the server's event loop for the body, so every frame the body
    sends is read in the loop's next pass (and stepped together)."""
    release, blocked = threading.Event(), threading.Event()

    def block():
        blocked.set()
        release.wait(20)

    server._loop.call_soon_threadsafe(block)
    assert blocked.wait(10)
    try:
        yield
    finally:
        time.sleep(0.1)  # loopback delivery of the last bytes sent
        release.set()


def rel_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / want))


def staggered(gw, starts: dict, steps: int) -> dict:
    """Step streams that join at different steps (``starts``: stream ->
    first step), every resident stream one token a pool step, so a step
    carries rows at different KV lengths; -> stream -> running answers."""
    out = {s: [] for s in starts}
    for t in range(steps):
        inputs = {}
        for s, t0 in starts.items():
            if t == t0:
                gw.admit(s)
            if t >= t0:
                inputs[s] = tokens(s, steps)[t - t0]
        for s, value in gw.step(inputs).items():
            out[s].append(value)
    return out


def worst_gap(svc, answers: dict) -> float:
    return max(rel_gap(vals, ref.running_surprisal(svc.params, tokens(s, 99)[:len(vals)], REF))
               for s, vals in answers.items())


def test_first_token_is_uniform(svc):
    gw = svc.open_gateway(capacity=2, max_seq_len=8)
    gw.admit("s")
    assert gw.step({"s": np.int32(5)})["s"] == pytest.approx(np.log(VOCAB), rel=1e-6)


def test_bp1_i4_frames_through_the_server_match_the_reference(svc):
    """Four connections send one i4 STEP a round, joining at rounds 0, 2,
    5 and 9: the server's coalescer steps them together, so pool steps
    carry rows at different KV lengths; every answer is the reference's."""
    gw = svc.open_gateway(capacity=6, max_seq_len=64)
    server = GatewayServer(gw, port=0, pump_interval_ms=2.0)
    host, port = server.start_in_thread()
    starts, rounds = [0, 2, 5, 9], 16
    clients = [GatewayClient(host, port, protocol="binary") for _ in starts]
    got = {i: [] for i in range(len(starts))}
    try:
        for r in range(rounds):
            sent = []
            with held(server):
                for i, (client, t0) in enumerate(zip(clients, starts)):
                    if r >= t0:
                        tok = tokens(100 + i, rounds)[r - t0: r - t0 + 1]
                        sent.append((i, client._send_frame(
                            wire.OP_STEP, meta={"t": 1, "dtype": "i4"},
                            data=tok.astype("<i4").tobytes())))
            for i, rid in sent:
                resp = clients[i].collect(rid)
                assert resp["ok"], resp
                got[i].extend(resp["running_errors"])
        counters = gw.stats()["counters"]
    finally:
        for client in clients:
            client.close()
        server.stop_in_thread()
    assert counters["pool.stream_steps"] > counters["pool.steps"]
    assert counters["pool.kv_room"] == counters["pool.stream_steps"] * 64
    assert counters["pool.kv_tokens"] == sum(n * (n + 1) // 2
                                             for n in (len(v) for v in got.values()))
    for i, vals in got.items():
        want = ref.running_surprisal(svc.params, tokens(100 + i, rounds)[:len(vals)], REF)
        assert rel_gap(vals, want) <= TOL


def test_staggered_pool_steps_match_the_reference(svc):
    gw = svc.open_gateway(capacity=4, max_seq_len=32)
    answers = staggered(gw, {0: 0, 1: 3, 2: 7}, 14)
    assert worst_gap(svc, answers) <= TOL


def test_evict_then_readmit_into_the_same_slot_starts_from_zero(svc):
    gw = svc.open_gateway(capacity=2, max_seq_len=16)
    slot = gw.admit("a")
    for tok in tokens(7, 10):
        gw.step({"a": tok})
    gw.evict("a")
    assert gw.admit(8) == slot
    got = [gw.step({8: tok})[8] for tok in tokens(8, 6)]
    assert rel_gap(got, ref.running_surprisal(svc.params, tokens(8, 6), REF)) <= TOL


def test_export_and_restore_resume_a_stream(svc):
    gw = svc.open_gateway(capacity=3, max_seq_len=16)
    toks = tokens(9, 9)
    gw.admit("a")
    for tok in toks[:6]:
        gw.step({"a": tok})
    rows, sq, steps = gw.pool.export_slot("a")
    gw.evict("a")
    gw.admit("other")  # the stream comes back to another slot
    slot = gw.pool.restore("a", rows, sq, steps)
    assert slot != gw.pool.slot_of("other") and steps == 6
    got = [gw.step({"a": tok})["a"] for tok in toks[6:]]
    want = ref.running_surprisal(svc.params, toks, REF)[6:]
    assert rel_gap(got, want) <= TOL


def test_a_stream_at_max_seq_len_is_refused(svc):
    gw = svc.open_gateway(capacity=2, max_seq_len=4)
    server = GatewayServer(gw, port=0)
    host, port = server.start_in_thread()
    try:
        with GatewayClient(host, port, protocol="binary") as client:
            answers = []
            for tok in tokens(12, 5):
                rid = client._send_frame(wire.OP_STEP, meta={"t": 1, "dtype": "i4"},
                                         data=np.int32(tok).astype("<i4").tobytes())
                answers.append(client._read_until(rid))
        assert all(a["ok"] for a in answers[:4])
        assert not answers[4]["ok"] and answers[4]["error"] == "SequenceFullError"
        assert gw.stats()["counters"]["pool.kv_full"] == 1
    finally:
        server.stop_in_thread()
    gw.admit("direct")
    for tok in tokens(13, 4):
        gw.step({"direct": tok})
    with pytest.raises(SequenceFullError):
        gw.step({"direct": np.int32(1)})


@pytest.mark.parametrize("bad", [VOCAB, -1])
def test_out_of_vocabulary_ids_answer_an_error_and_pin_no_slot(svc, bad):
    gw = svc.open_gateway(capacity=2, max_seq_len=8)
    server = GatewayServer(gw, port=0)
    host, port = server.start_in_thread()
    try:
        with GatewayClient(host, port, protocol="binary") as client:
            rid = client._send_frame(wire.OP_STEP, meta={"t": 1, "dtype": "i4"},
                                     data=np.int32(bad).astype("<i4").tobytes())
            resp = client._read_until(rid)
            f4 = client._send_frame(wire.OP_STEP, meta={"t": 1},
                                    data=np.float32(3).astype("<f4").tobytes())
            resp_f4 = client._read_until(f4)
        assert not resp["ok"] and resp["error"] == "ValueError"
        assert "outside the vocabulary" in resp["message"]
        assert not resp_f4["ok"] and "int32" in resp_f4["message"]
        assert gw.pool.active == 0
    finally:
        server.stop_in_thread()


def test_score_and_fit_refuse(svc):
    gw = svc.open_gateway(capacity=2, max_seq_len=8)
    with pytest.raises(ValueError, match="streams only"):
        gw.score([np.zeros((4, 1), np.float32)])
    with pytest.raises(ValueError, match="streams only"):
        svc.fit(None, steps=1)


def test_the_pool_step_donates_its_state_block(svc):
    gw = svc.open_gateway(capacity=2, max_seq_len=8)
    gw.admit("s")
    before = jax.tree.leaves(gw.pool._state)
    gw.step({"s": np.int32(3)})
    assert all(leaf.is_deleted() for leaf in before)


def test_the_stream_chain_equals_the_sequence_form(svc):
    """``stream_step`` token by token reaches the hidden state ``forward``
    computes over the whole sequence."""
    toks = tokens(21, 12)
    model = build_model(CFG).stream()
    state = model.init_state(1, 16)
    mask = jnp.ones((1,), bool)
    for t, tok in enumerate(toks):
        state, _ = model.step(svc.params, jnp.asarray([tok]), state, mask,
                              jnp.asarray([t], jnp.int32))
    h = svc.params["embed"]["table"][jnp.asarray(toks)[None]]
    seq, _, _ = jamba.forward(svc.params, h, CFG, remat=False)
    np.testing.assert_allclose(np.asarray(state["h"][0]), np.asarray(seq[0, -1]),
                               rtol=1e-4, atol=1e-5)


# -- planted faults --------------------------------------------------------

def _ssm_in_bf16(monkeypatch):
    from repro.layers import mamba

    real = mamba.ssm_step

    def bf16(dt, b_t, c_t, xc, a, state):
        y, new = real(*(v.astype(jnp.bfloat16) for v in (dt, b_t, c_t, xc, a, state)))
        return y.astype(jnp.float32), new.astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(mamba, "ssm_step", bf16)


def _one_layer_state_dropped(monkeypatch):
    real = jamba.apply_mamba_step
    calls = []

    def dropping(params, x, cfg, state):
        calls.append(1)
        y, new = real(params, x, cfg, state)
        return y, (state if len(calls) % 13 == 2 else new)  # the 2nd Mamba layer

    monkeypatch.setattr(jamba, "apply_mamba_step", dropping)


def _kv_length_shared(monkeypatch):
    real = jamba.decode_attention

    def shared(params, x, cache, cache_len, **kw):
        return real(params, x, cache, jnp.full_like(cache_len, cache_len[0]), **kw)

    monkeypatch.setattr(jamba, "decode_attention", shared)


@pytest.mark.parametrize("plant", [_ssm_in_bf16, _one_layer_state_dropped,
                                   _kv_length_shared],
                         ids=["ssm_state_bf16", "mamba_state_not_carried",
                              "kv_length_shared"])
def test_planted_faults_fail_the_comparison(svc, monkeypatch, plant):
    plant(monkeypatch)
    gw = svc.open_gateway(capacity=4, max_seq_len=32)
    answers = staggered(gw, {0: 0, 1: 3, 2: 7}, 14)
    assert worst_gap(svc, answers) > 10 * TOL
