"""Cross-connection STEP coalescing: samples that arrive in one pass of the
server's event loop share one pool step, and every answer is bit-for-bit
what its stream sees stepped alone.

A test that needs frames from many connections to land in ONE pass holds
the server's loop (a blocking callback on its thread) while the clients
send, so the sockets are all readable when the loop resumes."""
import threading
import time
from contextlib import contextmanager

import numpy as np
import pytest

from conftest import (
    GATEWAY_ARCH as ARCH,
    GATEWAY_FEATS as FEATS,
    gateway_series as _series,
    solo_stream_errors as _solo_errors,
)
from repro.engine import AnomalyService
from repro.gateway import wire
from repro.gateway.client import GatewayClient, GatewayClientError
from repro.gateway.durability import enable_durability
from repro.gateway.pool import StepCoalescer, UnknownStreamError
from repro.gateway.server import GatewayServer

CAPACITY = 12


@pytest.fixture(scope="module")
def svc():
    return AnomalyService(ARCH, schedule="wavefront")


@pytest.fixture(scope="module")
def oracle(svc):
    """Running errors of one stream stepped alone, one sample per pool
    step, through a pool of the served block's shape: the per-sample path
    the coalescer replaces."""
    gw = svc.open_gateway(capacity=CAPACITY)
    cache: dict = {}

    def errors(stream: int, t_len: int) -> np.ndarray:
        key = (stream, t_len)
        if key not in cache:
            gw.admit(key)
            cache[key] = np.array([gw.step({key: x})[key]
                                   for x in _series(stream, t_len)], np.float32)
            gw.evict(key)
        return cache[key]

    return errors


@pytest.fixture
def served(svc):
    gw = svc.open_gateway(capacity=CAPACITY, max_batch=4, max_wait_ms=10.0)
    server = GatewayServer(gw, port=0, pump_interval_ms=2.0)
    host, port = server.start_in_thread()
    yield server, host, port, gw
    server.stop_in_thread()


@contextmanager
def held(server):
    """Block the server's event loop for the body, so every frame the body
    sends is readable when the loop's next pass selects."""
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


def send_step(client: GatewayClient, xs) -> int:
    """Send one STEP request of ``xs`` (k, F) without waiting; -> its id."""
    xs = np.ascontiguousarray(xs, "<f4")
    if client.protocol == "bp1":
        return client._send_frame(wire.OP_STEP, meta={"t": len(xs)},
                                  data=xs.tobytes())
    assert len(xs) == 1
    return client._send({"op": "step", "x": xs[0].tolist()})


def running_errors(resp: dict) -> list:
    return resp.get("running_errors", [resp.get("running_error")])


def counters(gw) -> tuple:
    c = gw.stats()["counters"]
    return c.get("pool.steps", 0), c.get("pool.stream_steps", 0)


# -- equivalence -------------------------------------------------------------

#: per connection: protocol and the frame sizes it keeps in flight each round
PLANS = [
    ("binary", [1]), ("binary", [1]), ("binary", [1]), ("binary", [1, 1]),
    ("binary", [3]), ("binary", [3]), ("binary", [3, 1]), ("binary", [1, 3]),
    ("json", [1]),
]


def test_concurrent_connections_coalesce_and_match_solo(served, svc, oracle):
    """Nine connections (t = 1 and t = 3 frames, two frames in flight on
    some, one JSON) step in the same passes: steps carry several rows, and
    every running error equals the stream's own, stepped alone."""
    server, host, port, gw = served
    rounds = 3
    lengths = [rounds * sum(sizes) for _, sizes in PLANS]
    streams = [100 + i for i in range(len(PLANS))]
    clients = [GatewayClient(host, port, protocol=proto) for proto, _ in PLANS]
    got = [[] for _ in PLANS]
    try:
        steps0, rows0 = counters(gw)
        for r in range(rounds):
            sent = []
            with held(server):
                for i, (client, (_, sizes)) in enumerate(zip(clients, PLANS)):
                    data = _series(streams[i], lengths[i])
                    t = r * sum(sizes)
                    for k in sizes:
                        sent.append((i, send_step(client, data[t:t + k])))
                        t += k
            for i, rid in sent:
                got[i].extend(running_errors(clients[i].collect(rid)))
        steps, rows = counters(gw)
        assert (rows - rows0) / (steps - steps0) > 1
        assert rows - rows0 == sum(lengths)
        finals = [c.end_session()["final"] for c in clients]
    finally:
        for c in clients:
            c.close()
    for i, stream in enumerate(streams):
        want = oracle(stream, lengths[i])
        np.testing.assert_array_equal(np.array(got[i], np.float32), want)
        assert finals[i] == want[-1]
        np.testing.assert_allclose(want, _solo_errors(svc, _series(stream, lengths[i])),
                                   rtol=1e-5, atol=1e-5)


def test_one_connection_steps_one_row_at_a_time(served, oracle):
    """With one stream active every pool step carries one row, and the
    answers are those of the per-sample path."""
    server, host, port, gw = served
    data = _series(200, 9)
    got = []
    with GatewayClient(host, port) as client:
        steps0, rows0 = counters(gw)
        rids = []
        with held(server):
            for lo, hi in ((0, 1), (1, 4), (4, 5)):  # three frames in flight
                rids.append(send_step(client, data[lo:hi]))
        for rid in rids:
            got.extend(running_errors(client.collect(rid)))
        got.extend(client.step_many(data[5:8]))
        got.append(client.step(data[8])["running_error"])
        steps, rows = counters(gw)
    assert steps - steps0 == rows - rows0 == 9
    np.testing.assert_array_equal(np.array(got, np.float32), oracle(200, 9))


# -- ordering --------------------------------------------------------------


def test_close_after_pipelined_steps_includes_them(served, oracle):
    server, host, port, gw = served
    data = _series(300, 4)
    with GatewayClient(host, port) as client, GatewayClient(host, port) as other:
        with held(server):
            rids = [send_step(client, data[0:1]), send_step(client, data[1:4])]
            other_rid = send_step(other, _series(301, 1))
            close_rid = client._send_frame(wire.OPCODE_BY_NAME["close"])
        final = client.collect(close_rid)["final"]
        got = [e for rid in rids for e in running_errors(client.collect(rid))]
        assert running_errors(other.collect(other_rid)) == [oracle(301, 1)[0]]
    want = oracle(300, 4)
    np.testing.assert_array_equal(np.array(got, np.float32), want)
    assert final == want[-1]


def test_hang_up_with_queued_samples_frees_its_slot(served, oracle):
    """A connection that sends STEP frames and hangs up at once: the other
    streams are answered, and its slot is freed."""
    server, host, port, gw = served
    others = [GatewayClient(host, port) for _ in range(2)]
    quitter = GatewayClient(host, port)
    try:
        quitter.step(_series(400, 1)[0])  # resident before the hang-up
        assert gw.pool.active == 1
        with held(server):
            rids = [send_step(c, _series(401 + i, 3)) for i, c in enumerate(others)]
            send_step(quitter, _series(400, 3)[1:3])
            quitter.close()
        for i, (c, rid) in enumerate(zip(others, rids)):
            np.testing.assert_array_equal(
                np.array(running_errors(c.collect(rid)), np.float32), oracle(401 + i, 3))
        deadline = time.time() + 10
        while gw.pool.active != 2 and time.time() < deadline:
            time.sleep(0.01)
        assert gw.pool.active == 2
    finally:
        for c in others:
            c.close()


# -- failures -------------------------------------------------------------


def test_engine_failure_in_a_flush_answers_its_frames_and_serving_goes_on(
        served, oracle, monkeypatch):
    """The step of a flush raises once: every frame in that step (a t = 3
    frame among them) answers the engine's error, nothing is stepped, and
    the same connections then stream as if from scratch."""
    server, host, port, gw = served
    real = gw.pool._pool_step
    fail = [1]

    def broken(*args):
        if fail[0]:
            fail[0] -= 1
            raise RuntimeError("injected engine failure")
        return real(*args)

    monkeypatch.setattr(gw.pool, "_pool_step", broken)
    clients = [GatewayClient(host, port) for _ in range(3)]
    try:
        with held(server):
            rids = [send_step(c, _series(500 + i, 3)[:1 + 2 * (i == 0)])
                    for i, c in enumerate(clients)]
        for c, rid in zip(clients, rids):
            with pytest.raises(GatewayClientError) as ei:
                c.collect(rid)
            assert "injected engine failure" in ei.value.message
        for i, c in enumerate(clients):  # server still serving, state intact
            got = c.step_many(_series(500 + i, 3))
            np.testing.assert_array_equal(np.array(got, np.float32), oracle(500 + i, 3))
    finally:
        for c in clients:
            c.close()


def test_drain_answers_queued_steps(svc, oracle):
    gw = svc.open_gateway(capacity=CAPACITY)
    server = GatewayServer(gw, port=0, pump_interval_ms=2.0)
    host, port = server.start_in_thread()
    clients = [GatewayClient(host, port) for _ in range(3)]
    try:
        server.steps._schedule = lambda flush: None  # only drain flushes now
        rids = [send_step(c, _series(600 + i, 2)) for i, c in enumerate(clients)]
        deadline = time.time() + 10
        while server.steps.pending != 3 and time.time() < deadline:
            time.sleep(0.01)
        assert server.steps.pending == 3
        server.stop_in_thread()
        for i, (c, rid) in enumerate(zip(clients, rids)):
            np.testing.assert_array_equal(
                np.array(running_errors(c.collect(rid)), np.float32), oracle(600 + i, 2))
    finally:
        server.stop_in_thread()
        for c in clients:
            c.close()


def test_durable_seq_and_token_follow_step_order(svc, oracle, tmp_path):
    """Durable sessions through the coalescer: a frame's seq is its last
    sample's position, and tokens are minted at the refresh step itself."""
    gw = svc.open_gateway(capacity=CAPACITY)
    dur = enable_durability(gw, tmp_path / "store", snapshot_interval_ms=60_000)
    server = GatewayServer(gw, port=0, pump_interval_ms=2.0)
    host, port = server.start_in_thread()
    sizes = [1, 3, 3, 3, 3, 3, 2]  # 18 samples: the token refresh is at 16
    try:
        with GatewayClient(host, port) as a, GatewayClient(host, port) as b:
            with held(server):
                data = _series(700, 18)
                rids, t = [], 0
                for k in sizes:
                    rids.append(send_step(a, data[t:t + k]))
                    t += k
                b_rid = send_step(b, _series(701, 3))
            answers = [a.collect(rid) for rid in rids]
            b_answer = b.collect(b_rid)
            assert b_answer["seq"] == 3
            got = [e for r in answers for e in running_errors(r)]
            np.testing.assert_array_equal(np.array(got, np.float32), oracle(700, 18))
            assert [r["seq"] for r in answers] == list(np.cumsum(sizes))
            claims = [dur.store.signer.verify(r["token"]).seq for r in answers]
            assert claims == [0, 0, 0, 0, 0, 16, 16]
            assert a.end_session()["final"] == oracle(700, 18)[-1]
    finally:
        server.stop_in_thread()


# -- the coalescer alone ---------------------------------------------------


def test_evicted_stream_is_skipped_and_its_frames_fail(svc, oracle):
    gw = svc.open_gateway(capacity=CAPACITY)
    scheduled = []
    steps = StepCoalescer(gw.pool, scheduled.append)
    done = {}
    for sid in ("a", "b"):
        gw.admit(sid)
    steps.submit("a", _series(800, 2), lambda e, exc: done.setdefault("a", (e, exc)))
    steps.submit("b", _series(801, 2), lambda e, exc: done.setdefault("b", (e, exc)))
    assert scheduled == [steps.flush]  # one flush for the pass, not one a frame
    gw.evict("a")
    scheduled.pop()()
    errors, exc = done["a"]
    assert isinstance(exc, UnknownStreamError) and len(errors) == 0
    errors, exc = done["b"]
    assert exc is None
    np.testing.assert_array_equal(errors, oracle(801, 2))
    assert steps.pending == 0
