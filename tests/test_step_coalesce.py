"""Cross-connection STEP coalescing: samples that arrive in one pass of the
server's event loop share one pool step, and every answer is bit-for-bit
what its stream sees stepped alone.

A test that needs frames from many connections to land in ONE pass holds
the server's loop (a blocking callback on its thread) while the clients
send, so the sockets are all readable when the loop resumes.

The chained cases make the device slower than the launch (a step's errors
reach the host ``DELAY`` seconds after they are read) or the launch
slower than the device, and let the pool's own timings choose the mode."""
import queue
import sys
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
from repro.gateway.pool import TIMING_WINDOW, StepCoalescer, UnknownStreamError
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


# -- chained steps -----------------------------------------------------------

DELAY = 0.05


class SlowErrors:
    """A pool step's errors that reach the host ``DELAY`` seconds after they
    are read (a device step longer than its launch); the read raises while
    ``fail[0]`` is positive."""

    def __init__(self, errors, fail):
        self._errors = errors
        self._fail = fail

    def copy_to_host_async(self):
        self._errors.copy_to_host_async()

    def __getitem__(self, i):
        return self._errors[i]

    def __array__(self, dtype=None, copy=None):
        time.sleep(DELAY)
        if self._fail[0]:
            self._fail[0] -= 1
            raise RuntimeError("injected device failure")
        return np.asarray(self._errors)


def slow_device(monkeypatch, gw, fail=None) -> None:
    """Make the pool's device slower than its launch, and step a scratch
    stream until the pool's own timings say so."""
    pool = gw.pool
    real = pool.errors
    fail = [0] if fail is None else fail
    monkeypatch.setattr(pool, "errors", lambda: SlowErrors(real(), fail))
    gw.admit("warm")
    for x in _series(999, TIMING_WINDOW + 2):
        gw.step({"warm": x})
    gw.evict("warm")
    assert pool.device_bound


class Owner:
    """The coalescer's owner thread, played by the test: flushes are
    scheduled and completions posted here, and the test runs them."""

    def __init__(self):
        self.scheduled: list = []
        self.posted: queue.SimpleQueue = queue.SimpleQueue()

    def post(self, fn, *args):
        self.posted.put((fn, args))

    def run(self, steps: StepCoalescer) -> None:
        """Run the scheduled flushes, then every completion, until no step
        is in flight."""
        while self.scheduled:
            self.scheduled.pop(0)()
        while steps._inflight is not None:
            fn, args = self.posted.get(timeout=10)
            fn(*args)


def recorder(done: dict, sid, events: list = None):
    def on_done(errors, exc):
        if events is not None:
            events.append(("answer", sid))
        done[sid] = (np.array(errors), exc)
    return on_done


def chained_count(gw) -> float:
    return gw.stats()["counters"].get("pool.steps_chained", 0)


def wait_until(predicate, timeout: float = 10.0) -> None:
    deadline = time.time() + timeout
    while not predicate():
        assert time.time() < deadline, "timed out"
        time.sleep(0.002)


def test_chained_step_is_launched_before_the_finished_steps_answers(
        svc, oracle, monkeypatch):
    gw = svc.open_gateway(capacity=CAPACITY)
    slow_device(monkeypatch, gw)
    owner = Owner()
    steps = StepCoalescer(gw.pool, owner.scheduled.append, post=owner.post)
    events, done = [], {}
    real_launch = gw.pool.launch

    def launch(inputs, chained=False):
        events.append(("launch", sorted(inputs), chained))
        return real_launch(inputs, chained)

    monkeypatch.setattr(gw.pool, "launch", launch)
    for sid in ("a", "b"):
        gw.admit(sid)
    chained0 = chained_count(gw)
    steps.submit("a", _series(810, 2), recorder(done, "a", events))
    steps.submit("b", _series(811, 1), recorder(done, "b", events))
    owner.scheduled.pop()()  # the pass's flush launches and returns
    assert events == [("launch", ["a", "b"], False)] and not done
    fn, args = owner.posted.get(timeout=10)
    fn(*args)
    # a's second sample is on its way before b's answer is written
    assert events[1:] == [("launch", ["a"], True), ("answer", "b")]
    owner.run(steps)
    assert events[3:] == [("answer", "a")]
    assert chained_count(gw) - chained0 == 1
    for sid, stream, n in (("a", 810, 2), ("b", 811, 1)):
        errors, exc = done[sid]
        assert exc is None
        np.testing.assert_array_equal(errors, oracle(stream, n))
    waiter = steps._waiter
    steps.close()
    assert steps._waiter is None and not waiter.is_alive()


def test_chained_steps_through_the_server_match_solo(svc, oracle, monkeypatch):
    """The equivalence case with the device slower than the launch, and
    the interpreter switching threads every 10 us: steps chain, and every
    running error is the stream's own, stepped alone."""
    gw = svc.open_gateway(capacity=CAPACITY, max_batch=4, max_wait_ms=10.0)
    slow_device(monkeypatch, gw)
    server = GatewayServer(gw, port=0, pump_interval_ms=2.0)
    host, port = server.start_in_thread()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    rounds = 2
    lengths = [rounds * sum(sizes) for _, sizes in PLANS]
    streams = [150 + i for i in range(len(PLANS))]
    clients = [GatewayClient(host, port, protocol=proto) for proto, _ in PLANS]
    got = [[] for _ in PLANS]
    try:
        chained0 = chained_count(gw)
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
        finals = [c.end_session()["final"] for c in clients]
        assert chained_count(gw) - chained0 >= rounds
    finally:
        sys.setswitchinterval(interval)
        for c in clients:
            c.close()
        server.stop_in_thread()
    for i, stream in enumerate(streams):
        want = oracle(stream, lengths[i])
        np.testing.assert_array_equal(np.array(got[i], np.float32), want)
        assert finals[i] == want[-1]


def test_chained_durable_frames_answer_in_step_order(svc, oracle, tmp_path,
                                                    monkeypatch):
    """One stream's frames (t = 1, 3 and 2) through chained steps: each
    answers in order, its seq that of its last sample."""
    gw = svc.open_gateway(capacity=CAPACITY)
    dur = enable_durability(gw, tmp_path / "store", snapshot_interval_ms=60_000)
    slow_device(monkeypatch, gw)
    server = GatewayServer(gw, port=0, pump_interval_ms=2.0)
    host, port = server.start_in_thread()
    sizes = [1, 3, 3, 3, 3, 3, 2]  # 18 samples: the token refresh is at 16
    try:
        chained0 = chained_count(gw)
        with GatewayClient(host, port) as a, GatewayClient(host, port) as b:
            with held(server):
                data = _series(710, 18)
                rids, t = [], 0
                for k in sizes:
                    rids.append(send_step(a, data[t:t + k]))
                    t += k
                b_rid = send_step(b, _series(711, 3))
            answers = [a.collect(rid) for rid in rids]
            assert b.collect(b_rid)["seq"] == 3
            got = [e for r in answers for e in running_errors(r)]
            np.testing.assert_array_equal(np.array(got, np.float32), oracle(710, 18))
            assert [r["seq"] for r in answers] == list(np.cumsum(sizes))
            claims = [dur.store.signer.verify(r["token"]).seq for r in answers]
            assert claims == [0, 0, 0, 0, 0, 16, 16]
        assert chained_count(gw) - chained0 >= 15
    finally:
        server.stop_in_thread()


@pytest.mark.parametrize("op", ["close", "stats", "recalibrate", "drain",
                                "teardown"])
def test_sync_callers_wait_for_the_step_in_flight(svc, oracle, tmp_path,
                                                  monkeypatch, op):
    """A t = 3 frame is in flight when the op arrives: the op waits for
    it, and its samples are in what the op sees."""
    gw = svc.open_gateway(capacity=CAPACITY)
    if op == "teardown":
        enable_durability(gw, tmp_path / "store", snapshot_interval_ms=60_000)
    threshold = gw.threshold
    slow_device(monkeypatch, gw)
    server = GatewayServer(gw, port=0, pump_interval_ms=2.0)
    host, port = server.start_in_thread()
    data, want = _series(900, 4), oracle(900, 4)
    client = GatewayClient(host, port)
    try:
        first = client.step(data[0])
        rows0 = gw.stats()["counters"]["pool.stream_steps"]
        rid = send_step(client, data[1:4])
        wait_until(lambda: server.steps._inflight is not None)
        if op == "close":
            assert client.request("close")["final"] == want[-1]
        elif op == "stats":
            rows = client.stats()["counters"]["pool.stream_steps"]
            assert rows - rows0 == 3
        elif op == "recalibrate":
            client.recalibrate(0.0)  # the frame answers under the old value
        elif op == "drain":
            server.stop_in_thread()
        if op == "teardown":
            client.close()  # parked with every sample it sent
            wait_until(lambda: gw.pool.active == 0)
            with GatewayClient(host, port) as again:
                out = again.resume(first["token"], replay=[])
            assert out["seq"] == 4 and out["running_error"] == want[-1]
        else:
            answer = client.collect(rid)
            np.testing.assert_array_equal(
                np.array(running_errors(answer), np.float32), want[1:4])
            assert ("alert" in answer) is (threshold is not None)
    finally:
        client.close()
        server.stop_in_thread()
        gw.recalibrate(threshold=threshold)


@pytest.mark.parametrize("where", ["launch", "readback"])
def test_engine_failure_in_a_chained_step_answers_its_frames(
        svc, oracle, monkeypatch, where):
    """A chained launch that raises fails the frame it carried (its stepped
    samples answered with the error); a step whose errors fail to come
    back fails every frame in it.  Either way the streams step on."""
    gw = svc.open_gateway(capacity=CAPACITY)
    fail_read, fail_launch = [0], [0]
    slow_device(monkeypatch, gw, fail_read)
    real = gw.pool._pool_step

    def broken(*args):
        if fail_launch[0]:
            fail_launch[0] -= 1
            raise RuntimeError("injected device failure")
        return real(*args)

    monkeypatch.setattr(gw.pool, "_pool_step", broken)
    owner = Owner()
    steps = StepCoalescer(gw.pool, owner.scheduled.append, post=owner.post)
    done = {}
    for sid in ("a", "b"):
        gw.admit(sid)
    a, b = _series(820, 3), _series(821, 2)
    steps.submit("a", a[:2], recorder(done, "a"))
    steps.submit("b", b[:1], recorder(done, "b"))
    if where == "readback":
        fail_read[0] = 1
    owner.scheduled.pop()()
    fail_launch[0] = where == "launch"  # a's second sample, chained
    owner.run(steps)
    errors, exc = done["a"]
    assert "injected device failure" in str(exc)
    if where == "launch":
        np.testing.assert_array_equal(errors, oracle(820, 1))
        np.testing.assert_array_equal(done["b"][0], oracle(821, 1))
        assert done["b"][1] is None
        a_next = 1  # a's second sample was dropped
    else:
        assert len(errors) == 0 and "injected" in str(done["b"][1])
        a_next = 1  # the device stepped a's first sample
    done.clear()
    steps.submit("a", a[a_next:], recorder(done, "a"))
    steps.submit("b", b[1:], recorder(done, "b"))
    owner.run(steps)
    np.testing.assert_array_equal(done["a"][0], oracle(820, 3)[a_next:])
    np.testing.assert_array_equal(done["b"][0], oracle(821, 2)[1:])
    assert done["a"][1] is None and done["b"][1] is None
    steps.close()


def test_stream_evicted_while_its_step_is_in_flight_is_skipped(
        svc, oracle, monkeypatch):
    gw = svc.open_gateway(capacity=CAPACITY)
    slow_device(monkeypatch, gw)
    owner = Owner()
    steps = StepCoalescer(gw.pool, owner.scheduled.append, post=owner.post)
    done = {}
    for sid in ("a", "b"):
        gw.admit(sid)
    steps.submit("a", _series(830, 2), recorder(done, "a"))
    steps.submit("b", _series(831, 2), recorder(done, "b"))
    owner.scheduled.pop()()
    assert steps._inflight is not None
    gw.evict("a")
    owner.run(steps)
    errors, exc = done["a"]
    assert isinstance(exc, UnknownStreamError) and len(errors) == 0
    errors, exc = done["b"]
    assert exc is None
    np.testing.assert_array_equal(errors, oracle(831, 2))
    assert steps.pending == 0
    steps.close()


def test_short_wait_keeps_the_synchronous_path(svc, oracle, monkeypatch):
    """A launch slower than the device: the flush steps synchronously, no
    step is chained and no waiter thread starts."""
    gw = svc.open_gateway(capacity=CAPACITY)
    real = gw.pool._pool_step

    def slow_launch(*args):
        time.sleep(DELAY)
        return real(*args)

    monkeypatch.setattr(gw.pool, "_pool_step", slow_launch)
    gw.admit("warm")
    for x in _series(999, TIMING_WINDOW + 2):
        gw.step({"warm": x})
    gw.evict("warm")
    assert not gw.pool.device_bound
    owner = Owner()
    steps = StepCoalescer(gw.pool, owner.scheduled.append, post=owner.post)
    done = {}
    for sid in ("a", "b"):
        gw.admit(sid)
    chained0 = chained_count(gw)
    steps.submit("a", _series(840, 3), recorder(done, "a"))
    steps.submit("b", _series(841, 1), recorder(done, "b"))
    owner.scheduled.pop()()
    assert steps._inflight is None and steps._waiter is None
    assert owner.posted.empty() and steps.pending == 0
    assert chained_count(gw) == chained0
    np.testing.assert_array_equal(done["a"][0], oracle(840, 3))
    np.testing.assert_array_equal(done["b"][0], oracle(841, 1))


def test_cadence_snapshots_land_the_step_in_flight(svc, oracle, tmp_path,
                                                   monkeypatch):
    """A snapshot the pump takes while steps chain is taken with no step in
    flight, so the state it stores and each session's seq agree."""
    gw = svc.open_gateway(capacity=CAPACITY)
    dur = enable_durability(gw, tmp_path / "store", snapshot_interval_ms=150)
    slow_device(monkeypatch, gw)
    seen = []
    real = dur.snapshot_now

    def snapshot_now(**kw):
        seen.append(server.steps._inflight is None)
        return real(**kw)

    monkeypatch.setattr(dur, "snapshot_now", snapshot_now)
    server = GatewayServer(gw, port=0, pump_interval_ms=2.0)
    host, port = server.start_in_thread()
    data = _series(850, 12)
    try:
        chained0 = chained_count(gw)
        with GatewayClient(host, port) as client:
            got = [e for t in range(0, 12, 3) for e in client.step_many(data[t:t + 3])]
            sid = server.steps.pool.resident[0]
            wait_until(lambda: len(seen) >= 2)
            rec = dur.store.lookup(sid)
            assert rec.steps == rec.seq
        assert seen and all(seen)
        assert chained_count(gw) > chained0
    finally:
        server.stop_in_thread()
    np.testing.assert_array_equal(np.array(got, np.float32), oracle(850, 12))
