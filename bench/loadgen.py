"""The load generator: one process that sends a mix's bp1 frames over raw
non-blocking sockets, as ``bench/traffic.py`` plans them.

    python bench/loadgen.py <spec.json>

The spec (written by ``bench/run.py``) holds the mix, the seed, the chips,
the model's configuration and its family adapter's file
(``bench/families/``, which makes the frames' inputs), the server's
address, the file the answers go to, and which part of the mix's
connections this process drives (``part`` of ``parts``, a contiguous
slice; the whole mix where the spec names none).  The process never
imports JAX.  It talks to its parent over stdin and stdout:

1. it opens its connections; each ``step`` connection sends its stream's
   first frame, which admits the stream to the session pool, and waits
   for the answer; a stream refused as ``PoolFullError`` (a front whose
   kernel placed the connection on a full worker) reconnects and sends
   that frame again.  Then it prints ``READY``;
2. it reads ``GO <t0> <t1>`` (``time.monotonic`` instants, a clock shared
   by every process of the host), waits for ``t0`` and sends until
   ``t1``: a closed-loop connection keeps its frames in flight, an
   open-loop one sends on its own schedule, and a frame's send time is
   the instant it was due;
3. it sends nothing after ``t1``, waits for the answers still due (at
   most ``drain_s``), writes every answered value to the spec's ``out``
   file (``.npz``) and prints ``DONE <json summary>``.

Each answered value is recorded with its op (``traffic.OPS``), its keys
(a ``step`` value: the stream and the sample's index in it; a ``score``
value: the window's id and 0), the frame's send and receive instants, the
phase (0 warm-up, 1 window) and whether it is the frame's first value.
"""
from __future__ import annotations

import heapq
import json
import selectors
import socket
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import families  # noqa: E402
import traffic  # noqa: E402
from bp1 import connect, wire  # noqa: E402


#: connections a stream may try before its admission counts as failed
READMIT_TRIES = 64


class _Conn:
    __slots__ = ("sock", "plan", "rbuf", "wbuf", "inflight", "sent")

    def __init__(self, sock: socket.socket, plan: dict):
        self.sock = sock
        self.plan = plan
        self.rbuf = bytearray()
        self.wbuf = bytearray()
        self.inflight: dict = {}    # req_id -> (t_send, keys)
        self.sent = 0               # frames sent on this connection


class LoadGen:
    def __init__(self, spec: dict):
        self.spec = spec
        self.seed = int(spec["seed"])
        self.config = spec["config"]
        self.family = families.load_file(spec["family_file"])
        self.drain_s = float(spec.get("drain_s", 60.0))
        plan = traffic.connections(spec["mix"], int(spec["chips"]))
        parts, part = int(spec.get("parts", 1)), int(spec.get("part", 0))
        self.plan = plan[part * len(plan) // parts:(part + 1) * len(plan) // parts]
        self.lengths = traffic.group_lengths(spec["mix"], self.seed)
        self.sel = selectors.DefaultSelector()
        self.conns: list[_Conn] = []
        self.due: list = []         # heap of (instant, connection index)
        self.rid = 0
        self.records: list = []     # see the module docstring
        self.errors = 0
        self.error_msgs: list = []
        self.sending = False
        self.phase = 0
        self.window_sent = 0
        self._chunks: dict = {}     # stream -> (chunk index, samples)
        self._payloads: dict = {}   # window id -> SCORE payload
        self._refused: list = []    # connections refused admission

    # -- request bodies ------------------------------------------------------

    def _samples(self, plan: dict, t: int, k: int) -> np.ndarray:
        """Samples ``t .. t + k - 1`` of the connection's stream."""
        stream, out = plan["stream"], []
        for j in range(t, t + k):
            c, off = divmod(j, self.family.CHUNK)
            got = self._chunks.get(stream)
            if got is None or got[0] != c:
                got = (c, self.family.stream_chunk(self.seed, stream, c, self.config,
                                                   plan["anomaly_rate"]))
                self._chunks[stream] = got
            out.append(got[1][off])
        return np.stack(out)

    def _window_payload(self, plan: dict, w: int) -> bytes:
        wid = traffic.window_id(plan["group"], w)
        payload = self._payloads.get(wid)
        if payload is None:
            length = int(self.lengths[plan["group"]][w])
            x = self.family.window(self.seed, wid, length, self.config,
                                   plan["anomaly_rate"])
            meta, data = self.family.score_frame(x)
            payload = wire.pack_payload(dict(plan["meta"], **meta), data)
            self._payloads[wid] = payload
        return payload

    def _next_request(self, conn: _Conn):
        """-> (opcode, payload, keys) of the connection's next frame."""
        plan = conn.plan
        if plan["op"] == "step":
            k = plan["k"]
            t = conn.sent * k
            meta, data = self.family.step_frame(self._samples(plan, t, k))
            payload = wire.pack_payload(dict(plan["meta"], **meta), data)
            return wire.OP_STEP, payload, [(plan["stream"], t + j) for j in range(k)]
        n_windows = len(self.lengths[plan["group"]])
        w = (plan["index"] + plan["conns"] * conn.sent) % n_windows
        return (wire.OP_SCORE, self._window_payload(plan, w),
                [(traffic.window_id(plan["group"], w), 0)])

    # -- socket plumbing -----------------------------------------------------

    def _send_next(self, conn: _Conn, t_send: float) -> None:
        opcode, payload, keys = self._next_request(conn)
        self.rid += 1
        conn.wbuf += wire.pack_header(opcode, 0, self.rid, len(payload))
        conn.wbuf += payload
        conn.inflight[self.rid] = (t_send, keys)
        conn.sent += 1
        self.window_sent += self.phase
        self._flush(conn)

    def _flush(self, conn: _Conn) -> None:
        if conn.wbuf:
            try:
                n = conn.sock.send(conn.wbuf)
            except BlockingIOError:
                n = 0
            del conn.wbuf[:n]
        want = selectors.EVENT_READ | (selectors.EVENT_WRITE if conn.wbuf else 0)
        self.sel.modify(conn.sock, want, conn)

    def _on_readable(self, conn: _Conn) -> None:
        try:
            chunk = conn.sock.recv(1 << 18)
        except BlockingIOError:
            return
        if not chunk:
            raise ConnectionError(f"server closed connection {conn.plan}")
        conn.rbuf += chunk
        now = time.monotonic()
        op = traffic.OPS[conn.plan["op"]]
        while len(conn.rbuf) >= wire.HEADER_SIZE:
            _, flags, rid, plen = wire.unpack_header(conn.rbuf)
            end = wire.HEADER_SIZE + plen
            if len(conn.rbuf) < end:
                break
            meta, data = wire.split_payload(bytes(conn.rbuf[wire.HEADER_SIZE:end]))
            del conn.rbuf[:end]
            sent = conn.inflight.pop(rid, None)
            if sent is None:
                continue  # a connection-level notice, not an answer
            t_send, keys = sent
            values = np.frombuffer(data, "<f4")
            if (not self.phase and meta.get("error") == "PoolFullError"
                    and conn.plan["op"] == "step"):
                self._refused.append(conn)
            elif (flags & wire.FLAG_ERROR or not meta.get("ok", False)
                    or len(values) != len(keys)):
                self.errors += 1
                if len(self.error_msgs) < 5:
                    self.error_msgs.append(meta)
            else:
                for j, ((key0, key1), value) in enumerate(zip(keys, values)):
                    self.records.append((op, key0, key1, t_send, now, float(value),
                                         self.phase, j == 0))
            if self.sending and conn.plan["send"]["loop"] == "closed":
                self._send_next(conn, time.monotonic())

    def _pending(self) -> int:
        return sum(len(c.inflight) for c in self.conns)

    def _send_due(self, t0: float, t1: float) -> None:
        """Send every open-loop frame that is due, at its due instant."""
        now = time.monotonic()
        while self.due and self.due[0][0] <= now:
            at, i = heapq.heappop(self.due)
            conn = self.conns[i]
            self._send_next(conn, at)
            nxt = at + traffic.period_s(conn.plan["send"], at - t0)
            if nxt < t1 - 1e-6:  # a period summed up to the close is not in it
                heapq.heappush(self.due, (nxt, i))

    def _pump(self, until: float, t0: float = 0.0, t1: float = 0.0) -> None:
        """Serve socket events and due frames until ``until``, or until
        nothing is in flight once sending has stopped."""
        while True:
            now = time.monotonic()
            if now >= until or (not self.sending and not self._pending()):
                return
            wait = min(0.05, until - now)
            if self.sending and self.due:
                wait = max(0.0, min(wait, self.due[0][0] - now))
            for key, mask in self.sel.select(wait):
                conn = key.data
                if mask & selectors.EVENT_WRITE:
                    self._flush(conn)
                if mask & selectors.EVENT_READ:
                    self._on_readable(conn)
            if self.sending:
                self._send_due(t0, t1)

    # -- phases --------------------------------------------------------------

    def _connect(self) -> socket.socket:
        sock = connect(self.spec["host"], int(self.spec["port"]))
        sock.setblocking(False)
        return sock

    def open(self) -> None:
        for plan in self.plan:
            conn = _Conn(self._connect(), plan)
            self.conns.append(conn)
            self.sel.register(conn.sock, selectors.EVENT_READ, conn)
        now = time.monotonic()
        for conn in self.conns:
            if conn.plan["op"] == "step":
                self._send_next(conn, now)   # admits the stream to the pool
            else:
                n_windows = len(self.lengths[conn.plan["group"]])
                for w in range(conn.plan["index"], n_windows, conn.plan["conns"]):
                    self._window_payload(conn.plan, w)
        deadline = time.monotonic() + 600.0
        self._pump(deadline)
        for _ in range(READMIT_TRIES):
            if not self._refused:
                break
            refused, self._refused = self._refused, []
            for conn in refused:
                # a new connection is placed anew by the front's kernel
                self.sel.unregister(conn.sock)
                conn.sock.close()
                conn.sock = self._connect()
                conn.rbuf.clear()
                conn.wbuf.clear()
                conn.sent = 0
                self.sel.register(conn.sock, selectors.EVENT_READ, conn)
                self._send_next(conn, time.monotonic())
            self._pump(deadline)
        if self._refused:
            raise RuntimeError(f"{len(self._refused)} streams found no free "
                               f"slot in {READMIT_TRIES} connections")
        if self._pending():
            raise RuntimeError("warm-up answers did not arrive")

    def run(self, t0: float, t1: float) -> dict:
        self.phase = 1
        while time.monotonic() < t0:
            time.sleep(min(0.01, max(0.0, t0 - time.monotonic())))
        cpu0, wall0 = time.process_time(), time.monotonic()
        self.sending = True
        for i, conn in enumerate(self.conns):
            send = conn.plan["send"]
            if send["loop"] == "closed":
                for _ in range(int(send["in_flight"])):
                    self._send_next(conn, time.monotonic())
            elif t0 + conn.plan["offset_s"] < t1:
                heapq.heappush(self.due, (t0 + conn.plan["offset_s"], i))
        self._send_due(t0, t1)
        self._pump(t1, t0, t1)
        self.sending = False
        cpu1, wall1 = time.process_time(), time.monotonic()
        self._pump(t1 + self.drain_s)
        return {"cpu_busy_pct": 100.0 * (cpu1 - cpu0) / max(wall1 - wall0, 1e-9),
                "sent": self.window_sent, "unanswered": self._pending(),
                "errors": self.errors, "error_samples": self.error_msgs}

    def close(self) -> None:
        for conn in self.conns:
            try:
                self.sel.unregister(conn.sock)
            except (KeyError, ValueError):
                pass
            conn.sock.close()
        self.sel.close()

    def save(self, path: str) -> None:
        rec = np.array(self.records, dtype=np.float64).reshape(-1, 8)
        np.savez(path, op=rec[:, 0].astype(np.int8),
                 key0=rec[:, 1].astype(np.int64), key1=rec[:, 2].astype(np.int64),
                 t_send=rec[:, 3], t_recv=rec[:, 4], value=rec[:, 5],
                 phase=rec[:, 6].astype(np.int8), first=rec[:, 7].astype(bool))


def main(argv: list) -> int:
    spec = json.loads(Path(argv[0]).read_text())
    gen = LoadGen(spec)
    try:
        gen.open()
        print("READY", flush=True)
        line = sys.stdin.readline().split()
        if not line or line[0] != "GO":
            return 1
        summary = gen.run(float(line[1]), float(line[2]))
        gen.save(spec["out"])
    finally:
        gen.close()
    print("DONE " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
