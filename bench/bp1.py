"""The gateway's bp1 wire codec, loaded without the rest of the program.

``src/repro/gateway/wire.py`` is stdlib-only, but importing it as
``repro.gateway.wire`` runs the package's ``__init__``, which imports JAX.
The load generators must stay off JAX, so this module loads the codec
file on its own and adds the few socket helpers they and the harness
need.
"""
from __future__ import annotations

import importlib.util
import socket
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
_WIRE_PATH = ROOT / "src" / "repro" / "gateway" / "wire.py"


def _load_wire():
    name = "_bench_bp1_wire"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, _WIRE_PATH)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


wire = _load_wire()


def connect(host: str, port: int, timeout: float = 60.0) -> socket.socket:
    """Open a connection and negotiate bp1 (preamble, then the HELLO
    frame); returns the blocking socket."""
    sock = socket.create_connection((host, port), timeout=timeout)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    sock.sendall(wire.PREAMBLE)
    opcode, flags, _, meta, _ = read_frame(sock)
    if opcode != wire.OP_HELLO or flags & wire.FLAG_ERROR:
        sock.close()
        raise ConnectionError(f"bp1 negotiation failed: {meta}")
    return sock


def _read_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("server closed the connection")
        buf += chunk
    return bytes(buf)


def read_frame(sock: socket.socket):
    """Blocking read of one frame -> (opcode, flags, req_id, meta, data)."""
    opcode, flags, rid, plen = wire.unpack_header(
        _read_exact(sock, wire.HEADER_SIZE))
    payload = _read_exact(sock, plen) if plen else b""
    meta, data = wire.split_payload(payload)
    return opcode, flags, rid, meta, bytes(data)


def stats(host: str, port: int) -> dict:
    """One ``stats`` request on a fresh connection: the gateway's
    telemetry snapshot."""
    sock = connect(host, port)
    try:
        sock.sendall(wire.pack_frame(wire.OP_STATS, 1, meta={}))
        while True:
            opcode, flags, rid, meta, _ = read_frame(sock)
            if rid == 1:
                break
        if flags & wire.FLAG_ERROR or not meta.get("ok"):
            raise RuntimeError(f"stats failed: {meta}")
        return meta["stats"]
    finally:
        sock.close()
