"""Protocol-conformance gate for the bp1 wire format.

The golden frame corpus under ``tests/fixtures/wire/`` is the committed,
byte-exact definition of what every opcode's frames look like on the
wire — one file per opcode × edge case (empty batch, pipelined
multi-window frame, max-size payload, each typed error).  This script:

* rebuilds every corpus case with the live codec
  (:mod:`repro.gateway.wire`) and fails on ANY byte difference against
  the committed files — an unacknowledged wire-format change cannot pass
  CI;
* decodes every committed file back and asserts the round-trip
  (header fields, meta dict, raw data) matches the case spec exactly;
* fails on corpus files that no case claims (stale fixtures) and cases
  with no committed file.

Changing the wire format on purpose follows the same committed-baseline
workflow as ``analysis/baseline.json``:

    PYTHONPATH=src python scripts/wire_conformance.py \
        --update --reason "bp1: added <field> because <why>"

which rewrites the corpus and records the reason in
``tests/fixtures/wire/MANIFEST.json`` — the reason string is the audit
trail reviewers read.  Stdlib-only (struct + json; no numpy/jax), so the
CI ``lint`` job runs this in seconds before any dependency install.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import struct
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))


def _load_wire():
    """Import the codec without dragging in the full gateway package
    (whose ``__init__`` needs numpy) — the CI lint job runs this script
    on a bare interpreter, so fall back to loading wire.py by path."""
    try:
        from repro.gateway import wire
        return wire
    except ImportError:
        import importlib.util

        path = (Path(__file__).resolve().parent.parent
                / "src" / "repro" / "gateway" / "wire.py")
        spec = importlib.util.spec_from_file_location("repro_gateway_wire", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


wire = _load_wire()

CORPUS_DIR = Path(__file__).resolve().parent.parent / "tests" / "fixtures" / "wire"
MANIFEST = "MANIFEST.json"


def f32s(n: int, salt: int = 0) -> bytes:
    """n deterministic float32 values, every one an exact k/128 fraction
    so the byte pattern is stable across platforms and numpy versions."""
    return b"".join(
        struct.pack("<f", (((i * 37 + salt) % 256) - 128) / 128.0)
        for i in range(n)
    )


RESP = wire.FLAG_RESPONSE
ERR = wire.FLAG_RESPONSE | wire.FLAG_ERROR

#: name -> (opcode, flags, req_id, meta, data).  Names are the corpus
#: file stems; keep them sorted roughly by opcode, requests then
#: responses then typed errors.
CASES: dict[str, tuple] = {
    # hello: the server's greeting after preamble negotiation
    "hello_resp": (wire.OP_HELLO, RESP, wire.NO_REQUEST_ID,
                   {"ok": True, "op": "hello", "protocol": "bp1",
                    "version": 1, "max_frame_bytes": 16 << 20,
                    "features": 32}, b""),
    # ping round-trip
    "ping_req": (wire.OP_PING, 0, 1, None, b""),
    "ping_resp": (wire.OP_PING, RESP, 1, {"ok": True, "op": "ping"}, b""),
    # score: single window, pipelined multi-window, empty batch,
    # priority/tenant admission fields, max-size payload (capped
    # representative: 32 KiB of raw float32 — the format is
    # length-prefixed, so size only moves the header's payload_len)
    "score_req_single": (wire.OP_SCORE, 0, 2,
                         {"n": 1, "t": 16, "f": 32}, f32s(16 * 32)),
    "score_req_pipelined": (wire.OP_SCORE, 0, 3,
                            {"n": 4, "t": 8, "f": 32}, f32s(4 * 8 * 32, 1)),
    "score_req_empty_batch": (wire.OP_SCORE, 0, 4,
                              {"n": 0, "t": 8, "f": 32}, b""),
    "score_req_priority": (wire.OP_SCORE, 0, 5,
                           {"n": 1, "t": 8, "f": 32,
                            "priority": 2, "tenant": "acme"},
                           f32s(8 * 32, 2)),
    "score_req_max_payload": (wire.OP_SCORE, 0, 6,
                              {"n": 8, "t": 32, "f": 32},
                              f32s(8 * 32 * 32, 3)),
    "score_resp_single": (wire.OP_SCORE, RESP, 2,
                          {"ok": True, "op": "score", "n": 1}, f32s(1, 4)),
    "score_resp_pipelined_alert": (wire.OP_SCORE, RESP, 3,
                                   {"ok": True, "op": "score", "n": 4,
                                    "alert": [True, False, True, False]},
                                   f32s(4, 5)),
    "score_resp_empty_batch": (wire.OP_SCORE, RESP, 4,
                               {"ok": True, "op": "score", "n": 0}, b""),
    # step: single sample, pipelined samples, durable response (seq+token)
    "step_req_single": (wire.OP_STEP, 0, 7, {"t": 1}, f32s(32, 6)),
    "step_req_pipelined": (wire.OP_STEP, 0, 8, {"t": 16}, f32s(16 * 32, 7)),
    # a token stream's STEP: one int32 token id, "dtype": "i4"
    "step_req_tokens": (wire.OP_STEP, 0, 14, {"t": 1, "dtype": "i4"},
                        struct.pack("<i", 4097)),
    "step_resp_durable": (wire.OP_STEP, RESP, 7,
                          {"ok": True, "op": "step", "t": 1,
                           "running_error": 0.25, "seq": 41,
                           "token": "rt1.2hGVsAmVkY2FmZQ"}, f32s(1, 8)),
    # control ops (generic meta frames)
    "close_req": (wire.OP_CLOSE, 0, 9, None, b""),
    "close_resp": (wire.OP_CLOSE, RESP, 9,
                   {"ok": True, "op": "close", "final": 0.125}, b""),
    "resume_req": (wire.OP_RESUME, 0, 10, {"token": "rt1.2hGVsAmVkY2FmZQ"}, b""),
    "recalibrate_req": (wire.OP_RECALIBRATE, 0, 11, {"threshold": 0.5}, b""),
    "stats_req": (wire.OP_STATS, 0, 12, None, b""),
    "snapshot_req": (wire.OP_SNAPSHOT, 0, 13, None, b""),
    # typed errors: each class the server answers over the wire
    "error_overloaded": (wire.OP_SCORE, ERR, 20,
                         {"ok": False, "op": "score",
                          "error": "GatewayOverloadedError",
                          "message": "queue full (1024 pending); pump() or shed load"},
                         b""),
    "error_pool_full": (wire.OP_STEP, ERR, 21,
                        {"ok": False, "op": "step", "error": "PoolFullError",
                         "message": "session pool full"}, b""),
    "error_oversized_window": (wire.OP_SCORE, ERR, 22,
                               {"ok": False, "op": "score",
                                "error": "ValueError",
                                "message": "window length 2048 exceeds max_seq_len=1024"},
                               b""),
    "error_shed": (wire.OP_SCORE, ERR, 23,
                   {"ok": False, "op": "score",
                    "error": "GatewayOverloadedError",
                    "message": "priority 2 shed under load"}, b""),
    "error_tampered_token": (wire.OP_RESUME, ERR, 24,
                             {"ok": False, "op": "resume",
                              "error": "TamperedTokenError",
                              "message": "resumption token signature mismatch"},
                             b""),
    "error_expired_token": (wire.OP_RESUME, ERR, 25,
                            {"ok": False, "op": "resume",
                             "error": "ExpiredTokenError",
                             "message": "token older than every retained snapshot"},
                            b""),
    "error_token_out_of_range": (wire.OP_STEP, ERR, 27,
                                 {"ok": False, "op": "step", "error": "ValueError",
                                  "message": "token id 65536 is outside the "
                                             "vocabulary [0, 65536)"}, b""),
    "error_unknown_op": (0x7F, ERR, 26,
                         {"ok": False, "op": "?", "error": "ValueError",
                          "message": "unknown opcode 0x7f"}, b""),
    "error_framing": (0x00, ERR, wire.NO_REQUEST_ID,
                      {"ok": False, "op": "?", "error": "WireProtocolError",
                       "message": "bad magic b'zz'"}, b""),
}


def build(name: str) -> bytes:
    opcode, flags, rid, meta, data = CASES[name]
    return wire.pack_frame(opcode, rid, meta=meta, data=data, flags=flags)


def roundtrip(name: str, blob: bytes) -> list:
    """Decode ``blob`` and compare every field against the case spec;
    returns a list of problems (empty when conformant)."""
    opcode, flags, rid, meta, data = CASES[name]
    problems = []
    try:
        got_op, got_flags, got_rid, payload_len = wire.unpack_header(blob)
        payload = blob[wire.HEADER_SIZE:]
        if payload_len != len(payload):
            problems.append(f"{name}: header says {payload_len} payload "
                            f"bytes, file carries {len(payload)}")
        got_meta, got_data = wire.split_payload(payload)
    except wire.WireProtocolError as exc:
        return [f"{name}: does not decode: {exc}"]
    if (got_op, got_flags, got_rid) != (opcode, flags, rid):
        problems.append(
            f"{name}: header (op=0x{got_op:02x}, flags={got_flags}, "
            f"id={got_rid}) != spec (op=0x{opcode:02x}, flags={flags}, id={rid})"
        )
    if got_meta != (meta or {}):
        problems.append(f"{name}: meta {got_meta} != spec {meta or {}}")
    if bytes(got_data) != data:
        problems.append(f"{name}: data differs from spec "
                        f"({len(got_data)} vs {len(data)} bytes)")
    return problems


def check(corpus_dir: Path) -> int:
    problems: list = []
    if not corpus_dir.is_dir():
        print(f"wire-conformance: corpus dir {corpus_dir} missing — "
              f"run with --update --reason '...' to create it")
        return 1
    on_disk = {p.name for p in corpus_dir.iterdir() if p.suffix == ".bin"}
    for name in sorted(CASES):
        path = corpus_dir / f"{name}.bin"
        if not path.is_file():
            problems.append(f"{name}: corpus file missing ({path.name})")
            continue
        committed = path.read_bytes()
        rebuilt = build(name)
        if committed != rebuilt:
            i = next((k for k, (a, b) in enumerate(zip(committed, rebuilt))
                      if a != b), min(len(committed), len(rebuilt)))
            problems.append(
                f"{name}: byte mismatch at offset {i} "
                f"(committed {len(committed)}B, live codec {len(rebuilt)}B) — "
                f"the wire format changed; if intentional, re-run with "
                f"--update --reason '...'"
            )
        problems.extend(roundtrip(name, committed))
    stale = on_disk - {f"{n}.bin" for n in CASES}
    for extra in sorted(stale):
        problems.append(f"{extra}: on disk but no conformance case claims it")
    if problems:
        print(f"wire-conformance: {len(problems)} problem(s)")
        for p in problems:
            print(f"  FAIL {p}")
        return 1
    print(f"wire-conformance: {len(CASES)} frames byte-exact "
          f"(corpus {corpus_dir})")
    return 0


def update(corpus_dir: Path, reason: str) -> int:
    corpus_dir.mkdir(parents=True, exist_ok=True)
    for p in corpus_dir.iterdir():
        if p.suffix == ".bin":
            p.unlink()
    manifest: dict = {"format": "bp1", "version": wire.VERSION,
                      "reason": reason, "cases": {}}
    for name in sorted(CASES):
        blob = build(name)
        (corpus_dir / f"{name}.bin").write_bytes(blob)
        manifest["cases"][name] = {
            "bytes": len(blob),
            "sha256": hashlib.sha256(blob).hexdigest(),
        }
    (corpus_dir / MANIFEST).write_text(
        json.dumps(manifest, indent=2, sort_keys=True) + "\n"
    )
    print(f"wire-conformance: wrote {len(CASES)} frames to {corpus_dir}")
    print(f"  reason: {reason}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", type=Path, default=CORPUS_DIR,
                    help=f"corpus directory (default {CORPUS_DIR})")
    ap.add_argument("--update", action="store_true",
                    help="rewrite the corpus from the live codec "
                         "(requires --reason)")
    ap.add_argument("--reason", default="",
                    help="why the wire format changed (recorded in the "
                         "manifest; required with --update)")
    args = ap.parse_args(argv)
    if args.update:
        if not args.reason.strip():
            ap.error("--update requires --reason '<why the format changed>'")
        return update(args.dir, args.reason.strip())
    return check(args.dir)


if __name__ == "__main__":
    raise SystemExit(main())
