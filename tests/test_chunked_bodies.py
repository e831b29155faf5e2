"""Chunked Transfer-Encoding request bodies at the front door:
byte-exact round-trips for plain-SigV4 and streaming-SigV4 (aws-chunked
inside chunked TE) object PUTs, keep-alive reuse after a chunked PUT,
broken chunk-signature chains, torn mid-chunk aborts (admission-slot
release proven), the smuggling rejects (CL+TE, non-chunked TE,
HTTP/1.0), and the buffered-path cap."""

import os
import socket
import time

import pytest

from minio_tpu.erasure.engine import ErasureObjects
from minio_tpu.s3 import sigv4
from minio_tpu.s3.client import S3Client
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.xl import XLStorage

ACCESS, SECRET = "chunkak1", "chunk-secret-1"

@pytest.fixture
def door(tmp_path):
    """(srv, port, client) on a started server, bucket ready."""
    disks = [XLStorage(str(tmp_path / f"disk{i}")) for i in range(4)]
    layer = ErasureObjects(disks, 2, 2, block_size=256 * 1024)
    srv = S3Server(layer, ACCESS, SECRET)
    port = srv.start()
    cl = S3Client("127.0.0.1", port, ACCESS, SECRET)
    assert cl.make_bucket("bkt").status == 200
    yield srv, port, cl
    srv.stop()


def _read_response(f) -> tuple[int, dict, bytes]:
    status_line = f.readline().decode()
    if not status_line:
        return 0, {}, b""
    status = int(status_line.split(" ", 2)[1])
    headers = {}
    while True:
        line = f.readline().decode()
        if line in ("\r\n", "\n", ""):
            break
        k, _, v = line.partition(":")
        headers[k.strip().lower()] = v.strip()
    body = f.read(int(headers.get("content-length", 0) or 0))
    return status, headers, body


def _chunk_wire(payload: bytes, chunk: int = 7000,
                trailer: bytes = b"") -> bytes:
    """Encode payload as chunked TE frames (sizes with no relation to
    any aws-chunk boundary — the decoder must not care)."""
    out = bytearray()
    for i in range(0, len(payload), chunk):
        piece = payload[i:i + chunk]
        out += f"{len(piece):x}\r\n".encode() + piece + b"\r\n"
    out += b"0\r\n" + trailer + b"\r\n"
    return bytes(out)


def _head_bytes(method: str, path: str, hdrs: dict,
                version: str = "HTTP/1.1") -> bytes:
    head = [f"{method} {path} {version}\r\n"]
    head.extend(f"{k}: {v}\r\n" for k, v in hdrs.items())
    head.append("\r\n")
    return "".join(head).encode()


def _signed_chunked_head(path: str, payload: bytes, port: int) -> bytes:
    """Plain-SigV4 chunked PUT head: sign with the REAL payload (the
    signer stamps x-amz-content-sha256 from its body argument), then
    ship without content-length — TE carries the framing."""
    hdrs = {"host": f"127.0.0.1:{port}",
            "transfer-encoding": "chunked"}
    signed = sigv4.sign_request("PUT", path, "", hdrs, payload,
                                ACCESS, SECRET, "us-east-1")
    signed.pop("content-length", None)
    return _head_bytes("PUT", path, signed)


def _streaming_chunked_request(path: str, payload: bytes, port: int,
                               aws_chunk: int = 65536):
    """(head, aws_wire) for streaming-SigV4 nested in chunked TE."""
    hdrs, aws = sigv4.sign_streaming_request(
        "PUT", path, "", {"host": f"127.0.0.1:{port}"}, payload,
        ACCESS, SECRET, "us-east-1", chunk_size=aws_chunk)
    hdrs.pop("content-length", None)
    hdrs["transfer-encoding"] = "chunked"
    return _head_bytes("PUT", path, hdrs), aws


def _wait_inflight_zero(srv, timeout=10.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if srv.qos.foreground_inflight() == 0:
            return
        time.sleep(0.02)
    raise AssertionError(
        f"admission slots leaked: foreground_inflight="
        f"{srv.qos.foreground_inflight()}")


# ---------------- byte-exact round-trips ----------------


def test_chunked_put_roundtrips_and_reuses_keepalive(door):
    srv, port, cl = door
    payload = bytes(range(256)) * 1500  # 384 KB, multi-frame
    wire = (_signed_chunked_head("/bkt/obj", payload, port)
            + _chunk_wire(payload, trailer=b"x-ignored-trailer: v\r\n"))
    s = socket.create_connection(("127.0.0.1", port), timeout=30)
    try:
        s.sendall(wire)
        f = s.makefile("rb")
        status, _, _ = _read_response(f)
        assert status == 200
        # Keep-alive: the SAME socket must serve a second request —
        # proof the decoder consumed the trailer and left the stream
        # positioned at the next request line.
        hdrs = {"host": f"127.0.0.1:{port}", "content-length": "0"}
        signed = sigv4.sign_request("GET", "/bkt/obj", "", hdrs, b"",
                                    ACCESS, SECRET, "us-east-1")
        s.sendall(_head_bytes("GET", "/bkt/obj", signed))
        status2, _, body2 = _read_response(f)
        assert status2 == 200 and body2 == payload
    finally:
        s.close()
    got = cl.get_object("bkt", "obj")
    assert got.status == 200 and got.body == payload


def test_streaming_sigv4_inside_chunked_te_roundtrips(door):
    srv, port, cl = door
    payload = os.urandom(300_000)
    head, aws = _streaming_chunked_request("/bkt/sv4", payload, port)
    # TE frame sizes deliberately misaligned with aws-chunk boundaries.
    s = socket.create_connection(("127.0.0.1", port), timeout=30)
    try:
        s.sendall(head + _chunk_wire(aws, chunk=9001))
        status, _, _ = _read_response(s.makefile("rb"))
        assert status == 200
    finally:
        s.close()
    got = cl.get_object("bkt", "sv4")
    assert got.status == 200 and got.body == payload


def test_chunked_empty_buffered_body(door):
    """Non-object-PUT chunked bodies take the buffered path; an empty
    chunked bucket PUT must behave like Content-Length: 0."""
    srv, port, _cl = door
    hdrs = {"host": f"127.0.0.1:{port}",
            "transfer-encoding": "chunked"}
    signed = sigv4.sign_request("PUT", "/bkt2", "", hdrs, b"",
                                ACCESS, SECRET, "us-east-1")
    signed.pop("content-length", None)
    s = socket.create_connection(("127.0.0.1", port), timeout=30)
    try:
        s.sendall(_head_bytes("PUT", "/bkt2", signed) + b"0\r\n\r\n")
        status, _, _ = _read_response(s.makefile("rb"))
        assert status == 200
    finally:
        s.close()


# ---------------- signature failures mid-stream ----------------


def test_streaming_sigv4_broken_chunk_signature_rejected(door):
    """Corrupt ONE payload byte in the second aws-chunk: TE framing
    stays valid, the signature chain breaks → 403 SignatureDoesNotMatch,
    nothing stored, admission slot released."""
    srv, port, cl = door
    payload = b"Q" * 200_000
    head, aws = _streaming_chunked_request("/bkt/bad", payload, port)
    buf = bytearray(aws)
    second = buf.find(b"chunk-signature", buf.find(b"\r\n") + 65536)
    data_start = buf.find(b"\r\n", second) + 2
    buf[data_start + 10] ^= 0xFF
    s = socket.create_connection(("127.0.0.1", port), timeout=30)
    try:
        s.sendall(head + _chunk_wire(bytes(buf), chunk=9000))
        status, _, body = _read_response(s.makefile("rb"))
        assert status == 403
        assert b"SignatureDoesNotMatch" in body
    finally:
        s.close()
    assert cl.get_object("bkt", "bad").status == 404
    _wait_inflight_zero(srv)


def test_plain_chunked_content_hash_mismatch_rejected(door):
    """Plain SigV4 signs sha256(payload); streaming different bytes
    through chunked TE must fail the content-hash check, not store."""
    srv, port, cl = door
    signed_for = b"A" * 50_000
    sent = b"B" * 50_000
    wire = (_signed_chunked_head("/bkt/swap", signed_for, port)
            + _chunk_wire(sent))
    s = socket.create_connection(("127.0.0.1", port), timeout=30)
    try:
        s.sendall(wire)
        status, _, body = _read_response(s.makefile("rb"))
        assert status == 403
    finally:
        s.close()
    assert cl.get_object("bkt", "swap").status == 404
    _wait_inflight_zero(srv)


# ---------------- torn mid-chunk aborts ----------------


def test_torn_mid_chunk_abort_releases_slot(door):
    """Half-close mid-chunk while the body streams into the erasure
    pipeline: the PUT must abort (no partial object) and the admission
    slot must come back — the leak a decoder that swallows EOF would
    cause."""
    srv, port, cl = door
    payload = os.urandom(300_000)
    head, aws = _streaming_chunked_request("/bkt/torn", payload, port)
    s = socket.create_connection(("127.0.0.1", port), timeout=30)
    # Declare one huge TE chunk, send 30 KB of it, walk away.
    s.sendall(head + f"{len(aws):x}\r\n".encode() + aws[:30_000])
    time.sleep(0.3)
    s.close()
    _wait_inflight_zero(srv)
    assert cl.get_object("bkt", "torn").status == 404


def test_torn_between_chunks_abort_releases_slot(door):
    """EOF exactly on a frame boundary (no 0-chunk): still an abort,
    not a short-but-'complete' body."""
    srv, port, cl = door
    payload = os.urandom(120_000)
    wire = _chunk_wire(payload, chunk=40_000)
    cut = wire.find(b"\r\n", wire.find(b"\r\n") + 2 + 40_000) + 2
    s = socket.create_connection(("127.0.0.1", port), timeout=30)
    s.sendall(_signed_chunked_head("/bkt/torn2", payload, port)
              + wire[:cut])
    time.sleep(0.3)
    s.close()
    _wait_inflight_zero(srv)
    assert cl.get_object("bkt", "torn2").status == 404


# ---------------- rejects: smuggling + protocol ----------------


def test_content_length_plus_te_is_rejected(door):
    """CL+TE is THE request-smuggling primitive — hard 400."""
    srv, port, _cl = door
    payload = b"x" * 100
    hdrs = {"host": f"127.0.0.1:{port}",
            "transfer-encoding": "chunked",
            "content-length": str(len(payload))}
    signed = sigv4.sign_request("PUT", "/bkt/smug", "", hdrs, payload,
                                ACCESS, SECRET, "us-east-1")
    s = socket.create_connection(("127.0.0.1", port), timeout=30)
    try:
        s.sendall(_head_bytes("PUT", "/bkt/smug", signed)
                  + _chunk_wire(payload))
        status, _, _ = _read_response(s.makefile("rb"))
        assert status == 400
    finally:
        s.close()


def test_non_chunked_transfer_encoding_is_501(door):
    srv, port, _cl = door
    hdrs = {"host": f"127.0.0.1:{port}",
            "transfer-encoding": "gzip"}
    s = socket.create_connection(("127.0.0.1", port), timeout=30)
    try:
        s.sendall(_head_bytes("PUT", "/bkt/gz", hdrs))
        status, _, _ = _read_response(s.makefile("rb"))
        assert status == 501
    finally:
        s.close()


def test_chunked_on_http10_is_rejected(door):
    srv, port, _cl = door
    hdrs = {"host": f"127.0.0.1:{port}",
            "transfer-encoding": "chunked"}
    s = socket.create_connection(("127.0.0.1", port), timeout=30)
    try:
        s.sendall(_head_bytes("PUT", "/bkt/old", hdrs,
                              version="HTTP/1.0") + b"0\r\n\r\n")
        status, _, _ = _read_response(s.makefile("rb"))
        assert status == 400
    finally:
        s.close()


def test_buffered_chunked_body_over_cap_is_413(door, monkeypatch):
    """The buffered (non-object-PUT) path has no Content-Length to
    admission-check against — the decode cap is the only bound."""
    from minio_tpu.s3 import asyncserver
    monkeypatch.setattr(asyncserver, "CHUNKED_BUF_MAX", 1024)
    srv, port, _cl = door
    body = b"z" * 8192
    hdrs = {"host": f"127.0.0.1:{port}",
            "transfer-encoding": "chunked"}
    signed = sigv4.sign_request("PUT", "/bigbkt", "", hdrs, body,
                                ACCESS, SECRET, "us-east-1")
    signed.pop("content-length", None)
    s = socket.create_connection(("127.0.0.1", port), timeout=30)
    try:
        s.sendall(_head_bytes("PUT", "/bigbkt", signed)
                  + _chunk_wire(body, chunk=512))
        status, _, _ = _read_response(s.makefile("rb"))
        assert status == 413
    finally:
        s.close()
    _wait_inflight_zero(srv)


# ---------------- torn abort under a stalled loop ----------------


def test_blocked_loop_torn_chunked_put_releases_slot(door):
    """The loopmon stall scenario mid-body: the client walks away from
    a half-sent chunked PUT while every front-door loop is deliberately
    blocked 400ms. The abort must still release the admission slot and
    store nothing — a stalled loop delays teardown, it must never
    swallow it."""
    from minio_tpu.obs import loopmon
    srv, port, cl = door
    payload = os.urandom(300_000)
    head, aws = _streaming_chunked_request("/bkt/stall", payload, port)
    s = socket.create_connection(("127.0.0.1", port), timeout=30)
    # Declare one huge TE chunk, send 30 KB of it...
    s.sendall(head + f"{len(aws):x}\r\n".encode() + aws[:30_000])
    time.sleep(0.2)
    # ...block every loop while the body is half-read...
    for loop in srv._front_door._loops:
        loop.call_soon_threadsafe(loopmon._injected_loop_block, 0.4)
    # ...and walk away mid-stall.
    s.close()
    _wait_inflight_zero(srv)
    assert cl.get_object("bkt", "stall").status == 404
