"""Front door tests: event-loop serving semantics that the request
core + `s3/asyncserver.py` must uphold — keep-alive
framing after sheds/burnt deadlines (drain-or-close per
Content-Length), Expect: 100-continue gating (admission before
upload), admission-slot release tied to connection teardown, pipelined
requests, graceful drain, connection-plane metrics, the
single-listener fallback, and the high-concurrency asyncio loadgen.
All fast — tier-1."""

import http.client
import os
import socket
import threading
import time

import pytest

from minio_tpu.erasure.engine import ErasureObjects
from minio_tpu.obs.metrics2 import METRICS2
from minio_tpu.s3 import sigv4
from minio_tpu.s3.client import S3Client
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.xl import XLStorage

ACCESS, SECRET = "fdadmin1", "fdadmin-secret1"

def _start_server(tmp_path, n_disks=4, k=2, m=2):
    disks = [XLStorage(str(tmp_path / f"disk{i}"))
             for i in range(n_disks)]
    layer = ErasureObjects(disks, k, m, block_size=256 * 1024)
    srv = S3Server(layer, ACCESS, SECRET)
    port = srv.start()
    return srv, port


def _signed_headers(method, path, body, port, extra=None):
    hdrs = {"host": f"127.0.0.1:{port}",
            "content-length": str(len(body))}
    if extra:
        hdrs.update(extra)
    return sigv4.sign_request(method, path, "", hdrs, body,
                              ACCESS, SECRET, "us-east-1")


def _raw_request_bytes(method, path, body, port, extra=None) -> bytes:
    hdrs = _signed_headers(method, path, body, port, extra)
    head = [f"{method} {path} HTTP/1.1\r\n"]
    head.extend(f"{k}: {v}\r\n" for k, v in hdrs.items())
    head.append("\r\n")
    return "".join(head).encode()


def _read_head(sock_file) -> tuple[int, dict]:
    """Read one response head off a socket file; (status, headers)."""
    status_line = sock_file.readline().decode()
    status = int(status_line.split(" ", 2)[1])
    headers = {}
    while True:
        line = sock_file.readline().decode()
        if line in ("\r\n", "\n", ""):
            break
        k, _, v = line.partition(":")
        headers[k.strip().lower()] = v.strip()
    return status, headers


def _read_response(sock_file) -> tuple[int, dict, bytes]:
    status, headers = _read_head(sock_file)
    body = sock_file.read(int(headers.get("content-length", 0) or 0))
    return status, headers, body


def _wait_inflight_zero(srv, timeout=10.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if srv.qos.foreground_inflight() == 0:
            return
        time.sleep(0.02)
    raise AssertionError(
        f"admission slots leaked: foreground_inflight="
        f"{srv.qos.foreground_inflight()}")


# ---------------- keep-alive framing after sheds ----------------


def test_shed_keepalive_two_requests_one_socket(tmp_path):
    """Satellite regression: a shed (503 SlowDown) response on a
    keep-alive connection must leave it in a readable state — the
    SECOND request on the same socket parses and succeeds."""
    srv, port = _start_server(tmp_path)
    try:
        S3Client("127.0.0.1", port, ACCESS, SECRET).make_bucket("bkt")
        srv.config.set_kv("api requests_max_write=1 "
                          "requests_deadline=250ms")
        held = srv.qos.acquire("write")  # occupy the only slot
        body = os.urandom(4096)
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=30)
        try:
            conn.request("PUT", "/bkt/k1", body=body,
                         headers=_signed_headers("PUT", "/bkt/k1",
                                                 body, port))
            r1 = conn.getresponse()
            shed_body = r1.read()
            assert r1.status == 503
            assert b"SlowDown" in shed_body
            assert r1.getheader("Retry-After")
            held.release()
            # SAME socket: the framing must not have desynced.
            conn.request("PUT", "/bkt/k2", body=body,
                         headers=_signed_headers("PUT", "/bkt/k2",
                                                 body, port))
            r2 = conn.getresponse()
            r2.read()
            assert r2.status == 200
        finally:
            held.release()
            conn.close()
        srv.config.set_kv("api requests_max_write=0 "
                          "requests_deadline=10s")
        _wait_inflight_zero(srv)
    finally:
        srv.stop()


def test_burnt_deadline_keepalive_second_request_ok(tmp_path):
    """A burnt-deadline 503 (RequestTimeout) must equally leave the
    connection readable for the next pipelined request."""
    srv, port = _start_server(tmp_path)
    try:
        client = S3Client("127.0.0.1", port, ACCESS, SECRET)
        client.make_bucket("bkt")
        client.put_object("bkt", "k", b"x" * 1024)
        slow = {"on": True}
        # A GET's first call into the layer: it opens the object once.
        real_info = srv.handlers.layer.open_object

        def slow_info(*a, **kw):
            if slow["on"]:
                # What a deadline-capped storage/peer call raises once
                # the budget is spent (qos/deadline.py).
                from minio_tpu.qos.deadline import DeadlineExceeded
                raise DeadlineExceeded("budget spent")
            return real_info(*a, **kw)

        srv.handlers.layer.open_object = slow_info
        srv.config.set_kv("api requests_max_read=8 "
                          "requests_deadline=200ms")
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=30)
        try:
            conn.request("GET", "/bkt/k",
                         headers=_signed_headers("GET", "/bkt/k", b"",
                                                 port))
            r1 = conn.getresponse()
            b1 = r1.read()
            assert r1.status == 503
            assert b"RequestTimeout" in b1
            slow["on"] = False
            conn.request("GET", "/bkt/k",
                         headers=_signed_headers("GET", "/bkt/k", b"",
                                                 port))
            r2 = conn.getresponse()
            assert r2.status == 200
            assert r2.read() == b"x" * 1024
        finally:
            conn.close()
            srv.handlers.layer.open_object = real_info
            srv.config.set_kv("api requests_max_read=0 "
                              "requests_deadline=10s")
        _wait_inflight_zero(srv)
    finally:
        srv.stop()


# ---------------- Expect: 100-continue ----------------


def test_expect_100_continue_put_roundtrip(tmp_path):
    """A PUT with Expect: 100-continue gets the interim 100 BEFORE the
    body is read, then a 200; the bytes land exactly."""
    srv, port = _start_server(tmp_path)
    try:
        client = S3Client("127.0.0.1", port, ACCESS, SECRET)
        client.make_bucket("bkt")
        body = os.urandom(64 * 1024)
        raw = _raw_request_bytes("PUT", "/bkt/exp", body, port,
                                 extra={"expect": "100-continue"})
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=30) as s:
            f = s.makefile("rb")
            s.sendall(raw)  # head only — body held back
            status, _ = _read_head(f)
            assert status == 100
            s.sendall(body)
            status, headers, _ = _read_response(f)
            assert status == 200
        got = client.get_object("bkt", "exp")
        assert got.status == 200 and got.body == body
    finally:
        srv.stop()


def test_expect_shed_answers_before_body_and_closes(tmp_path):
    """QoS admission runs BEFORE the body upload: a shed Expect-PUT is
    answered 503 with NO interim 100, carries Connection: close (the
    client may or may not send the body — only a close keeps the
    framing safe), and never leaks its slot."""
    srv, port = _start_server(tmp_path)
    try:
        S3Client("127.0.0.1", port, ACCESS, SECRET).make_bucket("bkt")
        srv.config.set_kv("api requests_max_write=1 "
                          "requests_deadline=200ms")
        held = srv.qos.acquire("write")
        try:
            body = os.urandom(512 * 1024)
            raw = _raw_request_bytes("PUT", "/bkt/exp2", body, port,
                                     extra={"expect": "100-continue"})
            with socket.create_connection(("127.0.0.1", port),
                                          timeout=30) as s:
                f = s.makefile("rb")
                s.sendall(raw)
                status, headers = _read_head(f)
                assert status == 503  # shed, and NOT a 100 first
                f.read(int(headers.get("content-length", 0) or 0))
                assert headers.get("connection") == "close"
                assert f.read(1) == b""  # server closed the socket
        finally:
            held.release()
            srv.config.set_kv("api requests_max_write=0 "
                              "requests_deadline=10s")
        _wait_inflight_zero(srv)
    finally:
        srv.stop()


# ---------------- teardown-tied slot release ----------------


def test_aborted_mid_body_put_releases_slot(tmp_path):
    """A client that dies mid-upload of a STREAMING body must unwind
    the blocked worker and release its admission slot (structural:
    connection teardown abandons the bridge)."""
    srv, port = _start_server(tmp_path)
    try:
        S3Client("127.0.0.1", port, ACCESS, SECRET).make_bucket("bkt")
        size = 9 * 1024 * 1024  # past stream_threshold
        head = _raw_request_bytes("PUT", "/bkt/crash", b"\0" * size,
                                  port)
        s = socket.create_connection(("127.0.0.1", port), timeout=30)
        s.sendall(head)
        s.sendall(b"\0" * (1024 * 1024))  # 1 MiB of 9 — then vanish
        time.sleep(0.3)  # let the worker start consuming
        assert srv.qos.foreground_inflight() >= 1
        s.close()
        _wait_inflight_zero(srv)
        # The torn object must not exist.
        got = S3Client("127.0.0.1", port, ACCESS,
                       SECRET).get_object("bkt", "crash")
        assert got.status == 404
    finally:
        srv.stop()


def test_aborted_streaming_get_releases_slot(tmp_path):
    """A reader that disappears mid-download of a streaming GET frees
    its slot: with a read cap of 1, the NEXT GET must be admitted."""
    srv, port = _start_server(tmp_path)
    try:
        client = S3Client("127.0.0.1", port, ACCESS, SECRET)
        client.make_bucket("bkt")
        body = os.urandom(4 * 1024 * 1024)
        assert client.put_object("bkt", "big", body).status == 200
        srv.config.set_kv("api requests_max_read=1 "
                          "requests_deadline=5s")
        raw = _raw_request_bytes("GET", "/bkt/big", b"", port)
        s = socket.create_connection(("127.0.0.1", port), timeout=30)
        s.sendall(raw)
        s.recv(1024)  # first bytes of the response are flowing
        s.close()     # ...and the reader vanishes
        _wait_inflight_zero(srv)
        got = client.get_object("bkt", "big")  # slot must be free
        assert got.status == 200 and got.body == body
        srv.config.set_kv("api requests_max_read=0 "
                          "requests_deadline=10s")
    finally:
        srv.stop()


# ---------------- framing: pipelining, parse errors ----------------


def test_pipelined_requests_same_socket(tmp_path):
    """Two requests written back-to-back before reading: responses
    come back in order, correctly framed."""
    srv, port = _start_server(tmp_path)
    try:
        client = S3Client("127.0.0.1", port, ACCESS, SECRET)
        client.make_bucket("bkt")
        client.put_object("bkt", "a", b"AAAA")
        client.put_object("bkt", "b", b"BBBBBB")
        raw = (_raw_request_bytes("GET", "/bkt/a", b"", port)
               + _raw_request_bytes("GET", "/bkt/b", b"", port))
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=30) as s:
            f = s.makefile("rb")
            s.sendall(raw)
            s1, _, b1 = _read_response(f)
            s2, _, b2 = _read_response(f)
        assert (s1, b1) == (200, b"AAAA")
        assert (s2, b2) == (200, b"BBBBBB")
    finally:
        srv.stop()


def test_half_close_after_request_still_answered(tmp_path):
    """A client that shutdown(SHUT_WR)s after sending its request
    (Go-style CloseWrite) must still receive the full response."""
    srv, port = _start_server(tmp_path)
    try:
        client = S3Client("127.0.0.1", port, ACCESS, SECRET)
        client.make_bucket("bkt")
        body = os.urandom(128 * 1024)
        assert client.put_object("bkt", "hc", body).status == 200
        raw = _raw_request_bytes("GET", "/bkt/hc", b"", port)
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=30) as s:
            s.sendall(raw)
            s.shutdown(socket.SHUT_WR)
            f = s.makefile("rb")
            status, headers, got = _read_response(f)
        assert status == 200 and got == body
    finally:
        srv.stop()


def test_half_close_with_pipelined_request_answers_both(tmp_path):
    """sendall(reqA + reqB) then CloseWrite: BOTH responses arrive
    before the server closes — a buffered pipelined request must not
    be dropped just because the peer half-closed."""
    srv, port = _start_server(tmp_path)
    try:
        client = S3Client("127.0.0.1", port, ACCESS, SECRET)
        client.make_bucket("bkt")
        client.put_object("bkt", "p1", b"ONE!")
        client.put_object("bkt", "p2", b"TWO!!")
        raw = (_raw_request_bytes("GET", "/bkt/p1", b"", port)
               + _raw_request_bytes("GET", "/bkt/p2", b"", port))
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=30) as s:
            s.sendall(raw)
            s.shutdown(socket.SHUT_WR)
            f = s.makefile("rb")
            s1, _, b1 = _read_response(f)
            s2, _, b2 = _read_response(f)
            assert (s1, b1) == (200, b"ONE!")
            assert (s2, b2) == (200, b"TWO!!")
            assert f.read(1) == b""  # then the server closes
    finally:
        srv.stop()


def test_malformed_head_rejected_and_counted(tmp_path):
    srv, port = _start_server(tmp_path)
    try:
        before = METRICS2.get(
            "minio_tpu_v2_conn_parse_errors_total") or 0
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=30) as s:
            s.sendall(b"@@@garbage\r\n\r\n")
            f = s.makefile("rb")
            status, headers = _read_head(f)
            assert status == 400
            assert headers.get("connection") == "close"
        assert (METRICS2.get("minio_tpu_v2_conn_parse_errors_total")
                or 0) > before
    finally:
        srv.stop()


def test_many_requests_one_socket_mixed_ops(tmp_path):
    """Sustained keep-alive: dozens of mixed ops on one connection
    stay frame-exact (HEAD has no body, DELETE is 204, errors are
    XML)."""
    srv, port = _start_server(tmp_path)
    try:
        S3Client("127.0.0.1", port, ACCESS, SECRET).make_bucket("bkt")
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=30)
        try:
            payload = os.urandom(8192)
            for i in range(12):
                key = f"k{i}"
                conn.request(
                    "PUT", f"/bkt/{key}", body=payload,
                    headers=_signed_headers("PUT", f"/bkt/{key}",
                                            payload, port))
                assert conn.getresponse().read() is not None
                conn.request("HEAD", f"/bkt/{key}",
                             headers=_signed_headers(
                                 "HEAD", f"/bkt/{key}", b"", port))
                rh = conn.getresponse()
                rh.read()
                assert rh.status == 200
                conn.request("GET", f"/bkt/{key}",
                             headers=_signed_headers(
                                 "GET", f"/bkt/{key}", b"", port))
                rg = conn.getresponse()
                assert rg.read() == payload
                conn.request("GET", "/bkt/missing-404",
                             headers=_signed_headers(
                                 "GET", "/bkt/missing-404", b"",
                                 port))
                r404 = conn.getresponse()
                r404.read()
                assert r404.status == 404
        finally:
            conn.close()
    finally:
        srv.stop()


# ---------------- graceful drain ----------------


def test_graceful_stop_finishes_inflight_request(tmp_path,
                                                 monkeypatch):
    """stop() drains: an in-flight PUT completes with 200 while new
    connections are refused."""
    monkeypatch.setenv("MINIO_SHUTDOWN_DRAIN", "15")
    srv, port = _start_server(tmp_path)
    client = S3Client("127.0.0.1", port, ACCESS, SECRET)
    client.make_bucket("bkt")
    real_put = srv.handlers.layer.put_object

    def slow_put(*a, **kw):
        time.sleep(1.0)
        return real_put(*a, **kw)

    srv.handlers.layer.put_object = slow_put
    result = {}

    def do_put():
        result["resp"] = client.put_object("bkt", "slowk", b"d" * 1024)

    t = threading.Thread(target=do_put)
    t.start()
    time.sleep(0.3)  # the PUT is inside the handler now
    t_stop = time.monotonic()
    srv.stop()
    stop_s = time.monotonic() - t_stop
    t.join(timeout=20)
    assert result["resp"].status == 200
    assert stop_s < 15  # drained, not timed out
    with pytest.raises(OSError):
        socket.create_connection(("127.0.0.1", port), timeout=2)


# ---------------- connection-plane observability ----------------


def test_connection_metrics_and_timeline_row(tmp_path):
    srv, port = _start_server(tmp_path)
    try:
        socks = [socket.create_connection(("127.0.0.1", port),
                                          timeout=10)
                 for _ in range(5)]
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if (METRICS2.get("minio_tpu_v2_open_connections")
                    or 0) >= 5:
                break
            time.sleep(0.02)
        assert (METRICS2.get("minio_tpu_v2_open_connections")
                or 0) >= 5
        assert srv._front_door.open_connections() >= 5
        # Timeline sample carries the conns row…
        from minio_tpu.obs.timeline import TIMELINE, merge_timelines
        TIMELINE.tick()
        sample = TIMELINE.tick()
        assert sample["conns"] >= 5
        assert "acceptQueue" in sample and "parseErrors" in sample
        # …which survives the cluster merge (summed across nodes).
        merged = merge_timelines([
            {"periodS": 1.0, "samples": [sample]},
            {"periodS": 1.0, "samples": [dict(sample)]}])
        assert merged["samples"][-1]["conns"] == 2 * sample["conns"]
        # …and mtpu_top renders it.
        from tools.mtpu_top import render
        frame = render({"periodS": 1.0, "samples": [sample]})
        assert "conns: open" in frame
        for s in socks:
            s.close()
    finally:
        srv.stop()


# ---------------- high-concurrency loadgen ----------------


def test_async_loadgen_closed_loop(tmp_path):
    """The asyncio driver holds a keep-alive fleet, mixes signed
    PUT/GET closed-loop, and reports per-class connect/TTFB/total
    percentiles — with zero framing errors against the async front
    door and zero slot leaks after."""
    from tools.loadgen import run_async_load
    srv, port = _start_server(tmp_path)
    try:
        S3Client("127.0.0.1", port, ACCESS, SECRET).make_bucket("lgen")
        rep = run_async_load("127.0.0.1", port, ACCESS, SECRET, "lgen",
                             connections=64, duration=1.5, qps=0.0,
                             put_fraction=0.3, object_bytes=8192,
                             key_space=8, preload=True)
        assert rep["established"] == 64
        assert rep["connect_failures"] == 0
        assert rep["errors_other"] == 0
        assert rep["ok"] > 50
        for cls in ("get", "put"):
            assert rep[cls]["total_ms"]["count"] > 0
            assert rep[cls]["ttfb_ms"]["p99"] >= 0
        assert rep["connect_ms"]["count"] == 64
        _wait_inflight_zero(srv)
        # The clients have closed; the loop still has to see each EOF.
        # No leak is the claim, not that 64 closes are processed the
        # instant the generator returns (1 run in 6 read 1-3 left for
        # under 10 ms, parent and change alike: my runs, PR 25).
        deadline = time.monotonic() + 2.0
        while srv._front_door.open_connections() and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv._front_door.open_connections() == 0
    finally:
        srv.stop()


# ---------------- loop-under-stall (loopmon satellite) ----------------


def test_blocked_loop_put_completes_and_releases_slots(tmp_path):
    """The loopmon stall scenario against real traffic: every
    front-door loop gets a deliberate 400ms block while a PUT is in
    flight. The request must complete once the block clears (delayed,
    never dropped), admission slots must return to zero, and the
    flight recorder must have captured the stall blaming the injected
    frame — the lag -> blame chain on a live server."""
    from minio_tpu.obs import loopmon
    from minio_tpu.obs.loopmon import LOOPMON
    srv, port = _start_server(tmp_path)
    try:
        LOOPMON.configure(stall_ms=150)
        cl = S3Client("127.0.0.1", port, ACCESS, SECRET)
        assert cl.make_bucket("stall").status == 200
        front = srv._front_door
        # Let every loop beat first (boot-time CPU storms can delay
        # the first heartbeat) so the stall window is unambiguous.
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and len(
                [n for n in LOOPMON.lag_census() if
                 n.startswith("s3-")]) < len(front._loops):
            time.sleep(0.05)
        for loop in front._loops:
            loop.call_soon_threadsafe(loopmon._injected_loop_block,
                                      0.4)
        r = cl.put_object("stall", "k", b"x" * 50_000)
        assert r.status == 200
        got = cl.get_object("stall", "k")
        assert got.status == 200 and got.body == b"x" * 50_000
        _wait_inflight_zero(srv)
        deadline = time.monotonic() + 10
        blamed = []
        while time.monotonic() < deadline and not blamed:
            blamed = [e for e in LOOPMON.recent_stalls()
                      if e["loop"].startswith("s3-")
                      and e["topFrame"].startswith(
                          "_injected_loop_block")]
            time.sleep(0.05)
        assert blamed, LOOPMON.snapshot()["stalls"]
    finally:
        srv.stop()
        LOOPMON.configure(stall_ms=250)
