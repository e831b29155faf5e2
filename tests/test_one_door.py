"""The one front door (PR 30): what `s3/asyncserver.py` and
`S3Server.start` must do now that no second door stands beside them —
`door.recv` once per request for each of the four body forms, the
single-listener fallback of the accept path, a failed start that leaves
nothing behind, the bound address and its one reader, and a tripwire
for the three retired environment names."""

import errno
import os
import re
import socket
import threading
import time

import pytest

from minio_tpu.erasure.engine import ErasureObjects
from minio_tpu.obs import metrics2 as m2
from minio_tpu.obs.span import TRACER
from minio_tpu.s3 import sigv4
from minio_tpu.s3.client import S3Client
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.xl import XLStorage

ACCESS, SECRET = "onedoorak", "onedoor-secret"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASE_MS = "minio_tpu_v2_request_phase_ms"
STREAM_THRESHOLD = 128 * 1024


def _server(root) -> S3Server:
    disks = [XLStorage(str(root / f"d{i}")) for i in range(4)]
    return S3Server(ErasureObjects(disks, block_size=64 * 1024),
                    ACCESS, SECRET)


def _roundtrip(port: int, bucket: str, n: int = 4) -> None:
    """A PUT and a byte-compared GET on each of `n` fresh connections
    (S3Client opens one per request, so a two-loop door serves them
    from both loops)."""
    c = S3Client("127.0.0.1", port, ACCESS, SECRET)
    assert c.make_bucket(bucket).status == 200
    for i in range(n):
        body = os.urandom(40_000 + i)
        assert c.put_object(bucket, f"k{i}", body).status == 200
        got = c.get_object(bucket, f"k{i}")
        assert got.status == 200 and got.body == body


# -- door.recv: once a request, whichever way the body arrived --------------


@pytest.fixture(scope="module")
def door(tmp_path_factory):
    srv = _server(tmp_path_factory.mktemp("onedoor"))
    srv.stream_threshold = STREAM_THRESHOLD
    port = srv.start()
    c = S3Client("127.0.0.1", port, ACCESS, SECRET)
    assert c.make_bucket("recv").status == 200
    yield srv, port
    srv.stop()


def _chunk_wire(payload: bytes, chunk: int = 7000) -> bytes:
    out = bytearray()
    for i in range(0, len(payload), chunk):
        piece = payload[i:i + chunk]
        out += f"{len(piece):x}\r\n".encode() + piece + b"\r\n"
    return bytes(out + b"0\r\n\r\n")


def _send(port: int, method: str, path: str, query: str, body: bytes,
          chunked: bool) -> int:
    """One signed request on a raw socket, its body framed by
    Content-Length or by chunked Transfer-Encoding; the status."""
    hdrs = {"host": f"127.0.0.1:{port}"}
    if chunked:
        hdrs["transfer-encoding"] = "chunked"
    else:
        hdrs["content-length"] = str(len(body))
    signed = sigv4.sign_request(method, path, query, hdrs, body,
                                ACCESS, SECRET, "us-east-1")
    if chunked:
        signed.pop("content-length", None)
    url = path + (f"?{query}" if query else "")
    head = f"{method} {url} HTTP/1.1\r\n" + "".join(
        f"{k}: {v}\r\n" for k, v in signed.items()) + "\r\n"
    with socket.create_connection(("127.0.0.1", port), timeout=30) as s:
        s.sendall(head.encode()
                  + (_chunk_wire(body) if chunked else body))
        f = s.makefile("rb")
        status = int(f.readline().split(b" ", 2)[1])
        length = 0
        while (line := f.readline()) not in (b"\r\n", b"\n", b""):
            k, _, v = line.partition(b":")
            if k.strip().lower() == b"content-length":
                length = int(v)
        f.read(length)
    return status


def _recv_series(api: str) -> tuple[float, int]:
    """(sum of ms, observations) of this api's door.recv phase."""
    return m2.METRICS2.get(PHASE_MS, {"api": api, "phase": "door.recv"})


def _tree(api: str, path: str) -> dict:
    deadline = time.monotonic() + 5.0
    while True:
        for t in reversed(TRACER.recent(64)):
            if t["name"] == api and t["tags"].get("path") == path:
                return t
        assert time.monotonic() < deadline, f"no {api} tree for {path}"
        time.sleep(0.02)


VERSIONING = (b'<VersioningConfiguration xmlns="http://s3.amazonaws.com/'
              b'doc/2006-03-01/"><Status>Suspended</Status>'
              b'</VersioningConfiguration>')

# form -> (method, path, query, body, chunked TE?, api label, the body
# reaches the worker as: one buffer the loop filled | a stream it reads)
BODY_FORMS = {
    "content_length_buffered": (
        "PUT", "/recv/cl-small", "", os.urandom(4096), False,
        "PUT-object", "buffer"),
    "content_length_streamed": (
        "PUT", "/recv/cl-large", "", os.urandom(300_000), False,
        "PUT-object", "stream"),
    "chunked_streamed_object_put": (
        "PUT", "/recv/te-object", "", os.urandom(300_000), True,
        "PUT-object", "stream"),
    "chunked_buffered_bucket_request": (
        "PUT", "/recv", "versioning=", VERSIONING, True,
        "PUT-bucket", "buffer"),
}


@pytest.mark.parametrize("form", list(BODY_FORMS))
def test_door_recv_is_recorded_once_per_request(door, form):
    """`door.recv` is the span the benchmark's idle gaps are named by
    (`frontdoor.put_recv_auth_ms` reads it): each request with a body
    adds ONE observation to request_phase_ms{api, phase="door.recv"}
    under its own api label, however its body was framed and whether
    the loop buffered it or the worker streamed it."""
    _, port = door
    method, path, query, body, chunked, api, arrives_as = BODY_FORMS[form]
    if not chunked:
        assert (len(body) >= STREAM_THRESHOLD) == (arrives_as == "stream")
    TRACER.reset()
    sum0, count0 = _recv_series(api)
    assert _send(port, method, path, query, body, chunked) == 200
    tree = _tree(api, path)
    sum1, count1 = _recv_series(api)
    assert count1 == count0 + 1
    assert sum1 > sum0
    spans = [c for c in tree["children"] if c["name"] == "door.recv"]
    # The loop's span carries the byte count; the engine's (one per
    # batch it pulls off the body, be that a socket stream or a buffer)
    # do not. The reduction takes the union of all into the one
    # observation.
    from_loop = [s for s in spans if "bytes" in s.get("tags", {})]
    if arrives_as == "buffer":
        assert [s["tags"]["bytes"] for s in from_loop] == [len(body)]
    else:
        assert from_loop == [] and len(spans) >= 1
    if api == "PUT-object":
        got = S3Client("127.0.0.1", port, ACCESS, SECRET).get_object(
            "recv", path.rsplit("/", 1)[1])
        assert got.status == 200 and got.body == body


# -- the accept path: SO_REUSEPORT, and the single listener without it ------


class _RefusesReusePort(socket.socket):
    """A platform that defines SO_REUSEPORT and refuses to set it."""

    def setsockopt(self, level, optname, *value):
        if level == socket.SOL_SOCKET and \
                optname == _RefusesReusePort.OPT:
            raise OSError(errno.ENOPROTOOPT, "Protocol not available")
        return super().setsockopt(level, optname, *value)

    OPT = getattr(socket, "SO_REUSEPORT", None)


@pytest.mark.parametrize("platform", ["default", "option_absent",
                                      "option_refused"])
def test_listener_falls_back_to_one_socket(tmp_path, monkeypatch,
                                           platform):
    """Two loops: with SO_REUSEPORT each owns a listen socket; where
    the platform lacks the option or refuses it, ONE listener accepts
    and hands connections round the loops. Either way every connection
    is served."""
    monkeypatch.setenv("MINIO_LOOP_THREADS", "2")
    if platform == "option_absent":
        monkeypatch.delattr(socket, "SO_REUSEPORT")
    elif platform == "option_refused":
        monkeypatch.setattr(socket, "socket", _RefusesReusePort)
    srv = _server(tmp_path)
    port = srv.start()
    try:
        front = srv._front_door
        assert len(front._loops) == 2
        if platform == "default":
            assert front.reuseport is (_RefusesReusePort.OPT is not None)
        else:
            assert front.reuseport is False
        if front.reuseport:
            assert len(front._lsocks) == 2 and front._lsock is None
        else:
            assert front._lsocks == [] and front._lsock is not None
        _roundtrip(port, f"fb-{platform.replace('_', '-')}")
    finally:
        srv.stop()
    with pytest.raises(OSError):
        socket.create_connection(("127.0.0.1", port), timeout=2)


# -- a start that fails leaves nothing behind --------------------------------


def _door_threads() -> set[str]:
    return {t.name for t in threading.enumerate()
            if t.name.startswith(("s3-loop", "s3-worker", "s3-rpc",
                                  "s3-stream"))}


def test_start_on_a_taken_port_raises_and_leaves_no_thread(tmp_path):
    blocker = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    port = blocker.getsockname()[1]
    srv = _server(tmp_path)
    before = _door_threads()
    try:
        with pytest.raises(OSError) as e:
            srv.start("127.0.0.1", port)
        assert e.value.errno == errno.EADDRINUSE
        assert _door_threads() == before
        assert srv.address is None and srv._front_door is None
    finally:
        blocker.close()
    # Nothing half-started: the same server boots once the port is free.
    assert srv.start("127.0.0.1", port) == port
    try:
        _roundtrip(port, "after-taken", n=1)
    finally:
        srv.stop()


# -- the bound address, and who reads it --------------------------------------


def test_address_is_where_a_client_connects(tmp_path):
    srv = _server(tmp_path)
    assert srv.address is None and srv.web.server_port() == 0
    port = srv.start("127.0.0.1", 0)
    try:
        assert port != 0 and srv.address == ("127.0.0.1", port)
        assert srv.web.server_port() == port
        with socket.create_connection(srv.address, timeout=5) as s:
            assert s.getpeername() == srv.address
        _roundtrip(srv.address[1], "by-address", n=1)
    finally:
        srv.stop()


def test_presigned_url_without_a_host_carries_the_bound_port(tmp_path):
    """The web RPC's port probe is the one reader of `address` inside
    the program: a PresignedGet that names no host must point at this
    server."""
    import http.client
    import json
    srv = _server(tmp_path)
    port = srv.start()
    try:
        c = S3Client("127.0.0.1", port, ACCESS, SECRET)
        assert c.make_bucket("presign").status == 200
        assert c.put_object("presign", "o", b"presigned").status == 200

        def rpc(method, params, token=None):
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=30)
            hdrs = {"Content-Type": "application/json"}
            if token:
                hdrs["Authorization"] = f"Bearer {token}"
            conn.request("POST", "/minio-tpu/webrpc", headers=hdrs,
                         body=json.dumps({"jsonrpc": "2.0", "id": 1,
                                          "method": f"web.{method}",
                                          "params": params}))
            try:
                return json.loads(conn.getresponse().read())["result"]
            finally:
                conn.close()

        token = rpc("Login", {"username": ACCESS,
                              "password": SECRET})["token"]
        url = rpc("PresignedGet", {"bucketName": "presign",
                                   "objectName": "o"}, token)["url"]
        assert f"//127.0.0.1:{port}/presign/o?" in url
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", url.split(f"127.0.0.1:{port}", 1)[1])
        r = conn.getresponse()
        assert r.status == 200 and r.read() == b"presigned"
        conn.close()
    finally:
        srv.stop()


# -- the retired switches ------------------------------------------------------


def _program_sources():
    for top in ("minio_tpu", "tools"):
        for dirpath, _, files in os.walk(os.path.join(ROOT, top)):
            for f in files:
                if f.endswith((".py", ".cc")):
                    yield os.path.join(dirpath, f)
    yield os.path.join(ROOT, "chip_smoke.py")


@pytest.mark.parametrize("name", ["MINIO_FRONT_DOOR", "MINIO_RPC_FABRIC",
                                  "MINIO_REUSEPORT"])
def test_retired_switch_is_read_nowhere(name):
    """PR 30 retired these three: each selected between two
    implementations of one job, and one of each is gone. A deployment
    that still sets one gets the default it already had. (The longer
    name MINIO_FRONT_DOOR_WORKERS is another option and stays.)"""
    word = re.compile(re.escape(name) + r"(?![A-Z0-9_])")
    hits = [os.path.relpath(p, ROOT) for p in _program_sources()
            if word.search(open(p, encoding="utf-8").read())]
    assert hits == []
