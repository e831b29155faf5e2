"""The peer-RPC client (rpc/aio.py): offline gate + PR-6 jittered
reconnect probe, stale-pool single-shot retry, pool teardown on
close, deadline fast-fail/capping without offline marks,
the in-flight census behind the zero-thread-per-call claim, peer
fan-out, and HTTP/1.1 pipelining. All against a real wire server (an
S3Server front door serving an RPCRegistry), so the bytes on the
socket are the production protocol."""

import socket
import threading
import time

import pytest

from minio_tpu.qos.deadline import (Deadline, DeadlineExceeded,
                                    deadline_scope)
from minio_tpu.rpc import aio
from minio_tpu.rpc.cluster import derive_cluster_key
from minio_tpu.rpc.storage import RemoteStorage, StorageRPCService
from minio_tpu.rpc.transport import RPCClient, RPCRegistry
from minio_tpu.s3.server import S3Server
from minio_tpu.storage import errors as serr
from minio_tpu.storage.xl import XLStorage

ACCESS, SECRET = "fabricak1", "fabric-secret-1"
KEY = derive_cluster_key(ACCESS, SECRET)

class _EchoService:
    """Registry service exercising every fabric path: echo (request/
    response + payload), slow (in-flight census), create/append
    (pipelining order), boom (error mapping), mark (fire-and-forget)."""

    def __init__(self):
        self.chunks: list[bytes] = []
        self.marks: list[dict] = []

    def rpc_echo(self, args, payload):
        return {"echo": args.get("x")}, payload

    def rpc_slow(self, args, payload):
        time.sleep(args.get("sleepS", 0.2))
        return {"ok": True}, b""

    def rpc_create_file(self, args, payload):
        self.chunks = [payload]
        return {}, b""

    def rpc_append_file(self, args, payload):
        self.chunks.append(payload)
        return {}, b""

    def rpc_boom(self, args, payload):
        raise serr.FileNotFound(args.get("why", "boom"))

    def rpc_mark(self, args, payload):
        self.marks.append(args)
        return {}, b""


def _start_rpc_server():
    reg = RPCRegistry(KEY)
    svc = _EchoService()
    reg.register("test", svc)
    reg.register("peer", svc)  # fanout() speaks to the "peer" service
    srv = S3Server(None, ACCESS, SECRET, rpc_registry=reg)
    port = srv.start("127.0.0.1", 0)
    return srv, port, svc


@pytest.fixture()
def echo_server():
    srv, port, svc = _start_rpc_server()
    yield port, svc
    srv.stop()


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------- round trip + pool reuse ----------------


def test_async_call_roundtrip_and_pool_reuse(echo_server):
    port, _svc = echo_server
    cl = RPCClient("127.0.0.1", port, KEY)
    try:
        res, data = cl.call("test", "echo", {"x": 1}, b"payload")
        assert res["echo"] == 1 and data == b"payload"
        st = cl._aio_state
        assert len(st.pool) == 1
        res2, _ = cl.call("test", "echo", {"x": 2})
        assert res2["echo"] == 2
        # Keep-alive reuse: still ONE pooled connection, not two.
        assert len(st.pool) == 1
    finally:
        cl.close()


def test_close_drops_the_pool_and_a_later_call_reconnects(echo_server):
    """close() closes what the client still owns, its keep-alives on
    the RPC loop: the pooled socket is shut (not just forgotten), the
    pool's generation moves on, and the client stays usable."""
    port, _svc = echo_server
    cl = RPCClient("127.0.0.1", port, KEY)
    try:
        assert cl.call("test", "echo", {"x": 1})[0]["echo"] == 1
        st = cl._aio_state
        (conn,), gen = st.pool, st.gen
        cl.close()
        deadline = time.monotonic() + 5
        while st.pool and time.monotonic() < deadline:
            time.sleep(0.01)
        assert st.pool == [] and st.gen == gen + 1
        assert conn.writer.is_closing()
        assert cl.call("test", "echo", {"x": 2})[0]["echo"] == 2
        (fresh,) = st.pool
        assert fresh is not conn and fresh.gen == st.gen
    finally:
        cl.close()


# ---------------- offline gate: PR-6 jittered reconnect probe -------


def test_async_offline_gate_inherits_jittered_window():
    """A failed call marks the peer offline through the jittered
    window — repeated marks spread over [OFFLINE_RETRY,
    (1+J) x OFFLINE_RETRY] (no reconnect thundering herd), and while
    offline, calls fast-fail without touching the socket."""
    cl = RPCClient("127.0.0.1", _free_port(), KEY, timeout=2.0)
    try:
        windows = set()
        for _ in range(12):
            cl._offline_until = 0.0  # force a fresh probe each round
            with pytest.raises(serr.DiskNotFound, match="unreachable"):
                cl.call("test", "echo", {})
            windows.add(round(cl._offline_until - time.monotonic(), 3))
        assert not cl.is_online()
        with pytest.raises(serr.DiskNotFound, match="offline"):
            cl.call("test", "echo", {})
        assert len(windows) > 1, "no jitter: identical windows"
        assert min(windows) >= cl.OFFLINE_RETRY * 0.9
        assert max(windows) <= cl.OFFLINE_RETRY * (
            1 + cl.OFFLINE_JITTER) + 0.01
    finally:
        cl.close()


# ---------------- stale-pool single-shot retry ----------------------


class _DeadReader:
    @staticmethod
    def at_eof() -> bool:
        return False  # looks alive until used — the stale signature


class _DeadWriter:
    def write(self, data) -> None:
        pass

    async def drain(self) -> None:
        raise ConnectionResetError("stale pooled socket")

    def close(self) -> None:
        pass


def test_stale_pooled_conn_retries_once_on_fresh_socket(echo_server):
    """A reused connection failing BEFORE any response byte retries
    exactly once on a fresh socket — the peer-restart case — and the
    success neither marks the peer offline nor surfaces the
    transient."""
    port, _svc = echo_server
    cl = RPCClient("127.0.0.1", port, KEY)
    try:
        async def inject():
            st = aio._aio_state(cl)
            st.pool.append(
                aio._AConn(_DeadReader(), _DeadWriter(), st.gen))
        aio.RPC_LOOP.run(inject())
        res, _ = cl.call("test", "echo", {"x": 9})
        assert res["echo"] == 9
        assert cl.is_online()
    finally:
        cl.close()


def test_peer_restart_keep_alive_survives(echo_server):
    """End-to-end reconnect storm check: pool a keep-alive, restart
    the peer on the same port, call again — the fabric recovers on
    ONE call (drop-stale or single retry), no offline window."""
    port, _svc = echo_server
    cl = RPCClient("127.0.0.1", port, KEY)
    srv2 = None
    try:
        assert cl.call("test", "echo", {"x": 1})[0]["echo"] == 1
        reg2 = RPCRegistry(KEY)
        reg2.register("test", _EchoService())
        srv2 = S3Server(None, ACCESS, SECRET, rpc_registry=reg2)
        # echo_server's fixture still owns the first server; rebind
        # its port after stopping it.
        echo_srv = None
        port2 = None
        for _ in range(20):
            try:
                port2 = srv2.start("127.0.0.1", port)
                break
            except OSError:
                time.sleep(0.2)
        assert port2 == port
        res, _ = cl.call("test", "echo", {"x": 2})
        assert res["echo"] == 2 and cl.is_online()
    finally:
        cl.close()
        if srv2 is not None:
            srv2.stop()


# ---------------- deadline semantics ----------------


def test_deadline_fast_fail_before_dispatch(echo_server):
    port, _svc = echo_server
    cl = RPCClient("127.0.0.1", port, KEY)
    try:
        with deadline_scope(Deadline(0.0)):
            with pytest.raises(DeadlineExceeded):
                cl.call("test", "echo", {})
        assert cl.is_online()  # a burnt budget says nothing about peers
    finally:
        cl.close()


def test_deadline_caps_timeout_and_never_marks_offline(echo_server):
    port, _svc = echo_server
    cl = RPCClient("127.0.0.1", port, KEY)
    try:
        t0 = time.monotonic()
        with deadline_scope(Deadline(0.3)):
            with pytest.raises(DeadlineExceeded):
                cl.call("test", "slow", {"sleepS": 1.0})
        assert time.monotonic() - t0 < 0.95  # capped, not full sleep
        assert cl.is_online()
    finally:
        cl.close()


# ---------------- census: the zero-thread claim ----------------


def test_inflight_census_counts_without_thread_growth(echo_server):
    """64 concurrent peer calls in flight on the ONE loop thread: the
    census sees them all while the process thread count stays flat on
    the client side (the in-process SERVER pool accounts for the small
    bounded delta)."""
    port, _svc = echo_server
    cl = RPCClient("127.0.0.1", port, KEY)
    n = 64
    try:
        # Warm one call so both sides' steady-state threads exist.
        cl.call("test", "echo", {"x": 0})
        before = threading.active_count()
        futs = [aio.RPC_LOOP.submit(
            aio.call_async(cl, "test", "slow", {"sleepS": 0.4},
                           timeout=20.0)) for _ in range(n)]
        peak, during = 0, before
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            cur = aio.CENSUS.current()
            if cur > peak:
                peak = cur
                during = threading.active_count()
            if peak >= n:
                break
            time.sleep(0.005)
        for f in futs:
            res, _ = f.result(timeout=30)
            assert res["ok"]
        assert peak >= n - 4, f"census peak {peak} of {n}"
        # The client added ZERO threads; the in-process server's
        # bounded RPC worker pool is the only growth.
        assert during - before <= 24, (before, during, peak)
        assert aio.CENSUS.current() == 0
    finally:
        cl.close()


def test_timeline_sample_carries_rpc_census():
    from minio_tpu.obs.timeline import Timeline
    tl = Timeline(period_s=0.01)
    assert tl.tick() is None  # baseline
    sample = tl.tick()
    assert "rpcInflight" in sample
    assert sample["threads"] >= 1


# ---------------- peer fan-out ----------------


def test_fanout_parallel_results_and_per_peer_errors(echo_server):
    port, _svc = echo_server
    cl_up = RPCClient("127.0.0.1", port, KEY)
    cl_down = RPCClient("127.0.0.1", _free_port(), KEY, timeout=2.0)
    try:
        res = aio.fanout({"up": cl_up, "down": cl_down}, "echo",
                         {"x": 5})
        assert res is not None
        assert res["up"]["echo"] == 5
        assert isinstance(res["down"], serr.DiskNotFound)
    finally:
        cl_up.close()
        cl_down.close()


def test_fanout_nowait_delivers_and_returns_immediately(echo_server):
    port, svc = echo_server
    cl = RPCClient("127.0.0.1", port, KEY)
    try:
        t0 = time.monotonic()
        assert aio.fanout_nowait({"n": cl}, "mark", {"seq": 1})
        assert time.monotonic() - t0 < 0.5  # did not wait for the wire
        deadline = time.monotonic() + 5
        while not svc.marks and time.monotonic() < deadline:
            time.sleep(0.01)
        assert svc.marks == [{"seq": 1}]
    finally:
        cl.close()


def test_fanout_declines_non_rpcclient_peers():
    class FakePeer:
        pass
    assert aio.fanout({"a": FakePeer()}, "echo", {}) is None
    assert not aio.fanout_nowait({"a": FakePeer()}, "echo", {})
    assert aio.fanout({}, "echo", {}) is None


# ---------------- HTTP/1.1 pipelining ----------------


def test_pipeline_streams_chunks_in_order(echo_server):
    port, svc = echo_server
    cl = RPCClient("127.0.0.1", port, KEY)
    try:
        expected = [bytes([65 + i]) * 3 for i in range(9)]
        pipe = aio.Pipeline(cl)
        pipe.send("test", "create_file", {"p": 1}, expected[0])
        for piece in expected[1:]:
            pipe.send("test", "append_file", {"p": 1}, piece)
        pipe.finish()
        # Order is the whole contract: interleaved frames would
        # corrupt the remote file byte-for-byte.
        assert svc.chunks == expected
    finally:
        cl.close()


def test_pipeline_error_surfaces_and_aborts(echo_server):
    port, svc = echo_server
    cl = RPCClient("127.0.0.1", port, KEY)
    try:
        pipe = aio.Pipeline(cl)
        pipe.send("test", "create_file", {"p": 2}, b"x")
        pipe.send("test", "boom", {"why": "nope"})
        pipe.send("test", "append_file", {"p": 2}, b"y")
        with pytest.raises(serr.FileNotFound, match="nope"):
            pipe.finish()
        # A server-mapped error is NOT peer death.
        assert cl.is_online()
    finally:
        cl.close()


def test_pipeline_respects_deadline(echo_server):
    port, _svc = echo_server
    cl = RPCClient("127.0.0.1", port, KEY)
    try:
        with deadline_scope(Deadline(0.0)):
            with pytest.raises((DeadlineExceeded, serr.DiskNotFound)):
                pipe = aio.Pipeline(cl)
                pipe.send("test", "create_file", {"p": 3}, b"x")
                pipe.finish()
    finally:
        cl.close()


# ---------------- RemoteStorage.create_file over an iterator ----------


class _RecordingStorageService(StorageRPCService):
    """The storage service, noting the order its writes arrived in."""

    def __init__(self, disks):
        super().__init__(disks)
        self.writes: list[tuple[str, int]] = []

    def rpc_create_file(self, a, p):
        self.writes.append(("create_file", len(p)))
        return super().rpc_create_file(a, p)

    def rpc_append_file(self, a, p):
        self.writes.append(("append_file", len(p)))
        return super().rpc_append_file(a, p)


CHUNK_LISTS = {
    "several_chunks": [b"a" * 70_000, b"b" * 70_000, b"c" * 123],
    "one_chunk": [b"only"],
    "empty_iterator": [],
}


@pytest.mark.parametrize("chunks", list(CHUNK_LISTS))
@pytest.mark.parametrize("client_kind", ["in_process", "peer"])
def test_remote_create_file_streams_an_iterator(tmp_path, monkeypatch,
                                                client_kind, chunks):
    """First chunk creates, the rest append, an empty iterator still
    creates the file. A real peer gets the frames down one pipelined
    connection; an in-process (or test-double) client, which the RPC
    loop cannot speak to, gets one plain call a chunk. Same writes in
    the same order, same file."""
    from minio_tpu.parallel import quorum
    # RemoteStorage latches this for the process; give it back.
    monkeypatch.setattr(quorum, "FORCE_THREADS", quorum.FORCE_THREADS)
    local = XLStorage(str(tmp_path / "disk"))
    local.make_volume("vol")
    svc = _RecordingStorageService({local.root: local})
    plain_calls = []
    real_call = RemoteStorage._call

    def spy(self, method, args=None, payload=b""):
        plain_calls.append(method)
        return real_call(self, method, args, payload)

    monkeypatch.setattr(RemoteStorage, "_call", spy)
    srv = client = None
    if client_kind == "peer":
        reg = RPCRegistry(KEY)
        reg.register("storage", svc)
        srv = S3Server(None, ACCESS, SECRET, rpc_registry=reg)
        client = RPCClient("127.0.0.1", srv.start("127.0.0.1", 0), KEY)
    else:
        class _InProcess:
            def call(self, service, method, args, payload=b""):
                return getattr(svc, f"rpc_{method}")(args, payload)
        client = _InProcess()
    try:
        pieces = CHUNK_LISTS[chunks]
        RemoteStorage(client, local.root).create_file(
            "vol", "f/stream.bin", iter(pieces))
        want = [("create_file", len(pieces[0]))] + [
            ("append_file", len(p)) for p in pieces[1:]
        ] if pieces else [("create_file", 0)]
        assert svc.writes == want
        assert local.read_all("vol", "f/stream.bin") == b"".join(pieces)
        if client_kind == "peer":
            assert plain_calls == []  # every frame rode the pipeline
        else:
            assert plain_calls == [m for m, _ in want]
    finally:
        if srv is not None:
            client.close()
            srv.stop()
