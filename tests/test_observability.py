"""Trace hub, console log ring, audit webhook (ref pkg/pubsub,
cmd/handler-utils.go httpTraceAll, cmd/logger/audit.go,
cmd/consolelogger.go)."""

import json
import threading
import time

import pytest

from conftest import needs_crypto

from minio_tpu.erasure.engine import ErasureObjects
from minio_tpu.logger import Logger
from minio_tpu.logger.audit import AuditWebhook, audit_entry
from minio_tpu.s3.client import S3Client
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.xl import XLStorage
from minio_tpu.utils.pubsub import PubSub

ACCESS, SECRET = "obsadmin", "obsadmin-secret"


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    root = tmp_path_factory.mktemp("obsdisks")
    disks = [XLStorage(str(root / f"d{i}")) for i in range(4)]
    layer = ErasureObjects(disks, block_size=64 * 1024)
    srv = S3Server(layer, ACCESS, SECRET)
    port = srv.start()
    yield srv, port
    srv.stop()


@pytest.fixture
def client(server):
    _, port = server
    return S3Client("127.0.0.1", port, ACCESS, SECRET)


def test_pubsub_fanout_and_drop():
    hub = PubSub(buffer=4)
    a, b = hub.subscribe(), hub.subscribe()
    for i in range(10):
        hub.publish(i)
    # Bounded queues: only the first 4 survive per subscriber.
    got_a = [a.get_nowait() for _ in range(a.qsize())]
    got_b = [b.get_nowait() for _ in range(b.qsize())]
    assert got_a == got_b == [0, 1, 2, 3]
    hub.unsubscribe(a)
    hub.publish(99)
    assert a.qsize() == 0 and b.qsize() == 1


def test_admin_trace_captures_requests(server, client):
    """Subscribe via admin trace, fire S3 traffic from another thread,
    see the entries."""
    client.make_bucket("traceb")

    def later():
        time.sleep(0.3)
        client.put_object("traceb", "t.txt", b"traced")
        client.get_object("traceb", "t.txt")

    t = threading.Thread(target=later)
    t.start()
    r = client.request("GET", "/minio-tpu/admin/v1/trace",
                       query="timeout=2")
    t.join()
    assert r.status == 200
    entries = json.loads(r.body)["entries"]
    apis = [(e["method"], e["api"]) for e in entries]
    assert ("PUT", "PUT-object") in apis
    assert ("GET", "GET-object") in apis
    e = next(e for e in entries if e["api"] == "PUT-object")
    assert e["path"] == "/traceb/t.txt"
    assert e["statusCode"] == 200
    assert e["rx"] == 6 and e["durationMs"] > 0


def test_trace_not_published_without_subscribers(server, client):
    srv, _ = server
    assert srv.trace_hub.subscriber_count == 0
    client.make_bucket("notrace")  # must not error / leak


def test_console_log_ring(server, client):
    log = Logger.get()
    log.info("observability test message")
    log.log_once("dup-error")
    log.log_once("dup-error")  # deduped
    r = client.request("GET", "/minio-tpu/admin/v1/console-log",
                       query="n=50")
    entries = json.loads(r.body)["entries"]
    msgs = [e["message"] for e in entries]
    assert "observability test message" in msgs
    assert msgs.count("dup-error") == 1


def test_audit_webhook_delivery(server, client):
    """Point the audit sink at a local HTTP server, fire a request,
    expect an entry with the reference's field shape."""
    from http.server import BaseHTTPRequestHandler, HTTPServer
    got = []

    class Sink(BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            got.append(json.loads(self.rfile.read(n)))
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    sink = HTTPServer(("127.0.0.1", 0), Sink)
    threading.Thread(target=sink.serve_forever, daemon=True).start()
    srv, _ = server
    srv.audit = AuditWebhook(
        f"http://127.0.0.1:{sink.server_address[1]}/audit")
    try:
        client.make_bucket("auditb")
        client.put_object("auditb", "a.txt", b"x")
        deadline = time.time() + 5
        while time.time() < deadline and not any(
                e["api"]["name"] == "PUT-object" for e in got):
            time.sleep(0.05)
        entry = next(e for e in got if e["api"]["name"] == "PUT-object")
        assert entry["api"]["method"] == "PUT"
        assert entry["api"]["path"] == "/auditb/a.txt"
        assert entry["api"]["statusCode"] == 200
        assert entry["version"] == "1"
        assert entry["requestID"]
    finally:
        srv.audit.close()
        srv.audit = None
        sink.shutdown()


def test_audit_entry_shape():
    e = audit_entry("GET-object", "GET", "/b/k", 200, 12.5, 0, 100,
                    request_id="RID")
    assert e["api"]["timeToResponseNs"] == 12_500_000
    assert e["api"]["rx"] == 0 and e["api"]["tx"] == 100


# ---------------------------------------------------------------------------
# Metrics v2 + span tracing (obs/): span tree assembly, RPC trace
# propagation, kernel counters, Prometheus endpoints, and the obs lint.
# Engine-level fixtures on purpose: they exercise the same spans the S3
# handler threads through, without needing optional crypto deps.

import http.client
import os
import re

from minio_tpu.erasure.engine import ErasureObjects as _EO
from minio_tpu.obs import metrics2 as m2
from minio_tpu.obs.kernel_stats import KERNEL
from minio_tpu.obs.span import (MAX_CHILDREN, MAX_ROOT_CHILDREN, TRACER,
                                Span)


def _walk(node, depth=0, out=None):
    out = [] if out is None else out
    out.append((depth, node["name"], node.get("traceId")))
    for c in node.get("children", []):
        _walk(c, depth + 1, out)
    return out


def _engine(tmp_path, n=4):
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(n)]
    return _EO(disks, block_size=16 * 1024)


def _traced(fn, trace_id):
    root = TRACER.begin("test.op", trace_id)
    root.__enter__()
    fn()
    return root.finish()


def test_span_tree_covers_put_layers(tmp_path):
    eng = _engine(tmp_path / "sp")
    eng.make_bucket("b")
    tree = _traced(lambda: eng.put_object("b", "k", b"x" * 100_000),
                   "TRACEPUT")
    names = [n for _, n, _ in _walk(tree)]
    # Handler-root -> encode (with kernel child) -> per-disk writes ->
    # per-disk commits, all under ONE trace id.
    assert "ec.encode" in names
    assert "kernel.rs_encode" in names
    assert names.count("ec.shard_write") == 4
    assert names.count("ec.shard_commit") == 4
    assert all(t == "TRACEPUT" for _, _, t in _walk(tree))
    # Child durations are real measurements that fit inside the root.
    top = tree["children"]
    assert all(c["durationMs"] >= 0 for c in top)
    assert sum(c["durationMs"] for c in top) <= tree["durationMs"] * 1.1


def test_span_tree_get_reads(tmp_path):
    eng = _engine(tmp_path / "sg")
    eng.make_bucket("b")
    eng.put_object("b", "k", b"y" * 100_000)
    tree = _traced(lambda: eng.get_object("b", "k"), "TRACEGET")
    names = [n for _, n, _ in _walk(tree)]
    assert "ec.shard_read" in names
    assert "disk.read_file" in names


def test_span_tree_concurrent_put_get(tmp_path):
    """Concurrent requests must produce DISJOINT trees: every span in
    a request's tree carries that request's trace id only."""
    eng = _engine(tmp_path / "sc")
    eng.make_bucket("b")
    eng.put_object("b", "seed", b"s" * 50_000)
    trees = {}

    def worker(i):
        tid = f"CONC{i}"
        if i % 2 == 0:
            trees[tid] = _traced(
                lambda: eng.put_object("b", f"k{i}", b"z" * 60_000), tid)
        else:
            trees[tid] = _traced(
                lambda: eng.get_object("b", "seed"), tid)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(trees) == 8
    for tid, tree in trees.items():
        spans = _walk(tree)
        assert all(t == tid for _, _, t in spans), (tid, spans)
        names = [n for _, n, _ in spans]
        if int(tid[4:]) % 2 == 0:
            assert "ec.encode" in names


def test_trace_propagation_two_node_rpc(tmp_path):
    """A PUT through an engine with remote disks yields ONE stitched
    tree: the peer's server-side spans (with their local disk children)
    graft under the caller's rpc.storage.* spans, same trace id
    everywhere."""
    from minio_tpu.rpc.cluster import derive_cluster_key
    from minio_tpu.rpc.storage import RemoteStorage, StorageRPCService
    from minio_tpu.rpc.transport import RPCClient, RPCRegistry

    key = derive_cluster_key(ACCESS, SECRET)
    reg1 = RPCRegistry(key)
    remote = {str(tmp_path / "n1" / f"d{i}"):
              XLStorage(str(tmp_path / "n1" / f"d{i}"))
              for i in range(2)}
    reg1.register("storage", StorageRPCService(remote))
    srv1 = S3Server(None, ACCESS, SECRET, rpc_registry=reg1)
    port1 = srv1.start()
    try:
        client = RPCClient("127.0.0.1", port1, key)
        disks = [XLStorage(str(tmp_path / "n0" / f"d{i}"))
                 for i in range(2)]
        disks += [RemoteStorage(client, p) for p in remote]
        eng = _EO(disks, block_size=16 * 1024)
        eng.make_bucket("b")
        tree = _traced(
            lambda: eng.put_object("b", "k", b"w" * 80_000), "DIST1")
        spans = _walk(tree)
        assert all(t == "DIST1" for _, _, t in spans)
        names = [n for _, n, _ in spans]
        # Client-side RPC spans for the remote shard writes...
        assert "rpc.storage.append_file" in names
        # ...with the peer's server-side subtree grafted under them...
        assert "rpc.server.storage.append_file" in names
        assert "rpc.server.storage.rename_data" in names
        # ...down to the remote node's actual disk work.
        srv_append = [i for i, (_, n, _) in enumerate(spans)
                      if n == "rpc.server.storage.append_file"]
        assert srv_append, spans
        d0, _, _ = spans[srv_append[0]]
        assert (d0 + 1, "disk.append_file", "DIST1") in spans
        # Local shard writes appear too (2 local + 2 remote disks).
        assert names.count("ec.shard_write") == 4
    finally:
        srv1.stop()


def test_kernel_counters_monotonic():
    """Kernel counters only ever increase, and host RS encode/decode
    activity lands under kernel=rs_encode/rs_decode, device=host."""
    import numpy as np

    from minio_tpu.ops import batching

    lbl_enc = {"kernel": "rs_encode", "device": "host"}
    before_inv = m2.METRICS2.get(
        "minio_tpu_v2_kernel_invocations_total", lbl_enc)
    before_bytes = m2.METRICS2.get(
        "minio_tpu_v2_kernel_bytes_total", lbl_enc)
    blocks = np.random.default_rng(0).integers(
        0, 256, (4, 2, 512), dtype=np.uint8)
    encoded = batching.host_encode(blocks, 2, 2)
    mid_inv = m2.METRICS2.get(
        "minio_tpu_v2_kernel_invocations_total", lbl_enc)
    assert mid_inv == before_inv + 1
    assert m2.METRICS2.get("minio_tpu_v2_kernel_bytes_total",
                           lbl_enc) == before_bytes + blocks.nbytes
    # Reconstruction with a lost shard counts rs_decode.
    lbl_dec = {"kernel": "rs_decode", "device": "host"}
    before_dec = m2.METRICS2.get(
        "minio_tpu_v2_kernel_invocations_total", lbl_dec)
    damaged = [[None] + [encoded[b, j] for j in range(1, 4)]
               for b in range(4)]
    out = batching.reconstruct_blocks(damaged, 2, 2, want_all=False,
                                      use_device=lambda n: False)
    assert all(o[0] is not None for o in out)
    after_dec = m2.METRICS2.get(
        "minio_tpu_v2_kernel_invocations_total", lbl_dec)
    assert after_dec == before_dec + 1
    # Monotonic: re-reading never goes down.
    assert m2.METRICS2.get(
        "minio_tpu_v2_kernel_invocations_total", lbl_enc) >= mid_inv
    snap = KERNEL.snapshot()
    assert snap["rs_encode/host"]["invocations"] >= 1
    # Wall time is kernel_dispatch_ms (per kernel x backend); the
    # duplicate kernel_wall_seconds_total counter is gone.
    assert "wall_seconds" not in snap["rs_encode/host"]
    walls = m2.METRICS2.snapshot()["minio_tpu_v2_kernel_dispatch_ms"]
    assert sum(s["sum"] for s in walls["series"]
               if s["labels"]["kernel"] == "rs_encode") > 0


def test_metrics2_rejects_unregistered_names():
    with pytest.raises(ValueError):
        m2.METRICS2.inc("minio_tpu_v2_not_a_metric_total")
    with pytest.raises(ValueError):
        m2.METRICS2.observe("minio_tpu_v2_also_not_real", None, 1.0)


def test_metrics2_merge_sums_nodes():
    a = m2.MetricsV2()
    b = m2.MetricsV2()
    for r in (a, b):
        r.register("minio_tpu_v2_api_requests_total", "counter", "x")
        r.register("minio_tpu_v2_api_request_duration_ms", "histogram",
                   "y", buckets=(1, 10))
    a.inc("minio_tpu_v2_api_requests_total", {"api": "PUT"}, 3)
    b.inc("minio_tpu_v2_api_requests_total", {"api": "PUT"}, 4)
    b.inc("minio_tpu_v2_api_requests_total", {"api": "GET"}, 1)
    a.observe("minio_tpu_v2_api_request_duration_ms", {"api": "PUT"},
              0.5)
    b.observe("minio_tpu_v2_api_request_duration_ms", {"api": "PUT"},
              5.0)
    merged = m2.merge(a.snapshot(), b.snapshot())
    series = {tuple(sorted(s["labels"].items())): s
              for s in merged["minio_tpu_v2_api_requests_total"]
              ["series"]}
    assert series[(("api", "PUT"),)]["value"] == 7
    assert series[(("api", "GET"),)]["value"] == 1
    hist = merged["minio_tpu_v2_api_request_duration_ms"]["series"][0]
    assert hist["count"] == 2
    assert hist["counts"] == [1, 1, 0]


_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9]+(\.[0-9]+)?"
    r"([eE][+-][0-9]+)?$")


def _check_prometheus(text: str) -> None:
    """Structural validity of a text exposition: TYPE'd families,
    well-formed samples, cumulative histogram buckets capped by
    _count."""
    typed: dict[str, str] = {}
    hist_cum: dict[str, int] = {}
    for line in text.strip().split("\n"):
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, name, mtype = line.split(" ", 3)
            assert mtype in ("counter", "gauge", "histogram"), line
            typed[name] = mtype
            continue
        assert _SAMPLE_RE.match(line), f"bad sample line: {line!r}"
        name = re.split(r"[{ ]", line, 1)[0]
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if base.endswith(suffix) and base[:-len(suffix)] in typed:
                base = base[:-len(suffix)]
        assert base in typed, f"sample without TYPE: {line!r}"
        if name.endswith("_bucket"):
            series = line.split(" ")[0]
            val = int(float(line.rsplit(" ", 1)[1]))
            key = re.sub(r'le="[^"]*",?', "", series)
            assert val >= hist_cum.get(key, 0), \
                f"non-cumulative bucket: {line!r}"
            hist_cum[key] = val


def _http_get(port: int, path: str) -> tuple[int, str, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("GET", path)
    r = conn.getresponse()
    body = r.read()
    ctype = r.getheader("Content-Type", "")
    conn.close()
    return r.status, ctype, body


def test_v2_node_metrics_endpoint(tmp_path):
    # Populate a few series through the real recording paths.
    eng = _engine(tmp_path / "vm")
    eng.make_bucket("b")
    eng.put_object("b", "k", b"m" * 50_000)
    srv = S3Server(None, ACCESS, SECRET)
    port = srv.start()
    try:
        status, ctype, body = _http_get(port,
                                        "/minio-tpu/v2/metrics/node")
        assert status == 200
        assert ctype.startswith("text/plain")
        text = body.decode()
        _check_prometheus(text)
        assert "minio_tpu_v2_disk_op_duration_ms_bucket" in text
        assert "minio_tpu_v2_kernel_invocations_total" in text
        assert "minio_tpu_v2_put_phase_duration_ms_bucket" in text
    finally:
        srv.stop()


def test_v2_cluster_metrics_endpoint_two_nodes(tmp_path):
    """The cluster endpoint scrapes peers over the metrics2 RPC and
    returns merged counters in valid Prometheus text."""
    from minio_tpu.rpc.cluster import derive_cluster_key
    from minio_tpu.rpc.peer import NotificationSys, PeerRPCService
    from minio_tpu.rpc.transport import RPCClient, RPCRegistry

    key = derive_cluster_key(ACCESS, SECRET)
    reg1 = RPCRegistry(key)
    reg1.register("peer", PeerRPCService("topo"))
    srv1 = S3Server(None, ACCESS, SECRET, rpc_registry=reg1)
    port1 = srv1.start()
    srv0 = S3Server(None, ACCESS, SECRET)
    srv0.notification = NotificationSys(
        {f"127.0.0.1:{port1}": RPCClient("127.0.0.1", port1, key)})
    port0 = srv0.start()
    try:
        m2.METRICS2.inc("minio_tpu_v2_api_requests_total",
                        {"api": "PUT-object", "status": 200})
        status, _, body = _http_get(port0,
                                    "/minio-tpu/v2/metrics/cluster")
        assert status == 200
        text = body.decode()
        _check_prometheus(text)
        assert "minio_tpu_v2_cluster_nodes 2" in text
        # Merged counters are present and at least the local value
        # (both in-process nodes share the registry, so the cluster
        # view sums to >= the node view).
        node_text = _http_get(port0,
                              "/minio-tpu/v2/metrics/node")[2].decode()

        def val(txt):
            for line in txt.split("\n"):
                if line.startswith(
                        "minio_tpu_v2_api_requests_total") and \
                        'api="PUT-object"' in line:
                    return float(line.rsplit(" ", 1)[1])
            return 0.0

        assert val(text) >= val(node_text) > 0
    finally:
        srv0.stop()
        srv1.stop()


def test_trace_ring_and_children_bounded():
    TRACER.reset()
    for i in range(TRACER.RING_SIZE + 50):
        root = TRACER.begin("ring.test", f"R{i}")
        root.__enter__()
        root.finish()
    assert len(TRACER.recent(10_000)) == TRACER.RING_SIZE
    # Child cap: a pathological span fan-out drops the tail, counted.
    root = TRACER.begin("cap.test", "CAP")
    root.__enter__()
    # (A root holds the request's phases and has the wider cap.)
    for _ in range(MAX_ROOT_CHILDREN + 25):
        with TRACER.span("child"):
            pass
    with TRACER.span("fan") as fan:
        for _ in range(MAX_CHILDREN + 7):
            with TRACER.span("leaf"):
                pass
    tree = root.finish()
    assert len(tree["children"]) == MAX_ROOT_CHILDREN
    assert tree["droppedChildren"] == 25 + 1   # `fan` came too late
    assert len(fan.to_dict()["children"]) == MAX_CHILDREN
    assert fan.to_dict()["droppedChildren"] == 7


def test_span_noop_without_active_trace():
    """No active trace -> span() returns the shared no-op (the <=5%%
    overhead path) and records nothing."""
    assert TRACER.current() is None
    cm = TRACER.span("anything", bytes=123)
    with cm as s:
        assert s is None


def test_rpc_trace_header_ignored_when_absent(tmp_path):
    """Untraced RPC calls carry no trace header and the server adds no
    _trace_spans key (zero overhead off the traced path)."""
    from minio_tpu.rpc.cluster import derive_cluster_key
    from minio_tpu.rpc.transport import RPCRegistry, frame, sign
    import time as _time

    key = derive_cluster_key(ACCESS, SECRET)
    reg = RPCRegistry(key)

    class Echo:
        def rpc_ping(self, args, payload):
            return {"pong": True}, b""

    reg.register("echo", Echo())
    args_json = "{}"
    ts = str(int(_time.time()))
    status, _, body = reg.handle(
        "/minio-tpu/rpc/v1/echo/ping",
        {"x-mtpu-ts": ts,
         "x-mtpu-auth": sign(key, "echo/ping", ts, args_json, b"")},
        frame(args_json.encode(), b""))
    assert status == 200
    result = json.loads(body[4:4 + int.from_bytes(body[:4], "big")])
    assert result == {"pong": True}


def test_obs_lint_clean():
    """The tier-1 lint gate: no bare asserts in native/, no
    unregistered metrics-v2 names anywhere in the package."""
    import tools.obs_lint as lint
    assert lint.main() == 0


def test_obs_lint_rule5_catches_bad_calls(tmp_path):
    """Rule 5 flags dynamic and unregistered names in drivemon/slowlog
    recording calls (the unit the rule checks is the CALL, so rule 2's
    literal scan can't substitute)."""
    import tools.obs_lint as lint
    bad = tmp_path / "bad.py"
    bad.write_text(
        "METRICS2.inc(name)\n"
        "METRICS2.observe('minio_tpu_v2_not_registered_xx', None, 1)\n"
        "METRICS2.set_gauge('minio_tpu_v2_drive_state', None, 1)\n")
    v = lint._check_literal_metric_calls([str(bad)], "drivemon/slowlog")
    assert len(v) == 2  # line 3 is literal AND registered
    assert any("literal" in x for x in v)
    assert any("not registered" in x for x in v)
    # And the wired rule itself is clean on the real tree.
    assert lint.check_drivemon_slowlog_metric_calls() == []


# ---------------------------------------------------------------------------
# Drive-health monitor (obs/drivemon.py)

from minio_tpu.obs.drivemon import DRIVEMON, DriveMonitor, is_drive_fault


def _fill_windows(mon, eps, slow_ep, windows, slow_ms=60.0, fast_ms=1.0):
    for _ in range(windows * mon.WINDOW_OPS):
        for ep in eps:
            mon.record(ep, "read_file",
                       slow_ms if ep == slow_ep else fast_ms)


def test_drivemon_flags_peer_relative_outlier():
    """One drive consistently k-times slower than its set peers goes
    suspect after SUSPECT_WINDOWS windows; the peers stay ok."""
    mon = DriveMonitor()
    eps = [f"/dmtest/a/d{i}" for i in range(4)]
    mon.register_set(eps)
    _fill_windows(mon, eps, eps[0], mon.SUSPECT_WINDOWS + 1)
    snap = mon.snapshot()
    states = {d["endpoint"]: d["state"] for d in snap["drives"]}
    assert states[eps[0]] == "suspect"
    assert all(states[e] == "ok" for e in eps[1:])
    assert snap["suspect"] == 1 and snap["faulty"] == 0
    # Latency attribution is per op class.
    assert mon.ewma_for(eps[0])["read"] > \
        3 * mon.ewma_for(eps[1])["read"]


def test_drivemon_recovers_when_latency_normalizes():
    mon = DriveMonitor()
    eps = [f"/dmtest/b/d{i}" for i in range(4)]
    mon.register_set(eps)
    _fill_windows(mon, eps, eps[0], mon.SUSPECT_WINDOWS + 1)
    assert mon.state_of(eps[0]) == "suspect"
    # Drive replaced / contention gone: healthy windows decay the
    # EWMA back under OUTLIER_K x the peer median and the state clears
    # (alpha=0.3 -> ~10 windows to fall from 60x to <3x).
    _fill_windows(mon, eps, slow_ep=None, windows=14)
    assert mon.state_of(eps[0]) == "ok"


def test_drivemon_faulty_on_sustained_errors():
    mon = DriveMonitor()
    eps = [f"/dmtest/c/d{i}" for i in range(3)]
    mon.register_set(eps)
    for _ in range(mon.FAULTY_WINDOWS * mon.WINDOW_OPS):
        mon.record(eps[0], "write_all", 1.0, error=True)
        for ep in eps[1:]:
            mon.record(ep, "write_all", 1.0)
    assert mon.state_of(eps[0]) == "faulty"
    assert all(mon.state_of(e) == "ok" for e in eps[1:])
    # Transition counters landed in metrics2 under the REDACTED drive
    # identity (the metrics pages are unauthenticated surfaces).
    from minio_tpu.obs.drivemon import redacted_endpoint
    red = redacted_endpoint(eps[0])
    assert m2.METRICS2.get("minio_tpu_v2_drive_state_transitions_total",
                           {"disk": red, "state": "faulty"}) >= 1
    assert m2.METRICS2.get("minio_tpu_v2_drive_state",
                           {"disk": red}) == 2


def test_drivemon_dominance_shields_starved_bystander():
    """While a genuinely slow drive exists, a moderately-elevated
    healthy drive (scheduler starvation on a loaded host) must NOT
    co-flag: a suspect has to dominate the WORST peer, and the real
    laggard owns that slot."""
    mon = DriveMonitor()
    eps = [f"/dmtest/dom/d{i}" for i in range(5)]
    mon.register_set(eps)
    lat = {eps[0]: 60.0,   # the real laggard
           eps[1]: 20.0}   # starved bystander: 20x the median, but
    for _ in range(4 * mon.WINDOW_OPS):  # not 1.5x the laggard
        for ep in eps:
            mon.record(ep, "read_file", lat.get(ep, 1.0))
    assert mon.state_of(eps[0]) == "suspect"
    assert mon.state_of(eps[1]) == "ok"
    assert all(mon.state_of(e) == "ok" for e in eps[2:])


def test_drivemon_lone_drive_never_suspect():
    """No peers -> no outlier scoring (a single-drive group has no one
    to be slow relative to)."""
    mon = DriveMonitor()
    for _ in range(6 * mon.WINDOW_OPS):
        mon.record("/dmtest/lone", "read_all", 500.0)
    assert mon.state_of("/dmtest/lone") == "ok"


def test_drivemon_benign_errors_do_not_count():
    from minio_tpu.storage import errors as serr
    assert not is_drive_fault(serr.FileNotFound("x"))
    assert not is_drive_fault(serr.VolumeNotFound)
    assert not is_drive_fault(FileNotFoundError("x"))
    assert not is_drive_fault(None)
    assert is_drive_fault(serr.FaultyDisk("io error"))
    assert is_drive_fault(OSError("io"))


def test_drivemon_records_through_real_disk_ops(tmp_path):
    """The storage _DiskOp boundary feeds the monitor: real engine
    traffic shows up under the disks' endpoints."""
    eng = _engine(tmp_path / "dm")
    eng.make_bucket("b")
    eng.put_object("b", "k", b"d" * 50_000)
    eng.get_object("b", "k")
    snap = DRIVEMON.snapshot()
    mine = [d for d in snap["drives"]
            if d["endpoint"].startswith(str(tmp_path / "dm"))]
    assert len(mine) == 4
    assert all(d["opsTotal"] > 0 for d in mine)
    # All four disks of the set share one peer group.
    assert len({d["set"] for d in mine}) == 1


def test_drives_health_endpoints_node_and_cluster(tmp_path):
    """/minio-tpu/v2/health/drives serves the node snapshot; the
    cluster variant fan-in merges peers exactly like metrics2."""
    from minio_tpu.rpc.cluster import derive_cluster_key
    from minio_tpu.rpc.peer import NotificationSys, PeerRPCService
    from minio_tpu.rpc.transport import RPCClient, RPCRegistry

    eng = _engine(tmp_path / "hd")
    eng.make_bucket("b")
    eng.put_object("b", "k", b"h" * 30_000)

    key = derive_cluster_key(ACCESS, SECRET)
    reg1 = RPCRegistry(key)
    reg1.register("peer", PeerRPCService("topo"))
    srv1 = S3Server(None, ACCESS, SECRET, rpc_registry=reg1)
    port1 = srv1.start()
    srv0 = S3Server(None, ACCESS, SECRET)
    srv0.notification = NotificationSys(
        {f"127.0.0.1:{port1}": RPCClient("127.0.0.1", port1, key)})
    port0 = srv0.start()
    try:
        from minio_tpu.obs.drivemon import redacted_endpoint
        status, ctype, body = _http_get(port0,
                                        "/minio-tpu/v2/health/drives")
        assert status == 200 and ctype.startswith("application/json")
        node = json.loads(body)
        eps = {d["endpoint"] for d in node["drives"]}
        # The unauthenticated surface serves REDACTED identities —
        # never the absolute on-disk paths.
        assert not any(e.startswith(str(tmp_path)) for e in eps)
        assert redacted_endpoint(str(tmp_path / "hd" / "d0")) in eps
        assert {"suspect", "faulty"} <= set(node)

        status, _, body = _http_get(
            port0, "/minio-tpu/v2/health/cluster/drives")
        assert status == 200
        cluster = json.loads(body)
        assert cluster["nodes"] == 2
        # Every drive row is annotated with the node it came from
        # (peers as stable ordinals, not internal host:port).
        assert all("node" in d for d in cluster["drives"])
        assert any(d["node"] == "local" for d in cluster["drives"])
        assert not any(":" in d["node"] for d in cluster["drives"])
        # The authenticated admin route keeps the full endpoints.
        full = srv0.admin.h_drive_health({}, b"")
        assert any(d["endpoint"].startswith(str(tmp_path / "hd"))
                   for d in full["drives"])
    finally:
        srv0.stop()
        srv1.stop()


# ---------------------------------------------------------------------------
# Slow-request log (obs/slowlog.py)

from minio_tpu.obs.slowlog import SLOWLOG, SlowLog, blame_layers, \
    blamed_layer


def test_blame_attribution_self_times():
    tree = {
        "name": "PUT-object", "durationMs": 100.0,
        "children": [
            {"name": "auth.sigv4", "durationMs": 2.0},
            {"name": "ec.encode", "durationMs": 10.0, "children": [
                {"name": "kernel.rs_encode", "durationMs": 8.0}]},
            {"name": "ec.write", "durationMs": 70.0, "children": [
                {"name": "ec.shard_write", "durationMs": 65.0,
                 "children": [
                     {"name": "disk.append_file", "durationMs": 60.0}]},
            ]},
        ],
    }
    totals = blame_layers(tree, admission_wait_ms=3.0)
    assert blamed_layer(totals) == "disk"
    # disk = shard_write self (65-60) + disk.append self (60)
    assert totals["disk"] == pytest.approx(65.0)
    # encode-kernel = ec.encode self (2) + kernel self (8)
    assert totals["encode-kernel"] == pytest.approx(10.0)
    # client-stream = root self (18) MINUS the admission wait that
    # elapsed inside the root (3) + auth (2) + ec.write self (5),
    # the latter two inheriting the root's bucket.
    assert totals["client-stream"] == pytest.approx(22.0)
    assert totals["admission-wait"] == pytest.approx(3.0)
    # rpc spans bucket as rpc, grafted remote disk work as disk.
    rpc_tree = {"name": "GET-object", "durationMs": 50.0, "children": [
        {"name": "rpc.storage.read_file", "durationMs": 45.0,
         "children": [
             {"name": "rpc.server.storage.read_file",
              "durationMs": 20.0, "children": [
                  {"name": "disk.read_file", "durationMs": 18.0}]}]}]}
    t2 = blame_layers(rpc_tree)
    assert t2["rpc"] == pytest.approx(45.0 - 20.0 + 2.0)
    assert t2["disk"] == pytest.approx(18.0)
    assert blamed_layer(t2) == "rpc"
    # No trace at all -> other (unless admission wait dominates).
    assert blamed_layer(blame_layers(None)) == "other"
    assert blamed_layer(blame_layers(None, 5.0)) == "admission-wait"


def test_slowlog_capture_rules():
    sl = SlowLog()
    sl.configure(100.0, {"write": 50.0}, False)
    common = dict(api="GET-object", method="GET", path="/b/k",
                  request_id="R1")
    # Fast + 2xx: not captured.
    assert sl.record(api_class="read", status=200, duration_ms=10.0,
                     **common) is None
    # Over the class SLO: captured, slow-flagged.
    e = sl.record(api_class="write", status=200, duration_ms=60.0,
                  **common)
    assert e is not None and e["slow"] and e["thresholdMs"] == 50.0
    # 5xx under the SLO: captured anyway.
    e = sl.record(api_class="read", status=500, duration_ms=5.0,
                  **common)
    assert e is not None and not e["slow"]
    # Deliberate backpressure: exempt even at 503 + slow.
    assert sl.record(api_class="write", status=503, duration_ms=999.0,
                     exempt=True, **common) is None
    assert sl.total == 2
    assert len(sl.entries(10)) == 2
    # Filters.
    assert len(sl.entries(10, api="write")) == 1
    assert all(x["blamedLayer"] == "other"
               for x in sl.entries(10, blame="other"))
    # Ring bounded.
    for i in range(sl.RING_SIZE + 40):
        sl.record(api_class="read", status=500, duration_ms=1.0,
                  api="GET-object", method="GET", path=f"/b/k{i}")
    assert len(sl.entries(10_000)) == sl.RING_SIZE
    assert sl.total == 2 + sl.RING_SIZE + 40


def test_slowlog_qos_wait_blames_admission():
    sl = SlowLog()
    sl.configure(10.0, {}, False)
    e = sl.record(api="PUT-object", api_class="write", method="PUT",
                  path="/b/k", status=200, duration_ms=80.0,
                  qos={"class": "write", "waitMs": 70.0,
                       "deadlineS": 10.0})
    assert e["blamedLayer"] == "admission-wait"
    assert e["qos"]["waitMs"] == 70.0


def test_slowlog_end_to_end_with_admin_endpoint(server, client):
    """Full stack: a live-reloaded 1ms SLO captures a real PUT with
    its span tree + blame; the admin /slowlog endpoint serves and
    filters it; audit fields join against it."""
    srv, _ = server
    sent = []

    class _AuditStub:
        endpoint = "stub"
        sent_n = failed = dropped = 0

        def send(self, entry):
            sent.append(entry)

        def close(self):
            pass

    # Mark the stub env-configured so the set_kv apply hook (which
    # tears down config-owned sinks when audit_webhook is off) keeps it.
    old_audit, old_env = srv.audit, srv._audit_from_env
    srv.audit, srv._audit_from_env = _AuditStub(), True
    try:
        srv.config.set_kv("obs slow_ms=1")
        assert SLOWLOG.threshold_ms("write") == 1.0
        client.make_bucket("slowlogb")
        r = client.put_object("slowlogb", "s.txt", b"slow-capture")
        assert r.status == 200
        res = client.request("GET", "/minio-tpu/admin/v1/slowlog",
                             query="api=write&n=50")
        assert res.status == 200
        doc = json.loads(res.body)
        assert doc["thresholdsMs"]["default"] == 1.0
        entry = next(e for e in doc["entries"]
                     if e["path"] == "/slowlogb/s.txt")
        assert entry["apiClass"] == "write" and entry["slow"]
        assert entry["blamedLayer"] in (
            "disk", "client-stream", "encode-kernel")
        assert entry["spans"]["traceId"] == entry["requestID"]
        assert entry["qos"]["class"] == "write"
        # Blame filter excludes non-matching layers.
        res = client.request("GET", "/minio-tpu/admin/v1/slowlog",
                             query="blame=rpc")
        assert all(e["blamedLayer"] == "rpc"
                   for e in json.loads(res.body)["entries"])
        # The blame histogram counted it.
        total = m2.METRICS2.get(
            "minio_tpu_v2_slow_requests_total",
            {"class": "write", "blame": entry["blamedLayer"]})
        assert total >= 1
        # Audit satellite: the webhook entry carries the join keys.
        audit = next(a for a in sent
                     if a["api"]["path"] == "/slowlogb/s.txt")
        assert audit["trace_id"] == entry["requestID"]
        assert audit["qos_class"] == "write"
        assert audit["blamed_layer"] == entry["blamedLayer"]
    finally:
        srv.config.set_kv("obs slow_ms=1000")
        srv.audit, srv._audit_from_env = old_audit, old_env


def test_slowlog_profile_on_slow_burst(monkeypatch):
    sl = SlowLog()
    monkeypatch.setattr(SlowLog, "PROFILE_BURST_S", 0.1)
    sl.configure(1.0, {}, True)
    for i in range(sl.PROFILE_TRIGGER):
        sl.record(api="GET-object", api_class="read", method="GET",
                  path=f"/b/p{i}", status=200, duration_ms=50.0)
    deadline = time.time() + 5
    while time.time() < deadline and sl.last_profile is None:
        time.sleep(0.02)
    assert sl.last_profile is not None
    assert sl.last_profile["report"]["samples"] >= 0
    assert "self" in sl.last_profile["report"]


def test_audit_status_reports_queue_and_drops(server, client):
    srv, _ = server
    old = srv.audit
    srv.audit = AuditWebhook("http://127.0.0.1:1/never", queue_size=1)
    try:
        r = client.request("GET", "/minio-tpu/admin/v1/audit-status")
        doc = json.loads(r.body)
        assert doc["configured"]
        assert {"sent", "failed", "dropped", "queued"} <= set(doc)
    finally:
        srv.audit.close()
        srv.audit = old


def test_profiling_start_cleans_up_on_peer_fanout_failure(server):
    """Satellite regression: a raising cluster fan-out must not leave
    the local profiler stuck in 'profiling already running'."""
    srv, _ = server

    class BoomNotif:
        def profiling_start_all(self, interval_ms):
            raise RuntimeError("peer fan-out exploded")

    old = srv.notification
    srv.notification = BoomNotif()
    try:
        with pytest.raises(RuntimeError):
            srv.admin.h_profiling_start({"cluster": "true"}, b"")
        assert getattr(srv.admin, "_profiler", None) is None
        # Not stuck: a plain start now succeeds and stops cleanly.
        srv.notification = None
        assert srv.admin.h_profiling_start({}, b"")["ok"]
        out = srv.admin.h_profiling_stop({}, b"")
        assert "profile" in out
    finally:
        srv.notification = old


def test_phasetimer_feeds_metrics2():
    from minio_tpu.utils.phasetimer import PUT
    before = m2.METRICS2.get("minio_tpu_v2_put_phase_duration_ms",
                             {"phase": "obs_test_phase"})
    PUT.record("obs_test_phase", 2.5)
    after = m2.METRICS2.get("minio_tpu_v2_put_phase_duration_ms",
                            {"phase": "obs_test_phase"})
    assert after == (before[0] + 2.5, before[1] + 1)


@needs_crypto
def test_s3_trace_entry_carries_spans(server, client):
    """Full-stack: an S3 PUT published to the trace hub carries the
    span tree alongside the flat entry (needs the full handler stack)."""
    client.make_bucket("spanb")

    def later():
        time.sleep(0.3)
        client.put_object("spanb", "s.txt", b"span-traced")

    t = threading.Thread(target=later)
    t.start()
    r = client.request("GET", "/minio-tpu/admin/v1/trace",
                       query="timeout=2")
    t.join()
    entries = json.loads(r.body)["entries"]
    e = next(e for e in entries if e["api"] == "PUT-object"
             and e["path"] == "/spanb/s.txt")
    spans = e["spans"]
    assert spans["traceId"] == e["requestID"]
    names = [n for _, n, _ in _walk(spans)]
    assert "auth.sigv4" in names
    assert "ec.encode" in names
    assert "kernel.rs_encode" in names
    assert names.count("ec.shard_write") == 4
    assert spans["tags"]["statusCode"] == 200
