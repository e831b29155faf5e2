"""QoS subsystem tests: admission control (503 SlowDown + Retry-After
under overload, FIFO drain, live config reload), deadline propagation
(slow remote storage calls cancel; expired budgets never reach the
peer), and priority lanes (background heal defers to foreground but is
never starved). All fast — tier-1."""

import os
import threading
import time

import pytest

from minio_tpu.erasure.engine import ErasureObjects
from minio_tpu.obs.metrics2 import METRICS2
from minio_tpu.qos.admission import (AdmissionController, AdmissionShed,
                                     QUEUE_FACTOR, classify)
from minio_tpu.qos.deadline import (Deadline, DeadlineExceeded,
                                    open_deadline, parse_duration)
from minio_tpu.qos.scheduler import (BACKGROUND, FOREGROUND,
                                     PriorityGate, background_lane,
                                     current_lane)
from minio_tpu.s3.client import S3Client
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.xl import XLStorage

ACCESS, SECRET = "qosadmin1", "qosadmin-secret"


# ---------------- helpers ----------------


def _start_server(tmp_path, n_disks=4, k=2, m=2):
    disks = [XLStorage(str(tmp_path / f"disk{i}")) for i in range(n_disks)]
    layer = ErasureObjects(disks, k, m, block_size=256 * 1024)
    srv = S3Server(layer, ACCESS, SECRET)
    port = srv.start()
    return srv, S3Client("127.0.0.1", port, ACCESS, SECRET)


class _SlowDisk:
    """Delay-injecting disk wrapper (the fault-harness hook style of
    tests/test_engine.py's NaughtyDisk): every call sleeps `delay`."""

    def __init__(self, inner, delay=0.0):
        self.inner = inner
        self.delay = delay
        self.calls = 0

    def __getattr__(self, name):
        fn = getattr(self.inner, name)
        if not callable(fn):
            return fn

        def wrapped(*a, **kw):
            self.calls += 1
            if self.delay:
                time.sleep(self.delay)
            return fn(*a, **kw)
        return wrapped


# ---------------- unit: classify / durations ----------------


def test_deadline_engages_only_when_capped():
    """An unconfigured server opens NO execution deadline — a default
    10s budget must not quorum-commit partial writes under load."""
    c = AdmissionController()
    assert not c.engaged
    c.configure(0, {"write": 4}, 10.0)
    assert c.engaged
    c.configure(0, {}, 10.0)
    assert not c.engaged
    c.configure(16, {}, 10.0)
    assert c.engaged


def test_classify_api_classes():
    assert classify("GET", "bkt", "key") == "read"
    assert classify("HEAD", "bkt", "key") == "read"
    assert classify("PUT", "bkt", "key") == "write"
    assert classify("DELETE", "bkt", "key") == "write"
    assert classify("GET", "bkt", "") == "list"
    assert classify("PUT", "bkt", "") == "write"
    assert classify("GET", "", "") == "list"
    assert classify("POST", "", "") == "admin"


def test_parse_duration_forms():
    assert parse_duration("250ms") == pytest.approx(0.25)
    assert parse_duration("10s") == 10.0
    assert parse_duration("1m") == 60.0
    assert parse_duration("2.5") == 2.5
    assert parse_duration("") == 0.0
    with pytest.raises(ValueError):
        parse_duration("garbage")


# ---------------- unit: admission gates ----------------


def test_admission_over_cap_sheds_and_releases():
    c = AdmissionController()
    c.configure(0, {"write": 1}, 0.05)
    held = c.acquire("write", Deadline(0.05))
    with pytest.raises(AdmissionShed) as exc:
        c.acquire("write", Deadline(0.05))
    assert exc.value.reason == "wait-deadline"
    assert exc.value.retry_after >= 1
    with held:
        pass
    with c.acquire("write", Deadline(0.05)):  # slot free again
        assert c.foreground_inflight() == 1
    assert c.foreground_inflight() == 0


def test_admission_waiters_drain_fifo():
    c = AdmissionController()
    c.configure(0, {"write": 1}, 5.0)
    order = []
    hold = c.acquire("write", Deadline(5))

    def waiter(i):
        with c.acquire("write", Deadline(5)):
            order.append(i)
            time.sleep(0.01)

    threads = []
    for i in range(3):
        t = threading.Thread(target=waiter, args=(i,))
        t.start()
        threads.append(t)
        # Deterministic queue order: each waiter must be enqueued
        # before the next starts.
        deadline = time.monotonic() + 2
        while (c._classes["write"].queue_depth() < i + 1
               and time.monotonic() < deadline):
            time.sleep(0.002)
    hold.__exit__(None, None, None)
    for t in threads:
        t.join(timeout=5)
    assert order == [0, 1, 2]


def test_admission_queue_bounded():
    c = AdmissionController()
    c.configure(0, {"write": 1}, 30.0)
    hold = c.acquire("write", Deadline(30))
    gate = c._classes["write"]
    stop = threading.Event()

    def parked():
        try:
            with c.acquire("write", Deadline(30)):
                stop.wait(5)
        except AdmissionShed:
            pass

    threads = [threading.Thread(target=parked)
               for _ in range(QUEUE_FACTOR)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 2
    while (gate.queue_depth() < QUEUE_FACTOR
           and time.monotonic() < deadline):
        time.sleep(0.002)
    assert gate.queue_depth() == QUEUE_FACTOR
    with pytest.raises(AdmissionShed) as exc:
        c.acquire("write", Deadline(30))
    assert exc.value.reason == "queue-full"
    stop.set()
    hold.__exit__(None, None, None)
    for t in threads:
        t.join(timeout=5)


def test_global_cap_spans_classes():
    c = AdmissionController()
    c.configure(1, {}, 0.05)  # global cap 1, no per-class caps
    held = c.acquire("read", Deadline(0.05))
    with pytest.raises(AdmissionShed):
        c.acquire("write", Deadline(0.05))
    with held:
        pass
    with c.acquire("write", Deadline(0.05)):
        pass


def test_queued_class_waiters_hold_no_global_slot():
    """A request queued behind ITS class cap must not consume global
    capacity meanwhile — one flooded class cannot starve the others."""
    c = AdmissionController()
    c.configure(2, {"write": 1}, 5.0)
    held_write = c.acquire("write", Deadline(5))
    parked = threading.Event()

    def queued_write():
        try:
            with c.acquire("write", Deadline(5)):
                pass
        except AdmissionShed:
            pass

    t = threading.Thread(target=queued_write)
    t.start()
    deadline = time.monotonic() + 2
    while (c._classes["write"].queue_depth() < 1
           and time.monotonic() < deadline):
        time.sleep(0.002)
    # global: 1 running write + 1 QUEUED write; a read must still fit.
    with c.acquire("read", Deadline(0.2)):
        pass
    held_write.__exit__(None, None, None)
    t.join(timeout=5)
    parked.set()


def test_live_cap_raise_admits_all_waiters():
    """Raising a cap via config admits EVERY waiter it now covers, not
    just the queue head (the admit must re-notify)."""
    c = AdmissionController()
    c.configure(0, {"write": 1}, 30.0)
    held = c.acquire("write", Deadline(30))
    admitted = []
    release = threading.Event()

    def waiter(i):
        with c.acquire("write", Deadline(30)):
            admitted.append(i)
            release.wait(5)

    threads = [threading.Thread(target=waiter, args=(i,))
               for i in range(3)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 2
    while (c._classes["write"].queue_depth() < 3
           and time.monotonic() < deadline):
        time.sleep(0.002)
    c.configure(0, {"write": 8}, 30.0)  # live raise: room for everyone
    deadline = time.monotonic() + 2
    while len(admitted) < 3 and time.monotonic() < deadline:
        time.sleep(0.002)
    assert sorted(admitted) == [0, 1, 2]  # all admitted, no release
    release.set()
    held.__exit__(None, None, None)
    for t in threads:
        t.join(timeout=5)


def test_qos_context_crosses_quorum_pool():
    """Deadline and lane ride the quorum fan-out onto pool workers —
    a shard fan-out must stay deadline-capped and lane-tagged."""
    from minio_tpu.parallel import quorum
    from minio_tpu.qos.deadline import current_deadline

    seen = []

    def probe():
        dl = current_deadline()
        seen.append((threading.get_ident(), current_lane(),
                     None if dl is None else round(dl.remaining(), 1)))
        return True

    with open_deadline(5.0), background_lane():
        results, errs = quorum.parallel_map([probe] * 6)
    assert all(results) and not any(errs)
    assert all(lane == BACKGROUND for _, lane, _ in seen)
    assert all(rem is not None and rem > 0 for _, _, rem in seen)
    # And the default context pays no wrap (identity fast path).
    assert quorum._qos_ctx_wrap(probe) is probe


# ---------------- server: overload -> 503 SlowDown ----------------


def test_overload_sheds_503_while_undercap_succeeds(tmp_path):
    srv, client = _start_server(tmp_path)
    try:
        assert client.make_bucket("bench").status == 200
        srv.config.set_kv(
            "api requests_max_write=1 requests_deadline=250ms")
        assert srv.qos.limit_for("write") == 1
        assert srv.qos.deadline_s == pytest.approx(0.25)

        orig_put = srv.handlers.layer.put_object

        def slow_put(*a, **kw):
            time.sleep(0.8)
            return orig_put(*a, **kw)

        srv.handlers.layer.put_object = slow_put
        before_shed = METRICS2.get("minio_tpu_v2_qos_shed_total",
                                   {"class": "write",
                                    "reason": "wait-deadline"})
        results = []

        def put(i):
            r = client.put_object("bench", f"k{i}", b"x" * 512)
            results.append(r)

        threads = [threading.Thread(target=put, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.3)  # writes saturated; reads must still flow
        g = client.get_object("bench", "missing")
        assert g.status == 404  # admitted + served, not shed
        for t in threads:
            t.join(timeout=10)
        srv.handlers.layer.put_object = orig_put

        by_status = {}
        for r in results:
            by_status.setdefault(r.status, []).append(r)
        assert len(by_status.get(200, [])) == 1
        shed = by_status.get(503, [])
        assert len(shed) == 3
        for r in shed:
            assert b"<Code>SlowDown</Code>" in r.body
            assert int(r.headers["retry-after"]) >= 1
        after_shed = METRICS2.get("minio_tpu_v2_qos_shed_total",
                                  {"class": "write",
                                   "reason": "wait-deadline"})
        assert after_shed - before_shed == 3
    finally:
        srv.stop()


def test_live_config_cap_change_no_restart(tmp_path):
    srv, client = _start_server(tmp_path)
    try:
        assert client.make_bucket("bench").status == 200
        # Default: unlimited.
        assert srv.qos.limit_for("write") == 0
        srv.config.set_kv("api requests_max_write=2")
        assert srv.qos.limit_for("write") == 2
        # Back to unlimited — a parked waiter would be admitted by the
        # notify in set_limit; here just verify both directions apply.
        srv.config.set_kv("api requests_max_write=0")
        assert srv.qos.limit_for("write") == 0
        # Bad values are rejected before they persist.
        with pytest.raises(ValueError):
            srv.config.set_kv("api requests_max_write=-3")
        with pytest.raises(ValueError):
            srv.config.set_kv("api requests_deadline=xyz")
        # And traffic still flows after the reloads.
        assert client.put_object("bench", "obj", b"data").status == 200
    finally:
        srv.stop()


# ---------------- deadline propagation over storage RPC ----------------


def _rpc_remote_disk(tmp_path, delay):
    from minio_tpu.rpc.cluster import derive_cluster_key
    from minio_tpu.rpc.storage import RemoteStorage, StorageRPCService
    from minio_tpu.rpc.transport import RPCClient, RPCRegistry

    disk = XLStorage(str(tmp_path / "remote-disk"))
    disk.make_volume("vol")
    disk.write_all("vol", "obj", b"payload")
    slow = _SlowDisk(disk, delay)
    key = derive_cluster_key(ACCESS, SECRET)
    reg = RPCRegistry(key)
    reg.register("storage", StorageRPCService({"/d1": slow}))
    srv = S3Server(None, ACCESS, SECRET, rpc_registry=reg)
    port = srv.start("127.0.0.1", 0)
    client = RPCClient("127.0.0.1", port, key)
    return srv, slow, RemoteStorage(client, "/d1"), client


def test_deadline_cancels_slow_remote_storage(tmp_path):
    srv, slow, remote, rpc_client = _rpc_remote_disk(tmp_path, 0.0)
    try:
        assert remote.read_all("vol", "obj") == b"payload"
        slow.delay = 2.0
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceeded):
            with open_deadline(0.3):
                remote.read_all("vol", "obj")
        elapsed = time.monotonic() - t0
        assert elapsed < 1.5  # canceled at the deadline, not at 2s+
        # The peer is not the problem — it must NOT be marked offline.
        assert rpc_client.is_online()
        slow.delay = 0.0
        assert remote.read_all("vol", "obj") == b"payload"
    finally:
        srv.stop()


def test_expired_deadline_never_reaches_peer(tmp_path):
    srv, slow, remote, _ = _rpc_remote_disk(tmp_path, 0.0)
    try:
        before = slow.calls
        with pytest.raises(DeadlineExceeded):
            with open_deadline(0.001):
                time.sleep(0.01)
                remote.read_all("vol", "obj")
        assert slow.calls == before  # remote I/O skipped entirely
    finally:
        srv.stop()


def test_rpc_server_refuses_expired_deadline_header(tmp_path):
    """Even a hand-rolled caller with an expired budget is refused
    server-side (the wire carries the remaining budget)."""
    import json

    from minio_tpu.qos.deadline import H_DEADLINE
    from minio_tpu.rpc import transport as tp
    from minio_tpu.rpc.cluster import derive_cluster_key
    from minio_tpu.rpc.storage import StorageRPCService

    disk = XLStorage(str(tmp_path / "d"))
    disk.make_volume("vol")
    disk.write_all("vol", "obj", b"x")
    key = derive_cluster_key(ACCESS, SECRET)
    reg = tp.RPCRegistry(key)
    reg.register("storage", StorageRPCService({"/d": disk}))
    args_json = json.dumps({"disk": "/d", "volume": "vol",
                            "path": "obj"}, sort_keys=True)
    ts = str(int(time.time()))
    auth = tp.sign(key, "storage/read_all", ts, args_json, b"")
    status, _, body = reg.handle(
        f"{tp.RPC_PREFIX}/storage/read_all",
        {"x-mtpu-ts": ts, "x-mtpu-auth": auth, H_DEADLINE: "0"},
        tp.frame(args_json.encode(), b""))
    assert status == 503
    assert json.loads(body)["error_type"] == "DeadlineExceeded"


def test_handler_deadline_maps_to_request_timeout(tmp_path):
    """A request whose budget burns inside the handler answers 503
    RequestTimeout (the reference's ErrOperationTimedOut family), not
    a generic 500."""
    srv, client = _start_server(tmp_path)
    try:
        assert client.make_bucket("bench").status == 200
        # A cap must be configured for the EXECUTION deadline to
        # engage (unconfigured servers keep uncapped requests).
        srv.config.set_kv(
            "api requests_max=64 requests_deadline=200ms")
        assert srv.qos.engaged

        def expiring_put(*a, **kw):
            from minio_tpu.qos.deadline import current_deadline
            dl = current_deadline()
            assert dl is not None  # handler opened the budget
            time.sleep(0.3)
            dl.check("test-phase")
            raise AssertionError("unreached")

        orig = srv.handlers.layer.put_object
        srv.handlers.layer.put_object = expiring_put
        try:
            r = client.put_object("bench", "obj", b"x")
        finally:
            srv.handlers.layer.put_object = orig
        assert r.status == 503
        assert b"<Code>RequestTimeout</Code>" in r.body
        assert "retry-after" in r.headers
    finally:
        srv.stop()


# ---------------- priority lanes ----------------


def test_background_defers_then_promotes():
    gate = PriorityGate()
    gate.DEFER_SLICE_S = 0.01
    gate.MAX_DEFERRALS = 3
    release_fg = threading.Event()
    fg_entered = threading.Event()

    def fg_work():
        with gate.dispatch(FOREGROUND):
            fg_entered.set()
            release_fg.wait(5)

    t = threading.Thread(target=fg_work)
    t.start()
    assert fg_entered.wait(2)
    before_promos = METRICS2.get("minio_tpu_v2_qos_bg_promotions_total")
    t0 = time.monotonic()
    with gate.dispatch(BACKGROUND):
        elapsed = time.monotonic() - t0
    # Aged through MAX_DEFERRALS slices, then PROMOTED despite fg busy.
    assert elapsed >= gate.DEFER_SLICE_S * gate.MAX_DEFERRALS * 0.5
    assert METRICS2.get(
        "minio_tpu_v2_qos_bg_promotions_total") == before_promos + 1
    release_fg.set()
    t.join(timeout=5)
    # Idle foreground: background proceeds immediately.
    t0 = time.monotonic()
    with gate.dispatch(BACKGROUND):
        pass
    assert time.monotonic() - t0 < gate.DEFER_SLICE_S


def test_background_wakes_on_fg_completion():
    gate = PriorityGate()
    gate.DEFER_SLICE_S = 0.5    # long slices: the wake must be a notify
    gate.MAX_DEFERRALS = 10
    release_fg = threading.Event()
    fg_entered = threading.Event()

    def fg_work():
        with gate.dispatch(FOREGROUND):
            fg_entered.set()
            release_fg.wait(5)

    t = threading.Thread(target=fg_work)
    t.start()
    assert fg_entered.wait(2)
    done = []

    def bg_work():
        with gate.dispatch(BACKGROUND):
            done.append(time.monotonic())

    bg = threading.Thread(target=bg_work)
    t0 = time.monotonic()
    bg.start()
    time.sleep(0.05)
    release_fg.set()  # bg must wake promptly, not after the 0.5s slice
    bg.join(timeout=5)
    t.join(timeout=5)
    assert done and done[0] - t0 < 0.4


def test_heal_runs_in_background_lane(tmp_path):
    """Heal dispatches are tagged background: a full heal of a damaged
    object moves the bg dispatch counter, and foreground traffic keeps
    the fg counter moving — both lanes visible in metrics."""
    import shutil

    roots = [str(tmp_path / f"disk{i}") for i in range(4)]
    disks = [XLStorage(r) for r in roots]
    eng = ErasureObjects(disks, 2, 2, block_size=64 * 1024)
    eng.make_bucket("bench")
    body = os.urandom(256 * 1024)
    eng.put_object("bench", "obj", body)
    # Wipe the two disks holding the DATA shards (shard indices 0/1 in
    # the per-object distribution): both GET and heal must reconstruct.
    fi = eng.disks[0].read_version("bench", "obj")
    data_disks = [i for i, d in enumerate(fi.erasure.distribution)
                  if d - 1 < 2]
    for i in data_disks:
        shutil.rmtree(os.path.join(roots[i], "bench", "obj"),
                      ignore_errors=True)
    # Foreground degraded GET dispatches in the fg lane.
    before_fg = METRICS2.get("minio_tpu_v2_qos_dispatch_total",
                             {"lane": "fg"})
    got, _ = eng.get_object("bench", "obj")
    assert got == body
    assert METRICS2.get("minio_tpu_v2_qos_dispatch_total",
                        {"lane": "fg"}) > before_fg
    # The heal of the same damage dispatches in the bg lane.
    before_bg = METRICS2.get("minio_tpu_v2_qos_dispatch_total",
                             {"lane": "bg"})
    res = eng.healer.heal_object("bench", "obj")
    assert sorted(res.healed_disks) == sorted(data_disks)
    assert METRICS2.get("minio_tpu_v2_qos_dispatch_total",
                        {"lane": "bg"}) > before_bg


def test_crawler_cycle_tagged_background(tmp_path, monkeypatch):
    """The crawler's whole cycle runs in the background lane (its heal
    samples and lifecycle rewrites inherit it)."""
    from minio_tpu.bucket.metadata import BucketMetadataSys
    from minio_tpu.scanner.crawler import DataCrawler

    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    eng = ErasureObjects(disks, 2, 2, block_size=64 * 1024)
    eng.make_bucket("bench")
    eng.put_object("bench", "obj", b"z" * 1024)
    seen = []
    crawler = DataCrawler(eng, BucketMetadataSys.for_layer(eng))
    orig = crawler._apply_lifecycle

    def spy(*a, **kw):
        seen.append(current_lane())
        return orig(*a, **kw)

    monkeypatch.setattr(crawler, "_apply_lifecycle", spy)
    crawler.crawl_once()
    assert seen and all(lane == BACKGROUND for lane in seen)
    assert current_lane() == FOREGROUND  # scope restored


def test_shed_and_deadline_land_as_span_events():
    """Every shed/deadline event is a span event on the request's
    trace tree (the PR-1 observability contract)."""
    from minio_tpu.obs.span import Span

    c = AdmissionController()
    c.configure(0, {"write": 1}, 0.02)
    span = Span("s3.request", "trace-1")
    with span:
        held = c.acquire("write", Deadline(0.02))
        try:
            with pytest.raises(AdmissionShed):
                c.acquire("write", Deadline(0.02))
        finally:
            held.__exit__(None, None, None)
        with pytest.raises(DeadlineExceeded):
            Deadline(0.0).check("unit-phase")
    d = span.to_dict()
    names = [e["name"] for e in d.get("events", [])]
    assert "qos.shed" in names
    assert "qos.deadline_expired" in names
    shed = next(e for e in d["events"] if e["name"] == "qos.shed")
    assert shed["api_class"] == "write"
    assert shed["reason"] == "wait-deadline"


# ---------------- error family / loadgen ----------------


def test_throttle_error_family():
    from minio_tpu.s3 import errors as s3err

    assert s3err.ERR_SLOW_DOWN.code == "SlowDown"
    assert s3err.ERR_SLOW_DOWN.http_status == 503
    assert s3err.ERR_SERVICE_UNAVAILABLE.code == "ServiceUnavailable"
    assert s3err.ERR_SERVICE_UNAVAILABLE.http_status == 503
    assert s3err.ERR_REQUEST_TIMEOUT.code == "RequestTimeout"
    assert s3err.ERR_REQUEST_TIMEOUT.http_status == 503
    e = s3err.ERR_SLOW_DOWN.with_retry_after(7)
    assert e.headers() == {"Retry-After": "7"}
    assert e.code == "SlowDown"
    # The shared singleton stays clean.
    assert s3err.ERR_SLOW_DOWN.retry_after is None
    assert s3err.ERR_SLOW_DOWN.headers() == {}


def test_loadgen_against_capped_server(tmp_path):
    """loadgen drives a write-capped server: the report carries shed
    counts, Retry-After sightings, and sane percentiles."""
    from tools.loadgen import run_load

    srv, client = _start_server(tmp_path)
    try:
        assert client.make_bucket("bench").status == 200
        srv.config.set_kv(
            "api requests_max_write=1 requests_deadline=50ms")
        orig_put = srv.handlers.layer.put_object

        def slow_put(*a, **kw):
            time.sleep(0.05)
            return orig_put(*a, **kw)

        srv.handlers.layer.put_object = slow_put
        report = run_load("127.0.0.1", srv.address[1],
                          ACCESS, SECRET, "bench", concurrency=6,
                          duration=1.5, put_fraction=1.0,
                          object_bytes=2048)
        srv.handlers.layer.put_object = orig_put
        assert report["requests"] > 0
        assert report["ok"] > 0
        assert report["shed_503"] > 0  # 6 workers vs cap 1: must shed
        assert report["error_codes"].get("SlowDown", 0) > 0
        assert report["retry_after_headers"] > 0
        assert report["latency_ms"]["p99"] >= report["latency_ms"]["p50"]
    finally:
        srv.stop()


def test_qos_metrics_visible_on_node_endpoint(tmp_path):
    """The QoS series land on /minio-tpu/v2/metrics/node (acceptance:
    wait/shed metrics visible on the node scrape)."""
    srv, client = _start_server(tmp_path)
    try:
        assert client.make_bucket("bench").status == 200
        assert client.put_object("bench", "obj", b"x").status == 200
        status, _, body = srv.handle_ops(
            "GET", "/minio-tpu/v2/metrics/node", "", {}, b"")
        assert status == 200
        text = body.decode()
        assert "minio_tpu_v2_qos_admission_wait_ms" in text
        assert "minio_tpu_v2_qos_admission_inflight" in text
    finally:
        srv.stop()
