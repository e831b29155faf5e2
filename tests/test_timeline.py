"""Cluster timeline (obs/timeline.py): ring wraparound at fixed
memory, delta correctness across counter resets, concurrent exemplar
writers, bucket-aligned cluster merge with a lagging peer, the node +
cluster HTTP endpoints on a live server, the end-to-end backend-flip
visibility contract (gauge + span event + timeline series), and the
`tools/mtpu_top.py` --once snapshot mode tier-1 exercises so the
console view can't rot."""

import json
import os
import threading
import time
import urllib.request

import pytest

from minio_tpu.faultinject import FAULTS
from minio_tpu.obs.kernprof import KERNPROF
from minio_tpu.obs.timeline import (TIMELINE, Timeline,
                                    merge_timelines)

ACCESS, SECRET = "tladmin", "tladmin-secret"


@pytest.fixture(autouse=True)
def _clean_state():
    # The watchdog resets too: the backend-flip test deliberately
    # takes a backend DOWN, which (correctly) fires the
    # kernel_backend_down alert — state left mid-resolve would make
    # mtpu_top --once exit 2 in a later test (that exit code is the
    # feature; the leak across tests is not).
    from minio_tpu.obs.watchdog import WATCHDOG
    KERNPROF.reset()
    FAULTS.clear()
    WATCHDOG.reset()
    yield
    KERNPROF.reset()
    FAULTS.clear()
    WATCHDOG.reset()


class _ScriptedTimeline(Timeline):
    """Timeline fed synthetic raw counter reads, so delta/reset
    behavior is pinned without a live registry."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.raws: list[dict] = []

    @staticmethod
    def raw(rx=0, tx=0, qps_read=0, kern_native=0, shed_write=0):
        return {
            "qps": {"read": qps_read}, "shed": {"write": shed_write},
            "inflight": {"read": 1}, "queueDepth": 0,
            "rx": rx, "tx": tx,
            "kernelBytes": {"native": kern_native},
            "hedgeFired": 0, "mrfDepth": 0,
            "drives": {"suspect": 0, "faulty": 0, "quarantined": 0},
            "backendState": {"native": 0},
        }

    def _read_raw(self):
        return self.raws.pop(0)


# ---------------------------------------------------------------------------
# Ring mechanics


def test_ring_wraparound_fixed_memory():
    t = _ScriptedTimeline(period_s=1.0, retention_s=5.0)
    cap = t._ring.maxlen
    assert cap <= 5 + 2
    t.raws = [t.raw(rx=i) for i in range(30)]
    for i in range(30):
        t.tick(now=1000.0 + i)
    samples = t.samples()
    assert len(samples) == cap == t._ring.maxlen  # bounded, full
    # Oldest evicted: only the newest `cap` stamps survive.
    assert samples[0]["t"] == pytest.approx(1000.0 + 29 - (cap - 1))
    assert samples[-1]["t"] == pytest.approx(1029.0)


def test_default_ring_holds_fifteen_minutes_fixed_memory():
    """The acceptance floor: >= 15 min of 1 s samples at fixed memory
    (a bounded deque, capacity-clamped against bad config)."""
    t = Timeline()
    assert t.period_s == 1.0
    assert t.retention_s >= 15 * 60
    assert t._ring.maxlen >= 900
    # A hostile retention value cannot grow the ring unboundedly.
    t.configure(0.001, 10 ** 9)
    from minio_tpu.obs.timeline import MAX_SAMPLES, MIN_PERIOD_S
    assert t._ring.maxlen <= MAX_SAMPLES
    assert t.period_s >= MIN_PERIOD_S


def test_deltas_and_counter_reset_rebase():
    t = _ScriptedTimeline()
    t.raws = [t.raw(rx=100, qps_read=10, kern_native=1 << 20),
              t.raw(rx=150, qps_read=14, kern_native=3 << 20),
              # reset: every counter went DOWN (registry reset /
              # process restart behind a proxy)
              t.raw(rx=30, qps_read=2, kern_native=1 << 19)]
    assert t.tick(now=1.0) is None  # first tick = baseline only
    s = t.tick(now=2.0)
    assert s["rx"] == 50 and s["qps"]["read"] == 4
    assert s["kernelBytes"]["native"] == 2 << 20
    # 1s window, 2 MiB -> GiB/s
    assert s["kernelGiBs"]["native"] == pytest.approx(
        (2 << 20) / (1 << 30), rel=1e-3)
    s = t.tick(now=3.0)
    # Re-based on current values, never negative.
    assert s["rx"] == 30 and s["qps"]["read"] == 2
    assert s["kernelBytes"]["native"] == 1 << 19


def test_rate_uses_real_interval_not_nominal_period():
    t = _ScriptedTimeline(period_s=1.0)
    t.raws = [t.raw(kern_native=0), t.raw(kern_native=4 << 30)]
    t.tick(now=10.0)
    s = t.tick(now=12.0)  # sampler drifted: 2s elapsed
    assert s["kernelGiBs"]["native"] == pytest.approx(2.0, rel=1e-3)


def test_concurrent_exemplar_writers():
    t = _ScriptedTimeline()
    t.raws = [t.raw(), t.raw()]
    t.tick(now=1.0)
    threads = [threading.Thread(
        target=t.note_request, args=("read", float(i), f"trace-{i}"))
        for i in range(32)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    s = t.tick(now=2.0)
    assert s["worstRequest"]["traceId"] == "trace-31"
    assert s["worstRequest"]["durationMs"] == 31.0
    # folded into the window and cleared for the next one
    t.raws = [t.raw()]
    assert "worstRequest" not in t.tick(now=3.0)


def test_configure_reshapes_ring_keeping_history():
    t = _ScriptedTimeline(period_s=1.0, retention_s=100.0)
    t.raws = [t.raw(rx=i) for i in range(10)]
    for i in range(10):
        t.tick(now=float(i))
    t.configure(1.0, 3.0)
    kept = t.samples()
    assert len(kept) == t._ring.maxlen == 5
    assert kept[-1]["t"] == 9.0  # newest survives a shrink


# ---------------------------------------------------------------------------
# Cluster merge


def _sample(t, qps_read=0, rx=0, dev_state=0, worst_ms=None):
    s = {"t": t, "nodes": 1, "qps": {"read": qps_read},
         "shed": {}, "inflight": {"read": 1}, "queueDepth": 1,
         "rx": rx, "tx": 0, "kernelBytes": {"native": 100},
         "kernelGiBs": {"native": 0.1}, "hedgeFired": 0,
         "mrfDepth": 2,
         "drives": {"suspect": 1, "faulty": 0, "quarantined": 0},
         "backendState": {"device": dev_state}}
    if worst_ms is not None:
        s["worstRequest"] = {"durationMs": worst_ms,
                             "traceId": f"tr-{worst_ms}",
                             "class": "read"}
    return s


def test_merge_aligns_buckets_with_lagging_peer():
    """A peer whose newest samples lag the local node's (slow scrape,
    clock skew under a second) still merges into the right 1s buckets;
    windows only one node reported carry nodes=1, overlapping windows
    nodes=2 with summed rates and the max-duration trace exemplar."""
    local = {"periodS": 1.0, "samples": [
        _sample(100.0, qps_read=5, rx=50, worst_ms=10.0),
        _sample(101.0, qps_read=7, rx=70, dev_state=2),
        _sample(102.0, qps_read=9, rx=90)]}
    # Lagging peer: newest sample is local's oldest window, offset by
    # 0.4s inside the bucket.
    peer = {"periodS": 1.0, "samples": [
        _sample(99.4, qps_read=1, rx=10),
        _sample(100.4, qps_read=3, rx=30, worst_ms=25.0)]}
    merged = merge_timelines([local, peer])
    assert merged["nodes"] == 2
    by_t = {s["t"]: s for s in merged["samples"]}
    assert set(by_t) == {99.0, 100.0, 101.0, 102.0}
    assert by_t[99.0]["nodes"] == 1  # peer-only window
    assert by_t[100.0]["nodes"] == 2
    assert by_t[100.0]["qps"]["read"] == 8 and by_t[100.0]["rx"] == 80
    assert by_t[101.0]["nodes"] == 1  # lagging peer never got here
    # Gauges add across nodes; backend state takes the worst.
    assert by_t[100.0]["inflight"]["read"] == 2
    assert by_t[100.0]["mrfDepth"] == 4
    assert by_t[101.0]["backendState"]["device"] == 2
    # Worst exemplar across nodes wins the bucket.
    assert by_t[100.0]["worstRequest"]["traceId"] == "tr-25.0"
    assert by_t[100.0]["drives"]["suspect"] == 2


def test_merge_empty_and_single():
    assert merge_timelines([])["samples"] == []
    one = {"periodS": 1.0, "samples": [_sample(5.0, qps_read=2)]}
    merged = merge_timelines([one])
    assert merged["nodes"] == 1
    assert merged["samples"][0]["qps"]["read"] == 2


def test_merge_collapses_faster_sampling_node():
    """A node live-reloaded to a 200ms sample period merges against a
    1s peer as ONE node per bucket: its sub-period samples collapse
    (counters summed, gauges latest, GiB/s from summed bytes) instead
    of counting as 5 nodes with 5x gauges."""
    fast = {"periodS": 0.2, "samples": [
        _sample(100.0 + i * 0.2, qps_read=2, rx=10, worst_ms=float(i))
        for i in range(5)]}
    slow = {"periodS": 1.0, "samples": [_sample(100.0, qps_read=5,
                                                rx=50)]}
    merged = merge_timelines([fast, slow])
    assert merged["periodS"] == 1.0
    by_t = {s["t"]: s for s in merged["samples"]}
    b = by_t[100.0]
    assert b["nodes"] == 2                    # not 6
    assert b["qps"]["read"] == 2 * 5 + 5      # counters still sum
    assert b["rx"] == 10 * 5 + 50
    assert b["inflight"]["read"] == 2         # gauge: 1 per node
    assert b["mrfDepth"] == 4                 # not 12
    assert b["drives"]["suspect"] == 2        # census once per node
    # Collapsed bucket recomputes GiB/s from summed bytes over the
    # merge period — 500B/1s, which rounds (6 places, the tick()
    # convention) to 0 — not 5 summed 200ms rates. The slow node's
    # single sample keeps its own dt-based 0.1; summing the fast
    # node's per-sample rates would have read 0.6 here.
    assert b["kernelGiBs"]["native"] == pytest.approx(0.1, abs=1e-9)
    # Worst exemplar survives the collapse.
    assert b["worstRequest"]["durationMs"] == 4.0


# ---------------------------------------------------------------------------
# Live server: endpoints, three-sink backend flip, mtpu_top


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl import XLStorage
    root = tmp_path_factory.mktemp("tldisks")
    disks = [XLStorage(str(root / f"d{i}")) for i in range(6)]
    layer = ErasureObjects(disks, 4, 2, block_size=64 * 1024)
    srv = S3Server(layer, ACCESS, SECRET)
    # Fast sampling BEFORE start: the sampler's first wait uses the
    # period in force when it parks, and a 1s first window would
    # swallow short test traffic into the baseline. (The config-KV
    # path normally owns this knob — obs timeline_sample.)
    TIMELINE.configure(0.05, 60.0)
    TIMELINE.reset()
    port = srv.start()
    yield srv, port
    srv.stop()
    TIMELINE.configure(1.0, 900.0)


def _get_json(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return json.loads(r.read().decode())


def _client(port):
    from minio_tpu.s3.client import S3Client
    return S3Client("127.0.0.1", port, ACCESS, SECRET)


def test_node_endpoint_serves_samples_with_traffic(server):
    srv, port = server
    c = _client(port)
    assert c.make_bucket("tlb").status == 200
    body = os.urandom(128 * 1024)
    # Keep traffic flowing WHILE polling: sample windows only show
    # activity that happens after the sampler's baseline tick.
    deadline = time.time() + 15
    doc = None
    i = 0
    while time.time() < deadline:
        assert c.put_object("tlb", f"o{i}", body).status == 200
        i += 1
        doc = _get_json(port, "/minio-tpu/v2/timeline")
        if any(sum(s["qps"].values()) > 0
               for s in doc.get("samples", ())):
            break
        time.sleep(0.05)
    assert doc["periodS"] == pytest.approx(0.05)
    samples = doc["samples"]
    assert samples, "sampler produced no windows"
    busy = [s for s in samples if sum(s["qps"].values()) > 0]
    assert busy, samples[-3:]
    s = busy[-1]
    # The shape every consumer (mtpu_top, cluster merge) relies on.
    for field in ("qps", "inflight", "shed", "rx", "tx",
                  "kernelBytes", "kernelGiBs", "queueDepth",
                  "drives", "backendState", "mrfDepth"):
        assert field in s, field
    assert set(s["backendState"]) == {"device", "native", "xla-cpu",
                                      "host"}
    # PUT traffic moved kernel bytes on some host-side backend. The
    # qps count lands at ADMISSION time, the encode bytes at dispatch
    # a few ms later — under full-suite CPU starvation those can fall
    # in adjacent 50ms windows, so poll past the already-fetched doc
    # (with traffic still flowing) instead of asserting on it.
    deadline = time.time() + 15
    while time.time() < deadline:
        if any(sum((x.get("kernelBytes") or {}).values()) > 0
               for x in samples):
            break
        assert c.put_object("tlb", f"kb-{i}", body).status == 200
        i += 1
        time.sleep(0.05)
        samples = _get_json(port, "/minio-tpu/v2/timeline")["samples"]
    assert any(sum((x.get("kernelBytes") or {}).values()) > 0
               for x in samples), samples[-3:]
    # The worst-request exemplar links to a real trace id. It lands in
    # the window where the request FINISHES (qps counts admission), so
    # under load it can trail the busy window by a tick — poll for it.
    deadline = time.time() + 10
    with_worst: list = []
    while time.time() < deadline and not with_worst:
        assert c.put_object("tlb", "exemplar", body).status == 200
        time.sleep(0.1)
        allsamples = _get_json(port,
                               "/minio-tpu/v2/timeline")["samples"]
        with_worst = [x for x in allsamples if "worstRequest" in x]
    assert with_worst
    assert with_worst[-1]["worstRequest"]["traceId"]
    # ?n= tails the ring.
    assert len(_get_json(port,
                         "/minio-tpu/v2/timeline?n=2")["samples"]) <= 2


def test_cluster_endpoint_merges(server):
    srv, port = server
    doc = _get_json(port, "/minio-tpu/v2/timeline/cluster")
    assert doc["nodes"] >= 1
    assert isinstance(doc["samples"], list)
    if doc["samples"]:
        assert doc["samples"][0]["nodes"] >= 1
    # ?n= tails the merged view (a 1 Hz mtpu_top --cluster poll must
    # not re-download the full 15-minute history each refresh).
    doc2 = _get_json(port, "/minio-tpu/v2/timeline/cluster?n=1")
    assert len(doc2["samples"]) <= 1
    if doc["samples"] and doc2["samples"]:
        assert doc2["samples"][-1]["t"] == doc["samples"][-1]["t"]


def test_backend_flip_visible_in_all_three_sinks(server, monkeypatch):
    """Acceptance drive: a `kernel` fault plan flips dispatch off the
    device lane and the transition is visible in (1) the backend-state
    gauge, (2) a kernel.backend span event on the request's trace, and
    (3) the timeline series — then the fault clears and recovery is
    re-adopted and visible again."""
    from minio_tpu.erasure.codec import Erasure
    from minio_tpu.obs.metrics2 import METRICS2
    from minio_tpu.obs.span import TRACER
    from minio_tpu.ops import batching

    srv, port = server
    c = _client(port)
    assert c.make_bucket("flip").status == 200
    body = os.urandom(200_000)
    assert c.put_object("flip", "obj", body).status == 200
    # Remove one DATA shard so the GET reconstructs; force the device
    # lane on this CPU-only box (attempt_backend() -> xla-cpu).
    victim = None
    for d in srv.layer.disks:
        meta = os.path.join(d.root, "flip", "obj", "xl.meta")
        doc = json.loads(open(meta).read())
        if doc["versions"][0]["erasure"]["index"] == 1:
            victim = d.root
            break
    assert victim
    import shutil
    shutil.rmtree(os.path.join(victim, "flip", "obj"))
    monkeypatch.setattr(Erasure, "_use_tpu", lambda self, *a: True)
    backend = batching.attempt_backend()

    plan = json.dumps({"rules": [{"kind": "kernel",
                                  "target": "rs_decode"}]}).encode()
    r = c.request("POST", "/minio-tpu/admin/v1/fault-inject",
                  body=plan)
    assert r.status == 200, r.body
    g = c.get_object("flip", "obj")
    assert g.status == 200 and g.body == body  # host fallback served

    # Sink 1: the gauge.
    assert METRICS2.get("minio_tpu_v2_kernel_backend_state",
                        {"backend": backend}) == 1
    # Sink 2: the kernel.backend span event on the GET's trace.
    def events(node):
        out = list(node.get("events", []))
        for ch in node.get("children", []):
            out.extend(events(ch))
        return out
    # The trace publishes when the server finishes the request — the
    # client's body read can win that race on an idle box, so poll
    # like sink 3 below does (the event either lands within the
    # deadline or the sink is genuinely broken).
    ev = []
    deadline = time.time() + 5
    while time.time() < deadline:
        ev = [e for tree in TRACER.recent(16) for e in events(tree)
              if e["name"] == "kernel.backend"]
        if ev:
            break
        time.sleep(0.05)
    assert ev and ev[-1]["backend"] == backend
    assert ev[-1]["new"] == "degraded"
    # Sink 3: the timeline series.
    deadline = time.time() + 5
    while time.time() < deadline:
        doc = _get_json(port, "/minio-tpu/v2/timeline?n=1")
        if doc["samples"] and \
                doc["samples"][-1]["backendState"].get(backend) == 1:
            break
        time.sleep(0.05)
    assert doc["samples"][-1]["backendState"][backend] == 1

    # Clear the fault; recovery is re-adopted (probe) and visible.
    r = c.request("POST", "/minio-tpu/admin/v1/fault-inject",
                  query="clear=true")
    assert r.status == 200
    # Force DOWN first so the probe path (not the ok-streak) recovers:
    # that is the device re-adoption contract.
    KERNPROF.dispatch_failed(backend, RuntimeError("x"))
    KERNPROF.dispatch_failed(backend, RuntimeError("x"))
    assert KERNPROF.state_of(backend) == "down"
    assert KERNPROF.probe(backend) is True
    assert METRICS2.get("minio_tpu_v2_kernel_backend_state",
                        {"backend": backend}) == 0
    deadline = time.time() + 5
    while time.time() < deadline:
        doc = _get_json(port, "/minio-tpu/v2/timeline?n=1")
        if doc["samples"] and \
                doc["samples"][-1]["backendState"].get(backend) == 0:
            break
        time.sleep(0.05)
    assert doc["samples"][-1]["backendState"][backend] == 0


def test_admin_kernel_health_surface(server):
    srv, port = server
    c = _client(port)
    r = c.request("GET", "/minio-tpu/admin/v1/kernel-health")
    assert r.status == 200, r.body
    doc = json.loads(r.body)
    assert set(doc["backends"]) == {"device", "native", "xla-cpu",
                                    "host"}
    r = c.request("GET", "/minio-tpu/admin/v1/kernel-health",
                  query="probe=true")
    doc = json.loads(r.body)
    assert doc["probed"]["host"] is True


def test_mtpu_top_once_against_live_server(server, capsys):
    """The CI contract for the console view: --once needs no TTY and
    renders the load-bearing rows from a live node endpoint."""
    from tools import mtpu_top
    srv, port = server
    # Samples stamped while an earlier test's alert was firing may
    # still be the ring's NEWEST for a tick or two after the autouse
    # watchdog reset — wait for a post-reset sample (firing=0), since
    # a nonzero exit on a firing alert is mtpu_top's contract.
    deadline = time.time() + 10
    while time.time() < deadline:
        doc = _get_json(port, "/minio-tpu/v2/timeline?n=1")
        if doc["samples"] and not (doc["samples"][-1].get("alerts")
                                   or {}).get("firing", 0):
            break
        time.sleep(0.05)
    rc = mtpu_top.main(["--url", f"http://127.0.0.1:{port}", "--once",
                        "--n", "50"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "minio-tpu top" in out
    assert "kernel:" in out
    assert "alerts:" in out
    assert "drives:" in out and "qps" in out
    # Cluster mode rides the same renderer. Drop the TTL-cached merge
    # first: a cluster doc built up to 10s ago (by an earlier test,
    # while an alert from that test was still firing) would make the
    # exit-2-on-firing contract trip on STALE state.
    srv._cluster_timeline_cache = None
    rc = mtpu_top.main(["--url", f"http://127.0.0.1:{port}", "--once",
                        "--cluster"])
    assert rc == 0


def test_mtpu_top_once_unreachable_exits_nonzero(capsys):
    from tools import mtpu_top
    rc = mtpu_top.main(["--url", "http://127.0.0.1:1", "--once",
                        "--timeout", "0.5"])
    assert rc == 1
    assert "cannot read timeline" in capsys.readouterr().err


def test_timeline_config_kv_validation_and_reload(server):
    srv, port = server
    c = _client(port)
    # Bad duration rejected before persist.
    r = c.request("POST", "/minio-tpu/admin/v1/set-config-kv",
                  body=b"obs timeline_sample=banana")
    assert r.status == 400, r.body
    r = c.request("POST", "/minio-tpu/admin/v1/set-config-kv",
                  body=b"obs timeline_sample=0s")
    assert r.status == 400, r.body
    # Valid values reshape the live ring.
    r = c.request("POST", "/minio-tpu/admin/v1/set-config-kv",
                  body=b"obs timeline_sample=100ms "
                       b"timeline_retention=10s")
    assert r.status == 200, r.body
    assert TIMELINE.period_s == pytest.approx(0.1)
    assert TIMELINE._ring.maxlen <= 102
    # Back to the test fixture's fast sampling for later tests.
    r = c.request("POST", "/minio-tpu/admin/v1/del-config-kv",
                  body=b"obs")
    assert r.status == 200, r.body
    TIMELINE.configure(0.05, 60.0)
