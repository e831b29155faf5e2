"""Tenant & workload attribution plane (obs/usage.py): SpaceSaving
top-K accuracy and the <= N/K merge bound, the generic metrics2
cardinality guard, exact window accounts with the fold-to-_other cap,
cluster merge with honest node counts, the noisy_neighbor watchdog
rule's three sinks (console cause + gauge + incident bundle carrying
the usage snapshot), live config reload + rejected writes, the node +
cluster HTTP endpoints with redaction, and admin /top's stored-bytes
and slowlog joins against a live server."""

import json
import random
import time
import urllib.request

import pytest

from minio_tpu.obs.incidents import INCIDENTS
from minio_tpu.obs.metrics2 import METRICS2, MetricsV2, _OVERFLOW
from minio_tpu.obs.usage import (OTHER, USAGE, TopKSketch, _redact_name,
                                 merge_topk, merge_usage, redact_usage)
from minio_tpu.obs.watchdog import WATCHDOG, Watchdog

ACCESS, SECRET = "usageadmin", "usageadmin-secret"


@pytest.fixture(autouse=True)
def _clean_state():
    from minio_tpu.obs.loopmon import LOOPMON
    USAGE.reset()
    USAGE.configure()
    WATCHDOG.reset()
    INCIDENTS.reset()
    # The noisy_neighbor tests assert EXACT transition lists; a genuine
    # machine-load stall on the process-wide rpc loop within the last
    # 10 s makes the built-in loop_stall rule ride along (it did, in a
    # whole-suite run of PR 21). Same guard as tests/test_watchdog.py.
    prev_stall_ms = LOOPMON.stall_ms
    LOOPMON.configure(stall_ms=60_000)
    with LOOPMON._mu:
        LOOPMON._stall_ring.clear()
    yield
    USAGE.reset()
    USAGE.configure()
    WATCHDOG.reset()
    INCIDENTS.reset()
    LOOPMON.configure(stall_ms=prev_stall_ms)
    with LOOPMON._mu:
        LOOPMON._stall_ring.clear()


# ---------------------------------------------------------------------------
# SpaceSaving + count-min sketch


def test_topk_tracks_heavy_hitters_within_bound():
    """Every key with true count > N/K must be tracked, and a tracked
    key's estimate must be within its recorded err (<= N/K)."""
    sk = TopKSketch(8)
    rng = random.Random(7)
    true: dict[str, int] = {}
    # Zipf-ish skew over a keyspace far wider than K.
    for _ in range(20_000):
        r = min(int(rng.paretovariate(1.1)), 400)
        key = f"key{r}"
        true[key] = true.get(key, 0) + 1
        sk.offer(key)
    n = sk.total
    bound = n / sk.k
    tracked = {c["key"]: c for c in sk.top()}
    for key, cnt in true.items():
        if cnt > bound:
            assert key in tracked, (key, cnt, bound)
    for key, c in tracked.items():
        assert c["err"] <= bound
        assert abs(c["count"] - true.get(key, 0)) <= c["err"]


def test_topk_merge_bound_across_two_nodes():
    """The acceptance bound: the merged top-K still names the true
    heavy hitters with count error <= N/K, N summed across nodes —
    including a key only ONE node tracked (the count-min backing
    substitutes on the other)."""
    a, b = TopKSketch(10), TopKSketch(10)
    true: dict[str, int] = {}

    def feed(sk, key, n):
        true[key] = true.get(key, 0) + n
        for _ in range(n):
            sk.offer(key)

    feed(a, "hot", 3000)
    feed(b, "hot", 2000)
    feed(a, "a-only", 1200)          # b never sees it
    feed(b, "b-only", 900)
    rng = random.Random(3)
    for i in range(2000):            # long tail on both
        feed(a if i % 2 else b, f"tail{rng.randrange(500)}", 1)
    merged = merge_topk([a.snapshot(), b.snapshot()])
    n = merged["total"]
    assert n == sum(true.values())
    bound = n / merged["k"]
    counters = {c["key"]: c for c in merged["counters"]}
    assert list(counters)[0] == "hot"          # rank 1 survives merge
    for key in ("hot", "a-only", "b-only"):
        assert key in counters, (key, list(counters))
        assert abs(counters[key]["count"] - true[key]) <= bound, (
            key, counters[key], true[key], bound)


def test_topk_deterministic_seeds_merge_identically():
    """Same inputs -> identical count-min rows on both 'nodes' (the
    property cross-node merging depends on)."""
    a, b = TopKSketch(4), TopKSketch(4)
    for i in range(100):
        a.offer(f"k{i % 7}")
        b.offer(f"k{i % 7}")
    assert a.snapshot()["cm"] == b.snapshot()["cm"]
    assert a.cm_estimate("k1") == b.cm_estimate("k1")


# ---------------------------------------------------------------------------
# metrics2 generic cardinality guard


def test_metrics2_label_cap_folds_overflow_into_other():
    m2 = MetricsV2()
    m2.register("minio_tpu_v2_usage_requests_total", "counter", "t",
                cap_labels={"bucket": 2})
    for b in ("a", "b", "c", "d"):
        m2.inc("minio_tpu_v2_usage_requests_total",
               {"bucket": b, "class": "read"})
    names = sorted(
        s["labels"]["bucket"] for s in
        m2.snapshot()["minio_tpu_v2_usage_requests_total"]["series"])
    assert names == ["_other", "a", "b"]
    assert m2.get("minio_tpu_v2_usage_requests_total",
                  {"bucket": "_other", "class": "read"}) == 2
    # ...and the fold is itself observable.
    assert m2.get(_OVERFLOW,
                  {"metric": "minio_tpu_v2_usage_requests_total",
                   "label": "bucket"}) == 2
    # Uncapped labels on the same metric pass through untouched.
    assert {s["labels"]["class"] for s in
            m2.snapshot()["minio_tpu_v2_usage_requests_total"]
            ["series"]} == {"read"}


def test_metrics2_label_cap_is_generic_and_live_tunable():
    """The guard is not usage-only: any metric can register a cap,
    and set_label_cap retunes it live (shrinking only folds NEW
    values — admitted series keep their identity)."""
    m2 = MetricsV2()
    m2.register("minio_tpu_v2_api_requests_total", "counter", "t",
                cap_labels={"api": 3})
    for api in ("a", "b", "c"):
        m2.inc("minio_tpu_v2_api_requests_total", {"api": api})
    m2.set_label_cap("minio_tpu_v2_api_requests_total", "api", 1)
    m2.inc("minio_tpu_v2_api_requests_total", {"api": "a"})  # admitted
    m2.inc("minio_tpu_v2_api_requests_total", {"api": "z"})  # folds
    assert m2.get("minio_tpu_v2_api_requests_total",
                  {"api": "a"}) == 2
    assert m2.get("minio_tpu_v2_api_requests_total",
                  {"api": "_other"}) == 1
    with pytest.raises(ValueError):
        m2.set_label_cap("minio_tpu_v2_nope_total", "api", 1)


def test_usage_series_registered_with_caps():
    """The shipped registry carries the usage series and the overflow
    counter (O2/O10 lint also pin this statically)."""
    names = METRICS2.registered_names()
    for name in ("minio_tpu_v2_usage_requests_total",
                 "minio_tpu_v2_usage_rx_bytes_total",
                 "minio_tpu_v2_usage_tx_bytes_total",
                 "minio_tpu_v2_usage_errors_total",
                 "minio_tpu_v2_usage_shed_total",
                 "minio_tpu_v2_usage_tenant_requests_total",
                 _OVERFLOW):
        assert name in names, name


# ---------------------------------------------------------------------------
# Exact accounts: windows, cardinality fold, class shares


def _feed(now, *, hot=40, bg=0, shed_bg=0, cls="write"):
    for i in range(hot):
        USAGE.record(bucket="hot", access_key="ak-hot", qos_class=cls,
                     rx=100, tx=10, status=200, shed=False,
                     key=f"user-data-{i % 4}", client="10.0.0.1",
                     duration_ms=5.0 + i, trace_id=f"T{i}", now=now)
    for i in range(bg):
        USAGE.record(bucket=f"bg-{i % 3}", access_key="ak-bg",
                     qos_class=cls, rx=10, tx=1, status=200,
                     shed=False, now=now)
    for i in range(shed_bg):
        USAGE.record(bucket="hot", access_key="ak-hot", qos_class=cls,
                     rx=0, tx=0, status=503, shed=True, now=now)


def test_window_accounts_and_aging():
    USAGE.configure(fast_s=4.0, slow_s=16.0)
    now = time.time()
    _feed(now, hot=10, bg=3)
    fast = USAGE.window_accounts("buckets", 4.0, now)
    assert fast["hot"]["requests"] == 10
    assert fast["hot"]["rxBytes"] == 1000
    assert fast["bg-0"]["requests"] == 1
    # Outside the fast window but inside the slow one.
    later = now + 10.0
    assert USAGE.window_accounts("buckets", 4.0, later) == {}
    assert USAGE.window_accounts(
        "buckets", 16.0, later)["hot"]["requests"] == 10
    # Tenants account independently.
    assert USAGE.window_accounts(
        "tenants", 16.0, later)["ak-hot"]["requests"] == 10


def test_cardinality_cap_folds_accounts_and_counts():
    USAGE.configure(cardinality_cap=2, fast_s=4.0, slow_s=16.0)
    now = time.time()
    for i in range(6):
        USAGE.record(bucket=f"b{i}", access_key="ak", qos_class="read",
                     rx=1, tx=0, status=200, shed=False, now=now)
    acc = USAGE.window_accounts("buckets", 4.0, now)
    assert set(acc) == {"b0", "b1", OTHER}
    assert acc[OTHER]["requests"] == 4
    assert USAGE.folded_total >= 4


def test_class_shares_and_top_census():
    USAGE.configure(fast_s=4.0, slow_s=16.0)
    now = time.time()
    _feed(now, hot=30, bg=6, shed_bg=4)
    shares = USAGE.class_shares(4.0, now)["write"]
    assert shares["admitted"] == 36
    assert shares["shed"] == 4
    assert shares["bucketCount"] == 4         # hot + 3 bg
    assert shares["topBucket"]["name"] == "hot"
    assert shares["topBucket"]["share"] == pytest.approx(30 / 36,
                                                         abs=1e-3)
    assert shares["topShedBucket"]["name"] == "hot"
    census = USAGE.class_top_shares(now)
    assert census["write"]["name"] == "hot"
    assert census["write"]["kind"] == "bucket"


def test_top_report_ranks_and_carries_exemplars():
    USAGE.configure(fast_s=4.0, slow_s=16.0)
    now = time.time()
    _feed(now, hot=20, bg=3)
    doc = USAGE.top()
    assert doc["buckets"][0]["name"] == "hot"
    worst = doc["buckets"][0]["worst"]
    assert worst["traceId"] == "T19"          # slowest hot request
    assert worst["durationMs"] == pytest.approx(24.0)
    keys = doc["keys"]["write"]
    assert keys and keys[0]["key"].startswith("hot/")
    assert doc["clients"]["write"][0]["key"] == "10.0.0.1"


def test_disabled_plane_records_nothing():
    USAGE.configure(enable=False)
    USAGE.record(bucket="b", access_key="a", qos_class="read", rx=1,
                 tx=1, status=200, shed=False)
    assert USAGE.snapshot()["totals"]["requests"] == 0


# ---------------------------------------------------------------------------
# Cluster merge


def test_merge_usage_sums_accounts_and_merges_sketches():
    USAGE.configure(fast_s=4.0, slow_s=16.0)
    now = time.time()
    _feed(now, hot=10)
    snap = USAGE.snapshot()
    merged = merge_usage([("local", snap), ("peer0", snap),
                          ("peer1", {"error": "unreachable"})])
    # HONEST node count: the unreachable peer is not a node.
    assert merged["nodes"] == 2
    assert merged["totals"]["requests"] == 20
    assert merged["buckets"]["fast"]["hot"]["requests"] == 20
    sk = merged["sketches"]["key"]["write"]
    assert sk["total"] == 20
    assert sk["counters"][0]["key"].startswith("hot/")


def test_redaction_hides_tenants_and_clients_keeps_buckets():
    USAGE.configure(fast_s=4.0, slow_s=16.0)
    now = time.time()
    _feed(now, hot=5)
    red = redact_usage(USAGE.snapshot())
    assert "hot" in red["buckets"]["fast"]          # buckets stay
    assert "ak-hot" not in red["tenants"]["fast"]
    assert any(n.startswith("ak…#") for n in red["tenants"]["fast"])
    clients = red["sketches"]["client"]["write"]["counters"]
    assert all(c["key"] != "10.0.0.1" for c in clients)
    # Object-key tails redact too (keys can embed user data); the
    # bucket prefix stays so the hot-bucket shape is still readable.
    keys = red["sketches"]["key"]["write"]["counters"]
    assert all(c["key"].startswith("hot/") for c in keys)
    assert all("user-data" not in c["key"] for c in keys), keys
    # The un-redacted snapshot is untouched (copy semantics).
    assert "ak-hot" in USAGE.snapshot()["tenants"]["fast"]


# ---------------------------------------------------------------------------
# noisy_neighbor rule: three sinks, gates, resolve


def _skewed(now, sheds=10):
    _feed(now, hot=40, bg=8, shed_bg=sheds, cls="write")


def test_noisy_neighbor_fires_with_cause_gauge_and_bundle():
    USAGE.configure(fast_s=4.0, slow_s=16.0, noisy_share=0.5,
                    noisy_min_requests=10)
    now = time.time()
    _skewed(now)
    wd = Watchdog()
    wd.configure(pending_ticks=2, resolve_ticks=2)
    trs = wd.tick(now=now, samples=[])
    assert [(t["rule"], t["new"]) for t in trs] == [
        ("noisy_neighbor", "pending")]
    trs = wd.tick(now=now, samples=[])
    fired = [t for t in trs if t["new"] == "firing"]
    assert fired
    # Sink 1: the cause names the tenant by REDACTED identity only —
    # causes ride the unauthenticated /v2/alerts surface (R13), so the
    # verbatim name must never appear; the stable digest still lets an
    # operator correlate across alerts, and the incident bundle below
    # carries the real name for the authenticated surface.
    assert _redact_name("ak-hot") in fired[0]["cause"]
    assert "ak-hot" not in fired[0]["cause"]
    assert "'hot'" not in fired[0]["cause"]
    assert "write" in fired[0]["cause"]
    # Sink 2: the firing gauge.
    assert METRICS2.get("minio_tpu_v2_alerts_firing",
                        {"rule": "noisy_neighbor"}) == 1
    # Sink 3: the incident bundle froze the usage snapshot.
    bundle = INCIDENTS.get(fired[0]["alertId"])
    assert bundle["usage"]["totals"]["requests"] == 58
    assert "hot" in bundle["usage"]["buckets"]["fast"]
    # Resolve once the skew ages out of both windows.
    later = now + 60.0
    wd.tick(now=later, samples=[])
    trs = wd.tick(now=later, samples=[])
    assert any(t["new"] == "resolved" for t in trs)
    assert METRICS2.get("minio_tpu_v2_alerts_firing",
                        {"rule": "noisy_neighbor"}) == 0


def test_noisy_neighbor_needs_contention_and_a_neighbor():
    USAGE.configure(fast_s=4.0, slow_s=16.0, noisy_share=0.5,
                    noisy_min_requests=10)
    wd = Watchdog()
    wd.configure(pending_ticks=1)
    now = time.time()
    # Dominant share, multiple entities, NO sheds: workload shape,
    # not an incident — healthy one-winner traffic must never page.
    _feed(now, hot=40, bg=8, shed_bg=0)
    assert wd.tick(now=now, samples=[]) == []
    # Sheds but a single entity: no neighbor, no noisy neighbor.
    USAGE.reset()
    _feed(now, hot=40, bg=0, shed_bg=10)
    assert wd.tick(now=now, samples=[]) == []


def test_noisy_neighbor_anonymous_is_not_a_neighbor():
    """'-' (bucket-less service requests / anonymous probes) must not
    satisfy the >=2-entities gate: a genuinely single-tenant box that
    sheds under its own load stays a workload shape, not a page."""
    USAGE.configure(fast_s=4.0, slow_s=16.0, noisy_share=0.5,
                    noisy_min_requests=10)
    wd = Watchdog()
    wd.configure(pending_ticks=1)
    now = time.time()
    _feed(now, hot=40, bg=0, shed_bg=10)
    # A service-level request (no bucket) and an anonymous probe.
    USAGE.record(bucket="", access_key="", qos_class="write",
                 rx=0, tx=0, status=200, shed=False, now=now)
    assert wd.tick(now=now, samples=[]) == []


def test_claimed_access_key_parse_forms():
    from minio_tpu.obs.usage import claimed_access_key
    assert claimed_access_key(
        "AWS4-HMAC-SHA256 Credential=AKID/20260804/us-east-1/s3/"
        "aws4_request, SignedHeaders=host, Signature=ab") == "AKID"
    assert claimed_access_key("AWS LEGACYAK:sig") == "LEGACYAK"
    # Presigned URLs carry the credential in the query, not a header.
    assert claimed_access_key(
        "", {"X-Amz-Credential": "PRESIGNED/20260804/us-east-1/s3/"
                                 "aws4_request"}) == "PRESIGNED"
    assert claimed_access_key("", {}) == ""


def test_tenant_metric_label_is_redacted():
    """Raw access-key ids must not be enumerable on the
    unauthenticated metrics pages — the tenant label rides redacted
    (admin /top has the real names)."""
    from minio_tpu.obs.usage import _redact_name
    USAGE.configure(fast_s=4.0, slow_s=16.0)
    before = METRICS2.get(
        "minio_tpu_v2_usage_tenant_requests_total",
        {"tenant": _redact_name("ak-secret"), "class": "write"}) or 0
    USAGE.record(bucket="tmb", access_key="ak-secret",
                 qos_class="write", rx=1, tx=0, status=200,
                 shed=False)
    assert METRICS2.get(
        "minio_tpu_v2_usage_tenant_requests_total",
        {"tenant": _redact_name("ak-secret"),
         "class": "write"}) == before + 1
    assert (METRICS2.get(
        "minio_tpu_v2_usage_tenant_requests_total",
        {"tenant": "ak-secret", "class": "write"}) or 0) == 0


def test_noisy_neighbor_respects_volume_floor_and_disable():
    USAGE.configure(fast_s=4.0, slow_s=16.0, noisy_share=0.5,
                    noisy_min_requests=1000)
    wd = Watchdog()
    wd.configure(pending_ticks=1)
    now = time.time()
    _skewed(now)
    assert wd.tick(now=now, samples=[]) == []  # under the floor
    USAGE.configure(enable=False)
    assert wd.tick(now=now, samples=[]) == []


def test_noisy_neighbor_is_a_builtin_name():
    from minio_tpu.obs.watchdog import AlertRuleError, \
        validate_user_rules
    with pytest.raises(AlertRuleError):
        validate_user_rules(json.dumps([{
            "name": "noisy_neighbor",
            "metric": "minio_tpu_v2_usage_requests_total",
            "value": 1}]))


# ---------------------------------------------------------------------------
# Timeline census + mtpu_top row


def test_timeline_sample_carries_usage_top_and_merge_takes_worst():
    from minio_tpu.obs.timeline import merge_timelines
    USAGE.configure(fast_s=4.0, slow_s=16.0)
    _feed(time.time(), hot=10)
    from minio_tpu.obs.timeline import Timeline
    tl = Timeline(period_s=0.05, retention_s=10)
    tl.tick()          # baseline
    sample = tl.tick()
    assert sample["usageTop"]["write"]["name"] == "hot"
    # Cluster merge keeps the worst single-node concentration.
    t = sample["t"]
    a = {"periodS": 1.0, "samples": [dict(
        sample, usageTop={"write": {"kind": "bucket", "name": "hot",
                                    "share": 0.6}})]}
    b = {"periodS": 1.0, "samples": [dict(
        sample, usageTop={"write": {"kind": "bucket", "name": "mild",
                                    "share": 0.3},
                          "read": {"kind": "bucket", "name": "r",
                                   "share": 0.9}})]}
    merged = merge_timelines([a, b])
    by_t = {s["t"]: s for s in merged["samples"]}
    top = by_t[int(t // 1.0) * 1.0]["usageTop"]
    assert top["write"]["name"] == "hot"      # 0.6 beats 0.3
    assert top["read"]["name"] == "r"


def test_mtpu_top_renders_tenants_row():
    from tools.mtpu_top import render
    doc = {"periodS": 1.0, "samples": [{
        "t": 0.0, "qps": {"write": 5}, "inflight": {}, "shed": {},
        "rx": 0, "tx": 0, "kernelBytes": {}, "kernelGiBs": {},
        "queueDepth": 0, "drives": {}, "backendState": {},
        "mrfDepth": 0,
        "usageTop": {"write": {"kind": "bucket", "name": "hot",
                               "share": 0.87}}}]}
    out = render(doc)
    assert "tenants:" in out
    assert "write:hot=87%" in out
    doc["samples"][0]["usageTop"] = {}
    assert "tenants: no attributed traffic" in render(doc)


# ---------------------------------------------------------------------------
# Live server: endpoints, config reload, admin /top joins


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl import XLStorage
    root = tmp_path_factory.mktemp("usagedisks")
    disks = [XLStorage(str(root / f"d{i}")) for i in range(6)]
    layer = ErasureObjects(disks, 4, 2, block_size=64 * 1024)
    srv = S3Server(layer, ACCESS, SECRET)
    port = srv.start()
    yield srv, port
    srv.stop()


def _get_json(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return json.loads(r.read().decode())


def _client(port):
    from minio_tpu.s3.client import S3Client
    return S3Client("127.0.0.1", port, ACCESS, SECRET)


def _admin(port):
    from minio_tpu.s3.admin_client import AdminClient
    return AdminClient("127.0.0.1", port, ACCESS, SECRET)


def test_usage_endpoints_and_admin_top_on_live_server(server):
    srv, port = server
    c = _client(port)
    # The label guard's seen-set is process-wide: a full-suite run may
    # have admitted 64 bucket values already — raise the cap so THIS
    # test's bucket gets its own series (the fold itself is covered by
    # the dedicated cap tests).
    METRICS2.set_label_cap("minio_tpu_v2_usage_requests_total",
                           "bucket", 1_000_000)
    assert c.make_bucket("ubk").status == 200
    body = b"x" * 8192
    for i in range(12):
        assert c.put_object("ubk", f"k{i % 3}", body).status == 200
    # Node endpoint: bucket accounts + sketches, tenants redacted.
    doc = _get_json(port, "/minio-tpu/v2/usage")
    assert doc["enabled"] is True
    assert doc["buckets"]["fast"]["ubk"]["requests"] >= 12
    assert ACCESS not in doc["tenants"]["fast"]
    keys = doc["sketches"]["key"]["write"]["counters"]
    assert any(k["key"].startswith("ubk/") for k in keys)
    # usage_* series landed (through the capped labels).
    assert METRICS2.get("minio_tpu_v2_usage_requests_total",
                        {"bucket": "ubk", "class": "write"}) >= 12
    # Cluster endpoint: single node, honest count.
    cdoc = _get_json(port, "/minio-tpu/v2/usage/cluster")
    assert cdoc["nodes"] == 1
    assert cdoc["unreachable"] == 0
    assert cdoc["buckets"]["fast"]["ubk"]["requests"] >= 12
    # Admin /top: ranked buckets, full tenant names, trace exemplar.
    top = _admin(port).top()
    ub = [b for b in top["buckets"] if b["name"] == "ubk"]
    assert ub, top["buckets"]
    assert ub[0]["worst"]["traceId"]
    assert any(t["name"] == ACCESS for t in top["tenants"])


def test_admin_top_joins_crawler_stored_bytes(server):
    srv, port = server
    c = _client(port)
    assert c.make_bucket("sbk").status == 200
    assert c.put_object("sbk", "obj", b"y" * 4096).status == 200
    # Attach a crawler and run one synchronous cycle so the at-rest
    # census exists (serve() normally owns this wiring).
    from minio_tpu.scanner.crawler import DataCrawler
    srv.crawler = DataCrawler(srv.layer, srv.bucket_meta)
    try:
        srv.crawler.crawl_once()
        assert c.get_object("sbk", "obj").status == 200
        top = _admin(port).top()
        sb = [b for b in top["buckets"] if b["name"] == "sbk"]
        assert sb and sb[0]["storedBytes"] == 4096
    finally:
        srv.crawler = None


def test_usage_exemplar_resolves_in_slowlog(server):
    srv, port = server
    c = _client(port)
    assert c.make_bucket("slb").status == 200
    adm = _admin(port)
    adm.set_config_kv("obs slow_ms=0.001")  # capture everything
    try:
        # Only traffic AFTER the SLO drop has a slowlog entry; drop
        # the earlier make_bucket from the exemplar race.
        USAGE.reset()
        assert c.put_object("slb", "slow", b"z" * 8192).status == 200
        top = adm.top()
        row = [b for b in top["buckets"] if b["name"] == "slb"][0]
        assert row["worst"]["traceId"]
        assert row["worst"]["slowlog"]["blamedLayer"]
    finally:
        adm.set_config_kv("obs slow_ms=1000")


def test_usage_config_reload_and_rejected_writes(server):
    srv, port = server
    adm = _admin(port)
    # Live reload lands on the singleton.
    adm.set_config_kv("usage top_k=7 cardinality_cap=9 "
                      "fast_window=30s slow_window=5m "
                      "noisy_share=0.75 noisy_min_requests=50")
    assert USAGE.top_k == 7
    assert USAGE.cardinality_cap == 9
    assert USAGE.fast_s == pytest.approx(30.0)
    assert USAGE.slow_s == pytest.approx(300.0)
    assert USAGE.noisy_share == pytest.approx(0.75)
    assert USAGE.noisy_min_requests == 50
    # Rejected BEFORE persist: bad values answer 400 and change
    # nothing.
    from minio_tpu.s3.admin_client import AdminError
    for bad in ("usage enable=maybe",
                "usage top_k=0",
                "usage top_k=9999",
                "usage cardinality_cap=-1",
                "usage noisy_share=1.5",
                "usage noisy_share=nope",
                "usage fast_window=xyz",
                "usage fast_window=10m",       # > slow_window (5m)
                "usage noisy_min_requests=0"):
        with pytest.raises(AdminError):
            adm.set_config_kv(bad)
    assert USAGE.top_k == 7
    # enable=off stops recording live.
    adm.set_config_kv("usage enable=off")
    before = USAGE.snapshot()["totals"]["requests"]
    c = _client(port)
    assert c.make_bucket("offb").status == 200
    assert USAGE.snapshot()["totals"]["requests"] == before
    adm.set_config_kv("usage enable=on")
    # The metrics2 label guard followed the cap retune.
    assert METRICS2._cap_labels[
        "minio_tpu_v2_usage_requests_total"]["bucket"] == 9
    adm.set_config_kv("usage top_k=10 cardinality_cap=64 "
                      "fast_window=1m slow_window=15m "
                      "noisy_share=0.5 noisy_min_requests=20")


def test_shed_attribution_counts_as_shed_not_error(server):
    """A capped class's 503 SlowDown lands in the shed column (and
    the usage_shed_total series), never the error column — the same
    exemption split the slowlog applies."""
    srv, port = server
    c = _client(port)
    assert c.make_bucket("shedb").status == 200
    adm = _admin(port)
    METRICS2.set_label_cap("minio_tpu_v2_usage_shed_total",
                           "bucket", 1_000_000)
    shed0 = METRICS2.get("minio_tpu_v2_usage_shed_total",
                         {"bucket": "shedb"})
    adm.set_config_kv("api requests_max_write=1 "
                      "requests_deadline=50ms")
    try:
        import threading
        results: list[int] = []
        mu = threading.Lock()

        def put(i):
            s = c.put_object("shedb", f"s{i}", b"q" * 65536).status
            with mu:
                results.append(s)

        deadline = time.time() + 20
        while time.time() < deadline and 503 not in results:
            threads = [threading.Thread(target=put, args=(i,))
                       for i in range(12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30)
    finally:
        adm.set_config_kv("api requests_max_write=0 "
                          "requests_deadline=10s")
    assert 503 in results, results
    acc = USAGE.window_accounts("buckets", USAGE.slow_s)
    assert acc["shedb"]["shed"] >= 1
    assert acc["shedb"]["errors"] == 0
    assert METRICS2.get("minio_tpu_v2_usage_shed_total",
                        {"bucket": "shedb"}) > (shed0 or 0)


# ---------------------------------------------------------------------------
# Multi-tenant loadgen


def test_loadgen_multi_tenant_skew_and_per_tenant_report(server):
    from tools.loadgen import run_load
    srv, port = server
    c = _client(port)
    for i in range(3):
        assert c.make_bucket(f"lg-{i}").status == 200
    report = run_load("127.0.0.1", port, ACCESS, SECRET, "lg",
                      concurrency=4, duration=1.5, put_fraction=1.0,
                      object_bytes=4096, buckets=3, tenant_zipf_s=2.5,
                      seed=11)
    assert report["config"]["tenants"] == 3
    tenants = report["tenants"]
    assert set(tenants) == {"lg-0", "lg-1", "lg-2"}
    counts = [tenants[f"lg-{i}"]["requests"] for i in range(3)]
    assert sum(counts) == report["requests"]
    # Zipf skew: tenant 0 dominates.
    assert counts[0] > counts[1] >= 0
    assert counts[0] > report["requests"] * 0.5
    assert tenants["lg-0"]["latency_ms"]["p50"] > 0
