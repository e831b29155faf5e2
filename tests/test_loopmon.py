"""Event-loop health plane (obs/loopmon.py): heartbeat lag telemetry
into metrics + census reads, the stall flight recorder blaming the
exact injected frame, the faultinject ``loop_block`` kind driving a
real on-loop block, the watchdog ``loop_stall`` rule's hysteresis with
all three sinks + the incident-bundle join key on transitions and
webhook payloads, config-KV validation/live-reload on a booted server,
the continuous profiler + admin ``/profile``, and a paired on/off
overhead tripwire."""

import asyncio
import contextlib
import http.server
import json
import threading
import time

import pytest

from minio_tpu.faultinject import FAULTS
from minio_tpu.obs import loopmon
from minio_tpu.obs.incidents import INCIDENTS
from minio_tpu.obs.loopmon import LOOPMON, ContinuousProfiler
from minio_tpu.obs.metrics2 import METRICS2
from minio_tpu.obs.watchdog import (WATCHDOG, AlertRuleError, Watchdog,
                                    validate_user_rules)

ACCESS, SECRET = "lmadmin1", "lmadmin-secret1"


@pytest.fixture(autouse=True)
def _clean_state():
    WATCHDOG.reset()
    INCIDENTS.reset()
    FAULTS.clear()
    LOOPMON.set_enabled(True)
    prev_ms = LOOPMON.stall_ms
    # Park the threshold high: long-lived loops from EARLIER tests
    # (the process-wide rpc loop) stay registered, and a genuine
    # machine-load stall mid-test would land a real capture next to
    # the synthetic ones. Capture-driving tests configure their own
    # low threshold.
    LOOPMON.configure(stall_ms=60_000)
    with LOOPMON._mu:
        LOOPMON._stall_ring.clear()
    yield
    FAULTS.clear()
    LOOPMON.set_enabled(True)
    LOOPMON.stall_ms = prev_ms
    with LOOPMON._mu:
        LOOPMON._stall_ring.clear()
    WATCHDOG.reset()
    INCIDENTS.reset()


@contextlib.contextmanager
def _monitored_loop(name):
    """A real event loop on its own thread, registered with LOOPMON."""
    loop = asyncio.new_event_loop()
    t = threading.Thread(target=loop.run_forever, daemon=True,
                         name=f"lm-test-{name}")
    t.start()
    LOOPMON.register(name, loop)
    try:
        yield loop
    finally:
        LOOPMON.unregister(name)   # handshakes: heartbeat is done
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=5)
        loop.close()


def _wait(pred, timeout=10.0, period=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(period)
    return False


# ---------------------------------------------------------------------------
# Heartbeat lag telemetry


def test_heartbeat_measures_lag_census_and_metrics():
    hist0 = METRICS2.get("minio_tpu_v2_loop_lag_ms",
                         {"loop": "lm-t1"}) or (0.0, 0)
    with _monitored_loop("lm-t1"):
        assert _wait(lambda: "lm-t1" in LOOPMON.lag_census())
        # An idle loop's scheduling lag is small and non-negative.
        assert 0.0 <= LOOPMON.lag_census()["lm-t1"] < 250.0
        assert "lm-t1" in LOOPMON.task_census()
        assert _wait(lambda: (METRICS2.get(
            "minio_tpu_v2_loop_lag_ms",
            {"loop": "lm-t1"}) or (0.0, 0))[1] > hist0[1])
        rows = [r for r in LOOPMON.snapshot()["loops"]
                if r["loop"] == "lm-t1"]
        assert rows and rows[0]["beats"] >= 1
        assert rows[0]["p99Ms"] >= 0.0
        assert rows[0]["stalled"] is False
    # Unregister removes the loop from every census read.
    assert _wait(lambda: "lm-t1" not in LOOPMON.lag_census())


def test_register_is_idempotent():
    with _monitored_loop("lm-reg") as loop:
        assert _wait(lambda: "lm-reg" in LOOPMON.lag_census())
        beats = [r for r in LOOPMON.snapshot()["loops"]
                 if r["loop"] == "lm-reg"][0]["beats"]
        LOOPMON.register("lm-reg", loop)   # same loop: no re-arm
        time.sleep(0.3)
        rows = [r for r in LOOPMON.snapshot()["loops"]
                if r["loop"] == "lm-reg"]
        assert len(rows) == 1 and rows[0]["beats"] > beats


def test_configure_rejects_nonpositive_stall():
    for bad in (0, -5):
        with pytest.raises(ValueError):
            LOOPMON.configure(stall_ms=bad)
    LOOPMON.configure(stall_ms=123.0)
    assert LOOPMON.stall_ms == 123.0


# ---------------------------------------------------------------------------
# Stall flight recorder


def test_stall_capture_blames_injected_frame():
    from minio_tpu.logger import Logger
    LOOPMON.configure(stall_ms=150)
    with _monitored_loop("lm-stall") as loop:
        assert _wait(lambda: "lm-stall" in LOOPMON.lag_census())
        stalls0 = METRICS2.get("minio_tpu_v2_loop_stalls_total",
                               {"loop": "lm-stall"}) or 0
        loop.call_soon_threadsafe(loopmon._injected_loop_block, 0.4)
        assert _wait(lambda: any(
            e["loop"] == "lm-stall" for e in LOOPMON.recent_stalls()))
        entry = [e for e in LOOPMON.recent_stalls()
                 if e["loop"] == "lm-stall"][-1]
        # Captured WHILE blocked: the blamed frame is the blocking
        # CODE — not the heartbeat, asyncio machinery, or the
        # locktrace sleep shim the suite runs under.
        assert entry["topFrame"].startswith("_injected_loop_block")
        assert entry["overdueMs"] >= 150
        assert entry["topFrame"] in entry["stack"]
        assert (METRICS2.get("minio_tpu_v2_loop_stalls_total",
                             {"loop": "lm-stall"}) or 0) == stalls0 + 1
        # Cause-carrying console line with join-key fields.
        lines = [e for e in Logger.get().ring.tail(100)
                 if e.source == "loopmon" and "lm-stall" in e.message]
        assert lines, "no loopmon console line"
        assert "_injected_loop_block" in lines[-1].message
        assert lines[-1].fields["loop"] == "lm-stall"
        assert lines[-1].fields["frame"].startswith(
            "_injected_loop_block")
        # The episode closes once beats resume...
        assert _wait(lambda: not [
            r for r in LOOPMON.snapshot()["loops"]
            if r["loop"] == "lm-stall"][0]["stalled"])
        # ...and a SECOND block is a new episode with a new capture.
        loop.call_soon_threadsafe(loopmon._injected_loop_block, 0.4)
        assert _wait(lambda: (METRICS2.get(
            "minio_tpu_v2_loop_stalls_total",
            {"loop": "lm-stall"}) or 0) == stalls0 + 2)


def test_disabled_plane_records_nothing():
    LOOPMON.configure(stall_ms=150)
    with _monitored_loop("lm-off") as loop:
        assert _wait(lambda: "lm-off" in LOOPMON.lag_census())
        LOOPMON.set_enabled(False)
        stalls0 = METRICS2.get("minio_tpu_v2_loop_stalls_total",
                               {"loop": "lm-off"}) or 0
        loop.call_soon_threadsafe(loopmon._injected_loop_block, 0.3)
        time.sleep(0.6)
        assert (METRICS2.get("minio_tpu_v2_loop_stalls_total",
                             {"loop": "lm-off"}) or 0) == stalls0
        LOOPMON.set_enabled(True)


def test_faultinject_loop_block_drives_capture():
    """The e2e chain minus the server: a loop_block plan rule turns
    into a real block on the named loop via the heartbeat, and the
    recorder blames _injected_loop_block."""
    LOOPMON.configure(stall_ms=120)
    FAULTS.load_plan({"seed": 1, "rules": [
        {"kind": "loop_block", "target": "lm-fi",
         "latency_ms": 300, "count": 1}]})
    assert FAULTS.loop_block("unrelated") == 0.0
    with _monitored_loop("lm-fi"):
        assert _wait(lambda: any(
            e["loop"] == "lm-fi" for e in LOOPMON.recent_stalls()))
        entry = [e for e in LOOPMON.recent_stalls()
                 if e["loop"] == "lm-fi"][-1]
        assert entry["topFrame"].startswith("_injected_loop_block")
    FAULTS.clear()
    assert FAULTS.loop_block("lm-fi") == 0.0


# ---------------------------------------------------------------------------
# Watchdog loop_stall rule: hysteresis, sinks, incident join key


def S(t, qps=0):
    return {"t": float(t), "qps": {"write": qps}, "errors": {},
            "shed": {}, "slow": {}, "mrfDepth": 0, "mrfJournal": 0,
            "resets": 0, "cacheHits": 0, "cacheMisses": 0,
            "drives": {"suspect": 0, "faulty": 0, "quarantined": 0},
            "backendState": {}}


def make_wd(**kw):
    wd = Watchdog()
    base = dict(fast_s=10.0, slow_s=60.0, burn_threshold=0.10,
                pending_ticks=2, resolve_ticks=2)
    base.update(kw)
    wd.configure(**base)
    return wd


def _synthetic_stall(at, loop="s3-0", overdue=412.0):
    entry = {"loop": loop, "overdueMs": overdue, "at": at,
             "topFrame": "_injected_loop_block (loopmon.py:67)",
             "stack": ["_injected_loop_block (loopmon.py:67)",
                       "_run (events.py:78)"]}
    with LOOPMON._mu:
        LOOPMON._stall_ring.append(entry)
    return entry


def test_loop_stall_rule_hysteresis_sinks_and_bundle():
    from minio_tpu.logger import Logger
    wd = make_wd(pending_ticks=2, resolve_ticks=2)
    base = time.time()
    _synthetic_stall(base)
    fired0 = METRICS2.get("minio_tpu_v2_alert_transitions_total",
                          {"rule": "loop_stall",
                           "state": "firing"}) or 0
    # A ONE-SHOT 400ms block survives pending_ticks=2 on 1s ticks
    # because the capture keeps breaching for RECENT_STALL_S.
    trs = wd.tick(now=base + 1.0, samples=[S(base + 0.5, qps=1)])
    assert [(t["rule"], t["new"]) for t in trs] == [
        ("loop_stall", "pending")]
    trs = wd.tick(now=base + 2.0, samples=[S(base + 1.5, qps=1)])
    fired = [t for t in trs if t["new"] == "firing"]
    assert [t["rule"] for t in fired] == ["loop_stall"]
    # Cause names loop AND blamed frame.
    assert "s3-0" in fired[0]["cause"]
    assert "_injected_loop_block" in fired[0]["cause"]
    assert fired[0]["value"] == pytest.approx(412.0)
    # Sink 1: console line with join keys.
    lines = [e for e in Logger.get().ring.tail(100)
             if e.source == "watchdog" and "loop_stall" in e.message
             and "firing" in e.message]
    assert lines and lines[-1].fields["alert_id"] == fired[0]["alertId"]
    # Sink 2: metric series.
    assert METRICS2.get("minio_tpu_v2_alerts_firing",
                        {"rule": "loop_stall"}) == 1
    assert (METRICS2.get("minio_tpu_v2_alert_transitions_total",
                         {"rule": "loop_stall", "state": "firing"})
            or 0) == fired0 + 1
    # Sink 3: the incident bundle, joined by bundleId everywhere.
    assert fired[0]["bundleId"] == fired[0]["alertId"]
    idx = INCIDENTS.list()
    assert [b["rule"] for b in idx] == ["loop_stall"]
    assert idx[0]["bundleId"] == idx[0]["id"] == fired[0]["alertId"]
    bundle = INCIDENTS.get(idx[0]["id"])
    assert bundle["cause"] == fired[0]["cause"]
    # The frozen loops section carries the capture ring WITH stacks.
    stalls = bundle["loops"]["stalls"]
    assert stalls and stalls[-1]["topFrame"].startswith(
        "_injected_loop_block")
    assert stalls[-1]["stack"]
    # The window drains -> resolve_ticks clear ticks resolve it.
    late = base + loopmon.RECENT_STALL_S + 2.0
    assert wd.tick(now=late, samples=[S(late - 0.5, qps=1)]) == []
    trs = wd.tick(now=late + 1.0, samples=[S(late + 0.5, qps=1)])
    resolved = [t for t in trs if t["new"] == "resolved"]
    assert [t["rule"] for t in resolved] == ["loop_stall"]
    assert resolved[0]["bundleId"] == fired[0]["alertId"]
    assert METRICS2.get("minio_tpu_v2_alerts_firing",
                        {"rule": "loop_stall"}) == 0
    assert wd.state_of("loop_stall") == "ok"


def test_loop_stall_cause_counts_extra_captures():
    wd = make_wd(pending_ticks=1)
    base = time.time()
    _synthetic_stall(base, loop="s3-0", overdue=180.0)
    _synthetic_stall(base, loop="rpc", overdue=412.0)
    trs = wd.tick(now=base + 1.0, samples=[S(base + 0.5, qps=1)])
    fired = [t for t in trs if t["rule"] == "loop_stall"
             and t["new"] == "firing"]
    assert fired
    # Worst capture wins the headline; the rest are counted.
    assert "rpc" in fired[0]["cause"]
    assert "+1 more stall" in fired[0]["cause"]


def test_loop_stall_is_reserved_builtin_name():
    with pytest.raises(AlertRuleError):
        validate_user_rules(json.dumps([
            {"name": "loop_stall",
             "metric": "minio_tpu_v2_mrf_queue_depth", "value": 1}]))


class _Hook:
    """Local webhook target capturing posted alert JSON."""

    def __init__(self):
        received = self.received = []

        class H(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                received.append(json.loads(self.rfile.read(n)))
                self.send_response(200)
                self.send_header("Content-Length", "0")
                self.end_headers()

            def log_message(self, *a):
                pass

        self.httpd = http.server.ThreadingHTTPServer(
            ("127.0.0.1", 0), H)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}/"

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def test_webhook_payload_carries_bundle_join_key():
    hook = _Hook()
    try:
        wd = make_wd(pending_ticks=1, resolve_ticks=1,
                     webhook_endpoint=hook.url)
        base = time.time()
        _synthetic_stall(base)
        wd.tick(now=base + 1.0, samples=[S(base + 0.5, qps=1)])
        late = base + loopmon.RECENT_STALL_S + 2.0
        wd.tick(now=late, samples=[S(late - 0.5, qps=1)])
        assert _wait(lambda: len(hook.received) >= 2)
        by_state = {d["new"]: d for d in hook.received
                    if d["rule"] == "loop_stall"}
        assert set(by_state) == {"firing", "resolved"}
        # The webhook consumer can fetch the bundle by this id.
        fid = by_state["firing"]["bundleId"]
        assert fid == by_state["firing"]["alertId"]
        assert by_state["resolved"]["bundleId"] == fid
        assert INCIDENTS.get(fid)["rule"] == "loop_stall"
    finally:
        hook.close()


# ---------------------------------------------------------------------------
# Continuous profiler


def test_continuous_profiler_reports_folded_stacks():
    prof = ContinuousProfiler()
    stop = threading.Event()

    def _spin_for_profile():
        while not stop.is_set():
            sum(range(500))

    t = threading.Thread(target=_spin_for_profile, daemon=True)
    t.start()
    prof.start()
    prof.start()                       # idempotent
    try:
        assert prof.running is True
        assert _wait(lambda: prof.samples_total >= 3)
        rep = prof.report(top=20, minutes=1)
        assert rep["running"] is True and rep["samples"] >= 3
        assert rep["periodMs"] == pytest.approx(100.0)
        for row in rep["self"]:
            assert set(row) == {"function", "samples", "pct"}
        # The spinning thread dominates a quiet test process; its
        # frame must be visible both as self-time and in a folded
        # stack line ("f1;f2 N" — the flamegraph input format).
        assert any("_spin_for_profile" in r["function"]
                   for r in rep["self"])
        assert any("_spin_for_profile" in line and
                   line.rsplit(" ", 1)[1].isdigit()
                   for line in rep["folded"])
    finally:
        stop.set()
        prof.stop()
        t.join(timeout=5)
    assert prof.running is False


# ---------------------------------------------------------------------------
# Live server: loop registration, config-KV, admin /profile


def _start_server(tmp_path):
    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl import XLStorage
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    layer = ErasureObjects(disks, 2, 2, block_size=64 * 1024)
    srv = S3Server(layer, ACCESS, SECRET)
    port = srv.start()
    return srv, port


def _client(port):
    from minio_tpu.s3.client import S3Client
    return S3Client("127.0.0.1", port, ACCESS, SECRET)


def test_server_config_validation_reload_and_profile(tmp_path):
    srv, port = _start_server(tmp_path)
    try:
        c = _client(port)
        # Boot applied the defaults: stall bar + profiler running.
        assert LOOPMON.stall_ms == 250.0
        assert LOOPMON.profiler.running is True
        # Front-door loops and the RPC loop are registered.
        assert _wait(lambda: "s3-0" in LOOPMON.lag_census())
        # Live reload.
        r = c.request("POST", "/minio-tpu/admin/v1/set-config-kv",
                      body=b"obs loop_stall_ms=100")
        assert r.status == 200, r.body
        assert LOOPMON.stall_ms == 100.0
        # Rejected before persist; the previous value sticks.
        for bad in (b"obs loop_stall_ms=0",
                    b"obs loop_stall_ms=-5",
                    b"obs loop_stall_ms=nan",
                    b"obs loop_stall_ms=banana",
                    b"obs profile_continuous=maybe"):
            r = c.request("POST", "/minio-tpu/admin/v1/set-config-kv",
                          body=bad)
            assert r.status == 400, bad
        assert LOOPMON.stall_ms == 100.0
        r = c.request("POST", "/minio-tpu/admin/v1/set-config-kv",
                      body=b"obs profile_continuous=off")
        assert r.status == 200, r.body
        assert LOOPMON.profiler.running is False
        # Admin /profile serves even with the sampler paused (history
        # + loop census), and clamps its parameters.
        r = c.request("GET", "/minio-tpu/admin/v1/profile",
                      query="n=5&minutes=2")
        assert r.status == 200, r.body
        doc = json.loads(r.body)
        for field in ("running", "samples", "self", "folded", "loops"):
            assert field in doc, field
        assert doc["running"] is False
        assert doc["minutes"] == 2
        r = c.request("POST", "/minio-tpu/admin/v1/set-config-kv",
                      body=b"obs profile_continuous=on")
        assert r.status == 200, r.body
        assert LOOPMON.profiler.running is True
        assert _wait(lambda: json.loads(c.request(
            "GET", "/minio-tpu/admin/v1/profile").body)["samples"] > 0)
        # del-config-kv restores the defaults.
        r = c.request("POST", "/minio-tpu/admin/v1/del-config-kv",
                      body=b"obs")
        assert r.status == 200, r.body
        assert LOOPMON.stall_ms == 250.0
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Overhead tripwire


def test_paired_on_off_overhead_tripwire():
    """The monitor must be nearly free for loop work: a 10Hz heartbeat
    against thousands of wakeups per second. The bar is a TRIPWIRE for
    pathological regressions (e.g. per-callback hooks), deliberately
    generous so scheduler jitter can't flake it."""
    def batch(loop):
        async def work():
            for _ in range(2000):
                await asyncio.sleep(0)
        t0 = time.perf_counter()
        asyncio.run_coroutine_threadsafe(work(), loop).result(
            timeout=30)
        return time.perf_counter() - t0

    with _monitored_loop("lm-ovh") as loop:
        assert _wait(lambda: "lm-ovh" in LOOPMON.lag_census())
        on = sorted(batch(loop) for _ in range(5))[2]
        LOOPMON.set_enabled(False)
        off = sorted(batch(loop) for _ in range(5))[2]
        LOOPMON.set_enabled(True)
    assert on <= off * 3.0 + 0.05, (on, off)
