"""SLO watchdog + incident bundles (obs/watchdog.py, obs/incidents.py):
multi-window burn math (fast/slow agreement, volume floor), lifecycle
hysteresis on both edges, counter-reset immunity inherited from the
timeline, built-in rule sinks (console line + gauge + span event +
incident), user threshold rules + validation, webhook delivery with
bounded retry/drop, cluster merge with honest node counts, and the
end-to-end fault-harness scenario: an injected latency plan drives the
drive-degraded built-in pending->firing with a bundle containing the
blamed slowlog entry + timeline window, and clearing the plan resolves
the alert."""

import json
import os
import threading
import time
import urllib.request

import pytest

from minio_tpu.faultinject import FAULTS
from minio_tpu.obs.incidents import INCIDENTS
from minio_tpu.obs.metrics2 import METRICS2
from minio_tpu.obs.timeline import TIMELINE, Timeline
from minio_tpu.obs.watchdog import (WATCHDOG, AlertRuleError,
                                    AlertWebhook, Watchdog,
                                    burn_fractions, merge_alerts,
                                    validate_user_rules)

ACCESS, SECRET = "wdadmin", "wdadmin-secret"


@pytest.fixture(autouse=True)
def _clean_state():
    from minio_tpu.obs.kernprof import KERNPROF
    from minio_tpu.obs.loopmon import LOOPMON
    WATCHDOG.reset()
    INCIDENTS.reset()
    KERNPROF.reset()
    FAULTS.clear()
    # These tests assert EXACT transition lists; a genuine machine-load
    # stall on a long-lived loop (the process-wide rpc loop stays
    # registered across the suite) would make the built-in loop_stall
    # rule ride along. Park the threshold and drop any stale captures.
    prev_stall_ms = LOOPMON.stall_ms
    LOOPMON.configure(stall_ms=60_000)
    with LOOPMON._mu:
        LOOPMON._stall_ring.clear()
    yield
    WATCHDOG.reset()
    INCIDENTS.reset()
    KERNPROF.reset()
    FAULTS.clear()
    LOOPMON.configure(stall_ms=prev_stall_ms)
    with LOOPMON._mu:
        LOOPMON._stall_ring.clear()


def S(t, cls="write", qps=0, errors=0, shed=0, slow=0, mrf=0,
      journal=0, resets=0,
      cache_h=0, cache_m=0, drives=None, backend=None):
    """One synthetic timeline sample (the delta shape tick() emits)."""
    return {"t": float(t), "qps": {cls: qps}, "errors": {cls: errors},
            "shed": {cls: shed}, "slow": {cls: slow},
            "mrfDepth": mrf, "mrfJournal": journal, "resets": resets,
            "cacheHits": cache_h, "cacheMisses": cache_m,
            "drives": drives or {"suspect": 0, "faulty": 0,
                                 "quarantined": 0},
            "backendState": backend or {}}


def make_wd(**kw):
    wd = Watchdog()
    base = dict(fast_s=10.0, slow_s=60.0, burn_threshold=0.10,
                pending_ticks=2, resolve_ticks=2)
    base.update(kw)
    wd.configure(**base)
    return wd


# ---------------------------------------------------------------------------
# Burn-rate window math


def test_burn_requires_both_windows_to_breach():
    """Fast-only breach is a blip, not a burn: 50s of clean traffic
    dilutes the slow window below threshold, so a 10s error burst
    alone must not alert — only a burst against an ALREADY-burning
    slow window does."""
    wd = make_wd()
    clean = [S(t, qps=100) for t in range(50)]           # 5000 clean
    burst = [S(50 + i, qps=10, errors=9) for i in range(10)]
    # fast (t>50): 90/100 = 0.9 breach; slow (t>0): 90/5100 < 0.1.
    assert wd.tick(now=60.0, samples=clean + burst) == []
    assert wd.state_of("error_burn") == "ok"
    # All-bad history: both windows breach -> pending.
    trs = wd.tick(now=60.0, samples=burst)
    assert [(t["rule"], t["new"]) for t in trs] == [
        ("error_burn", "pending")]
    assert wd.state_of("error_burn") == "pending"


def test_burn_fraction_volume_floor():
    """1 failure out of 2 requests is 50% and still not a burn: below
    MIN_REQUESTS the fraction is not evaluated at all."""
    samples = [S(0, qps=2, errors=2)]
    fr = burn_fractions(samples, "errors", now=1.0, window_s=10.0,
                        min_requests=5)
    assert fr == {}
    wd = make_wd()
    assert wd.tick(now=1.0, samples=samples) == []


def test_burn_picks_worst_class_and_carries_cause():
    wd = make_wd(pending_ticks=1)
    samples = [dict(S(0), qps={"read": 100, "write": 10},
                    shed={"read": 20, "write": 9},
                    errors={}, slow={})]
    trs = wd.tick(now=1.0, samples=samples)
    fired = [t for t in trs if t["rule"] == "shed_burn"
             and t["new"] == "firing"]
    assert fired and "write" in fired[0]["cause"]  # 0.9 beats 0.2
    assert fired[0]["value"] == pytest.approx(0.9)


# ---------------------------------------------------------------------------
# Lifecycle hysteresis


def test_hysteresis_pending_ticks_gate_firing():
    wd = make_wd(pending_ticks=3, resolve_ticks=2)
    # Wall-clock-anchored stamps: snapshot()'s resolved-episode
    # retention window compares against time.time().
    base = time.time()

    def tick(now, breaching):
        # The sample rides just inside the window ending at `now`.
        return wd.tick(now=base + now, samples=[
            S(base + now - 0.5, qps=10, shed=8 if breaching else 0)])

    assert [t["new"] for t in tick(1, True)] == ["pending"]
    assert tick(2, True) == []                      # streak 2 of 3
    assert [t["new"] for t in tick(3, True)] == ["firing"]
    assert wd.fired_total == 1
    # One clear tick is not resolution...
    assert tick(101, False) == []
    assert wd.state_of("shed_burn") == "firing"
    # ...a breach resets the clear streak...
    assert tick(102, True) == []
    assert tick(103, False) == []
    # ...and only resolve_ticks consecutive clears resolve.
    assert [t["new"] for t in tick(104, False)] == ["resolved"]
    assert wd.state_of("shed_burn") == "ok"
    assert wd.snapshot()["resolved"][0]["rule"] == "shed_burn"


def test_flapping_below_hysteresis_never_fires_or_logs():
    wd = make_wd(pending_ticks=2, resolve_ticks=2)
    fired_before = METRICS2.get(
        "minio_tpu_v2_alert_transitions_total",
        {"rule": "shed_burn", "state": "firing"}) or 0
    transitions = []
    for i in range(6):
        now = 200.0 + i
        transitions += wd.tick(now=now, samples=[
            S(now - 0.5, qps=10, shed=8 if i % 2 == 0 else 0)])
    # Each breach opens a pending episode that dies quietly; firing
    # never happens and the quiet deaths emit no transitions.
    assert transitions and all(
        t["new"] == "pending" for t in transitions)
    assert wd.fired_total == 0
    assert (METRICS2.get("minio_tpu_v2_alert_transitions_total",
                         {"rule": "shed_burn", "state": "firing"})
            or 0) == fired_before


# ---------------------------------------------------------------------------
# Counter-reset immunity


class _ScriptedTimeline(Timeline):
    def __init__(self, **kw):
        super().__init__(**kw)
        self.raws: list[dict] = []

    @staticmethod
    def raw(qps_w=0, err_w=0):
        return {"qps": {"write": qps_w}, "shed": {},
                "errors": {"write": err_w}, "slow": {},
                "inflight": {}, "queueDepth": 0, "rx": 0, "tx": 0,
                "kernelBytes": {}, "hedgeFired": 0, "mrfDepth": 0,
                "drives": {"suspect": 0, "faulty": 0,
                           "quarantined": 0},
                "backendState": {}}

    def _read_raw(self):
        return self.raws.pop(0)


def test_counter_reset_rebases_and_is_counted():
    """A registry reset mid-window must not produce negative burn
    numerators (the delta re-bases) and IS itself a signal: the
    sample carries the re-base count for the counter_resets rule."""
    t = _ScriptedTimeline()
    t.raws = [t.raw(qps_w=100, err_w=50),
              t.raw(qps_w=140, err_w=60),
              t.raw(qps_w=20, err_w=5)]     # reset: both went DOWN
    t.tick(now=1.0)
    s1 = t.tick(now=2.0)
    assert s1["errors"]["write"] == 10 and s1["resets"] == 0
    s2 = t.tick(now=3.0)
    # Re-based on current values, never negative; resets counted.
    assert s2["qps"]["write"] == 20 and s2["errors"]["write"] == 5
    assert s2["resets"] == 2
    # Burn math over the re-based samples stays a sane fraction.
    fr = burn_fractions([s1, s2], "errors", now=3.0, window_s=10.0,
                        min_requests=5)
    assert 0.0 <= fr["write"] <= 1.0


def test_counter_reset_storm_rule():
    wd = make_wd(pending_ticks=1)
    calm = [S(t, qps=10, resets=1) for t in range(4)]
    assert wd.tick(now=4.0, samples=calm) == []    # 4 < STORM
    storm = [S(t, qps=10, resets=2) for t in range(5)]
    trs = wd.tick(now=5.0, samples=storm)          # 10 >= STORM
    assert any(t["rule"] == "counter_resets" and t["new"] == "firing"
               for t in trs)


# ---------------------------------------------------------------------------
# Built-in event rules + the three sinks


def test_drive_census_rule_all_sinks_and_incident():
    from minio_tpu.logger import Logger
    from minio_tpu.obs.span import TRACER
    wd = make_wd(pending_ticks=1, resolve_ticks=1)
    bad = [S(0, qps=10,
             drives={"suspect": 1, "faulty": 0, "quarantined": 0})]
    root = TRACER.begin("test.request", "wd-span-1")
    assert root is not None
    root.__enter__()
    trs = wd.tick(now=1.0, samples=bad)
    tree = root.finish()
    fired = [t for t in trs if t["new"] == "firing"]
    assert [t["rule"] for t in fired] == ["drive_degraded"]
    # Sink 1: cause-carrying console line with join-key fields.
    entries = [e for e in Logger.get().ring.tail(50)
               if e.source == "watchdog" and "drive_degraded" in
               e.message]
    assert entries and entries[-1].fields["rule"] == "drive_degraded"
    assert entries[-1].fields["alert_id"] == fired[0]["alertId"]
    # Sink 2: the metrics series.
    assert METRICS2.get("minio_tpu_v2_alerts_firing",
                        {"rule": "drive_degraded"}) == 1
    # Sink 3: the span event on the active trace.
    events = [e for e in tree.get("events", [])
              if e["name"] == "alert"]
    assert events and events[-1]["new"] == "firing"
    # Firing froze an incident bundle.
    idx = INCIDENTS.list()
    assert [b["rule"] for b in idx] == ["drive_degraded"]
    bundle = INCIDENTS.get(idx[0]["id"])
    assert "timeline" in bundle and "drives" in bundle
    assert bundle["cause"] == fired[0]["cause"]
    # Census clears -> resolved; the gauge drops.
    wd.tick(now=2.0, samples=[S(2, qps=10)])
    assert wd.state_of("drive_degraded") == "ok"
    assert METRICS2.get("minio_tpu_v2_alerts_firing",
                        {"rule": "drive_degraded"}) == 0


def test_backend_down_and_mrf_and_cache_rules():
    wd = make_wd(pending_ticks=1)
    # Kernel backend DOWN (state 2); DEGRADED (1) must NOT alert.
    ok = wd.tick(now=1.0, samples=[S(0, qps=1,
                                     backend={"device": 1})])
    assert not any(t["rule"] == "kernel_backend_down" for t in ok)
    trs = wd.tick(now=2.0, samples=[S(1, qps=1,
                                      backend={"device": 2})])
    assert any(t["rule"] == "kernel_backend_down"
               and t["new"] == "firing" and "device" in t["cause"]
               for t in trs)
    # The cause carries only the error CLASS — the raw lastError repr
    # (paths, compiler output) must not reach the unauthenticated
    # alerts surface.
    from minio_tpu.obs.kernprof import KERNPROF
    for _ in range(3):
        KERNPROF.dispatch_failed(
            "native", RuntimeError("/secret/build/path/lib.so: boom"))
    assert KERNPROF.state_of("native") == "down"
    wdn = make_wd(pending_ticks=1)
    trs = wdn.tick(now=1.0, samples=[S(0, qps=1,
                                       backend={"native": 2})])
    cause = [t for t in trs
             if t["rule"] == "kernel_backend_down"][0]["cause"]
    assert "RuntimeError" in cause and "/secret" not in cause, cause
    KERNPROF.reset()
    # MRF backlog: monotone growth to >= MIN_DEPTH over GROW_TICKS.
    wd2 = make_wd(pending_ticks=1)
    flat = [S(t, qps=1, mrf=20) for t in range(6)]
    assert not any(t["rule"] == "mrf_backlog"
                   for t in wd2.tick(now=6.0, samples=flat))
    growing = [S(t, qps=1, mrf=4 * t) for t in range(6)]
    trs = wd2.tick(now=6.0, samples=growing)
    assert any(t["rule"] == "mrf_backlog" and t["new"] == "firing"
               for t in trs)
    # Recovery backlog (the durable-queue twin): monotone growth of
    # the MRF journal backlog to >= MIN_DEPTH over GROW_TICKS; a flat
    # (even large) backlog stays quiet — a big-but-draining journal is
    # heal doing its job, growth is the non-convergence signal.
    wd_r = make_wd(pending_ticks=1)
    flat_j = [S(t, qps=1, journal=30) for t in range(6)]
    assert not any(t["rule"] == "recovery_backlog"
                   for t in wd_r.tick(now=6.0, samples=flat_j))
    growing_j = [S(t, qps=1, journal=3 * t) for t in range(6)]
    trs = wd_r.tick(now=6.0, samples=growing_j)
    assert any(t["rule"] == "recovery_backlog"
               and t["new"] == "firing" and "journal" in t["cause"]
               for t in trs)
    # Below MIN_DEPTH growth never fires (1-2-3 entries is noise).
    wd_s = make_wd(pending_ticks=1)
    small = [S(t, qps=1, journal=t) for t in range(6)]
    assert not any(t["rule"] == "recovery_backlog"
                   for t in wd_s.tick(now=6.0, samples=small))
    # Cache collapse: healthy slow-window ratio, collapsed fast one.
    wd3 = make_wd(fast_s=5.0, slow_s=60.0, pending_ticks=1)
    history = [S(t, qps=1, cache_h=90, cache_m=10)
               for t in range(50)]                      # 0.9 healthy
    collapsed = [S(55 + i, qps=1, cache_h=0, cache_m=30)
                 for i in range(5)]
    trs = wd3.tick(now=60.0, samples=history + collapsed)
    assert any(t["rule"] == "cache_collapse" and t["new"] == "firing"
               for t in trs)
    # An always-cold cache (no healthy history) never alerts.
    wd4 = make_wd(fast_s=5.0, slow_s=60.0, pending_ticks=1)
    cold = [S(t, qps=1, cache_h=0, cache_m=30) for t in range(60)]
    assert not any(t["rule"] == "cache_collapse"
                   for t in wd4.tick(now=60.0, samples=cold))


# ---------------------------------------------------------------------------
# User-defined threshold rules


def test_user_rule_validation():
    good = json.dumps([{"name": "deep_mrf",
                        "metric": "minio_tpu_v2_mrf_queue_depth",
                        "op": ">", "value": 100}])
    assert validate_user_rules(good)[0]["name"] == "deep_mrf"
    for bad, why in (
            ("{", "json"),
            ("{}", "array"),
            (json.dumps([{"name": "x", "metric": "nope",
                          "value": 1}]), "registered"),
            (json.dumps([{"name": "shed_burn",
                          "metric": "minio_tpu_v2_mrf_queue_depth",
                          "value": 1}]), "built-in"),
            (json.dumps([{"name": "a",
                          "metric": "minio_tpu_v2_mrf_queue_depth",
                          "value": 1, "op": ">="}]), "op"),
            (json.dumps([{"name": "a",
                          "metric": "minio_tpu_v2_mrf_queue_depth",
                          "value": 1},
                         {"name": "a",
                          "metric": "minio_tpu_v2_mrf_queue_depth",
                          "value": 2}]), "duplicate"),
            (json.dumps([{"name": "a",
                          "metric": "minio_tpu_v2_mrf_queue_depth",
                          "value": 1, "bogus": True}]), "unknown"),
    ):
        with pytest.raises(AlertRuleError):
            validate_user_rules(bad)


def test_user_threshold_value_and_rate_modes():
    METRICS2.set_gauge("minio_tpu_v2_hedge_budget_ms", None, 500.0)
    rules = validate_user_rules(json.dumps([
        {"name": "huge_budget",
         "metric": "minio_tpu_v2_hedge_budget_ms",
         "op": ">", "value": 100, "mode": "value"},
        {"name": "probe_storm",
         "metric": "minio_tpu_v2_kernel_backend_probes_total",
         "labels": {"result": "fail"},
         "op": ">", "value": 0.5, "mode": "rate", "window_s": 10},
    ]))
    wd = make_wd(pending_ticks=1, user_rules=rules)
    trs = wd.tick(now=1.0, samples=[S(0, qps=1)])
    assert any(t["rule"] == "huge_budget" and t["new"] == "firing"
               and "500" in t["cause"] for t in trs)
    # Rate rule: first tick is baseline-only; a 20-count jump over a
    # 10s window then reads 2/s > 0.5.
    assert not any(t["rule"] == "probe_storm" for t in trs)
    for _ in range(20):
        METRICS2.inc("minio_tpu_v2_kernel_backend_probes_total",
                     {"backend": "device", "result": "fail"})
    trs = wd.tick(now=2.0, samples=[S(1, qps=1)])
    assert any(t["rule"] == "probe_storm" and t["new"] == "firing"
               for t in trs)
    METRICS2.set_gauge("minio_tpu_v2_hedge_budget_ms", None, 0.0)


# ---------------------------------------------------------------------------
# Webhook delivery


class _Hook:
    """Local webhook target capturing posted alert JSON."""

    def __init__(self):
        import http.server

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


def test_webhook_delivers_firing_and_resolved():
    hook = _Hook()
    try:
        wd = make_wd(pending_ticks=1, resolve_ticks=1,
                     webhook_endpoint=hook.url)
        wd.tick(now=1.0, samples=[S(0, qps=10, shed=9)])
        wd.tick(now=2.0, samples=[S(2, qps=10)])
        deadline = time.time() + 10
        while time.time() < deadline and len(hook.received) < 2:
            time.sleep(0.05)
        kinds = [(d["rule"], d["new"]) for d in hook.received]
        assert ("shed_burn", "firing") in kinds
        assert ("shed_burn", "resolved") in kinds
        assert all(d["alertId"] for d in hook.received)
        assert wd._webhook.stats()["sent"] == len(hook.received)
    finally:
        hook.close()


def test_webhook_bounded_retry_and_drop():
    # A dead endpoint: RETRIES bounded attempts with backoff, then the
    # item counts failed — never a retry storm. An overflowing queue
    # drops (and counts) instead of blocking the watchdog tick.
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    wh = AlertWebhook(f"http://127.0.0.1:{port}/", queue_size=1)
    try:
        t0 = time.time()
        for i in range(4):
            wh.send({"rule": "r", "new": "firing", "i": i})
        stats = wh.stats()
        assert stats["dropped"] >= 1      # queue_size=1 overflowed
        deadline = time.time() + 20
        while time.time() < deadline and \
                wh.stats()["failed"] < 4 - stats["dropped"]:
            time.sleep(0.1)
        stats = wh.stats()
        assert stats["failed"] + stats["dropped"] == 4
        assert stats["sent"] == 0
        # Bounded: 3 attempts x backoff, not minutes of retries.
        assert time.time() - t0 < 15
    finally:
        wh.close()


def test_removing_firing_rule_zeroes_gauge_and_reset_does_too():
    """The firing gauge is transition-written: dropping a firing
    alert's rule (config edit) or reset() must zero it explicitly or
    it reads 1 on /v2/metrics forever."""
    rules = validate_user_rules(json.dumps([
        {"name": "stuck_gauge",
         "metric": "minio_tpu_v2_mrf_queue_depth",
         "op": ">", "value": -1}]))
    wd = make_wd(pending_ticks=1, user_rules=rules)
    wd.tick(now=1.0, samples=[S(0, qps=1)])
    assert METRICS2.get("minio_tpu_v2_alerts_firing",
                        {"rule": "stuck_gauge"}) == 1
    wd.configure(fast_s=10, slow_s=60, user_rules=())   # rule deleted
    assert METRICS2.get("minio_tpu_v2_alerts_firing",
                        {"rule": "stuck_gauge"}) == 0
    # Same for reset() mid-firing.
    wd2 = make_wd(pending_ticks=1)
    wd2.tick(now=1.0, samples=[S(0, qps=10, shed=9)])
    assert METRICS2.get("minio_tpu_v2_alerts_firing",
                        {"rule": "shed_burn"}) == 1
    wd2.reset()
    assert METRICS2.get("minio_tpu_v2_alerts_firing",
                        {"rule": "shed_burn"}) == 0


def test_webhook_close_with_full_queue_stops_worker():
    """close() racing a FULL queue can't enqueue its sentinel; the
    closed flag must still stop the worker at its next item instead
    of leaving it retrying stale alerts forever."""
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    wh = AlertWebhook(f"http://127.0.0.1:{port}/", queue_size=2)
    for i in range(6):
        wh.send({"i": i})
    wh.close()                      # queue likely full: sentinel lost
    wh._worker.join(timeout=20)     # flag stops it within one item
    assert not wh._worker.is_alive()
    assert wh.send({"late": True}) is None  # post-close sends drop
    # No alert vanishes untallied: everything submitted before the
    # close is accounted sent, failed, or dropped.
    stats = wh.stats()
    assert stats["sent"] + stats["failed"] + stats["dropped"] == 6, \
        stats
    assert stats["queued"] == 0


# ---------------------------------------------------------------------------
# Cluster merge


def test_merge_alerts_worst_state_and_node_counts():
    a = {"alerts": [{"rule": "shed_burn", "state": "firing",
                     "alertId": "shed_burn-3", "cause": "bad",
                     "value": 0.9}]}
    b = {"alerts": [{"rule": "shed_burn", "state": "pending",
                     "alertId": "shed_burn-1", "cause": "meh",
                     "value": 0.2},
                    {"rule": "mrf_backlog", "state": "firing",
                     "alertId": "mrf_backlog-1", "cause": "deep",
                     "value": 64.0}]}
    merged = merge_alerts([("local", a), ("peer0", b)])
    assert merged["nodes"] == 2
    assert merged["firing"] == 2
    by_rule = {x["rule"]: x for x in merged["alerts"]}
    shed = by_rule["shed_burn"]
    assert shed["state"] == "firing"          # worst across nodes
    assert shed["nodesFiring"] == 1
    assert sorted(shed["nodes"]) == ["local", "peer0"]
    assert shed["cause"] == "bad"             # worst value's cause
    assert by_rule["mrf_backlog"]["nodes"] == ["peer0"]
    # Empty cluster merges clean.
    assert merge_alerts([])["alerts"] == []


# ---------------------------------------------------------------------------
# Incident recorder bounds


def test_incident_ring_and_byte_bounds():
    for i in range(20):
        INCIDENTS.capture({"alertId": f"r-{i}", "rule": "r",
                           "cause": "c", "value": 1.0})
    idx = INCIDENTS.list()
    assert len(idx) == 16                      # MAX_BUNDLES
    assert idx[-1]["id"] == "r-19"             # newest kept
    assert idx[0]["id"] == "r-4"               # oldest evicted
    with pytest.raises(KeyError):
        INCIDENTS.get("r-0")
    assert all(b["bytes"] <= 512 * 1024 for b in idx)


def test_incident_byte_cap_holds_even_without_droppable_sections():
    """A pathological census (nothing in the droppable list) must
    still respect the byte cap — it is a memory bound, not advice."""
    INCIDENTS.providers["huge"] = lambda: "x" * (600 * 1024)
    try:
        INCIDENTS.capture({"alertId": "big-1", "rule": "r",
                           "cause": "c", "value": 1.0})
        b = INCIDENTS.get("big-1")
        assert b["bytes"] <= 512 * 1024
        assert "huge" in b["truncated"]
        assert b["cause"] == "c"          # headline survives
    finally:
        del INCIDENTS.providers["huge"]


def test_incident_config_redaction():
    from minio_tpu.obs.incidents import _redact_config
    doc = {"audit_webhook": {"_": {"endpoint": "http://x",
                                   "auth_token": "hunter2",
                                   "enable": "on"}},
           "alerts": {"_": {"webhook_auth_token": "",
                            "burn_threshold": "0.1"}}}
    red = _redact_config(doc)
    assert red["audit_webhook"]["_"]["auth_token"] == "REDACTED"
    assert red["audit_webhook"]["_"]["endpoint"] == "http://x"
    # Empty credentials stay empty (redacting "" would imply one).
    assert red["alerts"]["_"]["webhook_auth_token"] == ""


# ---------------------------------------------------------------------------
# Structured JSON log mode (logger satellite)


def test_logger_json_mode_carries_join_keys(capsys):
    from minio_tpu.logger.logger import Logger
    lg = Logger(json_output=True)
    lg.warn("watchdog: alert shed_burn pending -> firing (x)",
            "watchdog", alert_id="shed_burn-7", rule="shed_burn")
    line = capsys.readouterr().err.strip().splitlines()[-1]
    doc = json.loads(line)
    assert doc["level"] == "WARN"
    assert doc["fields"] == {"alert_id": "shed_burn-7",
                             "rule": "shed_burn"}
    # Text mode renders the fields as a suffix and stays one line.
    lg2 = Logger(json_output=False)
    lg2.info("drivemon: d ok -> suspect", "drivemon", disk="d#1",
             state="suspect", quarantined=False)
    out = capsys.readouterr().err.strip().splitlines()[-1]
    assert "[disk=d#1 quarantined=False state=suspect]" in out


def test_logger_env_opt_in(monkeypatch):
    from minio_tpu.logger.logger import Logger
    monkeypatch.setenv("MINIO_LOG_JSON", "1")
    assert Logger().json_output is True
    monkeypatch.setenv("MINIO_LOG_JSON", "0")
    assert Logger().json_output is False
    monkeypatch.delenv("MINIO_LOG_JSON")
    assert Logger().json_output is False


# ---------------------------------------------------------------------------
# Live server: endpoints, config reload, lost-peer honesty, e2e


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl import XLStorage
    root = tmp_path_factory.mktemp("wddisks")
    disks = [XLStorage(str(root / f"d{i}")) for i in range(6)]
    layer = ErasureObjects(disks, 4, 2, block_size=64 * 1024)
    srv = S3Server(layer, ACCESS, SECRET)
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


def test_alerts_endpoint_shape_and_config_reload(server):
    srv, port = server
    doc = _get_json(port, "/minio-tpu/v2/alerts")
    for field in ("enabled", "alerts", "resolved", "firing",
                  "pending", "rules", "windows"):
        assert field in doc, field
    assert "shed_burn" in doc["rules"]
    c = _client(port)
    # Live reload.
    r = c.request("POST", "/minio-tpu/admin/v1/set-config-kv",
                  body=b"alerts fast_window=2s burn_threshold=0.25 "
                       b"pending_ticks=4")
    assert r.status == 200, r.body
    assert WATCHDOG.fast_s == pytest.approx(2.0)
    assert WATCHDOG.burn_threshold == pytest.approx(0.25)
    assert WATCHDOG.pending_ticks == 4
    # Rejected before persist.
    for bad in (b"alerts burn_threshold=2",
                b"alerts fast_window=banana",
                b"alerts pending_ticks=0",
                b"alerts enable=maybe",
                b"alerts webhook_endpoint=ftp://x",
                # fast > (effective) slow would degenerate the
                # two-window confirm: rejected, not silently clamped.
                b"alerts fast_window=30m",
                b"alerts fast_window=5m slow_window=2m",
                b'alerts rules=[{"name":"x","metric":"nope",'
                b'"value":1}]'):
        r = c.request("POST", "/minio-tpu/admin/v1/set-config-kv",
                      body=bad)
        assert r.status == 400, bad
    # A user rule installs live.
    # COMPACT JSON (no spaces), like the fault_inject plan: the kv
    # line parser splits on unquoted spaces.
    rule = json.dumps([{"name": "cold_cache",
                        "metric": "minio_tpu_v2_cache_misses_total",
                        "op": ">", "value": 1e12}],
                      separators=(",", ":"))
    r = c.request("POST", "/minio-tpu/admin/v1/set-config-kv",
                  body=f'alerts rules={rule}'.encode())
    assert r.status == 200, r.body
    assert "cold_cache" in _get_json(
        port, "/minio-tpu/v2/alerts")["rules"]
    r = c.request("POST", "/minio-tpu/admin/v1/del-config-kv",
                  body=b"alerts")
    assert r.status == 200, r.body
    assert WATCHDOG.pending_ticks == 2


def test_unrelated_config_write_keeps_rule_state(server):
    """The apply hook runs on EVERY config write; only an effective
    alerts-config change may rebuild the rule set — a rebuild resets
    rate-rule delta windows and would falsely resolve a firing alert
    while an operator tunes an unrelated key mid-incident."""
    srv, port = server
    c = _client(port)
    rule = json.dumps([{"name": "probe_rate",
                        "metric":
                            "minio_tpu_v2_kernel_backend_probes_total",
                        "op": ">", "value": 1e9, "mode": "rate"}],
                      separators=(",", ":"))
    r = c.request("POST", "/minio-tpu/admin/v1/set-config-kv",
                  body=f"alerts rules={rule}".encode())
    assert r.status == 200, r.body
    before = id(WATCHDOG._rules["probe_rate"])
    r = c.request("POST", "/minio-tpu/admin/v1/set-config-kv",
                  body=b"api requests_max_list=7")
    assert r.status == 200, r.body
    assert id(WATCHDOG._rules["probe_rate"]) == before
    # An alerts write DOES rebuild.
    r = c.request("POST", "/minio-tpu/admin/v1/set-config-kv",
                  body=b"alerts pending_ticks=3")
    assert r.status == 200, r.body
    assert id(WATCHDOG._rules["probe_rate"]) != before
    c.request("POST", "/minio-tpu/admin/v1/del-config-kv",
              body=b"alerts")
    c.request("POST", "/minio-tpu/admin/v1/del-config-kv",
              body=b"api")


def test_stop_unregisters_incident_providers(tmp_path):
    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl import XLStorage
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(6)]
    layer = ErasureObjects(disks, 4, 2, block_size=64 * 1024)
    srv = S3Server(layer, ACCESS, SECRET)
    srv.start()
    assert INCIDENTS.providers["config"] == srv._incident_config
    srv.stop()
    # A stopped server must not stay reachable through the recorder
    # (nor report a dead server's config in later bundles).
    assert "config" not in INCIDENTS.providers
    assert "mrf" not in INCIDENTS.providers


def test_cluster_alerts_lost_peer_keeps_honest_counts(server):
    srv, port = server

    class _DeadClient:
        def call(self, *a, **kw):
            raise OSError("peer unreachable")

    from minio_tpu.rpc.peer import NotificationSys
    old = srv.notification
    srv.notification = NotificationSys({"n2": _DeadClient()})
    srv._cluster_alerts_cache = None
    try:
        doc = _get_json(port, "/minio-tpu/v2/alerts/cluster")
        # The lost peer is REPORTED unreachable, not silently counted
        # as an alert-free node.
        assert doc["nodes"] == 1
        assert doc["unreachable"] == 1
        assert isinstance(doc["alerts"], list)
    finally:
        srv.notification = old
        srv._cluster_alerts_cache = None


def test_e2e_fault_plan_fires_drive_alert_with_incident(tmp_path):
    """Acceptance: an injected latency fault plan drives the
    drive-degraded built-in pending -> firing within budget, with a
    cause-carrying console line, metrics series, and an incident
    bundle containing the blamed slowlog entry + timeline window;
    mtpu_top --once exits nonzero while firing; clearing the plan
    resolves the alert."""
    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.logger import Logger
    from minio_tpu.obs.drivemon import DRIVEMON
    from minio_tpu.obs.slowlog import SLOWLOG
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl import XLStorage
    from tools import mtpu_top

    # A suspect/faulty drive leaked into the global DRIVEMON by an
    # EARLIER module would keep drive_degraded breaching forever and
    # the resolution phase below could never pass — start from a
    # clean census (the engine constructed next re-registers its own
    # drives).
    if DRIVEMON.counts() != (0, 0) or DRIVEMON.quarantined_endpoints():
        DRIVEMON.reset()
    roots = [str(tmp_path / f"d{i}") for i in range(6)]
    disks = [XLStorage(r) for r in roots]
    slow_ep = disks[5].root
    layer = ErasureObjects(disks, 4, 2, block_size=64 * 1024)
    srv = S3Server(layer, ACCESS, SECRET)
    port = srv.start()
    try:
        # Fast sampler + short burn windows + tight hysteresis: the
        # whole loop must run inside a test budget.
        srv.config.set_kv("obs slow_ms=1 timeline_sample=100ms")
        srv.config.set_kv("alerts fast_window=3s slow_window=30s "
                          "pending_ticks=2 resolve_ticks=2")
        c = _client(port)
        r = c.request(
            "POST", "/minio-tpu/admin/v1/fault-inject",
            body=json.dumps({"seed": 1, "rules": [
                {"kind": "latency", "target": slow_ep,
                 "latency_ms": 25}]}).encode())
        assert r.status == 200, r.body
        assert c.make_bucket("wde2e").status == 200
        body = os.urandom(150_000)
        for i in range(30):
            assert c.put_object("wde2e", f"k{i}", body).status == 200
            if DRIVEMON.state_of(slow_ep) == "suspect":
                break
        assert DRIVEMON.state_of(slow_ep) == "suspect", \
            DRIVEMON.snapshot()

        # The built-in fires within budget (sampler ticks at 100ms).
        deadline = time.time() + 15
        while time.time() < deadline and \
                WATCHDOG.state_of("drive_degraded") != "firing":
            time.sleep(0.1)
        assert WATCHDOG.state_of("drive_degraded") == "firing", \
            WATCHDOG.snapshot()

        # Unauthenticated node endpoint carries the cause (redacted
        # drive identity, never the absolute path).
        doc = _get_json(port, "/minio-tpu/v2/alerts")
        mine = [a for a in doc["alerts"]
                if a["rule"] == "drive_degraded"]
        assert mine and mine[0]["state"] == "firing"
        assert "suspect" in mine[0]["cause"]
        assert slow_ep not in mine[0]["cause"]
        # Cause-carrying console line with join keys. The tick thread
        # logs it a moment after the state flip: poll, like the gauge
        # below (an earlier test's firing line is in the ring too).
        deadline = time.time() + 5
        while True:
            lines = [e for e in Logger.get().ring.tail(200)
                     if e.source == "watchdog"
                     and "drive_degraded" in e.message
                     and "firing" in e.message]
            if (lines and lines[-1].fields["alert_id"]
                    == mine[0]["alertId"]) or time.time() > deadline:
                break
            time.sleep(0.05)
        assert lines and lines[-1].fields["alert_id"] == \
            mine[0]["alertId"]
        # The gauge is written by the sampler-tick thread moments
        # after the state flip — under full-suite CPU contention the
        # assertions above can outrun it, so poll like the census
        # check below does.
        deadline = time.time() + 5
        while time.time() < deadline and METRICS2.get(
                "minio_tpu_v2_alerts_firing",
                {"rule": "drive_degraded"}) != 1:
            time.sleep(0.05)
        assert METRICS2.get("minio_tpu_v2_alerts_firing",
                            {"rule": "drive_degraded"}) == 1

        # mtpu_top --once is a health probe: nonzero while firing.
        # The sample's alert census lags the engine by one tick (the
        # watchdog evaluates AFTER each sample lands) — wait for the
        # census to catch up before asserting the exit code.
        deadline = time.time() + 10
        while time.time() < deadline:
            doc = _get_json(port, "/minio-tpu/v2/timeline?n=1")
            if doc["samples"] and (doc["samples"][-1]["alerts"]
                                   .get("firing", 0)) >= 1:
                break
            time.sleep(0.05)
        assert mtpu_top.main(
            ["--url", f"http://127.0.0.1:{port}", "--once"]) == 2

        # The incident bundle survives the rings: timeline window,
        # the blamed slowlog entries, the drive census, the fault
        # plan that caused it all, and the effective config.
        r = c.request("GET", "/minio-tpu/admin/v1/incidents")
        assert r.status == 200, r.body
        idx = json.loads(r.body)["incidents"]
        mine = [b for b in idx if b["rule"] == "drive_degraded"]
        assert mine, idx
        r = c.request("GET", "/minio-tpu/admin/v1/incidents",
                      query=f"id={mine[-1]['id']}")
        assert r.status == 200, r.body
        bundle = json.loads(r.body)
        assert bundle["timeline"]["samples"], "no timeline window"
        assert any((s.get("drives") or {}).get("suspect", 0) >= 1
                   for s in bundle["timeline"]["samples"])
        blamed = [e for e in bundle["slowlog"]
                  if e["blamedLayer"] == "disk"]
        assert blamed, bundle["slowlog"][-3:]
        assert bundle["worstTrace"] and bundle["worstTrace"]["spans"]
        assert bundle["drives"]["suspect"] >= 1
        assert bundle["faultPlan"]["active"] is True
        assert bundle["config"]["alerts"]["_"]["fast_window"] == "3s"
        # Unknown ids 404.
        r = c.request("GET", "/minio-tpu/admin/v1/incidents",
                      query="id=nope")
        assert r.status == 404

        # Clear the plan; scoring decays below the outlier bar and
        # the alert resolves.
        r = c.request("POST", "/minio-tpu/admin/v1/fault-inject",
                      query="clear=true")
        assert r.status == 200, r.body
        for i in range(120):
            assert c.put_object("wde2e", f"heal{i}",
                                body).status == 200
            if DRIVEMON.state_of(slow_ep) == "ok":
                break
        assert DRIVEMON.state_of(slow_ep) == "ok", DRIVEMON.snapshot()
        deadline = time.time() + 15
        while time.time() < deadline and \
                WATCHDOG.state_of("drive_degraded") != "ok":
            time.sleep(0.1)
        assert WATCHDOG.state_of("drive_degraded") == "ok", \
            WATCHDOG.snapshot()
        assert METRICS2.get("minio_tpu_v2_alerts_firing",
                            {"rule": "drive_degraded"}) == 0
        resolved = [x for x in WATCHDOG.snapshot()["resolved"]
                    if x["rule"] == "drive_degraded"]
        assert resolved, WATCHDOG.snapshot()
    finally:
        FAULTS.clear()
        srv.stop()
        SLOWLOG.configure(1000.0, {}, False)


def test_timeline_sample_carries_alert_census(server):
    """The alerts census rides every sample (mtpu_top's row and the
    cluster merge read it from there)."""
    srv, port = server
    deadline = time.time() + 10
    sample = None
    while time.time() < deadline:
        doc = _get_json(port, "/minio-tpu/v2/timeline?n=1")
        if doc["samples"]:
            sample = doc["samples"][-1]
            break
        time.sleep(0.05)
    assert sample is not None
    assert set(sample["alerts"]) == {"firing", "pending", "worst"}
    for field in ("errors", "slow", "resets"):
        assert field in sample, field
