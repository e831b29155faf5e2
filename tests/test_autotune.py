"""Codec dispatch autotuner (ops/autotune.py): probe-ladder seeding,
bounded live convergence, hysteresis, kernprof-DOWN gating, the
three-sink plan-transition contract (console line + codec.plan span
event + codec_plan_* gauge), the reprobe-rebuilds-mesh regression
(ISSUE 13 satellite), config plumbing, and the timeline / mtpu_top /
admin surfacing."""

from __future__ import annotations

import json
import urllib.request

import numpy as np
import pytest

from minio_tpu.obs.kernprof import (DEVICE, HOST, KERNPROF, NATIVE,
                                    XLA_CPU)
from minio_tpu.obs.metrics2 import METRICS2
from minio_tpu.ops import batching
from minio_tpu.ops.autotune import (AUTOTUNE, BUCKETS, RS_DECODE,
                                    RS_ENCODE, size_bucket)

ACCESS, SECRET = "atadmin", "atadmin-secret"


@pytest.fixture(autouse=True)
def _clean_state():
    AUTOTUNE.reset()
    KERNPROF.reset()
    yield
    AUTOTUNE.reset()
    KERNPROF.reset()


@pytest.fixture(scope="module")
def ladder_results():
    """One real probe ladder for the module (it pays jit compiles);
    tests that need a probed planner re-seed from these measurements
    instead of re-probing."""
    AUTOTUNE.reset()
    res = AUTOTUNE.probe_ladder()
    model = {k: (v.bps, v.samples)
             for k, v in AUTOTUNE._model.items()}
    plan = dict(AUTOTUNE._plan)
    AUTOTUNE.reset()
    return res, model, plan


def _seed_from(ladder_results):
    """Restore the module-probed model/plan onto the fresh AUTOTUNE."""
    _res, model, plan = ladder_results
    with AUTOTUNE._mu:
        for key, (bps, samples) in model.items():
            from minio_tpu.ops.autotune import _LaneModel
            m = _LaneModel()
            m.bps, m.samples = bps, samples
            AUTOTUNE._model[key] = m
        AUTOTUNE._plan.update(plan)
        AUTOTUNE._probed = True


# ---------------------------------------------------------------------------
# model basics


def test_size_buckets_cover_the_range():
    assert size_bucket(1) == "<64K"
    assert size_bucket(64 * 1024) == "<64K"
    assert size_bucket(64 * 1024 + 1) == "64K-1M"
    assert size_bucket(4 << 20) == "1-4M"
    assert size_bucket(16 << 20) == "4-16M"
    assert size_bucket(64 << 20) == "16M+"
    assert set(BUCKETS) == {"<64K", "64K-1M", "1-4M", "4-16M", "16M+"}


def test_static_policy_before_probe():
    """Pre-measurement the planner reproduces the legacy policy: no
    device on this box -> the host route for every size."""
    assert not AUTOTUNE._probed
    assert AUTOTUNE.decide(RS_ENCODE, 1024) == NATIVE
    assert AUTOTUNE.decide(RS_ENCODE, 32 << 20) == NATIVE
    assert not AUTOTUNE.use_jit_lane(RS_ENCODE, 32 << 20)
    assert not AUTOTUNE.coalesce_worthwhile()


def test_probe_ladder_measures_and_plans(ladder_results):
    """The ladder measures every reachable lane per rung with a
    known-answer check and the plan converges on the measured-fastest
    lane — host-native on a box with no accelerator, where the jit
    lane is XLA on the CPU and far slower."""
    res, _model, plan = ladder_results
    # Reachable lanes on a no-device box: native, xla-cpu, host.
    assert XLA_CPU in res and HOST in res and DEVICE not in res
    for lane, rungs in res.items():
        assert set(rungs) == {"<64K", "64K-1M", "1-4M", "4-16M"}
    # Native measured meaningfully faster than jit-on-CPU.
    if all(v for v in res.get(NATIVE, {}).values()):
        assert res[NATIVE]["1-4M"] > res[XLA_CPU]["1-4M"]
    # Full plan coverage, every bucket on a measured healthy lane.
    # Codec kernels fully covered; select_scan and regen_code run
    # their OWN known-answer probes, covering their buckets too.
    assert set(plan) == {(k, b)
                         for k in (RS_ENCODE, RS_DECODE,
                                   "select_scan", "regen_code")
                         for b in BUCKETS}
    fastest = {b: max((res[ln][b], ln) for ln in res)[1]
               for b in ("<64K", "64K-1M", "1-4M", "4-16M")}
    for (kern, bucket), lane in plan.items():
        if kern not in (RS_ENCODE, RS_DECODE):
            continue  # select_scan plans from its OWN probe results
        if bucket in fastest:
            assert lane == fastest[bucket], (kern, bucket)


def test_decide_follows_probed_plan(ladder_results):
    _seed_from(ladder_results)
    for nbytes in (1024, 1 << 20, 8 << 20, 64 << 20):
        lane = AUTOTUNE.decide(RS_ENCODE, nbytes)
        assert lane == AUTOTUNE._plan[(RS_ENCODE,
                                       size_bucket(nbytes))]


def test_never_selects_a_down_lane(ladder_results):
    """Acceptance: a kernprof-DOWN lane is never chosen, at decision
    time (not just plan time)."""
    _seed_from(ladder_results)
    chosen = AUTOTUNE.decide(RS_ENCODE, 1 << 20)
    for _ in range(KERNPROF.DOWN_AFTER):
        KERNPROF.dispatch_failed(chosen, RuntimeError("boom"))
    assert not KERNPROF.allow(chosen)
    alt = AUTOTUNE.decide(RS_ENCODE, 1 << 20)
    assert alt != chosen
    assert KERNPROF.allow(alt)
    # The fallback is the measured next-best, not arbitrary: on this
    # box host (0.1x) beats xla-cpu (0.02x).
    res = ladder_results[0]
    ranked = sorted(((res[ln]["64K-1M"], ln) for ln in res
                     if ln != chosen and res[ln]["64K-1M"]),
                    reverse=True)
    assert alt == ranked[0][1]


def test_fallback_prefers_host_over_xla_without_data():
    """No model data + static lane DOWN on a deviceless box: the last
    resort is numpy host, never jit-on-CPU (the bit-plane matmul on
    XLA's CPU backend is several times slower than numpy —
    post-review regression)."""
    from minio_tpu.obs.kernprof import NATIVE as _N
    for _ in range(KERNPROF.DOWN_AFTER):
        KERNPROF.dispatch_failed(_N, RuntimeError("native broke"))
    assert AUTOTUNE.decide(RS_ENCODE, 1 << 20) == HOST


def test_xla_cpu_unreachable_while_device_present(monkeypatch):
    """attempt_backend() can't land on xla-cpu while a device answers
    — a stale xla-cpu model entry must never route a dispatch onto
    the (possibly DOWN) device (post-review regression)."""
    monkeypatch.setattr(batching, "_device_present", True)
    monkeypatch.setattr(batching, "_device_count", 1)
    assert not AUTOTUNE._lane_available(XLA_CPU)
    assert AUTOTUNE._lane_available(DEVICE)
    monkeypatch.setattr(batching, "_device_present", False)
    assert AUTOTUNE._lane_available(XLA_CPU)


def test_live_convergence_is_bounded():
    """Without any probe ladder (codec probe_on_boot=off), the plan
    engages after MIN_SAMPLES live dispatches per bucket — bounded
    convergence to the measured-fastest exercised lane."""
    assert AUTOTUNE.decide(RS_ENCODE, 1 << 20) == NATIVE  # static
    nbytes = 1 << 20
    for _ in range(AUTOTUNE.MIN_SAMPLES):
        AUTOTUNE.observe(RS_ENCODE, NATIVE, nbytes, 0.001)
    # Plan present and engaged despite _probed == False.
    assert AUTOTUNE._plan[(RS_ENCODE, "64K-1M")] == NATIVE
    assert AUTOTUNE.decide(RS_ENCODE, nbytes) == NATIVE
    # A slower lane's samples never flip it.
    for _ in range(AUTOTUNE.MIN_SAMPLES + 2):
        AUTOTUNE.observe(RS_ENCODE, HOST, nbytes, 0.01)
    assert AUTOTUNE.decide(RS_ENCODE, nbytes) == NATIVE


def test_hysteresis_blocks_noisy_flips():
    """A challenger inside the hysteresis margin never unseats the
    incumbent; a decisive one does (with MIN_SAMPLES evidence)."""
    nbytes = 1 << 20
    for _ in range(AUTOTUNE.MIN_SAMPLES):
        AUTOTUNE.observe(RS_ENCODE, NATIVE, nbytes, 0.001)
    AUTOTUNE._probed = True
    # 1.1x faster < 1.25 hysteresis: no flip, even with samples.
    for _ in range(AUTOTUNE.MIN_SAMPLES + 1):
        AUTOTUNE.observe(RS_ENCODE, HOST, nbytes, 0.001 / 1.1)
    assert AUTOTUNE._plan[(RS_ENCODE, "64K-1M")] == NATIVE
    # 2x faster: flips.
    for _ in range(AUTOTUNE.MIN_SAMPLES + 1):
        AUTOTUNE.observe(RS_ENCODE, HOST, nbytes, 0.001 / 2.5)
    assert AUTOTUNE._plan[(RS_ENCODE, "64K-1M")] == HOST


def test_one_noisy_sample_cannot_flap():
    nbytes = 1 << 20
    for _ in range(AUTOTUNE.MIN_SAMPLES):
        AUTOTUNE.observe(RS_ENCODE, NATIVE, nbytes, 0.001)
    AUTOTUNE._probed = True
    before = AUTOTUNE._plan_version
    # One wild sample on another lane: EWMA admits it, but with one
    # sample the flip is rejected.
    AUTOTUNE.observe(RS_ENCODE, HOST, nbytes, 0.00001)
    assert AUTOTUNE._plan[(RS_ENCODE, "64K-1M")] == NATIVE
    assert AUTOTUNE._plan_version == before


def test_coalesce_window_stops_after_live_evidence(monkeypatch):
    """probe_on_boot=off (no ladder): once EVERY encode bucket has
    engaged live evidence routing off-device, the coalescing window
    stops — a window in front of host encodes is pure latency
    (post-review regression: this used to require the ladder)."""
    monkeypatch.setattr(batching, "_device_present", True)
    monkeypatch.setattr(batching, "_device_count", 1)
    assert AUTOTUNE.coalesce_worthwhile()  # static: device present
    for nbytes in (1024, 1 << 20, 2 << 20, 8 << 20, 32 << 20):
        for _ in range(AUTOTUNE.MIN_SAMPLES):
            # Walls must clear MIN_WALL_S or the sample is rejected
            # as a timer blip.
            AUTOTUNE.observe(RS_ENCODE, NATIVE, nbytes,
                             max(nbytes / 1e9, 1e-4))
    assert not AUTOTUNE._probed
    assert not AUTOTUNE.coalesce_worthwhile()


# ---------------------------------------------------------------------------
# three sinks


def test_plan_transition_hits_three_sinks():
    """Every plan flip is joinable to an incident: console line WITH
    CAUSE, codec_plan_lane gauge + transitions counter, and a
    codec.plan span event on the active trace (PR-7 pattern)."""
    from minio_tpu.logger import Logger
    from minio_tpu.obs.span import TRACER
    nbytes = 1 << 20
    span = TRACER.begin("codec-plan-test", "trace-ct")
    with span:
        for _ in range(AUTOTUNE.MIN_SAMPLES):
            AUTOTUNE.observe(RS_ENCODE, NATIVE, nbytes, 0.001)
    # Sink 1: cause-carrying console line.
    tail = [e.message for e in Logger.get().ring.tail(50)]
    assert any("autotune: plan rs_encode[64K-1M]" in m
               and "live samples" in m for m in tail), tail
    # Sink 2: gauge + transitions counter.
    snap = METRICS2.snapshot()
    gauges = {tuple(sorted(s["labels"].items())): s["value"]
              for s in snap["minio_tpu_v2_codec_plan_lane"]["series"]}
    key = tuple(sorted({"kernel": RS_ENCODE,
                        "bucket": "64K-1M"}.items()))
    assert gauges[key] == 1  # NATIVE index
    trans = snap["minio_tpu_v2_codec_plan_transitions_total"]["series"]
    assert any(s["labels"].get("lane") == NATIVE
               and s["labels"].get("bucket") == "64K-1M"
               for s in trans)
    # Sink 3: codec.plan span event.
    events = [e for e in span.events if e["name"] == "codec.plan"]
    assert events and events[0]["new"] == NATIVE
    assert "cause" in events[0]


def test_probe_results_logged_with_cause(ladder_results):
    """Satellite: probe outcomes emit cause-carrying console lines
    (the ladder fixture already ran; its lines are in the ring)."""
    from minio_tpu.logger import Logger
    tail = [e.message for e in Logger.get().ring.tail(1000)]
    assert any(m.startswith("autotune: probe native[") for m in tail) \
        or any(m.startswith("autotune: probe host[") for m in tail)
    probes = METRICS2.snapshot().get(
        "minio_tpu_v2_codec_plan_probes_total", {}).get("series", [])
    assert any(s["labels"].get("result") == "pass" for s in probes)


# ---------------------------------------------------------------------------
# reprobe / mesh rebuild (satellite regression)


def test_reprobe_rebuilds_mesh_on_device_count_change(monkeypatch):
    """ISSUE 13 satellite fix: reprobe_device_present() must rebuild
    the serving mesh (and re-plan) when the device count changes — a
    device set that comes back with a different census must not keep
    dispatching over the stale mesh."""
    import minio_tpu.ops.batching as b
    b.device_present()  # populate the census (8 virtual devices)
    assert b._device_count == 8
    # Simulate a stale census from a 4-device epoch.
    monkeypatch.setattr(b, "_device_count", 4)
    sentinel = object()
    monkeypatch.setattr(b, "_serving_mesh", sentinel)
    monkeypatch.setattr(b, "_serving_mesh_built", True)
    replans: list[tuple] = []
    monkeypatch.setattr(AUTOTUNE, "on_device_census_change",
                        lambda old, new: replans.append((old, new)))
    b.reprobe_device_present()
    # Mesh invalidated (rebuilt lazily on next dispatch) + re-planned.
    assert b._serving_mesh_built is False
    assert replans == [(4, 8)]
    # Same census -> no rebuild, no replan.
    b.serving_mesh()
    built_before = b._serving_mesh_built
    b.reprobe_device_present()
    assert b._serving_mesh_built == built_before
    assert replans == [(4, 8)]


def test_census_change_logs_and_replans():
    from minio_tpu.logger import Logger
    AUTOTUNE.on_device_census_change(1, 8)
    tail = [e.message for e in Logger.get().ring.tail(20)]
    assert any("device census changed (1 -> 8 devices)" in m
               for m in tail)


# ---------------------------------------------------------------------------
# config


def test_configure_disables_and_retunes():
    AUTOTUNE._probed = True
    with AUTOTUNE._mu:
        AUTOTUNE._plan[(RS_ENCODE, "<64K")] = HOST
        from minio_tpu.ops.autotune import _LaneModel
        m = _LaneModel()
        m.bps, m.samples = 1e9, 5
        AUTOTUNE._model[(RS_ENCODE, "<64K", HOST)] = m
    assert AUTOTUNE.decide(RS_ENCODE, 1024) == HOST
    AUTOTUNE.configure(enabled=False, hysteresis=1.5)
    assert AUTOTUNE.decide(RS_ENCODE, 1024) == NATIVE  # static
    assert AUTOTUNE.hysteresis == 1.5
    AUTOTUNE.configure(enabled=True, hysteresis=1.25)
    assert AUTOTUNE.decide(RS_ENCODE, 1024) == HOST


def test_hysteresis_floor_clamped():
    AUTOTUNE.configure(enabled=True, hysteresis=0.2)
    assert AUTOTUNE.hysteresis == 1.0


# ---------------------------------------------------------------------------
# surfacing: timeline, mtpu_top, snapshot


def test_timeline_sample_carries_codec_plan():
    from minio_tpu.obs.timeline import Timeline
    for _ in range(AUTOTUNE.MIN_SAMPLES):
        AUTOTUNE.observe(RS_ENCODE, NATIVE, 1 << 20, 0.001)
    tl = Timeline(period_s=0.05, retention_s=10)
    tl.tick()
    sample = tl.tick()
    assert sample is not None
    assert sample["codecPlan"].get(f"{RS_ENCODE}/64K-1M") == 1


def test_timeline_merge_takes_worst_lane():
    from minio_tpu.obs.timeline import merge_timelines
    mk = {"qps": {}, "shed": {}, "inflight": {}, "kernelBytes": {},
          "queueDepth": 0, "rx": 0, "tx": 0, "hedgeFired": 0,
          "mrfDepth": 0, "drives": {}, "backendState": {}}
    a = {"periodS": 1.0, "samples": [
        dict(mk, t=100.0, codecPlan={"rs_encode/<64K": 1})]}
    b = {"periodS": 1.0, "samples": [
        dict(mk, t=100.2, codecPlan={"rs_encode/<64K": 3})]}
    merged = merge_timelines([a, b])
    assert merged["samples"][0]["codecPlan"]["rs_encode/<64K"] == 3


def test_mtpu_top_renders_codec_row():
    from tools.mtpu_top import render
    doc = {"periodS": 1.0, "samples": [{
        "t": 1.0, "dt": 1.0, "qps": {}, "shed": {}, "inflight": {},
        "kernelBytes": {}, "kernelGiBs": {}, "backendState": {},
        "drives": {}, "alerts": {},
        "codecPlan": {"rs_encode/<64K": 1, "rs_encode/4-16M": 0,
                      "rs_decode/<64K": 1},
    }]}
    out = render(doc)
    assert "codec:" in out
    assert "enc[" in out and "dec[" in out
    assert "<64K:nat" in out and "4-16M:dev" in out
    # Unprobed planner renders honestly.
    doc["samples"][0]["codecPlan"] = {}
    assert "static policy" in render(doc)


def test_snapshot_shape(ladder_results):
    _seed_from(ladder_results)
    snap = AUTOTUNE.snapshot()
    assert snap["probed"] and snap["enabled"]
    assert set(snap["backendStates"]) == {DEVICE, NATIVE, XLA_CPU,
                                          HOST}
    assert f"{RS_ENCODE}/<64K" in snap["plan"]
    cross = snap["crossover"][RS_ENCODE]["1-4M"]
    assert all("gibs" in v and "samples" in v for v in cross.values())


# ---------------------------------------------------------------------------
# live server: admin /codec-plan + config-KV + boot probe


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl import XLStorage
    root = tmp_path_factory.mktemp("atdisks")
    disks = [XLStorage(str(root / f"d{i}")) for i in range(6)]
    layer = ErasureObjects(disks, 4, 2, block_size=64 * 1024)
    srv = S3Server(layer, ACCESS, SECRET)
    port = srv.start()
    yield srv, port
    srv.stop()


def _client(port):
    from minio_tpu.s3.client import S3Client
    return S3Client("127.0.0.1", port, ACCESS, SECRET)


def test_admin_codec_plan_surface(server, ladder_results):
    _seed_from(ladder_results)
    srv, port = server
    c = _client(port)
    r = c.request("GET", "/minio-tpu/admin/v1/codec-plan")
    assert r.status == 200
    doc = json.loads(r.body)
    assert doc["probed"] is True
    assert "crossover" in doc and "plan" in doc
    assert "affinity" in doc and "nDevices" in doc["affinity"]
    # AdminClient wrapper answers the same document.
    from minio_tpu.s3.admin_client import AdminClient
    ac = AdminClient("127.0.0.1", port, ACCESS, SECRET)
    doc2 = ac.codec_plan()
    assert doc2["plan"] == doc["plan"]


def test_codec_config_validated_and_applied(server):
    srv, port = server
    c = _client(port)
    # Garbage rejected BEFORE persist.
    r = c.request("POST", "/minio-tpu/admin/v1/set-config-kv",
                  body=b"codec hysteresis=0.5")
    assert r.status == 400
    r = c.request("POST", "/minio-tpu/admin/v1/set-config-kv",
                  body=b"codec autotune=banana")
    assert r.status == 400
    # A valid write applies live.
    r = c.request("POST", "/minio-tpu/admin/v1/set-config-kv",
                  body=b"codec autotune=off hysteresis=2.0")
    assert r.status == 200
    assert AUTOTUNE.enabled is False
    assert AUTOTUNE.hysteresis == 2.0
    r = c.request("POST", "/minio-tpu/admin/v1/set-config-kv",
                  body=b"codec autotune=on hysteresis=1.25")
    assert r.status == 200
    assert AUTOTUNE.enabled is True


def test_boot_probe_kicks_off(server):
    """Server start schedules the one-per-process background ladder
    (codec probe_on_boot default on): the worker ran (or is running)
    — observable as the probe thread or a probed planner."""
    srv, port = server
    t = AUTOTUNE._probe_thread
    assert AUTOTUNE._probed or (t is not None)
