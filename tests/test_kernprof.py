"""Kernel dispatch telemetry (obs/kernprof.py): per-dispatch profiles,
the per-backend health state machine (UP -> DEGRADED -> DOWN with
probe-driven recovery), its wiring into ops/batching.py (the
once-per-process ``_warned_fallback`` replacement), and the paired
on/off overhead contract on the PUT path (PR-4 pairing method)."""

import os
import statistics
import time

import numpy as np
import pytest

from minio_tpu.faultinject import FAULTS
from minio_tpu.obs.kernel_stats import KERNEL, RS_DECODE, RS_ENCODE
from minio_tpu.obs.kernprof import (BACKENDS, DEGRADED, DEVICE, DOWN,
                                    HOST, NATIVE, UP, XLA_CPU,
                                    KERNPROF, KernelProfiler,
                                    batch_bucket)
from minio_tpu.obs.metrics2 import METRICS2
from minio_tpu.ops import batching, rs_cpu

ACCESS, SECRET = "kpadmin", "kpadmin-secret"


@pytest.fixture(autouse=True)
def _clean_state():
    KERNPROF.reset()
    FAULTS.clear()
    yield
    KERNPROF.reset()
    FAULTS.clear()


# ---------------------------------------------------------------------------
# State machine unit behavior


def test_degrade_down_and_streak_recovery():
    kp = KernelProfiler()
    assert kp.state_of(DEVICE) == UP and kp.allow(DEVICE)
    kp.dispatch_failed(DEVICE, RuntimeError("device hung"))
    assert kp.state_of(DEVICE) == DEGRADED
    assert kp.allow(DEVICE)  # degraded still dispatches
    kp.dispatch_failed(DEVICE, RuntimeError("device hung"))
    kp.dispatch_failed(DEVICE, RuntimeError("device hung"))
    assert kp.state_of(DEVICE) == DOWN
    assert not kp.allow(DEVICE)  # down: dispatch policy skips it

    # DEGRADED clears only after RECOVER_OK consecutive successes (one
    # lucky dispatch amid a flapping device must not flap the state).
    kp2 = KernelProfiler()
    kp2.dispatch_failed(NATIVE, RuntimeError("bad rows"))
    for i in range(kp2.RECOVER_OK):
        assert kp2.state_of(NATIVE) == DEGRADED
        kp2.record_dispatch(RS_ENCODE, NATIVE, 1024, 0.001, blocks=1)
    assert kp2.state_of(NATIVE) == UP


def test_every_transition_carries_its_own_cause():
    """The _warned_fallback fix: a SECOND distinct failure cause (and
    a failure after recovery) must be recorded, not swallowed by a
    once-per-process latch."""
    kp = KernelProfiler()
    kp.dispatch_failed(DEVICE, RuntimeError("cause-one"))
    assert "cause-one" in kp.snapshot()["backends"][DEVICE]["lastError"]
    # recover via successes...
    for _ in range(kp.RECOVER_OK):
        kp.record_dispatch(RS_ENCODE, DEVICE, 1024, 0.001)
    assert kp.state_of(DEVICE) == UP
    # ...and the NEXT distinct failure is a fresh transition + cause.
    before = METRICS2.get(
        "minio_tpu_v2_kernel_backend_transitions_total",
        {"backend": DEVICE, "state": DEGRADED})
    kp.dispatch_failed(DEVICE, RuntimeError("cause-two"))
    assert "cause-two" in kp.snapshot()["backends"][DEVICE]["lastError"]
    assert METRICS2.get(
        "minio_tpu_v2_kernel_backend_transitions_total",
        {"backend": DEVICE, "state": DEGRADED}) == before + 1


def test_batch_bucket_edges():
    assert [batch_bucket(b) for b in (1, 2, 4, 5, 16, 17, 64, 65)] == \
        ["1", "2-4", "2-4", "5-16", "5-16", "17-64", "17-64", "65+"]


def test_record_dispatch_feeds_histogram_and_bytes():
    lbl = {"kernel": RS_ENCODE, "backend": NATIVE, "batch": "2-4"}
    _, n0 = METRICS2.get("minio_tpu_v2_kernel_dispatch_ms", lbl)
    b0 = METRICS2.get("minio_tpu_v2_kernel_backend_bytes_total",
                      {"kernel": RS_ENCODE, "backend": NATIVE})
    KERNEL.record(RS_ENCODE, False, 4096, 0.002, blocks=3,
                  backend=NATIVE)
    s, n = METRICS2.get("minio_tpu_v2_kernel_dispatch_ms", lbl)
    assert n == n0 + 1 and s >= 2.0 - 1e-6
    assert METRICS2.get("minio_tpu_v2_kernel_backend_bytes_total",
                        {"kernel": RS_ENCODE,
                         "backend": NATIVE}) == b0 + 4096
    assert KERNPROF.mix_snapshot()[NATIVE]["bytes"] >= 4096


# ---------------------------------------------------------------------------
# Wiring: real dispatch outcomes through ops/batching.py


def _damaged_blocks(k=4, m=2, S=256, B=3):
    """B stripe blocks of a 4+2 set, shard 1 missing in each."""
    rng = np.random.default_rng(7)
    blocks = []
    for _ in range(B):
        full = np.zeros((k + m, S), dtype=np.uint8)
        full[:k] = rng.integers(0, 256, (k, S)).astype(np.uint8)
        rs_cpu.encode(full, k, m)
        shards: list = [full[i].copy() for i in range(k + m)]
        shards[1] = None
        blocks.append(shards)
    return blocks


def test_reconstruct_fault_degrades_backend_then_down_skips_device():
    """The PR-6 `kernel` fault rule drives the state machine through
    UP -> DEGRADED -> DOWN, after which the device lane is SKIPPED
    (the fault hook stops being consulted) and a recovery probe
    re-adopts it once the fault clears — no process restart."""
    backend = batching.attempt_backend()  # xla-cpu on a CPU-only box
    FAULTS.load_plan({"rules": [{"kind": "kernel",
                                 "target": "rs_decode"}]})
    want = batching.reconstruct_blocks(
        _damaged_blocks(), 4, 2, want_all=False,
        use_device=lambda n: False)  # host ground truth

    for i in range(KERNPROF.DOWN_AFTER):
        out = batching.reconstruct_blocks(
            _damaged_blocks(), 4, 2, want_all=False,
            use_device=lambda n: True)
        # falls back to host, byte-exact
        assert all((a == b).all()
                   for ba, bb in zip(out, want)
                   for a, b in zip(ba, bb))
    assert KERNPROF.state_of(backend) == DOWN
    seen_at_down = FAULTS.snapshot()["rules"][0]["seen"]

    # DOWN: the device branch is skipped entirely — the fault rule is
    # no longer even consulted.
    batching.reconstruct_blocks(
        _damaged_blocks(), 4, 2, want_all=False,
        use_device=lambda n: True)
    assert FAULTS.snapshot()["rules"][0]["seen"] == seen_at_down
    assert METRICS2.get("minio_tpu_v2_kernel_backend_state",
                        {"backend": backend}) == 2

    # A pinned backend bypasses the gate (operator asked for errors).
    with pytest.raises(Exception):
        batching.reconstruct_blocks(
            _damaged_blocks(), 4, 2, want_all=False,
            use_device=lambda n: True, device_fallback=False)

    # Probe while the fault is ACTIVE: stays down (probes go through
    # the same fault hook as serving dispatch)... the rs_decode rule
    # does not match the probe's rs_encode, so target everything.
    FAULTS.load_plan({"rules": [{"kind": "kernel", "target": ""}]})
    assert KERNPROF.probe(backend) is False
    assert KERNPROF.state_of(backend) == DOWN

    # Fault cleared: the probe re-adopts the backend.
    FAULTS.clear()
    assert KERNPROF.probe(backend) is True
    assert KERNPROF.state_of(backend) == UP
    assert METRICS2.get("minio_tpu_v2_kernel_backend_state",
                        {"backend": backend}) == 0
    assert METRICS2.get("minio_tpu_v2_kernel_backend_probes_total",
                        {"backend": backend, "result": "pass"}) >= 1


def test_transition_emits_span_event():
    from minio_tpu.obs.span import TRACER
    FAULTS.load_plan({"rules": [{"kind": "kernel",
                                 "target": "rs_decode"}]})
    root = TRACER.begin("s3.request", "kernprof-span-test")
    with root:
        batching.reconstruct_blocks(
            _damaged_blocks(), 4, 2, want_all=False,
            use_device=lambda n: True)
    tree = TRACER.recent(8)[-1]
    assert tree["traceId"] == "kernprof-span-test"

    def events(node):
        out = list(node.get("events", []))
        for c in node.get("children", []):
            out.extend(events(c))
        return out

    ev = [e for e in events(tree) if e["name"] == "kernel.backend"]
    assert ev and ev[0]["new"] == DEGRADED


def test_maybe_probe_rate_limited():
    kp = KernelProfiler()
    for _ in range(kp.DOWN_AFTER):
        kp.dispatch_failed(HOST, RuntimeError("impossible"))
    assert kp.state_of(HOST) == DOWN
    # Host probe always passes (pure numpy) -> re-adopted on the first
    # due probe; a second maybe_probe inside the interval is a no-op.
    kp.maybe_probe(now=1000.0)
    assert kp.state_of(HOST) == UP
    for _ in range(kp.DOWN_AFTER):
        kp.dispatch_failed(HOST, RuntimeError("impossible"))
    kp.maybe_probe(now=1000.0 + kp.PROBE_INTERVAL_S / 2)
    assert kp.state_of(HOST) == DOWN  # not due yet
    kp.maybe_probe(now=2000.0)
    assert kp.state_of(HOST) == UP


def test_probe_failure_feeding_machine_itself_counts_once():
    """native.probe()'s failure path runs _disable_native, which
    ALREADY feeds dispatch_failed — KernelProfiler.probe must not feed
    a second time, or native reaches DOWN_AFTER in 2 probes where
    every other lane needs 3 and `failures` reads double."""
    import minio_tpu.obs.kernprof as kp_mod

    def probe_feeds_then_fails(backend):
        KERNPROF.dispatch_failed(backend, "known-answer mismatch")
        return False

    orig = kp_mod._probe_backend
    kp_mod._probe_backend = probe_feeds_then_fails
    try:
        assert KERNPROF.probe(NATIVE) is False
        snap = KERNPROF.snapshot()["backends"][NATIVE]
        assert snap["failures"] == 1
        assert snap["failStreak"] == 1
        assert KERNPROF.state_of(NATIVE) == DEGRADED  # not DOWN-in-2
    finally:
        kp_mod._probe_backend = orig


def test_host_apply_tagged_reports_real_lane():
    from minio_tpu.native import get_lib
    mat = np.array([[1, 2], [3, 4]], dtype=np.uint8)
    cols = np.arange(2 * 32, dtype=np.uint8).reshape(2, 32)
    out, backend = batching.host_apply_tagged(mat, cols)
    assert backend == (NATIVE if get_lib() is not None else HOST)
    from minio_tpu.ops.gf256 import gf_mat_vec_apply
    assert (out == gf_mat_vec_apply(mat, cols)).all()


def test_native_probe_unpoisons_disabled_lib():
    from minio_tpu import native
    if native.get_lib() is None:
        assert native.probe() is False  # no compiler: stays down
        pytest.skip("native lib unavailable on this box")
    native._disable_native("test poison")
    assert native.get_lib() is None
    # probe() is the only path that un-poisons the process-wide latch.
    assert native.probe() is True
    assert native.get_lib() is not None


def test_coalescer_records_queue_wait_split():
    lbl = {"kernel": RS_ENCODE}
    _, n0 = METRICS2.get("minio_tpu_v2_kernel_queue_wait_ms", lbl)
    co = batching.EncodeCoalescer(lambda n: False, window_s=0.002)
    blocks = np.zeros((1, 2, 64), dtype=np.uint8)
    try:
        out = co.encode(blocks, 2, 1)  # declined -> host encode
        assert out.shape == (1, 3, 64)
    finally:
        co.stop()
    _, n1 = METRICS2.get("minio_tpu_v2_kernel_queue_wait_ms", lbl)
    assert n1 >= n0 + 1


def test_probe_all_reports_every_backend():
    res = KERNPROF.probe_all()
    assert set(res) == set(BACKENDS)
    assert res[HOST] is True  # the numpy floor can never be down
    # On the CPU-only CI box the device lane has no accelerator.
    assert res[XLA_CPU] in (True, False)


# ---------------------------------------------------------------------------
# Overhead: kernprof + timeline on the PUT path (PR-4 paired method)


def test_put_path_overhead_paired_on_off(tmp_path):
    """Tripwire, not a measurement: guards against a catastrophic
    regression (e.g. sampling moved onto the hot path) with bounds
    loose enough for a loaded 2-core CI box."""
    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.obs.timeline import TIMELINE
    from minio_tpu.s3.client import S3Client
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl import XLStorage

    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(6)]
    layer = ErasureObjects(disks, 4, 2, block_size=256 * 1024)
    srv = S3Server(layer, ACCESS, SECRET)
    port = srv.start()
    try:
        c = S3Client("127.0.0.1", port, ACCESS, SECRET)
        assert c.make_bucket("bkt").status == 200
        body = os.urandom(256 * 1024)
        for i in range(4):
            assert c.put_object("bkt", f"warm{i}", body).status == 200
        on, off = [], []
        try:
            for i in range(30):
                order = (True, False) if i % 2 == 0 else (False, True)
                for flag in order:
                    KERNPROF.enabled = TIMELINE.enabled = flag
                    t0 = time.perf_counter()
                    r = c.put_object("bkt", f"o{i}-{int(flag)}", body)
                    (on if flag else off).append(
                        time.perf_counter() - t0)
                    assert r.status == 200
        finally:
            KERNPROF.enabled = TIMELINE.enabled = True
        med_delta = statistics.median(
            [a - b for a, b in zip(on, off)])
        p50_off = statistics.median(off)
        overhead = med_delta / max(p50_off, 1e-9)
        assert overhead < 0.25, (overhead, p50_off, med_delta)
    finally:
        srv.stop()
