"""Multi-device placement on the 8-device virtual CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``, forced by
conftest before any jax import — the same mechanism as
__graft_entry__.dryrun_multichip):

- per-erasure-set device AFFINITY: concurrent sets' dispatches land on
  DISTINCT devices, proven by the MESH_AFFINITY per-device dispatch
  counters (not just the assignment map);
- EncodeCoalescer device-parallel FAN-OUT: a coalesced multi-request
  window splits into parallel per-device dispatches whose merged
  results are byte-identical to the single-device encode;
- non-divisible-batch fallback: windows that don't split (single
  request, shared affinity) take the one-dispatch path unchanged."""

from __future__ import annotations

import os
import shutil
import threading

import jax
import numpy as np
import pytest

from minio_tpu.erasure.codec import Erasure
from minio_tpu.erasure.engine import ErasureObjects
from minio_tpu.obs.metrics2 import METRICS2
from minio_tpu.ops import batching
from minio_tpu.parallel.mesh import MESH_AFFINITY
from minio_tpu.storage.xl import XLStorage


@pytest.fixture(autouse=True)
def fresh_mesh():
    batching.reset_serving_mesh()
    MESH_AFFINITY.reset()
    yield
    batching.reset_serving_mesh()
    MESH_AFFINITY.reset()


def _fanout_count() -> float:
    snap = METRICS2.snapshot().get(
        "minio_tpu_v2_codec_plan_fanout_total", {})
    return sum(s["value"] for s in snap.get("series", []))


def test_eight_virtual_devices_present():
    assert len(jax.devices()) == 8, "conftest must provide 8 devices"
    assert MESH_AFFINITY.n_devices() == 8


def test_affinity_assignment_round_robins():
    idxs = [MESH_AFFINITY.assign(f"set-{i}") for i in range(10)]
    assert idxs[:8] == list(range(8))
    assert idxs[8:] == [0, 1]  # wraps
    # Idempotent per owner; released slots don't disturb others.
    assert MESH_AFFINITY.assign("set-3") == 3
    MESH_AFFINITY.release("set-3")
    assert MESH_AFFINITY.assign("set-3") == 2  # re-assigned, next slot


def test_indivisible_batch_pins_to_home_device():
    """The old behavior replicated an indivisible batch to all 8
    chips; with affinity it lands WHOLE on the home device — and the
    counters prove which one."""
    a = MESH_AFFINITY.assign("owner-a")
    b = MESH_AFFINITY.assign("owner-b")
    assert a != b
    x = np.arange(3 * 4 * 7, dtype=np.uint8).reshape(3, 4, 7)
    placed_a = batching.device_put_batch(x, a, kernel="rs_encode")
    placed_b = batching.device_put_batch(x, b, kernel="rs_encode")
    assert len(placed_a.sharding.device_set) == 1
    assert len(placed_b.sharding.device_set) == 1
    assert placed_a.sharding.device_set != placed_b.sharding.device_set
    np.testing.assert_array_equal(np.asarray(placed_a), x)
    counters = MESH_AFFINITY.counters()
    assert counters[a]["dispatches"] == 1
    assert counters[b]["dispatches"] == 1


def test_divisible_batch_still_shards_across_mesh():
    """Affinity never steals the real scaling path: a batch whose B
    divides the mesh spreads over all chips even with a home device."""
    a = MESH_AFFINITY.assign("owner-big")
    x = np.arange(16 * 4 * 256, dtype=np.uint8).reshape(16, 4, 256)
    placed = batching.device_put_batch(x, a, kernel="rs_encode")
    assert len(placed.sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(placed), x)


def test_affinity_encode_matches_default_placement():
    from minio_tpu.ops import rs_tpu
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (3, 4, 100)).astype(np.uint8)
    got = rs_tpu.encode_batch(data, 4, 2, affinity=5)
    want = batching.host_encode(data, 4, 2)
    np.testing.assert_array_equal(got, want)


def test_concurrent_sets_dispatch_on_distinct_devices(tmp_path,
                                                      monkeypatch):
    """Acceptance: concurrent erasure sets' dispatches land on
    distinct devices — affinity spread proven by per-device dispatch
    counters."""
    monkeypatch.setattr(Erasure, "_use_tpu", lambda self, *a: True)
    engines = []
    for e in range(2):
        disks = [XLStorage(str(tmp_path / f"e{e}d{i}"))
                 for i in range(6)]
        # Odd shard size (8188/4 = 2047) AND odd-ish batch (B=3): no
        # axis divides the 2x4 mesh, so every dispatch takes the
        # home-device pin, not the mesh shard.
        engines.append(ErasureObjects(disks, 4, 2, block_size=8188))
    try:
        affs = [eng.device_affinity for eng in engines]
        assert None not in affs and affs[0] != affs[1]
        payload = os.urandom(8188 * 3)
        before = MESH_AFFINITY.counters()

        def put(eng, name):
            eng.make_bucket("mesh")
            eng.put_object("mesh", name, payload)

        ts = [threading.Thread(target=put, args=(eng, f"o{i}"))
              for i, eng in enumerate(engines)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        after = MESH_AFFINITY.counters()

        def delta(dev):
            return (after.get(dev, {}).get("dispatches", 0)
                    - before.get(dev, {}).get("dispatches", 0))

        # Each engine's home device saw its dispatches; distinct
        # chips; NO other device saw any — the spread is exact, not
        # incidental.
        assert delta(affs[0]) >= 1
        assert delta(affs[1]) >= 1
        touched = {d for d in range(8) if delta(d) > 0}
        assert touched == {affs[0], affs[1]}
        # Each engine can read back its own bytes.
        for i, eng in enumerate(engines):
            got, _ = eng.get_object("mesh", f"o{i}")
            assert got == payload
    finally:
        for eng in engines:
            eng.shutdown()
        shutil.rmtree(tmp_path, ignore_errors=True)


def test_coalescer_fanout_byte_exact():
    """A coalesced window spanning 4 home devices fans out as 4
    parallel per-device dispatches; every request's shards are
    byte-identical to the single-device (host reference) encode."""
    co = batching.EncodeCoalescer(lambda n: True, window_s=0.05)
    fanouts_before = _fanout_count()
    results: dict[str, tuple] = {}
    barrier = threading.Barrier(4)

    def put(name: str, aff: int, seed: int) -> None:
        # (3, 4, 63): neither B=3 nor S=63 divides the 2x4 mesh, so
        # each sub-batch PINS to its home device — the fan-out
        # precondition (mesh-divisible sub-batches decline the split).
        data = np.random.default_rng(seed).integers(
            0, 256, (3, 4, 63)).astype(np.uint8)
        barrier.wait()  # submit together -> one coalescing window
        results[name] = (data, co.encode(data, 4, 2, affinity=aff))

    ts = [threading.Thread(target=put, args=(f"r{i}", i, i * 7))
          for i in range(4)]
    try:
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert len(results) == 4
        for name, (data, enc) in results.items():
            want = batching.host_encode(data, 4, 2)
            np.testing.assert_array_equal(enc, want, err_msg=name)
        assert _fanout_count() > fanouts_before
    finally:
        co.stop()


def test_coalescer_mesh_divisible_window_declines_fanout():
    """Sub-batches an axis of which divides the mesh would SHARD
    across all chips — fanning those out turns one combined mesh
    dispatch into N contending whole-mesh dispatches, so the split is
    declined and the window goes out as one dispatch (post-review
    regression)."""
    co = batching.EncodeCoalescer(lambda n: True, window_s=0.05)
    fanouts_before = _fanout_count()
    results: dict[str, tuple] = {}
    barrier = threading.Barrier(2)

    def put(name: str, aff: int, seed: int) -> None:
        # B=2 divides the mesh's blocks axis -> sub-batches shard.
        data = np.random.default_rng(seed).integers(
            0, 256, (2, 4, 64)).astype(np.uint8)
        barrier.wait()
        results[name] = (data, co.encode(data, 4, 2, affinity=aff))

    ts = [threading.Thread(target=put, args=(f"d{i}", i, 41 + i))
          for i in range(2)]
    try:
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for name, (data, enc) in results.items():
            np.testing.assert_array_equal(
                enc, batching.host_encode(data, 4, 2), err_msg=name)
        assert _fanout_count() == fanouts_before
    finally:
        co.stop()


def test_coalescer_single_request_no_fanout():
    """Non-divisible fallback: a lone request (nothing to split) takes
    the single-dispatch path — byte-exact, no fan-out counted."""
    co = batching.EncodeCoalescer(lambda n: True)
    fanouts_before = _fanout_count()
    try:
        data = np.random.default_rng(3).integers(
            0, 256, (3, 4, 64)).astype(np.uint8)
        enc = co.encode(data, 4, 2, affinity=2)
        np.testing.assert_array_equal(enc,
                                      batching.host_encode(data, 4, 2))
        assert _fanout_count() == fanouts_before
    finally:
        co.stop()


def test_coalescer_shared_affinity_no_fanout():
    """Requests sharing one home device coalesce into ONE dispatch on
    that device (fan-out needs >= 2 distinct devices)."""
    co = batching.EncodeCoalescer(lambda n: True)
    fanouts_before = _fanout_count()
    results: dict[str, tuple] = {}
    barrier = threading.Barrier(2)

    def put(name: str, seed: int) -> None:
        data = np.random.default_rng(seed).integers(
            0, 256, (2, 4, 64)).astype(np.uint8)
        barrier.wait()
        results[name] = (data, co.encode(data, 4, 2, affinity=6))

    ts = [threading.Thread(target=put, args=(f"s{i}", 11 + i))
          for i in range(2)]
    try:
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for name, (data, enc) in results.items():
            np.testing.assert_array_equal(
                enc, batching.host_encode(data, 4, 2), err_msg=name)
        assert _fanout_count() == fanouts_before
    finally:
        co.stop()


def test_fanout_aliased_affinities_decline(monkeypatch):
    """Stale raw affinities that alias (mod n_devices) onto ONE chip
    after a device-count shrink must not 'fan out' as serialized
    dispatches on the same device (post-review regression)."""
    from minio_tpu.parallel.mesh import DeviceAffinity
    monkeypatch.setattr(DeviceAffinity, "n_devices",
                        staticmethod(lambda: 4))
    mk = lambda aff: batching._EncodeRequest(  # noqa: E731
        np.zeros((3, 4, 63), np.uint8), 4, 2, affinity=aff)
    # 0 and 4 alias to device 0 under a 4-device census: no split.
    assert batching.EncodeCoalescer._fanout_split(
        [mk(0), mk(4)]) is None
    # 1 and 6 map to distinct devices (1, 2): split stands.
    by = batching.EncodeCoalescer._fanout_split([mk(1), mk(6)])
    assert by is not None and sorted(by) == [1, 2]


def test_fanout_failure_declines_to_host(monkeypatch):
    """A failing per-device sub-dispatch declines the WHOLE window
    back to the callers' host encode — no torn results."""
    from minio_tpu.ops import rs_tpu

    def boom(*a, **kw):
        raise RuntimeError("sub-dispatch exploded")

    monkeypatch.setattr(rs_tpu, "encode_batch", boom)
    co = batching.EncodeCoalescer(lambda n: True)
    results: dict[str, tuple] = {}
    barrier = threading.Barrier(2)

    def put(name: str, aff: int, seed: int) -> None:
        data = np.random.default_rng(seed).integers(
            0, 256, (2, 4, 64)).astype(np.uint8)
        barrier.wait()
        results[name] = (data, co.encode(data, 4, 2, affinity=aff))

    ts = [threading.Thread(target=put, args=(f"f{i}", i, 29 + i))
          for i in range(2)]
    try:
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for name, (data, enc) in results.items():
            np.testing.assert_array_equal(
                enc, batching.host_encode(data, 4, 2), err_msg=name)
    finally:
        co.stop()
