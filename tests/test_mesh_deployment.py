"""The four-chip deployment at a small block size, on the suite's
virtual CPU devices: two 8+4 erasure sets on 24 drives under a 2x2
serving mesh (benchmark configuration ``ec8p4_24d_mesh2x2``).

- what PUT leaves on the drives is byte-identical to the in-repo plain
  reference (``ops/rs_cpu.py`` + ``ops/hh256.py``), and an object's
  files live in exactly the set a plain SipHash-2-4 of its key names;
- the mesh census (``parallel/mesh.MESH_AFFINITY``) counts what each
  device HOLDS, once: a quarter each of a batch sharded four ways, the
  whole of a pinned one, the redundancy of an axis left replicated;
- the census and the per-set byte counter are exported as series, and
  on one device the mesh series do not exist."""

from __future__ import annotations

import json
import os
import struct
import uuid

import numpy as np
import pytest

from minio_tpu.erasure import bitrot
from minio_tpu.erasure.codec import Erasure
from minio_tpu.erasure.sets import ErasureSets
from minio_tpu.obs import metrics2
from minio_tpu.obs.metrics2 import METRICS2
from minio_tpu.obs.span import TRACER
from minio_tpu.ops import batching, hh256, hh256_tpu, rs_cpu
from minio_tpu.parallel.mesh import MESH_AFFINITY
from minio_tpu.storage.xl import XLStorage

K, R, SETS = 8, 4, 2
BLOCK = K * 4096                      # 32 KiB stripe block, 4 KiB shards
BUCKET = "mesh"
DEVICE_BYTES = "minio_tpu_v2_mesh_device_bytes_total"
DISPATCH_BYTES = "minio_tpu_v2_mesh_dispatch_bytes_total"
SET_BYTES = "minio_tpu_v2_erasure_set_bytes_total"
# name -> size: whole blocks, and a ragged last block with an odd tail
OBJECTS = {"even/0": 3 * BLOCK, "even/1": BLOCK,
           "odd/0": 2 * BLOCK + 12345, "odd/1": 4097, "odd/2": BLOCK + 1}


# -- the plain reference, written out ---------------------------------------

def siphash24(key: bytes, data: bytes) -> int:
    """SipHash-2-4 (Aumasson & Bernstein), 64-bit."""
    mask = (1 << 64) - 1
    k0, k1 = struct.unpack("<QQ", key)
    v = [k0 ^ 0x736F6D6570736575, k1 ^ 0x646F72616E646F6D,
         k0 ^ 0x6C7967656E657261, k1 ^ 0x7465646279746573]

    def rotl(x, b):
        return ((x << b) | (x >> (64 - b))) & mask

    def sipround():
        v[0] = (v[0] + v[1]) & mask
        v[1] = rotl(v[1], 13) ^ v[0]
        v[0] = rotl(v[0], 32)
        v[2] = (v[2] + v[3]) & mask
        v[3] = rotl(v[3], 16) ^ v[2]
        v[0] = (v[0] + v[3]) & mask
        v[3] = rotl(v[3], 21) ^ v[0]
        v[2] = (v[2] + v[1]) & mask
        v[1] = rotl(v[1], 17) ^ v[2]
        v[2] = rotl(v[2], 32)

    tail = len(data) % 8
    for (m,) in struct.iter_unpack("<Q", data[:len(data) - tail]):
        v[3] ^= m
        sipround()
        sipround()
        v[0] ^= m
    last = int.from_bytes(data[len(data) - tail:], "little") \
        | (len(data) & 0xFF) << 56
    v[3] ^= last
    sipround()
    sipround()
    v[0] ^= last
    v[2] ^= 0xFF
    for _ in range(4):
        sipround()
    return v[0] ^ v[1] ^ v[2] ^ v[3]


def reference_shard_file(body: bytes, index: int) -> bytes:
    """The shard file of 1-based erasure `index`: per stripe block one
    [32 B HighwayHash-256 digest][sub-block] frame."""
    out = bytearray()
    for off in range(0, len(body), BLOCK):
        sub = rs_cpu.encode_data(body[off:off + BLOCK], K, R)[
            index - 1].tobytes()
        out += hh256.hh256(sub) + sub
    return bytes(out)


def body_of(name: str) -> bytes:
    rng = np.random.default_rng(len(name) * 1000 + OBJECTS[name])
    return rng.integers(0, 256, OBJECTS[name], dtype=np.uint8).tobytes()


def set_bytes() -> dict[tuple[str, str], float]:
    series = METRICS2.snapshot()[SET_BYTES]["series"]
    return {(s["labels"]["set"], s["labels"]["op"]): s["value"]
            for s in series}


# -- fixtures -----------------------------------------------------------------

@pytest.fixture
def mesh2x2():
    batching.set_mesh_devices(4)
    MESH_AFFINITY.reset()
    assert dict(batching.serving_mesh().shape) == {"blocks": 2,
                                                   "lanes": 2}
    yield batching.serving_mesh()
    batching.set_mesh_devices(None)
    MESH_AFFINITY.reset()


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    """24 drives, two 8+4 sets, every codec and hash batch sent down
    the device lane of a 2x2 mesh; every object PUT once and read back
    once. Yields what the cases compare."""
    mp = pytest.MonkeyPatch()
    mp.setattr(batching, "_device_present", True)
    mp.setattr(bitrot, "HH_TPU_MIN_BYTES", 1)
    mp.setattr(Erasure, "_use_tpu", lambda self, *a: True)
    batching.set_mesh_devices(4)
    MESH_AFFINITY.reset()
    root = tmp_path_factory.mktemp("drives")
    drives = [str(root / f"d{i + 1}") for i in range(SETS * (K + R))]
    dep = str(uuid.UUID(int=0x28_0000_0000_0000_0028))
    sets = ErasureSets([XLStorage(d) for d in drives], [K + R] * SETS,
                       dep, K, R, block_size=BLOCK)
    sets.make_bucket(BUCKET)
    before = set_bytes() if SET_BYTES in METRICS2.snapshot() else {}
    got, tags = {}, {}
    try:
        for name in OBJECTS:
            span = TRACER.begin("PUT-object", name)
            with span:
                sets.put_object(BUCKET, name, body_of(name))
            tags[name] = dict(span.tags)
            _, stream = sets.get_object_stream(BUCKET, name)
            got[name] = b"".join(stream)
        yield {"drives": drives, "dep": dep, "got": got, "tags": tags,
               "set_bytes": {k: v - before.get(k, 0)
                             for k, v in set_bytes().items()},
               "census": MESH_AFFINITY.snapshot(),
               "text": metrics2.render(METRICS2.snapshot())}
    finally:
        sets.shutdown()
        mp.undo()
        batching.set_mesh_devices(None)
        MESH_AFFINITY.reset()


def holders(drives: list[str], name: str) -> dict[int, int]:
    """{drive position: 1-based erasure index} of the object's copies."""
    out = {}
    for pos, d in enumerate(drives):
        try:
            with open(os.path.join(d, BUCKET, name, "xl.meta"), "rb") as f:
                out[pos] = int(
                    json.load(f)["versions"][0]["erasure"]["index"])
        except OSError:
            pass
    return out


# -- the deployment -------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(OBJECTS))
def test_get_returns_the_bytes_put(deployment, name):
    assert deployment["got"][name] == body_of(name)


@pytest.mark.parametrize("name", sorted(OBJECTS))
def test_shard_files_equal_the_plain_reference(deployment, name):
    body = body_of(name)
    held = holders(deployment["drives"], name)
    assert sorted(held.values()) == list(range(1, K + R + 1))
    for pos, index in held.items():
        base = os.path.join(deployment["drives"][pos], BUCKET, name)
        parts = [os.path.join(dp, fn) for dp, _, fns in os.walk(base)
                 for fn in fns if fn.startswith("part.")]
        assert len(parts) == 1
        with open(parts[0], "rb") as f:
            assert f.read() == reference_shard_file(body, index), \
                f"{name}: shard {index} on drive {pos + 1}"


@pytest.mark.parametrize("name", sorted(OBJECTS))
def test_files_live_in_exactly_the_set_siphash_names(deployment, name):
    want = siphash24(uuid.UUID(deployment["dep"]).bytes,
                     name.encode()) % SETS
    held = holders(deployment["drives"], name)
    assert {pos // (K + R) for pos in held} == {want}
    assert len(held) == K + R
    assert deployment["tags"][name]["set"] == want


def test_both_sets_took_objects(deployment):
    homes = {next(iter(holders(deployment["drives"], n))) // (K + R)
             for n in OBJECTS}
    assert homes == {0, 1}


def test_set_bytes_equal_the_bytes_put_and_got(deployment):
    total = sum(OBJECTS.values())
    moved = deployment["set_bytes"]
    assert sum(v for (_, op), v in moved.items() if op == "put") == total
    assert sum(v for (_, op), v in moved.items() if op == "get") == total
    assert {s for s, _ in moved} == {"0", "1"}


def test_a_cut_stream_counts_nothing(deployment, tmp_path):
    drives = [XLStorage(str(tmp_path / f"d{i}")) for i in range(2 * 6)]
    sets = ErasureSets(drives, [6, 6], deployment["dep"], 4, 2,
                       block_size=4096)
    try:
        sets.make_bucket(BUCKET)
        sets.put_object(BUCKET, "k", os.urandom(3 * 4096))
        before = set_bytes()
        _, stream = sets.get_object_stream(BUCKET, "k")
        next(stream)
        stream.close()
        assert set_bytes() == before
        _, stream = sets.get_object_stream(BUCKET, "k")
        assert len(b"".join(stream)) == 3 * 4096
        after = set_bytes()
        assert sum(after.values()) - sum(before.values()) == 3 * 4096
    finally:
        sets.shutdown()


def test_served_census_counts_every_kernel_once(deployment):
    """PUTs and GETs of the deployment: RS encode went through
    device_put_batch, HH256 through hash_chunks; per kernel the devices
    hold the dispatched bytes plus what a replicated axis repeats."""
    kernels = deployment["census"]["kernels"]
    assert {"rs_encode", "hh256"} <= set(kernels)
    for census in kernels.values():
        held = sum(d["bytes"] for d in census["devices"].values())
        sent = sum(p["bytes"] for p in census["placements"].values())
        repeated = census["placements"].get(
            "replicated", {"bytes": 0})["bytes"]
        assert sent > 0 and sent <= held <= sent + 3 * repeated
        if not repeated:
            assert held == sent


def test_series_and_set_label_in_the_prometheus_text(deployment):
    text = deployment["text"]
    assert f'{DEVICE_BYTES}{{device="0",kernel="hh256"}}' in text
    assert f'{DISPATCH_BYTES}{{kernel="hh256",placement=' in text
    assert f'{DISPATCH_BYTES}{{kernel="rs_encode",placement=' in text
    assert f'{SET_BYTES}{{op="put",set="0"}}' in text
    assert f'{SET_BYTES}{{op="get",set="1"}}' in text


# -- the census -------------------------------------------------------------------

def test_hash_chunks_divisible_batch_is_a_quarter_on_each_device(mesh2x2):
    rows = np.arange(8 * 4096, dtype=np.uint32).astype(np.uint8).reshape(
        8, 4096)
    out = hh256_tpu.hash_chunks(rows)
    assert out[3].tobytes() == hh256.hh256(rows[3].tobytes())
    snap = MESH_AFFINITY.snapshot()["kernels"]["hh256"]
    assert snap["devices"] == {
        str(i): {"dispatches": 1, "bytes": rows.nbytes // 4}
        for i in range(4)}
    assert snap["placements"] == {
        "sharded": {"dispatches": 1, "bytes": rows.nbytes}}


def test_hash_chunks_indivisible_batch_is_whole_on_one_device(mesh2x2):
    rows = np.full((2, 4096), 7, np.uint8)
    out = hh256_tpu.hash_chunks(rows)
    assert out[1].tobytes() == hh256.hh256(rows[1].tobytes())
    snap = MESH_AFFINITY.snapshot()["kernels"]["hh256"]
    assert snap["devices"] == {
        "0": {"dispatches": 1, "bytes": rows.nbytes}}
    assert snap["placements"] == {
        "pinned": {"dispatches": 1, "bytes": rows.nbytes}}


def test_hash_chunks_shards_the_bucket_it_pads_to(mesh2x2):
    """Three rows go out as a bucket of four, which divides the mesh:
    each device holds one row, the padding row on the last."""
    rows = np.full((3, 4096), 7, np.uint8)
    out = hh256_tpu.hash_chunks(rows)
    assert out.shape == (3, 32)
    assert out[2].tobytes() == hh256.hh256(rows[2].tobytes())
    snap = MESH_AFFINITY.snapshot()["kernels"]["hh256"]
    assert snap["devices"] == {
        str(i): {"dispatches": 1, "bytes": 4096} for i in range(4)}
    assert snap["placements"] == {
        "sharded": {"dispatches": 1, "bytes": 4 * 4096}}


def test_replicated_axis_reads_the_redundancy_it_has(mesh2x2):
    """B = 1 does not divide 'blocks': the batch shards over 'lanes'
    alone and two chips repeat the other two's work (ROADMAP A10)."""
    x = np.zeros((1, K, 4096), np.uint8)
    placed = batching.device_put_batch(x, kernel="rs_encode")
    assert len(placed.sharding.device_set) == 4
    held = {labels["device"]: v for labels, v in
            MESH_AFFINITY.device_bytes()}
    assert held == {str(i): x.nbytes // 2 for i in range(4)}
    assert MESH_AFFINITY.dispatch_bytes() == [
        ({"kernel": "rs_encode", "placement": "replicated"}, x.nbytes)]
    redundant = (sum(held.values()) - x.nbytes) / sum(held.values())
    assert redundant == 0.5


def test_neither_axis_divides_without_a_home_is_on_every_device(mesh2x2):
    x = np.zeros((3, K, 4097), np.uint8)
    batching.device_put_batch(x, kernel="rs_decode")
    assert [v for _, v in MESH_AFFINITY.device_bytes()] == [x.nbytes] * 4
    assert MESH_AFFINITY.dispatch_bytes() == [
        ({"kernel": "rs_decode", "placement": "replicated"}, x.nbytes)]


def test_devices_sum_to_dispatch_bytes_when_nothing_is_replicated(mesh2x2):
    both = np.zeros((4, K, 4096), np.uint8)           # both axes divide
    pinned = np.zeros((3, K, 4097), np.uint8)         # neither: home 2
    batching.device_put_batch(both, kernel="rs_encode")
    batching.device_put_batch(pinned, 2, kernel="rs_encode")
    hh256_tpu.hash_chunks(np.zeros((4, 2048), np.uint8))
    held = sum(v for _, v in MESH_AFFINITY.device_bytes())
    sent = sum(v for _, v in MESH_AFFINITY.dispatch_bytes())
    assert held == sent == both.nbytes + pinned.nbytes + 4 * 2048
    per_device = MESH_AFFINITY.counters()
    assert per_device[2]["bytes"] == (both.nbytes // 4 + pinned.nbytes
                                      + 2048)
    assert {p for (labels, _) in MESH_AFFINITY.dispatch_bytes()
            for p in [labels["placement"]]} == {"sharded", "pinned"}


def test_one_device_exports_neither_mesh_series():
    batching.set_mesh_devices(1)
    MESH_AFFINITY.reset()
    try:
        assert batching.serving_mesh() is None
        rows = np.zeros((4, 2048), np.uint8)
        hh256_tpu.hash_chunks(rows)
        batching.device_put_batch(np.zeros((4, K, 64), np.uint8),
                                  kernel="rs_encode")
        text = metrics2.render(METRICS2.snapshot())
        assert DEVICE_BYTES not in text and DISPATCH_BYTES not in text
        assert MESH_AFFINITY.snapshot()["kernels"] == {}
    finally:
        batching.set_mesh_devices(None)
