"""How the rows of a device HighwayHash dispatch become its operand.

The ladder: every dispatch reaches `hh256_rows` at a power-of-two row
count, so the served paths build the programs their warm-up built. A
tree that dispatched 12 rows of an 8+4 PUT as 12 compiled inside the
measured windows and lost 10-19% of its goodput; these tests name the
exact (rows, packets, remainder) set each path sends.

The packer (`hh256_tpu.pack_rows`): rows given as bytes, memoryviews or
numpy rows and views are written once into one fresh operand, padding
rows stay zero and never reach a result, and the digests equal the
reference's."""

import os
import shutil

import numpy as np
import pytest

from minio_tpu.erasure import bitrot, heal
from minio_tpu.erasure.engine import ErasureObjects
from minio_tpu.native import hh256_native
from minio_tpu.obs.metrics2 import METRICS2
from minio_tpu.ops import batching, hh256_tpu
from minio_tpu.ops.hh256 import MAGIC_KEY, HighwayHash256
from minio_tpu.storage.xl import XLStorage

# A shard sub-block of 118 B is 3 packets + 22 B, the remainder the
# served 12+4 shard (873,814 B) has.
S = 118


def _want(row: bytes) -> bytes:
    return hh256_native(row, MAGIC_KEY) or \
        HighwayHash256(MAGIC_KEY).update(row).digest()


@pytest.fixture
def shapes(monkeypatch):
    """Device lane forced on one device; yields the list of
    (rows, n_packets, rem) every `hh256_rows` call received."""
    monkeypatch.setattr(batching, "_device_present", True)
    monkeypatch.setattr(bitrot, "HH_TPU_MIN_BYTES", 1)
    batching.set_mesh_devices(1)
    seen = []
    real = hh256_tpu.hh256_rows

    def recording(words, rem_packet, init, n_packets, rem, mesh=None):
        # The operand is the packer's own array, never caller memory.
        assert words.flags.c_contiguous and words.flags.owndata
        seen.append((words.shape[0], n_packets, rem))
        return real(words, rem_packet, init, n_packets, rem, mesh=mesh)

    monkeypatch.setattr(hh256_tpu, "hh256_rows", recording)
    try:
        yield seen
    finally:
        batching.set_mesh_devices(None)


def _engine(tmp_path, k, m):
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(k + m)]
    e = ErasureObjects(disks, k, m, block_size=k * S)
    e.make_bucket("b")
    return e


def _part_files(e, obj):
    out = {}
    for i, d in enumerate(e.disks):
        objdir = os.path.join(d.root, "b", obj)
        ddir = next(x for x in os.listdir(objdir) if x != "xl.meta")
        with open(os.path.join(objdir, ddir, "part.1"), "rb") as f:
            out[i] = f.read()
    return out


def _ladder_holds(seen, want):
    rows = [r for r, _, _ in seen]
    assert all(r & (r - 1) == 0 for r in rows), rows
    assert set(seen) == want


def test_put_of_8p4_dispatches_12_rows_as_16(tmp_path, shapes):
    e = _engine(tmp_path, 8, 4)
    e.put_object("b", "o", os.urandom(8 * S))
    _ladder_holds(shapes, {(16, 3, 22)})


def test_get_verifies_8_windows_at_8_rows(tmp_path, shapes):
    e = _engine(tmp_path, 8, 4)
    payload = os.urandom(8 * S)
    e.put_object("b", "o", payload)
    shapes.clear()
    got, _ = e.get_object("b", "o")
    assert got == payload
    _ladder_holds(shapes, {(8, 3, 22)})


def test_heal_of_12p4_group_of_6_blocks(tmp_path, shapes, monkeypatch):
    """One wiped drive of a 12+4 object of 6 blocks and a tail: the
    survivors' verify 72 -> 128 rows, the tail block's 12 -> 16, the
    rebuilt shard's 6 -> 8; the healed drive's shard file is the one
    the PUT wrote, byte for byte."""
    monkeypatch.setattr(heal, "HEAL_BATCH_BYTES", 6 * 12 * S)
    e = _engine(tmp_path, 12, 4)
    payload = os.urandom(6 * 12 * S + 12 * 40)
    e.put_object("b", "o", payload)
    before = _part_files(e, "o")
    wiped = e.disks[7].root
    shutil.rmtree(wiped)
    os.makedirs(wiped)
    shapes.clear()
    e.healer.heal_bucket("b")
    r = e.healer.heal_object("b", "o")
    assert r.healed_disks == [7]
    _ladder_holds(shapes, {(128, 3, 22), (16, 1, 8), (8, 3, 22)})
    assert _part_files(e, "o") == before
    got, _ = e.get_object("b", "o")
    assert got == payload


def test_digest_chunks_many_over_3_streams(shapes):
    rng = np.random.default_rng(3)
    streams = [rng.integers(0, 256, n * S + t, dtype=np.uint8).tobytes()
               for n, t in ((2, 5), (3, 0), (1, 77))]
    got = bitrot.digest_chunks_many(bitrot.DEFAULT_ALGORITHM, streams, S)
    assert got == [[_want(s[i:i + S]) for i in range(0, len(s), S)]
                   for s in streams]
    _ladder_holds(shapes, {(8, 3, 22)})


# -- the packer ---------------------------------------------------------------

def _as(kind: str, arr: np.ndarray):
    """The same (B, L) bytes in the form a caller hands them over."""
    B, L = arr.shape
    if kind == "bytes":
        return [r.tobytes() for r in arr]
    if kind == "memoryview":
        buf = memoryview(arr.tobytes())
        return [buf[i * L:(i + 1) * L] for i in range(B)]
    if kind == "array":
        return arr
    wide = np.zeros((B, 2 * L + 3), np.uint8)
    if kind == "row_views":          # each row contiguous, the 2-D not
        wide[:, 3:3 + L] = arr
        return list(wide[:, 3:3 + L])
    wide[:, 0:2 * L:2] = arr         # "strided": every other byte
    return wide[:, 0:2 * L:2]


@pytest.mark.parametrize("B", [1, 6, 12, 72])
@pytest.mark.parametrize("L", [64, 67, 80, 86])     # L % 32: 0, 3, 16, 22
@pytest.mark.parametrize("kind", ["bytes", "memoryview", "array",
                                  "row_views", "strided"])
def test_packed_rows_hash_as_the_reference(kind, L, B):
    batching.set_mesh_devices(1)
    try:
        arr = np.random.default_rng(B * 1000 + L).integers(
            0, 256, (B, L), dtype=np.uint8)
        got = hh256_tpu.hash_rows(_as(kind, arr))
    finally:
        batching.set_mesh_devices(None)
    assert got.shape == (B, 32)
    for i in range(B):
        assert got[i].tobytes() == _want(arr[i].tobytes()), i


@pytest.mark.parametrize("kind", ["bytes", "memoryview", "array",
                                  "row_views", "strided"])
def test_operand_is_fresh_and_padding_is_zero(kind):
    arr = np.random.default_rng(1).integers(0, 256, (6, 86),
                                            dtype=np.uint8)
    rows = _as(kind, arr)
    words, rem_packet = hh256_tpu.pack_rows(rows, 86)
    assert words.shape == (8, 2, 8) and rem_packet.shape == (8, 8)
    assert words.flags.c_contiguous and words.flags.owndata
    if isinstance(rows, np.ndarray):
        assert not np.shares_memory(words, rows)
    assert words.view(np.uint8).reshape(8, 64)[:6].tobytes() == \
        arr[:, :64].tobytes()
    assert not words[6:].any() and not rem_packet[6:].any()


def test_counters_say_each_row_was_copied_once():
    batching.set_mesh_devices(1)
    dev = {"kernel": "hh256", "device": "tpu"}
    op = {"kernel": "hh256"}

    def read():
        return (METRICS2.get("minio_tpu_v2_kernel_bytes_total", dev) or 0,
                METRICS2.get("minio_tpu_v2_kernel_host_copy_bytes_total",
                             op) or 0,
                METRICS2.get("minio_tpu_v2_kernel_pad_bytes_total",
                             op) or 0)
    try:
        before = read()
        hh256_tpu.hash_rows([bytes(86)] * 72)
        after = read()
    finally:
        batching.set_mesh_devices(None)
    sent, copied, padded = (a - b for a, b in zip(after, before))
    assert (copied, padded) == (72 * 86, 56 * 86)
    assert sent == copied + padded == 128 * 86


def test_rows_of_unequal_length_are_refused():
    with pytest.raises(ValueError):
        hh256_tpu.pack_rows([bytes(64), bytes(65)], 64)


@pytest.mark.parametrize("B,cap", [(1, 1), (2, 2), (3, 4), (6, 8),
                                   (12, 16), (16, 16), (72, 128)])
def test_bucket_is_the_next_power_of_two(B, cap):
    assert hh256_tpu.bucket_rows(B) == cap


@pytest.mark.parametrize("algo", [bitrot.HIGHWAYHASH256S, bitrot.SHA256])
def test_split_block_returns_a_view_into_the_stream(algo):
    framed = bitrot.encode_stream(os.urandom(3 * S - 7), S, algo) \
        if bitrot.is_streaming(algo) else os.urandom(3 * S - 7)
    want, data = bitrot.split_block(framed, 1, S, S, algo)
    assert isinstance(data, memoryview)
    assert np.shares_memory(np.frombuffer(data, np.uint8),
                            np.frombuffer(framed, np.uint8))
    hsz = 32 if bitrot.is_streaming(algo) else 0
    assert bytes(data) == framed[(hsz + S) + hsz:(hsz + S) * 2]
    assert want == framed[hsz + S:hsz + S + hsz]
    assert bitrot.extract_block(framed, 1, S, S, algo) == bytes(data)
