"""TPU HighwayHash kernel: byte-identity with the spec implementation,
batched digest/verify parity, and honesty counters proving the engine's
write/read paths actually reach the device dispatch (CPU-jax here; same
XLA semantics as TPU)."""

import os
import shutil

import numpy as np
import pytest

from minio_tpu.erasure import bitrot
from minio_tpu.ops import batching
from minio_tpu.ops.hh256 import MAGIC_KEY, hh256
from minio_tpu.ops import hh256_tpu


@pytest.mark.parametrize("B,L", [(1, 32), (2, 64), (7, 96), (4, 4096),
                                 (16, 1024), (3, 32 * 37)])
def test_kernel_matches_reference(B, L):
    rng = np.random.default_rng(B * 1000 + L)
    chunks = rng.integers(0, 256, (B, L)).astype(np.uint8)
    got = hh256_tpu.hash_chunks(chunks)
    want = np.stack([np.frombuffer(hh256(chunks[b].tobytes()), np.uint8)
                     for b in range(B)])
    assert np.array_equal(got, want)


def test_kernel_magic_key_vector_32aligned():
    """Device kernel reproduces known digests under the zero key for
    32-aligned inputs (the magic vector itself is 100 bytes, so it runs
    through the host path; pin a 32-aligned derivative instead)."""
    data = (b"0123456789abcdef" * 4)  # 64 bytes
    got = hh256_tpu.hash_chunks(
        np.frombuffer(data, np.uint8)[None, :], b"\x00" * 32)
    assert got[0].tobytes() == hh256(data, b"\x00" * 32)


@pytest.mark.parametrize("L", [1, 3, 5, 16, 17, 31, 33, 47, 63, 100,
                               2731])
def test_kernel_unaligned_lengths(L):
    """Remainder handling in-kernel: every len % 32 layout variant
    (including the real-world shard_size 2731 = ceil(8192/3))."""
    rng = np.random.default_rng(L)
    chunks = rng.integers(0, 256, (3, L)).astype(np.uint8)
    got = hh256_tpu.hash_chunks(chunks)
    want = np.stack([np.frombuffer(hh256(chunks[b].tobytes()), np.uint8)
                     for b in range(3)])
    assert np.array_equal(got, want)


def _kernel_digests(monkeypatch, chunks, key):
    """Digests through hh256_rows' own pipeline with the Pallas kernel
    (interpret mode) standing where the platform's form would: what a
    TPU runs, minus Mosaic. Blocks of 8 packets, so a few hundred
    bytes cross block edges."""
    import functools

    import jax
    monkeypatch.setattr(hh256_tpu, "_BLOCK_PACKETS", 8)
    monkeypatch.setattr(hh256_tpu, "_absorb_xla", functools.partial(
        hh256_tpu._absorb_pallas, interpret=True))
    B, L = chunks.shape
    n, rem = divmod(L, 32)
    words = chunks[:, :n * 32].copy().view(np.uint32).reshape(B, n, 8)
    rem_packet = (hh256_tpu._pack_remainder(chunks[:, n * 32:], rem)
                  if rem else np.zeros((B, 8), np.uint32))
    fn = jax.jit(functools.partial(hh256_tpu._digest_rows,
                                   n_packets=n, rem=rem))
    out = np.ascontiguousarray(
        fn(words, rem_packet, hh256_tpu._init_state_np(key)))
    return out.view(np.uint8).reshape(B, 32)


# (rows, n_packets, rem) with blocks of 8 packets and an unroll of 4:
# fewer packets than one block (3, 5), exactly one packet, exactly one
# block (8), two whole blocks (16), blocks + a tail that is a multiple
# of the unroll (12 = 8 + 4), + an odd tail (21 = 16 + 4 + 1; 9 = 8 + 1);
# 130 rows is two lane tiles, the second nearly empty.
@pytest.mark.parametrize("B,n,rem", [
    (1, 3, 0), (4, 8, 22), (8, 21, 31), (12, 16, 0), (16, 21, 22),
    (128, 5, 31), (130, 9, 0), (16, 1, 0), (4, 12, 31)])
def test_pallas_kernel_digests_match_reference(monkeypatch, B, n, rem):
    rng = np.random.default_rng(B * 10007 + n * 101 + rem)
    chunks = rng.integers(0, 256, (B, n * 32 + rem)).astype(np.uint8)
    got = _kernel_digests(monkeypatch, chunks, MAGIC_KEY)
    for b in range(B):
        assert got[b].tobytes() == hh256(chunks[b].tobytes()), b


def test_pallas_kernel_magic_key_vector(monkeypatch):
    """The reference's own golden vector (cmd/bitrot.go:31): HH-256 of
    the first 100 decimals of pi under the zero key IS the magic key.
    100 bytes = 3 packets in the kernel + a 4-byte remainder."""
    from minio_tpu.ops.hh256 import PI_100_DECIMALS
    data = np.frombuffer(PI_100_DECIMALS.encode(), np.uint8)[None, :]
    got = _kernel_digests(monkeypatch, data, b"\x00" * 32)
    assert got[0].tobytes() == MAGIC_KEY


def test_kernel_info_is_set_at_the_first_dispatch(monkeypatch):
    """`hh256_kernel_info{impl}` and kernel_report() say which form of
    the packet loop the process built: "xla" off the TPU."""
    from minio_tpu.obs.metrics2 import METRICS2
    monkeypatch.setattr(hh256_tpu, "_kernel_impl", [])
    assert hh256_tpu.kernel_report() == {"kernel": ""}
    hh256_tpu.hash_chunks(np.zeros((2, 64), np.uint8))
    assert hh256_tpu.kernel_report() == {"kernel": "xla"}
    assert METRICS2.get("minio_tpu_v2_hh256_kernel_info",
                        {"impl": "xla"}) == 1
    assert "minio_tpu_v2_hh256_kernel_info" in METRICS2.snapshot()


def test_kernel_rejects_empty():
    with pytest.raises(ValueError):
        hh256_tpu.hash_chunks(np.zeros((2, 0), np.uint8))


@pytest.fixture
def force_device(monkeypatch):
    """Pretend a device exists and drop the byte threshold so the
    device path runs under CPU jax."""
    monkeypatch.setattr(batching, "_device_present", True)
    monkeypatch.setattr(bitrot, "HH_TPU_MIN_BYTES", 1)
    batching.HH_STATS.reset()
    yield
    batching.HH_STATS.reset()


def test_digest_chunks_many_parity(force_device):
    rng = np.random.default_rng(7)
    streams = [rng.integers(0, 256, n).astype(np.uint8).tobytes()
               for n in (256, 300, 64, 31, 0)]
    got = bitrot.digest_chunks_many(bitrot.DEFAULT_ALGORITHM, streams, 64)
    want = [bitrot.digest_chunks(bitrot.DEFAULT_ALGORITHM, s, 64)
            for s in streams]
    assert got == want
    s = batching.HH_STATS.snapshot()
    assert s["tpu_dispatches"] == 1
    assert s["coalesced_requests"] == len(streams)


def test_digest_chunks_many_host_below_threshold(monkeypatch):
    monkeypatch.setattr(batching, "_device_present", True)
    batching.HH_STATS.reset()
    streams = [b"x" * 64]
    got = bitrot.digest_chunks_many(bitrot.DEFAULT_ALGORITHM, streams, 64)
    assert got == [bitrot.digest_chunks(bitrot.DEFAULT_ALGORITHM,
                                        streams[0], 64)]
    assert batching.HH_STATS.snapshot()["tpu_dispatches"] == 0


def test_encode_streams_matches_encode_stream(force_device):
    rng = np.random.default_rng(9)
    streams = [rng.integers(0, 256, n).astype(np.uint8).tobytes()
               for n in (4096, 4097, 100, 0)]
    got = bitrot.encode_streams(streams, 1024)
    want = [bitrot.encode_stream(s, 1024) for s in streams]
    assert got == want
    assert batching.HH_STATS.snapshot()["tpu_dispatches"] == 1


def test_verify_frames_batched(force_device):
    rng = np.random.default_rng(11)
    datas = [rng.integers(0, 256, 128).astype(np.uint8).tobytes()
             for _ in range(5)]
    wants = [bitrot.digest(bitrot.DEFAULT_ALGORITHM, d) for d in datas]
    wants[2] = b"\x00" * 32  # corrupt one expectation
    ok = bitrot.verify_frames(list(datas), wants)
    assert ok == [True, True, False, True, True]
    assert batching.HH_STATS.snapshot()["tpu_dispatches"] == 1


def test_verify_frames_mixed_lengths(force_device):
    """Unequal frames still verify (tail frames hash on host)."""
    datas = [b"a" * 128, b"b" * 128, b"c" * 37]
    wants = [bitrot.digest(bitrot.DEFAULT_ALGORITHM, d) for d in datas]
    assert bitrot.verify_frames(datas, wants) == [True, True, True]


# --- engine integration: PUT hashes on device, GET verifies on device --------


def _make_engine(tmp_path, n=6, block_size=8192):
    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.storage.xl import XLStorage
    disks = [XLStorage(str(tmp_path / f"disk{i}")) for i in range(n)]
    return ErasureObjects(disks, block_size=block_size)


def test_engine_put_get_device_hash_path(tmp_path, force_device):
    e = _make_engine(tmp_path)
    e.make_bucket("b")
    payload = os.urandom(8192 * 4 + 123)
    before = batching.HH_STATS.snapshot()
    e.put_object("b", "obj", payload)
    mid = batching.HH_STATS.snapshot()
    assert mid["tpu_dispatches"] > before["tpu_dispatches"], \
        "PUT bitrot hashing must reach the device dispatch"
    got, _ = e.get_object("b", "obj")
    after = batching.HH_STATS.snapshot()
    assert got == payload
    assert after["tpu_dispatches"] > mid["tpu_dispatches"], \
        "GET bitrot verify must reach the device dispatch"


def test_engine_get_detects_corruption_device_path(tmp_path, force_device):
    e = _make_engine(tmp_path)
    e.make_bucket("b")
    payload = os.urandom(8192 * 3)
    e.put_object("b", "obj", payload)
    # Flip one byte inside one shard file's first frame payload.
    root = e.disks[2].root
    objdir = os.path.join(root, "b", "obj")
    ddir = next(d for d in os.listdir(objdir) if d != "xl.meta")
    part = os.path.join(objdir, ddir, "part.1")
    blob = bytearray(open(part, "rb").read())
    blob[40] ^= 0xFF
    open(part, "wb").write(bytes(blob))
    got, _ = e.get_object("b", "obj")
    assert got == payload  # reconstructed around the rotten shard


def test_engine_shard_files_identical_with_and_without_device(tmp_path,
                                                              monkeypatch):
    """The device hash path must be invisible on disk: same framed
    bytes as the host path (golden guard for the kernel)."""
    payload = os.urandom(8192 * 2 + 7)

    def put_and_slurp(sub, force):
        if force:
            monkeypatch.setattr(batching, "_device_present", True)
            monkeypatch.setattr(bitrot, "HH_TPU_MIN_BYTES", 1)
        else:
            monkeypatch.setattr(batching, "_device_present", False)
            monkeypatch.setattr(bitrot, "HH_TPU_MIN_BYTES", 1 << 60)
        e = _make_engine(tmp_path / sub)
        e.make_bucket("b")
        e.put_object("b", "obj", payload)
        files = {}
        for i, d in enumerate(e.disks):
            objdir = os.path.join(d.root, "b", "obj")
            ddir = next(x for x in os.listdir(objdir) if x != "xl.meta")
            files[i] = open(os.path.join(objdir, ddir, "part.1"),
                            "rb").read()
        return files

    assert put_and_slurp("dev", True) == put_and_slurp("host", False)


def test_hash_chunks_takes_a_result_in_the_device_layout(monkeypatch):
    """On the TPU the (B, 8) digest words came back column-major and
    the uint8 view raised ('the last axis must be contiguous') AFTER
    the dispatch was counted, so every such batch was hashed twice,
    the second time on the host (found by chip_smoke.py, PR 21). The
    CPU backend always answers C-contiguous, so fake the layout."""
    import numpy as np

    from minio_tpu.ops import hh256_tpu
    from minio_tpu.ops.hh256 import MAGIC_KEY, HighwayHash256

    real = hh256_tpu.hh256_rows

    def column_major(*a, **kw):
        return np.asfortranarray(np.asarray(real(*a, **kw)))

    monkeypatch.setattr(hh256_tpu, "hh256_rows", column_major)
    chunks = np.random.default_rng(5).integers(
        0, 256, (8, 100)).astype(np.uint8)
    got = hh256_tpu.hash_chunks(chunks)
    for i in range(8):
        want = HighwayHash256(MAGIC_KEY).update(
            chunks[i].tobytes()).digest()
        assert got[i].tobytes() == want
