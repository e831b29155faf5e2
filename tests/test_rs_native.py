"""C++ GF(2^8) kernel (native/rs.cc): byte identity with the golden
numpy codec, and the host serving paths that route through it."""

import numpy as np
import pytest

from minio_tpu import native
from minio_tpu.ops import batching, rs_cpu
from minio_tpu.ops.gf256 import gf_mat_vec_apply
from minio_tpu.ops.rs_matrix import decode_matrix, parity_matrix


@pytest.fixture(scope="module")
def lib():
    got = native.get_lib()
    if got is None:
        pytest.skip("native lib unavailable (no compiler)")
    return got


@pytest.mark.parametrize("k,m", [(4, 2), (8, 4), (16, 4)])
def test_native_matches_golden(lib, k, m):
    rng = np.random.default_rng(0)
    for n in (1, 15, 16, 31, 32, 33, 1000, 65536):
        data = rng.integers(0, 256, (k, n)).astype(np.uint8)
        pm = parity_matrix(k, m)
        got = native.rs_apply_native(pm, data)
        assert got is not None
        assert np.array_equal(got, gf_mat_vec_apply(pm, data)), n


def test_native_mt_matches_single(lib):
    """Threaded column-split kernel is byte-identical to the
    single-threaded one regardless of chunk seams (forced to 4 threads —
    cpu_count may be 1 in CI, which would skip the threaded branch)."""
    import ctypes
    rng = np.random.default_rng(7)
    k, m = 8, 4
    n = 1_000_037  # odd size: ragged last chunk crosses SIMD width
    data = np.ascontiguousarray(
        rng.integers(0, 256, (k, n)).astype(np.uint8))
    pm = np.ascontiguousarray(parity_matrix(k, m))
    out = np.empty((m, n), dtype=np.uint8)
    lib.rs_gf_apply_mt(pm.ctypes.data, m, k, data.ctypes.data, n,
                       out.ctypes.data, 4)
    assert np.array_equal(out, gf_mat_vec_apply(pm, data))
    # Regression: n where floor(n/nthreads) is already a 64-multiple and
    # n % nthreads != 0 — a floor-based chunk split left the last
    # columns unwritten (returned np.empty garbage).
    n = 8 * 131072 + 3
    data = np.ascontiguousarray(
        rng.integers(0, 256, (k, n)).astype(np.uint8))
    out = np.empty((m, n), dtype=np.uint8)
    lib.rs_gf_apply_mt(pm.ctypes.data, m, k, data.ctypes.data, n,
                       out.ctypes.data, 8)
    assert np.array_equal(out, gf_mat_vec_apply(pm, data))
    # wrapper path over the threshold (whatever cpu_count dictates)
    big = np.ascontiguousarray(
        rng.integers(0, 256, (k, native.RS_MT_THRESHOLD // k + 1)
                     ).astype(np.uint8))
    got = native.rs_apply_native(pm, big)
    assert np.array_equal(got, gf_mat_vec_apply(pm, big))


def test_native_decode_matrix(lib):
    k, m = 8, 4
    rng = np.random.default_rng(1)
    avail = [i for i in range(k + m) if i not in (0, 5)]
    dec, used = decode_matrix(k, m, avail)
    rows = dec[[0, 5], :]
    data = rng.integers(0, 256, (len(used), 515)).astype(np.uint8)
    got = native.rs_apply_native(rows, data)
    assert np.array_equal(got, gf_mat_vec_apply(rows, data))


def test_host_encode_batch_fold():
    """batching.host_encode (folded, native-accelerated) must equal the
    per-block golden encode byte for byte."""
    rng = np.random.default_rng(2)
    k, m, S, B = 8, 4, 700, 5
    blocks = rng.integers(0, 256, (B, k, S)).astype(np.uint8)
    got = batching.host_encode(blocks, k, m)
    for b in range(B):
        want = np.concatenate(
            [blocks[b], np.zeros((m, S), np.uint8)])
        rs_cpu.encode(want, k, m)
        assert np.array_equal(got[b], want)


def test_codec_single_block_host_path():
    """Erasure.encode_data on the host backend routes through host_apply
    and still matches the golden split+encode."""
    from minio_tpu.erasure.codec import Erasure
    payload = bytes(range(256)) * 41
    codec = Erasure(4, 2, block_size=1 << 20, backend="cpu")
    got = codec.encode_data(payload)
    want = rs_cpu.encode_data(payload, 4, 2)
    assert np.array_equal(got, np.asarray(want))


def _scattered_blocks(rng, k, B, S):
    """B blocks of k rows of S bytes, each row its own allocation (as a
    heal's survivor sub-blocks lie in separate streams)."""
    return [[rng.integers(0, 256, S, dtype=np.uint8) for _ in range(k)]
            for _ in range(B)]


@pytest.mark.parametrize("S", [1, 15, 33, 63, 64, 65, 97, 1000, 4099])
@pytest.mark.parametrize("k,r", [(4, 1), (8, 3), (12, 4)])
def test_native_blocks_matches_golden(lib, k, r, S):
    """rs_gf_apply_blocks reads each row through its pointer and writes
    block b at columns b*S of rows wider than B*S; odd lengths and
    lengths under one 64-byte SIMD step."""
    rng = np.random.default_rng(S * 31 + k)
    mat = rng.integers(0, 256, (r, k), dtype=np.uint8)
    B = 3
    blocks = _scattered_blocks(rng, k, B, S)
    out = np.full((r, B * S + 7), 0xAB, dtype=np.uint8)
    assert native.rs_apply_blocks_native(mat, blocks, out) is out
    for b, rows in enumerate(blocks):
        np.testing.assert_array_equal(
            out[:, b * S:(b + 1) * S],
            gf_mat_vec_apply(mat, np.stack(rows)))
    assert (out[:, B * S:] == 0xAB).all()  # nothing past the blocks


@pytest.mark.parametrize("nthreads", [2, 3, 4, 8])
@pytest.mark.parametrize("S", [2 * 64 * 8 - 1, 8 * 131072 + 3, 200_001])
def test_native_blocks_thread_seams(lib, S, nthreads):
    """Columns split over threads at 64-byte seams, the same split for
    every block: byte-identical to the single-threaded call, into a
    column slice of wider rows (the ostride the heal's later runs
    write through)."""
    rng = np.random.default_rng(nthreads)
    k, r, B = 12, 2, 2
    mat = rng.integers(0, 256, (r, k), dtype=np.uint8)
    blocks = _scattered_blocks(rng, k, B, S)
    ptrs = np.array([row.ctypes.data for blk in blocks for row in blk],
                    dtype=np.uintp)
    wide = np.zeros((r, B * S + 100), dtype=np.uint8)
    dst = wide[:, 50:50 + B * S]
    lib.rs_gf_apply_blocks(mat.ctypes.data, r, k, ptrs.ctypes.data, B, S,
                           dst.ctypes.data, dst.strides[0], nthreads)
    for b, rows in enumerate(blocks):
        np.testing.assert_array_equal(
            dst[:, b * S:(b + 1) * S],
            gf_mat_vec_apply(mat, np.stack(rows)))
    assert not wide[:, :50].any() and not wide[:, 50 + B * S:].any()


def test_native_blocks_rejects_what_it_cannot_read(lib):
    rng = np.random.default_rng(0)
    mat = np.ones((1, 2), dtype=np.uint8)
    good = _scattered_blocks(rng, 2, 1, 64)
    with pytest.raises(ValueError):  # a strided row
        native.rs_apply_blocks_native(
            mat, [[good[0][0], np.zeros(128, np.uint8)[::2]]],
            np.empty((1, 64), np.uint8))
    with pytest.raises(ValueError):  # rows too narrow for the blocks
        native.rs_apply_blocks_native(mat, good, np.empty((1, 63),
                                                          np.uint8))


def test_probe_checks_the_row_pointer_entry(lib, monkeypatch):
    """native.probe() passes on a sound library and fails, naming the
    row-pointer entry, when that entry answers wrong."""
    assert native.probe()
    real = native.rs_apply_blocks_native

    def wrong(mat, blocks, out):
        real(mat, blocks, out)
        out[0, 0] ^= 1
        return out
    reasons = []
    monkeypatch.setattr(native, "rs_apply_blocks_native", wrong)
    monkeypatch.setattr(native, "_disable_native", reasons.append)
    assert not native.probe()
    assert reasons == ["probe: rs_gf_apply_blocks known-answer mismatch"]
