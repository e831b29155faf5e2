"""The plain reference against facts that do not come from the program:
the bitrot key re-derived from pi, HighwayHash's own published test
vector, and Reed-Solomon's defining property (any k shards give the
data back). Where the program's host lanes are importable they are a
second witness, never the reference's source."""

import os

import numpy as np
import pytest

from harness import reference as R


def test_bitrot_key_is_hh256_of_pi_under_a_zero_key():
    assert R.hh256(R.PI_100_DECIMALS.encode(), b"\0" * 32) == R.BITROT_KEY


def test_rows_hash_like_single_messages():
    rng = np.random.default_rng(7)
    for length in (1, 31, 32, 33, 1544, 4096 + 29):
        rows = rng.integers(0, 256, (5, length), dtype=np.uint8)
        digs = R.hh256_rows(rows)
        for i in range(5):
            assert digs[i].tobytes() == R.hh256(rows[i].tobytes())


@pytest.mark.parametrize("k,m", [(4, 2), (8, 4), (16, 4)])
def test_any_k_shards_give_the_data_back(k, m):
    rng = np.random.default_rng(k)
    body = rng.integers(0, 256, 10007, dtype=np.uint8).tobytes()
    shards = R.rs_encode_block(body, k, m)
    full = [[1 if i == j else 0 for j in range(k)] for i in range(k)] \
        + R.parity_rows(k, m)
    keep = sorted(rng.choice(k + m, k, replace=False).tolist())
    inv = R._mat_inv([full[i] for i in keep])
    data = np.zeros((k, shards.shape[1]), np.uint8)
    for r in range(k):
        for c, idx in enumerate(keep):
            if inv[r][c]:
                data[r] ^= R._mul_table(inv[r][c])[shards[idx]]
    assert data.reshape(-1)[:len(body)].tobytes() == body


def test_parity_rows_of_4_plus_2_by_hand():
    # vm rows 4, 5 times inverse(vm[:4]) over GF(2^8)/0x11d, worked with
    # klauspost/reedsolomon's matrix for New(4, 2).
    assert R.parity_rows(4, 2) == [[27, 28, 18, 20], [28, 27, 20, 18]]


def test_shard_file_layout():
    body = os.urandom(3 * 4096 + 100)
    blocks = R.shard_blocks(body, 4, 2, 4096)
    assert [b.shape for b in blocks] == [(6, 1024)] * 3 + [(6, 25)]
    files = R.shard_files(blocks, R.digests_for([blocks])[0])
    assert len(files) == 6
    assert len(files[0]) == 3 * (32 + 1024) + 32 + 25
    assert files[0][32:32 + 1024] == body[:1024]
    assert files[0][:32] == R.hh256(body[:1024])


def test_program_host_lanes_agree():
    hh = pytest.importorskip("minio_tpu.ops.hh256")
    rs_cpu = pytest.importorskip("minio_tpu.ops.rs_cpu")
    body = os.urandom(100_003)
    for n in (0, 5, 32, 1000):
        if n:
            assert R.hh256(body[:n]) == hh.HighwayHash256(
                hh.MAGIC_KEY).update(body[:n]).digest()
    for k, m in ((4, 2), (8, 4)):
        theirs = rs_cpu.encode_data(body, k, m)
        mine = R.rs_encode_block(body, k, m)
        assert all(np.array_equal(mine[i], theirs[i]) for i in range(k + m))
