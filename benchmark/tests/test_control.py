"""The control: the reference put in the program's place, with one
guarantee the configuration states broken, has to come out as not
correct; unbroken, as correct. The comparison is exact (limit 0), so
the lower reading is 0 on every sound run and the control only has to
read above it.

The guarantee broken: "shards and digests byte-identical to the
reference". Tempting steps that would break it and that a GET would
never notice: a parity matrix of another construction (a Cauchy matrix,
which decodes perfectly well against itself), a digest under another
key, a digest truncated to the cheaper 64-bit HighwayHash and padded.
Run on the chip at the cells' own sizes by sets of
`python benchmark/tests/test_control.py <cell> <seed>...` (PERF.md §2).

A cell with lost drives (`ec8p4_get_2lost`) adds a GET by the reference
from the drives that are left: with data shards among the lost, the one
read path on which a parity matrix of another construction also gives
wrong BYTES (`get_wrong_bytes`), since the reader rebuilds the data with
the reference's matrix from parity made with another.
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import atrest, reference, traffic  # noqa: E402


def cauchy_rows(k, m):
    """x_i = k + i, y_j = j: 1 / (x_i ^ y_j). MDS, not the reference's."""
    return [[reference.gf_inv((k + i) ^ j) for j in range(k)]
            for i in range(m)]


def degraded_get(drives, bucket, key, size, k, m, block, lost):
    """The object's bytes as the reference reads them from the drives
    not in `lost` (positions): the first k shard files left, frames
    stripped, the data rows rebuilt with the reference's own matrix."""
    full = [[int(i == j) for j in range(k)] for i in range(k)] \
        + reference.parity_rows(k, m)
    have = {}
    for i, d in enumerate(drives):
        got = None if i in lost else atrest._stored(d, bucket, key, 1)
        if got is not None and len(have) < k:
            have[got[0] - 1] = got[1]
    keep = sorted(have)
    inv = reference._mat_inv([full[i] for i in keep])
    out, pos = [], 0
    for lo in range(0, size, block):
        n = -(-min(block, size - lo) // k)
        rows = [np.frombuffer(have[i], np.uint8, n, pos + 32) for i in keep]
        pos += 32 + n
        data = np.zeros((k, n), np.uint8)
        for r in range(k):
            for c in range(k):
                if inv[r][c]:
                    data[r] ^= reference._mul_table(inv[r][c])[rows[c]]
        out.append(data.reshape(-1)[:min(block, size - lo)].tobytes())
    return b"".join(out)


def write_tree(root, bucket, key, body, k, m, block, broken=None,
               skip_drive=None):
    """The drive tree a PUT leaves, made by the reference (optionally
    with one guarantee broken): drive d<i> holds shard index i.
    `skip_drive`: a drive number, or several, left without its copy."""
    skip = {skip_drive} if isinstance(skip_drive, int) else \
        set(skip_drive or ())
    rows = cauchy_rows(k, m) if broken == "cauchy_parity" else None
    view = memoryview(body)
    blocks = [reference.rs_encode_block(view[o:o + block], k, m, rows)
              for o in range(0, len(view), block)]
    if broken == "zero_key_digest":
        digests = [reference.hh256_rows(b, b"\0" * 32) for b in blocks]
    else:
        digests = reference.digests_for([blocks])[0]
    if broken == "short_digest":
        digests = [np.concatenate([d[:, :8], np.zeros((d.shape[0], 24),
                                                      np.uint8)], axis=1)
                   for d in digests]
    files = reference.shard_files(blocks, digests)
    drives = []
    for i, data in enumerate(files, start=1):
        d = os.path.join(root, f"d{i}")
        drives.append(d)
        if i in skip:
            os.makedirs(d, exist_ok=True)
            continue
        base = os.path.join(d, bucket, key)
        os.makedirs(os.path.join(base, "dd"), exist_ok=True)
        with open(os.path.join(base, "xl.meta"), "w") as f:
            json.dump({"versions": [{"dataDir": "dd",
                                     "erasure": {"index": i}}]}, f)
        with open(os.path.join(base, "dd", "part.1"), "wb") as f:
            f.write(data)
    return drives


def read_control(tmp, k, m, block, sizes, seed, broken, skip_drive=None,
                 lost=()):
    """`lost`: drive NUMBERS the cell's fault takes out. The tree is
    written without them (unless `skip_drive` says otherwise: the fault
    stated and not applied), the comparison is told of them, and every
    object is read back degraded."""
    base = traffic.base_buffer(seed, max(sizes))
    objects = [(f"o{i}", n, 17 * i + 3) for i, n in enumerate(sizes)]
    if lost and skip_drive is None:
        skip_drive = lost
    for key, n, off in objects:
        drives = write_tree(tmp, "bench", key, base[off:off + n], k, m,
                            block, broken, skip_drive)
    gone = frozenset(d - 1 for d in lost)
    got = atrest.check(drives, "bench", objects,
                       lambda n, off: memoryview(base)[off:off + n],
                       k, m, block, lost=gone)
    if lost:
        got["get_wrong_bytes"] = sum(
            degraded_get(drives, "bench", key, n, k, m, block, gone)
            != base[off:off + n] for key, n, off in objects)
    return got


SMALL = dict(k=4, m=2, block=1 << 16, sizes=[1 << 16, 150001, 40])


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_019])
def test_reference_in_the_programs_place_is_correct(tmp_path, seed):
    got = read_control(str(tmp_path), seed=seed, broken=None, **SMALL)
    assert got["objects_checked"] == 3
    assert got["shard_files_checked"] == 18
    assert (got["shard_files_missing"], got["shard_frames_differ"],
            got["digest_frames_differ"]) == (0, 0, 0)


@pytest.mark.parametrize("broken,number", [
    ("cauchy_parity", "shard_frames_differ"),
    ("zero_key_digest", "digest_frames_differ"),
    ("short_digest", "digest_frames_differ"),
])
@pytest.mark.parametrize("seed", [1, 2, 3_000_000_019])
def test_control_is_not_correct(tmp_path, seed, broken, number):
    got = read_control(str(tmp_path), seed=seed, broken=broken, **SMALL)
    assert got[number] > 0


def test_cauchy_parity_leaves_data_shards_alone(tmp_path):
    got = read_control(str(tmp_path), seed=5, broken="cauchy_parity", **SMALL)
    # 5 stripe blocks over the three objects, m=2 parity shards each.
    assert got["shard_frames_differ"] == 5 * 2
    # Parity bytes differ, so their sound digests differ too.
    assert got["digest_frames_differ"] == 5 * 2


def test_missing_copy_is_not_correct(tmp_path):
    got = read_control(str(tmp_path), seed=5, broken=None, skip_drive=3,
                       **SMALL)
    assert got["shard_files_missing"] == 3


LOST = dict(SMALL, lost=(2, 5))      # 4+2: both lost shards are data


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_019])
def test_lost_drives_reference_in_the_programs_place_is_correct(tmp_path,
                                                                seed):
    got = read_control(str(tmp_path), seed=seed, broken=None, **LOST)
    assert got["shard_files_checked"] == 12
    assert [got[n] for n in ("shard_files_missing", "shard_frames_differ",
                             "digest_frames_differ", "lost_copies_present",
                             "get_wrong_bytes")] == [0] * 5


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_019])
def test_lost_drives_cauchy_parity_returns_wrong_bytes(tmp_path, seed):
    got = read_control(str(tmp_path), seed=seed, broken="cauchy_parity",
                       **LOST)
    assert got["shard_frames_differ"] > 0
    assert got["get_wrong_bytes"] == 3      # every object, read degraded


def test_lost_drives_that_kept_their_copies_are_not_correct(tmp_path):
    got = read_control(str(tmp_path), seed=5, broken=None, skip_drive=(),
                       **LOST)
    assert got["lost_copies_present"] == 6
    assert got["shard_files_missing"] == 0


if __name__ == "__main__":
    # On the chip's machine, at a cell's own sizes: the control's readings.
    import tempfile
    cell, seeds = sys.argv[1], [int(s) for s in sys.argv[2:]]
    shape = {"ec8p4_large_put_get": dict(
                 k=8, m=4, block=10 << 20,
                 sizes=[10485760, 26214400, 52441145]),
             "ec4p2_small_put_get": dict(
                 k=4, m=2, block=10 << 20,
                 sizes=[1048576] * 8 + [5242880]),
             "ec8p4_get_2lost": dict(
                 k=8, m=4, block=10 << 20, sizes=[26214400] * 6,
                 lost=(2, 5)),
             # The 15 sizes of warp's mix, one object each (a run
             # samples 12 of its window's PUTs, the largest among them).
             "ec8p4_warp_mixed": dict(
                 k=8, m=4, block=10 << 20,
                 sizes=[1 << i for i in range(10, 24)] + [10 << 20]),
             # The whole tree made by the broken reference; a REBUILD
             # alone broken is tests/test_heal_cell.py's control.
             "ec12p4_heal": dict(
                 k=12, m=4, block=10 << 20, sizes=[67108864] * 12)}[cell]
    for seed in seeds:
        for broken in (None, "cauchy_parity", "zero_key_digest",
                       "short_digest"):
            with tempfile.TemporaryDirectory(dir=os.path.join(
                    os.path.dirname(os.path.dirname(HERE)),
                    ".chip_smoke")) as tmp:
                got = read_control(tmp, seed=seed, broken=broken, **shape)
            print(json.dumps({"cell": cell, "seed": seed, "control": broken,
                              **got}), flush=True)
