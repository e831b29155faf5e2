"""The cell `ec12p4_multipart`: a whole --rehearse run on the CPU, and
the control for an object made of parts, which tests/test_control.py's
table of cells and its one-part `write_tree` do not hold:

    python benchmark/tests/test_multipart_cell.py <seed>...

writes, with the reference in the program's place, one 1 GiB upload in
64 MiB parts onto 16 drives (the cell's at-rest sample), once sound,
once with each stated guarantee broken, and once at 8+8, the geometry
the program gave every multipart upload of a 16-drive set before PR 32
(the set's default in place of the storage class's 12+4), and prints
what the at-rest comparison reads (PERF.md section 2)."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402
from harness import atrest, reference, traffic  # noqa: E402
from tests.test_control import cauchy_rows  # noqa: E402

CELL = "ec12p4_multipart"
MULTIPART_ONLY = {"multipart.part_ms", "multipart.complete_ms",
                  "multipart.load_list_ms"}


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One traced rehearsal of the cell (3 clients, 12 MiB uploads in
    5 MiB parts on 16 drives at 12+4); what the at-rest comparison was
    handed is kept, since the run removes its drives."""
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_PLATFORMS", "cpu")
    seen = {}
    real = atrest.check

    def check(drives, bucket, objects, body_of, k, m, block, **kw):
        seen.update(drives=len(drives), k=k, m=m, block=block,
                    part_size=kw["part_size"], objects=list(objects))
        return real(drives, bucket, objects, body_of, k, m, block, **kw)

    mp.setattr(atrest, "check", check)
    out = tmp_path_factory.mktemp("out") / "stdout"
    try:
        with open(out, "w") as f:
            mp.setattr(sys, "stdout", f)
            rc = bench_run.main(["--workload", CELL, "--seed", "3200000077",
                                 "--seconds", "4", "--trace", "1",
                                 "--rehearse"])
    finally:
        mp.undo()
    lines = out.read_text().strip().splitlines()
    assert rc == 0, "\n".join(lines[-30:])
    return json.loads(lines[-1]), seen


def test_rehearsal_is_correct_at_12p4(rehearsal):
    result, seen = rehearsal
    assert result["correct"] is True and result["failed"] == 0
    assert all(c["value"] == c["limit"] for c in result["checks"].values())
    assert (seen["drives"], seen["k"], seen["m"]) == (16, 12, 4)
    assert seen["part_size"] == 5 << 20
    assert [size for _, size, _ in seen["objects"]] == [12 << 20]


def test_the_line_carries_the_multipart_metrics(rehearsal):
    """Everything the cell lists that a CPU run can read: the three new
    quantities, the PUT-side ones it shares with the large cell (a part
    is api="PUT-object" and records a PUT's engine phases) and every `*`
    metric; nothing of the device."""
    metrics = rehearsal[0]["metrics"]
    for name in (*MULTIPART_ONLY, "engine.put_encode_ms", "engine.put_write_commit_ms",
                 "frontdoor.put_recv_auth_ms", "storage.append_ms",
                 "frontdoor.get_send_ms", "engine.get_fetch_ms",
                 "frontdoor.unattributed_ms", "loadgen.cpu_share"):
        assert metrics[name]["value"] > 0, name
    assert "kernels.roofline_share" not in metrics
    assert "device.idle_share" not in metrics


# -- the control for an object made of parts -----------------------------------

K, M, BLOCK = 12, 4, 10 << 20
SIZE, PART = 1 << 30, 64 << 20
BROKEN = (None, "cauchy_parity", "zero_key_digest", "short_digest",
          "parity_8p8")


def write_upload(root, bucket, key, body, part, k, m, block, broken=None):
    """The drive tree a completed multipart upload leaves, made by the
    reference: drive d<i> holds shard index i of every part, each part
    coded on its own from its first byte. `parity_8p8` codes it with
    half the drives as parity, whatever k+m the caller states."""
    n = k + m
    if broken == "parity_8p8":
        k, m = n // 2, n - n // 2
    rows = cauchy_rows(k, m) if broken == "cauchy_parity" else None
    view = memoryview(body)
    drives = [os.path.join(root, f"d{i}") for i in range(1, n + 1)]
    for i, d in enumerate(drives, start=1):
        base = os.path.join(d, bucket, key)
        os.makedirs(os.path.join(base, "dd"), exist_ok=True)
        with open(os.path.join(base, "xl.meta"), "w") as f:
            json.dump({"versions": [{"dataDir": "dd",
                                     "erasure": {"index": i}}]}, f)
    for pn, lo in enumerate(range(0, len(view), part), start=1):
        chunk = view[lo:lo + part]
        blocks = [reference.rs_encode_block(chunk[o:o + block], k, m, rows)
                  for o in range(0, len(chunk), block)]
        if broken == "zero_key_digest":
            digests = [reference.hh256_rows(b, b"\0" * 32) for b in blocks]
        else:
            digests = reference.digests_for([blocks])[0]
        if broken == "short_digest":
            digests = [np.concatenate(
                [d[:, :8], np.zeros((d.shape[0], 24), np.uint8)], axis=1)
                for d in digests]
        for d, data in zip(drives, reference.shard_files(blocks, digests)):
            with open(os.path.join(d, bucket, key, "dd", f"part.{pn}"),
                      "wb") as f:
                f.write(data)
    return drives


def control(root, seed, broken, size=SIZE, part=PART, k=K, m=M,
            block=BLOCK):
    base = traffic.base_buffer(seed, size)
    off = 4321
    drives = write_upload(root, "bench", "o", base[off:off + size], part,
                          k, m, block, broken)
    return atrest.check(drives, "bench", [("o", size, off)],
                        lambda n, o: memoryview(base)[o:o + n],
                        k, m, block, part_size=part)


SMALL = dict(size=365_537, part=1 << 17, k=4, m=2, block=1 << 16)
# 365,537 B in 128 KiB parts: two parts of two 64 KiB blocks and one of
# one block and a 37,857-byte tail: 6 frames on each of 6 drives.
FRAMES = 6 * 6


@pytest.mark.parametrize("seed", [1, 3_200_000_019])
def test_control_sound_and_each_guarantee_broken(tmp_path, seed):
    got = control(str(tmp_path / "sound"), seed, None, **SMALL)
    assert got["objects_checked"] == 1
    assert got["shard_files_checked"] == 3 * 6
    assert got["frames_checked"] == FRAMES
    assert [got[n] for n in ("shard_files_missing", "shard_frames_differ",
                             "digest_frames_differ")] == [0, 0, 0]
    got = control(str(tmp_path / "cauchy"), seed, "cauchy_parity", **SMALL)
    assert got["shard_frames_differ"] == 6 * 2          # frames x parity
    assert got["digest_frames_differ"] == 6 * 2
    for broken in ("zero_key_digest", "short_digest"):
        got = control(str(tmp_path / broken), seed, broken, **SMALL)
        assert got["digest_frames_differ"] == FRAMES
        assert got["shard_frames_differ"] == 0
    # The fault PR 32 repairs: 3+3 where the class says 4+2. Every shard
    # file has another length, so every frame of every file differs.
    got = control(str(tmp_path / "8p8"), seed, "parity_8p8", **SMALL)
    assert got["shard_frames_differ"] == FRAMES
    assert got["shard_files_missing"] == 0


if __name__ == "__main__":
    import tempfile
    scratch = os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           ".chip_smoke")
    os.makedirs(scratch, exist_ok=True)
    for seed in [int(s) for s in sys.argv[1:]]:
        for broken in BROKEN:
            with tempfile.TemporaryDirectory(dir=scratch) as tmp:
                got = control(tmp, seed, broken)
            print(json.dumps({"cell": CELL, "seed": seed,
                              "control": broken, **got}), flush=True)
