"""The four-chip cell `mesh2x2_large_put_get`: a whole --rehearse run
on four virtual CPU devices (two sets served by one process under a
2x2 mesh), and the control at the cell's own sizes for a deployment of
two sets, which tests/test_control.py's table of cells does not hold:

    python benchmark/tests/test_mesh_cell.py <seed>...

writes, with the reference in the program's place, three objects into
the first set's 12 drives and three into the second's, once sound and
once with each guarantee broken, and prints what the at-rest comparison
reads (PERF.md section 2)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402
from harness import atrest, traffic  # noqa: E402
from tests.test_control import write_tree  # noqa: E402

CELL = "mesh2x2_large_put_get"
MESH_ONLY = {"kernels.mesh_roofline_share", "mesh.device_bytes_balance",
             "mesh.redundant_bytes_share"}


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One traced rehearsal of the cell on four virtual devices; what
    the at-rest comparison was handed is kept, since the run removes
    its drives."""
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_PLATFORMS", "cpu")
    mp.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    seen = {}
    real = atrest.check

    def check(drives, bucket, objects, *a, **kw):
        per_set = len(drives) // kw["sets"]
        seen["sets"] = kw["sets"]
        seen["homes"] = {
            key: sorted({i // per_set for i, d in enumerate(drives)
                         if atrest._placed(d, bucket, key)})
            for key, _, _ in objects}
        return real(drives, bucket, objects, *a, **kw)

    mp.setattr(atrest, "check", check)
    out = tmp_path_factory.mktemp("out") / "stdout"
    try:
        with open(out, "w") as f:
            mp.setattr(sys, "stdout", f)
            rc = bench_run.main(["--workload", CELL, "--seed", "2800000077",
                                 "--seconds", "4", "--trace", "1",
                                 "--rehearse"])
    finally:
        mp.undo()
    lines = out.read_text().strip().splitlines()
    assert rc == 0, "\n".join(lines[-30:])
    return json.loads(lines[-1]), seen


def test_rehearsal_on_four_virtual_devices_is_correct(rehearsal):
    result, _ = rehearsal
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": 4, "memory_peak_bytes": 0}
    assert all(c["value"] == c["limit"] for c in result["checks"].values())


def test_both_sets_hold_sampled_objects_each_in_one_set(rehearsal):
    _, seen = rehearsal
    assert seen["sets"] == 2
    assert all(len(h) == 1 for h in seen["homes"].values()), seen
    assert {h[0] for h in seen["homes"].values()} == {0, 1}, seen


def test_set_balance_on_the_line_and_no_mesh_metric_off_the_chip(rehearsal):
    """`device_present()` is false on the CPU: a rehearsal dispatches
    nothing to a device, so the census's series do not move and the
    mesh metrics stay off the line (absent, never 0)."""
    metrics = rehearsal[0]["metrics"]
    assert 0 < metrics["sets.bytes_balance"]["value"] <= 100
    assert not MESH_ONLY & set(metrics)
    for name in ("engine.put_encode_ms", "storage.append_ms",
                 "frontdoor.put_recv_auth_ms", "loadgen.cpu_share"):
        assert name in metrics
    assert "kernels.roofline_share" not in metrics


# -- the control for two sets ---------------------------------------------------

K, M, BLOCK = 8, 4, 10 << 20
SIZES = [10485760, 26214400, 52441145]


def control(root, seed, broken, sizes=SIZES, k=K, m=M, block=BLOCK):
    base = traffic.base_buffer(seed, max(sizes))
    objects, drives = [], []
    for s in range(2):
        for i, n in enumerate(sizes):
            key, off = f"s{s}o{i}", 17 * (3 * s + i) + 3
            objects.append((key, n, off))
            made = write_tree(os.path.join(root, f"set{s}"), "bench", key,
                              base[off:off + n], k, m, block, broken)
        drives += made
    return atrest.check(drives, "bench", objects,
                        lambda n, off: memoryview(base)[off:off + n],
                        k, m, block, sets=2)


SMALL = dict(k=4, m=2, block=1 << 16, sizes=[1 << 16, 150001, 40])


def test_two_set_control_sound_and_broken(tmp_path):
    got = control(str(tmp_path / "sound"), 5, None, **SMALL)
    assert got["objects_checked"] == 6
    assert got["shard_files_checked"] == 36
    assert [got[n] for n in ("shard_files_missing", "shard_frames_differ",
                             "digest_frames_differ")] == [0, 0, 0]
    got = control(str(tmp_path / "cauchy"), 5, "cauchy_parity", **SMALL)
    assert got["shard_frames_differ"] == 2 * 5 * 2   # sets x blocks x parity
    for broken in ("zero_key_digest", "short_digest"):
        got = control(str(tmp_path / broken), 5, broken, **SMALL)
        assert got["digest_frames_differ"] == got["frames_checked"] > 0


if __name__ == "__main__":
    import tempfile
    scratch = os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           ".chip_smoke")
    os.makedirs(scratch, exist_ok=True)
    for seed in [int(s) for s in sys.argv[1:]]:
        for broken in (None, "cauchy_parity", "zero_key_digest",
                       "short_digest"):
            with tempfile.TemporaryDirectory(dir=scratch) as tmp:
                got = control(tmp, seed, broken)
            print(json.dumps({"cell": CELL, "seed": seed,
                              "control": broken, **got}), flush=True)
