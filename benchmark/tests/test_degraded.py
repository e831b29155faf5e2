"""A cell whose window writes nothing and whose drives are partly lost:
whole --rehearse runs of `ec8p4_get_2lost` (sound; the fault stated and
not applied; a copy written back before the child stops), the at-rest
comparison over two erasure sets, and the committed cells' result lines
as they were before the harness learned any of this."""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (HERE, os.path.dirname(HERE)):     # run as a script as well
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as bench_run  # noqa: E402
import test_control  # noqa: E402
from harness import atrest, server, traffic  # noqa: E402

NEW_CHECKS = ("lost_copies_present", "degraded_reads_not_decoded")
# Every cell prints them since the DELETE check; a mix that deletes
# nothing reads 0 there (nothing is checked).
DELETE_CHECKS = ("deleted_keys_readable", "deleted_keys_present")


def _run(capfd, monkeypatch, cell, trace=0, seconds="2"):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = bench_run.main(["--workload", cell, "--seed", "2700000077",
                         "--seconds", seconds, "--trace", str(trace),
                         "--rehearse"])
    out, err = capfd.readouterr()
    assert rc == 0, out[-2000:] + err[-2000:]
    return json.loads(out.strip().splitlines()[-1]), err


def _values(result):
    return {k: v["value"] for k, v in result["checks"].items()}


def test_degraded_cell_reads_correct(capfd, monkeypatch):
    result, err = _run(capfd, monkeypatch, "ec8p4_get_2lost")
    assert result["correct"] is True, err[-2000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(_values(result).values()) == {0}
    assert set(result["metrics"]) == {"goodput_mibps", "setup_s"}
    for name in NEW_CHECKS:
        assert f"check {name}: value 0 limit 0" in err


def test_fault_stated_and_not_applied_is_not_correct(capfd, monkeypatch):
    """The control of the two new numbers: every drive keeps its copies,
    so none is absent where the mix says it is, and no GET decodes."""
    monkeypatch.setattr(bench_run, "apply_faults", lambda faults, srv: None)
    result, err = _run(capfd, monkeypatch, "ec8p4_get_2lost")
    got = _values(result)
    assert result["correct"] is False
    # 3 objects sampled, 2 lost drives each.
    assert got["lost_copies_present"] == 6
    # 11 of the rehearsal's 12 objects lost a data shard to drives 2, 5.
    assert 0.5 * result["attempted"] < got["degraded_reads_not_decoded"] \
        <= result["attempted"]
    assert {k for k, v in got.items() if v} == set(NEW_CHECKS)
    assert "correct: False" in err


def test_copy_written_back_before_the_child_stops(capfd, monkeypatch):
    """What a heal onto a lost drive would leave: the window was not
    degraded to its end, whatever the GETs did."""
    real_stop = server.Server.stop

    def stop(self):
        src = os.path.join(self.drive(1), bench_run.BUCKET, "main")
        dst = os.path.join(self.drive(2), bench_run.BUCKET, "main")
        if self.proc is not None and self.proc.poll() is None \
                and os.path.isdir(src) and not os.path.exists(dst):
            shutil.copytree(src, dst)
        return real_stop(self)

    monkeypatch.setattr(server.Server, "stop", stop)
    result, _ = _run(capfd, monkeypatch, "ec8p4_get_2lost")
    got = _values(result)
    assert result["correct"] is False
    assert got["lost_copies_present"] == 3       # one drive, 3 sampled
    assert got["degraded_reads_not_decoded"] == 0


# --- two erasure sets (no server: files made by the reference) ----------------


def _two_sets(tmp, seed=5, k=2, m=2, block=1 << 14):
    """8 drives, two 2+2 sets in launch order; object i lives in set
    i % 2. Returns (drives, objects, body_of)."""
    base = traffic.base_buffer(seed, 50_000)
    objects = [(f"o{i}", n, 11 * i + 1)
               for i, n in enumerate([1 << 14, 50_000, 77, 30_001])]
    for i, (key, n, off) in enumerate(objects):
        root = os.path.join(tmp, f"set{i % 2}")
        test_control.write_tree(root, "bench", key, base[off:off + n],
                                k, m, block)
    drives = [os.path.join(tmp, f"set{s}", f"d{i}")
              for s in range(2) for i in range(1, k + m + 1)]
    return drives, objects, lambda n, off: memoryview(base)[off:off + n]


def test_two_sets_sound_files_read_nothing_missing(tmp_path):
    drives, objects, body_of = _two_sets(str(tmp_path))
    got = atrest.check(drives, "bench", objects, body_of, 2, 2, 1 << 14,
                       sets=2)
    assert got["objects_checked"] == 4
    assert got["shard_files_checked"] == 16
    assert (got["shard_files_missing"], got["shard_frames_differ"],
            got["digest_frames_differ"], got["lost_copies_present"]) \
        == (0, 0, 0, 0)
    # The same drives taken for one set of 8: four holes an object.
    one = atrest.check(drives, "bench", objects, body_of, 2, 2, 1 << 14)
    assert one["shard_files_missing"] == 16


def test_two_sets_copy_in_the_wrong_set_is_missing(tmp_path):
    drives, objects, body_of = _two_sets(str(tmp_path))
    shutil.copytree(os.path.join(drives[0], "bench", "o0"),
                    os.path.join(drives[5], "bench", "o0"))
    got = atrest.check(drives, "bench", objects, body_of, 2, 2, 1 << 14,
                       sets=2)
    assert got["shard_files_missing"] == 1
    os.remove(os.path.join(drives[2], "bench", "o0", "xl.meta"))
    got = atrest.check(drives, "bench", objects, body_of, 2, 2, 1 << 14,
                       sets=2)
    assert got["shard_files_missing"] == 2       # and a hole at home


def test_lost_drives_in_one_of_two_sets(tmp_path):
    drives, objects, body_of = _two_sets(str(tmp_path))
    lost = frozenset({1})                        # set 0's second drive
    got = atrest.check(drives, "bench", objects, body_of, 2, 2, 1 << 14,
                       sets=2, lost=lost)
    assert got["lost_copies_present"] == 2       # o0 and o2 live there
    assert got["shard_files_missing"] == 0
    assert got["shard_files_checked"] == 14
    for key in ("o0", "o2"):
        shutil.rmtree(os.path.join(drives[1], "bench", key))
    got = atrest.check(drives, "bench", objects, body_of, 2, 2, 1 << 14,
                       sets=2, lost=lost)
    assert (got["lost_copies_present"], got["shard_files_missing"]) == (0, 0)
    # Drive 2 of 2+2 held data shard 2 of the objects of set 0 only.
    assert [atrest.lost_data_shards(drives, "bench", key, 2, lost)
            for key, _, _ in objects] == [1, 0, 1, 0]


# --- the committed cells read what they read before ---------------------------

CHECKS_BEFORE = ["get_wrong_bytes", "ops_unanswered", "ops_error_status",
                 "shard_files_missing", "shard_frames_differ",
                 "digest_frames_differ", "at_rest_objects_unchecked",
                 "server_exit_code"]
# --trace 1 --rehearse on commit 478df7b (PR 26), the CPU: no device
# metric, nothing on a device lane (codec.dispatch_host_ms, _wait_ms,
# _depth stay off the line).
METRICS_BEFORE = {
    "ec8p4_large_put_get": {
        "codec.device_bytes_share", "codec.dispatch_wall_s_per_gib",
        "compile.in_window", "engine.get_fetch_ms", "engine.get_meta_ms",
        "engine.get_verify_ms", "engine.put_encode_ms",
        "engine.put_write_commit_ms", "frontdoor.get_send_ms",
        "frontdoor.put_recv_auth_ms", "frontdoor.unattributed_ms",
        "frontdoor.wait_ms", "loadgen.cpu_share", "server.cpu_cores",
        "storage.append_ms", "storage.rename_ms"},
    "ec4p2_small_put_get": {
        "client.get_p95_ms", "client.put_p95_ms",
        "codec.dispatch_wall_s_per_gib.ops", "compile.in_window.ops",
        "engine.get_fetch_ms.ops", "engine.get_meta_ms.ops",
        "engine.get_verify_ms.ops", "engine.put_encode_ms.ops",
        "engine.put_write_commit_ms.ops", "frontdoor.get_ms",
        "frontdoor.get_send_ms.ops", "frontdoor.put_ms",
        "frontdoor.put_recv_auth_ms.ops", "frontdoor.unattributed_ms.ops",
        "frontdoor.wait_ms.ops", "loadgen.cpu_share.ops",
        "server.cpu_cores.ops", "storage.append_ms.ops",
        "storage.rename_ms.ops"},
}


@pytest.mark.parametrize("cell", sorted(METRICS_BEFORE))
def test_committed_cell_reads_what_it_read(capfd, monkeypatch, cell):
    result, _ = _run(capfd, monkeypatch, cell, trace=1, seconds="3")
    assert result["correct"] is True
    assert set(result["metrics"]) == METRICS_BEFORE[cell]
    assert sorted(result["checks"]) == sorted(CHECKS_BEFORE
                                              + list(NEW_CHECKS)
                                              + list(DELETE_CHECKS))
    assert set(_values(result).values()) == {0}


def test_degraded_cell_traced_line_has_its_decode_metrics(capfd, monkeypatch):
    result, _ = _run(capfd, monkeypatch, "ec8p4_get_2lost", trace=1,
                     seconds="3")
    assert result["correct"] is True
    assert {"engine.get_decode_ms", "codec.decode_wall_s_per_gib",
            "codec.decode_device_bytes_share", "engine.get_fetch_ms",
            "engine.get_verify_ms", "compile.in_window"} \
        <= set(result["metrics"])
    # A window without a PUT has nothing for these to read.
    assert not {"frontdoor.put_recv_auth_ms", "storage.append_ms",
                "storage.rename_ms", "engine.put_encode_ms"} \
        & set(result["metrics"])
    assert result["metrics"]["engine.get_decode_ms"]["value"] > 0


if __name__ == "__main__":
    # On the chip's machine, at the cell's own size: the control of the
    # two new numbers, the fault stated and not applied.
    #   python benchmark/tests/test_degraded.py <cell> <seconds> <seed>...
    bench_run.apply_faults = lambda faults, srv: None
    for seed in sys.argv[3:]:
        bench_run.main(["--workload", sys.argv[1], "--seed", seed,
                        "--seconds", sys.argv[2], "--trace", "0",
                        "--tag", "notapplied"])
