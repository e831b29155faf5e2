"""The cell `ec12p4_heal` (one wiped drive of a 16-drive 12+4 set, one
admin heal, timed): whole --rehearse runs on the CPU, sound and with the
timed path broken underneath; the data / parity split of the cell's 48
keys on the wiped drive and the stratified at-rest sample; the heal
summary's arithmetic on synthetic statuses; and the control for a
REBUILT copy, which tests/test_control.py's whole-tree `write_tree`
does not hold:

    python benchmark/tests/test_heal_cell.py control <seed>...

writes, with the reference in the program's place, the cell's at-rest
sample (12 objects of 64 MiB on 16 drives at 12+4, shard indices rotated
as the reference's hashOrder rotates them) and then rebuilds drive 7's
copies from k survivors as a heal does, once sound and once with each
stated guarantee broken IN THE REBUILD, and prints what the at-rest
comparison reads (PERF.md section 2; 1.1 GiB of files while it runs,
under .chip_smoke/ or where BENCH_CONTROL_DIR says). And

    python benchmark/tests/test_heal_cell.py notapplied <seed>...

is, on the chip's machine at the cell's own size, the run with the fault
stated and not applied (nothing wiped, so nothing to heal)."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (HERE, os.path.dirname(HERE)):     # run as a script as well
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as bench_run  # noqa: E402
from harness import atrest, heal, reference, traffic  # noqa: E402
from test_control import cauchy_rows  # noqa: E402

CELL = "ec12p4_heal"
DRIVE, K, M = 7, 12, 4
FAULTY = os.path.join(HERE, "faulty_child.py")
MIX = os.path.join(os.path.dirname(HERE), "traffic", "heal_one_drive.json")
HEAL_METRICS = {
    "codec.decode_wall_s_per_gib.heal", "codec.decode_device_bytes_share.heal",
    "codec.dispatch_wall_s_per_gib.heal",
    "storage.append_ms.heal", "storage.rename_ms.heal",
    "server.cpu_cores.heal", "compile.in_window.heal",
    "heal.survivor_bytes_per_user_byte", "heal.object_ms",
    "heal.classify_ms", "heal.fetch_ms", "heal.verify_ms", "heal.decode_ms",
    "heal.frame_ms", "heal.write_commit_ms", "heal.unattributed_ms",
    "heal.list_ms"}


def _run(capfd, monkeypatch, trace=0, fault=None):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    argv = ["--workload", CELL, "--seed", "3400000077", "--seconds", "2",
            "--trace", str(trace), "--rehearse"]
    if fault:
        monkeypatch.setenv("BENCH_FAULT", fault)
        rc = bench_run.main(argv, child=FAULTY)
    else:
        rc = bench_run.main(argv)
    out, err = capfd.readouterr()
    assert rc == 0, out[-2000:] + err[-2000:]
    return json.loads(out.strip().splitlines()[-1]), err


def _values(result):
    return {k: v["value"] for k, v in result["checks"].items()}


# --- whole rehearsals ---------------------------------------------------------


def test_sound_run_is_correct_and_times_the_heal(capfd, monkeypatch):
    result, err = _run(capfd, monkeypatch)
    assert result["correct"] is True, err[-2000:]
    # 3 clients x 2 preloaded objects: the fault took a copy of each.
    assert (result["attempted"], result["failed"]) == (6, 0)
    assert set(result["metrics"]) == {"heal_s", "setup_s"}
    assert 0 < result["metrics"]["heal_s"]["value"] < 60
    assert set(_values(result).values()) == {0}
    assert list(result["checks"])[-4:] == [
        "healed_copies_missing", "heal_items_unhealed", "heal_sweeps_raced",
        "server_exit_code"]
    assert "check heal_items_unhealed: value 0 limit 0" in err
    assert err.strip().splitlines()[-1] == "correct: True"


def test_traced_line_has_the_heal_metrics(capfd, monkeypatch):
    result, _ = _run(capfd, monkeypatch, trace=1)
    assert result["correct"] is True
    # Nothing reaches a device lane on the CPU, so the three
    # codec.dispatch_* and the two device metrics stay off the line.
    assert set(result["metrics"]) == HEAL_METRICS
    got = {k: v["value"] for k, v in result["metrics"].items()}
    # 12 survivor files of (32 + 873,814) + (32 + 174,763) B for 12 MiB.
    assert got["heal.survivor_bytes_per_user_byte"] == pytest.approx(
        12 * reference.shard_file_bytes(12 << 20, K, 10 << 20) / (12 << 20),
        rel=1e-12)
    assert got["compile.in_window.heal"] == 0
    assert got["heal.object_ms"] > 0 and got["storage.rename_ms.heal"] > 0


def test_a_slice_that_outlasts_the_heal_gives_no_device_metric(
        capfd, monkeypatch):
    """The traced slice has to lie wholly inside the heal; one that
    starts after it ended is said in the record's notes, the run and
    its other metrics stand."""
    real = traffic.parse

    def parse(name, doc, rehearse=False):
        mix = real(name, doc, rehearse)
        mix.faults["heal"] = dict(mix.faults["heal"], trace_start_s=4.0)
        return mix
    monkeypatch.setattr(traffic, "parse", parse)
    result, _ = _run(capfd, monkeypatch, trace=1)
    assert result["correct"] is True
    assert set(result["metrics"]) == HEAL_METRICS
    with open(os.path.join(bench_run.ROOT, "chiprun_out",
                           f"{CELL}-3400000077-1.json")) as f:
        record = json.load(f)
    assert record["notes"]["heal_trace"][1]["slice_end_s"] > 4.0


def test_fault_stated_and_not_applied_is_not_correct(capfd, monkeypatch):
    """The control of `heal_items_unhealed`: nothing is wiped, so the
    sweep finds nothing to heal. That is no fast heal: no `heal_s`."""
    monkeypatch.setattr(heal, "empty_volume", lambda root, bucket: None)
    monkeypatch.setattr(heal, "wipe", lambda root, bucket: None)
    result, err = _run(capfd, monkeypatch)
    got = _values(result)
    assert result["correct"] is False
    assert (result["attempted"], result["failed"]) == (6, 6)
    assert got["heal_items_unhealed"] == 6
    assert {k for k, v in got.items() if v} == {"heal_items_unhealed"}
    assert set(result["metrics"]) == {"setup_s"}
    assert "correct: False" in err


def test_a_sweep_of_the_new_disk_monitor_that_raced(capfd, monkeypatch):
    """The control of `heal_sweeps_raced`: the monitor's tick falls
    between the wipe and the admin sweep's first step, so its own sweep
    heals the drive; the admin sweep finds every object healthy."""
    result, _ = _run(capfd, monkeypatch, fault="heal_raced")
    got = _values(result)
    assert result["correct"] is False
    assert got["heal_sweeps_raced"] == 1
    assert got["heal_items_unhealed"] == 6
    assert {k for k, v in got.items() if v} == {
        "heal_sweeps_raced", "heal_items_unhealed"}
    assert set(result["metrics"]) == {"setup_s"}


def test_a_heal_that_says_done_without_writing(capfd, monkeypatch):
    result, _ = _run(capfd, monkeypatch, fault="heal_noop")
    got = _values(result)
    assert result["correct"] is False
    assert got["heal_items_unhealed"] == 0       # it SAID it healed all
    assert got["healed_copies_missing"] == 6     # every object's copy
    assert got["shard_files_missing"] == 3       # the 3 sampled ones
    assert got["get_wrong_bytes"] == 0           # read back degraded


def test_a_rebuilt_shard_altered_where_it_is_written(capfd, monkeypatch):
    result, _ = _run(capfd, monkeypatch, fault="heal_shard")
    got = _values(result)
    assert result["correct"] is False
    # One frame (the first) of the healed copy of each sampled object.
    assert got["shard_frames_differ"] == 3
    assert {k for k, v in got.items() if v} == {"shard_frames_differ"}


# --- the keys on the wiped drive, and the sample ------------------------------


def _keys():
    mix = traffic.load(MIX, "heal_one_drive")
    (g,) = mix.groups
    assert (g.clients, g.ring, g.sizes) == (4, 12, [64 << 20])
    assert mix.preload_per_client == 12 and not mix.writes
    assert mix.faults["wipe_drive"] == DRIVE
    assert mix.faults["heal"] == {"poll_s": 0.1, "trace_start_s": 5.0}
    keys = []
    for s in traffic.streams(7, mix):
        puts = [s.next() for _ in range(mix.preload_per_client)]
        assert [o.kind for o in puts] == ["PUT"] * 12
        keys += [o.key for o in puts]
    return mix, keys


def test_data_parity_split_of_the_48_keys_on_drive_7():
    """Key names do not depend on the seed and shard indices rotate by
    crc32 of bucket/key, so the split is the same in every run."""
    mix, keys = _keys()
    assert len(set(keys)) == 48
    kinds = [heal.data_or_parity("bench", k, DRIVE, K, K + M) for k in keys]
    assert (kinds.count("data"), kinds.count("parity")) == (36, 12)
    # The same arithmetic as tests/test_traffic.py's for get_2lost.
    import zlib
    for key, kind in zip(keys, kinds):
        start = zlib.crc32(f"bench/{key}".encode()) % 16
        assert (1 + (start + DRIVE) % 16 <= K) == (kind == "data")
    warm = heal.warm_up_keys("bench", keys, DRIVE, K, K + M,
                             heal.WARM_UP_OBJECTS)
    assert sorted(heal.data_or_parity("bench", k, DRIVE, K, K + M)
                  for k in warm) == ["data", "parity"]


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_019])
def test_sample_holds_both_kinds_in_their_shares(seed):
    mix, keys = _keys()
    objects = [(k, 64 << 20, 0) for k in keys]

    def kind(key):
        return heal.data_or_parity("bench", key, DRIVE, K, K + M)

    got = atrest.sample(objects, mix.at_rest_sample, seed, kind)
    assert len(got) == len(set(got)) == 12
    kinds = [kind(o[0]) for o in got]
    assert (kinds.count("data"), kinds.count("parity")) == (9, 3)
    assert got == atrest.sample(objects, 12, seed, kind)
    assert got != atrest.sample(objects, 12, seed + 1, kind)
    # Without a stratum the draw is the one every other cell gets.
    assert atrest.sample(objects, 12, seed) == \
        atrest.sample(objects, 12, seed, None)


def test_copies_missing_counts_absent_and_short_files(tmp_path):
    k, m, block = 4, 2, 1 << 16
    base = traffic.base_buffer(5, 200_000)
    objects = [("a", 1 << 16, 3), ("b", 150_001, 20), ("c", 40, 37)]
    for key, n, off in objects:
        drives = _write_rotated(str(tmp_path), key, base[off:off + n], k, m,
                                block)
    assert [atrest.copies_missing(d, "bench", objects, k, block)
            for d in drives] == [0] * 6
    part = os.path.join(drives[2], "bench", "b", "dd", "part.1")
    with open(part, "r+b") as f:
        f.truncate(os.path.getsize(part) - 1)
    os.remove(os.path.join(drives[2], "bench", "c", "xl.meta"))
    assert atrest.copies_missing(drives[2], "bench", objects, k, block) == 2
    assert reference.shard_file_bytes(64 << 20, 12, 10 << 20) == 5_592_634


# --- the summary's arithmetic -------------------------------------------------


def _item(name, disks=(6,), **extra):
    return dict({"object": name, "beforeOk": 15, "afterOk": 16,
                 "healedDisks": list(disks), "dangling": False}, **extra)


def _status(items, seen, status="done", t_end=None):
    return {"status": status, "error": "", "items": items, "seen": seen,
            "polls": 40, "t_end_s": seen[-1] if t_end is None else t_end}


def test_summary_of_a_whole_heal():
    sizes = {f"o{i}": 64 << 20 for i in range(4)}
    got = heal.summarize(_status([_item(k) for k in sizes],
                                 [0.5, 1.0, 1.5, 2.0]), sizes, DRIVE)
    assert (got["attempted"], got["failed"]) == (4, 0)
    assert got["user_bytes"] == 4 * (64 << 20)
    assert got["heal_s"] == 2.0 and got["span_s"] == 2.0
    assert got["heal_mibps"] == pytest.approx(128.0)
    assert got["object_ms"] == pytest.approx(500.0)
    assert got["per_5s"] == [[0, 4]]


def test_summary_counts_what_was_not_restored():
    sizes = {f"o{i}": 10 for i in range(6)}
    items = [_item("o0"), _item("o1", disks=()),        # nothing to heal
             _item("o2", disks=(3,)),                   # another drive
             _item("o3", dangling=True),
             _item("o4", skipped="lock timeout"),
             _item("elsewhere")]                        # not of the N
    got = heal.summarize(_status(items, [1, 2, 3, 4, 5, 6.5]), sizes, DRIVE)
    assert (got["attempted"], got["failed"]) == (6, 5)
    assert got["user_bytes"] == 10
    # Done, but not with every object healed: no time to recover.
    assert got["heal_s"] is None and got["heal_mibps"] is None
    assert got["per_5s"] == [[0, 4], [5, 2]]
    # A heal still running at the timeout, or one that failed.
    for status in ("running", "failed"):
        got = heal.summarize(_status([_item("o0")], [1.0], status, 120.0),
                             sizes, DRIVE)
        assert (got["failed"], got["heal_s"], got["span_s"]) == (5, None, 120.0)
        assert got["object_ms"] is None


def test_wait_stamps_each_item_with_the_poll_that_first_held_it():
    answers = iter([
        {"status": "running", "itemsScanned": 0, "items": []},
        {"status": "running", "itemsScanned": 2,
         "items": [_item("a"), _item("b")]},
        {"status": "done", "itemsScanned": 3, "error": "",
         "items": [_item("a"), _item("b"), _item("c")]}])
    import time
    t0 = time.monotonic()
    got = heal.wait(lambda *a: next(answers), "tok", t0, 0.01, 5.0)
    assert (got["status"], got["polls"], len(got["seen"])) == ("done", 3, 3)
    assert got["seen"][0] == got["seen"][1] < got["seen"][2] == got["t_end_s"]
    assert heal.healed(got["items"], DRIVE) == {"a", "b", "c"}


def test_checks_count_a_sweep_that_raced_and_a_child_that_cannot_say(
        tmp_path):
    sweeps = [{"monitors": 1, "sweeps": 3}, {"monitors": 1, "sweeps": 4}]
    config = {"data": 4, "block_size": 1 << 16}
    got = heal.checks(sweeps, 2, str(tmp_path), "bench", [("a", 40, 0)],
                      config)
    assert got == {"healed_copies_missing": [1, 0],
                   "heal_items_unhealed": [2, 0],
                   "heal_sweeps_raced": [1, 0]}
    sweeps[1] = {"cmd": "newdisk_sweeps", "error": "no answer"}
    assert heal.checks(sweeps, 0, str(tmp_path), "bench", [], config)[
        "heal_sweeps_raced"] == [-1, 0]


# --- the control: the reference rebuilds the drive, a guarantee broken --------


def _write_rotated(root, key, body, k, m, block):
    """The drive tree a sound PUT leaves, made by the reference, shard
    indices rotated over the drives as the reference rotates them."""
    blocks = reference.shard_blocks(body, k, m, block)
    files = reference.shard_files(blocks, reference.digests_for([blocks])[0])
    order = reference.shard_index_on_drives("bench", key, k + m)
    drives = []
    for i, idx in enumerate(order, start=1):
        d = os.path.join(root, f"d{i}")
        drives.append(d)
        base = os.path.join(d, "bench", key)
        os.makedirs(os.path.join(base, "dd"), exist_ok=True)
        with open(os.path.join(base, "xl.meta"), "w") as f:
            json.dump({"versions": [{"dataDir": "dd",
                                     "erasure": {"index": idx}}]}, f)
        with open(os.path.join(base, "dd", "part.1"), "wb") as f:
            f.write(files[idx - 1])
    return drives


def rebuilt_shards(drives, key, size, k, m, block, drive, broken=None):
    """Drive `drive`'s shard of the object, stripe block by stripe
    block, rebuilt as a heal rebuilds it, by the reference: the first k
    other shard files (by index) read from the drives, frames stripped,
    the data solved for and the lost shard made from it. `broken`
    "cauchy_parity": the rebuild alone takes the code for another
    construction's (the survivors are sound)."""
    order = reference.shard_index_on_drives("bench", key, k + m)
    lost = order[drive - 1]
    have = {}
    for i, d in enumerate(drives):
        if i != drive - 1 and len(have) < k:
            idx, data = atrest._stored(d, "bench", key, 1)
            have[idx - 1] = data
    keep = sorted(have)[:k]
    parity = cauchy_rows(k, m) if broken == "cauchy_parity" \
        else reference.parity_rows(k, m)
    full = [[int(i == j) for j in range(k)] for i in range(k)] + parity
    inv = reference._mat_inv([full[i] for i in keep])
    want = [0] * k
    for c in range(k):                # row `lost` of full @ inv
        for t in range(k):
            want[c] ^= reference.gf_mul(full[lost - 1][t], inv[t][c])
    out, pos = [], 0
    for lo in range(0, size, block):
        n = -(-min(block, size - lo) // k)
        shard = np.zeros(n, np.uint8)
        for c, idx in enumerate(keep):
            if want[c]:
                shard ^= reference._mul_table(want[c])[
                    np.frombuffer(have[idx], np.uint8, n, pos + 32)]
        pos += 32 + n
        out.append(shard)
    return out


def write_rebuilt(drives, drive, shards_of, broken=None):
    """Frame every object's rebuilt shard ([digest][sub-block] a stripe
    block) and write it as drive `drive`'s part file; equal-length
    sub-blocks of all the objects are hashed side by side. `broken`
    "zero_key_digest" / "short_digest": the digests alone."""
    key = b"\0" * 32 if broken == "zero_key_digest" else reference.BITROT_KEY
    by_len: dict = {}
    for name, shards in shards_of.items():
        for b, shard in enumerate(shards):
            by_len.setdefault(len(shard), []).append((name, b))
    digest = {}
    for n, members in by_len.items():
        digs = reference.hh256_rows(
            np.stack([shards_of[name][b] for name, b in members]), key)
        if broken == "short_digest":
            digs[:, 8:] = 0
        digest.update(zip(members, digs))
    for name, shards in shards_of.items():
        with open(os.path.join(drives[drive - 1], "bench", name, "dd",
                               "part.1"), "wb") as f:
            for b, shard in enumerate(shards):
                f.write(digest[(name, b)].tobytes())
                f.write(shard.tobytes())


def read_control(tmp, seed, keys, sizes, k, m, block, drive, variants):
    """Per variant, what the at-rest comparison reads once the drive's
    copies were rebuilt with it; the sound tree is written once."""
    base = traffic.base_buffer(seed, max(sizes))
    objects = [(key, n, 17 * i + 3)
               for i, (key, n) in enumerate(zip(keys, sizes))]
    for key, n, off in objects:
        drives = _write_rotated(tmp, key, base[off:off + n], k, m, block)
    out = {}
    for broken in variants:
        write_rebuilt(drives, drive, {
            key: rebuilt_shards(drives, key, n, k, m, block, drive, broken)
            for key, n, _ in objects}, broken)
        got = atrest.check(drives, "bench", objects,
                           lambda n, off: memoryview(base)[off:off + n],
                           k, m, block)
        got["healed_copies_missing"] = atrest.copies_missing(
            drives[drive - 1], "bench", objects, k, block)
        got["healed_kinds"] = sorted(
            heal.data_or_parity("bench", key, drive, k, k + m)
            for key, _, _ in objects)
        out[broken] = got
    return out


SMALL = dict(keys=[f"main/c{i:03d}/k0000" for i in range(4)],
             sizes=[1 << 16, 150_001, 40, 70_000], k=4, m=2, block=1 << 16,
             drive=1)
VARIANTS = (None, "cauchy_parity", "zero_key_digest", "short_digest")


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_019])
def test_control_a_rebuild_with_a_guarantee_broken(tmp_path, seed):
    got = read_control(str(tmp_path), seed, variants=VARIANTS, **SMALL)
    # Drive 1 of 4+2 holds data of three of the four keys, parity of one.
    assert got[None]["healed_kinds"] == ["data", "data", "data", "parity"]
    sound = got[None]
    assert (sound["objects_checked"], sound["shard_files_checked"]) == (4, 24)
    assert [sound[n] for n in ("shard_files_missing", "shard_frames_differ",
                               "digest_frames_differ",
                               "healed_copies_missing")] == [0] * 4
    frames = 1 + 3 + 1 + 2       # stripe blocks of the four objects
    # A Cauchy rebuild from sound survivors: wrong whether the lost
    # shard was parity (another matrix's parity) or data (solved through
    # a parity row the survivors were not made with).
    assert got["cauchy_parity"]["shard_frames_differ"] == frames
    assert got["cauchy_parity"]["digest_frames_differ"] == frames
    for broken in ("zero_key_digest", "short_digest"):
        assert got[broken]["shard_frames_differ"] == 0
        assert got[broken]["digest_frames_differ"] == frames


if __name__ == "__main__":
    mode, seeds = sys.argv[1], sys.argv[2:]
    if mode == "notapplied":
        heal.empty_volume = heal.wipe = lambda root, bucket: None
        for seed in seeds:
            bench_run.main(["--workload", CELL, "--seed", seed,
                            "--seconds", "50", "--trace", "0",
                            "--tag", "notapplied"])
    elif mode == "control":
        import tempfile
        mix, keys = _keys()
        for seed in seeds:
            # The keys the run of this seed samples: 9 data, 3 parity.
            sampled = [o[0] for o in atrest.sample(
                [(k, 64 << 20, 0) for k in keys], mix.at_rest_sample,
                int(seed), lambda key: heal.data_or_parity(
                    "bench", key, DRIVE, K, K + M))]
            with tempfile.TemporaryDirectory(dir=os.environ.get(
                    "BENCH_CONTROL_DIR", os.path.join(
                        os.path.dirname(os.path.dirname(HERE)),
                        ".chip_smoke"))) as tmp:
                got = read_control(tmp, int(seed), sampled, [64 << 20] * 12,
                                   K, M, 10 << 20, DRIVE, VARIANTS)
            for broken, reading in got.items():
                print(json.dumps({"cell": CELL, "seed": int(seed),
                                  "control": broken, **reading}), flush=True)
    else:
        raise SystemExit(__doc__)
