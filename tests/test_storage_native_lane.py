"""A local drive's leg of a PUT has two lanes (storage/xl.py): a few
GIL-free native calls (native/fsops.cc) and the Python system calls they
batch, kept as the fallback and as the lane fault injection acts on.
After the same calls the two leave the same tree, name for name and byte
for byte, and raise the same typed errors."""

import os
import shutil

import pytest

from minio_tpu import native
from minio_tpu.faultinject import FAULTS
from minio_tpu.obs.metrics2 import METRICS2
from minio_tpu.storage import errors as serr
from minio_tpu.storage import xl
from minio_tpu.storage.metadata import (XL_META_FILE, ErasureInfo, FileInfo,
                                        ObjectPartInfo, XLMeta)
from minio_tpu.storage.xl import (INTENT_FILE, MINIO_META_BUCKET, TMP_PATH,
                                  XLStorage)

LANES = ("native", "python")
DD1 = "11111111-1111-4111-8111-111111111111"
DD2 = "22222222-2222-4222-8222-222222222222"
VID = "33333333-3333-4333-8333-333333333333"
INTENT = b'{"bucket": "b", "object": "o/k"}'


@pytest.fixture(autouse=True)
def _native_library_is_there():
    if native.get_lib() is None:
        pytest.skip("no native library on this box: one lane only")


def take(monkeypatch, lane: str) -> None:
    if lane == "python":
        monkeypatch.setattr(xl, "_native_lib", lambda: None)


def lane_count(op: str, lane: str) -> int:
    return METRICS2.get("minio_tpu_v2_disk_op_lane_total",
                        {"op": op, "lane": lane})


def fi_of(data_dir: str, size: int, version_id: str = "",
          mod_time: float = 1700000000.5, key: str = "o/k") -> FileInfo:
    return FileInfo(
        volume="b", name=key, version_id=version_id, data_dir=data_dir,
        size=size, mod_time=mod_time, metadata={"etag": "e" * 32},
        parts=[ObjectPartInfo(number=1, size=size, actual_size=size,
                              etag="e" * 32)],
        erasure=ErasureInfo(data_blocks=4, parity_blocks=2,
                            block_size=1 << 20, index=1,
                            distribution=[1, 2, 3, 4, 5, 6]))


def put_legs(disk: XLStorage, stage: str, data_dir: str, batches,
             version_id: str = "") -> None:
    """One drive's leg of a PUT as the engine drives it: the intent
    breadcrumb, the shard's batches, the commit."""
    tmp = f"{TMP_PATH}/{stage}"
    disk.append_file(MINIO_META_BUCKET, f"{tmp}/{INTENT_FILE}", INTENT)
    for b in batches:
        disk.append_file(MINIO_META_BUCKET, f"{tmp}/{data_dir}/part.1", b)
    size = sum(len(bytes(b)) for b in batches)
    disk.rename_data(MINIO_META_BUCKET, tmp,
                     fi_of(data_dir if batches else "", size, version_id),
                     "b", "o/k")


def first_put(disk):
    put_legs(disk, "s1", DD1, [b"a" * 1000])
    return {f"b/o/k/{DD1}/part.1": b"a" * 1000}, [DD1]


def overwrite_null(disk):
    put_legs(disk, "s1", DD1, [b"a" * 1000])
    put_legs(disk, "s2", DD2, [b"b" * 700])
    return {f"b/o/k/{DD2}/part.1": b"b" * 700}, [DD2]


def versioned_second(disk):
    put_legs(disk, "s1", DD1, [b"a" * 1000])
    put_legs(disk, "s2", DD2, [b"b" * 700], version_id=VID)
    return {f"b/o/k/{DD1}/part.1": b"a" * 1000,
            f"b/o/k/{DD2}/part.1": b"b" * 700}, [DD2, DD1]


def zero_byte(disk):
    put_legs(disk, "s1", DD1, [])
    return {}, [""]


def three_batches(disk):
    import numpy as np
    put_legs(disk, "s1", DD1,
             [b"a" * 300, bytearray(b"b" * 200),
              np.frombuffer(b"c" * 100, dtype=np.uint8)])
    return {f"b/o/k/{DD1}/part.1":
            b"a" * 300 + b"b" * 200 + b"c" * 100}, [DD1]


def stray_in_stage(disk):
    disk.append_file(MINIO_META_BUCKET, f"{TMP_PATH}/s1/stray/x.bin", b"x")
    put_legs(disk, "s1", DD1, [b"a" * 1000])
    return {f"b/o/k/{DD1}/part.1": b"a" * 1000}, [DD1]


SCENARIOS = [first_put, overwrite_null, versioned_second, zero_byte,
             three_batches, stray_in_stage]


def tree(root: str) -> dict:
    """Every name under `root`: a directory as None, a file as its
    bytes, xl.meta as its loaded versions."""
    out = {}
    for cur, dirs, files in os.walk(root):
        for d in dirs:
            out[os.path.relpath(os.path.join(cur, d), root)] = None
        for name in files:
            full = os.path.join(cur, name)
            with open(full, "rb") as f:
                raw = f.read()
            out[os.path.relpath(full, root)] = (
                XLMeta.load(raw).versions if name == XL_META_FILE else raw)
    return out


def run_on(monkeypatch, tmp_path, lane: str, scenario):
    with monkeypatch.context() as mp:
        take(mp, lane)
        disk = XLStorage(str(tmp_path / lane))
        disk.make_volume("b")
        return disk, scenario(disk)


@pytest.mark.parametrize("scenario", SCENARIOS, ids=lambda s: s.__name__)
@pytest.mark.parametrize("lane", LANES)
def test_leg_leaves_the_committed_tree(monkeypatch, tmp_path, lane,
                                       scenario):
    appends = lane_count("append_file", lane)
    renames = lane_count("rename_data", lane)
    disk, (files, data_dirs) = run_on(monkeypatch, tmp_path, lane, scenario)
    got = tree(disk.root)
    assert got[f"{MINIO_META_BUCKET}/{TMP_PATH}"] is None
    assert not [p for p in got
                if p.startswith(f"{MINIO_META_BUCKET}/{TMP_PATH}/")]
    held = {p: v for p, v in got.items()
            if v is not None and not p.endswith(XL_META_FILE)}
    assert held == files  # the new bytes, and no data dir freed too late
    assert [v["dataDir"] for v in got[f"b/o/k/{XL_META_FILE}"]] == data_dirs
    assert disk.read_version("b", "o/k").data_dir == data_dirs[0]
    assert lane_count("append_file", lane) > appends
    assert lane_count("rename_data", lane) > renames


@pytest.mark.parametrize("scenario", SCENARIOS, ids=lambda s: s.__name__)
def test_lanes_leave_identical_trees(monkeypatch, tmp_path, scenario):
    trees = [tree(run_on(monkeypatch, tmp_path, lane, scenario)[0].root)
             for lane in LANES]
    assert trees[0] == trees[1]
    assert any(v is not None for v in trees[0].values())


@pytest.mark.parametrize("lane", LANES)
def test_a_commit_reads_xl_meta_once_for_the_drive_monitor(
        monkeypatch, tmp_path, lane):
    """The commit's xl.meta read is a `read_all` of the drive on both
    lanes (the native one times it in C): the drive monitor's read
    class hears of a PUT-only drive either way."""
    def reads():
        return METRICS2.get("minio_tpu_v2_disk_op_duration_ms",
                            {"op": "read_all"})[1]
    before = reads()
    run_on(monkeypatch, tmp_path, lane, overwrite_null)
    assert reads() == before + 2


def staged(disk: XLStorage, data_dir: str = DD1) -> str:
    tmp = f"{TMP_PATH}/s1"
    disk.append_file(MINIO_META_BUCKET, f"{tmp}/{INTENT_FILE}", INTENT)
    disk.append_file(MINIO_META_BUCKET, f"{tmp}/{data_dir}/part.1",
                     b"a" * 10)
    return tmp


@pytest.mark.parametrize("lane", LANES)
def test_commit_into_a_removed_bucket_is_volume_not_found(
        monkeypatch, tmp_path, lane):
    take(monkeypatch, lane)
    disk = XLStorage(str(tmp_path / "d"))
    disk.make_volume("b")
    tmp = staged(disk)
    disk.delete_volume("b", force=True)
    with pytest.raises(serr.VolumeNotFound):
        disk.rename_data(MINIO_META_BUCKET, tmp, fi_of(DD1, 10), "b", "o/k")
    assert not os.path.exists(os.path.join(disk.root, "b"))


@pytest.mark.parametrize("lane", LANES)
def test_commit_of_a_missing_stage_is_file_not_found(
        monkeypatch, tmp_path, lane):
    take(monkeypatch, lane)
    disk = XLStorage(str(tmp_path / "d"))
    disk.make_volume("b")
    with pytest.raises(serr.FileNotFound):
        disk.rename_data(MINIO_META_BUCKET, f"{TMP_PATH}/none",
                         fi_of(DD1, 10), "b", "o/k")


@pytest.mark.parametrize("gone", ["object_dir", "volume"])
@pytest.mark.parametrize("lane", LANES)
def test_object_dir_pruned_between_the_calls(monkeypatch, tmp_path, lane,
                                             gone):
    """A racing delete takes the object directory (or the whole
    bucket) after the data dir is in and before xl.meta is written: the
    write recreates the directory below the re-checked volume and
    retries once, or answers VolumeNotFound; it never resurrects a
    bucket."""
    take(monkeypatch, lane)
    disk = XLStorage(str(tmp_path / "d"))
    disk.make_volume("b")
    tmp = staged(disk)
    victim = os.path.join(disk.root, "b" if gone == "volume" else "b/o")

    # The same instant on both lanes: the data dir is in, xl.meta is
    # not written yet. The Python lane has a crash point there; the
    # native lane has the seam between its two calls.
    def race(name):
        if name == xl.CRASH_RENAME_MID:
            shutil.rmtree(victim)
    monkeypatch.setattr(xl.FAULTS, "crash_point", race)
    real_meta = native.fs_commit_meta

    def racing_meta(*a):
        shutil.rmtree(victim)
        return real_meta(*a)
    monkeypatch.setattr(native, "fs_commit_meta", racing_meta)
    if gone == "volume":
        with pytest.raises(serr.VolumeNotFound):
            disk.rename_data(MINIO_META_BUCKET, tmp, fi_of(DD1, 10),
                             "b", "o/k")
        assert not os.path.exists(victim)
    else:
        disk.rename_data(MINIO_META_BUCKET, tmp, fi_of(DD1, 10), "b", "o/k")
        assert disk.read_version("b", "o/k").data_dir == DD1


@pytest.mark.parametrize("lane", LANES)
def test_append_below_a_regular_file_is_faulty_disk(monkeypatch, tmp_path,
                                                    lane):
    take(monkeypatch, lane)
    disk = XLStorage(str(tmp_path / "d"))
    disk.make_volume("b")
    disk.write_all("b", "plain", b"x")
    with pytest.raises(serr.FaultyDisk):
        disk.append_file("b", "plain/part.1", b"y")
    with pytest.raises(serr.VolumeNotFound):
        disk.append_file("nobucket", "k/part.1", b"y")
    assert not os.path.exists(os.path.join(disk.root, "nobucket"))


@pytest.mark.parametrize("lane", LANES)
def test_source_volume_gone_at_rename_time_is_its_volume_not_found(
        monkeypatch, tmp_path, lane):
    """The native lane does not look at the source volume before it
    renames; the refused rename (ENOENT) is told apart, and a source
    volume that is gone is named, as the Python lane's check names it."""
    take(monkeypatch, lane)
    disk = XLStorage(str(tmp_path / "d"))
    disk.make_volume("b")
    disk.make_volume(STAGE_VOL)
    stage_outside(disk, DD1, b"a" * 10)
    disk.delete_volume(STAGE_VOL, force=True)
    with pytest.raises(serr.VolumeNotFound, match=STAGE_VOL):
        disk.rename_data(STAGE_VOL, "s2", fi_of(DD1, 10), "b", "o/k")
    assert not os.path.exists(os.path.join(disk.root, STAGE_VOL))
    with pytest.raises(serr.FileNotFound):
        disk.read_version("b", "o/k")


@pytest.mark.parametrize("data_dir", [DD1, ""], ids=["data_dir", "no_data"])
@pytest.mark.parametrize("lane", LANES)
def test_commit_below_a_regular_file_is_faulty_disk(monkeypatch, tmp_path,
                                                    lane, data_dir):
    """A regular file holds the object directory's name: the mkdir's
    EEXIST is not looked into, the next call (the rename, or the open
    of xl.meta where the version has no data dir) says ENOTDIR, which
    is the drive's fault as it was; file and stage stay."""
    take(monkeypatch, lane)
    disk = XLStorage(str(tmp_path / "d"))
    disk.make_volume("b")
    disk.write_all("b", "o/k", b"squatter")
    tmp = staged(disk)
    with pytest.raises(serr.FaultyDisk):
        disk.rename_data(MINIO_META_BUCKET, tmp,
                         fi_of(data_dir, 10 if data_dir else 0), "b", "o/k")
    assert disk.read_all("b", "o/k") == b"squatter"
    assert disk.read_all(MINIO_META_BUCKET,
                         f"{tmp}/{DD1}/part.1") == b"a" * 10
    assert disk.read_all(MINIO_META_BUCKET,
                         f"{tmp}/{INTENT_FILE}") == INTENT


@pytest.mark.parametrize("lane", LANES)
def test_a_staged_data_dir_that_is_a_file_is_file_not_found(
        monkeypatch, tmp_path, lane):
    """rename(2) would move a regular file in as gladly as a directory:
    the stage's data dir is asked to be one (a trailing slash on the
    native lane, a stat on the Python one)."""
    take(monkeypatch, lane)
    disk = XLStorage(str(tmp_path / "d"))
    disk.make_volume("b")
    tmp = f"{TMP_PATH}/s1"
    disk.append_file(MINIO_META_BUCKET, f"{tmp}/{INTENT_FILE}", INTENT)
    disk.append_file(MINIO_META_BUCKET, f"{tmp}/{DD1}", b"not a directory")
    with pytest.raises(serr.FileNotFound):
        disk.rename_data(MINIO_META_BUCKET, tmp, fi_of(DD1, 10), "b", "o/k")
    assert not os.path.exists(os.path.join(disk.root, "b/o/k", DD1))
    assert disk.read_all(MINIO_META_BUCKET,
                         f"{tmp}/{DD1}") == b"not a directory"


@pytest.mark.parametrize("lane", LANES)
def test_an_old_data_dir_with_a_stray_file_is_removed_whole(
        monkeypatch, tmp_path, lane):
    """The freed data dir goes by the names its version listed; what
    else is in it (rmdir: ENOTEMPTY) takes the walk, so nothing of it
    stays."""
    take(monkeypatch, lane)
    disk = XLStorage(str(tmp_path / "d"))
    disk.make_volume("b")
    put_legs(disk, "s1", DD1, [b"a" * 1000])
    disk.write_all("b", f"o/k/{DD1}/stray.bin", b"x")
    disk.write_all("b", f"o/k/{DD1}/sub/deep.bin", b"y")
    put_legs(disk, "s2", DD2, [b"b" * 700])
    assert sorted(os.listdir(os.path.join(disk.root, "b/o/k"))) \
        == sorted([DD2, XL_META_FILE])
    assert disk.read_all("b", f"o/k/{DD2}/part.1") == b"b" * 700


@pytest.mark.parametrize("lane", LANES)
def test_large_xl_meta_is_merged_whole(monkeypatch, tmp_path, lane):
    """An xl.meta past the native read buffer (many versions) is read by
    the caller and merged all the same."""
    take(monkeypatch, lane)
    monkeypatch.setattr(native, "_FS_META_CAP", 256)
    monkeypatch.setattr(native, "_FS_TLS", type(native._FS_TLS)())
    disk = XLStorage(str(tmp_path / "d"))
    disk.make_volume("b")
    put_legs(disk, "s1", DD1, [b"a" * 10], version_id=VID)
    put_legs(disk, "s2", DD2, [b"b" * 10])
    assert [v.data_dir for v in disk.read_versions("b", "o/k")] == [DD2, DD1]


# --- the calls a leg costs, from the counter native/fsops.cc feeds

def syscalls(op: str) -> int:
    return METRICS2.get("minio_tpu_v2_disk_op_syscalls_total",
                        {"op": op}) or 0


def counted_leg(disk: XLStorage, stage: str, data_dir: str, body: bytes,
                key: str) -> tuple[list[int], int]:
    """One drive's leg of a PUT of `key`: the system calls of each
    append and of the commit."""
    tmp = f"{TMP_PATH}/{stage}"
    costs = []
    for path, data in ((f"{tmp}/{INTENT_FILE}", INTENT),
                       (f"{tmp}/{data_dir}/part.1", body)):
        before = syscalls("append_file")
        disk.append_file(MINIO_META_BUCKET, path, data)
        costs.append(syscalls("append_file") - before)
    before = syscalls("rename_data")
    disk.rename_data(MINIO_META_BUCKET, tmp,
                     fi_of(data_dir, len(body), key=key), "b", key)
    return costs, syscalls("rename_data") - before


def fresh_key(disk):
    return "k"


def overwrite(disk):
    counted_leg(disk, "s0", DD1, b"a" * 1000, "k")
    return "k"


def retried_commit(disk):
    # A first try moved its data dir in and failed before xl.meta: the
    # retry stages the same data dir again and finds the name taken.
    disk.write_all("b", f"k/{DD2}/part.1", b"first try")
    return "k"


def nested_prefix(disk):
    return "a/b/c/k"


# (scenario, most calls an append, most calls a commit): a fresh commit
# is mkdir + rename, then tmp open / write / close / rename and the
# stage's unlink + rmdir; an overwrite adds the xl.meta read (open,
# read, read, close) and the old data dir's unlink + rmdir. The slow
# paths (a walk over what holds the name; a prefix chain to build below
# the checked volume) may cost more.
BUDGETS = [(fresh_key, 5, 2 + 6), (overwrite, 5, 6 + 8),
           (retried_commit, 5, None), (nested_prefix, 5, None)]


@pytest.mark.parametrize("scenario, per_append, per_commit", BUDGETS,
                         ids=lambda v: getattr(v, "__name__", None))
def test_a_leg_keeps_its_call_budget(tmp_path, scenario, per_append,
                                     per_commit):
    """A leg acts first and checks on failure: no stat before a mkdir,
    a rename or an open, no listing to find a file xl.meta named."""
    disk = XLStorage(str(tmp_path / "d"))
    disk.make_volume("b")
    key = scenario(disk)
    appends, commit = counted_leg(disk, "s1", DD2, b"b" * 700, key)
    assert all(0 < n <= per_append for n in appends), appends
    assert commit > 0
    if per_commit is not None:
        assert commit <= per_commit
    got = tree(disk.root)
    assert {p: v for p, v in got.items()
            if v is not None and not p.endswith(XL_META_FILE)} \
        == {f"b/{key}/{DD2}/part.1": b"b" * 700}
    assert [v["dataDir"] for v in got[f"b/{key}/{XL_META_FILE}"]] == [DD2]
    assert not [p for p in got
                if p.startswith(f"{MINIO_META_BUCKET}/{TMP_PATH}/")]


def test_a_later_append_of_a_stream_is_three_calls(tmp_path):
    """Its directory is there: open, write, close."""
    disk = XLStorage(str(tmp_path / "d"))
    path = f"{TMP_PATH}/s1/{DD1}/part.1"
    disk.append_file(MINIO_META_BUCKET, path, b"a" * 10)
    before = syscalls("append_file")
    disk.append_file(MINIO_META_BUCKET, path, b"b" * 10)
    assert syscalls("append_file") - before == 3
    assert disk.read_all(MINIO_META_BUCKET, path) == b"a" * 10 + b"b" * 10


def test_the_python_lane_counts_no_native_calls(monkeypatch, tmp_path):
    take(monkeypatch, "python")
    before = syscalls("append_file"), syscalls("rename_data")
    run_on(monkeypatch, tmp_path, "python", overwrite_null)
    assert (syscalls("append_file"), syscalls("rename_data")) == before


def lane_counts() -> dict:
    return {(op, lane): lane_count(op, lane) for lane in LANES
            for op in ("append_file", "rename_data")}


def arm_a_fault_plan():
    FAULTS.load_plan({"rules": [{"kind": "latency", "target": "/nowhere",
                                 "op": "read_file", "latency_ms": 1}]})
    return FAULTS.clear


def turn_fsync_on():
    xl.set_fsync(True)
    return lambda: xl.set_fsync(False)


@pytest.mark.parametrize("condition", [arm_a_fault_plan, turn_fsync_on],
                         ids=lambda c: c.__name__)
def test_a_leg_that_may_wait_or_be_faulted_takes_the_python_lane(
        tmp_path, condition):
    """An armed fault plan (injected latency, errors, torn writes and
    crash points act on the Python system calls) and `storage fsync=on`
    (the fsyncs are commit_replace's; a leg that waits for the device
    gains nothing from fewer GIL round trips) take the Python lane for
    the whole operation, and the native lane comes back afterwards."""
    disk = XLStorage(str(tmp_path / "d"))
    disk.make_volume("b")
    before = lane_counts()
    undo = condition()
    try:
        put_legs(disk, "s1", DD1, [b"a" * 10])
        put_legs(disk, "s2", DD2, [b"b" * 7])
    finally:
        undo()
    during = lane_counts()
    assert during[("append_file", "python")] \
        == before[("append_file", "python")] + 4
    assert during[("rename_data", "python")] \
        == before[("rename_data", "python")] + 2
    assert during[("append_file", "native")] \
        == before[("append_file", "native")]
    assert during[("rename_data", "native")] \
        == before[("rename_data", "native")]
    assert disk.read_all("b", f"o/k/{DD2}/part.1") == b"b" * 7
    assert not os.path.exists(os.path.join(disk.root, "b/o/k", DD1))
    put_legs(disk, "s3", DD1, [b"c" * 5])
    assert lane_count("rename_data", "native") \
        == during[("rename_data", "native")] + 1


# --- a commit that FAILS part way, from file-system state alone (no
# fault plan: that would take the Python lane for both). Each case
# holds both lanes to one assertion: what was acknowledged before is
# still what a read finds, and the stage is kept for the caller.

STAGE_VOL = "stagevol"  # a stage outside .minio.sys/tmp, so that the
#                         tmp directory itself can be broken


def stage_outside(disk: XLStorage, data_dir: str, body: bytes) -> None:
    disk.append_file(STAGE_VOL, f"s2/{INTENT_FILE}", INTENT)
    disk.append_file(STAGE_VOL, f"s2/{data_dir}/part.1", body)


@pytest.mark.parametrize("lane", LANES)
def test_a_failed_xl_meta_write_keeps_the_old_version_whole(
        monkeypatch, tmp_path, lane):
    """The second half of a commit fails (the temporary for xl.meta
    cannot be made: .minio.sys/tmp is a regular file): the new data dir
    is already in the object directory, but xl.meta still names the old
    one, whose shards are still there: the old data dir is freed only
    AFTER the new xl.meta is in place."""
    take(monkeypatch, lane)
    disk = XLStorage(str(tmp_path / "d"))
    disk.make_volume("b")
    disk.make_volume(STAGE_VOL)
    put_legs(disk, "s1", DD1, [b"a" * 1000])
    meta_before = disk.read_all("b", f"o/k/{XL_META_FILE}")
    stage_outside(disk, DD2, b"b" * 700)
    sys_tmp = os.path.join(disk.root, MINIO_META_BUCKET, TMP_PATH)
    shutil.rmtree(sys_tmp)
    with open(sys_tmp, "wb") as f:
        f.write(b"not a directory")
    with pytest.raises(serr.FaultyDisk):
        disk.rename_data(STAGE_VOL, "s2", fi_of(DD2, 700), "b", "o/k")
    assert disk.read_all("b", f"o/k/{XL_META_FILE}") == meta_before
    assert disk.read_version("b", "o/k").data_dir == DD1
    assert disk.read_all("b", f"o/k/{DD1}/part.1") == b"a" * 1000
    # The stage is the caller's to clean (the engine's cleanup_tmp):
    # its breadcrumb is what a restart's sweep reads.
    assert disk.read_all(STAGE_VOL, f"s2/{INTENT_FILE}") == INTENT
    # With the directory back a retry of the PUT (staged anew: the
    # first try's data dir moved in above, and is replaced) goes
    # through, and only now is the old data dir freed.
    os.remove(sys_tmp)
    os.makedirs(sys_tmp)
    stage_outside(disk, DD2, b"b" * 700)
    disk.rename_data(STAGE_VOL, "s2", fi_of(DD2, 700), "b", "o/k")
    assert disk.read_version("b", "o/k").data_dir == DD2
    assert disk.read_all("b", f"o/k/{DD2}/part.1") == b"b" * 700
    assert not os.path.exists(os.path.join(disk.root, "b/o/k", DD1))


@pytest.mark.parametrize("lane", LANES)
def test_a_failed_data_dir_move_changes_nothing_visible(
        monkeypatch, tmp_path, lane):
    """The first half of a commit fails (a regular file holds the data
    dir's name in the object directory, so the rename is refused):
    xl.meta is untouched, the old shards are there, and the staged
    shards are still in the stage."""
    take(monkeypatch, lane)
    disk = XLStorage(str(tmp_path / "d"))
    disk.make_volume("b")
    put_legs(disk, "s1", DD1, [b"a" * 1000])
    meta_before = disk.read_all("b", f"o/k/{XL_META_FILE}")
    disk.write_all("b", f"o/k/{DD2}", b"squatter")
    tmp = f"{TMP_PATH}/s2"
    disk.append_file(MINIO_META_BUCKET, f"{tmp}/{INTENT_FILE}", INTENT)
    disk.append_file(MINIO_META_BUCKET, f"{tmp}/{DD2}/part.1", b"b" * 700)
    with pytest.raises(serr.FaultyDisk):
        disk.rename_data(MINIO_META_BUCKET, tmp, fi_of(DD2, 700),
                         "b", "o/k")
    assert disk.read_all("b", f"o/k/{XL_META_FILE}") == meta_before
    assert disk.read_all("b", f"o/k/{DD1}/part.1") == b"a" * 1000
    assert disk.read_all("b", f"o/k/{DD2}") == b"squatter"
    assert disk.read_all(MINIO_META_BUCKET,
                         f"{tmp}/{DD2}/part.1") == b"b" * 700
    assert disk.read_all(MINIO_META_BUCKET,
                         f"{tmp}/{INTENT_FILE}") == INTENT


@pytest.mark.parametrize("lane", LANES)
def test_a_corrupt_xl_meta_stops_the_commit_before_it_is_replaced(
        monkeypatch, tmp_path, lane):
    """Between the two halves: xl.meta does not load. The commit stops
    typed, the unreadable file is left for heal as it was, and the
    stage's breadcrumb stays."""
    take(monkeypatch, lane)
    disk = XLStorage(str(tmp_path / "d"))
    disk.make_volume("b")
    put_legs(disk, "s1", DD1, [b"a" * 1000])
    disk.write_all("b", f"o/k/{XL_META_FILE}", b"\x00garbage")
    tmp = staged(disk, DD2)
    with pytest.raises(serr.FileCorrupt):
        disk.rename_data(MINIO_META_BUCKET, tmp, fi_of(DD2, 10), "b", "o/k")
    assert disk.read_all("b", f"o/k/{XL_META_FILE}") == b"\x00garbage"
    assert disk.read_all("b", f"o/k/{DD1}/part.1") == b"a" * 1000
    assert disk.read_all(MINIO_META_BUCKET,
                         f"{tmp}/{INTENT_FILE}") == INTENT


# --- through the engine, under threads

def six_drive_engine(tmp_path):
    from minio_tpu.erasure.engine import ErasureObjects
    eng = ErasureObjects(
        [XLStorage(str(tmp_path / f"d{i}")) for i in range(6)], 4, 2)
    eng.make_bucket("bkt")
    return eng


def test_a_dead_drive_costs_one_drive_of_the_write_quorum(tmp_path,
                                                          monkeypatch):
    """Drive 3 of 6 fails its shard appends: the PUT succeeds at write
    quorum on five and the dead drive keeps no stage; three dead drives
    are under write quorum (4)."""
    from minio_tpu.parallel.quorum import QuorumError
    eng = six_drive_engine(tmp_path)

    def break_appends(disk):
        real = disk.append_file

        def broken(volume, path, data):
            if path.endswith("/part.1"):
                raise serr.FaultyDisk("injected")
            return real(volume, path, data)
        monkeypatch.setattr(disk, "append_file", broken)
    try:
        bad = eng.disks[3]
        break_appends(bad)
        body = os.urandom(300_000)
        eng.put_object("bkt", "k", body)
        data, _ = eng.get_object("bkt", "k")
        assert bytes(data) == body
        assert not os.path.exists(os.path.join(bad.root, "bkt", "k"))
        # (the stage's delete prunes an emptied tmp/ too; it self-creates)
        tmp = os.path.join(bad.root, MINIO_META_BUCKET, TMP_PATH)
        assert not os.path.exists(tmp) or os.listdir(tmp) == []
        for d in eng.disks[:3] + eng.disks[4:]:
            assert os.path.exists(
                os.path.join(d.root, "bkt", "k", XL_META_FILE))
        for d in eng.disks[:2]:
            break_appends(d)
        with pytest.raises(QuorumError):
            eng.put_object("bkt", "k2", body)
    finally:
        eng.shutdown()


def test_twenty_clients_put_and_get_on_the_native_lane(tmp_path):
    import threading
    eng = six_drive_engine(tmp_path)
    before = lane_counts()
    wrong = []

    def client(c):
        try:
            for i in range(20):
                body = bytes([c, i]) * 32768
                key = f"c{c}/k{i % 4}"  # overwrites from the fifth on
                eng.put_object("bkt", key, body)
                data, _ = eng.get_object("bkt", key)
                if bytes(data) != body:
                    wrong.append((c, i))
        except BaseException as e:  # noqa: BLE001 - reported below
            wrong.append((c, repr(e)))

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(20)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    try:
        assert not any(t.is_alive() for t in threads)
        assert wrong == []
        for d in eng.disks:
            assert os.listdir(os.path.join(d.root, MINIO_META_BUCKET,
                                           TMP_PATH)) == []
            # One data dir an object: every overwrite freed the old one.
            for c in range(20):
                for k in range(4):
                    obj = os.path.join(d.root, "bkt", f"c{c}", f"k{k}")
                    assert len(os.listdir(obj)) == 2, os.listdir(obj)
        after = lane_counts()
        assert after[("rename_data", "native")] \
            >= before[("rename_data", "native")] + 400 * 6
        assert after[("rename_data", "python")] \
            == before[("rename_data", "python")]
    finally:
        eng.shutdown()
