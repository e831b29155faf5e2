"""XLStorage — local POSIX StorageAPI (ref cmd/xl-storage.go).

On-disk layout per disk root (same shape as the reference):

    <root>/.minio.sys/tmp/<uuid>/...       staging for in-flight writes
    <root>/<bucket>/<object>/xl.meta       version metadata (JSON, metadata.py)
    <root>/<bucket>/<object>/<dataDir>/part.N   bitrot-wrapped shard files

Writes are crash-safe: tmp file + atomic replace (the reference's
reliable-rename pattern, cmd/os-reliable.go); object commit is
rename_data (ref cmd/xl-storage.go:1972). Every commit-path replace
goes through ONE blessed helper, :func:`commit_replace` (enforced by
mtpu-lint R7): by default it is fsync-less (page-cache crash window,
like the reference's default), and the ``storage fsync=on`` config-KV
knob routes the same helper through fsync-file + fsync-parent-dir for
power-cut durability at a measured latency cost (docs/robustness.md).

Crash consistency is TESTED, not assumed: rename_data hosts named
crash points (minio_tpu/faultinject crash kind) at the torn-state
boundaries — before the data-dir replace, between the replace and the
xl.meta merge, and after the meta write — which the subprocess harness
(tests/test_crash_consistency.py) arms to kill -9 the server
mid-commit and assert the restart invariants.
"""

from __future__ import annotations

import errno
import os
import shutil
import stat
import time
import uuid

from . import errors as serr
from .interface import StorageAPI
from .metadata import XL_META_FILE, FileInfo, XLMeta
from .. import native
from ..erasure import bitrot
from ..faultinject import FAULTS
from ..obs.drivemon import DRIVEMON, is_drive_fault

# Named crash points on the per-disk commit (rename_data) — the three
# windows a process death leaves distinguishable on-disk state. The
# crash harness arms these with `after` counts to land the kill
# BETWEEN disks of one quorum fan-out.
CRASH_RENAME_PRE = FAULTS.register_crash_point(
    "xl.rename_data.pre_replace")
CRASH_RENAME_MID = FAULTS.register_crash_point(
    "xl.rename_data.post_replace")
CRASH_RENAME_POST = FAULTS.register_crash_point(
    "xl.rename_data.post_meta")
from ..obs.metrics2 import METRICS2
from ..obs.span import TRACER, current_span


class _Seam:
    """What a storage call does when it FAILS, and nothing when it
    does not: no error of the operating system leaves XLStorage as it
    came. An OSError that nothing inside the call resolved is typed
    (`XLStorage._os_fault`): DiskNotFound where the drive's ROOT is
    not a directory that answers (replaced by a file, gone, EIO), the
    drive's own fault otherwise. A local DiskNotFound is evidence
    against the DRIVE (for a remote drive it is the transport's, which
    is why `is_drive_fault` leaves it out): the monitor hears of it as
    an error, the request's tree gets a `drive.offline` event.

    The calls the data plane times are `_DiskOp`s below; the others
    (volumes, listings, renames of flat files) stand in this class
    itself, which costs a healthy call one `with`."""

    __slots__ = ("op", "_disk")

    def __init__(self, op: str, disk: "XLStorage"):
        self.op = op
        self._disk = disk

    def __enter__(self):
        return self

    def __exit__(self, et, exc, tb):
        if et is not None:
            typed, fault = self._settle(et, exc)
            if fault:
                DRIVEMON.record(self._disk.root, self.op, 0.0, error=True)
            if typed is not exc:
                raise typed from exc
        return False

    def _settle(self, et, exc) -> tuple[BaseException, bool]:
        """(the exception as it leaves the seam, whether the drive
        monitor counts the call against the drive)."""
        raw = issubclass(et, OSError)
        typed = self._disk._os_fault(exc) if raw else exc
        if isinstance(typed, serr.DiskNotFound):
            _note_offline(self._disk.root, self.op, typed)
            return typed, True
        # With the root standing the monitor judges what it judged
        # before the seam typed it: the errno itself (the ENOENT family
        # is a namespace miss), or the errno a typed error was raised
        # `from`.
        return typed, is_drive_fault(
            exc if raw else typed.__cause__ or typed)


def _note_offline(root: str, op: str, err: serr.DiskNotFound) -> None:
    """A leg dropped because its drive is not there: the event on the
    request's tree (no-op when untraced)."""
    span = current_span()
    if span is not None:
        span.add_event("drive.offline", drive=root, op=op,
                       errno=getattr(err, "errno", 0))


class _DiskOp(_Seam):
    """Per-disk-call instrumentation: a child span on the active trace
    (no-op when untraced), the metrics-v2 disk-op histogram, AND the
    drive-health monitor's per-drive latency/error accounting — the
    per-disk attribution layer of the request trace (the reference's
    storage layer exports xl_storage api latencies the same way in
    cmd/metrics-v2.go; per-drive health in pkg/smart / admin obd).
    A failed call leaves it typed, as `_Seam` says."""

    __slots__ = ("_cm", "_t0")

    def __init__(self, op: str, disk: "XLStorage"):
        super().__init__(op, disk)
        self._cm = TRACER.span("disk." + op, disk=disk.root)

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._cm.__enter__()
        # Fault-injection hook (minio_tpu/faultinject): injected
        # latency sleeps — and injected errors raise — INSIDE the
        # measured op window, exactly what a degraded physical drive
        # looks like to the monitor. A raise must still close the
        # span and feed the drive-health error accounting, so it is
        # routed through our own __exit__ before propagating.
        try:
            FAULTS.disk_op(self._disk.root, self.op)
        except BaseException as e:
            self.__exit__(type(e), e, e.__traceback__)
            raise
        return self

    def __exit__(self, et, exc, tb):
        typed, fault = (exc, False) if et is None else self._settle(et, exc)
        self._cm.__exit__(et, exc, tb)
        _account(self._disk.root, self.op,
                 (time.perf_counter() - self._t0) * 1e3, error=fault)
        if typed is not exc:
            raise typed from exc
        return False


def _account(root: str, op: str, ms: float, error: bool = False) -> None:
    """One disk op into the histogram and the drive-health monitor."""
    METRICS2.observe("minio_tpu_v2_disk_op_duration_ms", {"op": op}, ms)
    DRIVEMON.record(root, op, ms, error=error)


MINIO_META_BUCKET = ".minio.sys"
TMP_DIR = ".minio.sys/tmp"
# Staging prefix inside the MINIO_META_BUCKET volume (engine + healer
# share this single source of truth).
TMP_PATH = "tmp"
# Recovery breadcrumb the engine drops into each staging dir (tiny
# JSON: bucket/object/versionId/dataDir): after a crash, the boot
# recovery sweep (storage/recovery.py) reads it to requeue the object
# for heal before GC-ing the orphaned stage.
INTENT_FILE = "intent.json"

_RESERVED_VOLUMES = {MINIO_META_BUCKET}

# `storage fsync=on` (config-KV; env MINIO_STORAGE_FSYNC): when True,
# commit_replace fsyncs the source (each file of a staged data dir)
# and the destination's parent directory around the rename, closing
# the power-cut window the fsync-less default leaves open. Process-
# wide on purpose — durability is a deployment property, not a
# per-call one.
FSYNC = False


def set_fsync(on: bool) -> None:
    """Flip the commit-path fsync policy (config apply hook)."""
    global FSYNC
    FSYNC = bool(on)


def _fsync_fd_of(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_src(path: str) -> None:
    """Flush a commit source: a staged data DIR syncs each shard file
    then the dir entries; a plain file syncs itself."""
    if os.path.isdir(path):
        for entry in os.scandir(path):
            if entry.is_file(follow_symlinks=False):
                _fsync_fd_of(entry.path)
        _fsync_fd_of(path)
    else:
        _fsync_fd_of(path)


def commit_replace(src: str, dst: str) -> None:
    """The ONE blessed commit-path rename (mtpu-lint R7): every
    os.replace/os.rename under minio_tpu/storage/ must route here, so
    the fsync policy — and any future commit-ordering change — has a
    single choke point instead of N hand-synced call sites.
    FileNotFoundError propagates unchanged (callers resolve it into
    their typed volume/race conditions)."""
    if FSYNC:
        _fsync_src(src)
    # mtpu-lint: disable=R7 -- the blessed helper itself; every other replace routes here
    os.replace(src, dst)
    if FSYNC:
        _fsync_fd_of(os.path.dirname(dst))


def _native_lib():
    """native/fsops.cc when a drive's leg may run through it, chosen
    from what the process can see: the library is loaded, no fault plan
    is armed and `storage fsync=on` is off. Else None: the Python lane,
    which is where injected latency, errors, torn writes and crash
    points act and where the fsyncs are (commit_replace)."""
    return None if FAULTS.enabled or FSYNC else native.get_lib()


def _fs_lane(op: str):
    """The lane one append_file / rename_data call takes (`_native_lib`
    or None for the Python lane), counted once a call."""
    lib = _native_lib()
    METRICS2.inc("minio_tpu_v2_disk_op_lane_total",
                 {"op": op, "lane": "python" if lib is None else "native"})
    return lib


def _count_syscalls(op: str, calls: int) -> None:
    """The system calls one native call made (native/fsops.cc counts
    them, nothing is timed): over `disk_op_duration_ms_count{op}` they
    are the file-system calls a leg costs."""
    METRICS2.inc("minio_tpu_v2_disk_op_syscalls_total", {"op": op}, calls)


# read_all's first read: an xl.meta, a part record or an upload's
# record fits it many times over, and it is under malloc's mmap
# threshold, so the buffer costs no system call of its own.
_READ_FIRST = 64 * 1024


def _read_whole(full: str) -> bytes:
    """A whole file in the fewest calls its size allows: open, read,
    the read that says "no more", close (a file past `_READ_FIRST` is
    sized once and its rest read in one piece). The builtin `open` +
    `read` makes eight for the same bytes (two fstats, an isatty ioctl
    and an lseek among them), each a release of the GIL that has to be
    won back from the process's other threads and, on a network mount,
    most of them a round trip."""
    fd = os.open(full, os.O_RDONLY | os.O_CLOEXEC)
    try:
        chunks = [os.read(fd, _READ_FIRST)]
        if len(chunks[0]) == _READ_FIRST:
            rest = os.fstat(fd).st_size - _READ_FIRST
            chunks.append(os.read(fd, max(rest, 0) + 1))
        while chunks[-1]:
            chunks.append(os.read(fd, _READ_FIRST))
        return chunks[0] if len(chunks) == 2 else b"".join(chunks)
    finally:
        os.close(fd)


def _read_range(full: str, offset: int, length: int) -> bytes:
    """`length` bytes at `offset` (fewer only where the file ends):
    open, ONE pread where the range is inside the file, close."""
    fd = os.open(full, os.O_RDONLY | os.O_CLOEXEC)
    try:
        data = os.pread(fd, length, offset)
        while 0 < len(data) < length:
            more = os.pread(fd, length - len(data), offset + len(data))
            if not more:
                break
            data += more
        return data
    finally:
        os.close(fd)


def _is_valid_volume(volume: str) -> bool:
    return (volume not in ("", ".", "..") and "/" not in volume
            and "\\" not in volume)


class XLStorage(StorageAPI):
    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.disk_id = ""
        self._sys_tmp = os.path.join(self.root, TMP_DIR)
        os.makedirs(self._sys_tmp, exist_ok=True)

    def __repr__(self) -> str:
        return f"XLStorage({self.root})"

    # --- path helpers ---

    def _vol_path(self, volume: str) -> str:
        if not _is_valid_volume(volume) and volume != MINIO_META_BUCKET:
            raise serr.VolumeNotFound(volume)
        return os.path.join(self.root, volume)

    def _file_path(self, volume: str, path: str) -> str:
        base = self._vol_path(volume)
        full = os.path.normpath(os.path.join(base, path))
        if not full.startswith(base + os.sep) and full != base:
            raise serr.FileNotFound(path)  # path traversal
        return full

    def _check_vol(self, volume: str) -> str:
        p = self._vol_path(volume)
        if not os.path.isdir(p):
            # "The bucket is not there" and "the drive is not there" are
            # different statements (the first feeds the bucket-not-found
            # quorum): the root is asked before the volume is blamed.
            gone = self._offline()
            if gone is not None:
                raise gone
            if volume == MINIO_META_BUCKET:
                # The system volume self-creates (a freshly swapped disk
                # must accept heal writes immediately).
                self._make_sys_tmp()
                return p
            raise serr.VolumeNotFound(volume)
        return p

    def _make_sys_tmp(self) -> None:
        """<root>/.minio.sys/tmp, made BELOW a root that stands: a root
        that is gone is a drive that is gone and is not made here (the
        mkdir fails ENOENT, which the seam types)."""
        for d in (os.path.dirname(self._sys_tmp), self._sys_tmp):
            try:
                os.mkdir(d)
            except FileExistsError:
                pass

    # --- a dead drive (failure paths only) ---

    def _offline(self) -> serr.DiskNotFound | None:
        """DiskNotFound where this drive's root is not a directory that
        answers: replaced by a regular file (ENOTDIR), gone (ENOENT),
        failing (EIO). One stat, made only after a call has FAILED in a
        way that leaves it open: a healthy leg never asks."""
        try:
            if stat.S_ISDIR(os.stat(self.root).st_mode):
                return None
            code = errno.ENOTDIR
        except OSError as e:
            code = e.errno or errno.EIO
        gone = serr.DiskNotFound(f"{self.root}: {os.strerror(code)}")
        gone.errno = code
        return gone

    def _os_fault(self, e: OSError) -> serr.StorageError:
        """The typed error for an OSError that no caller resolved into
        a namespace condition: the drive gone, the drive full, or the
        drive's fault."""
        if e.errno == errno.ENOSPC:
            return serr.DiskFull(str(e))
        return self._offline() or serr.FaultyDisk(str(e))

    # --- identity / health ---

    def disk_info(self) -> dict:
        with _DiskOp("disk_info", self):
            # Of the system volume, not of the root: a root replaced by
            # a regular file answers statvfs itself.
            st = os.statvfs(os.path.dirname(self._sys_tmp))
        return {
            "total": st.f_blocks * st.f_frsize,
            "free": st.f_bavail * st.f_frsize,
            "used": (st.f_blocks - st.f_bfree) * st.f_frsize,
            "root": self.root,
            "id": self.disk_id,
        }

    def endpoint(self) -> str:
        return self.root

    # --- volumes ---

    def make_volume(self, volume: str) -> None:
        if not _is_valid_volume(volume):
            raise serr.VolumeNotFound(volume)
        # mkdir, not makedirs: a volume stands directly under the root,
        # and a root that is gone is not made here (ENOENT -> the seam).
        with _Seam("make_volume", self):
            try:
                os.mkdir(os.path.join(self.root, volume))
            except FileExistsError:
                raise serr.VolumeExists(volume) from None

    def list_volumes(self) -> list[str]:
        out = []
        with _Seam("list_volumes", self):
            for name in sorted(os.listdir(self.root)):
                if name in _RESERVED_VOLUMES or name.startswith("."):
                    continue
                if os.path.isdir(os.path.join(self.root, name)):
                    out.append(name)
        return out

    def stat_volume(self, volume: str) -> dict:
        with _DiskOp("stat_volume", self):
            # One stat answers both questions; _check_vol is asked only
            # when it says "no directory" (it raises, or self-creates
            # the system volume).
            p = self._vol_path(volume)
            try:
                st = os.stat(p)
            except OSError:
                st = None
            if st is None or not stat.S_ISDIR(st.st_mode):
                st = os.stat(self._check_vol(volume))
        return {"name": volume, "created": st.st_mtime}

    def delete_volume(self, volume: str, force: bool = False) -> None:
        if volume in _RESERVED_VOLUMES:
            raise serr.VolumeNotFound(f"{volume} is reserved")
        with _Seam("delete_volume", self):
            p = self._check_vol(volume)
            try:
                if force:
                    shutil.rmtree(p)
                else:
                    os.rmdir(p)
            except OSError as e:
                if e.errno == errno.ENOTEMPTY:
                    raise serr.VolumeExists(f"{volume} not empty")
                raise

    # --- flat files ---

    def _makedirs_for(self, volume: str, dirpath: str) -> None:
        """makedirs with the volume re-checked IMMEDIATELY before: an
        implicit mkdir on a write path must never resurrect a bucket
        volume that a racing delete_bucket just removed — otherwise a
        deleted bucket and a stored object/metadata write can both
        report success with the volume left on a random disk subset.
        (The microsecond residual window is absorbed by the engine's
        majority checks and heal sweeps.)"""
        self._check_vol(volume)
        try:
            os.makedirs(dirpath, exist_ok=True)
        except FileNotFoundError as e:
            # A parent vanished mid-walk (racing force delete-bucket
            # rmtree): re-check the volume — gone is the typed
            # bucket-deleted condition the engine maps to NoSuchBucket;
            # still present means the race interleaved mid-create, one
            # retry rebuilds the chain. A second ENOENT means the
            # volume is mid-rmtree right now: same typed condition.
            self._check_vol(volume)
            try:
                os.makedirs(dirpath, exist_ok=True)
            except FileNotFoundError:
                raise serr.VolumeNotFound(volume) from e

    def _atomic_write(self, full: str, data: bytes,
                      volume: str | None = None,
                      dir_ready: bool = False) -> None:
        """dir_ready: the caller created (or just verified) the target
        directory within this same storage call — skip the repeat
        stat/mkdir. The replace below still fails ENOENT if a racing
        delete removed the directory; that surfaces as FaultyDisk,
        same as any other mid-commit disk mutation."""
        if not dir_ready:
            if volume is not None:
                self._makedirs_for(volume, os.path.dirname(full))
            else:
                os.makedirs(os.path.dirname(full), exist_ok=True)
        tmp = os.path.join(self.root, TMP_DIR, str(uuid.uuid4()))
        try:
            try:
                f = open(tmp, "wb")
            except FileNotFoundError:
                # tmp dir wiped under us (disk swap mid-flight): the
                # system volume self-creates, then retry once.
                self._make_sys_tmp()
                f = open(tmp, "wb")
            with f:
                f.write(data)
            try:
                commit_replace(tmp, full)
            except FileNotFoundError:
                # Target dir vanished mid-write (racing force
                # delete-bucket rmtree, or delete()'s empty-parent
                # pruning). Re-derive the TYPED cause: volume gone ->
                # VolumeNotFound (the engine's commit guard maps it to
                # NoSuchBucket, never a quorum 5xx); volume intact ->
                # only the object dir was pruned, recreate + retry.
                # _makedirs_for re-checks the volume first, so this
                # never resurrects a deleted bucket.
                if volume is None:
                    raise
                self._makedirs_for(volume, os.path.dirname(full))
                try:
                    commit_replace(tmp, full)
                except FileNotFoundError as e:
                    # Deleted again between retry-mkdir and replace:
                    # the volume is being torn down right now.
                    raise serr.VolumeNotFound(volume) from e
        except serr.StorageError:
            raise
        except OSError as e:
            raise self._os_fault(e)

    def write_all(self, volume: str, path: str, data: bytes) -> None:
        # Volume check happens in _makedirs_for, adjacent to the mkdir.
        with _DiskOp("write_all", self):
            self._atomic_write(
                self._file_path(volume, path),
                FAULTS.filter_write(self.root, "write_all",
                                    bytes(data)),
                volume=volume)

    def _raise_read_miss(self, volume: str, path: str, e: OSError) -> None:
        """Raise for a read's failed `open`. The readers act first
        and check on failure: no `stat` of the volume goes before the
        `open` (on a network mount: a round trip a read, ten to twelve
        a GET), and a path that does not resolve is told apart HERE
        into the drive gone (DiskNotFound), the volume gone
        (VolumeNotFound, from which the engine takes BucketNotFound)
        and the file gone."""
        self._check_vol(volume)
        if isinstance(e, NotADirectoryError):
            # A path THROUGH a file, the volume (and so the root)
            # standing: a fault of the name, which `from e` tells
            # _Seam._settle not to count against the drive.
            raise serr.FaultyDisk(str(e)) from e
        raise serr.FileNotFound(f"{volume}/{path}")

    def read_all(self, volume: str, path: str) -> bytes:
        full = self._file_path(volume, path)
        with _DiskOp("read_all", self):
            try:
                return FAULTS.filter_read(self.root, "read_all",
                                          _read_whole(full))
            except (FileNotFoundError, NotADirectoryError) as e:
                self._raise_read_miss(volume, path, e)
            except IsADirectoryError:
                raise serr.FileNotFound(f"{volume}/{path}")

    def read_file(self, volume: str, path: str, offset: int,
                  length: int) -> bytes:
        full = self._file_path(volume, path)
        with _DiskOp("read_file", self):
            try:
                return FAULTS.filter_read(
                    self.root, "read_file",
                    _read_range(full, offset, length))
            except (FileNotFoundError, NotADirectoryError) as e:
                self._raise_read_miss(volume, path, e)

    def create_file(self, volume: str, path: str, data) -> None:
        """bytes -> atomic write; iterable of chunks -> incremental
        streaming write (ref streaming CreateFile,
        cmd/xl-storage.go:1575). Streamed files land directly at the
        target path: callers always stage under tmp/ and commit via
        rename_data, so a torn stream never becomes visible.
        (Volume check happens in _makedirs_for, adjacent to mkdir.)"""
        full = self._file_path(volume, path)
        if isinstance(data, (bytes, bytearray, memoryview)):
            with _DiskOp("create_file", self):
                self._atomic_write(
                    full,
                    FAULTS.filter_write(self.root, "create_file",
                                        bytes(data)),
                    volume=volume)
            return
        with _Seam("create_file", self):
            self._makedirs_for(volume, os.path.dirname(full))
            with open(full, "wb") as f:
                for chunk in data:
                    f.write(chunk)

    def _native_vol(self, volume: str) -> tuple[bytes, bytes | None]:
        """A volume as native/fsops.cc takes it: its path, and for the
        system volume the tmp directory that self-creates with it."""
        return (os.fsencode(self._vol_path(volume)),
                os.fsencode(self._sys_tmp)
                if volume == MINIO_META_BUCKET else None)

    @staticmethod
    def _raise_native(rc: int, src: str, dst_volume: str,
                      src_volume: str = "") -> None:
        """Raise for a native leg's non-zero result what the Python
        lane raises for the same condition; an errno as the OSError it
        would have been, for the caller's `except OSError` to type."""
        if rc == native.FS_DST_VOLUME_NOT_FOUND:
            raise serr.VolumeNotFound(dst_volume)
        if rc == native.FS_SRC_VOLUME_NOT_FOUND:
            raise serr.VolumeNotFound(src_volume)
        if rc == native.FS_STAGE_NOT_FOUND:
            raise serr.FileNotFound(src)
        if rc == native.FS_DRIVE_NOT_FOUND:
            rc = errno.ENOENT      # the seam asks the root, as for any
        raise OSError(rc, os.strerror(rc), src)

    def append_file(self, volume: str, path: str, data: bytes) -> None:
        full = self._file_path(volume, path)
        data = FAULTS.filter_write(self.root, "append_file", data)
        lib = _fs_lane("append_file")
        with _DiskOp("append_file", self):
            if lib is not None:
                rc, calls = native.fs_append(
                    lib, os.fsencode(full), *self._native_vol(volume),
                    data)
                _count_syscalls("append_file", calls)
                if rc != 0:
                    self._raise_native(rc, f"{volume}/{path}", volume)
                return
            try:
                f = open(full, "ab")
            except FileNotFoundError:
                # First append of a staged stream: create the
                # directory (volume-guarded) and retry. Later
                # appends of the same stream skip the stat/mkdir
                # pair — on the pipelined PUT path that's one
                # fewer round of metadata syscalls per disk per
                # batch.
                self._makedirs_for(volume, os.path.dirname(full))
                f = open(full, "ab")
            with f:
                f.write(data)

    def delete(self, volume: str, path: str, recursive: bool = False,
               ) -> None:
        with _DiskOp("delete", self):
            self._check_vol(volume)
            full = self._file_path(volume, path)
            try:
                if os.path.isdir(full):
                    if recursive:
                        shutil.rmtree(full)
                    else:
                        os.rmdir(full)
                else:
                    os.remove(full)
            except FileNotFoundError:
                raise serr.FileNotFound(f"{volume}/{path}")
        # Prune now-empty parent dirs up to the volume root (the reference
        # deletes parent prefixes as they empty).
        parent = os.path.dirname(full)
        vol = self._vol_path(volume)
        while parent != vol:
            try:
                os.rmdir(parent)
            except OSError:
                break
            parent = os.path.dirname(parent)

    def link_file(self, src_volume: str, src_path: str,
                  dst_volume: str, dst_path: str) -> None:
        """Hard-link src to dst (same disk root, so same filesystem),
        REPLACING dst if present — the zero-copy lane multipart
        complete uses to stage immutable part shards into the commit
        data dir without rewriting their bytes. Callers must treat the
        linked file as immutable (shard files are append-once, read-
        only after commit). Storage backends without link support
        (remote RPC disks) simply don't expose this method; callers
        fall back to read+write copy."""
        with _Seam("link_file", self):
            self._check_vol(src_volume)
            src = self._file_path(src_volume, src_path)
            dst = self._file_path(dst_volume, dst_path)
            self._makedirs_for(dst_volume, os.path.dirname(dst))
        tmp = os.path.join(self.root, TMP_DIR, str(uuid.uuid4()))
        with _DiskOp("link_file", self):
            try:
                # link to a tmp name then replace: os.link alone fails
                # EEXIST on a dst left by a retried complete.
                try:
                    os.link(src, tmp)
                except FileNotFoundError:
                    self._make_sys_tmp()
                    os.link(src, tmp)
                commit_replace(tmp, dst)
            except FileNotFoundError:
                # The volumes stood a moment ago: it is the file.
                raise serr.FileNotFound(f"{src_volume}/{src_path}")

    def rename_file(self, src_volume: str, src_path: str, dst_volume: str,
                    dst_path: str) -> None:
        with _Seam("rename_file", self):
            self._check_vol(src_volume)
            self._check_vol(dst_volume)
            src = self._file_path(src_volume, src_path)
            dst = self._file_path(dst_volume, dst_path)
            if not os.path.exists(src):
                raise serr.FileNotFound(f"{src_volume}/{src_path}")
            self._makedirs_for(dst_volume, os.path.dirname(dst))
            commit_replace(src, dst)

    def list_dir(self, volume: str, path: str) -> list[str]:
        with _Seam("list_dir", self):
            self._check_vol(volume)
            full = self._file_path(volume, path) if path \
                else self._vol_path(volume)
            try:
                out = []
                for name in sorted(os.listdir(full)):
                    if os.path.isdir(os.path.join(full, name)):
                        out.append(name + "/")
                    else:
                        out.append(name)
                return out
            except (FileNotFoundError, NotADirectoryError):
                raise serr.FileNotFound(f"{volume}/{path}")

    # --- object versions ---

    def _read_xlmeta(self, volume: str, path: str) -> XLMeta:
        raw = self.read_all(volume, os.path.join(path, XL_META_FILE))
        try:
            return XLMeta.load(raw)
        except ValueError as e:
            raise serr.FileCorrupt(str(e))

    def _write_xlmeta(self, volume: str, path: str, meta: XLMeta) -> None:
        self._atomic_write(
            self._file_path(volume, os.path.join(path, XL_META_FILE)),
            meta.dump(), volume=volume)

    def rename_data(self, src_volume: str, src_path: str, fi: FileInfo,
                    dst_volume: str, dst_path: str) -> None:
        """Commit: move <src>/<dataDir> under dst object dir, then merge
        fi as a version into dst xl.meta (ref cmd/xl-storage.go:1972)."""
        with _DiskOp("rename_data", self):
            self._rename_data(src_volume, src_path, fi, dst_volume,
                              dst_path)

    def _rename_data(self, src_volume: str, src_path: str, fi: FileInfo,
                     dst_volume: str, dst_path: str) -> None:
        # What neither lane resolves into a typed condition (a refused
        # rename, EIO, a root that is gone) leaves rename_data's _DiskOp
        # typed, as from append_file and _atomic_write.
        lib = _fs_lane("rename_data")
        if lib is None:
            self._rename_data_py(src_volume, src_path, fi,
                                 dst_volume, dst_path)
        else:
            self._rename_data_native(lib, src_volume, src_path, fi,
                                     dst_volume, dst_path)

    def _rename_data_native(self, lib, src_volume: str, src_path: str,
                            fi: FileInfo, dst_volume: str,
                            dst_path: str) -> None:
        """_rename_data_py below as TWO GIL-free calls (native/fsops.cc)
        around the XLMeta merge: its order of visibility and its typed
        results, but where the Python lane asks first (both volumes, the
        stage, the name the data dir takes) this lane acts first and
        checks on failure: the mkdir that succeeds says the key is fresh
        (no xl.meta to read), the errno of a refused rename says what to
        look at. No crash point stands here: an armed fault plan takes
        the Python lane (_native_lib), which is where the crash windows
        are proven; what this lane leaves when a step FAILS is
        tests/test_storage_native_lane.py's, from file-system state."""
        src_vol, src_sys = self._native_vol(src_volume)
        dst_vol, dst_sys = self._native_vol(dst_volume)
        dst_obj_dir = self._file_path(dst_volume, dst_path)
        src_dir = self._file_path(src_volume, src_path)
        src_dd = dst_dd = None
        if fi.data_dir:
            src_dd = os.fsencode(self._file_path(
                src_volume, os.path.join(src_path, fi.data_dir)))
            dst_dd = os.fsencode(os.path.join(dst_obj_dir, fi.data_dir))
        xl_meta = os.path.join(dst_obj_dir, XL_META_FILE)
        rc, raw, read_ms, calls = native.fs_commit_stage(
            lib, src_vol, src_sys, dst_vol, dst_sys,
            os.fsencode(dst_obj_dir), src_dd, dst_dd,
            os.fsencode(xl_meta))
        _count_syscalls("rename_data", calls)
        if rc != 0:
            self._raise_native(rc, f"{src_volume}/{src_path}",
                               dst_volume, src_volume)
        # The Python lane reads xl.meta through read_all; the drive
        # monitor's read class goes on hearing of it on this lane too
        # (a drive judged slow on reads is cleared by reads), timed in
        # C, where no wait for the GIL is inside. On a fresh key, where
        # no xl.meta is opened, it is the mkdir that said so.
        _account(self.root, "read_all", read_ms)
        if raw is native.FS_META_TOO_BIG:
            with open(xl_meta, "rb") as f:
                raw = f.read()
        try:
            meta = XLMeta() if raw is None else XLMeta.load(raw)
        except ValueError as e:
            raise serr.FileCorrupt(str(e))
        old = self._merge_version(meta, fi)
        old_dd, old_parts = None, []
        if old is not None:
            old_dd = os.fsencode(os.path.join(dst_obj_dir, old["dataDir"]))
            old_parts = [p["number"] for p in old.get("parts", [])]
        tmp = os.path.join(self._sys_tmp, str(uuid.uuid4()))
        rc, calls = native.fs_commit_meta(
            lib, os.fsencode(tmp), os.fsencode(xl_meta), meta.dump(),
            dst_vol, dst_sys, os.fsencode(dst_obj_dir), old_dd, old_parts,
            os.fsencode(os.path.join(src_dir, INTENT_FILE)),
            os.fsencode(src_dir))
        _count_syscalls("rename_data", calls)
        if rc != 0:
            self._raise_native(rc, f"{dst_volume}/{dst_path}", dst_volume)

    @staticmethod
    def _merge_version(meta: XLMeta, fi: FileInfo) -> dict | None:
        """Add `fi` to `meta`; returns the version whose data dir this
        frees, if any (its `dataDir`, and its `parts`: the names of the
        files in it). Null-version overwrite frees the PREVIOUS NULL
        version's data dir only (real versions keep theirs; ref
        xlMetaV2.AddVersion null-version replacement semantics). Crash
        safety: the caller persists the new xl.meta BEFORE removing
        that data dir, so metadata never points at deleted shards."""
        old = None
        if fi.version_id == "":
            for v in meta.versions:
                if v.get("versionId", "") == "":
                    old = v
                    break
        meta.add_version(fi)
        if old and old.get("dataDir") and old["dataDir"] != fi.data_dir:
            return old
        return None

    def _rename_data_py(self, src_volume: str, src_path: str, fi: FileInfo,
                        dst_volume: str, dst_path: str) -> None:
        self._check_vol(src_volume)
        dst_obj_dir = self._file_path(dst_volume, dst_path)
        self._makedirs_for(dst_volume, dst_obj_dir)
        if fi.data_dir:
            src_dd = self._file_path(src_volume,
                                     os.path.join(src_path, fi.data_dir))
            dst_dd = os.path.join(dst_obj_dir, fi.data_dir)
            if not os.path.isdir(src_dd):
                raise serr.FileNotFound(f"{src_volume}/{src_path}")
            if os.path.isdir(dst_dd):
                shutil.rmtree(dst_dd)
            # Crash window A: shards fully staged, nothing visible yet
            # — a death here must leave the OLD version intact and the
            # stage for the boot sweep to GC.
            FAULTS.crash_point(CRASH_RENAME_PRE)
            try:
                commit_replace(src_dd, dst_dd)
            except FileNotFoundError:
                # dst object dir vanished between the makedirs above
                # and the replace (racing force delete-bucket, or a
                # concurrent delete's empty-parent pruning): typed
                # re-check — VolumeNotFound when the bucket is gone,
                # recreate + retry when only the object dir was pruned
                # (_makedirs_for re-checks the volume, so a deleted
                # bucket is never resurrected).
                self._makedirs_for(dst_volume, dst_obj_dir)
                try:
                    commit_replace(src_dd, dst_dd)
                except FileNotFoundError as e:
                    raise serr.VolumeNotFound(dst_volume) from e
        # Crash window B: the new data dir is in place but xl.meta
        # still names the old version — a death here must read as the
        # OLD version (the orphaned new data dir is invisible until
        # the meta merge below lands, and heal GCs it).
        FAULTS.crash_point(CRASH_RENAME_MID)
        try:
            meta = self._read_xlmeta(dst_volume, dst_path)
        except serr.FileNotFound:
            meta = XLMeta()
        old = self._merge_version(meta, fi)
        # dir_ready: dst_obj_dir was created at the top of this call;
        # xl.meta lives directly in it. volume still passed so a
        # mid-commit ENOENT (racing delete) resolves typed.
        self._atomic_write(
            self._file_path(dst_volume,
                            os.path.join(dst_path, XL_META_FILE)),
            meta.dump(), volume=dst_volume, dir_ready=True)
        # Crash window C: the NEW version is fully committed on this
        # disk; only garbage collection (old data dir, stage dir)
        # remains — a death here must read as the new version with
        # the leftovers swept at next boot.
        FAULTS.crash_point(CRASH_RENAME_POST)
        if old is not None:
            old_dd = os.path.join(dst_obj_dir, old["dataDir"])
            if os.path.isdir(old_dd):
                shutil.rmtree(old_dd, ignore_errors=True)
        # Clean the tmp staging dir — after the data-dir replace only
        # the recovery intent breadcrumb remains, so one targeted
        # unlink + bare rmdir does it (rmtree's listdir walk only for
        # the unusual leftover case).
        src_dir = self._file_path(src_volume, src_path)
        try:
            os.remove(os.path.join(src_dir, INTENT_FILE))
        except OSError:
            pass
        try:
            os.rmdir(src_dir)
        except OSError:
            shutil.rmtree(src_dir, ignore_errors=True)

    def write_metadata(self, volume: str, path: str, fi: FileInfo) -> None:
        try:
            meta = self._read_xlmeta(volume, path)
        except serr.FileNotFound:
            meta = XLMeta()
        meta.add_version(fi)
        self._write_xlmeta(volume, path, meta)

    def read_version(self, volume: str, path: str,
                     version_id: str = "") -> FileInfo:
        meta = self._read_xlmeta(volume, path)
        v = meta.find_version(version_id)
        if v is None:
            if version_id:
                raise serr.VersionNotFound(f"{path}@{version_id}")
            raise serr.FileNotFound(path)
        return FileInfo.from_version_dict(volume, path, v)

    def read_versions(self, volume: str, path: str) -> list[FileInfo]:
        meta = self._read_xlmeta(volume, path)
        return [FileInfo.from_version_dict(volume, path, v)
                for v in meta.versions]

    def delete_version(self, volume: str, path: str, fi: FileInfo) -> None:
        meta = self._read_xlmeta(volume, path)
        v = meta.delete_version(fi.version_id)
        if v is None:
            raise serr.VersionNotFound(f"{path}@{fi.version_id}")
        obj_dir = self._file_path(volume, path)
        # Metadata first, data-dir removal second (crash-safe ordering).
        if meta.versions:
            self._write_xlmeta(volume, path, meta)
            dd = v.get("dataDir")
            if dd and not any(x.get("dataDir") == dd
                              for x in meta.versions):
                shutil.rmtree(os.path.join(obj_dir, dd),
                              ignore_errors=True)
        else:
            self.delete(volume, path, recursive=True)

    def read_parts(self, volume: str, path: str, data_dir: str,
                   ) -> list[str]:
        full = self._file_path(volume, os.path.join(path, data_dir))
        with _Seam("read_parts", self):
            try:
                return sorted(n for n in os.listdir(full)
                              if n.startswith("part."))
            except FileNotFoundError:
                self._check_vol(volume)
                raise serr.FileNotFound(f"{volume}/{path}/{data_dir}")

    def verify_file(self, volume: str, path: str, fi: FileInfo) -> int:
        """Deep bitrot scan of every part shard on this disk
        (ref cmd/xl-storage.go:2312,2380). Returns the bytes it read
        (the heal's classification counts them)."""
        shard_size = fi.erasure.shard_size()
        scanned = 0
        for part in fi.parts:
            rel = os.path.join(path, fi.data_dir, f"part.{part.number}")
            stream = self.read_all(volume, rel)
            scanned += len(stream)
            algo = bitrot.DEFAULT_ALGORITHM
            for cs in fi.erasure.checksums:
                if cs.get("part") == part.number:
                    algo = cs.get("algorithm", algo)
            if bitrot.is_streaming(algo):
                if not bitrot.verify_stream(stream, shard_size, algo):
                    raise serr.FileCorrupt(f"{path} part {part.number}")
            else:
                want = ""
                for cs in fi.erasure.checksums:
                    if cs.get("part") == part.number:
                        want = cs.get("hash", "")
                if want and bitrot.digest(algo, stream).hex() != want:
                    raise serr.FileCorrupt(f"{path} part {part.number}")
        return scanned
