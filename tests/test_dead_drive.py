"""A node whose drive is dead at the OPERATING SYSTEM's level keeps
serving (PR 35). Not a wrapper that raises a typed error (NaughtyDisk,
tests/test_engine.py): the drive's root is a regular file, or is gone,
or every file-system call under it fails EIO, and the requests are
signed requests to an in-process server on the served path (front door
-> pools -> sets -> engine -> XLStorage).

What is held: no request answers 5xx while quorum absorbs the drive
(up to `parity` of a set); every GET's bytes are the PUT's; the shard
files on the SURVIVING drives are byte for byte the plain reference's
(ops/rs_cpu + the pure-Python HighwayHash-256, both pinned by
tests/test_golden.py); nothing lands on the dead drive; a raw OSError
never crosses the XLStorage seam and a root that is not there never
reads as "volume not found" or "file not found"; the drive turns
`faulty` after a bounded number of failed calls and is then left out
without a call; its debt is kept, not chased, and healed when it
answers again; a healthy leg still makes the file-system calls it made.
"""

import errno
import json
import os
import re
import time
import uuid
import zlib

import pytest

from minio_tpu import native
from minio_tpu.config.storageclass import StorageClassConfig
from minio_tpu.erasure.pools import ErasureServerPools
from minio_tpu.erasure.sets import ErasureSets
from minio_tpu.obs.drivemon import DRIVEMON, FAULTY
from minio_tpu.obs.metrics2 import METRICS2
from minio_tpu.obs.span import TRACER
from minio_tpu.ops import rs_cpu
from minio_tpu.ops.hh256 import hh256
from minio_tpu.s3.client import S3Client
from minio_tpu.s3.server import S3Server
from minio_tpu.storage import errors as serr
from minio_tpu.storage import xl
from minio_tpu.storage.xl import MINIO_META_BUCKET, XLStorage

ACCESS, SECRET = "deaddrive", "deaddrive-secret"
BUCKET = "deadb"
N, K, M = 12, 8, 4
BLOCK = 64 * 1024
DEAD = 4                     # drive 5, as the benchmark's cell
OLD = ("old/ckpt.bin", 150_001)
NEW = ("new/ckpt.bin", 2 * BLOCK + 77)
MODES = ("file", "gone", "eio")
FAULTY_AFTER = DRIVEMON.WINDOW_OPS * DRIVEMON.FAULTY_WINDOWS   # 32 calls


def body_of(key: str, size: int) -> bytes:
    seed = zlib.crc32(key.encode())
    return bytes((seed + i * 131 + (i >> 8)) & 0xFF for i in range(size))


def reference_shard_files(data: bytes, k: int = K, m: int = M) -> list[bytes]:
    """The k+m streaming-bitrot shard files of an object, shard 1 first:
    every stripe block split and RS-encoded on its own, framed
    [32-byte HighwayHash-256][sub-block]."""
    files = [bytearray() for _ in range(k + m)]
    for lo in range(0, len(data), BLOCK):
        for j, row in enumerate(rs_cpu.encode_data(data[lo:lo + BLOCK], k, m)):
            files[j] += hh256(row.tobytes()) + row.tobytes()
    return [bytes(f) for f in files]


def shard_of_drive(key: str, n: int = N) -> list[int]:
    """The reference's hashOrder: shard index (1-based) each drive of
    the set holds, rotated by crc32 of `bucket/key`."""
    start = zlib.crc32(f"{BUCKET}/{key}".encode()) % n
    return [1 + (start + i) % n for i in range(1, n + 1)]


# -- killing a drive the way an operating system does ----------------------


class _EIO:
    """`os` as storage/xl.py sees it, with every file-system call on a
    path under `root` failing EIO (what a dying device answers); file
    descriptors, other paths and the pure functions pass through."""

    SYSCALLS = ("open", "stat", "lstat", "mkdir", "makedirs", "listdir",
                "scandir", "rename", "replace", "remove", "unlink", "rmdir",
                "link", "statvfs")

    def __init__(self, real, root: str):
        self._real, self._root = real, root
        self.path = _EIOPath(real.path, self)

    def dead(self, p) -> bool:
        if isinstance(p, bytes):
            p = os.fsdecode(p)
        return isinstance(p, str) and (
            p == self._root or p.startswith(self._root + os.sep))

    def __getattr__(self, name):
        fn = getattr(self._real, name)
        if name not in self.SYSCALLS:
            return fn

        def call(*a, **kw):
            if any(self.dead(x) for x in a[:2]):
                raise OSError(errno.EIO, os.strerror(errno.EIO), a[0])
            return fn(*a, **kw)
        return call


class _EIOPath:
    def __init__(self, real, owner: _EIO):
        self._real, self._owner = real, owner

    def __getattr__(self, name):
        fn = getattr(self._real, name)
        if name not in ("isdir", "exists", "isfile"):
            return fn
        return lambda p: False if self._owner.dead(p) else fn(p)


def kill(root: str, mode: str, monkeypatch) -> None:
    if mode == "eio":
        # The native lane makes its calls in C; an armed fault plan or
        # a missing library takes the Python lane, as here.
        monkeypatch.setattr(native, "get_lib", lambda: None)
        fake = _EIO(os, root)
        monkeypatch.setattr(xl, "os", fake)
        real_open = open

        def dead_open(p, *a, **kw):
            if fake.dead(p):
                raise OSError(errno.EIO, os.strerror(errno.EIO), p)
            return real_open(p, *a, **kw)
        monkeypatch.setattr(xl, "open", dead_open, raising=False)
        return
    os.rename(root, root + ".aside")
    if mode == "file":
        with open(root, "wb"):
            pass


def restore(root: str, mode: str, monkeypatch) -> None:
    if mode == "eio":
        monkeypatch.undo()
        return
    if mode == "file":
        os.remove(root)
    os.rename(root + ".aside", root)


def tree(root: str) -> list[str]:
    return sorted(os.path.join(d, f)[len(root):]
                  for d, _, fs in os.walk(root) for f in fs)


# -- the node ----------------------------------------------------------------


class Node:
    def __init__(self, tmp_path, monkeypatch):
        self.monkeypatch = monkeypatch
        self.roots = [str(tmp_path / f"d{i + 1}") for i in range(N)]
        self.disks = [XLStorage(r) for r in self.roots]
        sets = ErasureSets(self.disks, [N], str(uuid.uuid4()),
                           block_size=BLOCK)
        self.eng = sets.sets[0]
        self.eng.multipart.min_part_size = 1024
        self.srv = S3Server(ErasureServerPools([sets]), ACCESS, SECRET)
        self.srv.handlers.storage_class = StorageClassConfig(
            standard_parity=M)
        self.c = S3Client("127.0.0.1", self.srv.start(), ACCESS, SECRET)
        assert self.c.make_bucket(BUCKET).status == 200
        self.dead: dict[int, str] = {}
        self.statuses: list[int] = []
        self.put(*OLD)

    def stop(self):
        self.srv.stop()
        self.eng.shutdown()

    def kill(self, mode: str, *positions: int) -> None:
        for pos in positions or (DEAD,):
            self.before = tree(self.roots[pos]) if mode == "eio" else None
            kill(self.roots[pos], mode, self.monkeypatch)
            self.dead[pos] = mode

    def request(self, method, path, **kw):
        r = self.c.request(method, path, **kw)
        self.statuses.append(r.status)
        return r

    def put(self, key: str, size: int):
        r = self.request("PUT", self.c._key_path(BUCKET, key),
                         body=body_of(key, size))
        assert r.status == 200, r.body
        return r

    def get(self, key: str, size: int) -> None:
        r = self.request("GET", self.c._key_path(BUCKET, key))
        assert r.status == 200, r.body[:300]
        assert r.body == body_of(key, size)

    def multipart(self, key: str, sizes: list[int]) -> bytes:
        path = self.c._key_path(BUCKET, key)
        r = self.request("POST", path, query="uploads")
        assert r.status == 200, r.body
        uid = re.search(rb"<UploadId>([^<]+)</UploadId>",
                        r.body).group(1).decode()
        parts, doc = [], ""
        for i, size in enumerate(sizes, start=1):
            parts.append(body_of(f"{key}#{i}", size))
            r = self.request("PUT", path, body=parts[-1],
                             query=f"partNumber={i}&uploadId={uid}")
            assert r.status == 200, r.body
            doc += (f"<Part><PartNumber>{i}</PartNumber><ETag>"
                    f"{r.headers['etag']}</ETag></Part>")
        r = self.request("POST", path, query=f"uploadId={uid}",
                         body=f"<CompleteMultipartUpload>{doc}"
                              "</CompleteMultipartUpload>".encode())
        assert r.status == 200 and b"<Error>" not in r.body, r.body
        return b"".join(parts)

    def check_at_rest(self, key: str, parts: list[bytes]) -> None:
        """Every surviving drive holds its shard file of every part,
        byte for byte the reference's; a dead drive holds nothing new."""
        want = [reference_shard_files(p) for p in parts]
        for pos, idx in enumerate(shard_of_drive(key)):
            if pos in self.dead:
                continue
            obj = os.path.join(self.roots[pos], BUCKET, key)
            with open(os.path.join(obj, "xl.meta")) as f:
                ver = json.load(f)["versions"][0]
            assert ver["erasure"]["index"] == idx
            assert (ver["erasure"]["data"], ver["erasure"]["parity"]) == (K, M)
            for n, files in enumerate(want, start=1):
                with open(os.path.join(obj, ver["dataDir"],
                                       f"part.{n}"), "rb") as f:
                    assert f.read() == files[idx - 1], (key, pos, n)

    def check_dead_untouched(self) -> None:
        for pos, mode in self.dead.items():
            root = self.roots[pos]
            if mode == "eio":
                assert tree(root) == self.before
            else:
                assert not os.path.isdir(root)

    def no_5xx(self) -> None:
        assert self.statuses and max(self.statuses) < 500, self.statuses


@pytest.fixture
def node(tmp_path, monkeypatch):
    n = Node(tmp_path, monkeypatch)
    yield n
    monkeypatch.undo()
    n.stop()


# -- every operation, every way of dying -----------------------------------


def op_put(n: Node):
    n.put(*NEW)
    n.check_at_rest(NEW[0], [body_of(*NEW)])


def op_get_old(n: Node):
    # Shard 5's drive holds a DATA shard of this key or it would prove
    # nothing: the GET has to reconstruct.
    assert shard_of_drive(OLD[0])[DEAD] <= K
    n.get(*OLD)
    n.check_at_rest(OLD[0], [body_of(*OLD)])


def op_get_new(n: Node):
    n.put(*NEW)
    n.get(*NEW)


def op_head(n: Node):
    r = n.request("HEAD", n.c._key_path(BUCKET, OLD[0]))
    assert r.status == 200
    assert int(r.headers["content-length"]) == OLD[1]
    assert n.request("HEAD", n.c._key_path(BUCKET, "nope")).status == 404


def op_delete(n: Node):
    assert n.request("DELETE", n.c._key_path(BUCKET, OLD[0])).status == 204
    assert n.request("GET", n.c._key_path(BUCKET, OLD[0])).status == 404
    for pos, root in enumerate(n.roots):
        if pos not in n.dead:
            assert not os.path.exists(os.path.join(root, BUCKET, OLD[0]))
    # A key that never was: the survivors say so, the dead one says
    # nothing, and the answer is the reference's (204, idempotent).
    assert n.request("DELETE", n.c._key_path(BUCKET, "nope")).status == 204


def op_multipart(n: Node):
    key, sizes = "mp/ckpt.bin", [3 * BLOCK + 5, BLOCK, 40]
    whole = n.multipart(key, sizes)
    r = n.request("GET", n.c._key_path(BUCKET, key))
    assert r.status == 200 and r.body == whole
    n.check_at_rest(key, [body_of(f"{key}#{i}", s)
                          for i, s in enumerate(sizes, start=1)])


def op_list(n: Node):
    n.put(*NEW)
    r = n.request("GET", f"/{BUCKET}", query="list-type=2")
    assert r.status == 200
    keys = re.findall(rb"<Key>([^<]+)</Key>", r.body)
    assert keys == sorted(keys)
    assert {NEW[0].encode(), OLD[0].encode()} <= set(keys)
    r = n.request("GET", "/")
    assert r.status == 200 and f"<Name>{BUCKET}</Name>".encode() in r.body


OPS = {"put": op_put, "get_old": op_get_old, "get_new": op_get_new,
       "head": op_head, "delete": op_delete, "multipart": op_multipart,
       "list": op_list}


@pytest.mark.parametrize("op", list(OPS))
@pytest.mark.parametrize("mode", MODES)
def test_served_path_with_one_drive_dead(node, mode, op):
    node.kill(mode)
    OPS[op](node)
    node.no_5xx()
    node.check_dead_untouched()


@pytest.mark.parametrize("mode", MODES)
def test_the_rest_of_the_api_with_one_drive_dead(node, mode):
    """Buckets, tags, copy, versioning, delete markers, listings of
    versions and uploads, parts, abort, the health and metrics pages:
    each answers what it answers on a healthy node."""
    node.kill(mode)
    tags = (b"<Tagging><TagSet><Tag><Key>a</Key><Value>b</Value></Tag>"
            b"</TagSet></Tagging>")
    on = (b"<VersioningConfiguration><Status>Enabled</Status>"
          b"</VersioningConfiguration>")
    old = node.c._key_path(BUCKET, OLD[0])
    for want, method, path, kw in [
            (200, "PUT", "/second", {}),
            (200, "PUT", "/second/k", {"body": b"x" * 1000}),
            (200, "PUT", old, {"query": "tagging", "body": tags}),
            (200, "GET", old, {"query": "tagging"}),
            (200, "PUT", f"/{BUCKET}/copy",
             {"headers": {"x-amz-copy-source": f"/{BUCKET}/{OLD[0]}"}}),
            (200, "PUT", f"/{BUCKET}", {"query": "versioning", "body": on}),
            (200, "GET", f"/{BUCKET}", {"query": "versioning"}),
            (200, "PUT", f"/{BUCKET}/v", {"body": b"1" * 70_000}),
            (204, "DELETE", f"/{BUCKET}/v", {}),
            (200, "GET", f"/{BUCKET}", {"query": "versions"}),
            (409, "DELETE", f"/{BUCKET}", {}),
            (204, "DELETE", "/second/k", {}),
            (204, "DELETE", "/second", {}),
            (200, "GET", f"/{BUCKET}", {"query": "uploads"}),
            (404, "GET", f"/{BUCKET}", {"query": "policy"}),
            (200, "GET", "/minio-tpu/health/cluster", {"sign": False}),
            (200, "GET", "/minio-tpu/v2/health/drives", {"sign": False}),
            (200, "GET", "/minio-tpu/v2/metrics/node", {"sign": False})]:
        r = node.request(method, path, **kw)
        assert r.status == want, (method, path, kw.get("query"), r.body[:300])
    r = node.request("GET", f"/{BUCKET}/copy")
    assert r.status == 200 and r.body == body_of(*OLD)
    r = node.request("POST", f"/{BUCKET}/mpx", query="uploads")
    uid = re.search(rb"<UploadId>([^<]+)</UploadId>", r.body).group(1).decode()
    up = f"uploadId={uid}"
    assert node.request("PUT", f"/{BUCKET}/mpx", body=b"p" * 5000,
                        query=f"partNumber=1&{up}").status == 200
    r = node.request("GET", f"/{BUCKET}/mpx", query=up)
    assert r.status == 200 and b"<PartNumber>1</PartNumber>" in r.body
    assert node.request("DELETE", f"/{BUCKET}/mpx", query=up).status == 204
    node.check_dead_untouched()


# -- the seam ----------------------------------------------------------------


def _every_call(disk: XLStorage):
    """Each StorageAPI method of a drive, as the engine calls it."""
    from minio_tpu.storage.metadata import (ErasureInfo, FileInfo,
                                            ObjectPartInfo)
    fi = FileInfo(volume=BUCKET, name="k", data_dir="dd", size=8,
                  parts=[ObjectPartInfo(1, 8, 8)],
                  erasure=ErasureInfo(data_blocks=K, parity_blocks=M,
                                      block_size=BLOCK, index=1))
    sysv = MINIO_META_BUCKET
    return {
        "disk_info": lambda: disk.disk_info(),
        "make_volume": lambda: disk.make_volume("fresh"),
        "list_volumes": lambda: disk.list_volumes(),
        "stat_volume": lambda: disk.stat_volume(BUCKET),
        "stat_volume(sys)": lambda: disk.stat_volume(sysv),
        "delete_volume": lambda: disk.delete_volume(BUCKET),
        "write_all": lambda: disk.write_all(sysv, "config/x.json", b"{}"),
        "read_all": lambda: disk.read_all(BUCKET, "k/xl.meta"),
        "read_all(sys)": lambda: disk.read_all(sysv, "format.json"),
        "read_file": lambda: disk.read_file(BUCKET, "k/dd/part.1", 0, 8),
        "create_file": lambda: disk.create_file(sysv, "tmp/s/p", b"x"),
        "create_file(stream)": lambda: disk.create_file(
            sysv, "tmp/s/p", iter([b"x"])),
        "append_file": lambda: disk.append_file(sysv, "tmp/s/part.1", b"x"),
        "delete": lambda: disk.delete(BUCKET, "k", recursive=True),
        "delete(sys)": lambda: disk.delete(sysv, "tmp/s", recursive=True),
        "link_file": lambda: disk.link_file(sysv, "a", sysv, "b"),
        "rename_file": lambda: disk.rename_file(sysv, "a", sysv, "b"),
        "list_dir": lambda: disk.list_dir(BUCKET, ""),
        "list_dir(sys)": lambda: disk.list_dir(sysv, "mpu"),
        "rename_data": lambda: disk.rename_data(sysv, "tmp/s", fi,
                                                BUCKET, "k"),
        "write_metadata": lambda: disk.write_metadata(BUCKET, "k", fi),
        "read_version": lambda: disk.read_version(BUCKET, "k"),
        "read_versions": lambda: disk.read_versions(BUCKET, "k"),
        "delete_version": lambda: disk.delete_version(BUCKET, "k", fi),
        "read_parts": lambda: disk.read_parts(BUCKET, "k", "dd"),
        "verify_file": lambda: disk.verify_file(BUCKET, "k", fi),
        "walk_dir": lambda: disk.walk_dir(BUCKET),
    }


@pytest.mark.parametrize("lane", ["native", "python"])
@pytest.mark.parametrize("mode", MODES)
def test_every_storage_call_on_a_dead_root_is_typed(tmp_path, monkeypatch,
                                                    mode, lane):
    """DiskNotFound from every method: never a raw OSError, never
    VolumeNotFound / FileNotFound (which would vote in the
    bucket-not-found and object-not-found quorums), and nothing is
    made where the root was."""
    if lane == "python" or mode == "eio":
        monkeypatch.setattr(native, "get_lib", lambda: None)
    elif native.get_lib() is None:
        pytest.skip("no native library on this host")
    root = str(tmp_path / "d1")
    disk = XLStorage(root)
    disk.make_volume(BUCKET)
    before = tree(root)
    kill(root, mode, monkeypatch)
    for name, call in _every_call(disk).items():
        if name == "walk_dir":
            assert call() == []      # a walk skips what does not answer
            continue
        with pytest.raises(serr.DiskNotFound) as exc:
            call()
        want = {"file": errno.ENOTDIR, "gone": errno.ENOENT,
                "eio": errno.EIO}[mode]
        assert exc.value.errno == want, name
    if mode == "gone":
        assert not os.path.exists(root)
    elif mode == "file":
        assert os.path.isfile(root)
    restore(root, mode, monkeypatch)
    assert tree(root) == before
    # ... and the same calls on the root that answers again say what
    # they said before: the volume is there, the file is not.
    with pytest.raises(serr.FileNotFound):
        disk.read_all(BUCKET, "k/xl.meta")
    with pytest.raises(serr.VolumeNotFound):
        disk.read_all("nobucket", "k/xl.meta")
    assert disk.list_dir(BUCKET, "") == []


def test_config_plane_skips_a_dead_drive(node):
    """Bucket metadata, notification rules and IAM read from the FIRST
    drive of the list: dead, it is one drive's transient failure."""
    from minio_tpu.iam.iam import ConfigStore
    store = ConfigStore(node.disks)
    store.save("config/probe.json", {"v": 1})
    node.kill("file", 0)
    assert store.load("config/probe.json") == {"v": 1}
    assert store.load("config/none.json") is None
    assert store.list("config") == ["probe.json"]
    store.save("config/probe.json", {"v": 2})        # on quorum
    assert store.load("config/probe.json") == {"v": 2}
    store.delete("config/probe.json")
    assert store.load("config/probe.json") is None
    # Through the server: a bucket's metadata is re-read every second.
    node.srv.handlers.bucket_meta.invalidate(BUCKET)
    node.put(*NEW)
    node.get(*NEW)
    r = node.request("GET", f"/{BUCKET}", query="versioning")
    assert r.status == 200
    node.no_5xx()


# -- more drives than one ------------------------------------------------------


def test_parity_drives_dead_still_serves(node):
    node.kill("file", 1, 4, 6, 9)
    node.put(*NEW)
    node.get(*NEW)
    node.get(*OLD)
    assert node.request("HEAD", node.c._key_path(BUCKET, OLD[0])).status == 200
    op_multipart(node)
    op_list(node)
    assert node.request("DELETE",
                        node.c._key_path(BUCKET, NEW[0])).status == 204
    node.check_at_rest(OLD[0], [body_of(*OLD)])
    node.no_5xx()
    node.check_dead_untouched()


def test_parity_plus_one_dead_refuses_cleanly(node):
    """Below quorum the reference answers 503 SlowDown (its
    InsufficientWriteQuorum / InsufficientReadQuorum,
    cmd/api-errors.go): retryable, never a 500, never a wrong byte, and
    nothing half-written is left where a reader finds it."""
    node.kill("gone", 0, 2, 4, 7, 10)
    path = node.c._key_path(BUCKET, NEW[0])
    r = node.c.request("PUT", path, body=body_of(*NEW))
    assert r.status == 503 and b"<Code>SlowDown</Code>" in r.body
    r = node.c.request("GET", node.c._key_path(BUCKET, OLD[0]))
    assert r.status == 503 and b"<Code>SlowDown</Code>" in r.body
    r = node.c.request("HEAD", node.c._key_path(BUCKET, OLD[0]))
    assert r.status == 503               # 7 copies of xl.meta, k = 8
    for pos, root in enumerate(node.roots):
        if pos not in node.dead:
            assert not os.path.exists(os.path.join(root, BUCKET, NEW[0]))
            stage = os.path.join(root, MINIO_META_BUCKET, "tmp")
            assert not os.path.isdir(stage) or os.listdir(stage) == []
    # One drive back: the set serves again, the old object whole.
    restore(node.roots[0], "gone", node.monkeypatch)
    del node.dead[0]
    deadline = time.monotonic() + 20
    while DRIVEMON.is_quarantined(node.roots[0]):
        assert time.monotonic() < deadline
        node.eng.quarantine_prober.tick()
    node.get(*OLD)
    node.put(*NEW)
    node.get(*NEW)


# -- drive health, the debt, the return --------------------------------------


def _counter(name: str, labels: dict | None = None) -> float:
    want = (labels or {}).items()
    return sum(s["value"] for s in METRICS2.snapshot()[name]["series"]
               if want <= s["labels"].items())


def test_faulty_after_a_bounded_number_of_calls_then_left_out(node):
    root = node.roots[DEAD]
    node.kill("file")
    def row():
        return next(d for d in DRIVEMON.snapshot()["drives"]
                    if d["endpoint"] == root)
    calls0 = row()["opsTotal"]
    n_put = 0
    while not DRIVEMON.is_quarantined(root):
        n_put += 1
        assert n_put <= FAULTY_AFTER, "the drive never turned faulty"
        node.put(f"warm/k{n_put}", BLOCK + n_put)
    assert row()["state"] == FAULTY and row()["quarantined"]
    # Bounded: two windows of sixteen failed calls, the thresholds that
    # were there.
    assert row()["opsTotal"] - calls0 <= FAULTY_AFTER + N
    issued = row()["opsTotal"]
    for i in range(6):
        node.put(f"after/k{i}", BLOCK + i)
        node.get(f"after/k{i}", BLOCK + i)
    node.get(*OLD)
    assert node.request("HEAD", node.c._key_path(BUCKET, OLD[0])).status == 200
    op_multipart(node)
    op_list(node)
    assert node.request(
        "DELETE", node.c._key_path(BUCKET, "after/k0")).status == 204
    assert row()["opsTotal"] == issued, "a call went to the faulty drive"
    node.no_5xx()
    node.check_dead_untouched()


def test_debt_is_kept_not_chased_and_healed_on_return(node):
    from minio_tpu.erasure.regen.repair import REPAIR_BYTES
    root = node.roots[DEAD]
    node.kill("file")
    DRIVEMON.quarantine(root, "test")     # past the first 32 calls
    read0 = dict(REPAIR_BYTES.snapshot().get("rs", {}))
    keys = [(f"debt/k{i}", BLOCK * 2 + i) for i in range(5)]
    for key, size in keys:
        node.put(key, size)
        node.put(key, size)               # an overwrite: ONE entry a key
    mp = node.multipart("debt/mp", [BLOCK + 3, 50])
    mrf = node.eng.mrf
    deadline = time.monotonic() + 10
    while mrf.parked() < len(keys) + 1:
        assert time.monotonic() < deadline, (mrf.parked(), mrf.depth())
        time.sleep(0.02)
    assert mrf.depth() == 0
    assert mrf.journal.backlog() == len(keys) + 1
    # Not chased: no survivor was read, by the MRF healer or by the
    # new-disk monitor, and a tick logs no traceback.
    assert node.eng.new_disk_monitor.tick() == []
    assert node.eng.new_disk_monitor.tick() == []
    assert dict(REPAIR_BYTES.snapshot().get("rs", {})) == read0
    node.check_dead_untouched()
    # The drive answers again: probation brings it back, the parked
    # entries are healed onto it.
    restore(root, "file", node.monkeypatch)
    node.dead.clear()
    deadline = time.monotonic() + 20
    while DRIVEMON.is_quarantined(root):
        assert time.monotonic() < deadline
        node.eng.quarantine_prober.tick()
    deadline = time.monotonic() + 30
    while mrf.parked() or mrf.depth() or mrf.journal.backlog():
        assert time.monotonic() < deadline, (
            mrf.parked(), mrf.depth(), mrf.journal.backlog())
        time.sleep(0.05)
    for key, size in keys:
        node.check_at_rest(key, [body_of(key, size)])
        node.get(key, size)
    node.check_at_rest("debt/mp", [body_of("debt/mp#1", BLOCK + 3),
                                   body_of("debt/mp#2", 50)])
    r = node.request("GET", node.c._key_path(BUCKET, "debt/mp"))
    assert r.status == 200 and r.body == mp
    node.check_at_rest(OLD[0], [body_of(*OLD)])
    node.no_5xx()


def test_spans_and_counters_of_a_dropped_leg(node):
    """`drive.offline` on the request's own tree where a leg is
    dropped; the counters the benchmark's degraded.* metrics read."""
    if not TRACER.enabled:
        pytest.skip("tracing is off in this run")
    root = node.roots[DEAD]
    node.kill("file")
    got0 = {k: _counter(k) for k in (
        "minio_tpu_v2_drive_op_errors_total",
        "minio_tpu_v2_mrf_entries_total",
        "minio_tpu_v2_drive_legs_skipped_total",
        "minio_tpu_v2_drive_faulty_calls_total",
        "minio_tpu_v2_heal_attempts_total")}
    r = node.put(*NEW)
    tree_ = next(t for t in TRACER.recent(50)
                 if t.get("traceId") == r.headers["x-amz-request-id"])

    def events(span):
        yield from span.get("events", [])
        for ch in span.get("children", []):
            yield from events(ch)
    offline = [e for e in events(tree_) if e["name"] == "drive.offline"]
    assert offline, json.dumps(tree_)[:2000]
    assert {e["drive"] for e in offline} == {root}
    assert {e["errno"] for e in offline} == {errno.ENOTDIR}
    assert {"append_file", "stat_volume"} & {e["op"] for e in offline}
    assert _counter("minio_tpu_v2_drive_op_errors_total") \
        > got0["minio_tpu_v2_drive_op_errors_total"]
    assert _counter("minio_tpu_v2_mrf_entries_total") \
        == got0["minio_tpu_v2_mrf_entries_total"] + 1
    DRIVEMON.quarantine(root, "test")
    node.put("c/k", BLOCK)
    node.get("c/k", BLOCK)
    assert _counter("minio_tpu_v2_drive_legs_skipped_total") \
        > got0["minio_tpu_v2_drive_legs_skipped_total"]
    # A served decode is on the profiler's clock under its own name
    # (obs/span.py mirrors phases and kernel.* spans as
    # jax.profiler.TraceAnnotations), so the traced slice's idle gaps
    # around it are named.
    from minio_tpu.obs import span as span_mod
    named: list[str] = []
    real = span_mod.annotation
    node.monkeypatch.setattr(
        span_mod, "annotation",
        lambda name, **kw: named.append(name) or real(name, **kw))
    node.get(*OLD)
    assert {"ec.decode", "kernel.rs_decode"} <= set(named)
    deadline = time.monotonic() + 10
    while node.eng.mrf.parked() < 1:
        assert time.monotonic() < deadline
        time.sleep(0.02)
    assert _counter("minio_tpu_v2_heal_attempts_total",
                    {"by": "mrf", "outcome": "abandoned_offline"}) >= 1
    node.eng.new_disk_monitor.tick()
    assert _counter("minio_tpu_v2_heal_attempts_total",
                    {"by": "newdisk", "outcome": "abandoned_offline"}) >= 1
    node.eng.quarantine_prober.tick()     # a probe IS a call to it
    assert _counter("minio_tpu_v2_drive_faulty_calls_total") \
        > got0["minio_tpu_v2_drive_faulty_calls_total"]
    node.no_5xx()


# -- and a healthy leg costs what it cost ------------------------------------


@pytest.mark.skipif(native.get_lib() is None, reason="no native library")
def test_a_healthy_leg_makes_the_calls_it_made(tmp_path):
    """PR 33's count, unchanged by the seam: 18 file-system calls a
    fresh leg of a PUT (two appends, one commit), 24 overwriting."""
    from minio_tpu.storage.metadata import (ErasureInfo, FileInfo,
                                            ObjectPartInfo)
    disk = XLStorage(str(tmp_path / "d1"))
    disk.make_volume(BUCKET)

    def syscalls() -> float:
        return _counter("minio_tpu_v2_disk_op_syscalls_total")

    def leg(dd: str) -> float:
        stage = f"tmp/{uuid.uuid4()}"
        c0 = syscalls()
        disk.append_file(MINIO_META_BUCKET, f"{stage}/intent.json", b"{}")
        disk.append_file(MINIO_META_BUCKET, f"{stage}/{dd}/part.1",
                         b"x" * 4096)
        fi = FileInfo(volume=BUCKET, name="k", data_dir=dd, size=4096,
                      parts=[ObjectPartInfo(1, 4096, 4096)],
                      erasure=ErasureInfo(data_blocks=K, parity_blocks=M,
                                          block_size=BLOCK, index=1,
                                          distribution=list(range(1, N + 1))))
        disk.rename_data(MINIO_META_BUCKET, stage, fi, BUCKET, "k")
        return syscalls() - c0

    assert leg(str(uuid.uuid4())) == 18
    assert leg(str(uuid.uuid4())) == 24
