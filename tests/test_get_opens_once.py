"""A GET opens its object once (PR 29): through the S3 handler on an
ErasureServerPools -> ErasureSets -> ErasureObjects stack with counting
drives, every GET / HEAD makes ONE xl.meta read a drive under ONE read
lock inside ONE ec.meta span and stats no bucket; a missing bucket
still answers NoSuchBucket (from that read's own errors), the lock is
free again whichever way the request ends, and headers, preconditions
and body come from one version."""

import collections
import hashlib
import shutil
import threading
import time
import uuid

import pytest

from minio_tpu.erasure.engine import BucketNotFound, ErasureObjects
from minio_tpu.erasure.pools import ErasureServerPools
from minio_tpu.erasure.sets import SET_BYTES, ErasureSets
from minio_tpu.obs.metrics2 import METRICS2
from minio_tpu.obs.span import TRACER
from minio_tpu.s3 import server as s3server
from minio_tpu.s3.client import S3Client
from minio_tpu.storage import errors as serr
from minio_tpu.storage.xl import XLStorage

ACCESS, SECRET = "onceadmin", "onceadmin-secret"
DRIVES = 6  # 4+2, the small cell's geometry
BUCKET = "census"
BODY = bytes(range(256)) * 1024  # 256 KiB
ETAG = hashlib.md5(BODY).hexdigest()


class CountingDisk(XLStorage):
    """A drive that counts the two metadata calls of the read path,
    by volume (the server's own housekeeping reads other volumes)."""

    def __init__(self, root: str):
        super().__init__(root)
        self.calls = collections.Counter()

    offline = False

    def read_version(self, volume, path, version_id=""):
        self.calls["read_version", volume] += 1
        if self.offline:
            raise serr.DiskNotFound("offline")
        return super().read_version(volume, path, version_id)

    def stat_volume(self, volume):
        self.calls["stat_volume", volume] += 1
        return super().stat_volume(volume)


@pytest.fixture(scope="module")
def stack(tmp_path_factory):
    root = tmp_path_factory.mktemp("oncedisks")
    disks = [CountingDisk(str(root / f"d{i}")) for i in range(DRIVES)]
    sets = ErasureSets(disks, [DRIVES], str(uuid.uuid4()),
                       data_shards=4, parity_shards=2,
                       block_size=64 * 1024)
    layer = ErasureServerPools([sets])
    srv = s3server.S3Server(layer, ACCESS, SECRET)
    port = srv.start()
    c = S3Client("127.0.0.1", port, ACCESS, SECRET)
    assert c.make_bucket(BUCKET).status == 200
    assert c.put_object(BUCKET, "obj", BODY).status == 200
    yield layer, c, disks
    srv.stop()


def _counts(disks, op: str, volume: str) -> list[int]:
    return [d.calls[op, volume] for d in disks]


def _reset(disks) -> None:
    for d in disks:
        d.calls.clear()
    TRACER.reset()


def _request_tree(path: str) -> dict:
    """The newest finished object-API tree of this path (a streamed
    GET's root finishes on the drain task, after the client has its
    bytes)."""
    deadline = time.monotonic() + 5.0
    while True:
        for t in reversed(TRACER.recent(64)):
            if t["name"].endswith("-object") \
                    and t["tags"].get("path") == path:
                return t
        assert time.monotonic() < deadline, f"no tree for {path}"
        time.sleep(0.02)


def _assert_lock_free(layer, bucket: str, key: str) -> None:
    """A write lock on the key is taken without waiting for a reader
    (the stream's lock goes on the drain task, a moment after the
    client has its last byte: hence the short retry)."""
    eng = layer.pools[0].set_for(key)
    deadline = time.monotonic() + 2.0
    while True:
        try:
            with eng.ns_lock.write_locked(bucket, key, timeout=0.05):
                return
        except TimeoutError:
            assert time.monotonic() < deadline, \
                f"a read lock on {bucket}/{key} was leaked"


# -- the fan-out census -----------------------------------------------------

REQUESTS = {
    "get": ("GET", {}, 200, BODY),
    "ranged_get": ("GET", {"range": "bytes=1000-70999"}, 206,
                   BODY[1000:71000]),
    "head": ("HEAD", {}, 200, b""),
    "if_none_match_304": ("GET", {"if-none-match": f'"{ETAG}"'}, 304,
                          b""),
    "if_match_412": ("GET", {"if-match": '"not-the-etag"'}, 412, None),
    "unsatisfiable_range_416": ("GET", {"range": "bytes=999999999-"},
                                416, None),
}


@pytest.mark.parametrize("case", list(REQUESTS))
def test_a_request_reads_xlmeta_once_and_stats_no_bucket(stack, case):
    layer, c, disks = stack
    method, headers, status, body = REQUESTS[case]
    _reset(disks)
    counted = METRICS2.get(SET_BYTES, {"set": "0", "op": "get"})
    r = c.request(method, f"/{BUCKET}/obj", headers=headers)
    assert r.status == status
    if body is not None:
        assert r.body == body
    if status in (200, 206, 304):
        assert r.headers["etag"].strip('"') == ETAG
    tree = _request_tree(f"/{BUCKET}/obj")
    assert _counts(disks, "read_version", BUCKET) == [1] * DRIVES
    assert _counts(disks, "stat_volume", BUCKET) == [0] * DRIVES
    phases = [ch["name"] for ch in tree["children"]]
    assert phases.count("ec.meta") == 1, phases
    assert phases.count("lock.wait") == 1, phases
    _assert_lock_free(layer, BUCKET, "obj")
    # The stream the handle hands out still counts the set's GET bytes
    # (it is read to its end by the time the lock is free).
    assert METRICS2.get(SET_BYTES, {"set": "0", "op": "get"}) - counted \
        == len(body or b"")


@pytest.mark.parametrize("entry", ["get_object_info",
                                   "get_object_stream", "open_object"])
def test_a_direct_call_reads_xlmeta_once_and_stats_no_bucket(stack,
                                                             entry):
    layer, _, disks = stack
    _reset(disks)
    if entry == "get_object_info":
        info = layer.get_object_info(BUCKET, "obj")
    elif entry == "get_object_stream":
        info, stream = layer.get_object_stream(BUCKET, "obj")
        assert b"".join(stream) == BODY
    else:
        with layer.open_object(BUCKET, "obj") as h:
            info = h.info
            assert b"".join(h.stream(10, 100)) == BODY[10:110]
    assert info.etag == ETAG and info.size == len(BODY)
    assert _counts(disks, "read_version", BUCKET) == [1] * DRIVES
    assert _counts(disks, "stat_volume", BUCKET) == [0] * DRIVES
    _assert_lock_free(layer, BUCKET, "obj")


def test_a_degraded_open_hands_the_agreed_copies_to_the_stream(stack):
    """Two drives without their copy: the one read's `agreed` reaches
    the stream with their Nones, and the bytes are reconstructed."""
    layer, c, disks = stack
    assert c.put_object(BUCKET, "two-lost", BODY).status == 200
    for d in disks[1:3]:
        shutil.rmtree(f"{d.root}/{BUCKET}/two-lost")
    _reset(disks)
    r = c.get_object(BUCKET, "two-lost")
    assert r.status == 200 and r.body == BODY
    assert _counts(disks, "read_version", BUCKET) == [1] * DRIVES
    assert _counts(disks, "stat_volume", BUCKET) == [0] * DRIVES


# -- the bucket's existence, from the read's own errors ---------------------

# (drives without the bucket volume, key present) -> (status, S3 code),
# pinned on the parent commit (503a087: two bucket stats and two
# xl.meta reads a GET) before the stats were taken out. 4+2 on six
# drives: the read quorum is 4, a not-found majority is 4.
MISSING_VOLUME = {
    (6, True): (404, "NoSuchBucket"),
    (6, False): (404, "NoSuchBucket"),
    (4, True): (404, "NoSuchBucket"),
    (4, False): (404, "NoSuchBucket"),
    (3, True): (503, "SlowDown"),
    (3, False): (503, "SlowDown"),
    (1, True): (200, ""),
    (1, False): (404, "NoSuchKey"),
}


def _code(r) -> str:
    body = r.body.decode(errors="replace")
    if "<Code>" not in body:
        return ""
    return body.split("<Code>")[1].split("</Code>")[0]


@pytest.mark.parametrize("missing,present", list(MISSING_VOLUME))
def test_a_missing_bucket_volume_answers_as_the_parent_did(
        stack, missing, present):
    layer, c, disks = stack
    bucket = f"vol-{missing}-{int(present)}"
    assert c.make_bucket(bucket).status == 200
    if present:
        assert c.put_object(bucket, "k", BODY).status == 200
    for d in disks[:missing]:
        shutil.rmtree(f"{d.root}/{bucket}")
    want = MISSING_VOLUME[missing, present]
    _reset(disks)
    r = c.get_object(bucket, "k")
    assert (r.status, _code(r)) == want
    if r.status == 200:
        assert r.body == BODY
    assert c.head_object(bucket, "k").status == want[0]
    assert _counts(disks, "stat_volume", bucket) == [0] * DRIVES
    _assert_lock_free(layer, bucket, "k")


def test_an_outage_is_not_a_missing_bucket(stack):
    """Every drive failing its xl.meta read with an I/O error: the
    parent's bucket stat (which counted answers, and got none) said
    NoSuchBucket; the read's own errors hold no VolumeNotFound, so it
    is the quorum 503 the reference gives, and a retry succeeds."""
    layer, c, disks = stack
    for d in disks:
        d.offline = True
    try:
        r = c.get_object(BUCKET, "obj")
        assert (r.status, _code(r)) == (503, "SlowDown")
        assert c.head_object(BUCKET, "obj").status == 503
    finally:
        for d in disks:
            d.offline = False
    _assert_lock_free(layer, BUCKET, "obj")
    assert c.get_object(BUCKET, "obj").body == BODY


@pytest.mark.parametrize("entry", ["get_object_info",
                                   "get_object_stream", "open_object"])
def test_the_system_namespace_stays_unreachable(stack, entry):
    layer, c, _ = stack
    with pytest.raises(BucketNotFound):
        getattr(layer, entry)(".minio.sys", "config/iam/format.json")
    with pytest.raises(BucketNotFound):
        getattr(layer, entry)(".minio.sys/buckets", "census")
    r = c.get_object(".minio.sys", "format.json")
    assert (r.status, _code(r)) == (404, "NoSuchBucket")


# -- the read lock, whichever way the request ends --------------------------


def test_the_lock_is_free_after_an_exception_between_open_and_stream(
        stack, monkeypatch):
    layer, c, disks = stack

    def boom(req, info, prefix=""):
        raise RuntimeError("between open and stream")

    monkeypatch.setattr(s3server, "check_preconditions", boom)
    r = c.get_object(BUCKET, "obj")
    assert r.status == 500
    monkeypatch.undo()
    _assert_lock_free(layer, BUCKET, "obj")
    assert c.get_object(BUCKET, "obj").body == BODY


def test_a_handle_releases_its_lock_once(stack):
    layer, _, _ = stack
    eng = layer.pools[0].set_for("obj")
    h = eng.open_object(BUCKET, "obj")
    with pytest.raises(TimeoutError):
        with eng.ns_lock.write_locked(BUCKET, "obj", timeout=0.05):
            pass
    with pytest.raises(ValueError):
        h.stream(len(BODY) + 1, 1)  # a refused range lets the lock go
    _assert_lock_free(layer, BUCKET, "obj")
    h.close()
    h.close()
    with pytest.raises(RuntimeError):
        h.stream()
    _assert_lock_free(layer, BUCKET, "obj")
    # A stream that was taken owns the lock; close() of the handle
    # then leaves it alone, the stream's end releases it.
    with eng.open_object(BUCKET, "obj") as h:
        stream = h.stream()
    with pytest.raises(TimeoutError):
        with eng.ns_lock.write_locked(BUCKET, "obj", timeout=0.05):
            pass
    assert b"".join(stream) == BODY
    _assert_lock_free(layer, BUCKET, "obj")


# -- one version a response -------------------------------------------------

V1 = b"1" * 300_000
V2 = b"2" * 123_457


@pytest.mark.parametrize("headers,want", [
    ({}, V1),
    ({"if-match": f'"{hashlib.md5(V1).hexdigest()}"'}, V1),
    ({"range": "bytes=200000-"}, V1[200000:]),
], ids=["whole", "if_match", "range_past_the_new_size"])
def test_a_get_racing_an_overwrite_answers_from_one_version(
        stack, monkeypatch, headers, want):
    """The overwrite arrives after the GET's preconditions have passed
    on version 1 and before a byte is streamed. The parent read its
    metadata again there, took version 2's, and answered 500 (version
    1's length asked of version 2); now the GET holds its read lock and
    the PUT's commit waits for the stream's end."""
    layer, c, _ = stack
    key = "raced-" + ("-".join(headers) or "plain")
    assert c.put_object(BUCKET, key, V1).status == 200
    real = s3server.check_preconditions
    put = {}

    def overwrite():
        c2 = S3Client("127.0.0.1", c.port, ACCESS, SECRET)
        put["r"] = c2.put_object(BUCKET, key, V2)

    writer = threading.Thread(target=overwrite)

    def preconditions_then_overwrite(req, info, prefix=""):
        status = real(req, info, prefix)
        if req.method == "GET":
            writer.start()
            writer.join(0.5)
        return status

    monkeypatch.setattr(s3server, "check_preconditions",
                        preconditions_then_overwrite)
    r = c.get_object(BUCKET, key, headers=headers)
    monkeypatch.undo()
    writer.join(10)
    assert put["r"].status == 200
    assert r.status in (200, 206)
    assert r.body == want
    assert r.headers["etag"].strip('"') == hashlib.md5(V1).hexdigest()
    assert int(r.headers["content-length"]) == len(want)
    after = c.get_object(BUCKET, key)
    assert after.body == V2
    assert after.headers["etag"].strip('"') == hashlib.md5(V2).hexdigest()



# -- the hot-object cache, through the handle -------------------------------


@pytest.fixture
def hot(stack, tmp_path):
    """The stack with the process-wide cache on (memory for one object
    and a half, the rest demoted to a disk tier), off again after."""
    from minio_tpu.cache.hotcache import HOTCACHE
    HOTCACHE.reset()
    HOTCACHE.configure(enable=True, mem_bytes=int(len(BODY) * 1.5),
                       disk_bytes=1 << 30, dirs=[str(tmp_path / "cache")],
                       min_hits=1, max_object_bytes=8 << 20,
                       revalidate_s=3600.0)
    yield HOTCACHE
    HOTCACHE.configure(enable=False, mem_bytes=128 << 20,
                       disk_bytes=1 << 30, dirs=[], min_hits=1,
                       max_object_bytes=32 << 20, revalidate_s=1.0)
    HOTCACHE.reset()


def test_the_open_consults_the_hot_cache_as_stat_and_stream_did(stack, hot):
    """Cold: one xl.meta read a drive, and the read fills the cache.
    Hot: the memory tier answers stat and bytes, no drive is asked and
    no lock taken. Demoted to the disk tier: the stat is the quorum
    read, and the open's own `info` revalidates the entry (no second
    read, no second lock under a waiting writer)."""
    layer, c, disks = stack
    other = BODY[::-1]
    assert c.put_object(BUCKET, "hot-1", BODY).status == 200
    assert c.put_object(BUCKET, "hot-2", other).status == 200
    eng = layer.pools[0].set_for("hot-1")

    _reset(disks)
    r = c.get_object(BUCKET, "hot-1")                      # cold: fills
    assert r.status == 200 and r.body == BODY
    assert _counts(disks, "read_version", BUCKET) == [1] * DRIVES
    _assert_lock_free(layer, BUCKET, "hot-1")
    assert hot.snapshot()["counters"]["fill"] == 1

    # The next fill takes the memory tier and demotes hot-1 to disk.
    assert c.get_object(BUCKET, "hot-2").body == other
    _assert_lock_free(layer, BUCKET, "hot-2")
    assert hot.snapshot()["diskEntries"] == 1
    _reset(disks)
    r = c.get_object(BUCKET, "hot-1")
    assert r.status == 200 and r.body == BODY
    assert _counts(disks, "read_version", BUCKET) == [1] * DRIVES
    assert hot.snapshot()["counters"]["hit_disk"] == 1
    _assert_lock_free(layer, BUCKET, "hot-1")

    _reset(disks)
    with eng.ns_lock.write_locked(BUCKET, "hot-2"):        # no lock needed
        r = c.get_object(BUCKET, "hot-2", headers={"range": "bytes=5-14"})
    assert r.status == 206 and r.body == other[5:15]
    assert r.headers["etag"].strip('"') == hashlib.md5(other).hexdigest()
    assert _counts(disks, "read_version", BUCKET) == [0] * DRIVES
    assert hot.snapshot()["counters"]["hit_mem"] == 1
    assert _counts(disks, "stat_volume", BUCKET) == [0] * DRIVES
