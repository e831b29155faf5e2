"""XLStorage + metadata format tests (ref test strategy SURVEY §4: real
disks in $TMPDIR, no mock FS)."""

import os

import pytest

from minio_tpu.storage import errors as serr
from minio_tpu.storage.metadata import (ErasureInfo, FileInfo, XLMeta,
                                        new_data_dir)
from minio_tpu.storage.xl import MINIO_META_BUCKET, XLStorage


@pytest.fixture
def disk(tmp_path):
    return XLStorage(str(tmp_path / "disk0"))


def test_volume_lifecycle(disk):
    disk.make_volume("bucket1")
    assert "bucket1" in disk.list_volumes()
    with pytest.raises(serr.VolumeExists):
        disk.make_volume("bucket1")
    assert disk.stat_volume("bucket1")["name"] == "bucket1"
    disk.delete_volume("bucket1")
    with pytest.raises(serr.VolumeNotFound):
        disk.stat_volume("bucket1")


def test_invalid_volume_names(disk):
    for bad in ("", ".", "..", "a/b"):
        with pytest.raises(serr.VolumeNotFound):
            disk.make_volume(bad)


def test_file_roundtrip(disk):
    disk.make_volume("v")
    disk.write_all("v", "a/b/c.txt", b"hello")
    assert disk.read_all("v", "a/b/c.txt") == b"hello"
    assert disk.read_file("v", "a/b/c.txt", 1, 3) == b"ell"
    with pytest.raises(serr.FileNotFound):
        disk.read_all("v", "missing")
    disk.delete("v", "a/b/c.txt")
    with pytest.raises(serr.FileNotFound):
        disk.read_all("v", "a/b/c.txt")
    # Parent prefix dirs pruned after delete.
    assert disk.list_dir("v", "") == []


READERS = {"read_all": lambda d, v, p: d.read_all(v, p),
           "read_file": lambda d, v, p: d.read_file(v, p, 1, 3)}


@pytest.mark.parametrize("gone, raised", [
    ("key", serr.FileNotFound), ("prefix", serr.FileNotFound),
    ("bucket", serr.VolumeNotFound), ("bucket_is_a_file", serr.VolumeNotFound),
    ("below_a_file", serr.FaultyDisk)])
@pytest.mark.parametrize("reader", sorted(READERS))
def test_a_read_miss_is_typed_by_what_is_gone(disk, reader, gone, raised):
    """The readers open first and look at the volume only when the open
    fails: the typed result is what the check in front used to give (the
    engine takes BucketNotFound from VolumeNotFound)."""
    disk.make_volume("v")
    disk.write_all("v", "plain", b"hello")
    with open(os.path.join(disk.root, "flat"), "wb") as f:
        f.write(b"a drive's bucket directory replaced by a file")
    volume, path = {"key": ("v", "missing"),
                    "prefix": ("v", "no/such/prefix/key"),
                    "bucket": ("nobucket", "plain"),
                    "bucket_is_a_file": ("flat", "plain"),
                    "below_a_file": ("v", "plain/below")}[gone]
    with pytest.raises(raised):
        READERS[reader](disk, volume, path)
    assert not os.path.exists(os.path.join(disk.root, "nobucket"))


@pytest.mark.parametrize("reader", sorted(READERS))
def test_a_read_hit_asks_nothing_about_its_volume(disk, reader,
                                                  monkeypatch):
    disk.make_volume("v")
    disk.write_all("v", "a/b.txt", b"hello")
    asked = []

    def counting(real):
        def call(path, *a, **kw):
            asked.append(path)
            return real(path, *a, **kw)
        return call
    monkeypatch.setattr(os, "stat", counting(os.stat))
    monkeypatch.setattr(os.path, "isdir", counting(os.path.isdir))
    want = b"hello" if reader == "read_all" else b"ell"
    assert READERS[reader](disk, "v", "a/b.txt") == want
    assert asked == []
    # ... and a miss does ask, once it has missed.
    with pytest.raises(serr.FileNotFound):
        READERS[reader](disk, "v", "a/missing")
    assert os.path.join(disk.root, "v") in asked


def count_os_calls(monkeypatch, *names):
    made = []
    for name in names:
        real = getattr(os, name)
        monkeypatch.setattr(
            os, name, lambda *a, _n=name, _r=real, **kw: (
                made.append(_n), _r(*a, **kw))[1])
    return made


@pytest.mark.parametrize("size", [0, 5, 65535, 65536, 65537, 300_000])
def test_read_all_makes_the_calls_its_size_allows(disk, monkeypatch, size):
    """open, read, the read that says "no more", close; a file past the
    first read's 64 KiB is sized once and its rest read in one piece."""
    body = os.urandom(size)
    disk.make_volume("v")
    disk.write_all("v", "k", body)
    made = count_os_calls(monkeypatch, "open", "read", "fstat", "stat",
                          "close", "lseek")
    assert disk.read_all("v", "k") == body
    if size == 0:
        assert made == ["open", "read", "close"]
    elif size < 65536:
        assert made == ["open", "read", "read", "close"]
    else:
        want = ["open", "read", "fstat", "read"]
        assert made == want + (["read"] if size > 65536 else []) + ["close"]


@pytest.mark.parametrize("offset, length, want", [
    (0, 1000, slice(0, 1000)), (100, 50, slice(100, 150)),
    (900, 500, slice(900, 1000)), (1000, 10, slice(0, 0)),
    (2000, 10, slice(0, 0)), (5, 0, slice(0, 0))])
def test_read_file_is_one_pread_inside_the_file(disk, monkeypatch, offset,
                                                length, want):
    body = os.urandom(1000)
    disk.make_volume("v")
    disk.write_all("v", "k", body)
    made = count_os_calls(monkeypatch, "open", "pread", "read", "fstat",
                          "stat", "close", "lseek")
    assert disk.read_file("v", "k", offset, length) == body[want]
    inside = offset + length <= 1000 or offset >= 1000
    assert made == ["open", "pread"] + ([] if inside else ["pread"]) \
        + ["close"]


@pytest.mark.parametrize("reader", sorted(READERS))
def test_a_read_of_a_directory_is_typed_as_it_was(disk, reader):
    disk.make_volume("v")
    disk.write_all("v", "a/b.txt", b"hello")
    with pytest.raises(serr.FileNotFound if reader == "read_all"
                       else serr.FaultyDisk):
        READERS[reader](disk, "v", "a")


def test_stat_volume_is_one_stat(disk, monkeypatch):
    disk.make_volume("v")
    made = count_os_calls(monkeypatch, "stat")
    assert disk.stat_volume("v")["name"] == "v"
    assert made == ["stat"]
    with pytest.raises(serr.VolumeNotFound):
        disk.stat_volume("nobucket")
    with open(os.path.join(disk.root, "flat"), "wb") as f:
        f.write(b"x")
    with pytest.raises(serr.VolumeNotFound):
        disk.stat_volume("flat")
    # The system volume self-creates, as before.
    import shutil
    shutil.rmtree(os.path.join(disk.root, MINIO_META_BUCKET))
    assert disk.stat_volume(MINIO_META_BUCKET)["name"] == MINIO_META_BUCKET
    assert os.path.isdir(os.path.join(disk.root, MINIO_META_BUCKET, "tmp"))


def test_path_traversal_blocked(disk):
    disk.make_volume("v")
    with pytest.raises(serr.StorageError):
        disk.write_all("v", "../../etc/passwd", b"x")


def test_rename_file(disk):
    disk.make_volume("v")
    disk.make_volume("w")
    disk.write_all("v", "src.txt", b"data")
    disk.rename_file("v", "src.txt", "w", "dst/deep.txt")
    assert disk.read_all("w", "dst/deep.txt") == b"data"
    with pytest.raises(serr.FileNotFound):
        disk.read_all("v", "src.txt")


def test_xlmeta_version_merge():
    meta = XLMeta()
    fi1 = FileInfo(volume="b", name="o", version_id="v1", size=10,
                   mod_time=1.0)
    fi2 = FileInfo(volume="b", name="o", version_id="v2", size=20,
                   mod_time=2.0)
    meta.add_version(fi1)
    meta.add_version(fi2)
    assert meta.versions[0]["versionId"] == "v2"  # newest first
    # Replace same version id.
    fi2b = FileInfo(volume="b", name="o", version_id="v2", size=25,
                    mod_time=3.0)
    meta.add_version(fi2b)
    assert len(meta.versions) == 2
    assert meta.find_version("v2")["size"] == 25
    # Round-trip through bytes.
    again = XLMeta.load(meta.dump())
    assert again.versions == meta.versions


def test_rename_data_commit(disk):
    disk.make_volume("bucket")
    dd = new_data_dir()
    tmp = "tmp/stage1"
    disk.create_file(MINIO_META_BUCKET, f"{tmp}/{dd}/part.1", b"shard-bytes")
    fi = FileInfo(volume="bucket", name="obj/key", data_dir=dd, size=11,
                  mod_time=1.0,
                  erasure=ErasureInfo(data_blocks=2, parity_blocks=1,
                                      block_size=1024, index=1,
                                      distribution=[1, 2, 3]))
    disk.rename_data(MINIO_META_BUCKET, tmp, fi, "bucket", "obj/key")
    got = disk.read_version("bucket", "obj/key")
    assert got.size == 11 and got.data_dir == dd
    assert disk.read_all("bucket", f"obj/key/{dd}/part.1") == b"shard-bytes"
    # Tmp staging is gone.
    with pytest.raises(serr.FileNotFound):
        disk.read_all(MINIO_META_BUCKET, f"{tmp}/{dd}/part.1")


def test_rename_data_null_version_overwrite_frees_old_datadir(disk):
    disk.make_volume("b")
    for round_ in range(2):
        dd = new_data_dir()
        tmp = f"tmp/stage{round_}"
        disk.create_file(MINIO_META_BUCKET, f"{tmp}/{dd}/part.1",
                         f"data{round_}".encode())
        fi = FileInfo(volume="b", name="o", data_dir=dd,
                      size=5, mod_time=float(round_ + 1))
        disk.rename_data(MINIO_META_BUCKET, tmp, fi, "b", "o")
    meta_dirs = [e for e in disk.list_dir("b", "o") if e.endswith("/")]
    assert len(meta_dirs) == 1  # old data dir removed on overwrite
    assert disk.read_version("b", "o").size == 5


def test_delete_version_lifecycle(disk):
    disk.make_volume("b")
    fi1 = FileInfo(volume="b", name="o", version_id="v1", mod_time=1.0)
    fi2 = FileInfo(volume="b", name="o", version_id="v2", mod_time=2.0)
    disk.write_metadata("b", "o", fi1)
    disk.write_metadata("b", "o", fi2)
    disk.delete_version("b", "o", fi1)
    assert disk.read_version("b", "o").version_id == "v2"
    disk.delete_version("b", "o", fi2)
    with pytest.raises(serr.FileNotFound):
        disk.read_version("b", "o")
    with pytest.raises(serr.FileNotFound):
        disk.delete_version("b", "o2", fi1)


def test_verify_file_detects_corruption(disk, tmp_path):
    from minio_tpu.erasure import bitrot
    disk.make_volume("b")
    dd = new_data_dir()
    shard_size = 64
    payload = os.urandom(200)
    stream = bitrot.encode_stream(payload, shard_size)
    disk.write_all("b", f"o/{dd}/part.1", stream)
    fi = FileInfo(volume="b", name="o", data_dir=dd, size=200,
                  erasure=ErasureInfo(data_blocks=2, parity_blocks=1,
                                      block_size=128, index=1),
                  parts=[])
    from minio_tpu.storage.metadata import ObjectPartInfo
    fi.parts = [ObjectPartInfo(number=1, size=200, actual_size=200)]
    fi.erasure.block_size = shard_size * 2
    disk.verify_file("b", "o", fi)  # clean
    # Corrupt one byte mid-stream.
    bad = bytearray(stream)
    bad[50] ^= 0xFF
    disk.write_all("b", f"o/{dd}/part.1", bytes(bad))
    with pytest.raises(serr.FileCorrupt):
        disk.verify_file("b", "o", fi)
