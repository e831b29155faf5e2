"""A multipart upload keeps its storage class from initiate to complete
(PR 32; ref newMultipartUpload / PutObjectPart, cmd/erasure-multipart.go):
on the served path (front door -> pools -> sets -> engine) every part
file and the completed xl.meta carry the k+m the class gave at initiate,
byte for byte what the tests' own reference gives per part (ops/rs_cpu
and the pure-Python HighwayHash-256, both pinned by tests/test_golden.py),
and everything that reads such an object takes k+m from xl.meta."""

import json
import os
import re
import shutil
import uuid

import numpy as np
import pytest

from minio_tpu.config.storageclass import StorageClassConfig
from minio_tpu.erasure.multipart import _upload_base
from minio_tpu.erasure.pools import ErasureServerPools
from minio_tpu.erasure.sets import ErasureSets
from minio_tpu.ops import rs_cpu
from minio_tpu.ops.hh256 import hh256
from minio_tpu.s3.client import S3Client
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.xl import MINIO_META_BUCKET, XLStorage

ACCESS, SECRET = "mpuclass", "mpuclass-secret"
BUCKET, KEY = "mpusc", "ckpt/step-7.bin"
MiB = 1 << 20


def reference_shard_files(part: bytes, k: int, m: int, block: int
                          ) -> list[bytes]:
    """The k+m streaming-bitrot shard files of one part: each stripe
    block split and RS-encoded on its own, ceil(len / k) bytes a shard,
    framed [32-byte HighwayHash-256][sub-block]."""
    files = [bytearray() for _ in range(k + m)]
    for lo in range(0, len(part), block):
        shards = rs_cpu.encode_data(part[lo:lo + block], k, m)
        for j, row in enumerate(shards):
            files[j] += hh256(row.tobytes()) + row.tobytes()
    return [bytes(f) for f in files]


def _initiate(c: S3Client, path: str, headers=None) -> str:
    r = c.request("POST", path, query="uploads", headers=headers or {})
    assert r.status == 200, r.body
    return re.search(rb"<UploadId>([^<]+)</UploadId>", r.body).group(
        1).decode()


def _complete(c: S3Client, path: str, uid: str, etags: list[str]):
    doc = "".join(f"<Part><PartNumber>{i}</PartNumber><ETag>\"{e}\"</ETag>"
                  "</Part>" for i, e in enumerate(etags, start=1))
    r = c.request("POST", path, query=f"uploadId={uid}",
                  body=f"<CompleteMultipartUpload>{doc}"
                       "</CompleteMultipartUpload>".encode())
    assert r.status == 200 and b"<Error>" not in r.body, r.body


def _upload(c: S3Client, path: str, uid: str, parts: list[bytes],
            part_headers=None) -> list[str]:
    etags = []
    for i, body in enumerate(parts, start=1):
        r = c.request("PUT", path, query=f"partNumber={i}&uploadId={uid}",
                      body=body, headers=part_headers or {})
        assert r.status == 200, r.body
        etags.append(r.headers["etag"].strip('"'))
    return etags


CASES = [
    # 6 drives, MINIO_STORAGE_CLASS_STANDARD=EC:2: 4+2, the set's
    # default being 3+3 (PERF.md's CPU rehearsal of the fault).
    pytest.param(dict(drives=6, standard=2, header=None, k=4, m=2,
                      block=64 * 1024, sizes=[150_001, 64 * 1024, 40]),
                 id="6d-EC2"),
    # BASELINE configs[2]: 16 drives, EC:4 -> 12+4 at the 10 MiB stripe
    # block. A 10 MiB + 1 B part is one 873,814-byte shard (27,306
    # packets and a 22-byte remainder) and a 1-byte tail block.
    pytest.param(dict(drives=16, standard=4, header=None, k=12, m=4,
                      block=10 * MiB, sizes=[10 * MiB + 1, 4321]),
                 id="16d-EC4"),
    # The class named by the request, not the configuration: 12 drives,
    # x-amz-storage-class: REDUCED_REDUNDANCY at initiate -> 10+2.
    pytest.param(dict(drives=12, standard=None,
                      header="REDUCED_REDUNDANCY", k=10, m=2,
                      block=64 * 1024, sizes=[150_001, 64 * 1024, 40]),
                 id="12d-RRS-header"),
]


@pytest.mark.parametrize("case", CASES)
def test_upload_keeps_its_class_from_initiate_to_complete(tmp_path, case):
    n, k, m, block = case["drives"], case["k"], case["m"], case["block"]
    roots = [str(tmp_path / f"d{i}") for i in range(n)]
    sets = ErasureSets([XLStorage(r) for r in roots], [n],
                       str(uuid.uuid4()), block_size=block)
    eng = sets.sets[0]
    eng.multipart.min_part_size = 1024
    assert (eng.k, eng.m) == (n // 2, n // 2) != (k, m)
    srv = S3Server(ErasureServerPools([sets]), ACCESS, SECRET)
    srv.handlers.storage_class = StorageClassConfig(
        standard_parity=case["standard"])
    port = srv.start()
    try:
        c = S3Client("127.0.0.1", port, ACCESS, SECRET)
        assert c.make_bucket(BUCKET).status == 200
        path = c._key_path(BUCKET, KEY)
        rng = np.random.default_rng(32)
        parts = [rng.integers(0, 256, s, dtype=np.uint8).tobytes()
                 for s in case["sizes"]]
        whole = b"".join(parts)
        want = [reference_shard_files(p, k, m, block) for p in parts]
        if n == 16:
            assert len(want[0][0]) == 32 + 873_814 + 32 + 1

        init_hdr = ({"x-amz-storage-class": case["header"]}
                    if case["header"] else {})
        uid = _initiate(c, path, init_hdr)
        base = _upload_base(BUCKET, KEY, uid)
        mpu = [os.path.join(r, MINIO_META_BUCKET, base) for r in roots]
        for d in mpu:
            with open(os.path.join(d, "upload.json")) as f:
                up = json.load(f)
            assert up["parity"] == m
        # A class header on UploadPart changes nothing (the other class).
        other = "STANDARD" if case["header"] else "REDUCED_REDUNDANCY"
        etags = _upload(c, path, uid, parts,
                        {"x-amz-storage-class": other})
        # Staged: drive i holds shard distribution[i] of every part.
        for i, d in enumerate(mpu):
            for pn in range(1, len(parts) + 1):
                with open(os.path.join(d, f"part.{pn}"), "rb") as f:
                    assert f.read() == want[pn - 1][
                        up["distribution"][i] - 1], (i, pn)
        _complete(c, path, uid, etags)

        def at_rest(i: int) -> list[bytes]:
            fi = eng.disks[i].read_version(BUCKET, KEY)
            assert (fi.erasure.data_blocks, fi.erasure.parity_blocks) == \
                (k, m)
            assert fi.erasure.index == up["distribution"][i]
            out = []
            for pn in range(1, len(parts) + 1):
                with open(os.path.join(roots[i], BUCKET, KEY, fi.data_dir,
                                       f"part.{pn}"), "rb") as f:
                    out.append(f.read())
            return out

        def as_reference(i: int) -> list[bytes]:
            return [w[up["distribution"][i] - 1] for w in want]

        for i in range(n):
            assert at_rest(i) == as_reference(i), i

        # Everything that reads it takes k+m from xl.meta.
        r = c.get_object(BUCKET, KEY)
        assert r.status == 200 and r.body == whole
        cut = len(parts[0])
        r = c.request("GET", path,
                      headers={"Range": f"bytes={cut - 777}-{cut + 776}"})
        assert r.status == 206 and r.body == whole[cut - 777:cut + 777]
        # Two drives that hold DATA shards lose their copies: the read
        # decodes at the class's geometry.
        gone = [i for i in range(n) if up["distribution"][i] <= k][:2]
        for i in gone:
            shutil.rmtree(os.path.join(roots[i], BUCKET, "ckpt"))
        r = c.get_object(BUCKET, KEY)
        assert r.status == 200 and r.body == whole
        # A heal puts back exactly the reference's bytes, and the class.
        res = sets.healer.heal_object(BUCKET, KEY)
        assert sorted(res.healed_disks) == gone, vars(res)
        for i in gone:
            assert at_rest(i) == as_reference(i), i
        r = c.get_object(BUCKET, KEY)
        assert r.status == 200 and r.body == whole

        # An upload begun by an older process (no "parity" in its
        # record) means the set's default, eng.m.
        uid = _initiate(c, path + ".old")
        base = _upload_base(BUCKET, KEY + ".old", uid)
        for r_ in roots:
            rec = os.path.join(r_, MINIO_META_BUCKET, base, "upload.json")
            with open(rec) as f:
                old = json.load(f)
            del old["parity"]
            with open(rec, "w") as f:
                json.dump(old, f)
        body = parts[-1]
        _complete(c, path + ".old", uid,
                  _upload(c, path + ".old", uid, [body]))
        fi = eng.disks[0].read_version(BUCKET, KEY + ".old")
        assert (fi.erasure.data_blocks, fi.erasure.parity_blocks) == \
            (eng.k, eng.m)
        with open(os.path.join(roots[0], BUCKET, KEY + ".old", fi.data_dir,
                               "part.1"), "rb") as f:
            assert f.read() == reference_shard_files(
                body, eng.k, eng.m, block)[fi.erasure.index - 1]
        assert c.get_object(BUCKET, KEY + ".old").body == body
    finally:
        srv.stop()
