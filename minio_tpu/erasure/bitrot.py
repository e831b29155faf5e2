"""Bitrot protection: per-shard checksums in the reference's formats.

Two modes (ref cmd/bitrot.go:99-111):
- streaming (default, HighwayHash256S): the shard file interleaves
  [32B hash][shard-block] for every shard_size sub-block
  (ref cmd/bitrot-streaming.go:46-57 write, :115-158 verify-on-read).
- whole-file (legacy): one checksum over the whole shard, stored in
  metadata (ref cmd/bitrot-whole.go).

Algorithms (ref cmd/bitrot.go:33-38): highwayhash256/highwayhash256S
(magic-keyed, byte-identical — ops/hh256 + native C++), blake2b-512,
sha256 (hashlib).
"""

from __future__ import annotations

import contextlib
import hashlib

from ..native import hh256_chunks_native, hh256_native
from ..ops.hh256 import MAGIC_KEY, HighwayHash256
from ..utils import ceil_frac

# Algorithm names as stored in metadata (ref cmd/bitrot.go:33-38).
SHA256 = "sha256"
BLAKE2B = "blake2b"
HIGHWAYHASH256 = "highwayhash256"
HIGHWAYHASH256S = "highwayhash256S"  # streaming mode

DEFAULT_ALGORITHM = HIGHWAYHASH256S

_ALGORITHMS = (SHA256, BLAKE2B, HIGHWAYHASH256, HIGHWAYHASH256S)


def is_streaming(algo: str) -> bool:
    return algo == HIGHWAYHASH256S


def hash_size(algo: str) -> int:
    return {SHA256: 32, BLAKE2B: 64,
            HIGHWAYHASH256: 32, HIGHWAYHASH256S: 32}[algo]


def digest(algo: str, data: bytes) -> bytes:
    if algo in (HIGHWAYHASH256, HIGHWAYHASH256S):
        native = hh256_native(data, MAGIC_KEY)
        if native is not None:
            return native
        return HighwayHash256(MAGIC_KEY).update(data).digest()
    if algo == SHA256:
        return hashlib.sha256(data).digest()
    if algo == BLAKE2B:
        return hashlib.blake2b(data, digest_size=64).digest()
    raise ValueError(f"unsupported bitrot algorithm: {algo}")


def digest_chunks(algo: str, data: bytes, chunk_size: int) -> list[bytes]:
    """Hash consecutive chunk_size chunks (the streaming-bitrot pattern)."""
    if len(data) == 0:
        return []
    if algo in (HIGHWAYHASH256, HIGHWAYHASH256S):
        from ..obs.kernel_stats import HH256, KERNEL, timed
        from ..obs.kernprof import NATIVE
        from ..obs.span import TRACER
        with TRACER.span("kernel.hh256", backend=NATIVE,
                         rows=ceil_frac(len(data), chunk_size),
                         bytes=len(data)), timed() as t:
            native = hh256_chunks_native(data, chunk_size, MAGIC_KEY)
        if native is not None:
            KERNEL.record(HH256, False, len(data), t.s,
                          blocks=len(native), backend=NATIVE)
            return native
    n = ceil_frac(len(data), chunk_size)
    return [digest(algo, data[i * chunk_size:(i + 1) * chunk_size])
            for i in range(n)]


# --- batched (device) hashing -------------------------------------------------

# Coalesced full-chunk bytes at or above this go to the TPU kernel
# (ops/hh256_tpu.py); below it, host hashing (C++ native) is assumed to
# win on the dispatch's fixed cost — same policy shape as the RS codec's
# TPU_MIN_BYTES (erasure/codec.py); nobody has measured the crossover on
# a local chip yet (ROADMAP A5).
HH_TPU_MIN_BYTES = 4 * 1024 * 1024

# Below this many coalesced bytes, host hashing stays on the calling
# thread: a multi-thread fan-out of sub-millisecond native hash calls
# costs more in scheduling than it saves in parallelism.
HOST_HASH_FANOUT_MIN = 8 * 1024 * 1024


def _device_hash_ok(algo: str, chunk_size: int, total_full_bytes: int,
                    ) -> bool:
    if algo not in (HIGHWAYHASH256, HIGHWAYHASH256S):
        return False
    if chunk_size <= 0 or total_full_bytes < HH_TPU_MIN_BYTES:
        return False
    from ..ops import batching
    return batching.device_present()


def _hash_rows_device(rows, total_bytes: int, n_requests: int):
    """One device dispatch over B equal-length rows -> (B, 32) digests
    or None on device failure (callers fall back to the host). `rows`
    is what `hh256_tpu.hash_rows` takes (a (B, L) array or a list of
    buffers), handed over uncopied: its packer writes each row once
    into the operand and pads the batch to its power-of-two bucket.
    HH_STATS counts the outcome either way."""
    from ..obs.span import TRACER
    from ..ops import batching
    with TRACER.span("kernel.hh256", backend=batching.attempt_backend(),
                     rows=len(rows), bytes=total_bytes):
        try:
            from ..ops import hh256_tpu
            digs = hh256_tpu.hash_rows(rows)
            batching.HH_STATS.add(True, total_bytes, n_requests)
            return digs
        except Exception as exc:  # noqa: BLE001 - degrade loudly, don't fail IO
            batching.device_dispatch_failed(exc)
            batching.HH_STATS.add(False, total_bytes, n_requests)
            return None


def digest_rows(algo: str, arr):
    """(B, chunk) contiguous uint8 -> (B, hash_size) digests, zero
    input copies on the native/device paths. Byte-identical to
    digest_chunks over arr.tobytes()."""
    import numpy as np
    B = arr.shape[0]
    if B and _device_hash_ok(algo, arr.shape[1], arr.size):
        digs = _hash_rows_device(arr, arr.size, 1)
        if digs is not None:
            return np.asarray(digs, dtype=np.uint8)
    if algo in (HIGHWAYHASH256, HIGHWAYHASH256S):
        from ..native import hh256_rows_native
        from ..obs.kernel_stats import HH256, KERNEL, timed
        from ..obs.kernprof import NATIVE
        from ..obs.span import TRACER
        with TRACER.span("kernel.hh256", backend=NATIVE, rows=B,
                         bytes=arr.size), timed() as t:
            out = hh256_rows_native(arr, MAGIC_KEY)
        if out is not None:
            from ..ops import batching
            batching.HH_STATS.add(False, arr.size)
            KERNEL.record(HH256, False, arr.size, t.s, blocks=B,
                          backend=NATIVE)
            return out
    out = np.empty((B, hash_size(algo)), dtype=np.uint8)
    for i in range(B):
        out[i] = np.frombuffer(digest(algo, arr[i].tobytes()),
                               dtype=np.uint8)
    return out


def encode_stream_arrays(arrs, algo: str = DEFAULT_ALGORITHM):
    """Frame per-shard sub-block ARRAYS into streaming-bitrot shard
    chunks with minimal copying — the batched write path's fast lane.

    arrs: one (n_blocks, chunk) contiguous uint8 array per shard (each
    row is one bitrot sub-block). Returns one flat uint8 array per
    shard laid out [hash][block][hash][block]..., byte-identical to
    ``encode_streams`` over the equivalent bytes (pinned by
    tests/test_golden.py) but with ONE data copy (into the frame)
    instead of four (ref cmd/bitrot-streaming.go:46 framing)."""
    import numpy as np
    if not is_streaming(algo):
        return [np.ascontiguousarray(a).reshape(-1) for a in arrs]
    hsize = hash_size(algo)
    # Device path: ONE dispatch over every shard's sub-blocks (they
    # all share the chunk size), mirroring digest_chunks_many.
    per_shard_digs = None
    total = sum(a.size for a in arrs)
    if arrs and _device_hash_ok(algo, arrs[0].shape[1], total):
        digs = _hash_rows_device([row for a in arrs for row in a],
                                 total, len(arrs))
        if digs is not None:
            digs = np.asarray(digs, dtype=np.uint8)
            per_shard_digs, row = [], 0
            for a in arrs:
                per_shard_digs.append(digs[row:row + a.shape[0]])
                row += a.shape[0]
    if per_shard_digs is None:
        # Host hashing: shards fan out on multicore (the native kernel
        # releases the GIL), sequential where a second core doesn't
        # exist — same policy as _host_digest_many. Small batches stay
        # sequential even on multicore: dispatching k+m sub-millisecond
        # hash jobs costs more in thread wakeups than the hashing
        # itself (measured 3-20ms of scheduling noise for a 1MiB PUT
        # batch vs 0.5ms hashed inline).
        from ..parallel.quorum import MULTICORE, parallel_map
        if len(arrs) > 1 and MULTICORE and total >= HOST_HASH_FANOUT_MIN:
            per_shard_digs, errs = parallel_map(
                [lambda a=a: digest_rows(algo, a) for a in arrs])
            if any(e is not None for e in errs):
                per_shard_digs = None
        if per_shard_digs is None:
            per_shard_digs = [digest_rows(algo, a) for a in arrs]
    out = []
    for a, hs in zip(arrs, per_shard_digs):
        B, S = a.shape
        frame = np.empty((B, hsize + S), dtype=np.uint8)
        frame[:, :hsize] = hs
        frame[:, hsize:] = a
        out.append(frame.reshape(-1))
    return out


def frame_shard(full_rows, tail: bytes | None,
                algo: str = DEFAULT_ALGORITHM) -> bytes:
    """Frame ONE shard's batch contribution: `full_rows` is a
    (n_blocks, shard_size) contiguous uint8 array (or None) of
    full-block sub-blocks, `tail` the final short block's bytes (or
    None). Byte-identical to this shard's slice of
    ``encode_stream_arrays`` + the tail frame of ``encode_streams``
    (pinned by tests/test_pipeline.py golden compare) — but callable
    per shard from the writer fan-out, so the hash of shard j overlaps
    the disk write of shard i on the pipelined PUT path."""
    import numpy as np
    if not is_streaming(algo):
        parts = []
        if full_rows is not None and full_rows.size:
            parts.append(np.ascontiguousarray(full_rows)
                         .reshape(-1).tobytes())
        if tail:
            parts.append(bytes(tail))
        return b"".join(parts)
    hsize = hash_size(algo)
    parts = []
    if full_rows is not None and full_rows.size:
        B, S = full_rows.shape
        frame = np.empty((B, hsize + S), dtype=np.uint8)
        frame[:, :hsize] = digest_rows(algo, full_rows)
        frame[:, hsize:] = full_rows
        parts.append(frame.reshape(-1).tobytes())
    if tail:
        parts.append(digest(algo, tail) + tail)
    return b"".join(parts)


def _host_digest_many(algo: str, streams: list[bytes],
                      chunk_size: int) -> list[list[bytes]]:
    """Host path of digest_chunks_many: on multicore hosts the k+m
    shards hash in parallel — the native HighwayHash kernel releases
    the GIL, so the fan-out is real concurrency."""
    from ..parallel.quorum import MULTICORE, parallel_map
    if len(streams) > 1 and MULTICORE and \
            sum(len(s) for s in streams) >= HOST_HASH_FANOUT_MIN:
        results, errs = parallel_map(
            [lambda s=s: digest_chunks(algo, s, chunk_size)
             for s in streams])
        if not any(e is not None for e in errs):
            return results
    return [digest_chunks(algo, s, chunk_size) for s in streams]


def digest_chunks_many(algo: str, streams: list[bytes], chunk_size: int,
                       ) -> list[list[bytes]]:
    """Per-stream chunk digests, with all full chunks of all streams
    hashed in ONE device dispatch when the coalesced bytes clear the
    policy threshold (the bitrot half of the TPU data plane; north star
    per BASELINE.json — ref cmd/bitrot-streaming.go hashes chunk-by-
    chunk on the CPU, per shard, per block).

    Ragged tail chunks (len % chunk_size) hash on the host: the device
    kernel handles equal-length chunks only.
    """
    full_counts = [len(s) // chunk_size for s in streams]
    total_full = sum(full_counts) * chunk_size
    if not _device_hash_ok(algo, chunk_size, total_full):
        return _host_digest_many(algo, streams, chunk_size)

    import numpy as np
    rows = []
    for s, nf in zip(streams, full_counts):
        if nf:
            v = np.frombuffer(s, dtype=np.uint8, count=nf * chunk_size)
            rows.extend(v[i * chunk_size:(i + 1) * chunk_size]
                        for i in range(nf))
    digs = _hash_rows_device(rows, total_full, len(streams))
    if digs is None:
        return _host_digest_many(algo, streams, chunk_size)

    out: list[list[bytes]] = []
    row = 0
    for s, nf in zip(streams, full_counts):
        hs = [digs[row + i].tobytes() for i in range(nf)]
        row += nf
        tail = s[nf * chunk_size:]
        if tail:
            hs.append(digest(algo, tail))
        out.append(hs)
    return out


def bitrot_shard_file_size(size: int, shard_size: int, algo: str) -> int:
    """On-disk size of a shard file including interleaved hashes
    (ref cmd/bitrot.go:140)."""
    if not is_streaming(algo):
        return size
    if size < 0:
        return -1
    return ceil_frac(size, shard_size) * hash_size(algo) + size


def encode_stream(data: bytes, shard_size: int,
                  algo: str = DEFAULT_ALGORITHM) -> bytes:
    """Wrap raw shard bytes in the streaming format:
    [hash][block][hash][block]... (ref cmd/bitrot-streaming.go:46)."""
    if not is_streaming(algo):
        return data
    hs = digest_chunks(algo, data, shard_size)
    out = bytearray()
    for i, h in enumerate(hs):
        out += h
        out += data[i * shard_size:(i + 1) * shard_size]
    return bytes(out)


def encode_streams(streams: list[bytes], shard_size: int,
                   algo: str = DEFAULT_ALGORITHM) -> list[bytes]:
    """Batched encode_stream: frame many shards' bytes, hashing ALL
    their sub-blocks in one (device-eligible) digest_chunks_many call —
    the write-path entry for TPU bitrot (engine._encode_batch hands the
    k+m shards of a whole PUT batch here at once)."""
    if not is_streaming(algo):
        return list(streams)
    all_hashes = digest_chunks_many(algo, streams, shard_size)
    out: list[bytes] = []
    for data, hs in zip(streams, all_hashes):
        buf = bytearray()
        for i, h in enumerate(hs):
            buf += h
            buf += data[i * shard_size:(i + 1) * shard_size]
        out.append(bytes(buf))
    return out


def verify_frames(datas: list, wants: list[bytes],
                  algo: str = DEFAULT_ALGORITHM) -> list[bool]:
    """Batch-verify many [hash][block] frames: datas[i] (bytes or uint8
    view) must hash to wants[i]. Equal-length frames coalesce into one
    device dispatch when the policy allows (the read-path entry for TPU
    bitrot — ref streamingBitrotReader verify-per-chunk,
    cmd/bitrot-streaming.go:115, lifted to a batch)."""
    import numpy as np

    def stack_group(idxs: list[int]):
        return np.stack([
            np.frombuffer(datas[i], dtype=np.uint8)
            if not isinstance(datas[i], np.ndarray) else datas[i]
            for i in idxs])

    # The device lane takes the frames as they are. On the host, worth
    # one (B, L) stack copy: enough same-length frames that a single
    # rows dispatch beats a Python loop of per-frame calls (~2x on a
    # degraded-GET read window's verify pass).
    HOST_ROWS_MIN_FRAMES = 5
    by_len: dict[int, list[int]] = {}
    for i, d in enumerate(datas):
        by_len.setdefault(len(d), []).append(i)
    ok = [False] * len(datas)
    for length, idxs in by_len.items():
        total = length * len(idxs)
        if length and _device_hash_ok(algo, length, total):
            digs = _hash_rows_device([datas[i] for i in idxs], total,
                                     len(idxs))
            if digs is not None:
                for row, i in enumerate(idxs):
                    ok[i] = digs[row].tobytes() == wants[i]
                continue
        if length and len(idxs) >= HOST_ROWS_MIN_FRAMES and \
                algo in (HIGHWAYHASH256, HIGHWAYHASH256S):
            digs = digest_rows(algo, stack_group(idxs))
            for row, i in enumerate(idxs):
                ok[i] = digs[row].tobytes() == wants[i]
            continue
        # A few frames: one native call each (no stack copy).
        from ..obs.kernprof import NATIVE
        from ..obs.span import TRACER
        with (TRACER.span("kernel.hh256", backend=NATIVE, rows=len(idxs),
                          bytes=total)
              if algo in (HIGHWAYHASH256, HIGHWAYHASH256S)
              else contextlib.nullcontext()):
            for i in idxs:
                d = datas[i]
                if not isinstance(d, (bytes, bytearray)):
                    d = bytes(d)
                ok[i] = digest(algo, d) == wants[i]
    return ok


class BitrotMismatch(Exception):
    """Shard sub-block hash mismatch (ref errHashMismatch,
    cmd/bitrot-streaming.go:30)."""


def split_block(buf, block_idx: int, chunk: int, shard_size: int,
                algo: str = DEFAULT_ALGORITHM) -> tuple[bytes, memoryview]:
    """(want, data) of one [hash][block] frame, NOT hashed — for
    callers that batch-verify many frames in one `verify_frames`
    dispatch (heal). `data` is a memoryview into `buf` (no copy: a
    survivor frame reaches the device operand, or the decode, straight
    from the bytes the drive returned). `want` is b"" for whole-file
    algorithms, whose shard files carry no inline hashes."""
    view = memoryview(buf)
    if not is_streaming(algo):
        return b"", view[block_idx * shard_size:
                         block_idx * shard_size + chunk]
    hsz = hash_size(algo)
    base = block_idx * (hsz + shard_size)
    want = bytes(view[base:base + hsz])
    data = view[base + hsz:base + hsz + chunk]
    if len(want) < hsz or len(data) < chunk:
        raise BitrotMismatch("truncated shard stream")
    return want, data


def extract_block(buf: bytes, block_idx: int, chunk: int, shard_size: int,
                  algo: str = DEFAULT_ALGORITHM) -> memoryview:
    """Extract + verify one [hash][block] frame from a streaming shard
    buffer whose frame 0 starts at byte 0 (a whole file or a ranged
    window). `chunk` is the expected block payload length; the block
    comes back as `split_block`'s view into `buf`."""
    want, data = split_block(buf, block_idx, chunk, shard_size, algo)
    if want and digest(algo, data) != want:
        raise BitrotMismatch(f"content hash mismatch at block {block_idx}")
    return data


def decode_stream_at(stream: bytes, offset: int, length: int,
                     shard_size: int, algo: str = DEFAULT_ALGORITHM,
                     ) -> bytes:
    """Read logical [offset, offset+length) from a streaming-format shard
    file, verifying every covered sub-block hash
    (ref streamingBitrotReader.ReadAt, cmd/bitrot-streaming.go:115).

    offset must be shard_size-aligned, like the reference.
    """
    if not is_streaming(algo):
        return stream[offset:offset + length]
    if offset % shard_size != 0:
        raise ValueError("offset must be aligned to shard_size")
    hsz = hash_size(algo)
    out = bytearray()
    block_idx = offset // shard_size
    remaining = length
    while remaining > 0:
        base = block_idx * (hsz + shard_size)
        avail = len(stream) - base - hsz
        if avail <= 0:
            raise BitrotMismatch("truncated shard stream")
        chunk = min(shard_size, avail)
        block = extract_block(stream, block_idx, chunk, shard_size, algo)
        take = min(remaining, len(block))
        out += block[:take]
        remaining -= take
        if len(block) < shard_size:
            break  # last (short) block
        block_idx += 1
    if remaining > 0:
        raise BitrotMismatch("short read from shard stream")
    return bytes(out)


def verify_stream(stream: bytes, shard_size: int,
                  algo: str = DEFAULT_ALGORITHM) -> bool:
    """Deep-scan a whole streaming shard file (VerifyFile equivalent,
    ref cmd/xl-storage.go:2312)."""
    if not is_streaming(algo):
        return True
    hsz = hash_size(algo)
    off = 0
    while off < len(stream):
        want = stream[off:off + hsz]
        block = stream[off + hsz:off + hsz + shard_size]
        if len(want) < hsz or len(block) == 0:
            return False
        if digest(algo, block) != want:
            return False
        off += hsz + len(block)
    return True
