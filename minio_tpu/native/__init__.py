"""Native (C++) host-side kernels, built on demand with g++ via ctypes.

The reference delegates its host hot loops to SIMD assembly libraries
(SURVEY §2.7). Here the host fallback/cryptographic loops live in C++
compiled once into a shared object under build/; the TPU kernels remain the
primary data plane. Everything degrades gracefully to pure Python if a
compiler is unavailable.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_HERE, "build")
_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None
_TRIED = False


_CXXFLAGS = ["-O3", "-march=native", "-pthread", "-shared", "-fPIC"]


def _host_cpu_id() -> str:
    """What `-march=native` resolves against: the CPU model and its
    feature flags. A .so built on another CPU gets another key."""
    import platform
    ident = [platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            seen = set()
            for line in f:
                key = line.split(":", 1)[0].strip()
                if key in ("model name", "flags", "Features") \
                        and key not in seen:
                    seen.add(key)
                    ident.append(line.strip())
    except OSError:
        ident.append(platform.processor())
    return "\n".join(ident)


def _build_key(srcs: list[str]) -> str:
    """Content key of a build: the sources, the flags and the building
    host's CPU — not mtimes, which tar/rsync/checkout rewrite."""
    import hashlib
    h = hashlib.sha256()
    for src in srcs:
        with open(src, "rb") as f:
            h.update(f.read())
    h.update(" ".join(_CXXFLAGS).encode())
    h.update(_host_cpu_id().encode())
    return h.hexdigest()[:16]


def _compile(srcs: list[str], so: str) -> None:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{so}.{os.getpid()}.tmp"
    subprocess.run(["g++", *_CXXFLAGS, "-o", tmp] + srcs,
                   check=True, capture_output=True, timeout=120)
    os.replace(tmp, so)


def _build_and_load() -> ctypes.CDLL | None:
    srcs = [os.path.join(_HERE, "highwayhash.cc"),
            os.path.join(_HERE, "lzblock.cc"),
            os.path.join(_HERE, "rs.cc"),
            os.path.join(_HERE, "fsops.cc")]
    try:
        so = os.path.join(
            _BUILD_DIR, f"libminio_tpu_native-{_build_key(srcs)}.so")
        if not os.path.exists(so):
            _compile(srcs, so)
        lib = ctypes.CDLL(so)
        lib.hh256_hash.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                   ctypes.c_size_t, ctypes.c_char_p]
        lib.hh256_hash.restype = None
        lib.hh256_chunks.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                     ctypes.c_size_t, ctypes.c_size_t,
                                     ctypes.c_char_p]
        lib.hh256_chunks.restype = ctypes.c_size_t
        lib.lzb_max_compressed.argtypes = [ctypes.c_size_t]
        lib.lzb_max_compressed.restype = ctypes.c_size_t
        lib.lzb_compress.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                     ctypes.c_char_p, ctypes.c_size_t]
        lib.lzb_compress.restype = ctypes.c_long
        lib.lzb_decompress.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                       ctypes.c_char_p, ctypes.c_size_t]
        lib.lzb_decompress.restype = ctypes.c_long
        lib.rs_gf_apply.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                                    ctypes.c_size_t, ctypes.c_void_p,
                                    ctypes.c_size_t, ctypes.c_void_p]
        lib.rs_gf_apply.restype = None
        lib.rs_gf_apply_mt.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                                       ctypes.c_size_t, ctypes.c_void_p,
                                       ctypes.c_size_t, ctypes.c_void_p,
                                       ctypes.c_size_t]
        lib.rs_gf_apply_mt.restype = None
        lib.rs_gf_apply_blocks.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t]
        lib.rs_gf_apply_blocks.restype = None
        path = ctypes.c_char_p
        out = ctypes.POINTER(ctypes.c_long)
        lib.fs_append.argtypes = [path, path, path, ctypes.c_void_p,
                                  ctypes.c_size_t, out]
        lib.fs_append.restype = ctypes.c_int
        lib.fs_commit_stage.argtypes = [
            path, path, path, path, path, path, path, path,
            ctypes.c_void_p, ctypes.c_size_t, out, out, out]
        lib.fs_commit_stage.restype = ctypes.c_int
        lib.fs_commit_meta.argtypes = [
            path, path, ctypes.c_void_p, ctypes.c_size_t, path, path,
            path, path, ctypes.POINTER(ctypes.c_int), ctypes.c_size_t,
            path, path, out]
        lib.fs_commit_meta.restype = ctypes.c_int
        return lib
    except Exception as exc:
        # Every native lane is now off for the process: say so, and
        # tell the health machine why (admin /kernel-health).
        detail = getattr(exc, "stderr", b"") or b""
        reason = (f"native library unavailable: {exc!r} "
                  f"{detail[-300:].decode(errors='replace')}").strip()
        import logging
        logging.getLogger("minio_tpu.native").warning("%s", reason)
        try:
            from ..obs.kernprof import KERNPROF, NATIVE
            KERNPROF.dispatch_failed(NATIVE, reason)
        except Exception:
            pass  # never let telemetry break the degrade path
        return None


def get_lib() -> ctypes.CDLL | None:
    global _LIB, _TRIED
    if _LIB is None and not _TRIED:
        with _LOCK:
            if _LIB is None and not _TRIED:
                _LIB = _build_and_load()
                _TRIED = True
    return _LIB


def _disable_native(reason: str) -> None:
    """A native kernel returned inconsistent results: distrust the
    whole library for the rest of the process (every caller degrades
    to its host/pure-Python path) and say so loudly once.  The
    kernprof backend state machine hears about it too, so the 'native'
    lane shows DEGRADED/DOWN on the health surfaces and the recovery
    probe (``probe()``) owns re-adoption."""
    global _LIB, _TRIED
    import logging
    with _LOCK:
        _LIB = None
        _TRIED = True
    logging.getLogger("minio_tpu.native").warning(
        "native kernel disabled: %s", reason)
    try:
        from ..obs.kernprof import KERNPROF, NATIVE
        KERNPROF.dispatch_failed(NATIVE, reason)
    except Exception:
        pass  # never let telemetry break the degrade path


def probe() -> bool:
    """Recovery probe for the kernprof 'native' backend: re-attempt
    build+load (a ``_disable_native`` poisons the cached handle for
    the process — this is the only path that un-poisons it) and run a
    known-answer self-check through both exported kernel families.
    True only when the library loads AND answers correctly."""
    global _TRIED
    with _LOCK:
        if _LIB is None:
            _TRIED = False  # allow get_lib() to rebuild/reload
    if get_lib() is None:
        return False
    try:
        import numpy as np

        from ..ops.gf256 import gf_mat_vec_apply
        from ..ops.hh256 import MAGIC_KEY, HighwayHash256
        data = b"minio-tpu native probe"
        want = HighwayHash256(MAGIC_KEY).update(data).digest()
        if hh256_native(data, MAGIC_KEY) != want:
            _disable_native("probe: hh256 known-answer mismatch")
            return False
        mat = np.array([[1, 2], [3, 4]], dtype=np.uint8)
        cols = np.arange(2 * 64, dtype=np.uint8).reshape(2, 64)
        got = rs_apply_native(mat, cols)
        if got is None or not (got == gf_mat_vec_apply(mat,
                                                       cols)).all():
            _disable_native("probe: rs_gf_apply known-answer mismatch")
            return False
        # The row-pointer entry: two blocks whose rows lie apart, into
        # rows wider than the blocks.
        blocks = [[cols[0, :48].copy(), cols[1, :48].copy()],
                  [cols[1, 16:64].copy(), cols[0, 16:64].copy()]]
        out = np.zeros((2, 100), dtype=np.uint8)
        want = np.concatenate(
            [gf_mat_vec_apply(mat, np.stack(b)) for b in blocks], axis=1)
        if rs_apply_blocks_native(mat, blocks, out) is None or not (
                out[:, :96] == want).all() or out[:, 96:].any():
            _disable_native(
                "probe: rs_gf_apply_blocks known-answer mismatch")
            return False
        return True
    except Exception as exc:  # noqa: BLE001 - a probe must not raise
        _disable_native(f"probe raised: {exc!r}")
        return False


def hh256_native(data: bytes, key: bytes) -> bytes | None:
    """One-shot HighwayHash-256 via C++; None if native lib unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(32)
    lib.hh256_hash(key, bytes(data), len(data), out)
    return out.raw


def hh256_chunks_native(data: bytes, chunk_size: int,
                        key: bytes) -> list[bytes] | None:
    """Hash consecutive chunk_size chunks (streaming-bitrot pattern)."""
    lib = get_lib()
    if lib is None:
        return None
    if len(data) == 0:
        return []
    n = -(-len(data) // chunk_size)
    out = ctypes.create_string_buffer(32 * n)
    got = lib.hh256_chunks(key, bytes(data), len(data), chunk_size, out)
    if got != n:
        # A short/garbled native return must NOT surface truncated
        # digests as "valid" (a bare assert here vanishes under -O):
        # fall back to the pure-Python path by reporting unavailable.
        _disable_native(f"hh256_chunks returned {got}, expected {n}")
        return None
    return [out.raw[i * 32:(i + 1) * 32] for i in range(n)]


def hh256_rows_native(arr, key: bytes):
    """Hash each row of a CONTIGUOUS (n, chunk) uint8 array -> (n, 32)
    uint8 array, with zero input copies (the array's buffer is handed
    straight to the C kernel). None if the native lib is unavailable.
    Byte-identical to hh256_chunks_native over arr.tobytes()."""
    lib = get_lib()
    if lib is None:
        return None
    import numpy as np
    if arr.size == 0:
        return np.empty((0, 32), dtype=np.uint8)
    a = np.ascontiguousarray(arr, dtype=np.uint8)
    n, chunk = a.shape
    out = np.empty((n, 32), dtype=np.uint8)
    got = lib.hh256_chunks(
        key, ctypes.cast(a.ctypes.data, ctypes.c_char_p), a.size,
        chunk, ctypes.cast(out.ctypes.data, ctypes.c_char_p))
    if got != n:
        # Explicit check (not a bare assert — stripped under -O): a
        # wrong row count means the output buffer is untrustworthy.
        _disable_native(f"hh256_chunks returned {got}, expected {n}")
        return None
    return out


# Large host applies (heal sweeps, mask-group folds in degraded mode)
# spread column ranges across threads; small ones stay single-threaded
# so per-request latency paths and the bench baseline are unaffected.
RS_MT_THRESHOLD = 8 * 1024 * 1024


def rs_apply_native(mat, data):
    """(r, k) GF(2^8) matrix applied to (k, n) byte rows -> (r, n), via
    the C++ nibble-shuffle kernel (native/rs.cc). None when the native
    lib is unavailable. Byte-identical to gf256.gf_mat_vec_apply.
    """
    lib = get_lib()
    if lib is None:
        return None
    import numpy as np
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    r, k = mat.shape
    if data.shape[0] != k:
        raise ValueError(f"data rows {data.shape[0]} != k={k}")
    n = data.shape[1]
    out = np.empty((r, n), dtype=np.uint8)
    if data.nbytes >= RS_MT_THRESHOLD:
        nthreads = min(8, os.cpu_count() or 1)
        lib.rs_gf_apply_mt(mat.ctypes.data, r, k, data.ctypes.data, n,
                           out.ctypes.data, nthreads)
    else:
        lib.rs_gf_apply(mat.ctypes.data, r, k, data.ctypes.data, n,
                        out.ctypes.data)
    return out


def rs_apply_blocks_native(mat, blocks, out):
    """(r, k) GF(2^8) matrix applied to each of B blocks, reading each
    block's k rows where they lie (native/rs.cc rs_gf_apply_blocks):
    `blocks` holds B lists of k C-contiguous uint8 rows of one length
    S; block b's r output rows land in ``out[:, b*S:(b+1)*S]``, where
    `out` is an (r, >= B*S) uint8 array whose columns are contiguous
    (a column slice of wider rows will do). Returns `out`, or None when
    the native lib is unavailable. Byte-identical to
    gf256.gf_mat_vec_apply per block."""
    lib = get_lib()
    if lib is None:
        return None
    import numpy as np
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    r, k = mat.shape
    B = len(blocks)
    S = len(blocks[0][0]) if B else 0
    if out.dtype != np.uint8 or out.ndim != 2 or out.shape[0] != r \
            or out.shape[1] < B * S or out.strides[1] != 1 \
            or not out.flags.writeable:
        raise ValueError(f"out {out.shape} cannot take {r} rows of "
                         f"{B} x {S} columns")
    ptrs = np.empty(B * k, dtype=np.uintp)
    for b, rows in enumerate(blocks):
        if len(rows) != k:
            raise ValueError(f"block {b}: {len(rows)} rows, k={k}")
        for j, row in enumerate(rows):
            if row.dtype != np.uint8 or row.shape != (S,) \
                    or not row.flags.c_contiguous:
                raise ValueError(f"block {b} row {j}: not {S} "
                                 f"contiguous bytes")
            ptrs[b * k + j] = row.ctypes.data
    if B and S:
        nthreads = (min(8, os.cpu_count() or 1)
                    if B * k * S >= RS_MT_THRESHOLD else 1)
        lib.rs_gf_apply_blocks(mat.ctypes.data, r, k, ptrs.ctypes.data,
                               B, S, out.ctypes.data, out.strides[0],
                               nthreads)
    return out


def lzb_compress_native(data: bytes) -> bytes | None:
    """LZ-block compress; None when native lib unavailable OR the data
    is incompressible (caller stores raw either way)."""
    lib = get_lib()
    if lib is None or len(data) == 0:
        return None
    cap = lib.lzb_max_compressed(len(data))
    out = ctypes.create_string_buffer(cap)
    got = lib.lzb_compress(bytes(data), len(data), out, cap)
    if got <= 0:
        return None
    return out.raw[:got]


def lzb_decompress_native(blob: bytes, out_size: int) -> bytes | None:
    lib = get_lib()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(max(out_size, 1))
    got = lib.lzb_decompress(bytes(blob), len(blob), out, out_size)
    if got < 0:
        raise ValueError("corrupt lzb block")
    return out.raw[:got]


# --- a local drive's leg of a PUT (native/fsops.cc; storage/xl.py) ---
#
# Results: 0, a positive errno, or one of these typed conditions. Each
# wrapper returns its result first and, last, the system calls the
# function made (minio_tpu_v2_disk_op_syscalls_total).
FS_SRC_VOLUME_NOT_FOUND = -1
FS_DST_VOLUME_NOT_FOUND = -2
FS_STAGE_NOT_FOUND = -3
FS_DRIVE_NOT_FOUND = -4   # the root is no directory: the drive is gone
# fs_commit_stage's answer for an xl.meta larger than the buffer it
# was handed: the caller reads the file itself.
FS_META_TOO_BIG = object()
_FS_META_CAP = 64 * 1024
# One xl.meta read buffer a thread, so that a commit stays two native
# calls (a buffer malloc'd in C would need a third to free it).
_FS_TLS = threading.local()


def fs_append(lib: ctypes.CDLL, full: bytes, vol: bytes,
              sys_tmp: bytes | None, data) -> tuple[int, int]:
    """XLStorage.append_file's system calls in one GIL-free call:
    ``(result, calls)``. The payload is handed over without a copy:
    ``bytes`` as they are, any other C-contiguous buffer through a numpy
    view (which raises for a strided one, as a file's ``write``
    does)."""
    calls = ctypes.c_long(0)
    if isinstance(data, bytes):
        ptr, size = data, len(data)
    else:
        import numpy as np
        view = np.frombuffer(data, dtype=np.uint8)
        ptr, size = view.ctypes.data, view.size
    rc = lib.fs_append(full, vol, sys_tmp, ptr, size, ctypes.byref(calls))
    return rc, calls.value


def fs_commit_stage(lib: ctypes.CDLL, src_vol: bytes,
                    src_sys_tmp: bytes | None, dst_vol: bytes,
                    dst_sys_tmp: bytes | None, dst_obj_dir: bytes,
                    src_dd: bytes | None, dst_dd: bytes | None,
                    xl_meta: bytes):
    """XLStorage._rename_data up to the XLMeta merge. Returns
    ``(result, raw, read_ms, calls)``: `raw` is the destination's
    xl.meta, None when there is none yet, FS_META_TOO_BIG when the
    caller has to read it; `read_ms` what reading it took (on a fresh
    key: the mkdir that said so), on the drive's side of the GIL."""
    buf = getattr(_FS_TLS, "meta", None)
    if buf is None:
        buf = _FS_TLS.meta = ctypes.create_string_buffer(_FS_META_CAP)
    n, ns, calls = ctypes.c_long(-1), ctypes.c_long(0), ctypes.c_long(0)
    rc = lib.fs_commit_stage(src_vol, src_sys_tmp, dst_vol, dst_sys_tmp,
                             dst_obj_dir, src_dd, dst_dd, xl_meta,
                             buf, _FS_META_CAP, ctypes.byref(n),
                             ctypes.byref(ns), ctypes.byref(calls))
    raw = None
    if rc == 0 and n.value != -1:
        raw = FS_META_TOO_BIG if n.value < 0 else buf[:n.value]
    return rc, raw, ns.value / 1e6, calls.value


def fs_commit_meta(lib: ctypes.CDLL, tmp: bytes, xl_meta: bytes,
                   blob: bytes, dst_vol: bytes,
                   dst_sys_tmp: bytes | None, dst_obj_dir: bytes,
                   old_dd: bytes | None, old_parts: list[int],
                   intent: bytes, stage_dir: bytes) -> tuple[int, int]:
    """XLStorage._rename_data after the merge: `blob` becomes the new
    xl.meta through a temporary, then the replaced data dir (its files
    by name: ``part.N`` for each of `old_parts`), the intent breadcrumb
    and the stage directory go. ``(result, calls)``."""
    calls = ctypes.c_long(0)
    parts = (ctypes.c_int * len(old_parts))(*old_parts) if old_parts else None
    rc = lib.fs_commit_meta(tmp, xl_meta, blob, len(blob), dst_vol,
                            dst_sys_tmp, dst_obj_dir, old_dd, parts,
                            len(old_parts), intent, stage_dir,
                            ctypes.byref(calls))
    return rc, calls.value
