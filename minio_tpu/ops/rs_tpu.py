"""TPU-native Reed-Solomon: GF(2^8) linear maps as MXU bit-plane matmuls.

Design (TPU-first, NOT a port of the reference's SIMD table lookups):

GF(2^8) multiplication by a constant is GF(2)-linear in the bits of the
input byte. An (r, k) GF(2^8) matrix therefore lowers to an (8r, 8k) 0/1
matrix over GF(2) (gf256.gf_matrix_to_bitplane). Applying it to shard bytes
becomes:

    unpack bytes -> bit-planes        (k, S) u8  -> (8k, S) bf16
    parity_bits  = (BigM @ bits) & 1  MXU matmul, f32 accumulation (exact:
                                      popcount <= 8k <= 2048 < 2^24)
    pack bit-planes -> bytes          (8m, S) -> (m, S) u8

The whole encode is one batched matmul — large, static-shaped, bf16: exactly
what the MXU wants. Reconstruction is the same kernel with a different
(host-inverted, see rs_matrix.decode_matrix) matrix, so a single compiled
function serves encode, reconstruct, and heal; the matrix is a runtime
argument and never triggers recompilation.

Batching: callers coalesce many blocks into (B, k, S) before dispatch
(ops/batching.py); the grid then has B*ceil(S/tile) independent tiles.

Reference parity points: cmd/erasure-coding.go:70 (EncodeData),
:89 (DecodeDataBlocks); shard bytes are byte-identical to the Go encoder
because the matrices come from rs_matrix (same construction).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from .gf256 import gf_matrix_to_bitplane
from .rs_matrix import any_decode_matrix, decode_matrix, parity_matrix

# --- host-side matrix prep ----------------------------------------------------


@lru_cache(maxsize=None)
def parity_bitplane(k: int, m: int) -> np.ndarray:
    """(8m, 8k) bf16 bit-plane matrix generating parity from data shards."""
    return gf_matrix_to_bitplane(parity_matrix(k, m)).astype(np.float32)


@lru_cache(maxsize=1024)
def decode_bitplane(k: int, m: int, available: tuple[int, ...],
                    missing: tuple[int, ...]) -> tuple[np.ndarray, list[int]]:
    """Bit-plane matrix rebuilding `missing` data shards from survivors.

    Returns (bitplane_matrix (8*len(missing), 8k), used_shard_indices).
    """
    dec, used = decode_matrix(k, m, list(available))
    rows = dec[list(missing), :]
    return gf_matrix_to_bitplane(rows).astype(np.float32), used


@lru_cache(maxsize=1024)
def any_decode_bitplane(k: int, m: int, available: tuple[int, ...],
                        missing: tuple[int, ...],
                        ) -> tuple[np.ndarray, tuple[int, ...]]:
    """Bit-plane matrix rebuilding arbitrary missing shards (data and
    parity) from survivors — one matmul serves GET-with-loss and heal
    (see rs_matrix.any_decode_matrix)."""
    mat, used = any_decode_matrix(k, m, available, missing)
    return gf_matrix_to_bitplane(mat).astype(np.float32), used


@lru_cache(maxsize=1024)
def _placed_parity(k: int, m: int, mesh,
                   device_index: int | None = None) -> "jnp.ndarray":
    """parity_bitplane already cached host-side; this caches the
    DEVICE-PLACED copy so the hot PUT path doesn't re-transfer the
    matrix on every dispatch (mesh is hashable; None on a single
    device).  ``device_index`` pins the matrix to the batch's home
    device when the batch itself is affinity-pinned — a mesh-
    replicated matrix against a single-device operand is a jit
    placement error."""
    from . import batching
    if device_index is not None:
        return _device_pinned(parity_bitplane(k, m), device_index)
    return batching.device_put_replicated(parity_bitplane(k, m))


@lru_cache(maxsize=1024)
def _placed_any_decode(k: int, m: int, available: tuple[int, ...],
                       missing: tuple[int, ...], mesh,
                       device_index: int | None = None,
                       ) -> "jnp.ndarray":
    from . import batching
    bm, _ = any_decode_bitplane(k, m, available, missing)
    if device_index is not None:
        return _device_pinned(bm, device_index)
    return batching.device_put_replicated(bm)


def _device_pinned(x: np.ndarray, device_index: int) -> "jnp.ndarray":
    devs = jax.devices()
    return jax.device_put(x, devs[device_index % len(devs)])


# --- device kernel ------------------------------------------------------------
#
# Two implementations of the same bit-plane linear map:
#  - rs_pallas.gf_apply: Pallas/Mosaic kernel that keeps the 16x bit-plane
#    inflation in VMEM (bytes-only HBM traffic) — the fast path on TPU.
#  - rs_gf_apply_xla below: plain XLA fallback (materializes the planes) —
#    used on CPU, for non-batched (2-D) inputs on a mesh, and when
#    Mosaic is unavailable on the platform (disabled loudly, once).
#    Mesh-sharded 3-D batches run the Pallas kernel under shard_map
#    (rs_pallas.gf_apply_sharded) — one local kernel per chip.

_pallas_state: dict = {"enabled": None, "cause": ""}


def _pallas_enabled() -> bool:
    """Pallas on a non-CPU platform, unless disabled by env or by a
    prior kernel failure. On a single device the kernel is called
    directly; on a multi-device serving mesh it runs under shard_map
    (rs_pallas.gf_apply_sharded) — each chip applies the packed kernel
    to its local block, no collectives."""
    import os
    st = _pallas_state["enabled"]
    if st is False:
        return False
    if os.environ.get("MINIO_TPU_NO_PALLAS"):
        _pallas_state["cause"] = "MINIO_TPU_NO_PALLAS is set"
        return False
    if st is None:
        from . import batching
        st = batching.device_present()
        if st:
            # Eager one-time smoke compile at a serving-like shape: a
            # kernel the platform's compiler refuses must fall back
            # HERE, loudly, not at a caller's jit-compile.
            from . import rs_pallas
            try:
                rs_pallas.smoke()
            except Exception as exc:
                batching.device_dispatch_failed(
                    f"pallas smoke compile failed: "
                    f"{type(exc).__name__}: {exc}"[:600])
                _disable_pallas(exc)
                return False
        else:
            _pallas_state["cause"] = \
                "no accelerator: jit lane is XLA on CPU"
        _pallas_state["enabled"] = st
    return bool(st)


def _disable_pallas(cause: BaseException | str) -> None:
    import logging
    _pallas_state["enabled"] = False
    _pallas_state["cause"] = cause if isinstance(cause, str) \
        else repr(cause)
    logging.getLogger("minio_tpu.ops").warning(
        "Pallas GF kernel disabled; using the XLA bit-plane path: %s",
        _pallas_state["cause"])


def kernel_report() -> dict:
    """Which GF kernel the jit lane runs (admin /codec-plan `rsKernel`
    and the server's boot line): "pallas" once the smoke compile has
    passed on an accelerator, "xla" otherwise, with the cause when the
    Pallas kernel was refused or disabled."""
    on = _pallas_enabled()
    return {"kernel": "pallas" if on else "xla",
            "cause": "" if on else _pallas_state.get("cause", "")}


def _pallas_failed(exc: BaseException, big_m, x) -> None:
    """The Pallas kernel was refused or failed at this shape: a KERNEL
    failure, reported to kernprof with the shape in the cause (so
    /kernel-health names it) before the XLA path takes over."""
    from . import batching
    cause = (f"pallas gf kernel failed at matrix="
             f"{tuple(big_m.shape)} shards={tuple(x.shape)}: "
             f"{type(exc).__name__}: {exc}")[:600]
    batching.device_dispatch_failed(cause)
    _disable_pallas(cause)


def _unpack_bits(x: jnp.ndarray) -> jnp.ndarray:
    """(..., k, S) uint8 -> (..., 8k, S) bf16 bit-planes (LSB-first)."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    # (..., k, 8, S)
    bits = (x[..., :, None, :] >> shifts[None, :, None]) & jnp.uint8(1)
    shape = bits.shape[:-3] + (bits.shape[-3] * 8, bits.shape[-1])
    return bits.reshape(shape).astype(jnp.bfloat16)


def _pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., 8m, S) int32 0/1 -> (..., m, S) uint8."""
    shape = bits.shape[:-2] + (bits.shape[-2] // 8, 8, bits.shape[-1])
    b = bits.reshape(shape)
    weights = (1 << jnp.arange(8, dtype=jnp.int32))[None, :, None]
    return jnp.sum(b * weights, axis=-2).astype(jnp.uint8)


@jax.jit
def rs_gf_apply_xla(big_m: jnp.ndarray, shards: jnp.ndarray) -> jnp.ndarray:
    bits = _unpack_bits(shards)
    acc = jnp.matmul(big_m.astype(jnp.bfloat16), bits,
                     preferred_element_type=jnp.float32)
    out_bits = acc.astype(jnp.int32) & 1
    return _pack_bits(out_bits)


def _dispatch(pallas_fn, pallas_sharded_fn, xla_fn, big_m, x):
    """Pallas on TPU (direct on one device, shard_map'd over a serving
    mesh), XLA otherwise. Argument errors (rs_pallas.check_args: a caller
    bug, the same on either path) are checked FIRST and propagate.
    Whatever the kernel then raises — a lowering ValueError included —
    is a kernel failure: it goes to kernprof with the shape in its
    cause, disables the Pallas path for the process and the XLA path
    answers this dispatch.

    Scope of the fallback: it protects EAGER callers, i.e. the whole
    serving path (batching, encode_batch). When gf_apply/encode_blocks
    are traced inside a caller's own jit (driver entry points:
    __graft_entry__.entry, models.ec_pipeline.full_step), Mosaic
    compiles later at the outer jit's compile and a shape-specific
    failure surfaces THERE, by design — the driver's compile check must
    see it, not have it silently papered over."""
    if _pallas_enabled():
        from . import batching, rs_pallas
        rs_pallas.check_args(big_m, x)
        mesh = batching.serving_mesh()
        try:
            if mesh is None:
                return pallas_fn(big_m, x)
            if getattr(x, "ndim", 0) == 3:
                sh = getattr(x, "sharding", None)
                if sh is not None and len(sh.device_set) == 1:
                    # Affinity-pinned batch: the whole batch lives on
                    # one chip (parallel/mesh.batch_placement) — run
                    # the packed kernel there directly, no shard_map.
                    return pallas_fn(big_m, x)
                return pallas_sharded_fn(mesh, big_m, x)
        except Exception as exc:  # lowering / Mosaic / runtime failure
            _pallas_failed(exc, big_m, x)
    return xla_fn(big_m, x)


def gf_apply(big_m: jnp.ndarray, shards: jnp.ndarray) -> jnp.ndarray:
    """Apply a bit-plane GF matrix to shard bytes.

    big_m:  (8r, 8k) float/bf16 0/1 matrix (from parity_bitplane /
            decode_bitplane).
    shards: (..., k, S) uint8.
    Returns (..., r, S) uint8.

    Dispatches to the Pallas packed kernel on a single TPU, the XLA
    bit-plane matmul otherwise; both are byte-identical.
    """
    from . import rs_pallas
    return _dispatch(rs_pallas.gf_apply, rs_pallas.gf_apply_sharded,
                     rs_gf_apply_xla, big_m, shards)


@jax.jit
def rs_encode_blocks_xla(big_m: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    parity = rs_gf_apply_xla(big_m, data)
    return jnp.concatenate([data, parity], axis=-2)


def encode_blocks(big_m: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    """Batched encode: (..., k, S) data shards -> (..., k+m, S) all shards."""
    from . import rs_pallas
    return _dispatch(rs_pallas.encode_blocks,
                     rs_pallas.encode_blocks_sharded, rs_encode_blocks_xla,
                     big_m, data)


# --- convenience host API -----------------------------------------------------


def encode_batch(data: np.ndarray, k: int, m: int,
                 affinity: int | None = None) -> np.ndarray:
    """Encode a (B, k, S) or (k, S) uint8 batch on the device(s) —
    batches spread across the serving mesh when >1 device is visible,
    or land whole on the owning set's home device (``affinity``) when
    they don't divide it (ops/batching.device_put_batch). Every
    dispatch lands in the metrics-v2 kernel counters
    (invocations/bytes/wall/occupancy)."""
    from . import batching
    from ..obs.kernel_stats import KERNEL, RS_ENCODE, dispatch, timed
    blocks = data.shape[0] if data.ndim == 3 else 1
    with dispatch(RS_ENCODE, rows=blocks, nbytes=data.nbytes) as ph:
        home = (batching.batch_home_device(data, affinity)
                if data.ndim == 3 else None)
        bm = _placed_parity(k, m, batching.serving_mesh(), home)
        ph.phase("enqueue")
        with timed() as t:
            if data.ndim == 3:
                placed = batching.device_put_batch(data, affinity,
                                                   kernel=RS_ENCODE)
            else:
                placed = jnp.asarray(data)
            dev = encode_blocks(bm, placed)
            ph.phase("wait")
            out = np.asarray(dev)
    KERNEL.record(RS_ENCODE, True, data.nbytes, t.s, blocks=blocks,
                  backend=batching.attempt_backend())
    return out


def reconstruct_batch(shards: np.ndarray, k: int, m: int,
                      available: tuple[int, ...],
                      missing: tuple[int, ...]) -> np.ndarray:
    """Rebuild `missing` data shards for a batch sharing one erasure mask.

    shards: (B, n_avail, S) uint8 — ONLY the survivor shards actually used,
    i.e. the first k available in index order (see decode_bitplane's `used`).
    Returns (B, len(missing), S) rebuilt shards.

    Batches are grouped by mask on the host (ops/batching.py) so each device
    call has a single dense matrix — SURVEY §7 hard part (f).
    """
    bm, used = decode_bitplane(k, m, available, missing)
    if shards.shape[-2] != len(used):
        raise ValueError(
            f"expected {len(used)} survivor shards, got {shards.shape[-2]}")
    return np.asarray(gf_apply(jnp.asarray(bm), jnp.asarray(shards)))
