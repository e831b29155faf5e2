"""Pallas packed-GF Reed-Solomon kernel: bytes in HBM, bit-planes in VMEM.

The round-1..3 device codec (rs_tpu.gf_apply) lowered GF(2^8) to a
bit-plane matmul in plain XLA: unpack bytes to (8k, S) bf16, matmul,
pack. XLA materializes the unpacked planes in HBM — 16x the input
bytes of traffic (8 planes x 2-byte bf16) — so the codec was HBM-bound
at a fraction of the achievable rate.

This kernel keeps the inflation on-chip:

    HBM:   (B, k, S) uint8  ->  (B, r, S) uint8    (bytes only)
    VMEM:  unpack (k,T)->(8k,T) bf16, MXU matmul, mask+pack

Per grid cell (one batch row x one lane tile T):
  1. load (k, T) bytes, widen to int32 on the VPU
  2. unpack LSB-first bit-planes as a CONCAT along sublanes — plane-major
     layout (plane a of all k bytes contiguous), not byte-major, so no
     sublane interleave is needed
  3. one (8r, 8k) @ (8k, T) MXU matmul, f32 accumulation — exact: the
     popcount per output bit is <= 8k <= 128 < 2^24
  4. mod-2 via int32 &1, pack 8 planes back to bytes with shifts+or

The (8r, 8k) GF(2) matrix is permuted host-side to match the
plane-major layout (_permute_bitplane): row b*r+i is bit b of output
byte i, column a*k+j is bit a of input byte j. The permutation is a
pure relabeling of the same GF(2) linear map, so results are
byte-identical to the XLA path and to the rs_cpu golden codec
(tests/test_rs_pallas.py, interpret mode).

Serves encode, reconstruct and heal exactly like rs_tpu.gf_apply — the
matrix is the only difference between them. Reference parity points:
cmd/erasure-coding.go:70 (EncodeData), :89 (DecodeDataBlocks); the
reference's AVX2 galois kernels are SIMD table lookups, which have no
MXU analogue — the bit-plane matmul is the TPU-native formulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128           # TPU lane width: last-dim tiles must be multiples
_MAX_TILE = 4096     # lanes per grid cell; bounds VMEM (see _tile_for)


@functools.lru_cache(maxsize=None)
def _plane_perms(r: int, k: int) -> tuple[np.ndarray, np.ndarray]:
    """(row_perm, col_perm) mapping plane-major positions to the
    byte-major layout of gf256.gf_matrix_to_bitplane."""
    rows = np.array([i * 8 + b for b in range(8) for i in range(r)],
                    dtype=np.int32)
    cols = np.array([j * 8 + a for a in range(8) for j in range(k)],
                    dtype=np.int32)
    return rows, cols


def _permute_bitplane(big_m: jnp.ndarray, r: int, k: int,
                      dtype=jnp.bfloat16) -> jnp.ndarray:
    """Byte-major (8r, 8k) bit matrix -> plane-major."""
    rows, cols = _plane_perms(r, k)
    return big_m[rows][:, cols].astype(dtype)


def _tile_for(r: int, k: int, S: int) -> int:
    """Lane-tile size: large enough to amortize grid overhead, small
    enough that the unpacked planes + accumulator fit VMEM comfortably
    (bits (8k,T) bf16 + acc (8r,T) f32 + int32 temps, double-buffered)."""
    budget = 6 * 1024 * 1024
    per_lane = 16 * k + 4 * 8 * r + 8 * k  # bf16 planes + f32 acc + temps
    t = min(_MAX_TILE, max(LANE, (budget // per_lane) // LANE * LANE))
    if S < t:
        t = (S + LANE - 1) // LANE * LANE
    return t


def _kernel(r: int, k: int, dtype, m_ref, x_ref, o_ref):
    """One (k, T) byte tile -> (r, T) byte tile."""
    xi = x_ref[...].astype(jnp.int32)                       # (k, T)
    planes = [((xi >> a) & 1) for a in range(8)]
    bits = jnp.concatenate(planes, axis=0).astype(dtype)    # (8k, T)
    acc = jnp.dot(m_ref[...], bits,
                  preferred_element_type=jnp.float32)       # (8r, T)
    ib = acc.astype(jnp.int32) & 1
    out = ib[0:r, :]
    for b in range(1, 8):
        out = out | (ib[b * r:(b + 1) * r, :] << b)
    o_ref[...] = out.astype(jnp.uint8)


@functools.partial(jax.jit,
                   static_argnames=("r", "k", "interpret", "with_data"))
def rs_gf_apply(big_m: jnp.ndarray, shards: jnp.ndarray, r: int, k: int,
               interpret: bool = False,
               with_data: bool = False) -> jnp.ndarray:
    """One fused dispatch: permute matrix, lane-pad, pallas_call,
    un-pad, and (encode) append parity to data — all under jit so the
    pad/slice/concat around the kernel never round-trip HBM separately."""
    lead = shards.shape[:-2]
    S = shards.shape[-1]
    B = 1
    for d in lead:
        B *= d
    # bf16 operands feed the MXU on TPU; interpret mode (CPU CI) uses
    # f32 — XLA-CPU has no bf16 dot thunk. Both are exact: operands are
    # 0/1 and the f32 accumulator holds popcounts <= 8k <= 128.
    dtype = jnp.float32 if interpret else jnp.bfloat16
    mperm = _permute_bitplane(big_m, r, k, dtype)
    # 3-D (B, k, S) blocked (1, k, T): the sublane block dim is the
    # WHOLE array dim, which Mosaic accepts for any k / r. Flattening
    # to (B*k, S) blocked (k, T) is refused as soon as B > 1 and k or
    # r is not a multiple of 8 (tests/test_chip_compile.py).
    x = shards.reshape(B, k, S)
    T = _tile_for(r, k, S)
    pad = (-S) % T
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
    Sp = S + pad
    grid = (B, Sp // T)
    out = pl.pallas_call(
        functools.partial(_kernel, r, k, dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((8 * r, 8 * k), lambda b, t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, k, T), lambda b, t: (b, 0, t),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((None, r, T), lambda b, t: (b, 0, t),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, r, Sp), jnp.uint8),
        cost_estimate=pl.CostEstimate(
            flops=2 * grid[0] * grid[1] * (8 * r) * (8 * k) * T,
            bytes_accessed=B * k * Sp + B * r * Sp,
            transcendentals=0),
        interpret=interpret,
        # Stable names: the trace's device line reads
        # jit_rs_gf_apply(<fingerprint>) and the kernel rs_gf_apply,
        # whatever the private functions here are called.
        name="rs_gf_apply",
    )(mperm, x)
    if pad:
        out = out[:, :, :S]
    out = out.reshape(*lead, r, S)
    if with_data:
        return jnp.concatenate([shards, out], axis=-2)
    return out


def check_args(big_m, shards) -> tuple[int, int]:
    """(r, k) of a well-formed call; ValueError on a caller bug. Looks
    at shapes only, so rs_tpu._dispatch can tell an argument error
    (raised here, BEFORE any kernel runs) from a kernel failure."""
    r, k = big_m.shape[0] // 8, big_m.shape[1] // 8
    if shards.ndim < 2 or shards.shape[-2] != k:
        raise ValueError(
            f"shards sublane dim {shards.shape[-2:-1]} != k={k}")
    return r, k


def _norm(big_m, shards) -> tuple[jnp.ndarray, jnp.ndarray, int, int]:
    big_m = jnp.asarray(big_m)
    shards = jnp.asarray(shards, dtype=jnp.uint8)
    r, k = check_args(big_m, shards)
    return big_m, shards, r, k


def gf_apply(big_m, shards, *, interpret: bool = False) -> jnp.ndarray:
    """Pallas drop-in for rs_tpu.gf_apply.

    big_m:  (8r, 8k) byte-major bit-plane matrix (0/1, any float/int
            dtype) — the SAME matrices rs_tpu builds; permutation to the
            kernel's plane-major layout happens in-jit.
    shards: (..., k, S) uint8.
    Returns (..., r, S) uint8, byte-identical to the XLA path.
    """
    big_m, shards, r, k = _norm(big_m, shards)
    return rs_gf_apply(big_m, shards, r, k, interpret=interpret)


def encode_blocks(big_m, data, *, interpret: bool = False) -> jnp.ndarray:
    """(..., k, S) data -> (..., k+m, S) all shards (parity appended)."""
    big_m, data, r, k = _norm(big_m, data)
    return rs_gf_apply(big_m, data, r, k, interpret=interpret,
                      with_data=True)


def _shard_map(fn, mesh, in_specs, out_specs):
    """shard_map with the varying-axes checker off: the kernel body is
    a pallas_call (whose out_shape declares no varying-axes info) and
    contains no collectives, so the check adds nothing but rejects the
    call."""
    from jax import shard_map
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def _apply_sharded(mesh, big_m, x, *, interpret: bool,
                   with_data: bool) -> jnp.ndarray:
    """Multi-chip apply: shard_map over the serving mesh, each device
    running the packed kernel on its local (B/nb, k, S/nl) block.

    GF(2^8) maps are independent per byte column and per batch row, so
    there are ZERO collectives — the mesh only partitions work. Specs
    come from parallel/mesh.batch_sharding (single source of truth for
    placement), so the shard_map matches how device_put_batch laid the
    data out and no resharding occurs.
    """
    from jax.sharding import PartitionSpec as P
    from ..parallel.mesh import batch_sharding
    big_m, x, r, k = _norm(big_m, x)
    if x.ndim != 3:
        raise ValueError("sharded apply expects (B, k, S)")
    B, _, S = x.shape
    spec = batch_sharding(mesh, B, S).spec
    fn = _shard_map(
        functools.partial(rs_gf_apply, r=r, k=k, interpret=interpret,
                          with_data=with_data),
        mesh, (P(None, None), spec), spec)
    return fn(big_m, x)


def gf_apply_sharded(mesh, big_m, shards, *,
                     interpret: bool = False) -> jnp.ndarray:
    return _apply_sharded(mesh, big_m, shards, interpret=interpret,
                          with_data=False)


def encode_blocks_sharded(mesh, big_m, data, *,
                          interpret: bool = False) -> jnp.ndarray:
    """Multi-chip encode: local data+parity concat on each device."""
    return _apply_sharded(mesh, big_m, data, interpret=interpret,
                          with_data=True)


def smoke() -> None:
    """One eager compile+run at a shape that fails when serving shapes
    would: B > 1, k and r below the 8-row sublane tile, S off the lane
    grid (the pad path). Raises unless the parity bytes match the host
    codec. Run ONCE by rs_tpu._pallas_enabled so a platform whose
    compiler refuses the kernel falls back eagerly, not at some
    caller's jit-compile time."""
    from .gf256 import gf_mat_vec_apply
    from .rs_matrix import parity_matrix
    from .rs_tpu import parity_bitplane
    B, k, m, S = 3, 4, 2, 2 * LANE + 44
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (B, k, S)).astype(np.uint8)
    got = np.asarray(gf_apply(parity_bitplane(k, m), data))
    for b in range(B):
        want = gf_mat_vec_apply(parity_matrix(k, m), data[b])
        if not np.array_equal(got[b], want):
            raise RuntimeError(
                "pallas smoke: parity bytes differ from host")
