"""TPU HighwayHash-256: the bitrot checksum as a device kernel.

The reference hashes every shard sub-block with HighwayHash-256 on the
CPU (ref cmd/bitrot-streaming.go:46,115; cmd/bitrot.go:35-46). Here the
hash runs on the TPU, batched across independent sub-blocks — the
TPU-native redesign is *batch* parallelism (one chunk per batch row, the
packet loop sequential in a `lax.fori_loop`), because the hash itself is
a serial chain per chunk.

TPU-first representation: HighwayHash state is 4 lanes x 64-bit x 4
vectors (v0, v1, mul0, mul1). TPUs have no fast u64, so every 64-bit
lane is a (lo, hi) pair of uint32 arrays of shape (B, 4) — B independent
chunks hashed in lockstep on the VPU. All 64-bit ops (wrapping add, xor,
32x32->64 multiply, constant shifts, byte shuffles) are emulated with
exact u32 arithmetic, so digests are byte-identical to ops/hh256.py
(asserted in tests/test_hh256_tpu.py against the magic-key vector and
random chunk patterns).

Chunks of ANY equal length hash on device: full 32-byte packets run in
the fori_loop, and the remainder step runs in-kernel too — its
irregular byte-packing depends only on len % 32, which is constant
across the batch (shard sub-blocks are equal-sized; ref
cmd/erasure-coding.go:115 ShardSize), so the remainder packet is
pre-packed on the host with static layout. Only the ragged FINAL
sub-block of a stream differs per stream; it hashes on the host.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from .hh256 import _INIT0, _INIT1, MAGIC_KEY

_M16 = 0xFFFF


def _swap32_int(x: int) -> int:
    return ((x & 0xFFFFFFFF) << 32) | (x >> 32)


@lru_cache(maxsize=8)
def _init_state_np(key: bytes) -> tuple[np.ndarray, ...]:
    """(v0lo, v0hi, v1lo, v1hi, mul0lo, mul0hi, mul1lo, mul1hi), each
    (4,) uint32 — the per-lane init vectors for this key."""
    import struct
    kw = struct.unpack("<4Q", key)
    v0 = [_INIT0[i] ^ kw[i] for i in range(4)]
    v1 = [_INIT1[i] ^ _swap32_int(kw[i]) for i in range(4)]
    mul0, mul1 = list(_INIT0), list(_INIT1)

    def split(vals):
        lo = np.array([v & 0xFFFFFFFF for v in vals], dtype=np.uint32)
        hi = np.array([v >> 32 for v in vals], dtype=np.uint32)
        return lo, hi

    return (*split(v0), *split(v1), *split(mul0), *split(mul1))


# --- u64-as-u32-pair primitives (all exact, wrapping) ------------------------


def _add64(alo, ahi, blo, bhi):
    rlo = alo + blo
    carry = (rlo < alo).astype(jnp.uint32)
    return rlo, ahi + bhi + carry


def _mul32x32(a, b):
    """Full 64-bit product of two u32 arrays -> (lo, hi) u32."""
    a0 = a & _M16
    a1 = a >> 16
    b0 = b & _M16
    b1 = b >> 16
    m00 = a0 * b0
    m01 = a0 * b1
    m10 = a1 * b0
    m11 = a1 * b1
    t = (m00 >> 16) + (m01 & _M16) + (m10 & _M16)
    lo = (m00 & _M16) | (t << 16)
    hi = m11 + (m01 >> 16) + (m10 >> 16) + (t >> 16)
    return lo, hi


def _shl64(lo, hi, k: int):
    if k == 0:
        return lo, hi
    if k >= 32:
        return lo * 0, lo << (k - 32) if k > 32 else lo
    return lo << k, (hi << k) | (lo >> (32 - k))


def _shr64(lo, hi, k: int):
    if k == 0:
        return lo, hi
    if k >= 32:
        return hi >> (k - 32) if k > 32 else hi, hi * 0
    return (lo >> k) | (hi << (32 - k)), hi >> k


def _byte64(lo, hi, idx: int):
    """Byte `idx` (0 = least significant) of each 64-bit lane, as u32."""
    w = lo if idx < 4 else hi
    return (w >> (8 * (idx % 4))) & 0xFF


def _from_bytes64(byte_map: list[tuple[int, object]]):
    """Assemble (lo, hi) from [(dest_byte_idx, u32_byte_array), ...]."""
    lo = None
    hi = None
    for dest, b in byte_map:
        w = b << (8 * (dest % 4))
        if dest < 4:
            lo = w if lo is None else lo | w
        else:
            hi = w if hi is None else hi | w
    z = (byte_map[0][1] * 0)
    return (z if lo is None else lo), (z if hi is None else hi)


def _zipper_lo(xlo, xhi, ylo, yhi):
    """First zipper-merge output: formula of hh256._zipper_merge_and_add
    for add[i0], with x = the `v0` param, y = the `v1` param.

    Byte-level reading of the reference masks (dest <- source byte):
      0<-x3? no: ((x & 0xFF000000)|(y & 0xFF00000000)) >> 24 places
      x byte3 at byte0 and y byte4 at byte1, etc.
    """
    return _from_bytes64([
        (0, _byte64(xlo, xhi, 3)), (1, _byte64(ylo, yhi, 4)),
        (3, _byte64(xlo, xhi, 5)), (4, _byte64(ylo, yhi, 6)),
        (2, _byte64(xlo, xhi, 2)), (5, _byte64(xlo, xhi, 1)),
        (6, _byte64(ylo, yhi, 7)), (7, _byte64(xlo, xhi, 0)),
    ])


def _zipper_hi(xlo, xhi, ylo, yhi):
    """Second zipper-merge output (add[i1]), same parameter convention."""
    return _from_bytes64([
        (0, _byte64(ylo, yhi, 3)), (1, _byte64(xlo, xhi, 4)),
        (2, _byte64(ylo, yhi, 2)), (3, _byte64(ylo, yhi, 5)),
        (4, _byte64(ylo, yhi, 1)), (5, _byte64(xlo, xhi, 6)),
        (6, _byte64(ylo, yhi, 0)), (7, _byte64(xlo, xhi, 7)),
    ])


# --- the kernel ---------------------------------------------------------------


def _update_lanes(state, plo, phi):
    """One 32-byte packet for all B chunks.

    state: dict of (4, B) u32 arrays; plo/phi: (4, B) packet words.

    Layout note (TPU): the BATCH dim is the minor (lane) axis. With the
    natural (B, 4) layout the 4-wide lane dim pads to the 128-lane VPU
    register — 3% lane utilization; transposed, every elementwise op in
    the packet chain runs min(B, 128)/128 of the VPU.
    """
    v0lo, v0hi = state["v0lo"], state["v0hi"]
    v1lo, v1hi = state["v1lo"], state["v1hi"]
    m0lo, m0hi = state["m0lo"], state["m0hi"]
    m1lo, m1hi = state["m1lo"], state["m1hi"]

    # v1 += mul0 + lanes
    tlo, thi = _add64(m0lo, m0hi, plo, phi)
    v1lo, v1hi = _add64(v1lo, v1hi, tlo, thi)
    # mul0 ^= lo32(v1) * hi32(v0)
    qlo, qhi = _mul32x32(v1lo, v0hi)
    m0lo, m0hi = m0lo ^ qlo, m0hi ^ qhi
    # v0 += mul1
    v0lo, v0hi = _add64(v0lo, v0hi, m1lo, m1hi)
    # mul1 ^= lo32(v0) * hi32(v1)
    qlo, qhi = _mul32x32(v0lo, v1hi)
    m1lo, m1hi = m1lo ^ qlo, m1hi ^ qhi

    # Zipper merges. Lane pairing: calls are (v1[1],v1[0])->v0[1],v0[0]
    # and (v1[3],v1[2])->v0[3],v0[2]; then the same with v0 as source
    # and v1 as target. Source "x" = even lanes, "y" = odd lanes.
    def zip_add(src_lo, src_hi, dst_lo, dst_hi):
        xlo, xhi = src_lo[0::2], src_hi[0::2]         # lanes 0, 2
        ylo, yhi = src_lo[1::2], src_hi[1::2]         # lanes 1, 3
        e_lo, e_hi = _zipper_lo(xlo, xhi, ylo, yhi)   # -> dst lanes 0, 2
        o_lo, o_hi = _zipper_hi(xlo, xhi, ylo, yhi)   # -> dst lanes 1, 3
        add_lo = jnp.stack([e_lo, o_lo], axis=1).reshape(dst_lo.shape)
        add_hi = jnp.stack([e_hi, o_hi], axis=1).reshape(dst_hi.shape)
        return _add64(dst_lo, dst_hi, add_lo, add_hi)

    v0lo, v0hi = zip_add(v1lo, v1hi, v0lo, v0hi)
    v1lo, v1hi = zip_add(v0lo, v0hi, v1lo, v1hi)

    return {"v0lo": v0lo, "v0hi": v0hi, "v1lo": v1lo, "v1hi": v1hi,
            "m0lo": m0lo, "m0hi": m0hi, "m1lo": m1lo, "m1hi": m1hi}


def _permute_and_update(state):
    """update with permuted v0: lanes (2,3,0,1), 32-bit halves swapped.
    swap32 in pair representation is just (lo, hi) -> (hi, lo)."""
    perm = jnp.array([2, 3, 0, 1])
    plo = state["v0hi"][perm]      # swapped halves: lo <- hi
    phi = state["v0lo"][perm]
    return _update_lanes(state, plo, phi)


def _modular_reduction(a3lo, a3hi, a2lo, a2hi, a1lo, a1hi, a0lo, a0hi):
    """(m1, m0) pairs per hh256._modular_reduction."""
    a3hi = a3hi & 0x3FFFFFFF           # a3 &= 2^62-1 (top 2 bits of hi)
    s1lo, s1hi = _shl64(a3lo, a3hi, 1)
    r1lo, r1hi = _shr64(a2lo, a2hi, 63)
    s2lo, s2hi = _shl64(a3lo, a3hi, 2)
    r2lo, r2hi = _shr64(a2lo, a2hi, 62)
    m1lo = a1lo ^ (s1lo | r1lo) ^ (s2lo | r2lo)
    m1hi = a1hi ^ (s1hi | r1hi) ^ (s2hi | r2hi)
    t1lo, t1hi = _shl64(a2lo, a2hi, 1)
    t2lo, t2hi = _shl64(a2lo, a2hi, 2)
    m0lo = a0lo ^ t1lo ^ t2lo
    m0hi = a0hi ^ t1hi ^ t2hi
    return m1lo, m1hi, m0lo, m0hi


def _rot32_halves(w, c: int):
    """Rotate each 32-bit word left by c (the u64 halves rotate
    independently, so pair representation needs no cross-word bits)."""
    if c == 0:
        return w
    return (w << c) | (w >> (32 - c))


@partial(jax.jit, static_argnames=("n_packets", "rem"))
def hh256_rows(words, rem_packet, init, n_packets: int, rem: int):
    """The device program, under a name that says what it is: a trace's
    device line reads `jit_hh256_rows(<fingerprint>)`.

    words: (B, n_packets, 8) u32 (little-endian 64-bit lane pairs);
    rem_packet: (B, 8) u32 pre-packed remainder packet (ignored when
    rem == 0); init: 8 x (4,) u32 from _init_state_np.
    Returns (B, 8) u32 digests."""
    B = words.shape[0]
    # Batch-minor layout: (n, 8, B) packet stream, (4, B) state (see
    # _update_lanes layout note). One device-side transpose up front.
    words = jnp.transpose(words, (1, 2, 0))
    rem_t = rem_packet.T
    names = ("v0lo", "v0hi", "v1lo", "v1hi", "m0lo", "m0hi", "m1lo", "m1hi")
    state = {n: jnp.broadcast_to(init[i][:, None], (4, B)).astype(jnp.uint32)
             for i, n in enumerate(names)}

    def body(i, st):
        pkt = jax.lax.dynamic_slice_in_dim(words, i, 1, axis=0)[0]
        plo = pkt[0::2]
        phi = pkt[1::2]
        return _update_lanes(st, plo, phi)

    if n_packets:
        state = jax.lax.fori_loop(0, n_packets, body, state)

    if rem:
        # v0 += (rem << 32) + rem; v1 = rot32_halves(v1, rem & 31)
        # (hh256._update_remainder with static sizes).
        rlo = jnp.uint32(rem)
        state["v0lo"], state["v0hi"] = _add64(
            state["v0lo"], state["v0hi"],
            jnp.broadcast_to(rlo, (4, B)), jnp.broadcast_to(rlo, (4, B)))
        state["v1lo"] = _rot32_halves(state["v1lo"], rem & 31)
        state["v1hi"] = _rot32_halves(state["v1hi"], rem & 31)
        state = _update_lanes(state, rem_t[0::2], rem_t[1::2])

    for _ in range(10):
        state = _permute_and_update(state)

    # h = mod_reduction over (v1[i]+mul1[i], v0[i]+mul0[i]) lane sums.
    slo, shi = _add64(state["v1lo"], state["v1hi"],
                      state["m1lo"], state["m1hi"])   # v1 + mul1
    tlo, thi = _add64(state["v0lo"], state["v0hi"],
                      state["m0lo"], state["m0hi"])   # v0 + mul0
    h1lo, h1hi, h0lo, h0hi = _modular_reduction(
        slo[1], shi[1], slo[0], shi[0],
        tlo[1], thi[1], tlo[0], thi[0])
    h3lo, h3hi, h2lo, h2hi = _modular_reduction(
        slo[3], shi[3], slo[2], shi[2],
        tlo[3], thi[3], tlo[2], thi[2])
    out = jnp.stack([h0lo, h0hi, h1lo, h1hi, h2lo, h2hi, h3lo, h3hi],
                    axis=1)
    return out


def _pack_remainder(tail: np.ndarray, rem: int) -> np.ndarray:
    """(B, rem) trailing bytes -> (B, 8) u32 remainder packets, exactly
    hh256._update_remainder's byte layout (static given rem)."""
    B = tail.shape[0]
    size_mod4 = rem & 3
    remainder_off = rem & ~3
    packet = np.zeros((B, 32), dtype=np.uint8)
    packet[:, :remainder_off] = tail[:, :remainder_off]
    if rem & 16:
        for i in range(4):
            packet[:, 28 + i] = tail[:, remainder_off + i + size_mod4 - 4]
    elif size_mod4:
        packet[:, 16] = tail[:, remainder_off]
        packet[:, 17] = tail[:, remainder_off + (size_mod4 >> 1)]
        packet[:, 18] = tail[:, remainder_off + size_mod4 - 1]
    return packet.view(np.uint32)


def hash_chunks(chunks: np.ndarray, key: bytes = MAGIC_KEY,
                t_prep: float | None = None) -> np.ndarray:
    """Hash B equal-length chunks on the device.

    chunks: (B, L) uint8, L > 0 (any length — the remainder step is
    in-kernel). Returns (B, 32) uint8 HighwayHash-256 digests,
    byte-identical to ops/hh256.HighwayHash256. `t_prep`: when the
    caller's own packing for this dispatch began (perf_counter), so the
    dispatch's prep phase holds it too.
    """
    if chunks.ndim != 2:
        raise ValueError("chunks must be (B, L)")
    B, L = chunks.shape
    if L == 0:
        raise ValueError("chunk length must be positive")
    from . import batching
    from ..obs.kernel_stats import HH256, KERNEL, dispatch, timed
    with dispatch(HH256, rows=B, nbytes=chunks.nbytes,
                  t_prep=t_prep) as ph:
        n_full, rem = divmod(L, 32)
        chunks = np.ascontiguousarray(chunks)
        words = chunks[:, :n_full * 32].copy().view(np.uint32).reshape(
            B, n_full, 8)
        if rem:
            rem_packet = _pack_remainder(chunks[:, n_full * 32:], rem)
        else:
            rem_packet = np.zeros((B, 8), dtype=np.uint32)
        init = _init_state_np(key)
        ph.phase("enqueue")
        # Spread independent chunks across the serving mesh; the hash
        # chain is per-row, so no cross-device collectives.
        m = batching.serving_mesh()
        if m is not None:
            # Rows shard over every mesh device when B divides it; a
            # batch that does not stays whole on the default device
            # (index 0).
            from ..obs.metrics2 import METRICS2
            sharded = B % m.size == 0
            METRICS2.inc("minio_tpu_v2_hh256_mesh_dispatches_total",
                         {"placement": "sharded" if sharded else "single"})
            if sharded:
                from ..parallel.mesh import rows_sharding
                words = jax.device_put(words, rows_sharding(m, B, 3))
                rem_packet = jax.device_put(rem_packet,
                                            rows_sharding(m, B, 2))
        with timed() as t:
            dev = hh256_rows(words, rem_packet, init, n_full, rem)
            ph.phase("wait")
            # C-contiguous: a TPU result can come back in the device's
            # own (column-major) layout, and the byte view below needs
            # the last axis contiguous. On the CPU it always was.
            out = np.ascontiguousarray(dev)
    KERNEL.record(HH256, True, chunks.nbytes, t.s, blocks=B,
                  backend=batching.attempt_backend())
    return out.view(np.uint8).reshape(B, 32)
