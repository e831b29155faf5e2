"""TPU HighwayHash-256: the bitrot checksum as a device kernel.

The reference hashes every shard sub-block with HighwayHash-256 on the
CPU (ref cmd/bitrot-streaming.go:46,115; cmd/bitrot.go:35-46). Here the
hash runs on the TPU, batched across independent sub-blocks: the hash is
a serial chain per chunk, so the parallelism is one chunk per batch row
and the packet loop stays sequential, INSIDE one device operation.

Representation. HighwayHash state is 4 lanes x 64-bit x 4 vectors (v0,
v1, mul0, mul1). TPUs have no u64, so every 64-bit lane is a (lo, hi)
pair of uint32, and all 64-bit ops (wrapping add, xor, 32x32->64
multiply, byte shuffles) are exact u32 arithmetic: digests are
byte-identical to ops/hh256.py (tests/test_hh256_tpu.py). The zipper
merge mixes lanes (0,1) and (2,3) only, so the state is kept as EVEN
lanes (0, 2) and ODD lanes (1, 3) apart: 16 arrays (v0, v1, mul0, mul1
x even/odd x lo/hi), each `(2, rows)`, and one packet update
(`_packet_update`) is ~210 elementwise operations with no slice, stack
or reshape between them.

Layout. The packet stream is transposed once on the device to
`(n_packets, 8, rows)`: packet-major, the 8 words of a packet in the
order [even.lo, even.hi, odd.lo, odd.hi] x [pair 0, pair 1] on the
sublane axis, rows on the lane axis (the device's tiled layout pads
them to the register's 128 lanes; the host never does). One packet of
up to 128 rows is one (8, 128) vector register; three sublane rotations bring even.hi, odd.lo and
odd.hi to sublanes 0-1 where the state lives (sublanes 2-7 of every
state register carry don't-care values).

Two forms of the one algorithm, chosen by the platform the program is
lowered for (`lax.platform_dependent`, i.e. by where the arrays live):

* TPU: one `pallas_call`. Grid (lane tiles of 128 rows: parallel,
  blocks of P packets: sequential). The 16 state registers of a tile
  are a revisited output block, set from the key's init at block 0;
  inside a block a loop over packets, unrolled `_UNROLL` times by hand
  (Mosaic's scf.for takes no partial unroll), reads packet `(8, 128)`
  from VMEM and applies `_packet_update`. Block rule: P =
  min(`_BLOCK_PACKETS`, n_packets) (4 KiB of VMEM a packet,
  double-buffered: 4 MiB); the last block runs the shorter loop the
  shapes give; packets are never padded (zeros would change the
  digest), and rows are not padded either: a block wider than the
  array reads don't-care lanes.
* elsewhere (CPU tests): a `lax.fori_loop` over packets in plain XLA,
  same `_packet_update`, `(2, rows)` arrays.

Measured (my chip runs, PR 26; TPU v5 lite): on the device's timeline
in the served large cell, 16 rows x 1.25 MiB = 40,960 packets take
2.000 ms a program (130 executions, 1.999-2.009 ms; 8 rows 1.970 ms),
of which the kernel ~1.6 ms = ~40 ns a packet (several vector
operations a cycle: the VPU's issue width binds, not HBM) and the
transpose 0.25 ms, against 124.99 ms = 3.05 us a packet for the
fori_loop it replaces. By the host clock, device-resident input: 2.74
ms against 126.4; 4, 8 and 16 rows alike; blocks of 128 to 2048 packets
alike; unroll 1 / 4 / 16 reads 3.19 / 2.71 / 2.61 ms and builds in
0.18 / 0.32 / 1.07 s (docs/KERNELS.md).

Chunks of ANY equal length hash on device: the remainder packet's
irregular byte layout depends only on len % 32, constant across the
batch (shard sub-blocks are equal-sized; ref cmd/erasure-coding.go:115
ShardSize), so it is pre-packed on the host with static layout; it, the
ten permute rounds and the modular reduction run once per dispatch in
XLA after the kernel. Only the ragged FINAL sub-block of a stream
differs per stream; it hashes on the host.

The operand. Every device dispatch enters through `hash_rows`, which
takes its callers' rows as they lie (shard arrays, survivor frames as
memoryviews into the bytes a drive returned, offsets into streams) and
`pack_rows` writes each row once into a fresh (cap, n_packets, 8)
uint32 array; cap, the next power of two above the row count
(`bucket_rows`), is decided there and nowhere else. The power-of-two
ladder stays on purpose: a dispatch at the rows it has (12 for an 8+4
PUT) builds programs the served cells never warmed, and a tree that
did so lost 10-19% of their goodput to in-window compiles while the
heal it sped up gained (PERF.md); the copies, not the padding rows,
were the heal's cost. tests/test_hh_dispatch_shapes.py pins the ladder.
"""

from __future__ import annotations

import struct
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hh256 import _INIT0, _INIT1, MAGIC_KEY

LANE = 128             # rows per lane tile: one vector register's width
_BLOCK_PACKETS = 512   # packets per grid step: 2 MiB of VMEM a buffer
_UNROLL = 4            # packet updates per loop iteration in the kernel

_M16 = np.uint32(0xFFFF)
_B0 = np.uint32(0x000000FF)
_B1 = np.uint32(0x0000FF00)
_B2 = np.uint32(0x00FF0000)
_B3 = np.uint32(0xFF000000)


def _swap32_int(x: int) -> int:
    return ((x & 0xFFFFFFFF) << 32) | (x >> 32)


@lru_cache(maxsize=8)
def _init_state_np(key: bytes) -> np.ndarray:
    """(16, 2) uint32: the state words of this key in kernel order,
    (v0, v1, mul0, mul1) x (even.lo, even.hi, odd.lo, odd.hi), each
    over the lane pairs (0|1, 2|3)."""
    kw = struct.unpack("<4Q", key)
    vecs = ([_INIT0[i] ^ kw[i] for i in range(4)],
            [_INIT1[i] ^ _swap32_int(kw[i]) for i in range(4)],
            _INIT0, _INIT1)
    # lane = 2 * pair + even/odd  ->  [vector, even/odd, pair]
    v = np.array(vecs, dtype=np.uint64).reshape(4, 2, 2).transpose(0, 2, 1)
    out = np.stack([v & np.uint64(0xFFFFFFFF), v >> np.uint64(32)],
                   axis=2).astype(np.uint32).reshape(16, 2)
    out.setflags(write=False)
    return out


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
    hi = m11 + (m01 >> 16) + (m10 >> 16) + (t >> 16)
    return a * b, hi


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


def _zipper(xlo, xhi, ylo, yhi):
    """Both outputs of hh256._zipper_merge_and_add for source lanes
    x = even (its `v0` parameter) and y = odd (its `v1`): the words to
    add to the even and to the odd target lane, (elo, ehi, olo, ohi).

    The reference's masks read byte by byte (dest <- source):
      even: 0<-x3 1<-y4 2<-x2 3<-x5 4<-y6 5<-x1 6<-y7 7<-x0
      odd:  0<-y3 1<-x4 2<-y2 3<-y5 4<-y1 5<-x6 6<-y0 7<-x7
    """
    elo = (xlo >> 24) | ((yhi & _B0) << 8) | (xlo & _B2) | \
        ((xhi & _B1) << 16)
    ehi = ((yhi >> 16) & _B0) | (xlo & _B1) | ((yhi >> 8) & _B2) | \
        (xlo << 24)
    olo = (ylo >> 24) | ((xhi & _B0) << 8) | (ylo & _B2) | \
        ((yhi & _B1) << 16)
    ohi = ((ylo >> 8) & _B0) | ((xhi >> 8) & _B1) | ((ylo & _B0) << 16) | \
        (xhi & _B3)
    return elo, ehi, olo, ohi


# --- the algorithm, once ------------------------------------------------------


def _lane_update(v0l, v0h, v1l, v1h, m0l, m0h, m1l, m1h, pl_, ph):
    """The per-lane half of hh256._update_lanes."""
    tl, th = _add64(m0l, m0h, pl_, ph)
    v1l, v1h = _add64(v1l, v1h, tl, th)           # v1 += mul0 + packet
    ql, qh = _mul32x32(v1l, v0h)
    m0l, m0h = m0l ^ ql, m0h ^ qh                 # mul0 ^= lo(v1)*hi(v0)
    v0l, v0h = _add64(v0l, v0h, m1l, m1h)         # v0 += mul1
    ql, qh = _mul32x32(v0l, v1h)
    m1l, m1h = m1l ^ ql, m1h ^ qh                 # mul1 ^= lo(v0)*hi(v1)
    return v0l, v0h, v1l, v1h, m0l, m0h, m1l, m1h


def _packet_update(st, pkt):
    """One 32-byte packet for every row: the ONE statement of the
    update, used by the kernel body on (8, 128) registers and by the
    XLA form and the finalisation on (2, rows) arrays.

    st: the 16 state arrays in `_init_state_np` order; pkt: (even.lo,
    even.hi, odd.lo, odd.hi) packet words. Elementwise only."""
    (v0el, v0eh, v0ol, v0oh, v1el, v1eh, v1ol, v1oh,
     m0el, m0eh, m0ol, m0oh, m1el, m1eh, m1ol, m1oh) = st
    pel, peh, pol, poh = pkt
    v0el, v0eh, v1el, v1eh, m0el, m0eh, m1el, m1eh = _lane_update(
        v0el, v0eh, v1el, v1eh, m0el, m0eh, m1el, m1eh, pel, peh)
    v0ol, v0oh, v1ol, v1oh, m0ol, m0oh, m1ol, m1oh = _lane_update(
        v0ol, v0oh, v1ol, v1oh, m0ol, m0oh, m1ol, m1oh, pol, poh)
    zel, zeh, zol, zoh = _zipper(v1el, v1eh, v1ol, v1oh)
    v0el, v0eh = _add64(v0el, v0eh, zel, zeh)     # v0 += zipper(v1)
    v0ol, v0oh = _add64(v0ol, v0oh, zol, zoh)
    zel, zeh, zol, zoh = _zipper(v0el, v0eh, v0ol, v0oh)
    v1el, v1eh = _add64(v1el, v1eh, zel, zeh)     # v1 += zipper(v0)
    v1ol, v1oh = _add64(v1ol, v1oh, zol, zoh)
    return (v0el, v0eh, v0ol, v0oh, v1el, v1eh, v1ol, v1oh,
            m0el, m0eh, m0ol, m0oh, m1el, m1eh, m1ol, m1oh)


def _permute_and_update(st):
    """update with permuted v0: lanes (2,3,0,1), 32-bit halves swapped.
    Even lanes (0, 2) and odd lanes (1, 3) each swap places, and
    swap32 of a pair is just (lo, hi) -> (hi, lo)."""
    v0el, v0eh, v0ol, v0oh = st[:4]
    return _packet_update(st, (v0eh[::-1], v0el[::-1],
                               v0oh[::-1], v0ol[::-1]))


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


def _split_packet(pkt):
    """(8, rows) packet in kernel word order -> the four (2, rows)
    arrays `_packet_update` takes."""
    return pkt[0:2], pkt[2:4], pkt[4:6], pkt[6:8]


# --- the packet loop: two forms -----------------------------------------------


def _absorb_xla(w, init):
    """Plain-XLA form: w (n, 8, B) packet stream, init (16, 2) ->
    (16, 2, B) state after all n packets."""
    B = w.shape[2]
    st = tuple(jnp.broadcast_to(init[i][:, None], (2, B))
               for i in range(16))

    def body(i, st):
        pkt = jax.lax.dynamic_index_in_dim(w, i, 0, keepdims=False)
        return _packet_update(st, _split_packet(pkt))

    return jnp.stack(jax.lax.fori_loop(0, w.shape[0], body, st))


def _absorb_kernel(P: int, n: int, init_ref, w_ref, o_ref):
    """One block of up to P packets for one lane tile of 128 rows.
    o_ref (16, 8, 128) is revisited over the block axis: the state."""
    j = pl.program_id(1)
    last = (n - 1) // P
    tail = n - last * P                 # packets in the last block

    @pl.when(j == 0)
    def _():
        o_ref[...] = init_ref[...]

    def update(p, st):
        pkt = w_ref[p]                  # (8, 128): one packet, all rows
        return _packet_update(st, (pkt, pltpu.roll(pkt, 6, 0),
                                   pltpu.roll(pkt, 4, 0),
                                   pltpu.roll(pkt, 2, 0)))

    def unrolled(i, st):
        for u in range(_UNROLL):
            st = update(i * _UNROLL + u, st)
        return st

    st = tuple(o_ref[i] for i in range(16))
    steps = jnp.where(j == last, tail // _UNROLL, P // _UNROLL)
    st = jax.lax.fori_loop(0, steps, unrolled, st)
    for i in range(16):
        o_ref[i] = st[i]

    if tail % _UNROLL:                  # the last block's odd packets
        @pl.when(j == last)
        def _():
            st = tuple(o_ref[i] for i in range(16))
            for p in range(tail - tail % _UNROLL, tail):
                st = update(p, st)
            for i in range(16):
                o_ref[i] = st[i]


def _absorb_pallas(w, init, *, interpret: bool = False):
    """The kernel form of `_absorb_xla`, same arguments and result."""
    n, _, B = w.shape
    G = -(-B // LANE)
    P = min(_BLOCK_PACKETS, n)
    # Sublane s of a state register holds lane pair s % 2; only
    # sublanes 0-1 are read back.
    tile = jnp.broadcast_to(jnp.tile(init, (1, 4))[:, :, None],
                            (16, 8, LANE))
    out = pl.pallas_call(
        partial(_absorb_kernel, P, n),
        grid=(G, -(-n // P)),
        in_specs=[
            pl.BlockSpec((16, 8, LANE), lambda g, j: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((P, 8, LANE), lambda g, j: (j, 0, g),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((16, 8, LANE), lambda g, j: (0, 0, g),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((16, 8, G * LANE), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=210 * n * 8 * G * LANE,
            bytes_accessed=4 * n * 8 * G * LANE,
            transcendentals=0),
        interpret=interpret,
        name="hh256_absorb",
    )(tile, w)
    return out[:, 0:2, :B]


def _packet_major(words):
    """(B, n, 8) u32, a packet's words in byte order (lane, half) ->
    (n, 8, B) in kernel word order (half-of-pair: even.lo, even.hi,
    odd.lo, odd.hi; then the lane pair). One device-side transpose."""
    B, n, _ = words.shape
    w = words.reshape(B, n, 2, 2, 2)            # pair, even/odd, lo/hi
    return jnp.transpose(w, (1, 3, 4, 2, 0)).reshape(n, 8, B)


def _digest_rows(words, rem_packet, init, n_packets: int, rem: int):
    """hh256_rows for the rows of one device."""
    B = words.shape[0]
    if n_packets:
        state = jax.lax.platform_dependent(
            _packet_major(words), init,
            tpu=_absorb_pallas, default=_absorb_xla)
    else:
        state = jnp.broadcast_to(init[:, :, None], (16, 2, B))
    st = tuple(state[i] for i in range(16))

    if rem:
        # v0 += (rem << 32) + rem; v1 = rot32_halves(v1, rem & 31)
        # (hh256._update_remainder with static sizes).
        r = jnp.full((2, B), rem, jnp.uint32)
        v0 = _add64(st[0], st[1], r, r) + _add64(st[2], st[3], r, r)
        v1 = tuple(_rot32_halves(x, rem & 31) for x in st[4:8])
        st = _packet_update(
            v0 + v1 + st[8:],
            _split_packet(_packet_major(rem_packet[:, None, :])[0]))

    # A loop, not ten unrolled rounds: XLA sinks each round's lane
    # reversal through the elementwise rounds before it, and the
    # unrolled program doubles per round (XLA:CPU never finished it).
    st = jax.lax.fori_loop(0, 10, lambda _, st: _permute_and_update(st),
                           st)

    (v0el, v0eh, v0ol, v0oh, v1el, v1eh, v1ol, v1oh,
     m0el, m0eh, m0ol, m0oh, m1el, m1eh, m1ol, m1oh) = st
    # h = mod_reduction over (v1[i]+mul1[i], v0[i]+mul0[i]) lane sums;
    # row p of each array is lane pair p: (h1, h0) then (h3, h2).
    sel, seh = _add64(v1el, v1eh, m1el, m1eh)
    sol, soh = _add64(v1ol, v1oh, m1ol, m1oh)
    tel, teh = _add64(v0el, v0eh, m0el, m0eh)
    tol, toh = _add64(v0ol, v0oh, m0ol, m0oh)
    holo, hohi, helo, hehi = _modular_reduction(
        sol, soh, sel, seh, tol, toh, tel, teh)
    # (2, 4, B) [pair, (even.lo, even.hi, odd.lo, odd.hi), row] ->
    # (B, 8) = h0 h1 h2 h3 as lo/hi words
    out = jnp.stack([helo, hehi, holo, hohi], axis=1)
    return jnp.transpose(out, (2, 0, 1)).reshape(B, 8)


@partial(jax.jit, static_argnames=("n_packets", "rem", "mesh"))
def hh256_rows(words, rem_packet, init, n_packets: int, rem: int,
               mesh=None):
    """The device program, under a name that says what it is: a trace's
    device line reads `jit_hh256_rows(<fingerprint>)`.

    words: (B, n_packets, 8) u32 (little-endian 64-bit lane pairs);
    rem_packet: (B, 8) u32 pre-packed remainder packet (ignored when
    rem == 0); init: (16, 2) u32 from _init_state_np. `mesh`: rows
    are sharded over every axis of this serving mesh (B divides it);
    each device hashes its own rows, no collectives.
    Returns (B, 8) u32 digests."""
    fn = partial(_digest_rows, n_packets=n_packets, rem=rem)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        from .rs_pallas import _shard_map
        rows = P(tuple(mesh.axis_names))
        fn = _shard_map(fn, mesh, (rows, rows, P()), rows)
    return fn(words, rem_packet, init)


_kernel_impl: list[str] = []    # the form the first program was built in


def kernel_report() -> dict:
    """Which form of the packet loop this process's device programs
    were built in (admin /codec-plan `hhKernel`): "pallas" on a TPU,
    "xla" elsewhere, "" before the first dispatch."""
    return {"kernel": _kernel_impl[0] if _kernel_impl else ""}


def _report_impl(mesh) -> None:
    """Mirror of `lax.platform_dependent`'s choice in `_digest_rows`
    (the platform of the devices the rows go to), for the info series;
    once, when the first program is built."""
    if not _kernel_impl:
        from ..obs.metrics2 import METRICS2
        dev = jax.devices()[0] if mesh is None else mesh.devices.flat[0]
        _kernel_impl.append("pallas" if dev.platform == "tpu" else "xla")
        METRICS2.set_gauge("minio_tpu_v2_hh256_kernel_info",
                           {"impl": _kernel_impl[0]}, 1)


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


def bucket_rows(B: int) -> int:
    """The batch dimension B rows are dispatched at: the next power of
    two, so a process builds one program per (bucket, length) and every
    bucket of four rows or more divides a 2x2 mesh. Computed here only
    (see `pack_rows`)."""
    return 1 << max(B - 1, 0).bit_length()


def _row_bytes(row) -> np.ndarray:
    """A row as a uint8 array over the caller's memory: an ndarray (any
    strides) as it is, any other buffer (bytes, memoryview) through
    `np.frombuffer`. Nothing is copied."""
    if isinstance(row, np.ndarray):
        return row
    return np.frombuffer(row, dtype=np.uint8)


def pack_rows(rows, L: int) -> tuple[np.ndarray, np.ndarray]:
    """The device operand of one HH256 dispatch, built in one place.

    rows: B >= 1 rows of L bytes each: a (B, L) uint8 array or a
    sequence of buffers (bytes, memoryview, numpy rows or views, any
    strides). Returns (words, rem_packet): a fresh C-contiguous
    (cap, L // 32, 8) uint32 array holding each row's full packets and
    the (cap, 8) remainder packets, cap = `bucket_rows(B)`. Each row's
    bytes are written once, straight from the caller's memory; the
    cap - B padding rows are `np.zeros` pages the host never writes,
    and their digests are the caller's to drop. Neither array is a view
    of caller memory."""
    B = len(rows)
    cap = bucket_rows(B)
    n_full, rem = divmod(L, 32)
    words = np.zeros((cap, n_full, 8), dtype=np.uint32)
    body = words.view(np.uint8).reshape(cap, n_full * 32)
    tail = np.zeros((cap, rem), dtype=np.uint8)
    for i, row in enumerate(rows):
        a = _row_bytes(row)
        if a.shape != (L,):
            raise ValueError(
                f"row {i} is {a.shape} bytes, the batch's rows ({L},)")
        body[i] = a[:n_full * 32]
        tail[i] = a[n_full * 32:]
    if rem:
        rem_packet = _pack_remainder(tail, rem)
    else:
        rem_packet = np.zeros((cap, 8), dtype=np.uint32)
    return words, rem_packet


def hash_rows(rows, key: bytes = MAGIC_KEY) -> np.ndarray:
    """Hash B equal-length rows on the device: ONE dispatch at
    `bucket_rows(B)` rows, its operand built by `pack_rows` inside the
    dispatch's prep phase.

    rows: what `pack_rows` takes, L > 0 (any length — the remainder
    step is in-kernel). Returns (B, 32) uint8 HighwayHash-256 digests,
    byte-identical to ops/hh256.HighwayHash256."""
    B = len(rows)
    if B == 0:
        raise ValueError("no rows to hash")
    L = _row_bytes(rows[0]).size
    if L == 0:
        raise ValueError("chunk length must be positive")
    from . import batching
    from ..obs.kernel_stats import HH256, KERNEL, dispatch, timed
    cap = bucket_rows(B)
    nbytes = cap * L
    with dispatch(HH256, rows=cap, nbytes=nbytes) as ph:
        words, rem_packet = pack_rows(rows, L)
        n_full, rem = divmod(L, 32)
        init = _init_state_np(key)
        # Spread independent chunks across the serving mesh; the hash
        # chain is per-row, so no cross-device collectives.
        m = batching.serving_mesh()
        sharded = m is not None and cap % m.size == 0
        if m is not None:
            # Rows shard over every mesh device when the bucket divides
            # it; one that does not stays whole on the default device
            # (index 0). The census takes either as it is placed.
            from ..parallel.mesh import MESH_AFFINITY
            if sharded:
                from ..parallel.mesh import rows_sharding
                words = jax.device_put(words, rows_sharding(m, cap, 3))
                rem_packet = jax.device_put(rem_packet,
                                            rows_sharding(m, cap, 2))
                MESH_AFFINITY.record_dispatch(
                    HH256, tuple(range(m.size)), nbytes, nbytes // m.size)
            else:
                MESH_AFFINITY.record_dispatch(HH256, (0,), nbytes)
        mesh = m if sharded else None
        _report_impl(mesh)
        # The placement above is prep: enqueue + wait is the timed()
        # region, kernel_dispatch_ms, and nothing else.
        ph.phase("enqueue")
        with timed() as t:
            dev = hh256_rows(words, rem_packet, init, n_full, rem,
                             mesh=mesh)
            ph.phase("wait")
            # C-contiguous: a TPU result can come back in the device's
            # own (column-major) layout, and the byte view below needs
            # the last axis contiguous. On the CPU it always was.
            out = np.ascontiguousarray(dev)
    KERNEL.record(HH256, True, nbytes, t.s, blocks=cap,
                  backend=batching.attempt_backend())
    KERNEL.record_operand(HH256, copied=B * L, padded=(cap - B) * L)
    return out.view(np.uint8).reshape(cap, 32)[:B]


def hash_chunks(chunks: np.ndarray, key: bytes = MAGIC_KEY) -> np.ndarray:
    """`hash_rows` over the rows of a (B, L) uint8 array."""
    if chunks.ndim != 2:
        raise ValueError("chunks must be (B, L)")
    return hash_rows(chunks, key)
