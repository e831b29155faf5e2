"""The plain reference the benchmark's `correct` is decided against.

Imports nothing of the program and takes nothing the program made: its
own GF(2^8) tables, its own systematic-Vandermonde Reed-Solomon matrix
(the construction of the reference's codec dependency,
klauspost/reedsolomon `New(k, m)`: vm[r, c] = r^c, encode = vm @
inverse(vm[:k])), its own HighwayHash-256 (Google's portable algorithm,
vectorised across frames with numpy) under the reference's bitrot key
(cmd/bitrot.go:31 — HighwayHash-256 of the first 100 decimals of pi
under a zero key), and the streaming-bitrot shard layout
([32-byte digest][sub-block] per stripe block,
cmd/bitrot-streaming.go:46-57).

`shard_files(body, k, m, block)` gives the k+m shard files a PUT of
`body` has to leave on the drives, byte for byte.
"""

from __future__ import annotations

import numpy as np

# --- GF(2^8), polynomial x^8+x^4+x^3+x^2+1 (0x11d) ----------------------------


def _gf_tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= 0x11D
    exp[255:510] = exp[0:255]
    return exp, log


_EXP, _LOG = _gf_tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(_EXP[_LOG[a] + _LOG[b]])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("GF(2^8) inverse of 0")
    return int(_EXP[255 - _LOG[a]])


def gf_pow(a: int, n: int) -> int:
    """a^n with the dependency's galExp convention: a^0 == 1, 0^n == 0."""
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(_EXP[(_LOG[a] * n) % 255])


def _mat_mul(a: list[list[int]], b: list[list[int]]) -> list[list[int]]:
    out = [[0] * len(b[0]) for _ in a]
    for i, row in enumerate(a):
        for j in range(len(b[0])):
            acc = 0
            for t, x in enumerate(row):
                acc ^= gf_mul(x, b[t][j])
            out[i][j] = acc
    return out


def _mat_inv(m: list[list[int]]) -> list[list[int]]:
    n = len(m)
    a = [list(row) + [1 if i == j else 0 for j in range(n)]
         for i, row in enumerate(m)]
    for col in range(n):
        piv = next((r for r in range(col, n) if a[r][col]), None)
        if piv is None:
            raise ValueError("singular matrix")
        a[col], a[piv] = a[piv], a[col]
        inv = gf_inv(a[col][col])
        a[col] = [gf_mul(x, inv) for x in a[col]]
        for r in range(n):
            if r != col and a[r][col]:
                f = a[r][col]
                a[r] = [x ^ gf_mul(f, y) for x, y in zip(a[r], a[col])]
    return [row[n:] for row in a]


def parity_rows(k: int, m: int) -> list[list[int]]:
    """The m parity-generating rows of the systematic (k+m, k) matrix."""
    vm = [[gf_pow(r, c) for c in range(k)] for r in range(k + m)]
    enc = _mat_mul(vm, _mat_inv(vm[:k]))
    for i in range(k):
        if enc[i] != [1 if i == j else 0 for j in range(k)]:
            raise ValueError("encode matrix is not systematic")
    return enc[k:]


def _mul_table(c: int) -> np.ndarray:
    return np.array([gf_mul(c, x) for x in range(256)], dtype=np.uint8)


def rs_encode_block(block: bytes | memoryview, k: int, m: int,
                    rows: list[list[int]] | None = None) -> np.ndarray:
    """One stripe block -> (k+m, ceil(len/k)) uint8 shards: the block
    split into k zero-padded data shards, then m parity shards."""
    n = len(block)
    s = -(-n // k)
    data = np.zeros((k, s), dtype=np.uint8)
    data.reshape(-1)[:n] = np.frombuffer(block, dtype=np.uint8)
    out = np.empty((k + m, s), dtype=np.uint8)
    out[:k] = data
    rows = rows or parity_rows(k, m)
    for j, row in enumerate(rows):
        acc = np.zeros(s, dtype=np.uint8)
        for i, c in enumerate(row):
            if c:
                acc ^= _mul_table(c)[data[i]]
        out[k + j] = acc
    return out


# --- HighwayHash-256 ----------------------------------------------------------

_U = np.uint64
_M32 = _U(0xFFFFFFFF)
_S32 = _U(32)
_INIT0 = np.array([0xDBE6D5D5FE4CCE2F, 0xA4093822299F31D0,
                   0x13198A2E03707344, 0x243F6A8885A308D3], dtype=_U)
_INIT1 = np.array([0x3BD39E10CB0EF593, 0xC0ACF169B5F18A8C,
                   0xBE5466CF34E90C6C, 0x452821E638D01377], dtype=_U)
# ZipperMergeAndAdd as the byte shuffle it is (per 16-byte lane pair).
_ZIP16 = [3, 12, 2, 5, 14, 1, 15, 0, 11, 4, 10, 13, 9, 6, 8, 7]
_ZIP32 = np.array(_ZIP16 + [16 + i for i in _ZIP16], dtype=np.intp)

PI_100_DECIMALS = ("14159265358979323846264338327950288419716939937510"
                   "58209749445923078164062862089986280348253421170679")
# cmd/bitrot.go:31 magicHighwayHash256Key; test_reference.py re-derives
# it from the decimals above with this file's own hash.
BITROT_KEY = bytes.fromhex(
    "4be734fa8e238acd263e83e6bb968552040f935da39f441497e09d1322de36a0")


def _rot32(x: np.ndarray) -> np.ndarray:
    return (x >> _S32) | (x << _S32)


def _zipper(v: np.ndarray) -> np.ndarray:
    n = v.shape[0]
    return np.ascontiguousarray(v).view(np.uint8).reshape(n, 32)[
        :, _ZIP32].copy().view(_U).reshape(n, 4)


class _State:
    def __init__(self, key: bytes, n: int):
        kk = np.frombuffer(key, dtype="<u8").astype(_U)
        self.mul0 = np.tile(_INIT0, (n, 1))
        self.mul1 = np.tile(_INIT1, (n, 1))
        self.v0 = self.mul0 ^ kk
        self.v1 = self.mul1 ^ _rot32(kk)

    def update(self, lanes: np.ndarray) -> None:
        self.v1 += self.mul0 + lanes
        self.mul0 ^= (self.v1 & _M32) * (self.v0 >> _S32)
        self.v0 += self.mul1
        self.mul1 ^= (self.v0 & _M32) * (self.v1 >> _S32)
        self.v0 += _zipper(self.v1)
        self.v1 += _zipper(self.v0)

    def remainder(self, tail: np.ndarray) -> None:
        """tail: (n, r) uint8 with 0 < r < 32."""
        n, r = tail.shape
        mod4 = r & 3
        whole = r & ~3
        self.v0 += _U((r << 32) + r)
        lo = self.v1 & _M32
        hi = self.v1 >> _S32
        c = _U(r)
        back = _U(32 - r)
        lo = ((lo << c) | (lo >> back)) & _M32
        hi = ((hi << c) | (hi >> back)) & _M32
        self.v1 = lo | (hi << _S32)
        packet = np.zeros((n, 32), dtype=np.uint8)
        packet[:, :whole] = tail[:, :whole]
        if r & 16:
            for i in range(4):
                packet[:, 28 + i] = tail[:, whole + i + mod4 - 4]
        elif mod4:
            packet[:, 16] = tail[:, whole]
            packet[:, 17] = tail[:, whole + (mod4 >> 1)]
            packet[:, 18] = tail[:, whole + mod4 - 1]
        self.update(packet.view("<u8").astype(_U))

    def finalize(self) -> np.ndarray:
        for _ in range(10):
            v = self.v0
            self.update(np.stack([_rot32(v[:, 2]), _rot32(v[:, 3]),
                                  _rot32(v[:, 0]), _rot32(v[:, 1])], axis=1))
        out = np.empty((self.v0.shape[0], 4), dtype=_U)
        for half in (0, 2):
            a0 = self.v0[:, half] + self.mul0[:, half]
            a1 = self.v0[:, half + 1] + self.mul0[:, half + 1]
            a2 = self.v1[:, half] + self.mul1[:, half]
            a3 = (self.v1[:, half + 1] + self.mul1[:, half + 1]) \
                & _U(0x3FFFFFFFFFFFFFFF)
            out[:, half + 1] = a1 ^ ((a3 << _U(1)) | (a2 >> _U(63))) \
                ^ ((a3 << _U(2)) | (a2 >> _U(62)))
            out[:, half] = a0 ^ (a2 << _U(1)) ^ (a2 << _U(2))
        return out.astype("<u8").view(np.uint8).reshape(-1, 32)


def hh256_rows(rows: np.ndarray, key: bytes = BITROT_KEY) -> np.ndarray:
    """(n, L) uint8 -> (n, 32) digests: n equal-length messages hashed
    side by side (one numpy step per 32-byte packet, whatever n is)."""
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    n, length = rows.shape
    st = _State(key, n)
    packets = length // 32
    if packets:
        body = rows[:, :packets * 32]
        if not body.flags.c_contiguous:
            body = np.ascontiguousarray(body)
        lanes = body.view("<u8").reshape(n, packets, 4)
        with np.errstate(over="ignore"):
            for p in range(packets):
                st.update(lanes[:, p, :])
    with np.errstate(over="ignore"):
        if length % 32:
            st.remainder(rows[:, packets * 32:])
        return st.finalize()


def hh256(data: bytes, key: bytes = BITROT_KEY) -> bytes:
    arr = np.frombuffer(data, dtype=np.uint8).reshape(1, -1)
    return hh256_rows(arr, key)[0].tobytes()


# --- what a PUT has to leave on the drives ------------------------------------


def shard_blocks(body: bytes | memoryview, k: int, m: int, block: int
                 ) -> list[np.ndarray]:
    """Per stripe block, the (k+m, shard) array of sub-blocks."""
    rows = parity_rows(k, m)
    view = memoryview(body)
    return [rs_encode_block(view[off:off + block], k, m, rows)
            for off in range(0, len(view), block)]


def digests_for(blocks_of_objects: list[list[np.ndarray]]
                ) -> list[list[np.ndarray]]:
    """HighwayHash every sub-block of every object, grouping sub-blocks
    of equal length so each group costs one pass. Returns, per object
    and block, the (k+m, 32) digests."""
    groups: dict[int, list[tuple[int, int]]] = {}
    for oi, blocks in enumerate(blocks_of_objects):
        for bi, arr in enumerate(blocks):
            groups.setdefault(arr.shape[1], []).append((oi, bi))
    out = [[None] * len(b) for b in blocks_of_objects]
    for _, members in groups.items():
        stacked = np.concatenate(
            [blocks_of_objects[oi][bi] for oi, bi in members], axis=0)
        digs = hh256_rows(stacked)
        row = 0
        for oi, bi in members:
            t = blocks_of_objects[oi][bi].shape[0]
            out[oi][bi] = digs[row:row + t]
            row += t
    return out


def shard_files(blocks: list[np.ndarray], digests: list[np.ndarray]
                ) -> list[bytes]:
    """The k+m shard files: [digest][sub-block] per stripe block."""
    total = blocks[0].shape[0]
    files = []
    for s in range(total):
        parts = []
        for arr, dig in zip(blocks, digests):
            parts.append(dig[s].tobytes())
            parts.append(arr[s].tobytes())
        files.append(b"".join(parts))
    return files
