"""Erasure codec orchestration: the reference's `Erasure` struct rebuilt
around batched TPU dispatch.

Size semantics are byte-compatible with the reference (ref
cmd/erasure-coding.go:115-143 ShardSize/ShardFileSize/ShardFileOffset and
the Split padding of its codec dependency): objects are striped into
`block_size` blocks; each block splits into k shards of ceil(block/k)
bytes (zero-padded) plus m parity shards.

Backend selection (SURVEY §7 hard part c): a device dispatch has a
fixed cost small batches cannot amortize, so they must not pay a device
round-trip.  The crossover is MEASURED, not hardwired: ``ops/autotune.py`` probes every
dispatch lane at boot and refines per-(kernel, batch-size-bucket)
throughput from live dispatches; this module only consults the plan
(pinned ``backend="tpu"|"cpu"`` bypasses it).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ..ops import batching, rs_cpu, rs_tpu
from ..ops.autotune import (AUTOTUNE, DEFAULT_DEVICE_MIN_BYTES,
                            RS_DECODE, RS_ENCODE)
from ..utils import ceil_frac

# Default stripe block: 10 MiB (ref cmd/object-api-common.go:32).
BLOCK_SIZE = 10 * 1024 * 1024

# Back-compat alias: the static pre-measurement crossover now lives in
# ops/autotune.py (the one sanctioned hardwired threshold, R9); no
# dispatch decision compares against it here anymore.
TPU_MIN_BYTES = DEFAULT_DEVICE_MIN_BYTES


@dataclass
class Erasure:
    data_blocks: int
    parity_blocks: int
    block_size: int = BLOCK_SIZE
    backend: str = "auto"  # "auto" | "cpu" | "tpu"
    # Home device of the owning erasure set (parallel/mesh.py
    # DeviceAffinity, assigned by ErasureObjects): concurrent sets'
    # dispatches spread across the mesh instead of queueing on chip 0.
    affinity: int | None = field(default=None, repr=False)

    def __post_init__(self):
        if self.data_blocks <= 0 or self.parity_blocks <= 0:
            raise ValueError("data and parity block counts must be positive")
        if self.data_blocks + self.parity_blocks > 256:
            raise ValueError("too many shards (k+m > 256)")

    # --- sizes (byte-compatible with the reference) ---

    @property
    def total_shards(self) -> int:
        return self.data_blocks + self.parity_blocks

    def shard_size(self) -> int:
        """Per-shard size of a full block (ref cmd/erasure-coding.go:115)."""
        return ceil_frac(self.block_size, self.data_blocks)

    def chunk_size(self, block_len: int) -> int:
        """Per-shard stored bytes for a block of block_len bytes (the
        codec-agnostic form the read/heal paths size their frames with
        — RegenErasure's differs from this k-way split)."""
        return ceil_frac(block_len, self.data_blocks)

    def shard_file_size(self, total_length: int) -> int:
        """On-disk per-shard data size for an object of total_length bytes
        (ref cmd/erasure-coding.go:120)."""
        if total_length == 0:
            return 0
        if total_length < 0:
            return -1
        num_shards = total_length // self.block_size
        last_block_size = total_length % self.block_size
        last_shard_size = ceil_frac(last_block_size, self.data_blocks)
        return num_shards * self.shard_size() + last_shard_size

    def shard_file_offset(self, start_offset: int, length: int,
                          total_length: int) -> int:
        """Until-offset for shard reads covering [start, start+length)
        (ref cmd/erasure-coding.go:134)."""
        shard_size = self.shard_size()
        shard_file_size = self.shard_file_size(total_length)
        end_shard = (start_offset + length) // self.block_size
        till = end_shard * shard_size + shard_size
        return min(till, shard_file_size)

    # --- encode / decode ---

    def _use_tpu(self, nbytes: int, kernel: str = RS_ENCODE) -> bool:
        """Route this batch through the jitted rs_tpu path?  Pins win
        ("cpu" never, "tpu" always — the operator asked for errors,
        not silent rerouting); "auto" asks the measured plan
        (ops/autotune.py), which never picks a kernprof-DOWN lane."""
        if self.backend == "cpu":
            return False
        if self.backend == "tpu":
            return True
        return AUTOTUNE.use_jit_lane(kernel, nbytes)

    def _use_tpu_decode(self, nbytes: int) -> bool:
        return self._use_tpu(nbytes, RS_DECODE)

    # Note: the host branches below consult the planner a second time
    # (AUTOTUNE.host_lane) after _use_tpu said "not jit".  Deliberate:
    # _use_tpu is the test-override seam (monkeypatched to force the
    # jit path), so the decision can't be collapsed into one call
    # without breaking it; the second consult is a dict lookup per
    # DISPATCH, and a plan flip between the two calls just falls back
    # to the native-first default — benign and self-correcting.

    def _coalesce_ok(self) -> bool:
        """Route encodes through the cross-request coalescer? Only
        when the backend isn't pinned and the plan still sends encode
        work to a real device — the window buys nothing (and costs its
        latency) in front of host encodes."""
        return (self.backend == "auto"
                and AUTOTUNE.coalesce_worthwhile())

    def encode_data(self, data: bytes | np.ndarray) -> np.ndarray:
        """Encode one block: returns (k+m, shard_len) uint8
        (ref EncodeData, cmd/erasure-coding.go:70)."""
        buf = np.frombuffer(data, dtype=np.uint8) if isinstance(
            data, (bytes, bytearray, memoryview)) else data
        if buf.size == 0:
            return np.zeros((self.total_shards, 0), dtype=np.uint8)
        shards = rs_cpu.split(buf, self.data_blocks, self.parity_blocks)
        if self.backend == "tpu":
            out = rs_tpu.encode_batch(
                shards[None, :self.data_blocks, :],
                self.data_blocks, self.parity_blocks,
                affinity=self.affinity)[0]
            batching.STATS.add(True, shards[:self.data_blocks].nbytes)
            return out
        data_bytes = shards[:self.data_blocks].nbytes
        if self._coalesce_ok():
            return batching.get_coalescer().encode(
                shards[None, :self.data_blocks, :],
                self.data_blocks, self.parity_blocks,
                affinity=self.affinity)[0]
        if self._use_tpu(data_bytes):
            # Plan picked the jit lane while the coalescer window is
            # off (e.g. XLA-CPU measured fastest with no device): one
            # direct dispatch.
            out = rs_tpu.encode_batch(
                shards[None, :self.data_blocks, :],
                self.data_blocks, self.parity_blocks,
                affinity=self.affinity)[0]
            batching.STATS.add(True, data_bytes)
            return out
        from ..obs.kernel_stats import KERNEL, timed
        from ..ops.rs_matrix import parity_matrix
        with timed() as t:
            parity, host_backend = batching.host_apply_tagged(
                parity_matrix(self.data_blocks, self.parity_blocks),
                shards[:self.data_blocks],
                AUTOTUNE.host_lane(RS_ENCODE, data_bytes))
            shards[self.data_blocks:] = parity
        batching.STATS.add(False, data_bytes)
        KERNEL.record(RS_ENCODE, False, data_bytes, t.s, blocks=1,
                      backend=host_backend)
        return shards

    def encode_blocks_batch(self, blocks: np.ndarray) -> np.ndarray:
        """Batched encode of (B, k, S) pre-split blocks -> (B, k+m, S).
        The heal/multipart fast path: one device dispatch for many blocks
        (and still coalescable with concurrent requests)."""
        if self._use_tpu(blocks.nbytes):
            out = rs_tpu.encode_batch(blocks, self.data_blocks,
                                      self.parity_blocks,
                                      affinity=self.affinity)
            batching.STATS.add(True, blocks.nbytes)
            return out
        if self._coalesce_ok():
            return batching.get_coalescer().encode(
                blocks, self.data_blocks, self.parity_blocks,
                affinity=self.affinity)
        return batching.host_encode(
            blocks, self.data_blocks, self.parity_blocks,
            lane=AUTOTUNE.host_lane(RS_ENCODE, blocks.nbytes))

    def encode_blocks_batch_shardmajor(self, blocks: np.ndarray,
                                       ) -> np.ndarray:
        """Batched encode returning SHARD-MAJOR (k+m, B, S) contiguous —
        the layout the bitrot framer wants. The pure-host path encodes
        straight into that layout (two full-batch copies cheaper); the
        device/coalescer path reuses encode_blocks_batch and pays one
        transpose copy."""
        if self._use_tpu(blocks.nbytes) or self._coalesce_ok():
            encoded = self.encode_blocks_batch(blocks)
            return np.ascontiguousarray(encoded.transpose(1, 0, 2))
        return batching.host_encode_shardmajor(
            blocks, self.data_blocks, self.parity_blocks,
            lane=AUTOTUNE.host_lane(RS_ENCODE, blocks.nbytes))

    def decode_data_blocks(self, shards: list[np.ndarray | None],
                           ) -> list[np.ndarray]:
        """Reconstruct missing DATA shards in place of Nones
        (ref DecodeDataBlocks, cmd/erasure-coding.go:89)."""
        return self.decode_data_blocks_batch([shards])[0]

    def decode_data_blocks_batch(self, blocks: list,
                                 ) -> list[list[np.ndarray]]:
        """Mask-grouped batched data reconstruct: blocks sharing an
        erasure signature collapse into one device dispatch
        (ops/batching.py; the TPU-native replacement for the reference's
        per-call ReconstructData, cmd/erasure-decode.go:214)."""
        return batching.reconstruct_blocks(
            blocks, self.data_blocks, self.parity_blocks,
            want_all=False, use_device=self._use_tpu_decode,
            device_fallback=self.backend != "tpu",
            affinity=self.affinity)

    def rebuild_shards(self, blocks: list, wanted: tuple[int, ...],
                       ) -> np.ndarray:
        """Heal's reconstruct: shards `wanted` (data or parity) of every
        block, one contiguous row each, data and parity rebuilt by a
        single combined matrix (ref DecodeDataAndParityBlocks,
        cmd/erasure-coding.go:106, which also solves shards it is not
        asked for)."""
        return batching.reconstruct_rows(
            blocks, self.data_blocks, self.parity_blocks, tuple(wanted),
            use_device=self._use_tpu_decode,
            device_fallback=self.backend != "tpu",
            affinity=self.affinity)


def codec_for_algorithm(algorithm: str | None, data_blocks: int,
                        parity_blocks: int,
                        block_size: int = BLOCK_SIZE,
                        backend: str = "auto",
                        affinity: int | None = None):
    """The codec for an xl.meta erasure algorithm stamp: plain RS
    (`rs-vandermonde`, the default and the value every pre-REGEN object
    carries) or the regenerating-code class (`pm-mbr-rbt`).  Lazy
    imports keep codec.py free of the regen subsystem for the common
    path and avoid the metadata<->ops cycle."""
    from ..storage.metadata import REGEN_ALGORITHM
    if algorithm == REGEN_ALGORITHM:
        from .regen import RegenErasure
        return RegenErasure(data_blocks, parity_blocks, block_size,
                            backend=backend, affinity=affinity)
    return Erasure(data_blocks, parity_blocks, block_size,
                   backend=backend, affinity=affinity)
