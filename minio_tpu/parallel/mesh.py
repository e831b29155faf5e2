"""Device-mesh parallelism for the erasure data plane.

The object store's parallel axes (SURVEY §2.6 parallelism inventory) map to
a 2-D device mesh:

- 'blocks' (≈DP): independent 10MiB-stripe blocks from concurrent PUTs/heals
  batch along the leading axis — embarrassingly parallel.
- 'lanes'  (≈TP): shard bytes (the S axis). Every GF(2^8) op is elementwise
  along S, so S shards cleanly with zero communication in encode/decode;
  collectives only appear in integrity reductions (verify sums) and in
  cross-host shard movement.

Multi-chip hardware is not present in dev; shapes/shardings are validated on
a virtual CPU mesh (tests) and via __graft_entry__.dryrun_multichip.
"""

from __future__ import annotations

import math
import threading

import jax
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)


def make_mesh(n_devices: int | None = None,
              axis_names: tuple[str, str] = ("blocks", "lanes"),
              ) -> Mesh:
    """Build a near-square 2-D mesh over the first n devices."""
    devs = jax.devices()
    n = n_devices or len(devs)
    devs = devs[:n]
    # Factor n into (a, b) with a as large as possible <= sqrt-ish.
    a = 1
    for cand in range(int(math.isqrt(n)), 0, -1):
        if n % cand == 0:
            a = cand
            break
    import numpy as np
    arr = np.array(devs).reshape(a, n // a)
    return Mesh(arr, axis_names)


def block_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for (B, k, S) shard-block batches: B over 'blocks', S over
    'lanes', shard index replicated (each chip sees whole GF columns)."""
    return NamedSharding(mesh, P("blocks", None, "lanes"))


def batch_sharding(mesh: Mesh, B: int, S: int) -> NamedSharding:
    """block_sharding with divisibility fallback: an axis that doesn't
    divide its mesh dimension stays replicated (serving batches have
    arbitrary B and tail-block S). Single source of truth for the
    serving path AND the dryrun demo."""
    return NamedSharding(mesh, P(
        "blocks" if B % mesh.shape["blocks"] == 0 else None, None,
        "lanes" if S % mesh.shape["lanes"] == 0 else None))


def rows_sharding(mesh: Mesh, B: int, ndim: int) -> NamedSharding:
    """Row-parallel sharding for per-row-independent kernels (the
    HighwayHash batch): B spreads over EVERY mesh axis when divisible,
    remaining dims replicated."""
    if B % mesh.size == 0:
        return NamedSharding(
            mesh, P(tuple(mesh.axis_names), *([None] * (ndim - 1))))
    return NamedSharding(mesh, P())


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_placement(mesh: Mesh, B: int, S: int,
                    affinity: int | None = None,
                    ) -> tuple[object, tuple[int, ...]]:
    """(sharding, device indices) for a (B, k, S) serving batch.

    Divisible axes shard across the mesh exactly like
    ``batch_sharding``.  A batch NEITHER axis of which divides used to
    replicate to every chip (each one redundantly computing the whole
    thing); with a per-set ``affinity`` it now lands WHOLE on the
    owning erasure set's home device, so concurrent sets' small
    dispatches spread across chips instead of all queueing on device
    0.  The device-index tuple is what the dispatch actually occupies
    — fed to ``MESH_AFFINITY.record_dispatch`` so the spread is
    provable, not aspirational."""
    sh = batch_sharding(mesh, B, S)  # the one divisibility rule
    if affinity is not None and sh.spec == P(None, None, None):
        devs = jax.devices()
        idx = affinity % len(devs)
        return SingleDeviceSharding(devs[idx]), (idx,)
    return sh, tuple(range(mesh.size))


class DeviceAffinity:
    """Per-erasure-set home-device assignment + per-device dispatch
    census (``MESH_AFFINITY``).

    Each ``ErasureObjects`` set registers at construction and gets the
    next device round-robin; every placed dispatch records which
    device indices it occupied.  The census is the proof behind the
    admin ``/codec-plan`` affinity map and the 8-virtual-device spread
    tests — per-set affinity is only real if the counters say so."""

    def __init__(self):
        self._mu = threading.Lock()
        self._assign: dict[str, int] = {}
        self._next = 0
        self._dispatches: dict[int, int] = {}
        self._bytes: dict[int, int] = {}

    @staticmethod
    def n_devices() -> int:
        return len(jax.devices())

    def assign(self, owner: str) -> int | None:
        """Home device index for `owner` (idempotent); None on a
        single-device box — affinity only means something on a mesh."""
        n = self.n_devices()
        if n <= 1:
            return None
        with self._mu:
            idx = self._assign.get(owner)
            if idx is None:
                idx = self._next % n
                self._next += 1
                self._assign[owner] = idx
            return idx

    def release(self, owner: str) -> None:
        with self._mu:
            self._assign.pop(owner, None)

    def record_dispatch(self, device_indices: tuple[int, ...],
                        nbytes: int) -> None:
        with self._mu:
            for i in device_indices:
                self._dispatches[i] = self._dispatches.get(i, 0) + 1
                self._bytes[i] = self._bytes.get(i, 0) + nbytes

    def counters(self) -> dict[int, dict]:
        with self._mu:
            return {i: {"dispatches": self._dispatches.get(i, 0),
                        "bytes": self._bytes.get(i, 0)}
                    for i in sorted(set(self._dispatches)
                                    | set(self._bytes))}

    def snapshot(self) -> dict:
        """The affinity map the admin /codec-plan serves."""
        with self._mu:
            return {
                "nDevices": self.n_devices(),
                "assignments": dict(sorted(self._assign.items())),
                "dispatches": {
                    str(i): {"dispatches": self._dispatches.get(i, 0),
                             "bytes": self._bytes.get(i, 0)}
                    for i in sorted(set(self._dispatches)
                                    | set(self._bytes))},
            }

    def reset(self) -> None:
        with self._mu:
            self._assign.clear()
            self._next = 0
            self._dispatches.clear()
            self._bytes.clear()


MESH_AFFINITY = DeviceAffinity()
