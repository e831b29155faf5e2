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


SHARDED, PINNED, REPLICATED = "sharded", "pinned", "replicated"


def shard_nbytes(sharding, x) -> int:
    """Bytes of `x` that ONE device holds under `sharding`: the whole
    array on a single device or along an axis left replicated, a
    share of it along an axis that is sharded."""
    return math.prod(sharding.shard_shape(x.shape)) * x.itemsize


class DeviceAffinity:
    """Per-erasure-set home-device assignment + the census of what
    every device dispatch placed where (``MESH_AFFINITY``).

    Each ``ErasureObjects`` set registers at construction and gets the
    next device round-robin.  Every dispatch onto a serving mesh
    (``ops/batching.device_put_batch`` for the RS kernels,
    ``ops/hh256_tpu.hash_chunks``) records the device indices it
    occupied and the bytes EACH of them holds: a batch sharded four
    ways adds a quarter to each of four devices, an axis left
    replicated adds the whole to each (chips repeating each other's
    work), a pinned batch adds the whole to one.  The batch's own
    bytes are counted once, by kernel and placement.  One store, read
    by the admin ``/codec-plan`` affinity map and by the
    ``minio_tpu_v2_mesh_*`` series (obs/metrics2.py collects them at
    scrape); on a single device nothing records, so neither exists."""

    def __init__(self):
        self._mu = threading.Lock()
        self._assign: dict[str, int] = {}
        self._next = 0
        # (kernel, device index) -> [dispatches, bytes that device held]
        self._devices: dict[tuple[str, int], list[int]] = {}
        # (kernel, placement) -> [dispatches, bytes of the batches, once]
        self._placements: dict[tuple[str, str], list[int]] = {}

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

    def record_dispatch(self, kernel: str,
                        device_indices: tuple[int, ...], nbytes: int,
                        device_nbytes: int | None = None) -> None:
        """One dispatch of `nbytes` that occupied `device_indices`,
        each holding `device_nbytes` (default: the whole batch)."""
        if device_nbytes is None:
            device_nbytes = nbytes
        if len(device_indices) == 1:
            placement = PINNED
        elif device_nbytes * len(device_indices) == nbytes:
            placement = SHARDED
        else:
            placement = REPLICATED
        with self._mu:
            for i in device_indices:
                d = self._devices.setdefault((kernel, i), [0, 0])
                d[0] += 1
                d[1] += device_nbytes
            p = self._placements.setdefault((kernel, placement), [0, 0])
            p[0] += 1
            p[1] += nbytes

    def counters(self) -> dict[int, dict]:
        """Per device index, over every kernel."""
        out: dict[int, dict] = {}
        with self._mu:
            for (_, i), (n, b) in sorted(self._devices.items(),
                                         key=lambda kv: kv[0][1]):
                d = out.setdefault(i, {"dispatches": 0, "bytes": 0})
                d["dispatches"] += n
                d["bytes"] += b
        return out

    def device_bytes(self) -> list[tuple[dict, int]]:
        """``mesh_device_bytes_total``: (labels, bytes) per kernel and
        device."""
        with self._mu:
            return [({"kernel": k, "device": str(i)}, v[1])
                    for (k, i), v in sorted(self._devices.items())]

    def dispatch_bytes(self) -> list[tuple[dict, int]]:
        """``mesh_dispatch_bytes_total``: (labels, bytes) per kernel
        and placement."""
        with self._mu:
            return [({"kernel": k, "placement": p}, v[1])
                    for (k, p), v in sorted(self._placements.items())]

    def snapshot(self) -> dict:
        """The affinity map the admin /codec-plan serves."""
        with self._mu:
            assignments = dict(sorted(self._assign.items()))
            kernels: dict[str, dict] = {}
            for (k, i), (n, b) in sorted(self._devices.items()):
                kernels.setdefault(k, {"devices": {}, "placements": {}})[
                    "devices"][str(i)] = {"dispatches": n, "bytes": b}
            for (k, p), (n, b) in sorted(self._placements.items()):
                kernels.setdefault(k, {"devices": {}, "placements": {}})[
                    "placements"][p] = {"dispatches": n, "bytes": b}
        return {
            "nDevices": self.n_devices(),
            "assignments": assignments,
            "dispatches": {str(i): v
                           for i, v in self.counters().items()},
            "kernels": kernels,
        }

    def reset(self) -> None:
        with self._mu:
            self._assign.clear()
            self._next = 0
            self._devices.clear()
            self._placements.clear()


MESH_AFFINITY = DeviceAffinity()


def _export() -> None:
    from ..obs.metrics2 import METRICS2
    METRICS2.collect("minio_tpu_v2_mesh_device_bytes_total",
                     MESH_AFFINITY.device_bytes)
    METRICS2.collect("minio_tpu_v2_mesh_dispatch_bytes_total",
                     MESH_AFFINITY.dispatch_bytes)


_export()
