"""TPU data-plane kernel accounting: the whole point of this
reproduction is the TPU codec (PAPER.md), yet until metrics-v2 it
exported zero metrics. Every kernel entry point now records through
``KERNEL`` into the v2 registry:

- ``rs_encode``  — batched Reed-Solomon encode (ops/rs_tpu.encode_batch
  on device, ops/batching.host_encode* on the host)
- ``rs_decode``  — mask-grouped reconstruction (ops/batching)
- ``hh256``      — batched HighwayHash bitrot hashing (ops/hh256_tpu /
  the host chunk path in erasure/bitrot.py)

Per kernel x device the registry carries invocations, bytes,
batch-occupancy blocks and coalesced request counts (wall time is
``kernel_dispatch_ms``, per kernel x backend); the existing
ops/batching.STATS honesty counters stay untouched (they feed the v1
page), metrics-v2 is the superset the next perf PR reads.

A DEVICE dispatch is also split where it happens (``dispatch`` below):
prep | enqueue | wait, as ``kernel_dispatch_phase_ms`` and as
``dispatch.<kernel>.<phase>`` annotations on the profiler's clock.
"""

from __future__ import annotations

import threading
import time

from .metrics2 import METRICS2
from .span import annotation

RS_ENCODE = "rs_encode"
RS_DECODE = "rs_decode"
HH256 = "hh256"
# Columnar S3 Select predicate scan (ops/select_kernels.py): the
# analytics workload's kernel identity in the dispatch profiles, the
# autotuner model and the backend health machine.
SELECT_SCAN = "select_scan"


class KernelStats:
    """Recording facade over the v2 registry's kernel counters.

    ``backend`` refines the binary device flag into the real dispatch
    lane (obs/kernprof.py BACKENDS: device / native / xla-cpu / host);
    every record also feeds the kernprof per-dispatch profile layer —
    latency histogram per (kernel, backend, batch bucket), per-backend
    byte counters, and the backend health state machine's success
    outcomes.  Callers that don't know their lane omit it and the
    coarse device flag maps to device/host."""

    @staticmethod
    def record(kernel: str, device: bool, nbytes: int,
               wall_s: float = 0.0, blocks: int = 0,
               requests: int = 1, backend: str | None = None) -> None:
        lbl = {"kernel": kernel, "device": "tpu" if device else "host"}
        METRICS2.inc("minio_tpu_v2_kernel_invocations_total", lbl)
        METRICS2.inc("minio_tpu_v2_kernel_bytes_total", lbl, nbytes)
        if blocks:
            METRICS2.inc("minio_tpu_v2_kernel_batch_blocks_total", lbl,
                         blocks)
        if requests > 1:
            METRICS2.inc("minio_tpu_v2_kernel_coalesced_requests_total",
                         lbl, requests)
        from .kernprof import DEVICE, HOST, KERNPROF
        if backend is None:
            backend = DEVICE if device else HOST
        KERNPROF.record_dispatch(kernel, backend, nbytes, wall_s,
                                 blocks)

    @staticmethod
    def record_operand(kernel: str, copied: int, padded: int) -> None:
        """How a dispatch's operand was built: `copied` bytes the host
        wrote into it from the caller's rows, `padded` bytes of zero
        rows sent with them. For hh256 the two sum to what
        ``kernel_bytes_total{device="tpu"}`` gains for the dispatch;
        an rs_decode that reads its survivors in place copies 0."""
        lbl = {"kernel": kernel}
        METRICS2.inc("minio_tpu_v2_kernel_host_copy_bytes_total", lbl,
                     copied)
        METRICS2.inc("minio_tpu_v2_kernel_pad_bytes_total", lbl, padded)

    @staticmethod
    def record_coalesced(kernel: str, requests: int) -> None:
        METRICS2.inc("minio_tpu_v2_kernel_coalesced_requests_total",
                     {"kernel": kernel, "device": "tpu"}, requests)

    @staticmethod
    def snapshot() -> dict:
        """{kernel/device: {invocations, bytes, blocks}} — the
        admin-info / test view of the registry's kernel series."""
        out: dict[str, dict] = {}
        snap = METRICS2.snapshot()
        for metric, field in (
                ("minio_tpu_v2_kernel_invocations_total", "invocations"),
                ("minio_tpu_v2_kernel_bytes_total", "bytes"),
                ("minio_tpu_v2_kernel_batch_blocks_total", "blocks")):
            for s in snap.get(metric, {}).get("series", []):
                lb = s["labels"]
                key = f"{lb.get('kernel')}/{lb.get('device')}"
                out.setdefault(key, {})[field] = s["value"]
        return out


KERNEL = KernelStats()


class timed:
    """``with timed() as t: ...; t.s`` — wall-clock for kernel calls."""

    __slots__ = ("t0", "s")

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.s = time.perf_counter() - self.t0
        return False


_inflight_mu = threading.Lock()
_inflight = 0


class dispatch:
    """The host's three phases of ONE device dispatch, split by clock
    reads alone (no synchronisation is added):

        with dispatch(HH256, rows=B, nbytes=n) as ph:
            ...pack...                    # prep: host-side packing
            ph.phase("enqueue")
            dev = jitted(...)             # device_puts + the call's return
            ph.phase("wait")
            out = np.asarray(dev)         # queue + execute + D2H

    Each phase lands in ``kernel_dispatch_phase_ms{kernel, backend,
    phase}`` and is entered as a ``dispatch.<kernel>.<phase>``
    TraceAnnotation on the calling thread, so a profiler session's host
    plane carries the program's own names on the device trace's clock.
    ``kernel_dispatch_depth`` is the number of this process's device
    dispatches already in flight when this one entered. A dispatch that
    raises observes nothing."""

    __slots__ = ("kernel", "tags", "depth", "_name", "_t", "_ms", "_ann")

    def __init__(self, kernel: str, rows: int, nbytes: int):
        self.kernel = kernel
        self.tags = {"kernel": kernel, "rows": rows, "bytes": nbytes}
        self._t = time.perf_counter()
        self._ms: dict[str, float] = {}
        self._name = ""
        self._ann = None

    def __enter__(self) -> "dispatch":
        global _inflight
        with _inflight_mu:
            self.depth = _inflight
            _inflight += 1
        self._open("prep")
        return self

    def _open(self, name: str) -> None:
        self._name = name
        self._ann = annotation(f"dispatch.{self.kernel}.{name}",
                               **self.tags)
        self._ann.__enter__()

    def _close(self) -> None:
        now = time.perf_counter()
        self._ann.__exit__(None, None, None)
        self._ms[self._name] = (now - self._t) * 1e3
        self._t = now

    def phase(self, name: str) -> None:
        self._close()
        self._open(name)

    def __exit__(self, exc_type, *exc) -> bool:
        global _inflight
        self._close()
        with _inflight_mu:
            _inflight -= 1
        if exc_type is None:
            from ..ops.batching import attempt_backend
            lbl = {"kernel": self.kernel, "backend": attempt_backend()}
            METRICS2.observe("minio_tpu_v2_kernel_dispatch_depth", lbl,
                             self.depth)
            METRICS2.observe_each("minio_tpu_v2_kernel_dispatch_phase_ms",
                                  lbl, "phase", self._ms)
        return False
