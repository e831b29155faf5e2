"""The least time the chip could take for the codec work a run's device
lane carried: algorithm-level byte and operation counts (whatever
kernel implements them) over the chip's published peaks.

- HighwayHash-256 reads each hashed byte once; its arithmetic is 64-bit
  integer multiply/add on the vector unit, for which the chip publishes
  no peak, so only the HBM bound is taken.
- RS encode of n input bytes at k+r reads n and writes n*r/k: it moves
  n*(k+r)/k bytes. As a GF(2) bit-matrix product it needs 2*64*r
  operations per input byte (an 8x8 bit matrix per coefficient: 64 ANDs
  and 64 XORs, for each of r parity rows), held against the int8 peak.
- RS reconstruct of n rebuilt-stripe input bytes with `lost` shards
  rebuilt reads n (k shards) and writes n*lost/k; 2*64*lost operations
  per input byte.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {_PEAKS}")
    return table[device_kind]


def hh256_work(n_bytes: float) -> tuple[float, float]:
    """(bytes moved, operations held against a published peak)."""
    return float(n_bytes), 0.0


def rs_encode_work(n_bytes: float, k: int, r: int) -> tuple[float, float]:
    return n_bytes * (k + r) / k, n_bytes * 2 * 64 * r


def rs_reconstruct_work(n_bytes: float, k: int, lost: int
                        ) -> tuple[float, float]:
    return n_bytes * (k + lost) / k, n_bytes * 2 * 64 * lost


def least_seconds(work: list[tuple[float, float]], device_kind: str
                  ) -> tuple[float, str]:
    """Least time for the listed (bytes, ops) items run one after the
    other, and which bound binds the total: 'hbm' or 'int8'."""
    p = peaks(device_kind)
    t_bytes = sum(b for b, _ in work) / p["hbm_bytes_per_s"]
    t_ops = sum(o for _, o in work) / p["int8_ops_per_s"]
    total = sum(max(b / p["hbm_bytes_per_s"], o / p["int8_ops_per_s"])
                for b, o in work)
    return total, "hbm" if t_bytes >= t_ops else "int8"
