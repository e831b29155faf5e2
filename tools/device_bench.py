"""Run every device-dependent bench config on a live accelerator.

One process owns the chip: either this script run on its own
(`python tools/device_bench.py`) or bench.py, which imports `run()` and
calls it in-process. Neither starts a child that needs the chip.

Prints ONE JSON line:
  {"ok": true, "north_star": {...}, "configs": [...], "tune": {...}}
or {"ok": false, ...} with "error" / per-phase "errors" — always valid
JSON on stdout, progress on stderr. Exit code 0 only with "ok": true:
a phase that failed, or no accelerator, is a nonzero exit.

Measured here (all device-asserted via ops.batching STATS deltas):
  - north-star kernel roundtrip (8+4/1MiB encode+decode marginal GiB/s)
  - ec8+4 encode + HighwayHash bitrot verify (device HH256 kernel)
  - ec8+4 GetObject with 2 shards lost, through the engine
  - ec16+4 full-disk heal, through the engine
  - Pallas-vs-XLA tile sweep + device HH throughput (tools/tpu_tune.py)

Reference harness being beaten: cmd/erasure-encode_test.go:209-247,
cmd/erasure-decode_test.go:344, cmd/benchmark-utils_test.go.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _progress(msg: str) -> None:
    print(f"[device-bench +{time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.monotonic()


def run() -> dict:
    import numpy as np
    import jax
    import jax.numpy as jnp

    from minio_tpu.utils import compile_cache
    compile_cache.configure()

    devs = jax.devices()
    if not any(d.platform != "cpu" for d in devs):
        return {"ok": False, "error": "no accelerator visible"}
    platform = next(d.platform for d in devs if d.platform != "cpu")

    import bench
    from minio_tpu.ops import rs_tpu

    out: dict = {"ok": True, "platform": platform,
                 "n_devices": len(devs)}
    errors: dict[str, str] = {}

    _progress("north star kernel (device)")
    try:
        tpu_gibs, cpu_gibs = bench.bench_kernel_north_star(
            np, jnp, rs_tpu, device=True)
        out["north_star"] = {
            "value": round(tpu_gibs, 3), "unit": "GiB/s",
            "vs_host_native": round(tpu_gibs / max(cpu_gibs, 1e-9), 2),
            "host_native_GiBs": round(cpu_gibs, 3),
            "kernel": rs_tpu.kernel_report()["kernel"],
        }
    except Exception as exc:  # noqa: BLE001
        errors["north_star"] = f"{type(exc).__name__}: {exc}"

    configs: list[dict] = []
    workdir = tempfile.mkdtemp(prefix="minio-tpu-devbench-")
    try:
        for name, fn in (
                ("encode_verify",
                 lambda: bench.bench_encode_verify(np, True)),
                ("get_2lost",
                 lambda: bench.bench_get_with_loss(np, workdir, True)),
                ("heal", lambda: bench.bench_heal(np, workdir, True))):
            _progress(f"config {name} (device)")
            res, err = bench._retrying(fn, name, attempts=2,
                                       base_sleep=1.0)
            if res is not None:
                res["device_asserted"] = True
                configs.append(res)
            else:
                errors[name] = err or "unknown"
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    out["configs"] = configs

    _progress("tile sweep + device HH (tpu_tune)")
    try:
        from tools import tpu_tune
        out["tune"] = tpu_tune.run()
    except Exception as exc:  # noqa: BLE001
        errors["tune"] = f"{type(exc).__name__}: {exc}"

    from minio_tpu.ops import batching
    out["stats"] = batching.STATS.snapshot()
    out["hh_stats"] = batching.HH_STATS.snapshot()
    if errors:
        # A device phase that failed fails the run (module docstring).
        out["errors"] = errors
        out["ok"] = False
    return out


def main() -> None:
    try:
        out = run()
    except BaseException as exc:  # noqa: BLE001 - one JSON line, always
        out = {"ok": False, "error": f"{type(exc).__name__}: {exc}"}
    print(json.dumps(out))
    sys.exit(0 if out.get("ok") else 1)


if __name__ == "__main__":
    main()
