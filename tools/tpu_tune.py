"""Measure the device codec paths on real TPU hardware.

Compares the Pallas packed-GF kernel vs the XLA bit-plane path on the
north-star config (8+4, 1MiB blocks), sweeps lane-tile sizes, and
measures device HighwayHash throughput. Prints one JSON line.

Usage: python -m tools.tpu_tune   (the one process that owns the chip;
exits nonzero with an error JSON when only CPU is visible). Timings are
steady-state marginal cost: pipelined launches ending in a readback.
"""

from __future__ import annotations

import json
import sys
import time


def _pipelined(launch, sync, n1=4, n2=20):
    def run(n):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = launch()
        sync(out)
        return time.perf_counter() - t0
    run(2)
    t1 = min(run(n1) for _ in range(2))
    t2 = min(run(n2) for _ in range(2))
    return max(t2 - t1, 1e-9) / (n2 - n1)


def run() -> dict:
    """Measure and return the tuning dict (raises without accelerator)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from minio_tpu.utils import compile_cache
    compile_cache.configure()
    if not any(d.platform != "cpu" for d in jax.devices()):
        raise RuntimeError("no accelerator visible")

    from minio_tpu.ops import rs_pallas, rs_tpu

    k, m = 8, 4
    S = (1024 * 1024) // k
    batch = 64
    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.integers(0, 256, (batch, k, S)).astype(np.uint8))
    bm = jnp.asarray(rs_tpu.parity_bitplane(k, m))
    nbytes = batch * k * S

    out: dict = {"config": f"{k}+{m} S={S} B={batch}"}

    # XLA bit-plane path
    def launch_xla():
        return rs_tpu.rs_gf_apply_xla(bm, data)

    def sync(o):
        np.asarray(o[0, 0, 0])

    t = _pipelined(launch_xla, sync)
    out["xla_GiBs"] = round(nbytes / t / (1 << 30), 2)

    # Pallas kernel, tile sweep
    tiles = {}
    for tile in (1024, 2048, 4096, 8192):
        try:
            rs_pallas._MAX_TILE = tile
            rs_pallas.rs_gf_apply.clear_cache()

            def launch_p():
                return rs_pallas.gf_apply(bm, data)

            t = _pipelined(launch_p, sync)
            tiles[str(tile)] = round(nbytes / t / (1 << 30), 2)
        except Exception as exc:  # noqa: BLE001
            tiles[str(tile)] = f"error: {type(exc).__name__}: {exc}"
    out["pallas_GiBs_by_tile"] = tiles

    # correctness spot-check at the final tile setting
    got = np.asarray(rs_pallas.gf_apply(bm, data[:2]))
    want = np.asarray(rs_tpu.rs_gf_apply_xla(bm, data[:2]))
    out["pallas_matches_xla"] = bool(np.array_equal(got, want))

    # device HighwayHash throughput (batch of shard sub-blocks)
    from minio_tpu.ops import hh256_tpu
    chunks = rng.integers(0, 256, (256, 128 * 1024)).astype(np.uint8)

    def launch_hh():
        return hh256_tpu.hash_chunks(chunks)

    t0 = time.perf_counter()
    launch_hh()
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    launch_hh()
    t = time.perf_counter() - t0
    out["hh_GiBs"] = round(chunks.nbytes / t / (1 << 30), 2)
    out["hh_warm_s"] = round(warm, 1)
    return out


def main() -> None:
    try:
        out = run()
    except Exception as exc:  # noqa: BLE001
        print(json.dumps({"error": f"{type(exc).__name__}: {exc}"}))
        sys.exit(1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
