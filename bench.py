"""Benchmark: all five BASELINE.md configs through the real engine.

Prints ONE JSON line. Top-level keys keep the round-1..3 north-star
contract — {"metric", "value", "unit", "vs_baseline"} for RS
encode+decode GiB/s/chip (8+4, 1MiB blocks) — plus:

  "configs":  the five BASELINE.md target configs, each measured through
              the real code path (S3 server / erasure engine / kernels):
     1. ec4+2_put_p50_ms          single 1MiB PutObject p50 via the HTTP
                                  S3 server (SigV4-signed requests)
     2. ec8+4_encode_verify_GiBs  encode + HighwayHash bitrot verify
                                  roundtrip, device codec vs host codec
     3. ec12+4_multipart_GiBs     multipart upload through the engine
                                  (batched shard encode; scaled from
                                  BASELINE's 10GiB to bound wall time,
                                  noted in "scale")
     4. ec8+4_get_2lost_GiBs      GetObject with 2 shards lost through
                                  the engine (mask-grouped TPU
                                  reconstruct); asserts the device path
                                  actually ran via batching.STATS
     5. ec16+4_heal_GiBs          full-disk heal through the engine
                                  (batched reconstruct); STATS-asserted
     6. qos_brownout              loadgen at ~4x the write cap: shed
                                  rate + admitted p50/p99, and fg PUT
                                  p50 with/without a concurrent heal
                                  sweep (priority-lane interference)
     7. hot_get                   Zipfian GETs, hot-object cache on vs
                                  off (paired off/on/off): GET QPS
                                  speedup, hit ratio, coalesced fills,
                                  p99, cache-off consult overhead
     8. noisy_neighbor            one Zipf-hot tenant amid uniform
                                  background through the multi-tenant
                                  loadgen: admin /top ranks the hot
                                  bucket, the noisy_neighbor watchdog
                                  rule fires naming it and resolves,
                                  paired usage-on/off PUT p50 <= 2%
  "stats":    batching.STATS snapshot (device-vs-host honesty counters)
  "errors":   per-config error strings (configs that failed still leave
              the others reported; any error makes the exit code 1)

Baselines are the host codec (C++ nibble-shuffle RS in native/rs.cc and
C++ HighwayHash; numpy fallback without a compiler) on this machine — a
stand-in for the Go reference's AVX2 reedsolomon (harness parity:
cmd/erasure-encode_test.go:209, erasure-decode_test.go:344,
cmd/benchmark-utils_test.go).

One process owns the chip: bench.py itself. It needs an accelerator
(no accelerator -> nonzero exit, nothing published), does every device
measurement in-process (tools/device_bench.run(), then the engine
configs under whatever lane the codec plan picks — each config's
"backend_mix" says which), and pins the server CHILDREN it starts
(front_door, crash_recovery, fabric nodes) to JAX_PLATFORMS=cpu, so no
child ever asks for the chip its parent holds. A device phase that
fails is an error: it lands in "errors" and the exit code is nonzero.

Timing note: kernel-level numbers use steady-state marginal cost
(pipelined N1/N2 dispatches ending in a readback); engine-level numbers
are wall-clock end-to-end, which is what an operator sees.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time


def _progress(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.monotonic()


def _retrying(fn, what: str, attempts: int = 4, base_sleep: float = 2.0):
    """Run fn with exponential backoff. Returns (value, None) or
    (None, error-string) — bench configs degrade, they never abort."""
    last = None
    for i in range(attempts):
        try:
            return fn(), None
        except Exception as exc:  # noqa: BLE001 - report, don't die
            last = f"{what}: {type(exc).__name__}: {exc}"
            if i < attempts - 1:
                time.sleep(base_sleep * (2 ** i))
    return None, last


def _backend_mix(before: dict, after: dict) -> dict:
    """Fractions of kernel-dispatched BYTES per kernprof backend over
    a [before, after) mix_snapshot window (dispatch-count fractions
    when no bytes moved). This is the stamp that keeps a host-mode
    bench from masquerading as a device number."""
    deltas = {}
    for b, cur in after.items():
        prev = before.get(b, {})
        deltas[b] = {k: cur.get(k, 0) - prev.get(k, 0)
                     for k in ("bytes", "dispatches")}
    basis = "bytes" if any(d["bytes"] for d in deltas.values()) \
        else "dispatches"
    total = sum(d[basis] for d in deltas.values())
    if total <= 0:
        return {}
    return {b: round(d[basis] / total, 4)
            for b, d in sorted(deltas.items()) if d[basis]}


def _pipelined_seconds_per_iter(launch, sync, n1: int = 4, n2: int = 20,
                                ) -> float:
    def run(n: int) -> float:
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = launch()
        sync(out)
        return time.perf_counter() - t0

    run(2)  # warm
    t1 = min(run(n1) for _ in range(2))
    t2 = min(run(n2) for _ in range(2))
    return max(t2 - t1, 1e-9) / (n2 - n1)


# --- north star: kernel encode+decode marginal throughput --------------------


def bench_kernel_north_star(np, jnp, rs_tpu, device: bool = True,
                            ) -> tuple[float, float]:
    """(tpu_gibs, cpu_gibs) for the 8+4/1MiB encode+decode roundtrip —
    same measurement as rounds 1-3 for cross-round comparability."""
    k, m = 8, 4
    S = (1024 * 1024) // k
    batch = 64 if device else 8  # XLA-CPU fallback: bound wall time

    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (batch, k, S)).astype(np.uint8)

    big_enc = jnp.asarray(rs_tpu.parity_bitplane(k, m))
    missing = (0, 5)
    available = tuple(i for i in range(k + m) if i not in missing)
    big_dec_np, used = rs_tpu.decode_bitplane(k, m, available, missing)
    big_dec = jnp.asarray(big_dec_np)

    data_dev = jnp.asarray(data)
    shards = rs_tpu.encode_blocks(big_enc, data_dev)
    survivors = jnp.take(shards, jnp.asarray(used, dtype=jnp.int32), axis=-2)

    def launch():
        s = rs_tpu.encode_blocks(big_enc, data_dev)
        r = rs_tpu.gf_apply(big_dec, survivors)
        return s, r

    def sync(out):
        s, r = out
        np.asarray(s[0, k, 0])
        np.asarray(r[0, 0, 0])

    if device:
        t_iter = _pipelined_seconds_per_iter(launch, sync)
    else:
        t_iter = _pipelined_seconds_per_iter(launch, sync, n1=1, n2=3)
    tpu_gibs = (batch * k * S) / t_iter / (1 << 30)

    # CPU baseline: the PRODUCTION host path — C++ nibble-shuffle kernel
    # (native/rs.cc) when built, numpy table-gather otherwise — the
    # honest stand-in for the reference's AVX2 reedsolomon.
    from minio_tpu.ops import batching as _batching
    from minio_tpu.ops.rs_matrix import decode_matrix, parity_matrix
    pm = parity_matrix(k, m)
    dec_full, _ = decode_matrix(k, m, list(available))
    dec_miss = dec_full[list(missing), :]
    cpu_batch = max(1, batch // 16)
    cpu_data = data[:cpu_batch]
    cpu_survivors = np.asarray(survivors[:cpu_batch])

    def cpu_roundtrip():
        for b in range(cpu_batch):
            _batching.host_apply(pm, cpu_data[b])
            _batching.host_apply(dec_miss, cpu_survivors[b])

    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        cpu_roundtrip()
        times.append(time.perf_counter() - t0)
    cpu_gibs = (cpu_batch * k * S) / min(times) / (1 << 30)
    return tpu_gibs, cpu_gibs


def bench_host_native_north_star(np) -> float:
    """The engine's REAL degraded-mode number: the 8+4/1MiB roundtrip
    through the same folded host applies the serving path uses when no
    device is reachable (batching.host_encode / _host_reconstruct over
    the C++ nibble-shuffle kernel). Round-4 verdict weak #2: reporting
    jit-on-CPU here (0.016 GiB/s) was misleading — the engine never
    falls back to XLA-CPU, it falls back to native/rs.cc."""
    from minio_tpu.ops import batching
    from minio_tpu.ops.rs_matrix import decode_matrix

    k, m = 8, 4
    S = (1024 * 1024) // k
    batch = 16
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (batch, k, S)).astype(np.uint8)

    missing = (0, 5)
    available = [i for i in range(k + m) if i not in missing]
    dec_full, used = decode_matrix(k, m, available)
    dec_miss = np.ascontiguousarray(dec_full[list(missing), :])

    encoded = batching.host_encode(data, k, m)
    survivors = np.ascontiguousarray(encoded[:, used, :])

    def roundtrip():
        enc = batching.host_encode(data, k, m)
        rec = batching._host_reconstruct(survivors, dec_miss)
        return enc, rec

    roundtrip()  # warm (native lib build, first-touch)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        roundtrip()
        times.append(time.perf_counter() - t0)
    return (batch * k * S) / min(times) / (1 << 30)


# --- config 1: 4+2 single PutObject p50 through the S3 server ----------------


def bench_put_p50(np, workdir: str) -> dict:
    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.s3.client import S3Client
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl import XLStorage
    from minio_tpu.utils.phasetimer import PUT

    access, secret = "benchadmin", "benchadmin-secret"
    # tmpfs when available: this config tracks the serving path's CPU
    # cost; the VM's disk writeback throttling swings 2-12ms run to
    # run and would drown the signal (labeled in "workdir").
    base = workdir
    if os.path.isdir("/dev/shm"):
        base = tempfile.mkdtemp(prefix="minio-tpu-p50-", dir="/dev/shm")
    root = os.path.join(base, "cfg1")
    disks = [XLStorage(os.path.join(root, f"disk{i}")) for i in range(6)]
    layer = ErasureObjects(disks, 4, 2, block_size=1024 * 1024)
    srv = S3Server(layer, access, secret)
    port = srv.start()
    try:
        client = S3Client("127.0.0.1", port, access, secret)
        client.make_bucket("bench")
        rng = np.random.default_rng(1)
        body = rng.integers(0, 256, 1024 * 1024).astype(np.uint8).tobytes()
        # warm (compile/caches/first-touch disk dirs)
        for i in range(5):
            client.put_object("bench", f"warm-{i}", body)
        PUT.reset()

        # Acceptance: drivemon+slowlog recording overhead on this path
        # must measure <= 2%. This VM's throughput drifts +/-20% on
        # second timescales, so pool-median A/B aliases drift into the
        # comparison; instead each recording-ON PUT is PAIRED with the
        # immediately-following recording-OFF PUT (toggling is two
        # attribute writes) and the overhead is the median of the
        # per-pair deltas — drift moves both halves of a pair
        # together, the systematic recording cost survives.
        from minio_tpu.obs.drivemon import DRIVEMON
        from minio_tpu.obs.slowlog import SLOWLOG
        from minio_tpu.obs.watchdog import WATCHDOG
        lat_on: list = []
        lat_off: list = []
        try:
            for i in range(80):
                # Alternate which half leads: a fixed on-first order
                # would alias any position-within-pair effect (post-
                # pair stalls, allocator periodicity) into the delta.
                order = (True, False) if i % 2 == 0 else (False, True)
                for on in order:
                    # The watchdog toggles with the other recorders:
                    # its only request-path cost is the 5xx class
                    # counter, but the paired measurement should cover
                    # the whole PR-9 layer (sampler-tick evaluation
                    # steals CPU on a 2-core box).
                    DRIVEMON.enabled = SLOWLOG.enabled = on
                    WATCHDOG.enabled = on
                    t0 = time.perf_counter()
                    r = client.put_object(
                        "bench", f"obj-{i}-{int(on)}", body)
                    (lat_on if on else lat_off).append(
                        time.perf_counter() - t0)
                    if r.status != 200:
                        raise RuntimeError(
                            f"PutObject failed: {r.status}")
        finally:
            DRIVEMON.enabled = SLOWLOG.enabled = True
            WATCHDOG.enabled = True
        p50_ms = statistics.median(lat_on) * 1e3
        p50_off_ms = statistics.median(lat_off) * 1e3
        med_delta_ms = statistics.median(
            [(a - b) * 1e3 for a, b in zip(lat_on, lat_off)])
        overhead_pct = med_delta_ms / max(p50_off_ms, 1e-9) * 100.0
        return {"metric": "ec4+2_put_p50", "value": round(p50_ms, 3),
                "unit": "ms", "objects": len(lat_on),
                "object_bytes": len(body),
                "workdir": "tmpfs" if base != workdir else "disk",
                # Drive-health + slowlog recording cost on the hot
                # path (acceptance bar: <= 2%; sub-ms medians make
                # small negatives normal measurement noise).
                "put_p50_no_obs_ms": round(p50_off_ms, 3),
                "obs_overhead_pct": round(overhead_pct, 2),
                # Round-4 verdict weak #3: publish where the ms go.
                "phase_p50_ms": {k: v["p50_ms"] for k, v in
                                 sorted(PUT.snapshot().items())}}
    finally:
        srv.stop()
        shutil.rmtree(root, ignore_errors=True)
        if base != workdir:
            shutil.rmtree(base, ignore_errors=True)


# --- config 2: 8+4 encode + HighwayHash bitrot verify roundtrip --------------


def bench_encode_verify(np, device: bool) -> dict:
    from minio_tpu.erasure import bitrot
    from minio_tpu.erasure.codec import Erasure

    k, m = 8, 4
    S = (1024 * 1024) // k          # 1MiB stripe -> 128KiB shards
    batch = 32                       # 32 MiB of data per dispatch
    rng = np.random.default_rng(2)
    blocks = rng.integers(0, 256, (batch, k, S)).astype(np.uint8)

    def roundtrip(backend: str) -> float:
        """The engine's real write pipeline for one batch: shard-major
        encode + streaming-bitrot framing (what _encode_batch runs),
        not a hand-rolled encode+digest loop."""
        codec = Erasure(k, m, block_size=1024 * 1024, backend=backend)
        t0 = time.perf_counter()
        sm = codec.encode_blocks_batch_shardmajor(blocks)
        frames = bitrot.encode_stream_arrays(list(sm))
        if len(frames) != k + m:
            raise RuntimeError("bitrot frame count mismatch")
        return time.perf_counter() - t0

    from minio_tpu.ops import batching
    backend = "tpu" if device else "cpu"
    roundtrip(backend)  # warm
    before = batching.HH_STATS.snapshot()
    t_dev = min(roundtrip(backend) for _ in range(3))
    hh_tpu = (batching.HH_STATS.snapshot()["tpu_dispatches"]
              - before["tpu_dispatches"])
    t_cpu = min(roundtrip("cpu") for _ in range(2))
    gibs = (batch * k * S) / t_dev / (1 << 30)
    cpu_gibs = (batch * k * S) / t_cpu / (1 << 30)
    return {"metric": "ec8+4_encode_verify", "value": round(gibs, 3),
            "unit": "GiB/s", "vs_baseline": round(gibs / cpu_gibs, 2),
            "device": device, "hh_tpu_dispatches": hh_tpu}


# --- config: codec autotuner — paired tuned-vs-untuned dispatch --------------


def bench_codec_autotune(np) -> dict:
    """Measured-plan dispatch vs the legacy static device-first policy,
    PAIRED per batch-size bucket (alternating order, like put_p50's
    overhead pairs — this VM drifts +/-20% on second timescales, so
    only the within-pair delta is trustworthy).  Stamps the probe
    ladder's full crossover table and the converged plan; the
    acceptance bar is tuned >= untuned within noise on every bucket —
    where the host lane measures fastest both policies converge on
    it, so the deltas there measure planner overhead, not lane wins."""
    from minio_tpu.erasure.codec import Erasure
    from minio_tpu.ops.autotune import AUTOTUNE

    AUTOTUNE.reset()
    ladder = AUTOTUNE.probe_ladder()

    k, m = 8, 4
    # (bucket, B, data bytes) — S = bytes / (B*k); one case per plan
    # bucket the serving path actually exercises.
    cases = (("<64K", 1, 32 * 1024),
             ("64K-1M", 8, 512 * 1024),
             ("1-4M", 8, 2 * 1024 * 1024),
             ("4-16M", 8, 8 * 1024 * 1024))
    codec = Erasure(k, m, block_size=1024 * 1024)
    rng = np.random.default_rng(7)
    buckets: dict[str, dict] = {}
    worst_speedup = None
    best_tuned = 0.0
    for bucket, B, nbytes in cases:
        S = nbytes // (B * k)
        blocks = rng.integers(0, 256, (B, k, S)).astype(np.uint8)

        def encode_once(blocks=blocks) -> float:
            t0 = time.perf_counter()
            codec.encode_blocks_batch(blocks)
            return time.perf_counter() - t0

        encode_once()  # warm (native lib, jit shapes, caches)
        tuned: list[float] = []
        untuned: list[float] = []
        try:
            for i in range(6):
                order = (True, False) if i % 2 == 0 else (False, True)
                for on in order:
                    AUTOTUNE.enabled = on
                    (tuned if on else untuned).append(encode_once())
        finally:
            AUTOTUNE.enabled = True
        t_t = statistics.median(tuned)
        t_u = statistics.median(untuned)
        speedup = round(t_u / max(t_t, 1e-9), 3)
        lane = AUTOTUNE.decide("rs_encode", nbytes)
        gibs = nbytes / t_t / (1 << 30)
        best_tuned = max(best_tuned, gibs)
        buckets[bucket] = {
            "chosen_lane": lane,
            "tuned_GiBs": round(gibs, 3),
            "untuned_GiBs": round(nbytes / t_u / (1 << 30), 3),
            "tuned_over_untuned": speedup,
        }
        if worst_speedup is None or speedup < worst_speedup:
            worst_speedup = speedup
    return {"metric": "codec_autotune_encode",
            "value": round(best_tuned, 3), "unit": "GiB/s",
            # Paired acceptance signal: min tuned/untuned across
            # buckets (>= ~1.0 within noise = the planner never made
            # dispatch slower).
            "worst_tuned_over_untuned": worst_speedup,
            "buckets": buckets,
            "crossover_GiBs": ladder,
            "plan": AUTOTUNE.plan_compact()}


def bench_north_star_scaling(np) -> dict:
    """n_devices-aware north star: sweep serving meshes of 1..N
    devices (batching.set_mesh_devices) and report the encode scaling
    curve.  Empty on a single-device box — the sweep only means
    something when jax exposes a mesh (a 4-chip host, or virtual CPU
    devices under XLA_FLAGS).  Runs in the one process that owns the
    chips, like everything else here."""
    import jax

    from minio_tpu.ops import batching, rs_tpu
    n_dev = len(jax.devices())
    if n_dev < 2:
        return {}
    k, m = 8, 4
    S = (1 << 20) // k
    steps = sorted({n for n in (1, 2, 4, 8, n_dev) if n <= n_dev})
    curve: dict[str, float] = {}
    rng = np.random.default_rng(0)
    try:
        for n in steps:
            batching.set_mesh_devices(n)
            batch = 8 * max(1, n)  # B divides every mesh in the sweep
            data = rng.integers(0, 256, (batch, k, S)).astype(np.uint8)
            rs_tpu.encode_batch(data, k, m)  # warm/compile
            t = min(
                _timed_call(lambda: rs_tpu.encode_batch(data, k, m))
                for _ in range(3))
            curve[str(n)] = round(
                batch * k * S / t / (1 << 30), 3)
    finally:
        batching.set_mesh_devices(None)
    return {"devices": n_dev, "scaling_GiBs": curve}


def _timed_call(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


# --- config 3: 12+4 multipart upload through the engine ----------------------


def bench_multipart(np, workdir: str) -> dict:
    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.storage.xl import XLStorage

    root = os.path.join(workdir, "cfg3")
    disks = [XLStorage(os.path.join(root, f"disk{i}")) for i in range(16)]
    eng = ErasureObjects(disks, 12, 4, block_size=1024 * 1024)
    eng.make_bucket("bench")
    part_bytes = 32 * 1024 * 1024
    n_parts = 8                      # 256 MiB total (scaled from 10GiB)
    rng = np.random.default_rng(3)
    part = rng.integers(0, 256, part_bytes).astype(np.uint8).tobytes()
    try:
        # warm: single-part upload compiles the encode shapes
        eng.put_object("bench", "warm", part)
        up = eng.multipart.new_multipart_upload("bench", "big")
        t0 = time.perf_counter()
        etags = []
        for p in range(1, n_parts + 1):
            info = eng.multipart.put_object_part("bench", "big", up, p, part)
            etags.append((p, info["etag"]))
        eng.multipart.complete_multipart_upload("bench", "big", up, etags)
        dt = time.perf_counter() - t0
        total = n_parts * part_bytes
        return {"metric": "ec12+4_multipart_encode",
                "value": round(total / dt / (1 << 30), 3), "unit": "GiB/s",
                "total_bytes": total,
                "scale": "256MiB stand-in for BASELINE's 10GiB (wall-time bound)"}
    finally:
        shutil.rmtree(root, ignore_errors=True)


# --- config 4: 8+4 GetObject with 2 shards lost ------------------------------


def bench_get_with_loss(np, workdir: str, device: bool = False) -> dict:
    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.ops import batching
    from minio_tpu.storage.xl import XLStorage

    root = os.path.join(workdir, "cfg4")
    roots = [os.path.join(root, f"disk{i}") for i in range(12)]
    disks = [XLStorage(r) for r in roots]
    eng = ErasureObjects(disks, 8, 4, block_size=1024 * 1024)
    eng.make_bucket("bench")
    size = 64 * 1024 * 1024
    rng = np.random.default_rng(4)
    body = rng.integers(0, 256, size).astype(np.uint8).tobytes()
    try:
        eng.put_object("bench", "obj", body)
        # Lose 2 shards: wipe the object's data on two disks.
        for r in roots[:2]:
            shutil.rmtree(os.path.join(r, "bench", "obj"),
                          ignore_errors=True)
        eng.get_object("bench", "obj")  # warm (compile reconstruct shapes)
        before = batching.STATS.snapshot()
        t0 = time.perf_counter()
        got, _info = eng.get_object("bench", "obj")
        dt = time.perf_counter() - t0
        after = batching.STATS.snapshot()
        if got != body:
            raise RuntimeError("reconstructed object bytes differ")
        tpu_delta = after["tpu_dispatches"] - before["tpu_dispatches"]
        if device and tpu_delta == 0:
            raise RuntimeError(
                "device present but GET reconstruct never dispatched to "
                "it (honesty check)")
        return {"metric": "ec8+4_get_2lost",
                "value": round(size / dt / (1 << 30), 3), "unit": "GiB/s",
                "object_bytes": size,
                "tpu_dispatches": after["tpu_dispatches"]
                - before["tpu_dispatches"]}
    finally:
        shutil.rmtree(root, ignore_errors=True)


# --- config 5: 16+4 full-disk heal -------------------------------------------


def bench_heal(np, workdir: str, device: bool = False) -> dict:
    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.ops import batching
    from minio_tpu.storage.xl import XLStorage

    root = os.path.join(workdir, "cfg5")
    roots = [os.path.join(root, f"disk{i}") for i in range(20)]
    disks = [XLStorage(r) for r in roots]
    eng = ErasureObjects(disks, 16, 4, block_size=1024 * 1024)
    eng.make_bucket("bench")
    # 2x96MiB (was 24x8MiB): same 192MiB total, but objects larger than
    # one HEAL_BATCH_BYTES group so the heal pipeline (reconstruct
    # overlapping write-back) actually engages — the shape the BASELINE
    # 1000x64MiB workload has.
    n_objects, obj_bytes = 2, 96 * 1024 * 1024  # 192 MiB (scaled from
    rng = np.random.default_rng(5)              # 1000x64MiB; wall-time bound)
    try:
        for i in range(n_objects):
            body = rng.integers(0, 256, obj_bytes).astype(np.uint8)
            eng.put_object("bench", f"obj-{i}", body.tobytes())
        # Wipe one disk wholesale (full-disk loss), keep format metadata
        # dirs intact enough for rejoin by recreating the root.
        shutil.rmtree(roots[0])
        os.makedirs(roots[0], exist_ok=True)
        before = batching.STATS.snapshot()
        t0 = time.perf_counter()
        results = eng.healer.heal_disk(0)
        dt = time.perf_counter() - t0
        after = batching.STATS.snapshot()
        healed = sum(1 for r in results if r.healed_disks)
        if healed == 0:
            raise RuntimeError("heal_disk healed nothing")
        tpu_delta = after["tpu_dispatches"] - before["tpu_dispatches"]
        if device and tpu_delta == 0:
            raise RuntimeError(
                "device present but heal reconstruct never dispatched to "
                "it (honesty check)")
        total = n_objects * obj_bytes
        return {"metric": "ec16+4_heal",
                "value": round(total / dt / (1 << 30), 3), "unit": "GiB/s",
                "objects_healed": healed, "total_bytes": total,
                "scale": "2x96MiB stand-in for BASELINE's 1000x64MiB",
                "tpu_dispatches": after["tpu_dispatches"]
                - before["tpu_dispatches"]}
    finally:
        shutil.rmtree(root, ignore_errors=True)


# --- config 6: degraded tail — hedged reads vs one slow drive ----------------


def bench_degraded_tail(np, workdir: str) -> dict:
    """Paired hedging-on/off GET p99 with ONE injected-slow drive (a
    data-shard holder at 10x-ish the healthy read), using PR 4's
    paired-delta method: each hedging-ON GET is paired with the
    immediately-following hedging-OFF GET (alternating pair order so
    position-within-pair effects don't alias), so VM drift moves both
    halves together and the hedge's tail win survives. Also reports
    the hedge fire rate and the wasted-read fraction (completed
    hedges the primary beat anyway)."""
    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.faultinject import FAULTS
    from minio_tpu.obs.metrics2 import METRICS2
    from minio_tpu.s3.client import S3Client
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl import XLStorage

    def hedges(result: str) -> int:
        return METRICS2.get("minio_tpu_v2_hedged_reads_total",
                            {"result": result}) or 0

    access, secret = "benchadmin", "benchadmin-secret"
    root = os.path.join(workdir, "cfg-degraded")
    disks = [XLStorage(os.path.join(root, f"disk{i}"))
             for i in range(6)]
    layer = ErasureObjects(disks, 4, 2, block_size=256 * 1024)
    srv = S3Server(layer, access, secret)
    port = srv.start()
    try:
        client = S3Client("127.0.0.1", port, access, secret)
        client.make_bucket("bench")
        rng = np.random.default_rng(7)
        body = rng.integers(0, 256, 1024 * 1024).astype(
            np.uint8).tobytes()
        r = client.put_object("bench", "obj", body)
        if r.status != 200:
            raise RuntimeError(f"PutObject failed: {r.status}")
        # Calibrate the hedge budget on healthy reads.
        for _ in range(10):
            if client.get_object("bench", "obj").status != 200:
                raise RuntimeError("warm GET failed")
        healthy_ms = []
        for _ in range(10):
            t0 = time.perf_counter()
            client.get_object("bench", "obj")
            healthy_ms.append((time.perf_counter() - t0) * 1e3)
        # Slow ONE data-shard holder's shard reads to ~10x the
        # healthy GET (shard reads are a fraction of that, so the
        # multiple vs the read itself is far larger).
        import json as _json
        slow = None
        for d in disks:
            meta = os.path.join(d.root, "bench", "obj", "xl.meta")
            doc = _json.loads(open(meta).read())
            if doc["versions"][0]["erasure"]["index"] == 1:
                slow = d.root
                break
        inj_ms = max(50.0, 10.0 * statistics.median(healthy_ms))
        FAULTS.load_plan({"seed": 1, "rules": [
            {"kind": "latency", "target": slow, "op": "read_file",
             "latency_ms": inj_ms}]})
        fired0, won0, wasted0 = (hedges("fired"), hedges("won"),
                                 hedges("wasted"))
        lat_on: list = []
        lat_off: list = []
        try:
            for i in range(40):
                order = (True, False) if i % 2 == 0 else (False, True)
                for on in order:
                    layer.hedge_enabled = on
                    t0 = time.perf_counter()
                    g = client.get_object("bench", "obj")
                    (lat_on if on else lat_off).append(
                        (time.perf_counter() - t0) * 1e3)
                    if g.status != 200:
                        raise RuntimeError(f"GET failed: {g.status}")
        finally:
            layer.hedge_enabled = True
            FAULTS.clear()

        def p99(xs):
            return sorted(xs)[max(0, int(len(xs) * 0.99) - 1)]

        fired = hedges("fired") - fired0
        completed = (hedges("won") - won0) + (hedges("wasted")
                                              - wasted0)
        return {
            "metric": "degraded_get_p99_hedged_ms",
            "value": round(p99(lat_on), 3), "unit": "ms",
            "object_bytes": len(body),
            "injected_latency_ms": round(inj_ms, 1),
            "healthy_get_p50_ms": round(
                statistics.median(healthy_ms), 3),
            "get_p99_hedge_off_ms": round(p99(lat_off), 3),
            "get_p50_hedge_on_ms": round(
                statistics.median(lat_on), 3),
            "get_p50_hedge_off_ms": round(
                statistics.median(lat_off), 3),
            # How often the budget tripped, and how much of the fired
            # I/O the primary beat anyway (the hedging tax).
            "hedge_fire_rate": round(fired / max(1, len(lat_on)), 3),
            "hedge_wasted_fraction": round(
                (hedges("wasted") - wasted0) / max(1, completed), 3),
            "hedge_budget_ms": round(
                layer.hedge_budget.budget() * 1e3, 3),
        }
    finally:
        srv.stop()
        shutil.rmtree(root, ignore_errors=True)


# --- config 7: QoS brownout — overload shedding + heal interference ----------


def bench_qos_brownout(np, workdir: str) -> dict:
    """Two degradation numbers the QoS subsystem owns:

    1. brownout: loadgen drives 1MiB PUTs at ~4x the configured write
       cap; the server must SHED the excess with 503 SlowDown +
       Retry-After (bounded admitted p50/p99) instead of queueing
       unboundedly.
    2. heal interference: foreground 1MiB PUT p50 with a continuous
       heal sweep running vs heal-off baseline — the priority lanes
       (qos/scheduler.py) keep repair work out of the serving path.
    """
    import statistics as stats

    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.s3.client import S3Client
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl import XLStorage
    from tools.loadgen import run_load

    access, secret = "benchadmin", "benchadmin-secret"
    root = os.path.join(workdir, "cfg6")
    disks = [XLStorage(os.path.join(root, f"disk{i}")) for i in range(6)]
    layer = ErasureObjects(disks, 4, 2, block_size=1024 * 1024)
    srv = S3Server(layer, access, secret)
    port = srv.start()
    write_cap = 4
    try:
        client = S3Client("127.0.0.1", port, access, secret)
        client.make_bucket("bench")
        client.make_bucket("healbkt")
        rng = np.random.default_rng(6)
        body = rng.integers(0, 256, 1024 * 1024).astype(
            np.uint8).tobytes()
        for i in range(4):  # warm compile/caches
            client.put_object("bench", f"warm-{i}", body)

        # -- brownout: loadgen at ~4x the write cap ---------------------
        # Sheds are DELIBERATE backpressure: they must not pollute the
        # slow-request log or its blame histogram. Asserted via the
        # slowlog's exemption counter — every 503 the loadgen saw must
        # have been EXEMPTED (shed/deadline), not captured. (Raw 503
        # entry counts can't distinguish a leaked shed from a quorum
        # 503, which the slowlog deliberately captures.)
        from minio_tpu.obs.metrics2 import METRICS2 as _M2
        from minio_tpu.obs.slowlog import SLOWLOG
        from minio_tpu.obs.watchdog import WATCHDOG
        slowlog_before = SLOWLOG.total
        exempted_before = SLOWLOG.exempted
        # Standing regression test for the watchdog itself: with fast
        # sampling and short burn windows, the shed-rate built-in MUST
        # fire during the brownout and resolve after it — the bench
        # asserts the whole pending->firing->resolved loop against
        # real overload, not synthetic samples.
        shed_fired_before = _M2.get(
            "minio_tpu_v2_alert_transitions_total",
            {"rule": "shed_burn", "state": "firing"}) or 0
        srv.config.set_kv("obs timeline_sample=250ms")
        srv.config.set_kv("alerts fast_window=3s slow_window=30s "
                          "pending_ticks=2 resolve_ticks=2")
        srv.config.set_kv(f"api requests_max_write={write_cap} "
                          "requests_deadline=250ms")
        brown = run_load("127.0.0.1", port, access, secret, "bench",
                         concurrency=4 * write_cap, duration=4.0,
                         put_fraction=1.0, object_bytes=len(body))
        # The last shed-heavy samples are still inside the fast window:
        # give the sampler a moment to evaluate them before the caps
        # lift (the alert may already have fired mid-load).
        shed_deadline = time.time() + 10
        while (time.time() < shed_deadline
               and (_M2.get("minio_tpu_v2_alert_transitions_total",
                            {"rule": "shed_burn", "state": "firing"})
                    or 0) <= shed_fired_before):
            time.sleep(0.25)
        srv.config.set_kv("api requests_max_write=0 "
                          "requests_deadline=10s")
        shed_alert_fired = (_M2.get(
            "minio_tpu_v2_alert_transitions_total",
            {"rule": "shed_burn", "state": "firing"}) or 0) \
            - shed_fired_before
        if shed_alert_fired < 1:
            raise RuntimeError(
                "shed-rate watchdog built-in never fired during the "
                f"brownout (shed rate {brown['shed_rate']})")
        exempted = SLOWLOG.exempted - exempted_before
        if exempted < brown["shed_503"]:
            raise RuntimeError(
                f"only {exempted} of {brown['shed_503']} shed 503s "
                "were slowlog-exempt (sheds leaked into the blame "
                "histogram)")

        def put_lat(tag: str, n: int = 14) -> list[float]:
            lat = []
            for i in range(n):
                t0 = time.perf_counter()
                r = client.put_object("bench", f"{tag}-{i}", body)
                lat.append(time.perf_counter() - t0)
                if r.status != 200:
                    raise RuntimeError(f"PUT failed: {r.status}")
            return lat

        # -- heal interference ------------------------------------------
        # off -> on -> off: the two baselines bracket the measurement
        # so page-cache/VM drift doesn't masquerade as interference.
        for i in range(16):
            client.put_object("healbkt", f"obj-{i}", body)
        lat_off = put_lat("off1")
        stop = threading.Event()

        def heal_forever():
            import shutil as _sh
            while not stop.is_set():
                for i in range(16):  # re-damage so the sweep never idles
                    _sh.rmtree(os.path.join(root, "disk0", "healbkt",
                                            f"obj-{i}"),
                               ignore_errors=True)
                layer.healer.heal_disk(0)

        ht = threading.Thread(target=heal_forever, daemon=True)
        ht.start()
        time.sleep(0.3)  # let the sweep reach steady state
        lat_on = put_lat("on")
        stop.set()
        ht.join(timeout=60)
        lat_off += put_lat("off2")
        p50_off = stats.median(lat_off) * 1e3
        p50_on = stats.median(lat_on) * 1e3
        # The shed-rate alert must RESOLVE once the brownout is over:
        # the heal-interference PUTs above ran shed-free, so the fast
        # window has long cleared — poll out the resolve hysteresis.
        resolve_deadline = time.time() + 30
        while (time.time() < resolve_deadline
               and WATCHDOG.state_of("shed_burn") != "ok"):
            time.sleep(0.25)
        if WATCHDOG.state_of("shed_burn") != "ok":
            raise RuntimeError(
                "shed-rate alert never resolved after the brownout: "
                f"{WATCHDOG.snapshot()['alerts']}")
        from minio_tpu.obs.metrics2 import METRICS2
        return {
            "metric": "qos_brownout",
            "value": brown["shed_rate"], "unit": "shed_rate",
            "write_cap": write_cap,
            "overload_concurrency": 4 * write_cap,
            "requests": brown["requests"], "ok": brown["ok"],
            "shed_503": brown["shed_503"],
            "retry_after_headers": brown["retry_after_headers"],
            "admitted_p50_ms": brown["latency_ms"]["p50"],
            "admitted_p99_ms": brown["latency_ms"]["p99"],
            "put_p50_heal_off_ms": round(p50_off, 3),
            "put_p50_heal_on_ms": round(p50_on, 3),
            "heal_interference_ratio": round(p50_on / max(p50_off, 1e-9),
                                             3),
            "bg_deferrals": METRICS2.get(
                "minio_tpu_v2_qos_bg_deferrals_total"),
            "bg_promotions": METRICS2.get(
                "minio_tpu_v2_qos_bg_promotions_total"),
            # Asserted above: every shed was slowlog-exempt.
            "slowlog_exempted_sheds": exempted,
            "slowlog_entries_during": SLOWLOG.total - slowlog_before,
            # Asserted above: the shed-rate built-in fired during the
            # brownout and resolved after it.
            "shed_alert_fired": shed_alert_fired,
            "shed_alert_resolved": True,
        }
    finally:
        srv.stop()
        shutil.rmtree(root, ignore_errors=True)


def bench_hot_get(np, workdir: str) -> dict:
    """Hot-object serving tier: Zipfian GETs with the cache on vs off,
    PAIRED off/on/off so VM drift brackets the measurement (PR 4's
    method). Reports GET QPS both ways, the speedup, hit ratio,
    coalesced-fill count, and p99 — stamped with the cache config the
    way every config is stamped with backend_mix. Also records the
    cache-OFF PUT+GET p50 as a cross-round tripwire: the consult hook
    when disabled is one attribute read, so this number regressing
    against earlier bench records means the default-off path grew
    real cost (the code-present vs code-absent A/B cannot be toggled
    at runtime — the round history IS the baseline)."""
    import statistics as stats

    from minio_tpu.cache.hotcache import HOTCACHE
    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.obs.metrics2 import METRICS2
    from minio_tpu.s3.client import S3Client
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl import XLStorage
    from tools.loadgen import run_load

    access, secret = "benchadmin", "benchadmin-secret"
    base = workdir
    if os.path.isdir("/dev/shm"):
        # tmpfs like put_p50: this config tracks the serving path's
        # CPU cost, not VM writeback noise.
        base = tempfile.mkdtemp(prefix="minio-tpu-hotget-",
                                dir="/dev/shm")
    root = os.path.join(base, "cfg7")
    # 4+2 like put_p50: wider sets convoy this 2-core box's quorum
    # pool into multi-second tails that drown the signal.
    disks = [XLStorage(os.path.join(root, f"disk{i}"))
             for i in range(6)]
    layer = ErasureObjects(disks, 4, 2, block_size=1024 * 1024)
    srv = S3Server(layer, access, secret)
    port = srv.start()
    # revalidate must outlast warm+segment: a mem hit that trips the
    # revalidation window pays a metadata fan-out, which is the miss
    # path's dominant cost — the window is the operator's staleness
    # bound, and the bench measures steady-state hits inside it.
    keys, obj_bytes, zipf_s, seg_s = 64, 256 * 1024, 1.2, 4.0
    cache_kv = ("cache enable=on mem_bytes=268435456 min_hits=1 "
                "max_object_bytes=8388608 revalidate=30s")
    try:
        client = S3Client("127.0.0.1", port, access, secret)
        client.make_bucket("bench")
        rng = np.random.default_rng(7)
        body = rng.integers(0, 256, obj_bytes).astype(np.uint8).tobytes()
        for r in range(keys):   # preload the Zipf key space + warm
            client.put_object("bench", f"hot/z{r}", body)

        def seg(tag: str) -> dict:
            return run_load("127.0.0.1", port, access, secret, "bench",
                            concurrency=4, duration=seg_s,
                            put_fraction=0.0, object_bytes=obj_bytes,
                            key_prefix="hot", key_space=keys,
                            zipf_s=zipf_s, seed=7)

        off1 = seg("off1")

        def m(name, labels=None):
            return METRICS2.get(name, labels)

        srv.config.set_kv(cache_kv)
        for r in range(keys):
            # Warm the tier: the measured window is STEADY-STATE hot
            # serving (cold-fill cost is the miss path, measured by
            # the off segments and amortized over an object's life).
            client.get_object("bench", f"hot/z{r}")
        hits0 = (m("minio_tpu_v2_cache_hits_total", {"tier": "mem"})
                 + m("minio_tpu_v2_cache_hits_total", {"tier": "disk"}))
        miss0 = m("minio_tpu_v2_cache_misses_total")
        coal0 = m("minio_tpu_v2_cache_coalesced_waits_total")
        on = seg("on")
        hits = (m("minio_tpu_v2_cache_hits_total", {"tier": "mem"})
                + m("minio_tpu_v2_cache_hits_total", {"tier": "disk"})
                - hits0)
        misses = m("minio_tpu_v2_cache_misses_total") - miss0
        coalesced = m("minio_tpu_v2_cache_coalesced_waits_total") - coal0
        srv.config.set_kv("cache enable=off")
        off2 = seg("off2")

        # Cache-OFF PUT+GET p50 tripwire (see docstring): the default
        # mode's absolute cost, judged against prior rounds' records.
        lat_pg: list[float] = []
        for i in range(30):
            t0 = time.perf_counter()
            client.put_object("bench", f"ov-{i}", body)
            client.get_object("bench", f"ov-{i}")
            lat_pg.append(time.perf_counter() - t0)

        qps_off = (off1["qps_achieved"] + off2["qps_achieved"]) / 2
        qps_on = on["qps_achieved"]
        lookups = hits + misses
        return {
            "metric": "hot_get",
            "value": round(qps_on / max(qps_off, 1e-9), 2),
            "unit": "x_get_qps",
            "get_qps_cache_on": qps_on,
            "get_qps_cache_off": round(qps_off, 2),
            "p99_ms_cache_on": on["latency_ms"]["p99"],
            "p99_ms_cache_off": round(
                (off1["latency_ms"]["p99"]
                 + off2["latency_ms"]["p99"]) / 2, 3),
            "hit_ratio": round(hits / lookups, 4) if lookups else 0.0,
            "cache_hits": hits, "cache_misses": misses,
            "coalesced_fills": coalesced,
            "key_distribution": on.get("key_distribution", {}),
            "cache_off_put_get_p50_ms": round(
                stats.median(lat_pg) * 1e3, 3),
            "errors_other": (off1["errors_other"] + on["errors_other"]
                             + off2["errors_other"]),
            # The stamp: which cache config produced these numbers
            # (like backend_mix stamps which backend ran the math).
            "cache": {"keys": keys, "object_bytes": obj_bytes,
                      "zipf_s": zipf_s, "segment_s": seg_s,
                      "kv": cache_kv,
                      "workdir": "tmpfs" if base != workdir else "disk"},
        }
    finally:
        HOTCACHE.reset()
        srv.stop()
        shutil.rmtree(root, ignore_errors=True)
        if base != workdir:
            shutil.rmtree(base, ignore_errors=True)


def bench_noisy_neighbor(np, workdir: str) -> dict:
    """Tenant attribution plane end-to-end (obs/usage.py): one
    Zipf-hot tenant amid uniform background, driven through the
    multi-tenant loadgen against a capped write class so the hot
    tenant causes real sheds.  Asserts the whole loop the plane
    exists for:

    1. admin /top ranks the injected hot bucket first, with a
       worst-request trace-id exemplar that resolves in the slowlog;
    2. the watchdog's noisy_neighbor built-in fires with the tenant
       named in the cause, and resolves after the skew stops;
    3. a paired usage-on/off PUT p50 stays within the PR-4 noise bar
       (<= 2%) — attribution must be free on the hot path.
    """
    import statistics as stats

    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.obs.metrics2 import METRICS2
    from minio_tpu.obs.usage import USAGE
    from minio_tpu.obs.watchdog import WATCHDOG
    from minio_tpu.s3.admin_client import AdminClient
    from minio_tpu.s3.client import S3Client
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl import XLStorage
    from tools.loadgen import run_load

    access, secret = "benchadmin", "benchadmin-secret"
    base = workdir
    if os.path.isdir("/dev/shm"):
        # tmpfs like put_p50/hot_get: the paired p50 tracks the
        # record() hook's CPU cost, not VM writeback noise.
        base = tempfile.mkdtemp(prefix="minio-tpu-noisy-",
                                dir="/dev/shm")
    root = os.path.join(base, "cfg-noisy")
    disks = [XLStorage(os.path.join(root, f"disk{i}"))
             for i in range(6)]
    layer = ErasureObjects(disks, 4, 2, block_size=1024 * 1024)
    srv = S3Server(layer, access, secret)
    port = srv.start()
    n_tenants, write_cap = 4, 2
    try:
        USAGE.reset()
        client = S3Client("127.0.0.1", port, access, secret)
        adm = AdminClient("127.0.0.1", port, access, secret)
        for i in range(n_tenants):
            client.make_bucket(f"nz-{i}")
        client.make_bucket("ovh")
        rng = np.random.default_rng(15)
        # 1MiB like qos_brownout: big enough that a 4x-cap overload
        # piles queue waits past the deadline and actually SHEDS.
        body = rng.integers(0, 256, 1024 * 1024).astype(
            np.uint8).tobytes()
        for i in range(4):  # warm compile/caches
            client.put_object("ovh", f"warm-{i}", body)

        # -- paired usage-on/off PUT p50 (off/on/off brackets drift) --
        def put_lat(tag: str, n: int = 24) -> list[float]:
            lat = []
            for i in range(n):
                t0 = time.perf_counter()
                r = client.put_object("ovh", f"{tag}-{i}", body)
                lat.append(time.perf_counter() - t0)
                if r.status != 200:
                    raise RuntimeError(f"PUT failed: {r.status}")
            return lat

        adm.set_config_kv("usage enable=off")
        lat_off = put_lat("off1")
        adm.set_config_kv("usage enable=on")
        lat_on = put_lat("on")
        adm.set_config_kv("usage enable=off")
        lat_off += put_lat("off2")
        adm.set_config_kv("usage enable=on")
        p50_off = stats.median(lat_off) * 1e3
        p50_on = stats.median(lat_on) * 1e3
        overhead_pct = (p50_on - p50_off) / max(p50_off, 1e-9) * 100
        if overhead_pct > 2.0:
            raise RuntimeError(
                f"usage-on PUT p50 overhead {overhead_pct:.2f}% "
                f"exceeds the 2% noise bar "
                f"(on {p50_on:.3f}ms vs off {p50_off:.3f}ms)")

        # -- skewed fleet: Zipf-hot tenant 0 vs uniform background ----
        USAGE.reset()
        adm.set_config_kv("obs timeline_sample=250ms slow_ms=100")
        adm.set_config_kv("usage fast_window=2s slow_window=10s "
                          "noisy_share=0.5 noisy_min_requests=20")
        adm.set_config_kv("alerts pending_ticks=2 resolve_ticks=2")
        # ~12x the cap: the bounded wait queue (QUEUE_FACTOR x cap)
        # overflows and the 100ms budget burns, so the overload SHEDS
        # instead of merely queueing on a fast box.
        adm.set_config_kv(f"api requests_max_write={write_cap} "
                          "requests_deadline=100ms")
        fired_before = METRICS2.get(
            "minio_tpu_v2_alert_transitions_total",
            {"rule": "noisy_neighbor", "state": "firing"}) or 0
        load = run_load("127.0.0.1", port, access, secret, "nz",
                        concurrency=12 * write_cap, duration=3.0,
                        put_fraction=1.0, object_bytes=len(body),
                        buckets=n_tenants, tenant_zipf_s=3.0, seed=15)
        # The skew is still inside the fast window: give the sampler
        # a moment to evaluate it before the caps lift.
        fire_deadline = time.time() + 10
        while (time.time() < fire_deadline
               and (METRICS2.get(
                   "minio_tpu_v2_alert_transitions_total",
                   {"rule": "noisy_neighbor", "state": "firing"})
                   or 0) <= fired_before):
            time.sleep(0.25)
        fired = (METRICS2.get(
            "minio_tpu_v2_alert_transitions_total",
            {"rule": "noisy_neighbor", "state": "firing"})
            or 0) - fired_before
        snap_alerts = {a["rule"]: a for a in
                       WATCHDOG.snapshot()["alerts"]}
        cause = snap_alerts.get("noisy_neighbor", {}).get("cause", "")
        if fired < 1 or "nz-0" not in cause:
            raise RuntimeError(
                "noisy_neighbor never fired naming the hot tenant "
                f"(fired={fired}, cause={cause!r}, "
                f"shed_rate={load['shed_rate']})")

        # -- admin /top names the hot bucket, exemplar -> slowlog -----
        top = adm.top()
        ranked = [b for b in top["buckets"]
                  if b["name"].startswith("nz-")]
        if not ranked or ranked[0]["name"] != "nz-0":
            raise RuntimeError(
                f"/top did not rank the hot tenant first: "
                f"{[b['name'] for b in top['buckets']]}")
        worst = ranked[0].get("worst", {})
        if not worst.get("traceId"):
            raise RuntimeError(f"/top carried no trace exemplar: "
                               f"{ranked[0]}")
        hot_keys = (top.get("keys") or {}).get("write", [])
        if not any(k["key"].startswith("nz-0/") for k in hot_keys):
            raise RuntimeError(
                f"write-key sketch missed the hot bucket: {hot_keys}")

        # -- resolve once the skew stops ------------------------------
        adm.set_config_kv("api requests_max_write=0 "
                          "requests_deadline=10s")
        resolve_deadline = time.time() + 30
        while (time.time() < resolve_deadline
               and WATCHDOG.state_of("noisy_neighbor") != "ok"):
            time.sleep(0.25)
        if WATCHDOG.state_of("noisy_neighbor") != "ok":
            raise RuntimeError(
                "noisy_neighbor never resolved after the skew "
                f"stopped: {WATCHDOG.snapshot()['alerts']}")

        hot = (load.get("tenants") or {}).get("nz-0", {})
        return {
            "metric": "noisy_neighbor",
            "value": round(hot.get("requests", 0)
                           / max(load["requests"], 1), 4),
            "unit": "hot_tenant_share",
            "tenants": n_tenants, "write_cap": write_cap,
            "requests": load["requests"],
            "shed_503": load["shed_503"],
            "per_tenant": load.get("tenants", {}),
            "alert_fired": fired, "alert_cause": cause,
            "alert_resolved": True,
            "top_bucket": ranked[0]["name"],
            "worst_trace_id": worst.get("traceId", ""),
            "worst_in_slowlog": "slowlog" in worst,
            "usage_folded": USAGE.folded_total,
            "put_p50_usage_on_ms": round(p50_on, 3),
            "put_p50_usage_off_ms": round(p50_off, 3),
            "usage_overhead_pct": round(overhead_pct, 2),
        }
    finally:
        USAGE.reset()
        from minio_tpu.config.kv import DEFAULT_KVS
        USAGE.configure(
            top_k=int(DEFAULT_KVS["usage"]["top_k"]),
            cardinality_cap=int(DEFAULT_KVS["usage"]
                                ["cardinality_cap"]))
        srv.stop()
        shutil.rmtree(root, ignore_errors=True)
        if base != workdir:
            shutil.rmtree(base, ignore_errors=True)


def bench_loop_health(np, workdir: str) -> dict:
    """Event-loop health plane end-to-end (obs/loopmon.py), two
    promises:

    1. a paired loopmon-on/off keep-alive PUT p50 within the repo's
       2% noise bar — a 10Hz heartbeat + watcher must be free on the
       hot path;
    2. an injected 400ms ``loop_block`` fault plan against a
       front-door loop drives the ``loop_stall`` watchdog built-in to
       firing with the blamed frame (``_injected_loop_block``) named
       in the cause, and the alert resolves after the plan clears and
       the recent-stall window drains.
    """
    import statistics as stats

    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.obs.loopmon import LOOPMON
    from minio_tpu.obs.metrics2 import METRICS2
    from minio_tpu.obs.watchdog import WATCHDOG
    from minio_tpu.s3.admin_client import AdminClient
    from minio_tpu.s3.client import S3Client
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl import XLStorage

    access, secret = "benchadmin", "benchadmin-secret"
    base = workdir
    if os.path.isdir("/dev/shm"):
        # tmpfs like put_p50: the paired p50 tracks the heartbeat's
        # CPU cost, not VM writeback noise.
        base = tempfile.mkdtemp(prefix="minio-tpu-loop-",
                                dir="/dev/shm")
    root = os.path.join(base, "cfg-loop")
    disks = [XLStorage(os.path.join(root, f"disk{i}"))
             for i in range(6)]
    layer = ErasureObjects(disks, 4, 2, block_size=1024 * 1024)
    srv = S3Server(layer, access, secret)
    port = srv.start()
    try:
        client = S3Client("127.0.0.1", port, access, secret)
        adm = AdminClient("127.0.0.1", port, access, secret)
        client.make_bucket("lhealth")
        rng = np.random.default_rng(19)
        body = rng.integers(0, 256, 1024 * 1024).astype(
            np.uint8).tobytes()
        for i in range(4):  # warm compile/caches
            client.put_object("lhealth", f"warm-{i}", body)

        # -- paired loopmon-on/off PUT p50 (off/on/off brackets drift)
        def put_lat(tag: str, n: int = 24) -> list[float]:
            lat = []
            for i in range(n):
                t0 = time.perf_counter()
                r = client.put_object("lhealth", f"{tag}-{i}", body)
                lat.append(time.perf_counter() - t0)
                if r.status != 200:
                    raise RuntimeError(f"PUT failed: {r.status}")
            return lat

        LOOPMON.set_enabled(False)
        lat_off = put_lat("off1")
        LOOPMON.set_enabled(True)
        lat_on = put_lat("on")
        LOOPMON.set_enabled(False)
        lat_off += put_lat("off2")
        LOOPMON.set_enabled(True)
        p50_off = stats.median(lat_off) * 1e3
        p50_on = stats.median(lat_on) * 1e3
        overhead_pct = (p50_on - p50_off) / max(p50_off, 1e-9) * 100
        if overhead_pct > 2.0:
            raise RuntimeError(
                f"loopmon-on PUT p50 overhead {overhead_pct:.2f}% "
                f"exceeds the 2% noise bar "
                f"(on {p50_on:.3f}ms vs off {p50_off:.3f}ms)")

        # -- injected 400ms loop_block -> loop_stall fires -> resolves
        adm.set_config_kv("obs timeline_sample=250ms "
                          "loop_stall_ms=200")
        adm.set_config_kv("alerts pending_ticks=2 resolve_ticks=2")
        fired_before = METRICS2.get(
            "minio_tpu_v2_alert_transitions_total",
            {"rule": "loop_stall", "state": "firing"}) or 0
        # ONE deterministic block on the first front-door loop: the
        # heartbeat schedules it as a real time.sleep on the loop.
        adm.fault_inject({"seed": 19, "rules": [
            {"kind": "loop_block", "target": "s3-0",
             "latency_ms": 400, "count": 1}]})
        fire_deadline = time.time() + 20
        while (time.time() < fire_deadline
               and (METRICS2.get(
                   "minio_tpu_v2_alert_transitions_total",
                   {"rule": "loop_stall", "state": "firing"})
                   or 0) <= fired_before):
            time.sleep(0.25)
        fired = (METRICS2.get(
            "minio_tpu_v2_alert_transitions_total",
            {"rule": "loop_stall", "state": "firing"})
            or 0) - fired_before
        snap_alerts = {a["rule"]: a for a in
                       WATCHDOG.snapshot()["alerts"]}
        cause = snap_alerts.get("loop_stall", {}).get("cause", "")
        if fired < 1 or "_injected_loop_block" not in cause:
            raise RuntimeError(
                "loop_stall never fired naming the injected frame "
                f"(fired={fired}, cause={cause!r}, "
                f"stalls={LOOPMON.snapshot()['stalls'][-3:]})")

        adm.fault_inject(clear=True)
        # The recent-stall window (10s) drains, then resolve_ticks.
        resolve_deadline = time.time() + 40
        while (time.time() < resolve_deadline
               and WATCHDOG.state_of("loop_stall") != "ok"):
            time.sleep(0.25)
        if WATCHDOG.state_of("loop_stall") != "ok":
            raise RuntimeError(
                "loop_stall never resolved after the plan cleared: "
                f"{WATCHDOG.snapshot()['alerts']}")

        prof = LOOPMON.profiler.report(top=5, minutes=2)
        return {
            "metric": "loop_health",
            "value": round(overhead_pct, 2),
            "unit": "loopmon_on_p50_overhead_pct",
            "put_p50_loopmon_on_ms": round(p50_on, 3),
            "put_p50_loopmon_off_ms": round(p50_off, 3),
            "alert_fired": fired, "alert_cause": cause,
            "alert_resolved": True,
            "loop_census": LOOPMON.lag_census(),
            "profiler_running": prof["running"],
            "profiler_samples": prof["samples"],
        }
    finally:
        from minio_tpu.faultinject import FAULTS
        FAULTS.clear()
        LOOPMON.set_enabled(True)
        srv.stop()
        shutil.rmtree(root, ignore_errors=True)
        if base != workdir:
            shutil.rmtree(base, ignore_errors=True)


# --- config 9: crash recovery — kill -9 mid-PUT-loop, restart, recover -------


def bench_front_door(np, workdir: str) -> dict:
    """Event-loop front door at connection scale, three numbers:

    1. connection sweep — the asyncio loadgen (subprocess: client and
       server each get their own fd budget) holds 100 / 1k / 10k
       keep-alive sockets and drives a paced in-cap GET/PUT mix;
       p50/p99 vs connection count. Flat p99 = idle sockets are free.
    2. idle-connection RSS: server RSS delta while 10k established
       connections sit on keep-alive, per connection.
    3. paired low-concurrency put_p50 tripwire: async vs threaded
       front door on identical layers, alternating pairs (PR-4's
       method — this VM drifts on second timescales, pairing cancels
       it); the event loop must cost ~nothing at today's workloads.
    4. distributed fan-out: a 2-node cluster (half the erasure set
       behind peer RPC) drives paired async-vs-threaded RPC-fabric
       PUTs (same alternating-pair method, flipping MINIO_RPC_FABRIC
       per call), then parks 1k concurrent peer calls on the RPC loop
       and reads the in-flight census against the process thread
       count — the zero-thread-per-call claim, stamped.

    Tripwires raise (bench records the failure): p99 flatness
    (10k within 2x of 100-conn p99 plus a 15ms scheduling-jitter
    floor — two python processes on 2 cores), zero loadgen framing
    errors, zero admission-slot leaks, put_p50 delta within noise,
    census >= 900 of 1k in flight with <= 8 extra threads.
    """
    import statistics as stats
    import subprocess
    import sys

    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.s3.client import S3Client
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl import XLStorage

    access, secret = "benchadmin", "benchadmin-secret"
    root = os.path.join(workdir, "cfg_fd")

    def rss_kib() -> int:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
        return 0

    def boot(front: str, tag: str):
        disks = [XLStorage(os.path.join(root, f"{tag}{i}"))
                 for i in range(6)]
        layer = ErasureObjects(disks, 4, 2, block_size=1024 * 1024)
        prev = os.environ.get("MINIO_FRONT_DOOR")
        os.environ["MINIO_FRONT_DOOR"] = front
        try:
            srv = S3Server(layer, access, secret)
            port = srv.start()
        finally:
            if prev is None:
                os.environ.pop("MINIO_FRONT_DOOR", None)
            else:
                os.environ["MINIO_FRONT_DOOR"] = prev
        return srv, port

    srv, port = boot("async", "disk")
    srv_t = None
    try:
        client = S3Client("127.0.0.1", port, access, secret)
        client.make_bucket("bench")
        body16k = os.urandom(16 * 1024)
        for i in range(6):  # warm codec/caches
            client.put_object("bench", f"warm-{i}", body16k)
        # In-cap traffic: executing concurrency is capped, so request
        # latency must not depend on how many sockets are PARKED.
        srv.config.set_kv("api requests_max_read=8 requests_max_write=4"
                          " requests_deadline=10s")

        def drive(conns: int, duration: float, qps: float) -> dict:
            out = subprocess.run(
                [sys.executable, "-m", "tools.loadgen",
                 "--port", str(port), "--access-key", access,
                 "--secret-key", secret, "--bucket", "bench",
                 "--connections", str(conns),
                 "--duration", str(duration), "--qps", str(qps),
                 "--put-fraction", "0.1", "--size", str(len(body16k))],
                capture_output=True, text=True, timeout=600,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))
            if out.returncode != 0:
                raise RuntimeError(
                    f"loadgen at {conns} conns failed: "
                    f"{out.stderr[-500:]}")
            return json.loads(out.stdout)

        sweep: list[dict] = []
        rss_idle_per_conn = 0.0
        for conns in (100, 1000, 10000):
            rss_before = rss_kib()
            rep = drive(conns, 6.0, 150.0)
            if rep["errors_other"] or rep["connect_failures"]:
                raise RuntimeError(
                    f"loadgen framing/connect errors at {conns} "
                    f"conns: {rep['errors_other']} / "
                    f"{rep['connect_failures']}")
            sweep.append({
                "connections": conns,
                "established": rep["established"],
                "requests": rep["requests"], "ok": rep["ok"],
                "shed_503": rep["shed_503"],
                "reconnects": rep["reconnects"],
                "connect_p50_ms": rep["connect_ms"]["p50"],
                "connect_p99_ms": rep["connect_ms"]["p99"],
                "get_p50_ms": rep["get"]["total_ms"]["p50"],
                "get_p99_ms": rep["get"]["total_ms"]["p99"],
                "get_ttfb_p99_ms": rep["get"]["ttfb_ms"]["p99"],
                "put_p50_ms": rep["put"]["total_ms"]["p50"],
                "put_p99_ms": rep["put"]["total_ms"]["p99"],
                "rss_before_kib": rss_before,
            })
        p99_100 = sweep[0]["get_p99_ms"]
        p99_10k = sweep[-1]["get_p99_ms"]
        # Flatness: within 2x plus a fixed scheduling-jitter floor —
        # client (10k coroutines) and server share 2 cores here, and
        # the 100-conn baseline p99 itself swings 6-12ms run to run.
        if p99_10k > 2.0 * p99_100 + 15.0:
            raise RuntimeError(
                f"p99 not flat across the sweep: {p99_100:.1f}ms @100 "
                f"vs {p99_10k:.1f}ms @10k conns")
        if srv.qos.foreground_inflight() != 0:
            raise RuntimeError(
                f"admission slots leaked after sweep: "
                f"{srv.qos.foreground_inflight()}")

        # -- idle-connection RSS: hold 10k established, mostly idle --
        rss_before = rss_kib()
        hold = subprocess.Popen(
            [sys.executable, "-m", "tools.loadgen",
             "--port", str(port), "--access-key", access,
             "--secret-key", secret, "--bucket", "bench",
             "--connections", "10000", "--duration", "6",
             "--qps", "20", "--put-fraction", "0", "--size", "4096"],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        try:
            # Sample at the held plateau: wait for the full fleet, but
            # a TIME_WAIT-throttled connect storm (the sweep's 10k
            # sockets just closed) may cap below 10k — any plateau of
            # thousands gives a valid per-connection number.
            deadline = time.time() + 120
            held = peak = 0
            rss_at_peak = rss_before
            while time.time() < deadline:
                held = srv._front_door.open_connections()
                if held >= peak:
                    peak = held
                    rss_at_peak = rss_kib()
                if held >= 9900:
                    break
                if held < peak * 0.8 and peak >= 2000:
                    break  # fleet already draining; peak was the hold
                time.sleep(0.25)
            if peak >= 2000:
                rss_idle_per_conn = (rss_at_peak - rss_before) \
                    * 1024.0 / peak
        finally:
            hold.wait(timeout=300)
        open_after = srv._front_door.open_connections()

        # -- paired async vs threaded put_p50 tripwire ---------------
        # KEEP-ALIVE clients (how every real S3 SDK talks): one
        # persistent connection per server, alternating pair order so
        # VM drift cancels. A second, per-request-CONNECT series is
        # recorded informationally (the async accept path pays a loop
        # hop per connection that the thread-spawn path does not).
        import http.client as _hc

        from minio_tpu.s3 import sigv4 as _sigv4

        srv.config.set_kv("api requests_max_read=0 requests_max_write=0"
                          " requests_deadline=10s")
        srv_t, port_t = boot("threaded", "tdisk")
        client_t = S3Client("127.0.0.1", port_t, access, secret)
        client_t.make_bucket("bench")
        body1m = os.urandom(1024 * 1024)

        def timed_put_ka(conn, sport, tag, i) -> float:
            path = f"/bench/{tag}-{i}"
            hdrs = _sigv4.sign_request(
                "PUT", path, "",
                {"host": f"127.0.0.1:{sport}",
                 "content-length": str(len(body1m))},
                body1m, access, secret, "us-east-1")
            t0 = time.perf_counter()
            conn.request("PUT", path, body=body1m, headers=hdrs)
            r = conn.getresponse()
            r.read()
            if r.status != 200:
                raise RuntimeError(f"PUT failed: {r.status}")
            return (time.perf_counter() - t0) * 1e3

        conn_a = _hc.HTTPConnection("127.0.0.1", port, timeout=60)
        conn_t = _hc.HTTPConnection("127.0.0.1", port_t, timeout=60)
        for i in range(3):  # warm both paths + connections
            timed_put_ka(conn_a, port, "wa", i)
            timed_put_ka(conn_t, port_t, "wt", i)
        deltas, lat_a, lat_t = [], [], []
        for i in range(14):
            if i % 2 == 0:  # alternate order inside each pair
                a = timed_put_ka(conn_a, port, "pa", i)
                t = timed_put_ka(conn_t, port_t, "pt", i)
            else:
                t = timed_put_ka(conn_t, port_t, "pt", i)
                a = timed_put_ka(conn_a, port, "pa", i)
            lat_a.append(a)
            lat_t.append(t)
            deltas.append(a - t)
        conn_a.close()
        conn_t.close()
        p50_a = stats.median(lat_a)
        p50_t = stats.median(lat_t)
        delta_pct = stats.median(deltas) / max(p50_t, 1e-9) * 100.0

        # Informational: per-request-connection pairs (S3Client opens
        # a fresh socket each time).
        def timed_put_conn(cl, tag, i) -> float:
            t0 = time.perf_counter()
            r = cl.put_object("bench", f"{tag}-{i}", body1m)
            if r.status != 200:
                raise RuntimeError(f"PUT failed: {r.status}")
            return (time.perf_counter() - t0) * 1e3

        rc_deltas, rc_t = [], []
        for i in range(10):
            if i % 2 == 0:
                a = timed_put_conn(client, "ra", i)
                t = timed_put_conn(client_t, "rt", i)
            else:
                t = timed_put_conn(client_t, "rt", i)
                a = timed_put_conn(client, "ra", i)
            rc_t.append(t)
            rc_deltas.append(a - t)
        reconnect_delta_pct = stats.median(rc_deltas) \
            / max(stats.median(rc_t), 1e-9) * 100.0

        fanout = _bench_fanout_fabric(stats, workdir, access, secret)

        return {
            "metric": "front_door",
            "value": round(p99_10k / max(p99_100, 1e-9), 3),
            "unit": "p99_ratio_10k_vs_100_conns",
            "sweep": sweep,
            "qps_paced": 150.0,
            "get_p99_100_ms": p99_100,
            "get_p99_10k_ms": p99_10k,
            "idle_conn_rss_bytes": round(rss_idle_per_conn, 1),
            "idle_conns_held": peak,
            "open_connections_after": open_after,
            "slot_leaks": srv.qos.foreground_inflight(),
            "put_p50_async_ms": round(p50_a, 3),
            "put_p50_threaded_ms": round(p50_t, 3),
            # Median of PAIRED keep-alive deltas over the threaded
            # median — the tripwire number (<= ~2% = the event loop is
            # free at today's workloads; this VM's unpaired drift is
            # +/-20%). Negative = the async door is FASTER (NODELAY +
            # single-segment coalesced responses).
            "put_p50_paired_delta_pct": round(delta_pct, 2),
            # Per-request-connection variant: pays the accept-path
            # loop hop per socket (real SDKs keep connections alive).
            "put_p50_reconnect_delta_pct": round(reconnect_delta_pct,
                                                 2),
            "fanout": fanout,
        }
    finally:
        if srv_t is not None:
            srv_t.stop()
        srv.stop()
        shutil.rmtree(root, ignore_errors=True)


def _bench_fanout_fabric(stats, workdir: str, access: str,
                         secret: str) -> dict:
    """Distributed fan-out step: 2-node cluster, half of every erasure
    stripe behind peer RPC.

    (a) Paired RPC-fabric PUTs: each front-door PUT on node 0 fans its
    remote shards out over the internal RPC plane; MINIO_RPC_FABRIC is
    flipped per call (the knob is read at dispatch time) in
    alternating pair order, so VM drift cancels and the async fabric's
    cost shows up as a paired delta, not an absolute.

    (b) In-flight census: 1k concurrent peer calls submitted straight
    onto the RPC loop against a registered nap service on node 1 —
    client-side in-flight peaks near 1k while the process grows ~zero
    threads (the in-process SERVER'S bounded rpc pool is pre-warmed to
    cap so it cannot pollute the delta).
    """
    import http.client as _hc

    from minio_tpu.rpc import aio as _aio
    from minio_tpu.rpc.cluster import build_cluster_node, \
        derive_cluster_key
    from minio_tpu.rpc.transport import RPCClient, RPCRegistry
    from minio_tpu.s3 import sigv4 as _sigv4
    from minio_tpu.s3.client import S3Client
    from minio_tpu.s3.server import S3Server

    croot = os.path.join(workdir, "cfg_fd_cluster")
    key = derive_cluster_key(access, secret)
    servers, ports = [], []
    for _ in range(2):
        reg = RPCRegistry(key)
        srv = S3Server(None, access, secret, rpc_registry=reg)
        ports.append(srv.start("127.0.0.1", 0))
        servers.append((srv, reg))
    endpoints = [f"http://127.0.0.1:{p}{croot}/n{i}/d{d}"
                 for i, p in enumerate(ports) for d in (1, 2)]

    nodes = [None, None]
    errors: list = []

    def boot_node(i):
        try:
            srv, reg = servers[i]
            node = build_cluster_node(
                endpoints, "127.0.0.1", ports[i], access, secret,
                block_size=256 * 1024, registry=reg,
                format_timeout=30.0)
            srv.set_layer(node.layer)
            nodes[i] = node
        except Exception as e:  # pragma: no cover - bench plumbing
            errors.append(e)

    threads = [threading.Thread(target=boot_node, args=(i,))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    if errors or any(n is None for n in nodes):
        raise RuntimeError(f"cluster boot failed: {errors}")

    rcl = None
    prev_fabric = os.environ.get("MINIO_RPC_FABRIC")
    try:
        cl0 = S3Client("127.0.0.1", ports[0], access, secret)
        if cl0.make_bucket("fan").status != 200:
            raise RuntimeError("cluster make_bucket failed")
        body = os.urandom(1024 * 1024)

        def timed_put(conn, tag, i) -> float:
            path = f"/fan/{tag}-{i}"
            hdrs = _sigv4.sign_request(
                "PUT", path, "",
                {"host": f"127.0.0.1:{ports[0]}",
                 "content-length": str(len(body))},
                body, access, secret, "us-east-1")
            t0 = time.perf_counter()
            conn.request("PUT", path, body=body, headers=hdrs)
            r = conn.getresponse()
            r.read()
            if r.status != 200:
                raise RuntimeError(f"cluster PUT failed: {r.status}")
            return (time.perf_counter() - t0) * 1e3

        def fabric_put(conn, fabric, tag, i) -> float:
            os.environ["MINIO_RPC_FABRIC"] = fabric
            try:
                return timed_put(conn, tag, i)
            finally:
                if prev_fabric is None:
                    os.environ.pop("MINIO_RPC_FABRIC", None)
                else:
                    os.environ["MINIO_RPC_FABRIC"] = prev_fabric

        conn = _hc.HTTPConnection("127.0.0.1", ports[0], timeout=60)
        for i in range(2):  # warm both fabrics' pools + codec
            fabric_put(conn, "async", "wa", i)
            fabric_put(conn, "threaded", "wt", i)
        lat_a, lat_t, deltas = [], [], []
        for i in range(12):
            if i % 2 == 0:
                a = fabric_put(conn, "async", "fa", i)
                t = fabric_put(conn, "threaded", "ft", i)
            else:
                t = fabric_put(conn, "threaded", "ft", i)
                a = fabric_put(conn, "async", "fa", i)
            lat_a.append(a)
            lat_t.append(t)
            deltas.append(a - t)
        conn.close()
        rpc_p50_a, rpc_p50_t = stats.median(lat_a), stats.median(lat_t)
        rpc_p99_a = sorted(lat_a)[-1]
        rpc_p99_t = sorted(lat_t)[-1]
        rpc_delta_pct = stats.median(deltas) \
            / max(rpc_p50_t, 1e-9) * 100.0

        # -- census: 1k concurrent peer calls, ~zero new threads -----
        class _Nap:
            def rpc_nap(self, args, payload):
                time.sleep(args.get("sleepS", 0.02))
                return {}, b""

        servers[1][1].register("benchnap", _Nap())
        rcl = RPCClient("127.0.0.1", ports[1], key)
        # Pre-warm the in-process SERVER's bounded rpc worker pool to
        # its cap so pool spin-up can't masquerade as client threads.
        warm = [_aio.RPC_LOOP.submit(_aio.call_async(
            rcl, "benchnap", "nap", {"sleepS": 0.01}, timeout=30.0))
            for _ in range(64)]
        for f in warm:
            f.result(timeout=60)
        n = 1000
        threads_before = threading.active_count()
        futs = [_aio.RPC_LOOP.submit(_aio.call_async(
            rcl, "benchnap", "nap", {"sleepS": 0.02}, timeout=60.0))
            for _ in range(n)]
        peak = 0
        threads_at_peak = threads_before
        deadline = time.time() + 30
        while time.time() < deadline:
            cur = _aio.CENSUS.current()
            if cur > peak:
                peak = cur
                threads_at_peak = threading.active_count()
            if all(f.done() for f in futs):
                break
            time.sleep(0.002)
        fails = 0
        for f in futs:
            try:
                f.result(timeout=120)
            except Exception:
                fails += 1
        extra_threads = threads_at_peak - threads_before
        if fails:
            raise RuntimeError(f"{fails}/{n} census peer calls failed")
        if peak < 900:
            raise RuntimeError(
                f"census never saw the fleet in flight: peak {peak}")
        if extra_threads > 8:
            raise RuntimeError(
                f"async fabric grew {extra_threads} threads at {peak} "
                "in-flight peer calls — the zero-thread claim broke")
        return {
            "rpc_put_p50_async_ms": round(rpc_p50_a, 3),
            "rpc_put_p50_threaded_ms": round(rpc_p50_t, 3),
            "rpc_put_p99_async_ms": round(rpc_p99_a, 3),
            "rpc_put_p99_threaded_ms": round(rpc_p99_t, 3),
            # Median PAIRED delta over the threaded median — negative
            # = the async fabric is faster end-to-end.
            "rpc_put_paired_delta_pct": round(rpc_delta_pct, 2),
            "census_calls": n,
            "census_peak_inflight": peak,
            "threads_before": threads_before,
            "threads_at_peak": threads_at_peak,
            "extra_threads_at_peak": extra_threads,
        }
    finally:
        if prev_fabric is None:
            os.environ.pop("MINIO_RPC_FABRIC", None)
        else:
            os.environ["MINIO_RPC_FABRIC"] = prev_fabric
        if rcl is not None:
            rcl.close()
        for srv, _reg in servers:
            srv.stop()
        shutil.rmtree(croot, ignore_errors=True)


def bench_crash_recovery(np, workdir: str) -> dict:
    """PR-11 acceptance: a real `python -m minio_tpu server` is
    SIGKILL-ed mid-PUT-loop and restarted on the same disks; report
    (a) time-to-first-served-request after the restart exec, (b) the
    boot recovery sweep's duration + census, and (c) the `storage
    fsync=on` commit-path overhead as PAIRED on/off put_p50 deltas
    (PR-4's method — this VM drifts +/-20% on second timescales, so
    only paired deltas survive the noise)."""
    import signal
    import socket
    import subprocess
    import sys as _sys

    from minio_tpu.s3.admin_client import AdminClient
    from minio_tpu.s3.client import S3Client

    access, secret = "benchadmin", "benchadmin-secret"
    # Deliberately DISK-backed (unlike the other configs' tmpfs):
    # crash recovery is about durable media, and `fsync=on` measured
    # on tmpfs reads ~0 — the number would flatter the knob.
    root = tempfile.mkdtemp(prefix="minio-tpu-crash-")
    disks = [os.path.join(root, f"d{i}") for i in range(1, 7)]
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ, MINIO_ACCESS_KEY=access,
               MINIO_SECRET_KEY=secret, JAX_PLATFORMS="cpu",
               MINIO_RECOVERY_TMP_AGE="1",
               MINIO_CRAWLER_INTERVAL="3600",
               MINIO_HEAL_NEWDISK_INTERVAL="3600")
    log_path = os.path.join(root, "node.log")
    os.makedirs(root, exist_ok=True)

    def boot():
        log = open(log_path, "ab")
        p = subprocess.Popen(
            [_sys.executable, "-m", "minio_tpu", "server", *disks,
             "--address", f"127.0.0.1:{port}"],
            stdout=log, stderr=subprocess.STDOUT, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        log.close()
        return p

    def wait_serving(client, key, want, timeout=90.0):
        t0 = time.perf_counter()
        deadline = time.time() + timeout
        while time.time() < deadline:
            try:
                g = client.get_object("bench", key)
                if g.status == 200 and g.body == want:
                    return time.perf_counter() - t0
            except Exception:
                pass
            time.sleep(0.02)
        raise RuntimeError("restarted server never served")

    client = S3Client("127.0.0.1", port, access, secret)
    adm = AdminClient("127.0.0.1", port, access, secret)
    rng = np.random.default_rng(11)
    body = rng.integers(0, 256, 256 * 1024).astype(np.uint8).tobytes()
    proc = boot()
    try:
        wait_serving_boot = time.time() + 90
        while time.time() < wait_serving_boot:
            try:
                if client.make_bucket("bench").status in (200, 409):
                    break
            except Exception:
                pass
            time.sleep(0.1)  # every retry backs off, not just refusals
        client.put_object("bench", "anchor", body)

        # Kill -9 mid-PUT-loop: the loop runs in its own thread so the
        # SIGKILL lands while a PUT is actually in flight on the
        # commit path (a synchronous loop is ~always between requests
        # at these object sizes).
        counted = [0]
        halt = threading.Event()

        def put_loop():
            put_client = S3Client("127.0.0.1", port, access, secret)
            while not halt.is_set():
                try:
                    put_client.put_object(
                        "bench", f"k-{counted[0]}", body)
                    counted[0] += 1
                except Exception:
                    return  # the kill landed mid-request
        # mtpu-lint: disable=R1 -- bench driver thread, no request context to carry
        putter = threading.Thread(target=put_loop, daemon=True)
        putter.start()
        deadline = time.time() + 30
        while time.time() < deadline and counted[0] < 20:
            time.sleep(0.01)
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)
        halt.set()
        putter.join(timeout=10)
        killed_after = counted[0]
        time.sleep(1.2)  # orphans must clear the 1s recovery age gate

        t_restart = time.perf_counter()
        proc = boot()
        wait_serving(client, "anchor", body)
        ttfs_s = time.perf_counter() - t_restart
        rep = adm.recovery()
        sweep_ms = sum(s_.get("durationS", 0.0)
                       for s_ in rep["sweeps"]) * 1e3
        census = {k: sum(s_.get(k, 0) for s_ in rep["sweeps"])
                  for k in ("found", "cleaned", "stageFiles",
                            "journalReplayed")}
        census["requeued"] = sum(len(s_.get("requeued", []))
                                 for s_ in rep["sweeps"])

        # Paired fsync on/off PUT p50 (the toggle is one config write,
        # applied live through storage/xl.py set_fsync).
        lat_on: list = []
        lat_off: list = []
        for i in range(24):
            order = (True, False) if i % 2 == 0 else (False, True)
            for on in order:
                adm.set_config_kv(
                    f"storage fsync={'on' if on else 'off'}")
                t0 = time.perf_counter()
                r = client.put_object("bench", f"fs-{i}-{int(on)}",
                                      body)
                dt = time.perf_counter() - t0
                if r.status != 200:
                    raise RuntimeError(f"fsync PUT failed: {r.status}")
                (lat_on if on else lat_off).append(dt)
        adm.set_config_kv("storage fsync=off")
        p50_on = statistics.median(lat_on) * 1e3
        p50_off = statistics.median(lat_off) * 1e3
        delta = statistics.median(
            [(a - b) * 1e3 for a, b in zip(lat_on, lat_off)])
        return {
            "metric": "crash_recovery_time_to_first_served",
            "value": round(ttfs_s * 1e3, 1), "unit": "ms",
            "kill_after_puts": killed_after,
            "object_bytes": len(body),
            "workdir": "disk",
            "recovery_sweep_ms": round(sweep_ms, 2),
            "recovery_census": census,
            # storage fsync=on paired overhead (default-off ships; the
            # knob buys power-cut durability at this measured cost).
            "fsync_on_put_p50_ms": round(p50_on, 3),
            "fsync_off_put_p50_ms": round(p50_off, 3),
            "fsync_overhead_pct": round(
                delta / max(p50_off, 1e-9) * 100.0, 2),
        }
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGKILL)
            try:
                proc.wait(timeout=10)
            except Exception:
                pass
        shutil.rmtree(root, ignore_errors=True)


def bench_select_scan(np, workdir: str) -> dict:
    """Columnar S3 Select scan engine vs the row-engine oracle.

    Two paired fixtures (numeric-heavy 256MiB Parquet, string-heavy
    256MiB CSV), scan GiB/s both ways with BYTE-IDENTICAL payload
    verification at the paired point, a selectivity sweep
    (0.1%/10%/90% pass rates) on the columnar side, and a brownout
    phase: a capped `select` class flooded with scans must shed 503
    while paired fg PUT/GET p99 stays within noise of the no-scan
    baseline.  backend_mix is stamped by the config harness like
    every other config, so a host-mode run can't masquerade as a
    device number."""
    from minio_tpu.s3select import parquet as pqm
    from minio_tpu.s3select.message import decode_messages
    from minio_tpu.s3select.select import parse_request, run_select

    def _req(expr: str, inp: str) -> dict:
        from xml.sax.saxutils import escape
        xml = ("<SelectObjectContentRequest><Expression>"
               f"{escape(expr)}</Expression>"
               "<ExpressionType>SQL</ExpressionType>"
               f"<InputSerialization>{inp}</InputSerialization>"
               "<OutputSerialization><JSON/></OutputSerialization>"
               "</SelectObjectContentRequest>")
        return parse_request(xml.encode())

    def timed_select(req: dict, data: bytes, engine: str):
        os.environ["MINIO_SELECT_ENGINE"] = engine
        try:
            t0 = time.perf_counter()
            body = run_select(req, data)
            wall = time.perf_counter() - t0
        finally:
            os.environ.pop("MINIO_SELECT_ENGINE", None)
        msgs = decode_messages(body)
        if msgs and msgs[0]["headers"].get(":message-type") == "error":
            raise RuntimeError(f"select errored: {msgs[0]['headers']}")
        payload = b"".join(
            m["payload"] for m in msgs
            if m["headers"].get(":event-type") == "Records")
        return wall, payload

    out: dict = {"metric": "select_scan",
                 "unit": "columnar_over_row_speedup"}

    # -- numeric-heavy 256MiB Parquet (the acceptance config) ----------
    n = 8_388_608  # 4 x float64 columns = 256 MiB of data
    rng = np.random.default_rng(14)
    cols = [pqm.Column(c, pqm.DOUBLE, optional=False)
            for c in ("c0", "c1", "c2", "c3")]
    pdata = pqm.write_parquet_columns(
        cols, {c.name: rng.uniform(0.0, 1.0, n) for c in cols}, n)
    pq_gib = len(pdata) / (1 << 30)
    sweep = []
    row_wall = row_payload = None
    col_wall_paired = None
    for sel in (0.001, 0.1, 0.9):
        req = _req(f"SELECT c1 FROM S3Object WHERE c0 < {sel}",
                   "<Parquet/>")
        wall, payload = timed_select(req, pdata, "")
        if sel == 0.1:
            # Paired point: the row oracle runs the SAME query on the
            # SAME bytes immediately after, and the payloads must be
            # byte-identical (the differential suite, at full scale).
            # Row wall time is selectivity-independent (decode
            # dominates), so one row run prices all three points.
            col_wall_paired = wall
            row_wall, row_payload = timed_select(req, pdata, "row")
            if row_payload != payload:
                raise RuntimeError(
                    "columnar payload diverged from the row oracle "
                    f"({len(payload)} vs {len(row_payload)} bytes)")
        sweep.append({
            "selectivity": sel,
            "columnar_s": round(wall, 3),
            "columnar_gibs": round(pq_gib / wall, 3),
        })
    pq_speedup = row_wall / col_wall_paired
    out["value"] = round(pq_speedup, 2)
    out["parquet"] = {
        "bytes": len(pdata), "rows": n,
        "row_s": round(row_wall, 3),
        "row_gibs": round(pq_gib / row_wall, 4),
        "columnar_gibs": round(pq_gib / col_wall_paired, 3),
        "speedup": round(pq_speedup, 2),
        "selectivity_sweep": sweep,
    }
    if pq_speedup < 5.0:
        raise RuntimeError(
            f"select_scan speedup {pq_speedup:.2f}x < 5x on the "
            "numeric-heavy 256MiB Parquet config")

    # -- string-heavy CSV ----------------------------------------------
    # 96MiB, not 256: the ROW oracle needs ~4 min for 256MiB of CSV
    # (the whole reason this engine exists) and the paired run prices
    # both sides; the acceptance-gated 256MiB config is the Parquet
    # one above.
    words = np.asarray(["alphaville", "betatronic", "gammaray",
                        "deltaforce", "epsilonic", "zetapotential",
                        "etacarinae", "thetawaves"])
    rows_csv = 2_100_000   # ~96 MiB of ~48-byte lines
    w1 = words[rng.integers(0, len(words), rows_csv)]
    w2 = words[rng.integers(0, len(words), rows_csv)]
    nums = rng.integers(0, 100000, rows_csv).astype("U6")
    lines = np.char.add(np.char.add(np.char.add(np.char.add(
        w1, ","), nums), ","), w2)
    cdata = ("h1,h2,h3\n" + "\n".join(lines.tolist()) + "\n").encode()
    del lines, w1, w2, nums
    csv_gib = len(cdata) / (1 << 30)
    creq = _req("SELECT h2 FROM S3Object WHERE h1 LIKE 'gamma%' "
                "AND h2 > 90000",
                "<CSV><FileHeaderInfo>USE</FileHeaderInfo></CSV>")
    c_wall, c_payload = timed_select(creq, cdata, "")
    r_wall, r_payload = timed_select(creq, cdata, "row")
    if r_payload != c_payload:
        raise RuntimeError("CSV columnar payload diverged from the "
                           "row oracle")
    out["csv"] = {
        "bytes": len(cdata), "rows": rows_csv,
        "row_s": round(r_wall, 3),
        "row_gibs": round(csv_gib / r_wall, 4),
        "columnar_s": round(c_wall, 3),
        "columnar_gibs": round(csv_gib / c_wall, 3),
        "speedup": round(r_wall / c_wall, 2),
    }
    del cdata

    # -- brownout: capped select class vs fg PUT/GET -------------------
    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.obs.metrics2 import METRICS2
    from minio_tpu.s3.client import S3Client
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl import XLStorage
    root = os.path.join(workdir, "cfgsel")
    disks = [XLStorage(os.path.join(root, f"disk{i}"))
             for i in range(6)]
    layer = ErasureObjects(disks, 4, 2, block_size=1024 * 1024)
    srv = S3Server(layer, "benchadmin", "benchadmin-secret")
    port = srv.start()
    try:
        # The server boot kicks the background probe ladder (RS rungs,
        # jit compiles, select probes); on a 2-core box it would crush
        # the paired p99 measurement below — drain it first.
        from minio_tpu.ops.autotune import AUTOTUNE as _AT
        _AT.ensure_probed(background=False)
        client = S3Client("127.0.0.1", port, "benchadmin",
                          "benchadmin-secret")
        client.make_bucket("selbench")
        # a 2MiB slice of the parquet fixture as the scan target
        small_n = 65_536
        sdata = pqm.write_parquet_columns(
            cols, {c.name: rng.uniform(0.0, 1.0, small_n)
                   for c in cols}, small_n)
        client.put_object("selbench", "t.parquet", sdata)
        body = rng.integers(0, 256, 1024 * 1024).astype(
            np.uint8).tobytes()
        for i in range(4):
            client.put_object("selbench", f"warm-{i}", body)
        sel_xml = (
            "<SelectObjectContentRequest><Expression>"
            "SELECT c1 FROM S3Object WHERE c0 &lt; 0.5"
            "</Expression><ExpressionType>SQL</ExpressionType>"
            "<InputSerialization><Parquet/></InputSerialization>"
            "<OutputSerialization><JSON/></OutputSerialization>"
            "</SelectObjectContentRequest>").encode()

        def fg_lat(tag: str, ops: int = 40):
            put, get = [], []
            for i in range(ops):
                t0 = time.perf_counter()
                r = client.put_object("selbench", f"{tag}-{i}", body)
                put.append(time.perf_counter() - t0)
                if r.status != 200:
                    raise RuntimeError(f"PUT {r.status}")
                t0 = time.perf_counter()
                r = client.get_object("selbench", f"{tag}-{i}")
                get.append(time.perf_counter() - t0)
                if r.status != 200:
                    raise RuntimeError(f"GET {r.status}")
            return put, get

        def p99(xs):
            return sorted(xs)[max(0, int(len(xs) * 0.99) - 1)] * 1e3

        put_off1, get_off1 = fg_lat("off1")
        srv.config.set_kv("api requests_max_select=1 "
                          "requests_deadline=250ms")
        stop = threading.Event()
        shed = [0]
        okc = [0]

        def scan_forever():
            sc = S3Client("127.0.0.1", port, "benchadmin",
                          "benchadmin-secret")
            while not stop.is_set():
                r = sc.request("POST", "/selbench/t.parquet",
                               query="select=&select-type=2",
                               body=sel_xml)
                if r.status == 503:
                    shed[0] += 1
                elif r.status == 200:
                    okc[0] += 1

        threads = [threading.Thread(target=scan_forever, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.5)  # flood reaches the cap
        put_on, get_on = fg_lat("on")
        stop.set()
        for t in threads:
            t.join(timeout=30)
        srv.config.set_kv("api requests_max_select=0 "
                          "requests_deadline=10s")
        put_off2, get_off2 = fg_lat("off2")
        put_off = put_off1 + put_off2
        get_off = get_off1 + get_off2
        if shed[0] < 1:
            raise RuntimeError(
                "capped select class never shed under the scan flood "
                f"(ok={okc[0]})")
        put_ratio = p99(put_on) / max(p99(put_off), 1e-9)
        get_ratio = p99(get_on) / max(p99(get_off), 1e-9)
        out["brownout"] = {
            "select_cap": 1, "scan_threads": 4,
            "select_ok": okc[0], "select_shed_503": shed[0],
            "fg_put_p99_off_ms": round(p99(put_off), 2),
            "fg_put_p99_on_ms": round(p99(put_on), 2),
            "fg_put_p99_ratio": round(put_ratio, 3),
            "fg_get_p99_off_ms": round(p99(get_off), 2),
            "fg_get_p99_on_ms": round(p99(get_on), 2),
            "fg_get_p99_ratio": round(get_ratio, 3),
            "select_sheds_total": METRICS2.get(
                "minio_tpu_v2_qos_shed_total",
                {"class": "select", "reason": "wait-deadline"}),
        }
        # Two python processes' worth of work on 2 cores: allow real
        # scheduling noise, catch real starvation.
        if put_ratio > 3.0 or get_ratio > 3.0:
            raise RuntimeError(
                "fg p99 degraded past noise under the capped scan "
                f"flood (put x{put_ratio:.2f}, get x{get_ratio:.2f})")
        out["fg_p99_ratio"] = round(max(put_ratio, get_ratio), 3)
    finally:
        srv.stop()
        shutil.rmtree(root, ignore_errors=True)

    from minio_tpu.ops.autotune import AUTOTUNE
    out["select_plan"] = AUTOTUNE.plan_compact().get("select_scan", {})
    return out


# --- config: regen_repair — RS vs REGEN heal repair traffic -----------------


def bench_regen_repair(np, workdir: str) -> dict:
    """Paired RS-vs-REGEN heal of the SAME dataset on a 4+2 layout:
    identical objects stored under both classes, the same single-disk
    shard loss inflicted on each, and each class healed separately so
    the repair-traffic ledger (erasure/regen/repair.REPAIR_BYTES)
    yields per-mode bytes moved (net + disk) and per-mode heal GiB/s.
    The headline value is the rs/regen disk-traffic ratio — the
    repair-by-transfer construction predicts B/d (RS moves ~1 block
    per repaired block, regen moves d stripe rows of block/B bytes):
    for 4+2, B=14, d=5, exactly 2.8x.  The ratio is measured on one
    box so VM drift cancels (host-mode caveat: absolute GiB/s is
    whatever lane the autotuner picked — trust the paired ratio,
    which counts bytes, not seconds)."""
    from minio_tpu.erasure.engine import ErasureObjects
    from minio_tpu.erasure.regen.repair import REPAIR_BYTES
    from minio_tpu.storage.metadata import REGEN_ALGORITHM
    from minio_tpu.storage.xl import XLStorage

    root = os.path.join(workdir, "cfg-regen")
    n_objects, obj_bytes = 4, 24 * 1024 * 1024  # 96 MiB per class
    rng = np.random.default_rng(11)
    try:
        roots = [os.path.join(root, f"disk{i}") for i in range(6)]
        disks = [XLStorage(r) for r in roots]
        eng = ErasureObjects(disks, 4, 2, block_size=1024 * 1024)
        eng.make_bucket("bench")
        for i in range(n_objects):
            body = rng.integers(0, 256, obj_bytes).astype(
                np.uint8).tobytes()
            eng.put_object("bench", f"rs-{i}", body)
            eng.put_object("bench", f"regen-{i}", body,
                           algorithm=REGEN_ALGORITHM)

        def lose_and_heal(prefix: str) -> tuple[dict, float]:
            for i in range(n_objects):
                shutil.rmtree(os.path.join(roots[0], "bench",
                                           f"{prefix}-{i}"))
            REPAIR_BYTES.reset()
            t0 = time.perf_counter()
            for i in range(n_objects):
                res = eng.healer.heal_object("bench", f"{prefix}-{i}")
                if not res.healed_disks:
                    raise RuntimeError(
                        f"heal of {prefix}-{i} repaired nothing")
            dt = time.perf_counter() - t0
            return REPAIR_BYTES.snapshot(), dt

        rs_bytes, rs_dt = lose_and_heal("rs")
        regen_bytes, regen_dt = lose_and_heal("regen")
        total = n_objects * obj_bytes
        ratio_disk = rs_bytes["rs"]["disk"] / regen_bytes["regen"]["disk"]
        ratio_net = rs_bytes["rs"]["net"] / regen_bytes["regen"]["net"]
        if min(ratio_disk, ratio_net) < 2.0:
            raise RuntimeError(
                f"regen repair reduction below 2x (disk {ratio_disk:.2f}, "
                f"net {ratio_net:.2f})")
        return {"metric": "regen_repair", "layout": "4+2",
                "value": round(ratio_disk, 3), "unit": "x_less_disk",
                "repair_bytes": {"rs": rs_bytes["rs"],
                                 "regen": regen_bytes["regen"]},
                "ratio_net": round(ratio_net, 3),
                "rs_heal_gibps": round(total / rs_dt / (1 << 30), 3),
                "regen_heal_gibps": round(
                    total / regen_dt / (1 << 30), 3),
                "total_bytes_per_class": total,
                "note": "ratio counts bytes (drift-free); GiB/s is "
                        "host-lane dependent"}
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main() -> None:
    import numpy as np

    errors: dict[str, str] = {}

    # This process owns the chip for the whole run (module docstring).
    import jax
    from minio_tpu.utils import compile_cache
    compile_cache.configure()
    devs = jax.devices()
    if not any(d.platform != "cpu" for d in devs):
        print(f"bench.py needs an accelerator; jax sees only "
              f"{devs[0].platform}", file=sys.stderr)
        sys.exit(1)

    out: dict = {"metric": "rs_encode+decode_8+4_1MiB_GiB_per_s_per_chip",
                 "value": 0.0, "unit": "GiB/s", "vs_baseline": 0.0,
                 "baseline": "host codec (C++ nibble-shuffle native/rs.cc "
                             "when built; stand-in for the reference's "
                             "AVX2 reedsolomon)"}

    out["device"] = {"platform": devs[0].platform,
                     "kind": devs[0].device_kind, "count": len(devs)}

    # The baseline: the engine's REAL host lane (native C++ codec
    # through the same folded applies the serving path uses), not
    # jit-on-CPU. Never published under the headline metric.
    _progress("host-lane baseline")
    host_native = 0.0
    try:
        host_native = bench_host_native_north_star(np)
    except Exception as exc:  # noqa: BLE001
        errors["north_star_host"] = f"{type(exc).__name__}: {exc}"
    out["host_native_GiBs"] = round(host_native, 3)

    # The headline and the device-pinned configs, in THIS process.
    _progress("device bench (in-process)")
    from tools import device_bench
    try:
        device_res = device_bench.run()
    except Exception as exc:  # noqa: BLE001
        device_res = {"ok": False,
                      "error": f"{type(exc).__name__}: {exc}"}
    if not device_res.get("ok"):
        errors["device"] = str(device_res.get("error")
                               or device_res.get("errors"))
    ns = device_res.get("north_star", {})
    if ns.get("value"):
        out["value"] = ns["value"]
        out["kernel"] = ns.get("kernel")
        base = ns.get("host_native_GiBs") or host_native
        out["vs_baseline"] = round(ns["value"] / max(base, 1e-9), 2)
    out["device_bench"] = device_res

    # The engine configs under the codec plan's own lane choice
    # (device_asserted=False: each record's backend_mix says what ran).
    # Workdir on tmpfs when available: the VM disk's writeback
    # throttling swings single-shard writes 2-12ms run to run, drowning
    # the codec/engine signal these configs track (labeled so the
    # record says what was measured).
    workdir = tempfile.mkdtemp(
        prefix="minio-tpu-bench-",
        dir="/dev/shm" if os.path.isdir("/dev/shm") else None)
    out["workdir"] = ("tmpfs" if workdir.startswith("/dev/shm")
                      else "disk")
    # Which data-plane pipeline (utils/pipeline.py PIPE_STATS name) each
    # config exercises; its overlap factor (stage busy seconds / wall
    # seconds, > 1.0 = stages genuinely overlapped) is attached to the
    # config record so bench records track pipelining
    # regressions. put_p50's 1MiB objects fit one encode batch, so its
    # pipeline never engages and no factor is reported there.
    from minio_tpu.utils.pipeline import PIPE_STATS, PipelineStats
    # Silent-degradation tripwires per config: slowlog captures during
    # the run plus the drive-health suspect/faulty census afterwards —
    # a future regression that makes a config quietly slow (or drags
    # one disk) shows up in the BENCH record, not just in the value.
    from minio_tpu.obs.drivemon import DRIVEMON
    from minio_tpu.obs.kernprof import KERNPROF
    from minio_tpu.obs.slowlog import SLOWLOG
    from minio_tpu.obs.watchdog import WATCHDOG
    config_pipeline = {"put_p50": "put", "multipart": "put",
                       "get_2lost": "get", "heal": "heal"}
    configs: list[dict] = []
    for name, fn in (("put_p50", lambda: bench_put_p50(np, workdir)),
                     ("codec_autotune",
                      lambda: bench_codec_autotune(np)),
                     ("encode_verify",
                      lambda: bench_encode_verify(np, False)),
                     ("multipart", lambda: bench_multipart(np, workdir)),
                     ("get_2lost",
                      lambda: bench_get_with_loss(np, workdir, False)),
                     ("heal", lambda: bench_heal(np, workdir, False)),
                     ("degraded_tail",
                      lambda: bench_degraded_tail(np, workdir)),
                     ("qos_brownout",
                      lambda: bench_qos_brownout(np, workdir)),
                     ("hot_get",
                      lambda: bench_hot_get(np, workdir)),
                     ("noisy_neighbor",
                      lambda: bench_noisy_neighbor(np, workdir)),
                     ("front_door",
                      lambda: bench_front_door(np, workdir)),
                     ("loop_health",
                      lambda: bench_loop_health(np, workdir)),
                     ("crash_recovery",
                      lambda: bench_crash_recovery(np, workdir)),
                     ("select_scan",
                      lambda: bench_select_scan(np, workdir)),
                     ("regen_repair",
                      lambda: bench_regen_repair(np, workdir))):
        _progress(f"config {name}")
        pipe = config_pipeline.get(name)
        factor_box: dict = {}

        def run_measured(fn=fn, pipe=pipe, factor_box=factor_box):
            # Snapshot per ATTEMPT: a failed first try's partial
            # pipeline stats must not pollute the successful run's
            # overlap factor. The drive monitor RESETS per attempt —
            # a suspect frozen from an earlier config's destroyed
            # disks must not leak into this config's tripwire.
            DRIVEMON.reset()
            # The watchdog resets with it: a firing alert frozen from
            # an earlier config's deliberate faults must not leak into
            # this config's alerts_fired tripwire.
            WATCHDOG.reset()
            before = PIPE_STATS.snapshot()
            slow_before = SLOWLOG.total
            mix_before = KERNPROF.mix_snapshot()
            out = fn()
            if pipe is not None:
                factor_box["factor"] = PipelineStats.overlap_factor(
                    before, PIPE_STATS.snapshot(), pipe)
            factor_box["slowlog"] = SLOWLOG.total - slow_before
            factor_box["mix"] = _backend_mix(mix_before,
                                             KERNPROF.mix_snapshot())
            return out

        res, err = _retrying(run_measured, name, attempts=2,
                             base_sleep=1.0)
        if res is not None:
            res["device_asserted"] = False
            if factor_box.get("factor") is not None:
                res["overlap_factor"] = round(factor_box["factor"], 3)
            res["slowlog_entries"] = factor_box.get("slowlog", 0)
            # Which dispatch backend actually did this config's math
            # (kernprof byte fractions): a host-mode run can never
            # masquerade as a device number.
            res["backend_mix"] = factor_box.get("mix", {})
            # The codec dispatch plan in force when this config ran —
            # the lane story behind the backend_mix fractions.
            from minio_tpu.ops.autotune import AUTOTUNE as _AT
            res.setdefault("codec_plan", _AT.plan_compact())
            suspect, faulty = DRIVEMON.counts()
            res["drive_suspect"] = suspect
            res["drive_faulty"] = faulty
            # Watchdog tripwire (like drive_suspect): firing
            # transitions during this config. qos_brownout fires the
            # shed built-in BY DESIGN and asserts it resolves; any
            # other config alerting is a silent regression surfaced
            # in the BENCH record.
            res["alerts_fired"] = WATCHDOG.fired_total
            configs.append(res)
        else:
            errors[name] = err or "unknown"
    shutil.rmtree(workdir, ignore_errors=True)

    from minio_tpu.ops import batching
    out["configs"] = configs
    out["stats"] = batching.STATS.snapshot()
    # Whole-run dispatch honesty stamp: byte fractions per kernprof
    # backend plus the backend health states at exit.
    out["backend_mix"] = _backend_mix({}, KERNPROF.mix_snapshot())
    out["kernel_backends"] = {
        b: info["state"]
        for b, info in KERNPROF.snapshot()["backends"].items()}
    # Whole-run codec-plan stamp (next to backend_mix): which lane the
    # measured planner routed each (kernel, bucket) to by run end.
    from minio_tpu.ops.autotune import AUTOTUNE
    out["codec_plan"] = AUTOTUNE.plan_compact()
    # n_devices-aware scaling curve ({} on a single-device box).
    scaling = bench_north_star_scaling(np)
    if scaling:
        out["north_star_scaling"] = scaling
    if errors:
        out["errors"] = errors
    print(json.dumps(out))
    sys.exit(1 if errors else 0)


if __name__ == "__main__":
    main()
