"""The heal's reconstruct (ops/batching.reconstruct_rows): only the
shards a heal writes are solved, each into one contiguous row, reading
the survivors' sub-blocks where they lie. Every case is held to the
rows reconstruct_blocks(want_all=True) keeps and to the rs_cpu encode
of each block."""

import numpy as np
import pytest

from minio_tpu import native
from minio_tpu.obs.metrics2 import METRICS2
from minio_tpu.ops import batching, rs_cpu

CODES = [(4, 2), (8, 4), (12, 4)]


def _lost_cases():
    for k, m in CODES:
        n = k + m
        for j in range(n):
            yield k, m, (j,)
        yield k, m, (0, k)          # a data and a parity shard
        yield k, m, (1, k - 1)      # two data shards
        yield k, m, (k, n - 1)      # two parity shards


LOST = list(_lost_cases())


def _encoded(rng, k, m, S):
    full = np.zeros((k + m, S), dtype=np.uint8)
    full[:k] = rng.integers(0, 256, (k, S), dtype=np.uint8)
    rs_cpu.encode(full, k, m)
    return full


def _blocks(k, m, lost, sizes, *, read_k, seed=0):
    """Blocks of shard lengths `sizes` as a heal sees them: each
    survivor a view into one stream per shard (as read_all returns it),
    `lost` None, and with `read_k` only k survivors read (the rest None
    too). Returns (blocks, the rs_cpu-encoded truth per block)."""
    rng = np.random.default_rng(seed)
    n = k + m
    truth = [_encoded(rng, k, m, S) for S in sizes]
    survivors = [j for j in range(n) if j not in lost]
    if read_k:
        survivors = sorted(rng.permutation(survivors)[:k].tolist())
    streams = {j: b"".join(t[j].tobytes() for t in truth)
               for j in survivors}
    blocks, off = [], 0
    for S in sizes:
        sh = [None] * n
        for j in survivors:
            sh[j] = np.frombuffer(memoryview(streams[j])[off:off + S],
                                  dtype=np.uint8)
        blocks.append(sh)
        off += S
    return blocks, truth


@pytest.fixture(params=["native", "numpy"])
def host_lane(request, monkeypatch):
    """The native in-place lane, or the one-gather fallback a host
    without the native library runs."""
    if request.param == "native":
        if native.get_lib() is None:
            pytest.skip("native lib unavailable (no compiler)")
    else:
        monkeypatch.setattr(native, "get_lib", lambda: None)
    return request.param


def _check(rows, blocks, truth, k, m, lost):
    want = np.stack([np.concatenate([t[j] for t in truth]) for j in lost])
    assert rows.shape == want.shape and rows.flags.c_contiguous
    np.testing.assert_array_equal(rows, want)
    full = batching.reconstruct_blocks(blocks, k, m, want_all=True,
                                       use_device=lambda n: False)
    kept = np.stack([np.concatenate([np.asarray(b[j]) for b in full])
                     for j in lost])
    np.testing.assert_array_equal(rows, kept)


@pytest.mark.parametrize("read_k", [False, True],
                         ids=["all_survivors", "k_read"])
@pytest.mark.parametrize("k,m,lost", LOST)
def test_rows_match_golden(host_lane, k, m, lost, read_k):
    sizes = [97, 97, 97, 40]  # odd lengths, a tail block of its own
    blocks, truth = _blocks(k, m, lost, sizes, read_k=read_k)
    rows = batching.reconstruct_rows(blocks, k, m, lost,
                                     use_device=lambda n: False)
    _check(rows, blocks, truth, k, m, lost)


@pytest.mark.parametrize("k,m,lost", [(4, 2, (1,)), (12, 4, (7,)),
                                      (8, 4, (3, 9))])
def test_rows_device_lane(k, m, lost):
    """The device lane (CPU jax here) gathers once and solves only the
    wanted rows, byte-identical to the host lanes."""
    blocks, truth = _blocks(k, m, lost, [128, 128, 64], read_k=True)
    batching.STATS.reset()
    rows = batching.reconstruct_rows(blocks, k, m, lost,
                                     use_device=lambda n: True)
    assert batching.STATS.snapshot()["tpu_dispatches"] == 2  # 2 runs
    _check(rows, blocks, truth, k, m, lost)


@pytest.mark.parametrize("tail", [False, True], ids=["uniform", "tail"])
def test_one_dispatch_per_run(host_lane, tail):
    """Blocks sharing survivors and length are one dispatch; a tail
    block of another length, or a block whose survivors differ, starts
    a run of its own, written at its offset of the same rows."""
    k, m, lost = 8, 4, (5,)
    sizes = [300] * 5 + ([123] if tail else [])
    blocks, truth = _blocks(k, m, lost, sizes, read_k=False)
    # A block whose shard 0 is also unread: another survivor set.
    blocks[2] = [None] + blocks[2][1:]
    batching.STATS.reset()
    rows = batching.reconstruct_rows(blocks, k, m, lost,
                                     use_device=lambda n: False)
    s = batching.STATS.snapshot()
    assert s["cpu_dispatches"] == (4 if tail else 3)
    assert s["coalesced_requests"] == 2 + 2  # runs [0, 1] and [3, 4]
    _check(rows, blocks, truth, k, m, lost)


def test_copy_counter_reads_in_place_lane(host_lane):
    """kernel_host_copy_bytes_total{kernel="rs_decode"}: 0 where the
    native kernel reads the survivors in place, every survivor byte the
    solve read where they are gathered; kernel_bytes_total gains the
    same survivor bytes on either lane."""
    k, m, lost = 12, 4, (7,)
    sizes = [1000] * 6
    blocks, truth = _blocks(k, m, lost, sizes, read_k=True)
    copy = ("minio_tpu_v2_kernel_host_copy_bytes_total",
            {"kernel": "rs_decode"})
    done = ("minio_tpu_v2_kernel_bytes_total",
            {"kernel": "rs_decode", "device": "host"})
    c0, b0 = METRICS2.get(*copy), METRICS2.get(*done)
    batching.reconstruct_rows(blocks, k, m, lost,
                              use_device=lambda n: False)
    survivor_bytes = k * sum(sizes)
    assert METRICS2.get(*done) - b0 == survivor_bytes
    assert METRICS2.get(*copy) - c0 == (
        0 if host_lane == "native" else survivor_bytes)


def test_wanted_shard_present_or_too_few_survivors():
    k, m = 4, 2
    blocks, _ = _blocks(k, m, (0,), [64], read_k=False)
    with pytest.raises(ValueError):
        batching.reconstruct_rows(blocks, k, m, (1,),
                                  use_device=lambda n: False)
    blocks[0][1] = blocks[0][2] = None
    with pytest.raises(batching.ReconstructError):
        batching.reconstruct_rows(blocks, k, m, (0,),
                                  use_device=lambda n: False)


def test_device_fault_falls_back_to_host_rows():
    """A failed device dispatch (the `kernel` fault rule) falls back to
    the host lane, byte-exact; a pinned backend raises instead."""
    from minio_tpu.faultinject import FAULTS
    from minio_tpu.obs.kernprof import KERNPROF
    k, m, lost = 8, 4, (2,)
    blocks, truth = _blocks(k, m, lost, [256, 256], read_k=True)
    KERNPROF.reset()
    FAULTS.load_plan({"rules": [{"kind": "kernel", "target": "rs_decode"}]})
    try:
        batching.STATS.reset()
        rows = batching.reconstruct_rows(blocks, k, m, lost,
                                         use_device=lambda n: True)
        s = batching.STATS.snapshot()
        assert (s["tpu_dispatches"], s["cpu_dispatches"]) == (0, 1)
        assert FAULTS.snapshot()["rules"][0]["fired"] == 1
        _check(rows, blocks, truth, k, m, lost)
        with pytest.raises(Exception):
            batching.reconstruct_rows(blocks, k, m, lost,
                                      use_device=lambda n: True,
                                      device_fallback=False)
    finally:
        FAULTS.clear()
        KERNPROF.reset()
