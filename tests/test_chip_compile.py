"""Compile the main-path kernels at serving widths for a DESCRIBED TPU
v5e (no chip attached): what the chip's compiler would refuse fails
here, at no chip time (on-chip-measurement guide §2, rehearsal 3).

The interpret-mode tests (tests/test_rs_pallas.py) cannot see these
faults: the flattened `(B*k, S)` blocking passed every one of them and
was refused by the TPU lowering at every serving shape with B > 1.

Rules this file keeps (guide §2): the topology is described inside a
module-scoped fixture that skips when it cannot be; nothing touches
`topologies` at import, in a skipif or in parametrize; all of these
tests live in this ONE file; the persistent compile cache is off
around them; a compile that passes is not a chip run.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

MiB = 1 << 20
BLOCK = 10 * MiB  # erasure/codec.BLOCK_SIZE


def _shard_len(k: int) -> int:
    return -(-BLOCK // k)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-chip executable is written to the persistent cache
    # but cannot be read back without a chip: keep it out.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices).reshape(2, 2), ("blocks", "lanes"))


def _compile_rs(sharding, B, k, r, S, with_data):
    from minio_tpu.ops import rs_pallas
    bm = jax.ShapeDtypeStruct((8 * r, 8 * k), jnp.float32,
                              sharding=sharding)
    x = jax.ShapeDtypeStruct((B, k, S), jnp.uint8, sharding=sharding)
    c = rs_pallas.rs_gf_apply.lower(bm, x, r=r, k=k,
                                   with_data=with_data).compile()
    assert "tpu_custom_call" in c.as_text()  # the Mosaic kernel is in
    return c


# (k, m, B): encode at the default 10 MiB block, B > 1. 12+4's shard
# length (873814) is off the 128-lane grid: the in-jit pad path.
@pytest.mark.parametrize("k,m,B", [(4, 2, 8), (8, 4, 7), (8, 4, 16),
                                   (12, 4, 3), (16, 4, 16)])
def test_rs_encode_compiles_for_v5e(one_chip, k, m, B):
    c = _compile_rs(one_chip, B, k, m, _shard_len(k), True)
    out = c.memory_analysis().output_size_in_bytes
    assert out >= B * (k + m) * _shard_len(k)


# The north-star 1 MiB block (S = 1 MiB / k; 12+4's 87382 is off the
# lane grid) at a coalesced window of 16 PUTs.
@pytest.mark.parametrize("k,m", [(4, 2), (8, 4), (12, 4), (16, 4)])
def test_rs_encode_1mib_block_window_compiles_for_v5e(one_chip, k, m):
    _compile_rs(one_chip, 16, k, m, -(-MiB // k), True)


# The autotuner's own probe ladder (ops/autotune._PROBE_RUNGS): a rung
# the compiler refuses leaves the device lane unmeasured at boot.
@pytest.mark.parametrize("S", [1024, 16384, 65536, 262144])
def test_rs_probe_rungs_compile_for_v5e(one_chip, S):
    _compile_rs(one_chip, 8, 4, 2, S, False)


@pytest.mark.parametrize("S", [1024, 16384, 65536, 262144])
def test_rs_probe_rungs_compile_sharded_on_2x2(mesh, S):
    """On a serving mesh the ladder's device rung places its (8, 4, S)
    batch as a served batch is placed (PR 28): both axes divide, one
    local kernel per chip over (4, 4, S/2)."""
    from minio_tpu.ops import rs_pallas
    from minio_tpu.parallel.mesh import batch_sharding, replicated
    bm = jax.ShapeDtypeStruct((8 * 2, 8 * 4), jnp.float32,
                              sharding=replicated(mesh))
    x = jax.ShapeDtypeStruct((8, 4, S), jnp.uint8,
                             sharding=batch_sharding(mesh, 8, S))
    txt = jax.jit(lambda bm, x: rs_pallas._apply_sharded(
        mesh, bm, x, interpret=False, with_data=False)).lower(
            bm, x).compile().as_text()
    assert "tpu_custom_call" in txt and "all-gather" not in txt


@pytest.mark.parametrize("B,S", [(6, _shard_len(8)), (16, MiB // 8)])
@pytest.mark.parametrize("r", [1, 2])
def test_rs_reconstruct_8_4_compiles_for_v5e(one_chip, r, B, S):
    """Any-mask reconstruct (GET with loss): r rows rebuilt from 8, at
    the 10 MiB block (a heal group of 6) and the 1 MiB block."""
    _compile_rs(one_chip, B, 8, r, S, False)


def test_rs_heal_16_4_compiles_for_v5e(one_chip):
    _compile_rs(one_chip, 7, 16, 4, _shard_len(16), False)


def test_rs_smoke_shape_compiles_for_v5e(one_chip):
    """rs_pallas.smoke()'s shape (B > 1, k and r below the sublane
    tile, S off the lane grid) — the gate rs_tpu._pallas_enabled runs."""
    _compile_rs(one_chip, 3, 4, 2, 2 * 128 + 44, False)


@pytest.mark.parametrize("B,k,m", [(8, 8, 4), (7, 8, 4)])
def test_rs_shard_map_compiles_on_2x2(mesh, B, k, m):
    """The serving-mesh form: one local packed kernel per chip, no
    collectives. B=8 shards both axes; B=7 only the lanes."""
    from minio_tpu.ops import rs_pallas
    from minio_tpu.parallel.mesh import batch_sharding, replicated
    S = _shard_len(k)
    bm = jax.ShapeDtypeStruct((8 * m, 8 * k), jnp.float32,
                              sharding=replicated(mesh))
    x = jax.ShapeDtypeStruct((B, k, S), jnp.uint8,
                             sharding=batch_sharding(mesh, B, S))
    c = jax.jit(lambda bm, x: rs_pallas._apply_sharded(
        mesh, bm, x, interpret=False, with_data=True)).lower(
            bm, x).compile()
    txt = c.as_text()
    assert "tpu_custom_call" in txt
    for coll in ("all-gather", "all-reduce", "collective-permute",
                 "all-to-all"):
        assert coll not in txt


def _hh256_args(sharding_of, B, L):
    n, rem = divmod(L, 32)
    shapes = (((B, n, 8), 3), ((B, 8), 2), ((16, 2), 0))
    return n, rem, [jax.ShapeDtypeStruct(shape, jnp.uint32,
                                         sharding=sharding_of(rows))
                    for shape, rows in shapes]


def _assert_packet_loop_is_one_kernel(txt: str, n: int):
    """The Mosaic kernel is in, and no HLO loop runs once per packet:
    the only `while` left is the ten finalisation rounds."""
    assert "tpu_custom_call" in txt
    assert txt.count(" while(") <= 1
    assert f"constant({n})" not in txt  # the parent's loop bound


@pytest.mark.parametrize("B,L", [(16, _shard_len(8)),
                                 (16, _shard_len(12)),
                                 (16, -(-4 * MiB // 12)),
                                 (128, MiB // 8),
                                 (64, -(-MiB // 12)),
                                 (8, _shard_len(4)),
                                 (256, MiB // 8),
                                 (1, 32 * 3)])
def test_hh256_compiles_for_v5e(one_chip, B, L):
    """Device HighwayHash at real bitrot sub-block lengths: 8+4's
    1310720 (len % 32 == 0) and 12+4's 873814 (len % 32 == 22, the
    remainder packet; 27306 packets, not a multiple of the block) at
    the 10 MiB block, and 349526 (% 32 == 22 too), the 4 MiB tail block
    of a 64 MiB multipart part at 12+4 (PR 32); 131072 and 87382 (len % 32 == 22) at the 1 MiB
    block; 4+2's 2621440 (81920 packets); 256 rows (two lane tiles);
    one row of fewer packets than the unroll. Each build holds the
    Pallas kernel and no per-packet `while`."""
    from minio_tpu.ops import hh256_tpu
    n, rem, args = _hh256_args(lambda rows: one_chip, B, L)
    c = hh256_tpu.hh256_rows.lower(*args, n_packets=n, rem=rem).compile()
    assert c.memory_analysis().argument_size_in_bytes >= B * n * 32
    _assert_packet_loop_is_one_kernel(c.as_text(), n)


def test_hh256_shard_map_compiles_on_2x2(mesh):
    """The serving-mesh form of hash_chunks: rows over all four chips,
    one local kernel each, no collectives."""
    from minio_tpu.ops import hh256_tpu
    from minio_tpu.parallel.mesh import replicated, rows_sharding
    B = 16
    n, rem, args = _hh256_args(
        lambda rows: rows_sharding(mesh, B, rows) if rows
        else replicated(mesh), B, _shard_len(12))
    txt = hh256_tpu.hh256_rows.lower(
        *args, n_packets=n, rem=rem, mesh=mesh).compile().as_text()
    _assert_packet_loop_is_one_kernel(txt, n)
    for coll in ("all-gather", "all-reduce", "collective-permute",
                 "all-to-all"):
        assert coll not in txt
