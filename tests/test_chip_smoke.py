"""chip_smoke.py's CPU rehearsal and the honesty of the device lane:
a check must never pass without the chip, and a kernel the compiler
refuses must be reported as a kernel failure naming its shape."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_tiny(*extra: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the smoke sets its own where needed
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--tiny",
         *extra], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=600)


def _verdict(p: subprocess.CompletedProcess) -> dict:
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_chip_smoke_tiny_rehearsal_never_claims_a_tpu():
    """Every phase and byte comparison runs on the CPU; the device
    assertions are evaluated and reported as not met; the verdict is
    ok: false with the CPU named, and the exit code is nonzero."""
    p = _run_tiny()
    assert p.returncode != 0, p.stdout[-2000:]
    assert '"platform": "tpu"' not in p.stdout
    assert "FAILED" not in p.stdout, p.stdout[-3000:]
    v = _verdict(p)
    assert v["ok"] is False
    assert v["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    for phase in ("boot", "load (c) multipart upload",
                  "device pass: GET with two shards lost",
                  "device pass: admin heal of a wiped drive",
                  "teardown (SIGTERM, rc must be 0)"):
        assert f"phase {phase}: ok" in p.stdout, phase
    assert "assert NOT MET the RS kernel that ran is the Pallas one" \
        in p.stdout
    assert "assert MET     server exited 0 on SIGTERM" in p.stdout


def test_chip_smoke_tiny_mesh_rehearsal_on_virtual_devices():
    """--chips 4 runs ONLY the serving-mesh path (two erasure sets on
    24 drives), here on four virtual CPU devices; count is 4."""
    p = _run_tiny("--chips", "4")
    assert p.returncode != 0
    assert '"platform": "tpu"' not in p.stdout
    assert "FAILED" not in p.stdout, p.stdout[-3000:]
    v = _verdict(p)
    assert v["ok"] is False and v["device"]["count"] == 4
    assert "phase mesh: heal a wiped drive in each set: ok" in p.stdout
    assert "phase load (a)" not in p.stdout  # no one-chip phase ran
    assert "byte-identical to rs_cpu" in p.stdout


def test_chip_smoke_alone_fails_without_printing_a_result(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo it exits nonzero and prints no result line."""
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout and '"platform"' not in p.stdout


@pytest.fixture
def fresh_kernprof(monkeypatch):
    from minio_tpu.obs import kernprof
    kp = kernprof.KernelProfiler()
    monkeypatch.setattr(kernprof, "KERNPROF", kp)
    return kp


def test_pallas_lowering_error_reaches_kernprof_with_its_shape(
        monkeypatch, fresh_kernprof):
    """A lowering ValueError from Pallas is a KERNEL failure (kernprof
    cause naming the shape, Pallas disabled with that cause, the XLA
    path answers) — not a caller bug re-raised three layers up."""
    import jax.numpy as jnp

    from minio_tpu.ops import batching, rs_tpu
    from minio_tpu.ops.gf256 import gf_mat_vec_apply
    from minio_tpu.ops.rs_matrix import parity_matrix

    monkeypatch.setitem(rs_tpu._pallas_state, "enabled", True)
    monkeypatch.setitem(rs_tpu._pallas_state, "cause", "")
    monkeypatch.setattr(batching, "serving_mesh", lambda: None)

    def refused(bm, x):
        raise ValueError(
            "The Pallas TPU lowering currently requires that the last "
            "two dimensions of your block shape are divisible by 8 and "
            "128 respectively")

    k, m, B, S = 4, 2, 2, 256
    data = np.random.default_rng(3).integers(
        0, 256, (B, k, S)).astype(np.uint8)
    bm = jnp.asarray(rs_tpu.parity_bitplane(k, m))
    out = np.asarray(rs_tpu._dispatch(refused, refused,
                                      rs_tpu.rs_gf_apply_xla, bm,
                                      jnp.asarray(data)))
    for b in range(B):  # the XLA path answered, byte-exact
        assert np.array_equal(
            out[b], gf_mat_vec_apply(parity_matrix(k, m), data[b]))
    lane = fresh_kernprof.snapshot()["backends"][
        batching.attempt_backend()]
    assert lane["failures"] == 1
    assert "shards=(2, 4, 256)" in lane["lastError"]
    assert "matrix=(16, 32)" in lane["lastError"]
    assert "Pallas TPU lowering" in lane["lastError"]
    report = rs_tpu.kernel_report()
    assert report["kernel"] == "xla"
    assert "shards=(2, 4, 256)" in report["cause"]

    # A caller bug (wrong shard count) still raises, before any kernel
    # runs and without touching the health machine.
    monkeypatch.setitem(rs_tpu._pallas_state, "enabled", True)
    with pytest.raises(ValueError, match="sublane dim"):
        rs_tpu._dispatch(refused, refused, rs_tpu.rs_gf_apply_xla, bm,
                         jnp.asarray(data[:, :3]))
    assert fresh_kernprof.snapshot()["backends"][
        batching.attempt_backend()]["failures"] == 1


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
        tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code. Unset:
    one fixed directory inside the checkout."""
    code = ("import jax; from minio_tpu.utils import compile_cache as c;"
            "print(c.configure()); "
            "print(jax.config.jax_compilation_cache_dir); "
            "print(jax.config."
            "jax_persistent_cache_min_compile_time_secs)")
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    base["JAX_PLATFORMS"] = "cpu"

    def run(env):
        p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                           env=env, capture_output=True, text=True,
                           timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]
        return p.stdout.strip().splitlines()

    outside = str(tmp_path / "cache")
    got = run(dict(base, JAX_COMPILATION_CACHE_DIR=outside))
    assert got[0] == outside and got[1] == outside
    assert float(got[2]) == 1.0  # JAX's own default: untouched
    got = run(base)
    want = os.path.join(REPO, ".jax_compile_cache")
    assert got[0] == want and got[1] == want
