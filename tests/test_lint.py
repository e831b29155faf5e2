"""mtpu-lint framework + rules + locktrace sanitizer tests.

Three layers:

1. unit: each rule gets one minimal POSITIVE snippet (flagged) and one
   NEGATIVE snippet (clean) — the rule's contract, pinned;
2. framework: suppression syntax (justification required, stale
   waivers flagged), baseline plumbing, --json output, rule subsets;
3. the tier-1 gate itself: ``python -m tools.mtpu_lint minio_tpu/
   tools/`` must exit 0 on this tree with the EMPTY checked-in
   baseline, and the runtime sanitizer must see the constructed
   deadlock (and nothing in the real tree — enforced by the
   conftest session-end hook).
"""

from __future__ import annotations

import json
import threading
import time

import pytest

from tools import mtpu_lint
from tools.mtpu_lint.core import ModuleCtx, run
from tools.mtpu_lint.rules.asyncblocking import AsyncBlockingRule
from tools.mtpu_lint.rules.commits import CommitReplaceRule
from tools.mtpu_lint.rules.concurrency import ThreadCtxRule
from tools.mtpu_lint.rules.dispatch import DispatchPolicyRule
from tools.mtpu_lint.rules.errormap import ErrorMapRule
from tools.mtpu_lint.rules.kernels import KernelPurityRule
from tools.mtpu_lint.rules.locks import BlockingUnderLockRule
from tools.mtpu_lint.rules.obs import (AutotuneMetricCallRule,
                                       KernprofTimelineMetricCallRule,
                                       MetricNameRule, NativeAssertRule,
                                       QosMetricCallRule,
                                       WatchdogIncidentMetricCallRule)
from tools.mtpu_lint.rules.resources import ResourceLeakRule
from tools.mtpu_lint.rules.retries import BoundedRetryRule

from minio_tpu.utils import locktrace


def _ctx(source: str, relpath: str = "minio_tpu/sample.py") -> ModuleCtx:
    """A synthetic module with a chosen repo-relative path (rules scope
    themselves by relpath, so tests pick the scope they target)."""
    ctx = ModuleCtx("/synthetic/sample.py", source)
    ctx.relpath = relpath
    return ctx


def _check(rule, source: str, relpath: str = "minio_tpu/sample.py"):
    ctx = _ctx(source, relpath)
    assert rule.applies(ctx), f"{rule.id} must apply to {relpath}"
    return rule.check(ctx)


# ---------------------------------------------------------------------------
# R1 — thread-boundary QoS context propagation


def test_r1_flags_bare_thread_and_submit():
    src = (
        "import threading\n"
        "def go(pool, fn):\n"
        "    threading.Thread(target=fn).start()\n"
        "    pool.submit(fn)\n")
    findings = _check(ThreadCtxRule(), src)
    assert len(findings) == 2
    assert all("ctx_wrap" in f.message for f in findings)


def test_r1_flags_positional_thread_target():
    src = ("import threading\n"
           "def go(fn):\n"
           "    threading.Thread(None, fn).start()\n")
    findings = _check(ThreadCtxRule(), src)
    assert len(findings) == 1


def test_r1_accepts_ctx_wrapped_hops_and_ignores_other_trees():
    src = (
        "import threading\n"
        "from minio_tpu.qos.ctx import ctx_wrap\n"
        "def go(pool, fn):\n"
        "    threading.Thread(target=ctx_wrap(fn)).start()\n"
        "    pool.submit(ctx_wrap(fn))\n")
    assert _check(ThreadCtxRule(), src) == []
    # Outside minio_tpu/ the rule does not apply at all.
    assert not ThreadCtxRule().applies(_ctx(src, "tools/loadgen.py"))


# ---------------------------------------------------------------------------
# R2 — resource releases on every exit path


def test_r2_flags_leaked_handle_span_slot_prefetch():
    src = (
        "def leak_handle(p):\n"
        "    f = open(p)\n"
        "    return f.read()\n"
        "def leak_span(TRACER, rid):\n"
        "    s = TRACER.begin('x', rid)\n"
        "    s.add_event('y')\n"
        "def leak_slot(self, dl):\n"
        "    slot = self.admission.acquire('read', dl)\n"
        "    do_work()\n"
        "def leak_pipe(src):\n"
        "    p = Prefetch(src, depth=2)\n"
        "    return list(p)\n")
    findings = _check(ResourceLeakRule(), src)
    kinds = sorted(f.message.split(" acquired")[0] for f in findings)
    assert kinds == ["Prefetch pipeline", "admission slot",
                     "file handle", "root span"]


def test_r2_accepts_with_finally_return_and_attribute_store():
    src = (
        "def ok_with(p):\n"
        "    with open(p) as f:\n"
        "        return f.read()\n"
        "def ok_finally(p):\n"
        "    f = open(p)\n"
        "    try:\n"
        "        return f.read()\n"
        "    finally:\n"
        "        f.close()\n"
        "def ok_transfer(src):\n"
        "    return Prefetch(src)\n"
        "def ok_owned(self, src):\n"
        "    self._pipe = Prefetch(src)\n"
        "def ok_with_name(self, dl):\n"
        "    slot = self.admission.acquire('read', dl)\n"
        "    with slot:\n"
        "        do_work()\n")
    assert _check(ResourceLeakRule(), src) == []


def test_r2_flags_orphaned_single_flight_fill():
    # A registered fill that is never finished/aborted strands every
    # coalesced waiter: the registration is a resource.
    src = (
        "def leak_fill(HOTCACHE, ns, b, k, info):\n"
        "    fill = HOTCACHE.begin_fill(ns, b, k, info)\n"
        "    if fill is None:\n"
        "        return None\n"
        "    return read_chunks()\n")
    findings = _check(ResourceLeakRule(), src)
    assert len(findings) == 1
    assert "single-flight fill" in findings[0].message


def test_r2_accepts_structurally_released_fill():
    # The engine's real shape: abort in a finally unless ownership
    # transferred into the reader stream; plus the plain-return
    # transfer and try/finally abort shapes.
    src = (
        "def ok_handoff(HOTCACHE, ns, b, k, info, src_iter):\n"
        "    fill = HOTCACHE.begin_fill(ns, b, k, info)\n"
        "    handed = False\n"
        "    try:\n"
        "        rdr = fill.reader(src_iter)\n"
        "        handed = True\n"
        "        return rdr\n"
        "    finally:\n"
        "        if not handed:\n"
        "            fill.abort(RuntimeError('setup failed'))\n"
        "def ok_transfer(HOTCACHE, ns, b, k, info):\n"
        "    return HOTCACHE.begin_fill(ns, b, k, info)\n"
        "def ok_finally(HOTCACHE, ns, b, k, info):\n"
        "    fill = HOTCACHE.begin_fill(ns, b, k, info)\n"
        "    try:\n"
        "        pump(fill)\n"
        "    finally:\n"
        "        fill.finish()\n")
    assert _check(ResourceLeakRule(), src) == []


# ---------------------------------------------------------------------------
# R3 — no blocking calls under a mutex in hot-path modules


def test_r3_flags_blocking_under_mutex():
    src = (
        "import time, threading\n"
        "_mu = threading.Lock()\n"
        "def bad(sock, fut):\n"
        "    with _mu:\n"
        "        time.sleep(0.1)\n"
        "        sock.sendall(b'x')\n"
        "        fut.result()\n")
    findings = _check(BlockingUnderLockRule(), src,
                      "minio_tpu/qos/sample.py")
    assert len(findings) == 3
    assert all("_mu" in f.message for f in findings)


def test_r3_negative_scopes_and_blessed_waits():
    src = (
        "import time, threading\n"
        "_mu = threading.Lock()\n"
        "_cv = threading.Condition()\n"
        "def ok(sock):\n"
        "    with _mu:\n"
        "        x = 1\n"
        "    time.sleep(0.1)\n"        # outside the lock
        "def ok_cv_wait():\n"
        "    with _cv:\n"
        "        _cv.wait(1)\n"         # wait on the HELD cv releases it
        "def ok_nested_def():\n"
        "    with _mu:\n"
        "        def later():\n"
        "            time.sleep(1)\n"   # does not run under the lock
        "        return later\n"
        "def ok_ns_lock(ns_lock):\n"
        "    with ns_lock.write_locked('b', 'o'):\n"
        "        time.sleep(0.01)\n")   # namespace locks guard I/O by design
    assert _check(BlockingUnderLockRule(), src,
                  "minio_tpu/erasure/sample.py") == []
    # Not a hot-path module -> rule does not apply.
    assert not BlockingUnderLockRule().applies(
        _ctx(src, "minio_tpu/s3/sample.py"))


def test_r3_flags_foreign_wait_under_mutex():
    src = (
        "import threading\n"
        "_mu = threading.Lock()\n"
        "def bad(ev):\n"
        "    with _mu:\n"
        "        ev.wait(5)\n")
    findings = _check(BlockingUnderLockRule(), src,
                      "minio_tpu/obs/sample.py")
    assert len(findings) == 1 and "wait" in findings[0].message


# ---------------------------------------------------------------------------
# R4 — kernel purity


def test_r4_flags_side_effects_in_jit_and_pallas_regions():
    src = (
        "import jax\n"
        "from functools import partial\n"
        "@jax.jit\n"
        "def k1(x):\n"
        "    print('trace-time only')\n"
        "    return x\n"
        "@partial(jax.jit, static_argnames=('n',))\n"
        "def k2(x, n):\n"
        "    METRICS2.inc('minio_tpu_v2_x', None, 1)\n"
        "    return x.nonzero()\n"
        "def _kernel(ref, o_ref):\n"
        "    jax.debug.print('{}', ref[0])\n"
        "def launch(x):\n"
        "    return pl.pallas_call(_kernel, out_shape=x)(x)\n")
    findings = _check(KernelPurityRule(), src, "minio_tpu/ops/sample.py")
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 4
    assert "print" in msgs and "nonzero" in msgs
    assert "METRICS2" in msgs and "host callback" in msgs


def test_r4_negative_outside_regions_and_sized_ops():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def k(x):\n"
        "    return jnp.nonzero(x, size=4)\n"
        "def host_wrapper(x):\n"
        "    print('fine: not traced')\n"
        "    METRICS2.inc('minio_tpu_v2_x', None, 1)\n"
        "    return k(x)\n")
    assert _check(KernelPurityRule(), src,
                  "minio_tpu/native/sample.py") == []
    assert not KernelPurityRule().applies(
        _ctx(src, "minio_tpu/erasure/sample.py"))


# ---------------------------------------------------------------------------
# R5 — error-map completeness (cross-file project rule)


_STORAGE_SRC = (
    "class StorageError(Exception):\n    pass\n"
    "class DiskNotFound(StorageError):\n    pass\n"
    "class SubDisk(DiskNotFound):\n    pass\n")


def _errmap_ctxs(map_body: str):
    sctx = _ctx(_STORAGE_SRC, "minio_tpu/storage/errors.py")
    ectx = _ctx(map_body, "minio_tpu/s3/errors.py")
    return [sctx, ectx]


def test_r5_flags_missing_stale_and_unknown_entries():
    body = (
        "ERR_A = object()\n"
        "STORAGE_ERROR_MAP = {\n"
        "    StorageError: ERR_A,\n"
        "    DiskNotFound: ERR_MISSING,\n"   # unknown value
        "    Ghost: ERR_A,\n"                # stale key
        "}\n")                                # SubDisk missing
    findings = ErrorMapRule().check_project(_errmap_ctxs(body))
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 3
    assert "SubDisk" in msgs and "Ghost" in msgs and "ERR_MISSING" in msgs


def test_r5_negative_complete_map():
    body = (
        "ERR_A = object()\n"
        "STORAGE_ERROR_MAP = {\n"
        "    StorageError: ERR_A,\n"
        "    DiskNotFound: ERR_A,\n"
        "    SubDisk: ERR_A,\n"
        "}\n")
    assert ErrorMapRule().check_project(_errmap_ctxs(body)) == []


def test_storage_api_error_runtime_mapping():
    """The runtime twin of R5: raw storage errors answer typed S3
    codes, subclasses inherit via the MRO, non-storage errors pass."""
    from minio_tpu.s3 import errors as s3err
    from minio_tpu.storage import errors as serr
    assert s3err.storage_api_error(serr.FileNotFound("k")) is \
        s3err.ERR_NO_SUCH_KEY
    assert s3err.storage_api_error(serr.VolumeNotFound("b")) is \
        s3err.ERR_NO_SUCH_BUCKET
    assert s3err.storage_api_error(serr.DiskFull("d")).http_status == 507

    class Flaky(serr.FaultyDisk):
        pass

    assert s3err.storage_api_error(Flaky("x")) is s3err.ERR_SLOW_DOWN
    assert s3err.storage_api_error(ValueError("not storage")) is None


def test_r4_auto_scopes_regen_kernel_module():
    """The regen product-matrix kernels live under minio_tpu/ops/, so
    R4's purity scope covers them by construction — a side effect in a
    jit region of rs_regen.py is a finding, and the shipped module
    itself is clean under the rule."""
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def regen_project(x):\n"
        "    print('leak')\n"
        "    return x\n")
    findings = _check(KernelPurityRule(), src, "minio_tpu/ops/rs_regen.py")
    assert len(findings) == 1 and "print" in findings[0].message
    import minio_tpu.ops.rs_regen as rr
    with open(rr.__file__) as f:
        real = ModuleCtx(rr.__file__, f.read())
    real.relpath = "minio_tpu/ops/rs_regen.py"
    assert KernelPurityRule().applies(real)
    assert KernelPurityRule().check(real) == []


def test_r5_regen_repair_failed_mapped():
    """RegenRepairFailed is a first-class storage error: the checked-in
    map carries a literal entry (R5 fixpoint over the real files) and
    the runtime mapping answers the retryable SlowDown — a failed
    minimum-bandwidth repair is a retry-me, not a 500."""
    import minio_tpu.s3.errors as s3e
    import minio_tpu.storage.errors as se
    ctxs = []
    for mod, rel in ((se, "minio_tpu/storage/errors.py"),
                     (s3e, "minio_tpu/s3/errors.py")):
        with open(mod.__file__) as f:
            ctx = ModuleCtx(mod.__file__, f.read())
        ctx.relpath = rel
        ctxs.append(ctx)
    assert ErrorMapRule().check_project(ctxs) == []
    from minio_tpu.storage import errors as serr
    assert s3e.storage_api_error(serr.RegenRepairFailed("x")) is \
        s3e.ERR_SLOW_DOWN

    class SubRegen(serr.RegenRepairFailed):
        pass

    assert s3e.storage_api_error(SubRegen("x")) is s3e.ERR_SLOW_DOWN


# ---------------------------------------------------------------------------
# R6 — retry loops bounded + backed off


def test_r6_flags_unbounded_and_hot_while_retry():
    src = (
        "def call(op):\n"
        "    while True:\n"
        "        try:\n"
        "            return op()\n"
        "        except OSError:\n"
        "            continue\n")
    found = _check(BoundedRetryRule(), src)
    msgs = " ".join(f.message for f in found)
    assert len(found) == 2, found
    assert "unbounded" in msgs and "backoff" in msgs


def test_r6_flags_attempt_loop_without_backoff():
    src = (
        "def call(op):\n"
        "    for attempt in range(4):\n"
        "        try:\n"
        "            return op()\n"
        "        except OSError:\n"
        "            pass\n")
    found = _check(BoundedRetryRule(), src)
    assert len(found) == 1 and "backoff" in found[0].message


def test_r6_negative_bounded_backoff_and_iteration():
    src = (
        "import time\n"
        "def call(op, items):\n"
        "    for attempt in range(4):\n"
        "        try:\n"
        "            return op()\n"
        "        except OSError:\n"
        "            time.sleep(2 ** attempt)\n"
        "    out = []\n"
        "    for it in items:\n"
        "        try:\n"
        "            out.append(op(it))\n"
        "        except OSError:\n"
        "            continue\n"
        "    while items:\n"
        "        it = items.pop()\n"
        "        try:\n"
        "            op(it)\n"
        "        except OSError:\n"
        "            continue\n"
        "    return out\n")
    assert _check(BoundedRetryRule(), src) == []


def test_r6_ignores_continue_owned_by_nested_loop():
    src = (
        "def call(op, xs):\n"
        "    while True:\n"
        "        try:\n"
        "            return op()\n"
        "        except OSError:\n"
        "            for x in xs:\n"
        "                if not x:\n"
        "                    continue\n"
        "                op(x)\n"
        "            return None\n")
    assert _check(BoundedRetryRule(), src) == []


def test_r6_ignores_event_loop_with_per_item_try():
    """`while True:` wrapping a for whose try/except continue-skips a
    bad ITEM is an event loop — the continue re-runs the for, not the
    while, so R6 must stay quiet (iteration, not retry)."""
    src = (
        "def serve(q):\n"
        "    while True:\n"
        "        for item in q.drain():\n"
        "            try:\n"
        "                handle(item)\n"
        "            except OSError:\n"
        "                continue\n")
    assert _check(BoundedRetryRule(), src) == []


def test_r6_scoped_to_package():
    src = (
        "def call(op):\n"
        "    while True:\n"
        "        try:\n"
        "            return op()\n"
        "        except OSError:\n"
        "            continue\n")
    rule = BoundedRetryRule()
    assert not rule.applies(_ctx(src, "tools/sample.py"))


# ---------------------------------------------------------------------------
# R7 — storage renames route through the blessed commit helper


def test_r7_flags_raw_replace_and_rename_in_storage():
    src = (
        "import os\n"
        "def commit(tmp, dst):\n"
        "    os.replace(tmp, dst)\n"
        "def move(a, b):\n"
        "    os.rename(a, b)\n")
    findings = _check(CommitReplaceRule(), src,
                      "minio_tpu/storage/sample.py")
    assert len(findings) == 2
    assert all("commit_replace" in f.message for f in findings)


def test_r7_negative_helper_call_and_waiver():
    good = (
        "from minio_tpu.storage.xl import commit_replace\n"
        "def commit(tmp, dst):\n"
        "    commit_replace(tmp, dst)\n")
    assert _check(CommitReplaceRule(), good,
                  "minio_tpu/storage/sample.py") == []
    waived = (
        "import os\n"
        "def helper(tmp, dst):\n"
        "    # mtpu-lint: disable=R7 -- the helper itself\n"
        "    os.replace(tmp, dst)\n")
    res = run(["minio_tpu"], rules=[CommitReplaceRule()],
              baseline_path=None)
    # whole-tree gate below covers the real tree; here pin that the
    # suppression machinery waives the helper's own replace.
    ctx = _ctx(waived, "minio_tpu/storage/sample.py")
    raw = CommitReplaceRule().check(ctx)
    assert len(raw) == 1  # rule fires pre-suppression
    assert res.findings == []  # the real tree is clean under R7


def test_r7_scoped_to_storage_package():
    src = "import os\ndef f(a, b):\n    os.replace(a, b)\n"
    rule = CommitReplaceRule()
    assert not rule.applies(_ctx(src, "minio_tpu/erasure/sample.py"))
    assert not rule.applies(_ctx(src, "tools/sample.py"))


_R7_NATIVE = (
    "int commit_rename(const char* src, const char* dst) {\n"
    "  return rename(src, dst) == 0 ? 0 : errno;  // the one rename\n"
    "}\n"
    "int fs_commit_meta(const char* tmp, const char* dst) {\n"
    "  // rename(tmp, dst) in a comment is not a call\n"
    "  return commit_rename(tmp, dst);\n"
    "}\n")


@pytest.mark.parametrize("source, flagged", [
    (_R7_NATIVE, []),
    (_R7_NATIVE.replace("return commit_rename(tmp, dst);",
                        "return rename(tmp, dst);"), [6]),
    (_R7_NATIVE.replace("return commit_rename(tmp, dst);",
                        "return renameat(AT_FDCWD, tmp, AT_FDCWD, dst);"),
     [6]),
    (_R7_NATIVE.replace("int commit_rename(", "int move_it(", 1), [2]),
], ids=["blessed", "raw-rename", "raw-renameat", "helper-renamed"])
def test_r7_native_lane_renames_route_through_commit_rename(source,
                                                            flagged):
    """The second blessed site: minio_tpu/native/fsops.cc renames only
    inside commit_rename(src, dst)."""
    from tools.mtpu_lint.rules.commits import check_native_source
    findings = check_native_source("minio_tpu/native/sample.cc", source)
    assert [f.line for f in findings] == flagged
    assert all(f.rule == "R7" and "commit_rename" in f.message
               for f in findings)


def test_r7_reads_the_real_native_sources():
    """The rule reaches fsops.cc through the native loader (the linter
    walks .py files), finds its rename inside the helper, and flags the
    same file with the helper's name taken away."""
    from tools.mtpu_lint.core import REPO
    from tools.mtpu_lint.rules import commits
    import os
    with open(os.path.join(REPO, "minio_tpu/native/fsops.cc")) as f:
        text = f.read()
    # The native lane is not taken with `storage fsync=on`
    # (xl._native_lib), so it must hold no fsync to forget either.
    assert "rename(src, dst)" in text and "fsync(" not in text
    assert commits.check_native_source("fsops.cc", text) == []
    assert commits.check_native_source(
        "fsops.cc", text.replace("int commit_rename(", "int move_it("))
    rule = CommitReplaceRule()
    with open(os.path.join(REPO, commits.NATIVE_LOADER)) as f:
        ctx = ModuleCtx(os.path.join(REPO, commits.NATIVE_LOADER), f.read())
    assert rule.applies(ctx) and rule.check(ctx) == []


# ---------------------------------------------------------------------------
# R8 — no blocking calls in async def bodies under minio_tpu/s3/


def test_r8_flags_blocking_calls_in_async_def():
    src = (
        "import time, os\n"
        "async def handle(sock, lock):\n"
        "    time.sleep(1)\n"
        "    lock.acquire()\n"
        "    sock.recv(1024)\n"
        "    sock.sendall(b'x')\n"
        "    open('/tmp/f')\n"
        "    os.fsync(3)\n")
    found = _check(AsyncBlockingRule(), src,
                   "minio_tpu/s3/sample.py")
    assert len(found) == 6, found
    assert all("event loop" in f.message for f in found)


def test_r8_awaited_calls_and_sync_defs_exempt():
    src = (
        "import asyncio\n"
        "async def pump(loop, pool, fut):\n"
        "    await asyncio.sleep(0.1)\n"
        "    await asyncio.wait_for(fut, 5)\n"
        "    chunk = await loop.run_in_executor(pool, produce)\n"
        "    transport.write(chunk)\n"
        "def produce():\n"
        "    import time\n"
        "    time.sleep(1)\n"       # sync def: runs off-loop
        "    lock.acquire()\n")
    assert _check(AsyncBlockingRule(), src,
                  "minio_tpu/s3/sample.py") == []


def test_r8_nested_sync_def_inside_async_exempt():
    src = (
        "async def outer(pool):\n"
        "    def worker():\n"
        "        lock.acquire()\n"   # runs on the pool, not the loop
        "        return 1\n"
        "    return await pool.run(worker)\n")
    assert _check(AsyncBlockingRule(), src,
                  "minio_tpu/s3/sample.py") == []


def test_r8_nested_async_def_checked():
    src = (
        "def factory():\n"
        "    async def inner(lock):\n"
        "        lock.acquire()\n"
        "    return inner\n")
    found = _check(AsyncBlockingRule(), src,
                   "minio_tpu/s3/sample.py")
    assert len(found) == 1 and "lock acquire" in found[0].message


def test_r8_covers_rpc_package():
    """PR-18 fabric: the async RPC loop (rpc/aio.py) has the same
    one-blocking-call-stalls-everything failure mode as the front
    door — R8 must patrol minio_tpu/rpc/ too."""
    src = (
        "import time\n"
        "async def roundtrip(conn, lock):\n"
        "    lock.acquire()\n"
        "    time.sleep(0.1)\n"
        "    conn.sendall(b'frame')\n")
    found = _check(AsyncBlockingRule(), src,
                   "minio_tpu/rpc/sample.py")
    assert len(found) == 3, found


def test_r8_rpc_package_awaited_calls_exempt():
    src = (
        "import asyncio\n"
        "async def exchange(writer, reader, rlock):\n"
        "    writer.write(b'frame')\n"
        "    await writer.drain()\n"
        "    async with rlock:\n"
        "        return await asyncio.wait_for(reader.readexactly(4), 5)\n")
    assert _check(AsyncBlockingRule(), src,
                  "minio_tpu/rpc/sample.py") == []


def test_r8_scoped_to_s3_package_with_waiver_escape():
    src = (
        "import time\n"
        "async def f():\n"
        "    time.sleep(1)\n")
    rule = AsyncBlockingRule()
    assert not rule.applies(_ctx(src, "minio_tpu/erasure/sample.py"))
    assert not rule.applies(_ctx(src, "tools/sample.py"))
    assert rule.applies(_ctx(src, "minio_tpu/rpc/sample.py"))
    waived = (
        "import time\n"
        "async def f():\n"
        "    # mtpu-lint: disable=R8 -- startup-only coroutine, loop not yet serving\n"
        "    time.sleep(1)\n")
    ctx = _ctx(waived, "minio_tpu/s3/sample.py")
    raw = AsyncBlockingRule().check(ctx)
    assert len(raw) == 1  # fires pre-suppression…
    waived_lines = {s.line for s in ctx.suppressions
                    if "R8" in s.rules}
    assert all(f.line in waived_lines for f in raw)  # …and is waived


# ---------------------------------------------------------------------------
# R9 — backend-selection policy lives in ops/autotune.py


def test_r9_flags_hardwired_threshold_and_lane_literal():
    src = (
        "TPU_MIN_BYTES = 4 * 1024 * 1024\n"
        "def pick(nbytes, lane):\n"
        "    if nbytes < TPU_MIN_BYTES:\n"
        "        return False\n"
        "    if lane == 'device':\n"
        "        return True\n"
        "    return nbytes >= 8388608\n")
    found = _check(DispatchPolicyRule(), src,
                   "minio_tpu/ops/sample.py")
    msgs = [f.message for f in found]
    assert len(found) == 3
    assert any("size threshold" in m for m in msgs)
    assert any("lane literal" in m for m in msgs)
    assert any("inline byte-size crossover" in m for m in msgs)
    # Same violations flagged in the codec module too.
    assert len(_check(DispatchPolicyRule(), src,
                      "minio_tpu/erasure/codec.py")) == 3


def test_r9_exempts_autotune_and_out_of_scope_trees():
    src = ("def pick(nbytes):\n"
           "    return nbytes >= 4194304\n")
    rule = DispatchPolicyRule()
    # The planner itself is the sanctioned home of the threshold.
    assert not rule.applies(_ctx(src, "minio_tpu/ops/autotune.py"))
    # bitrot/heal/engine are not dispatch-decision modules for R9.
    assert not rule.applies(_ctx(src, "minio_tpu/erasure/bitrot.py"))
    assert not rule.applies(_ctx(src, "minio_tpu/s3/server.py"))


def test_r9_accepts_pins_and_constant_identity():
    """User-facing codec pins ("tpu"/"cpu") and comparisons through
    the imported kernprof constants stay legal — identity is fine,
    inline POLICY is not."""
    src = (
        "from minio_tpu.obs.kernprof import DEVICE\n"
        "def pick(backend, lane, n_blocks):\n"
        "    if backend == 'tpu':\n"
        "        return True\n"
        "    if backend == 'cpu':\n"
        "        return False\n"
        "    if lane == DEVICE:\n"
        "        return True\n"
        "    return n_blocks > 4\n")
    assert _check(DispatchPolicyRule(), src,
                  "minio_tpu/ops/sample.py") == []


def test_r9_waiver_escape_hatch():
    src = (
        "def pick(nbytes):\n"
        "    # mtpu-lint: disable=R9 -- probe rung floor, not a dispatch crossover\n"
        "    return nbytes >= 4194304\n")
    ctx = _ctx(src, "minio_tpu/ops/sample.py")
    raw = DispatchPolicyRule().check(ctx)
    assert len(raw) == 1  # fires pre-suppression…
    waived_lines = {s.line for s in ctx.suppressions
                    if "R9" in s.rules}
    assert all(f.line in waived_lines for f in raw)  # …and is waived


# ---------------------------------------------------------------------------
# O-rules (ported obs_lint) — representative positive/negative pairs;
# tests/test_observability.py keeps the original shim-level coverage.


def test_o1_native_asserts():
    bad = "def f(x):\n    assert x > 0\n"
    good = "def f(x):\n    if x <= 0:\n        raise ValueError(x)\n"
    assert len(_check(NativeAssertRule(), bad,
                      "minio_tpu/native/sample.py")) == 1
    assert _check(NativeAssertRule(), good,
                  "minio_tpu/native/sample.py") == []
    assert not NativeAssertRule().applies(
        _ctx(bad, "minio_tpu/ops/sample.py"))


def test_o2_metric_name_registration():
    bad = "NAME = 'minio_tpu_v2_definitely_not_registered'\n"
    good = "NAME = 'minio_tpu_v2_api_requests_total'\n"
    assert len(_check(MetricNameRule(), bad)) == 1
    assert _check(MetricNameRule(), good) == []


def test_o3_literal_recording_calls():
    bad = ("def f(name):\n"
           "    METRICS2.inc(name)\n"
           "    METRICS2.observe('minio_tpu_v2_nope', None, 1)\n")
    good = ("def f():\n"
            "    METRICS2.inc('minio_tpu_v2_qos_shed_total',"
            " {'class': 'read', 'reason': 'x'})\n")
    assert len(_check(QosMetricCallRule(), bad,
                      "minio_tpu/qos/sample.py")) == 2
    assert _check(QosMetricCallRule(), good,
                  "minio_tpu/qos/sample.py") == []


def test_o6_kernprof_timeline_literal_recording_calls():
    # POSITIVE: dynamic name + unregistered literal, in both scoped
    # files of the kernprof/timeline family.
    bad = ("def f(name):\n"
           "    METRICS2.inc(name)\n"
           "    METRICS2.set_gauge('minio_tpu_v2_not_a_real_series',"
           " {'backend': 'device'}, 1)\n")
    for path in ("minio_tpu/obs/kernprof.py",
                 "minio_tpu/obs/timeline.py"):
        assert len(_check(KernprofTimelineMetricCallRule(), bad,
                          path)) == 2
    # NEGATIVE: literal registered names are clean.
    good = ("def f():\n"
            "    METRICS2.set_gauge("
            "'minio_tpu_v2_kernel_backend_state',"
            " {'backend': 'device'}, 2)\n"
            "    METRICS2.observe('minio_tpu_v2_kernel_dispatch_ms',"
            " {'kernel': 'rs_encode'}, 1.5)\n")
    assert _check(KernprofTimelineMetricCallRule(), good,
                  "minio_tpu/obs/kernprof.py") == []
    # Out of scope: the rule does not apply elsewhere in obs/.
    assert not KernprofTimelineMetricCallRule().applies(
        _ctx(bad, "minio_tpu/obs/metrics2.py"))


def test_o7_watchdog_incidents_literal_recording_calls():
    # POSITIVE: dynamic name + unregistered literal, in both scoped
    # files of the watchdog/incidents family.
    bad = ("def f(name):\n"
           "    METRICS2.inc(name)\n"
           "    METRICS2.set_gauge('minio_tpu_v2_not_a_real_series',"
           " {'rule': 'shed_burn'}, 1)\n")
    for path in ("minio_tpu/obs/watchdog.py",
                 "minio_tpu/obs/incidents.py"):
        assert len(_check(WatchdogIncidentMetricCallRule(), bad,
                          path)) == 2
    # NEGATIVE: literal registered names are clean.
    good = ("def f():\n"
            "    METRICS2.set_gauge('minio_tpu_v2_alerts_firing',"
            " {'rule': 'shed_burn'}, 1)\n"
            "    METRICS2.inc('minio_tpu_v2_incidents_total',"
            " {'rule': 'shed_burn'})\n"
            "    METRICS2.inc('minio_tpu_v2_alert_webhook_total',"
            " {'result': 'sent'})\n")
    assert _check(WatchdogIncidentMetricCallRule(), good,
                  "minio_tpu/obs/watchdog.py") == []
    # Out of scope: the rule does not apply elsewhere in obs/.
    assert not WatchdogIncidentMetricCallRule().applies(
        _ctx(bad, "minio_tpu/obs/slowlog.py"))


def test_o8_autotune_literal_recording_calls():
    # POSITIVE: dynamic name + unregistered codec_plan literal.
    bad = ("def f(name):\n"
           "    METRICS2.inc(name)\n"
           "    METRICS2.set_gauge('minio_tpu_v2_codec_plan_bogus',"
           " {'kernel': 'rs_encode'}, 1)\n")
    assert len(_check(AutotuneMetricCallRule(), bad,
                      "minio_tpu/ops/autotune.py")) == 2
    # NEGATIVE: the real codec_plan_* series are registered.
    good = ("def f():\n"
            "    METRICS2.set_gauge('minio_tpu_v2_codec_plan_lane',"
            " {'kernel': 'rs_encode', 'bucket': '<64K'}, 1)\n"
            "    METRICS2.inc("
            "'minio_tpu_v2_codec_plan_transitions_total',"
            " {'kernel': 'rs_encode', 'bucket': '<64K',"
            " 'lane': 'native'})\n"
            "    METRICS2.inc('minio_tpu_v2_codec_plan_probes_total',"
            " {'lane': 'native', 'result': 'pass'})\n")
    assert _check(AutotuneMetricCallRule(), good,
                  "minio_tpu/ops/autotune.py") == []
    # Out of scope: the rule does not apply elsewhere in ops/.
    assert not AutotuneMetricCallRule().applies(
        _ctx(bad, "minio_tpu/ops/batching.py"))


def test_r10_no_row_eval_in_columnar_scan_path():
    from tools.mtpu_lint.rules.selectscan import SelectScanRowEvalRule
    # POSITIVE: per-row Node.eval and a sql.execute hand-off inside
    # the scan path.
    bad = ("def scan(where, batch):\n"
           "    for i in range(batch.nrows):\n"
           "        if where.eval(batch.record(i)) is True:\n"
           "            pass\n"
           "    return sql.execute(q, recs)\n")
    assert len(_check(SelectScanRowEvalRule(), bad,
                      "minio_tpu/s3select/engine.py")) == 2
    # NEGATIVE: vectorized node .run() calls and fallback-module
    # routing are the sanctioned shapes.
    good = ("def scan(plan, batch, ctx):\n"
            "    vv = plan.root.run(ctx)\n"
            "    return fallback.eval_where(where, batch.record(0))\n")
    assert _check(SelectScanRowEvalRule(), good,
                  "minio_tpu/s3select/compile.py") == []
    # The designated fallback module (and the row engine itself) are
    # out of scope — that is where per-row eval BELONGS.
    assert not SelectScanRowEvalRule().applies(
        _ctx(bad, "minio_tpu/s3select/fallback.py"))
    assert not SelectScanRowEvalRule().applies(
        _ctx(bad, "minio_tpu/s3select/sql.py"))


def test_r10_waiver_escape_hatch():
    from tools.mtpu_lint.rules.selectscan import SelectScanRowEvalRule
    src = ("def scan(where, rec):\n"
           "    return where.eval(rec)  "
           "# mtpu-lint: disable=R10 -- one-off schema sniff, "
           "not the row loop\n")
    ctx = _ctx(src, "minio_tpu/s3select/engine.py")
    raw = SelectScanRowEvalRule().check(ctx)
    assert len(raw) == 1  # fires pre-suppression…
    waived_lines = {s.line for s in ctx.suppressions
                    if "R10" in s.rules}
    assert all(f.line in waived_lines for f in raw)  # …and is waived


def test_o9_select_literal_recording_calls():
    from tools.mtpu_lint.rules.obs import SelectMetricCallRule
    # POSITIVE: dynamic name + unregistered select_* literal.
    bad = ("def f(name):\n"
           "    METRICS2.inc(name)\n"
           "    METRICS2.inc('minio_tpu_v2_select_bogus_total')\n")
    assert len(_check(SelectMetricCallRule(), bad,
                      "minio_tpu/s3select/select.py")) == 2
    # NEGATIVE: the real select_* series are registered.
    good = ("def f():\n"
            "    METRICS2.inc("
            "'minio_tpu_v2_select_scanned_bytes_total', None, 1)\n"
            "    METRICS2.inc("
            "'minio_tpu_v2_select_processed_bytes_total', None, 1)\n"
            "    METRICS2.inc("
            "'minio_tpu_v2_select_returned_bytes_total', None, 1)\n"
            "    METRICS2.inc('minio_tpu_v2_select_requests_total',"
            " {'engine': 'columnar'})\n"
            "    METRICS2.inc("
            "'minio_tpu_v2_select_fallback_rows_total', None, 1)\n")
    assert _check(SelectMetricCallRule(), good,
                  "minio_tpu/ops/select_kernels.py") == []
    # Out of scope: the rule does not apply elsewhere in ops/.
    assert not SelectMetricCallRule().applies(
        _ctx(bad, "minio_tpu/ops/batching.py"))


def test_o10_usage_literal_recording_calls():
    from tools.mtpu_lint.rules.obs import UsageMetricCallRule
    # POSITIVE: dynamic name + unregistered usage_* literal.
    bad = ("def f(kind):\n"
           "    METRICS2.inc('minio_tpu_v2_usage_' + kind)\n"
           "    METRICS2.inc('minio_tpu_v2_usage_bogus_total',"
           " {'bucket': 'b'})\n")
    assert len(_check(UsageMetricCallRule(), bad,
                      "minio_tpu/obs/usage.py")) == 2
    # NEGATIVE: the real usage_* series (and the cardinality-guard
    # overflow counter) are registered.
    good = ("def f(bucket, cls):\n"
            "    METRICS2.inc('minio_tpu_v2_usage_requests_total',"
            " {'bucket': bucket, 'class': cls})\n"
            "    METRICS2.inc('minio_tpu_v2_usage_rx_bytes_total',"
            " {'bucket': bucket}, 100)\n"
            "    METRICS2.inc('minio_tpu_v2_usage_shed_total',"
            " {'bucket': bucket})\n"
            "    METRICS2.inc("
            "'minio_tpu_v2_usage_tenant_requests_total',"
            " {'tenant': 'ak', 'class': cls})\n"
            "    METRICS2.inc("
            "'minio_tpu_v2_metrics_label_overflow_total',"
            " {'metric': 'm', 'label': 'bucket'})\n")
    assert _check(UsageMetricCallRule(), good,
                  "minio_tpu/obs/usage.py") == []
    # Out of scope: the rule does not apply elsewhere in obs/.
    assert not UsageMetricCallRule().applies(
        _ctx(bad, "minio_tpu/obs/timeline.py"))


def test_o11_loopmon_profiler_literal_recording_calls():
    from tools.mtpu_lint.rules.obs import LoopmonProfilerMetricCallRule
    # POSITIVE: dynamic name + unregistered loop_* literal, in both
    # scoped files of the loopmon/profiler family.
    bad = ("def f(name):\n"
           "    METRICS2.inc(name)\n"
           "    METRICS2.observe('minio_tpu_v2_loop_bogus_ms',"
           " {'loop': 's3-0'}, 1.0)\n")
    for path in ("minio_tpu/obs/loopmon.py",
                 "minio_tpu/utils/profiler.py"):
        assert len(_check(LoopmonProfilerMetricCallRule(), bad,
                          path)) == 2
    # NEGATIVE: the real loop_*/pool_*/profile_* series are registered.
    good = ("def f(loop, pool):\n"
            "    METRICS2.observe('minio_tpu_v2_loop_lag_ms',"
            " {'loop': loop}, 1.5)\n"
            "    METRICS2.set_gauge('minio_tpu_v2_loop_lag_ewma_ms',"
            " {'loop': loop}, 1.5)\n"
            "    METRICS2.set_gauge('minio_tpu_v2_loop_tasks',"
            " {'loop': loop}, 3)\n"
            "    METRICS2.inc('minio_tpu_v2_loop_stalls_total',"
            " {'loop': loop})\n"
            "    METRICS2.set_gauge('minio_tpu_v2_pool_threads',"
            " {'pool': pool}, 8)\n"
            "    METRICS2.set_gauge('minio_tpu_v2_pool_threads_busy',"
            " {'pool': pool}, 2)\n"
            "    METRICS2.inc('minio_tpu_v2_profile_samples_total',"
            " {}, 40)\n")
    assert _check(LoopmonProfilerMetricCallRule(), good,
                  "minio_tpu/obs/loopmon.py") == []
    # Out of scope: the rule does not apply elsewhere in obs/ or
    # utils/.
    assert not LoopmonProfilerMetricCallRule().applies(
        _ctx(bad, "minio_tpu/obs/timeline.py"))
    assert not LoopmonProfilerMetricCallRule().applies(
        _ctx(bad, "minio_tpu/utils/pipeline.py"))


# ---------------------------------------------------------------------------
# Framework: suppressions, baseline, output modes


def _run_snippet(tmp_path, source: str, rules=None, args=None):
    f = tmp_path / "snippet.py"
    f.write_text(source)
    return run([str(f)], rules=rules), str(f)


def test_suppression_waives_with_justification(tmp_path):
    res, _ = _run_snippet(
        tmp_path,
        "def f(p):\n"
        "    f = open(p)  # mtpu-lint: disable=R2 -- handed to caller-managed pool\n"
        "    return f.read()\n",
        rules=[ResourceLeakRule()])
    assert res.findings == []


def test_suppression_on_preceding_line(tmp_path):
    res, _ = _run_snippet(
        tmp_path,
        "def f(p):\n"
        "    # mtpu-lint: disable=R2 -- lifetime owned by the registry\n"
        "    f = open(p)\n"
        "    return f.read()\n",
        rules=[ResourceLeakRule()])
    assert res.findings == []


def test_suppression_without_justification_is_a_finding(tmp_path):
    res, _ = _run_snippet(
        tmp_path,
        "def f(p):\n"
        "    f = open(p)  # mtpu-lint: disable=R2\n"
        "    return f.read()\n",
        rules=[ResourceLeakRule()])
    assert [f.rule for f in res.findings] == ["SUP"]
    assert "justification" in res.findings[0].message


def test_unused_suppression_is_a_finding(tmp_path):
    res, _ = _run_snippet(
        tmp_path,
        "def f():\n"
        "    x = 1  # mtpu-lint: disable=R2 -- nothing to waive here\n"
        "    return x\n",
        rules=[ResourceLeakRule()])
    assert [f.rule for f in res.findings] == ["SUP"]
    assert "unused" in res.findings[0].message


def test_multi_rule_suppression_not_stale_in_subset_run(tmp_path):
    # 'disable=R1,R2' used by R1: an R2-only run must not call it
    # stale (staleness is judged only when EVERY listed rule ran).
    res, _ = _run_snippet(
        tmp_path,
        "import threading\n"
        "def f(fn):\n"
        "    # mtpu-lint: disable=R1,R2 -- daemon, no request context\n"
        "    threading.Thread(target=fn).start()\n",
        rules=[ResourceLeakRule()])
    assert res.findings == []
    # ...but when both rules run and neither fires, it IS stale.
    res2, _ = _run_snippet(
        tmp_path,
        "def f():\n"
        "    # mtpu-lint: disable=R1,R2 -- nothing here\n"
        "    return 1\n",
        rules=[ThreadCtxRule(), ResourceLeakRule()])
    assert [f.rule for f in res2.findings] == ["SUP"]


def test_missing_path_fails_instead_of_vacuous_ok(capsys):
    # A typoed path must not produce a green zero-file gate.
    rc = mtpu_lint.main(["definitely_not_a_dir_xyz"])
    out = capsys.readouterr().out
    assert rc == 1 and "no Python files found" in out


def test_unknown_rule_id_fails_instead_of_vacuous_ok(tmp_path, capsys):
    # Same failure class for --rules: a typoed id must not silently
    # select zero rules and gate green.
    f = tmp_path / "snippet.py"
    f.write_text("x = 1\n")
    rc = mtpu_lint.main(["--rules", "R2x", str(f)])
    out = capsys.readouterr().out
    assert rc == 1 and "unknown rule id" in out


def test_baseline_key_is_line_anchored(tmp_path):
    # One baselined legacy site must not waive a NEW violation of the
    # same rule in the same file.
    f = tmp_path / "snippet.py"
    f.write_text("def f(p):\n    f = open(p)\n    return f.read()\n")
    res = run([str(f)], rules=[ResourceLeakRule()])
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([res.findings[0].key()]))
    f.write_text("def f(p):\n    f = open(p)\n    return f.read()\n"
                 "def g(p):\n    h = open(p)\n    return h.read()\n")
    res2 = run([str(f)], rules=[ResourceLeakRule()],
               baseline_path=str(bl))
    assert len(res2.findings) == 1 and res2.findings[0].line == 5
    assert res2.baselined == 1


def test_unrun_rules_do_not_judge_suppressions(tmp_path):
    # An R1 waiver must not be called stale by an R2-only run (the
    # obs_lint shim runs subsets).
    res, _ = _run_snippet(
        tmp_path,
        "import threading\n"
        "def f(fn):\n"
        "    # mtpu-lint: disable=R1 -- daemon, no request context\n"
        "    threading.Thread(target=fn).start()\n",
        rules=[ResourceLeakRule()])
    assert res.findings == []


def test_baseline_subtracts_known_findings(tmp_path):
    src = "def f(p):\n    f = open(p)\n    return f.read()\n"
    f = tmp_path / "snippet.py"
    f.write_text(src)
    res = run([str(f)], rules=[ResourceLeakRule()])
    assert len(res.findings) == 1
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([res.findings[0].key()]))
    res2 = run([str(f)], rules=[ResourceLeakRule()],
               baseline_path=str(bl))
    assert res2.findings == [] and res2.baselined == 1


def test_checked_in_baseline_is_empty():
    with open(mtpu_lint.DEFAULT_BASELINE, encoding="utf-8") as f:
        assert json.load(f) == []


def test_json_output_and_exit_codes(tmp_path, capsys):
    f = tmp_path / "snippet.py"
    f.write_text("def f(p):\n    f = open(p)\n    return f.read()\n")
    rc = mtpu_lint.main(["--json", str(f)])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["files"] == 1
    assert out["findings"][0]["rule"] == "R2"
    assert out["findings"][0]["line"] == 2
    f.write_text("def f(p):\n    with open(p) as fh:\n"
                 "        return fh.read()\n")
    rc = mtpu_lint.main(["--json", str(f)])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["findings"] == []


def test_syntax_error_reported_not_crashed(tmp_path, capsys):
    f = tmp_path / "broken.py"
    f.write_text("def f(:\n")
    rc = mtpu_lint.main([str(f)])
    assert rc == 1
    assert "SyntaxError" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# The tier-1 gate: the real tree is clean under ALL rules with the
# empty checked-in baseline (this is the test that gates future PRs).


def test_whole_tree_lint_clean(capsys):
    rc = mtpu_lint.main(["minio_tpu", "tools"])
    out = capsys.readouterr().out
    assert rc == 0, f"mtpu-lint found violations:\n{out}"


# ---------------------------------------------------------------------------
# Runtime sanitizer (utils/locktrace.py)


needs_locktrace = pytest.mark.skipif(
    not locktrace.installed(),
    reason="locktrace not installed (MTPU_LOCKTRACE disabled)")


@needs_locktrace
def test_constructed_deadlock_reports_exactly_one_cycle():
    """Two threads taking two locks in opposite order — sequenced so
    the deadlock cannot actually trigger — must yield exactly one
    cycle naming both construction sites."""
    with locktrace.isolated() as lt:
        lock_a = threading.Lock()
        lock_b = threading.Lock()
        first_done = threading.Event()

        def first():
            with lock_a:
                with lock_b:
                    pass
            first_done.set()

        def second():
            assert first_done.wait(10)
            with lock_b:
                with lock_a:
                    pass

        t1 = threading.Thread(target=first)
        t2 = threading.Thread(target=second)
        t1.start()
        t2.start()
        t1.join(10)
        t2.join(10)
        cyc = lt.cycles()
        rep = lt.report()
    assert len(cyc) == 1, f"expected exactly one cycle, got {cyc}"
    sites = set(cyc[0])
    assert len(sites) == 2
    assert all("test_lint.py" in s for s in sites)
    # The human-readable report names both sites too.
    for s in sites:
        assert s in rep


@needs_locktrace
def test_consistent_order_has_no_cycle():
    with locktrace.isolated() as lt:
        lock_a = threading.Lock()
        lock_b = threading.Lock()

        def use():
            with lock_a:
                with lock_b:
                    pass

        threads = [threading.Thread(target=use) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        assert lt.cycles() == []
        assert len(lt.edges()) == 1


@needs_locktrace
def test_sleep_while_holding_lock_is_reported():
    with locktrace.isolated() as lt:
        lk = threading.Lock()
        with lk:
            time.sleep(0.001)
        blk = lt.blocking_reports()
    assert any(kind == "time.sleep" and "test_lint.py" in lock_site
               for (lock_site, _call, kind) in blk)


@needs_locktrace
def test_cross_thread_release_leaves_no_stale_held_entry():
    """Handoff-latch pattern: a Lock acquired on a worker and released
    by another thread must not leave a stale entry in the worker's
    held stack (which would draw false edges / blocking reports on
    everything the worker does afterwards)."""
    with locktrace.isolated() as lt:
        latch = threading.Lock()
        acquired = threading.Event()
        released = threading.Event()
        after = threading.Lock()

        def worker():
            latch.acquire()
            acquired.set()
            assert released.wait(10)
            # The latch was released by the MAIN thread; this thread's
            # held stack must be clean now.
            with after:
                time.sleep(0.001)

        t = threading.Thread(target=worker)
        t.start()
        assert acquired.wait(10)
        latch.release()          # cross-thread release (legal for Lock)
        released.set()
        t.join(10)
        # (Event.wait under the held latch legitimately records an
        # edge latch -> Event-internal lock; what must NOT exist is
        # anything recorded AFTER the cross-thread release.)
        assert (latch.site, after.site) not in lt.edges(), lt.edges()
        assert not any(lock_site == latch.site
                       for (lock_site, _c, _k) in lt.blocking_reports()), \
            lt.blocking_reports()


def test_maybe_install_respects_falsy_spellings(monkeypatch):
    for off in ("0", "off", "OFF", "false", "False", "no", ""):
        monkeypatch.setenv("MTPU_LOCKTRACE", off)
        assert locktrace.maybe_install() is False


@needs_locktrace
def test_transaction_lock_waives_blocking_but_not_cycles():
    """transaction_lock() is the runtime twin of an inline suppression:
    held-lock blocking reports are waived, lock-ORDER edges still
    record (a transaction lock can still deadlock)."""
    with locktrace.isolated() as lt:
        txn = locktrace.transaction_lock(threading.Lock())
        inner = threading.Lock()
        with txn:
            time.sleep(0.001)
            with inner:
                pass
        assert lt.blocking_reports() == {}
        assert len(lt.edges()) == 1  # txn -> inner still recorded


@needs_locktrace
def test_rlock_reentry_draws_no_self_edge():
    with locktrace.isolated() as lt:
        rl = threading.RLock()
        with rl:
            with rl:
                pass
        assert lt.edges() == {}


def test_locktrace_condition_and_queue_still_work():
    """The wrapper must stay duck-compatible with Condition/Queue
    internals (the _release_save/_is_owned delegation paths)."""
    q_depth = 64
    import queue
    q: queue.Queue = queue.Queue(maxsize=4)

    def prod():
        for i in range(q_depth):
            q.put(i)

    t = threading.Thread(target=prod)
    t.start()
    got = [q.get() for _ in range(q_depth)]
    t.join(10)
    assert got == list(range(q_depth))

    cv = threading.Condition()
    ready = []

    def waiter():
        with cv:
            while not ready:
                cv.wait(5)

    w = threading.Thread(target=waiter)
    w.start()
    time.sleep(0.02)
    with cv:
        ready.append(1)
        cv.notify_all()
    w.join(10)
    assert not w.is_alive()


# ---------------------------------------------------------------------------
# qos.ctx.ctx_wrap — the helper R1 mandates


def test_ctx_wrap_carries_deadline_and_lane_across_threads():
    from minio_tpu.qos import scheduler
    from minio_tpu.qos.ctx import ctx_wrap
    from minio_tpu.qos.deadline import (Deadline, current_deadline,
                                        deadline_scope)
    seen = {}

    def probe():
        dl = current_deadline()
        seen["deadline"] = dl.remaining() if dl else None
        seen["lane"] = scheduler.current_lane()

    with deadline_scope(Deadline(30.0)), \
            scheduler.lane_scope(scheduler.BACKGROUND):
        t = threading.Thread(target=ctx_wrap(probe))
    t.start()
    t.join(10)
    assert seen["lane"] == scheduler.BACKGROUND
    assert seen["deadline"] is not None and seen["deadline"] > 0

    # Default context: wrap is the identity (no overhead on the
    # untagged path).
    def f():
        pass
    assert ctx_wrap(f) is f
