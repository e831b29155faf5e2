"""One request timeline (PR 25): the span tree reduced to per-phase
time when its root finishes (obs/span.reduce_phases ->
request_phase_ms{api, phase}), the spans that fill its holes on a real
PUT and GET, the device dispatch's prep | enqueue | wait split and its
depth, and the process CPU counter."""

import glob
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from minio_tpu.erasure.engine import ErasureObjects
from minio_tpu.obs import metrics2 as m2
from minio_tpu.obs.span import (MAX_ROOT_CHILDREN, PHASES, TRACER, Span,
                                reduce_phases)
from minio_tpu.s3.client import S3Client
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.xl import XLStorage

ACCESS, SECRET = "phaseadmin", "phaseadmin-secret"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASE_MS = "minio_tpu_v2_request_phase_ms"


# -- the reduction, on hand-built trees ------------------------------------


def _tree(dur_ms: float, kids: list[tuple]) -> Span:
    """A finished root of `dur_ms` whose depth-1 children are
    (name, start_ms, end_ms) on the root's clock."""
    root = TRACER.begin("PUT-object", "T")
    root.duration_ms = dur_ms
    for name, a, b in kids:
        TRACER.record(name, root, root._t0 + a / 1e3, root._t0 + b / 1e3)
    return root


def test_sequential_children_sum_to_the_root():
    got = reduce_phases(_tree(100.0, [("auth.sigv4", 0, 10),
                                      ("ec.encode", 10, 40),
                                      ("ec.write", 40, 90),
                                      ("ec.commit", 90, 100)]))
    assert got["unattributed"] == pytest.approx(0.0, abs=1e-6)
    named = sum(v for k, v in got.items() if k != "unattributed")
    # No overlapping phases: the phases ARE the root, to 1%.
    assert named == pytest.approx(100.0, rel=0.01)
    assert got["ec.write"] == pytest.approx(50.0)


def test_overlapping_phases_reduce_by_union_not_sum():
    # A streamed PUT: two batches, encode of batch 2 under write of 1.
    got = reduce_phases(_tree(100.0, [("ec.encode", 0, 30),
                                      ("ec.write", 30, 70),
                                      ("ec.encode", 30, 60),
                                      ("ec.write", 70, 100)]))
    assert got["ec.encode"] == pytest.approx(60.0)
    assert got["ec.write"] == pytest.approx(70.0)
    assert got["unattributed"] == pytest.approx(0.0, abs=1e-6)
    # Sum over phases >= root - unattributed, always.
    assert got["ec.encode"] + got["ec.write"] >= 100.0 - 1e-6
    # Two spans of one name that overlap each other count once.
    got = reduce_phases(_tree(50.0, [("ec.fetch", 0, 30),
                                     ("ec.fetch", 20, 40)]))
    assert got["ec.fetch"] == pytest.approx(40.0)


def test_unattributed_is_root_minus_union():
    got = reduce_phases(_tree(100.0, [("auth.sigv4", 5, 15),
                                      ("ec.meta", 10, 30),
                                      ("door.send", 60, 80)]))
    assert got["unattributed"] == pytest.approx(100.0 - 25.0 - 20.0)


def test_a_phase_before_the_root_is_reported_but_not_subtracted():
    # door.hop ends where the root starts.
    got = reduce_phases(_tree(40.0, [("door.hop", -12, 0),
                                     ("ec.meta", 0, 10)]))
    assert got["door.hop"] == pytest.approx(12.0)
    assert got["unattributed"] == pytest.approx(30.0)


def test_unknown_depth1_name_lands_in_other():
    got = reduce_phases(_tree(10.0, [("select.scan", 0, 4),
                                     ("cache.lookup", 4, 6)]))
    assert got["other"] == pytest.approx(6.0)
    assert set(got) == {"other", "unattributed"}
    assert "other" not in PHASES and "unattributed" not in PHASES


def test_grafted_dicts_and_deeper_spans_are_not_reduced():
    root = _tree(20.0, [("ec.write", 0, 10)])
    root.add_child({"name": "ec.commit", "durationMs": 9.0,
                    "start": root.start})
    # Depth 2: a disk span under ec.write.
    TRACER.record("ec.commit", root.children[0], root._t0 + 0.010,
                  root._t0 + 0.020)
    got = reduce_phases(root)
    assert "ec.commit" not in got
    assert got["unattributed"] == pytest.approx(10.0)


def test_child_overflow_is_dropped_from_the_tree_and_still_reduced():
    root = _tree(1000.0, [("door.send", i, i + 0.5)
                          for i in range(MAX_ROOT_CHILDREN + 30)])
    assert root.dropped == 30
    # The TREE (slow log, trace endpoint) stops at the cap and says so;
    got = root.to_dict()
    assert len(got["children"]) == MAX_ROOT_CHILDREN
    assert got["droppedChildren"] == 30
    # the phases do not read the tree: every span that closed counts.
    got = reduce_phases(root)
    assert got["door.send"] == pytest.approx((MAX_ROOT_CHILDREN + 30) * 0.5)
    assert got["unattributed"] == pytest.approx(
        1000.0 - (MAX_ROOT_CHILDREN + 30) * 0.5)


def _walk_reduce(root: Span, kids: list[tuple]) -> dict[str, float]:
    """The reduction as it was before the fold (PR 25), without its cap
    of 256: per name the union of ALL the (name, start_ms, end_ms)
    intervals, sorted, one pass."""
    def union(ivs):
        total, end = 0.0, float("-inf")
        for lo, hi in sorted(ivs):
            if hi > end:
                total += hi - max(lo, end)
                end = hi
        return total

    t0 = root._t0
    t1 = t0 + root.duration_ms / 1e3
    by, inside = {}, []
    for name, a, b in kids:
        # The ends as Tracer.record keeps them: start + duration.
        lo = t0 + a / 1e3
        hi = lo + max(0.0, (t0 + b / 1e3) - lo) * 1e3 / 1e3
        by.setdefault(name if name in PHASES else "other",
                      []).append((lo, hi))
        if hi > t0 and lo < t1:
            inside.append((max(lo, t0), min(hi, t1)))
    out = {name: union(ivs) * 1e3 for name, ivs in by.items()}
    out["unattributed"] = max(0.0, root.duration_ms - union(inside) * 1e3)
    return out


def _random_kids(n: int, seed: int, span_ms: float) -> list[tuple]:
    """n depth-1 spans over [-5, span_ms + 5) ms: seven names (one
    outside PHASES), lengths 0..12 ms, so most overlap a neighbour of
    another name and many one of their own; closed in no time order."""
    rng = np.random.default_rng(seed)
    names = ["ec.fetch", "ec.verify", "door.send", "door.hop", "ec.join",
             "mpu.load", "select.scan"]
    kids = []
    for _ in range(n):
        a = float(rng.uniform(-5.0, span_ms))
        kids.append((names[int(rng.integers(len(names)))], a,
                     a + float(rng.uniform(0.0, 12.0))))
    return kids


def test_a_root_of_1000_phases_reduces_exactly():
    """A whole read of a 1 GiB object in 64 MiB parts is 112 block
    groups of about six depth-1 spans: the tree keeps 256, the fold
    all."""
    kids = _random_kids(1000, 32, 900.0)
    root = _tree(900.0, kids)
    assert root.dropped == 1000 - MAX_ROOT_CHILDREN
    got, want = reduce_phases(root), _walk_reduce(root, kids)
    assert set(got) == set(want) == {
        "ec.fetch", "ec.verify", "door.send", "door.hop", "ec.join",
        "mpu.load", "other", "unattributed"}
    for name in want:
        # Merged on arrival against summed in sorted order: the same
        # number but for the order of the float additions.
        assert got[name] == pytest.approx(want[name], rel=1e-9, abs=1e-9)
    # Sparse, too: unattributed is most of the root.
    kids = _random_kids(300, 33, 60_000.0)
    root = _tree(60_000.0, kids)
    got, want = reduce_phases(root), _walk_reduce(root, kids)
    assert want["unattributed"] > 50_000.0
    for name in want:
        assert got[name] == pytest.approx(want[name], rel=1e-9, abs=1e-9)


@pytest.mark.parametrize("n", [1, 7, 40, MAX_ROOT_CHILDREN - 1])
def test_under_the_cap_the_fold_reads_what_the_tree_walk_read(n):
    kids = _random_kids(n, 100 + n, 300.0)
    root = _tree(300.0, kids)
    assert root.dropped == 0 and len(root.children) == n
    got, want = reduce_phases(root), _walk_reduce(root, kids)
    assert set(got) == set(want)
    for name in want:
        assert got[name] == pytest.approx(want[name], rel=1e-12, abs=1e-9)


def test_the_fold_takes_spans_closed_on_other_threads():
    root = TRACER.begin("PUT-object", "T-threads")
    root.__enter__()

    def work():
        for _ in range(200):
            with TRACER.span("ec.encode", parent=root):
                pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for _ in range(200):
        with TRACER.span("ec.write"):
            pass
    for t in threads:
        t.join()
    root.finish()
    got = reduce_phases(root)
    assert 0.0 < got["ec.encode"] <= root.duration_ms + 1e-6
    assert 0.0 < got["ec.write"] <= root.duration_ms + 1e-6
    assert root.dropped == 1000 - MAX_ROOT_CHILDREN
    # A deeper span is not a phase, wherever it closes.
    with root._fold_mu:
        assert set(root._fold) == {"", "ec.encode", "ec.write"}


def test_root_finish_observes_each_phase_once_per_request():
    lbl = {"api": "phase-unit", "phase": "ec.encode"}
    una = {"api": "phase-unit", "phase": "unattributed"}
    b_enc, b_una = m2.METRICS2.get(PHASE_MS, lbl), \
        m2.METRICS2.get(PHASE_MS, una)
    root = TRACER.begin("phase-unit", "U1")
    root.__enter__()
    for _ in range(2):
        with TRACER.span("ec.encode"):
            time.sleep(0.002)
    root.finish()
    a_enc, a_una = m2.METRICS2.get(PHASE_MS, lbl), \
        m2.METRICS2.get(PHASE_MS, una)
    assert a_enc[1] == b_enc[1] + 1 and a_una[1] == b_una[1] + 1
    assert a_enc[0] - b_enc[0] >= 4.0
    # Tracing off: no root, nothing observed.
    TRACER.enabled = False
    try:
        assert TRACER.begin("phase-unit", "U2") is None
    finally:
        TRACER.enabled = True
    assert m2.METRICS2.get(PHASE_MS, una)[1] == a_una[1]


# -- a real PUT and a real GET through the front door ----------------------


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    root = tmp_path_factory.mktemp("phasedisks")
    disks = [XLStorage(str(root / f"d{i}")) for i in range(4)]
    layer = ErasureObjects(disks, block_size=2 * 1024 * 1024)
    srv = S3Server(layer, ACCESS, SECRET)
    port = srv.start()
    c = S3Client("127.0.0.1", port, ACCESS, SECRET)
    assert c.make_bucket("phases").status == 200
    yield srv, c, str(root)
    srv.stop()


BODY = np.random.default_rng(25).integers(
    0, 256, 12 * 1024 * 1024 + 4321, dtype=np.uint8).tobytes()


def _phase_counts(api: str) -> dict[str, int]:
    snap = m2.METRICS2.snapshot()[PHASE_MS]
    return {s["labels"]["phase"]: s["count"] for s in snap["series"]
            if s["labels"]["api"] == api}


def _request_tree(api: str, path: str) -> dict:
    """The newest finished tree of this api and path (a streamed GET's
    root finishes on the drain task, after the client has its bytes)."""
    deadline = time.monotonic() + 5.0
    while True:
        for t in reversed(TRACER.recent(64)):
            if t["name"] == api and t["tags"].get("path") == path:
                return t
        assert time.monotonic() < deadline, f"no {api} tree for {path}"
        time.sleep(0.02)


def _names(node: dict, out=None) -> list[str]:
    out = [] if out is None else out
    out.append(node["name"])
    for c in node.get("children", []):
        _names(c, out)
    return out


def _reduced(tree: dict) -> dict[str, float]:
    """The phase arithmetic again, on the published dict (wall-clock
    `start` is exact enough at the 15% the test asks for)."""
    t0, dur = tree["start"], tree["durationMs"]
    inside = []
    for c in tree.get("children", []):
        lo = (c["start"] - t0) * 1e3
        hi = lo + c["durationMs"]
        if hi > 0 and lo < dur:
            inside.append((max(lo, 0.0), min(hi, dur)))
    total, end = 0.0, float("-inf")
    for lo, hi in sorted(inside):
        if hi > end:
            total += hi - max(lo, end)
            end = hi
    return {"unattributed": max(0.0, dur - total), "root": dur}


def test_put_fills_its_phases(server):
    _, c, _ = server
    before = _phase_counts("PUT-object")
    shares = []
    for i in range(3):
        r = c.put_object("phases", f"put-{i}", BODY)
        assert r.status == 200
        tree = _request_tree("PUT-object", f"/phases/put-{i}")
        red = _reduced(tree)
        shares.append(red["unattributed"] / red["root"])
    after = _phase_counts("PUT-object")
    for phase in ("door.hop", "door.recv", "qos.wait", "auth.sigv4",
                  "ec.encode", "ec.write", "lock.wait", "ec.commit",
                  "unattributed"):
        assert after.get(phase, 0) >= before.get(phase, 0) + 3, phase
    names = _names(tree)
    assert "kernel.hh256" in names and "kernel.rs_encode" in names
    # The best of three: one stalled request on a loaded box is not
    # the instrument's blind spot.
    assert min(shares) < 0.15, shares


def test_get_fills_its_phases(server):
    _, c, _ = server
    assert c.put_object("phases", "get-me", BODY).status == 200
    before = _phase_counts("GET-object")
    shares = []
    for _ in range(3):
        r = c.get_object("phases", "get-me")
        assert r.status == 200 and r.body == BODY
        tree = _request_tree("GET-object", "/phases/get-me")
        TRACER.reset()
        red = _reduced(tree)
        shares.append(red["unattributed"] / red["root"])
    after = _phase_counts("GET-object")
    for phase in ("door.hop", "qos.wait", "auth.sigv4", "lock.wait",
                  "ec.meta", "ec.fetch", "ec.verify", "ec.join",
                  "door.send", "unattributed"):
        assert after.get(phase, 0) >= before.get(phase, 0) + 3, phase
    assert after.get("ec.decode", 0) == before.get("ec.decode", 0)
    names = _names(tree)
    assert "kernel.hh256" in names
    # ec.shard_read hangs under its group's ec.fetch now.
    fetch = next(ch for ch in tree["children"] if ch["name"] == "ec.fetch")
    assert "ec.shard_read" in _names(fetch)
    assert min(shares) < 0.15, shares


def test_get_with_a_lost_shard_adds_ec_decode(server):
    _, c, root = server
    assert c.put_object("phases", "lose-one", BODY).status == 200
    # Drop the copy of the drive that holds data shard 1.
    for d in range(4):
        meta = os.path.join(root, f"d{d}", "phases", "lose-one", "xl.meta")
        with open(meta, "rb") as f:
            idx = json.loads(f.read())["versions"][0]["erasure"]["index"]
        if idx == 1:
            for part in glob.glob(os.path.join(
                    root, f"d{d}", "phases", "lose-one", "*", "part.1")):
                os.remove(part)
    before = _phase_counts("GET-object").get("ec.decode", 0)
    TRACER.reset()
    r = c.get_object("phases", "lose-one")
    assert r.status == 200 and r.body == BODY
    tree = _request_tree("GET-object", "/phases/lose-one")
    assert _phase_counts("GET-object").get("ec.decode", 0) == before + 1
    dec = next(ch for ch in tree["children"] if ch["name"] == "ec.decode")
    assert "kernel.rs_decode" in _names(dec)


def test_one_upload_of_n_parts_in_the_multipart_series(server):
    """multipart_op_ms 1 / N / 1, a part's engine phases beside a
    PUT's (N times a phase), and mpu.* among the request phases."""
    from tests.test_multipart_storage_class import (_complete, _initiate,
                                                    _upload)
    srv, c, _ = server
    srv.layer.multipart.min_part_size = 1024
    n_parts = 3
    part = BODY[:3 * 1024 * 1024 + 17]

    def ops():
        return {op: _hist("minio_tpu_v2_multipart_op_ms", {"op": op})[1]
                for op in ("initiate", "part", "complete")}

    def put_phases():
        return {ph: _hist("minio_tpu_v2_put_phase_duration_ms",
                          {"phase": ph})[1]
                for ph in ("engine_encode", "engine_write", "engine_commit")}

    b_ops, b_put = ops(), put_phases()
    b_bytes = m2.METRICS2.get("minio_tpu_v2_multipart_part_bytes_total")
    b_post, b_part = _phase_counts("POST-object"), _phase_counts("PUT-object")
    path = c._key_path("phases", "mpu-series")
    uid = _initiate(c, path)
    _complete(c, path, uid, _upload(c, path, uid, [part] * n_parts))
    a_ops, a_put = ops(), put_phases()
    assert {op: a_ops[op] - b_ops[op] for op in a_ops} == {
        "initiate": 1, "part": n_parts, "complete": 1}
    assert {ph: a_put[ph] - b_put[ph] for ph in a_put} == {
        ph: n_parts for ph in a_put}
    assert m2.METRICS2.get("minio_tpu_v2_multipart_part_bytes_total") \
        == b_bytes + n_parts * len(part)
    a_post, a_part = _phase_counts("POST-object"), _phase_counts("PUT-object")
    # Complete: the upload record, the part listing (twice, §7), the
    # link loop, the write lock, the renames.
    for phase in ("mpu.load", "mpu.list", "mpu.stage", "lock.wait",
                  "ec.commit", "unattributed"):
        assert a_post.get(phase, 0) >= b_post.get(phase, 0) + 1, phase
    assert a_post["unattributed"] == b_post.get("unattributed", 0) + 2
    for phase in ("mpu.load", "door.recv", "ec.encode", "ec.write",
                  "ec.commit"):
        assert a_part.get(phase, 0) == b_part.get(phase, 0) + n_parts, phase
    tree = _request_tree("POST-object", "/phases/mpu-series")
    names = [ch["name"] for ch in tree["children"]]
    assert names.count("mpu.stage") == names.count("ec.commit") == 4
    assert names.count("mpu.list") == 2
    assert c.get_object("phases", "mpu-series").body == part * n_parts


# -- the device dispatch ----------------------------------------------------


def _hist(name: str, want: dict) -> tuple[float, int]:
    s, n = 0.0, 0
    for ser in m2.METRICS2.snapshot()[name]["series"]:
        if all(ser["labels"].get(k) == v for k, v in want.items()):
            s, n = s + ser["sum"], n + ser["count"]
    return s, n


def _dispatch_deltas(kernel: str, fn):
    from minio_tpu.ops.batching import attempt_backend
    lbl = {"kernel": kernel, "backend": attempt_backend()}
    names = ("prep", "enqueue", "wait")
    b = {p: _hist("minio_tpu_v2_kernel_dispatch_phase_ms",
                  {**lbl, "phase": p}) for p in names}
    b_wall = _hist("minio_tpu_v2_kernel_dispatch_ms", lbl)
    b_depth = _hist("minio_tpu_v2_kernel_dispatch_depth", lbl)
    fn()
    ph = {}
    for p in names:
        s, n = _hist("minio_tpu_v2_kernel_dispatch_phase_ms",
                     {**lbl, "phase": p})
        assert n == b[p][1] + 1, p
        ph[p] = s - b[p][0]
    wall = _hist("minio_tpu_v2_kernel_dispatch_ms", lbl)
    depth = _hist("minio_tpu_v2_kernel_dispatch_depth", lbl)
    assert wall[1] == b_wall[1] + 1 and depth[1] == b_depth[1] + 1
    return ph, wall[0] - b_wall[0], depth[0] - b_depth[0]


@pytest.mark.parametrize("kernel", ["hh256", "rs_encode", "rs_decode"])
def test_dispatch_phases_add_up_to_the_dispatch(kernel):
    rng = np.random.default_rng(7)
    if kernel == "hh256":
        from minio_tpu.ops import hh256_tpu
        rows = rng.integers(0, 256, (8, 512 * 1024 + 22), dtype=np.uint8)
        call = lambda: hh256_tpu.hash_chunks(rows)  # noqa: E731
    elif kernel == "rs_encode":
        from minio_tpu.ops import rs_tpu
        data = rng.integers(0, 256, (8, 4, 256 * 1024), dtype=np.uint8)
        call = lambda: rs_tpu.encode_batch(data, 4, 2)  # noqa: E731
    else:
        from minio_tpu.ops import batching
        stack = rng.integers(0, 256, (8, 4, 256 * 1024), dtype=np.uint8)
        call = lambda: batching._device_reconstruct(  # noqa: E731
            stack, 4, 2, (1, 2, 3, 4), (0,))
    call()                                  # compile, place the matrix
    ph, wall_ms, depth = _dispatch_deltas(kernel, call)
    assert depth == 0                       # a lone dispatch
    assert all(v >= 0 for v in ph.values())
    # kernel_dispatch_ms is the `timed()` region, kept where it was:
    # enqueue + wait. prep (packing) lies before it at every site.
    assert ph["enqueue"] + ph["wait"] == pytest.approx(wall_ms, rel=0.05)
    if kernel != "hh256":
        # A placed matrix leaves RS no packing to do: all three add up.
        assert sum(ph.values()) == pytest.approx(wall_ms, rel=0.05)
    else:
        assert ph["prep"] > 0               # the .copy().view() pack


def test_an_hh256_placement_over_the_mesh_is_prep(monkeypatch):
    """The rows' placement over the serving mesh lies before `timed()`,
    and so in `prep`: a slow placement moves neither `enqueue` nor
    `kernel_dispatch_ms`, and those two still add up."""
    import jax

    from minio_tpu.ops import batching, hh256_tpu
    rows = np.random.default_rng(8).integers(
        0, 256, (8, 512 * 1024 + 22), dtype=np.uint8)
    hh256_tpu.hash_chunks(rows)             # compile
    m = batching.serving_mesh()
    assert m is not None and hh256_tpu.bucket_rows(8) % m.size == 0
    real = jax.device_put
    placed = []

    def slow_put(*a, **kw):
        time.sleep(0.1)
        placed.append(1)
        return real(*a, **kw)
    monkeypatch.setattr(hh256_tpu.jax, "device_put", slow_put)
    ph, wall_ms, _ = _dispatch_deltas(
        "hh256", lambda: hh256_tpu.hash_chunks(rows))
    assert len(placed) == 2                 # words, remainder packets
    assert ph["prep"] >= 200
    assert ph["enqueue"] < 100
    assert ph["enqueue"] + ph["wait"] == pytest.approx(wall_ms, rel=0.05)


def test_dispatch_depth_counts_dispatches_in_flight():
    from minio_tpu.obs.kernel_stats import dispatch
    entered, release = threading.Event(), threading.Event()

    def first():
        with dispatch("hh256", rows=1, nbytes=1) as ph:
            ph.phase("enqueue")
            entered.set()
            release.wait(5)
            ph.phase("wait")

    t = threading.Thread(target=first)
    t.start()
    assert entered.wait(5)
    with dispatch("hh256", rows=1, nbytes=1) as second:
        second.phase("enqueue")
        second.phase("wait")
    release.set()
    t.join()
    assert second.depth >= 1
    with dispatch("hh256", rows=1, nbytes=1) as lone:
        pass
    assert lone.depth == 0
    # A dispatch that raises leaves the count where it was.
    with pytest.raises(RuntimeError):
        with dispatch("hh256", rows=1, nbytes=1):
            raise RuntimeError("device lost")
    with dispatch("hh256", rows=1, nbytes=1) as after:
        pass
    assert after.depth == 0


# -- the process counter, the registry, the lint ---------------------------


def test_process_cpu_seconds_is_monotone_and_rendered():
    name = "minio_tpu_v2_process_cpu_seconds_total"

    def read() -> float:
        return m2.METRICS2.snapshot()[name]["series"][0]["value"]

    a = read()
    t0 = time.process_time()
    while time.process_time() - t0 < 0.05:
        pass
    b = read()
    assert b >= a + 0.04
    assert read() >= b
    assert f"\n{name} " in m2.render(m2.METRICS2.snapshot())


def test_new_series_registered_and_the_old_one_gone():
    have = m2.METRICS2.registered_names()
    assert {PHASE_MS, "minio_tpu_v2_kernel_dispatch_phase_ms",
            "minio_tpu_v2_kernel_dispatch_depth",
            "minio_tpu_v2_process_cpu_seconds_total"} <= have
    assert "minio_tpu_v2_kernel_wall_seconds_total" not in have
    # The label set is bounded by construction.
    phases = {s["labels"]["phase"]
              for s in m2.METRICS2.snapshot()[PHASE_MS]["series"]}
    assert phases <= set(PHASES) | {"other", "unattributed"}


def test_mtpu_lint_still_exits_zero():
    out = subprocess.run(
        [sys.executable, "-m", "tools.mtpu_lint", "minio_tpu/", "tools/"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]


# -- docs/observability.md's span trees are these requests' ----------------


def _shape(node: dict, depth: int = 0, out=None) -> list[str]:
    """A tree's nesting by NAME: each parent's distinct child names
    (counts and timings vary, nesting does not)."""
    out = [] if out is None else out
    out.append("  " * depth + node["name"])
    seen: dict[str, dict] = {}
    for c in node.get("children", []):
        if depth == 0 and c["name"] not in PHASES:
            continue        # phase "other": a cache-miss disk.read_all
        # Same-named siblings merge: the union of their children.
        seen.setdefault(c["name"], {"name": c["name"], "children": []})[
            "children"].extend(c.get("children", []))
    # The request's own timeline keeps its order; below it fan-out
    # workers race, so names sort.
    kids = list(seen.values()) if depth == 0 else \
        sorted(seen.values(), key=lambda c: c["name"])
    for c in kids:
        _shape(c, depth + 1, out)
    return out


def test_docs_span_trees_are_generated_from_real_requests(server):
    _, c, _ = server
    # Once before, so bucket metadata is cached whatever ran earlier.
    assert c.put_object("phases", "doc", BODY).status == 200
    assert c.get_object("phases", "doc").status == 200
    time.sleep(0.2)
    TRACER.reset()
    assert c.put_object("phases", "doc", BODY).status == 200
    put = _request_tree("PUT-object", "/phases/doc")
    assert c.get_object("phases", "doc").body == BODY
    get = _request_tree("GET-object", "/phases/doc")
    block = "\n".join(_shape(put) + [""] + _shape(get))
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        doc = f.read()
    begin, end = "<!-- span-trees:begin -->\n```\n", \
        "\n```\n<!-- span-trees:end -->"
    assert begin in doc and end in doc
    have = doc.split(begin, 1)[1].split(end, 1)[0]
    assert have == block, (
        "docs/observability.md's span trees drifted from what a real "
        "PUT and GET produce; paste this between the markers:\n" + block)
