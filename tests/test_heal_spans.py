"""A heal is traced from inside (erasure/heal.py, s3/admin.py): one
`heal-object` root per healed object, one `heal-list` root for an admin
sweep's listing step, their depth-1 phases folded into
request_phase_ms{api, phase} and mirrored on the profiler's clock, as a
request's are (obs/span.py). A local 6-drive 4+2 engine on the CPU; an
object of four block groups, so the rebuild's producer runs on the
pipeline's worker thread."""

import os
import shutil
import threading

import numpy as np
import pytest

from minio_tpu.erasure import heal as heal_mod
from minio_tpu.erasure.engine import ErasureObjects
from minio_tpu.obs import metrics2 as m2
from minio_tpu.obs import span as span_mod
from minio_tpu.obs.span import PHASES, TRACER
from minio_tpu.s3.admin import AdminHandlers
from minio_tpu.storage.xl import XLStorage

BLOCK = 256 * 1024
PHASE_MS = "minio_tpu_v2_request_phase_ms"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The rebuild's producer: on the pipeline's worker for a multi-group
# object, so each names the heal's root as its parent.
PRODUCER = ("ec.fetch", "ec.verify", "ec.decode", "heal.frame")
REBUILD = {"lock.wait", "heal.classify", "ec.meta", "ec.write",
           "ec.commit", *PRODUCER}


@pytest.fixture
def node(tmp_path, monkeypatch):
    """(engine, disks) with bucket `b` holding `obj`: 6 blocks of
    256 KiB and a tail, two blocks a reconstruct group -> 4 groups."""
    monkeypatch.setattr(TRACER, "enabled", True)
    monkeypatch.setattr(heal_mod, "HEAL_BATCH_BYTES", 2 * BLOCK)
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(6)]
    eng = ErasureObjects(disks, 4, 2, block_size=BLOCK)
    eng.make_bucket("b")
    body = np.random.default_rng(38).integers(
        0, 256, 6 * BLOCK + 99, dtype=np.uint8).tobytes()
    eng.put_object("b", "obj", body)
    yield eng, disks
    eng.shutdown()


def _wipe(disks, i: int = 1, name: str = "obj") -> None:
    shutil.rmtree(os.path.join(disks[i].root, "b", name))


def _roots(name: str) -> list[dict]:
    return [t for t in TRACER.recent(256) if t["name"] == name]


def _kids(tree: dict, name: str) -> list[dict]:
    return [c for c in tree.get("children", []) if c["name"] == name]


def _counts(api: str) -> dict[str, int]:
    out: dict[str, int] = {}
    for ser in m2.METRICS2.snapshot()[PHASE_MS]["series"]:
        if ser["labels"].get("api") == api:
            out[ser["labels"]["phase"]] = ser["count"]
    return out


def _folded(before: dict, after: dict) -> set[str]:
    """Phases the roots finished in between observed."""
    return {p for p, n in after.items() if n > before.get(p, 0)}


def _annotations(monkeypatch) -> list[tuple[str, str]]:
    """(name, thread) of every span mirrored on the profiler's clock
    from here on (Span.__enter__ -> obs/span.annotation)."""
    named: list[tuple[str, str]] = []
    real = span_mod.annotation
    monkeypatch.setattr(
        span_mod, "annotation",
        lambda name, **kw: named.append(
            (name, threading.current_thread().name)) or real(name, **kw))
    return named


def test_a_rebuild_is_one_root_holding_every_phase(node):
    eng, disks = node
    _wipe(disks)
    TRACER.reset()
    before = _counts("heal-object")
    res = eng.healer.heal_object("b", "obj")
    assert res.healed_disks == [1]
    (tree,) = _roots("heal-object")
    assert tree["tags"] == {"bucket": "b", "object": "obj"}
    classify = _kids(tree, "heal.classify")
    assert sorted(c["tags"]["dry"] for c in classify) == [0, 1]
    assert {c["tags"]["mode"] for c in _kids(tree, "lock.wait")} == {
        "read", "write"}
    # Four groups: the producer's phases once a group, a part's survivor
    # reads once, a write a group beside the intent's.
    assert len(_kids(tree, "ec.verify")) == 4
    assert len(_kids(tree, "heal.frame")) == 4
    assert len(_kids(tree, "ec.fetch")) == 1
    assert len(_kids(tree, "ec.write")) == 5
    folded = _folded(before, _counts("heal-object"))
    assert REBUILD | {"unattributed"} <= folded
    # One root finished: every phase it observed counted once.
    after = _counts("heal-object")
    assert after["unattributed"] == before.get("unattributed", 0) + 1


def test_the_producer_runs_on_the_worker_and_folds_at_depth_one(
        node, monkeypatch):
    eng, disks = node
    _wipe(disks)
    named = _annotations(monkeypatch)
    TRACER.reset()
    before = _counts("heal-object")
    eng.healer.heal_object("b", "obj")
    (tree,) = _roots("heal-object")
    on = {}
    for name, thread in named:
        on.setdefault(name, set()).add(thread)
    for name in PRODUCER:
        assert on[name] == {"pipe-heal"}, (name, on[name])
        # A child of the root itself, attached across the thread hop.
        assert _kids(tree, name)
        assert all(c["parentId"] == tree["spanId"]
                   for c in _kids(tree, name))
    assert set(PRODUCER) <= _folded(before, _counts("heal-object"))
    # The consumer's phases stay on the calling thread.
    me = threading.current_thread().name
    assert on["ec.write"] == on["ec.commit"] == {me}
    # The verify's hash nests under it on the worker: a kernel span of
    # the heal's trace at depth 2.
    verify = _kids(tree, "ec.verify")[0]
    assert any(c["name"] == "kernel.hh256"
               for c in verify.get("children", []))


def test_the_series_are_in_the_node_metrics(node):
    eng, disks = node
    _wipe(disks)
    eng.healer.heal_object("b", "obj")
    text = m2.render(m2.METRICS2.snapshot())
    for phase in ("heal.classify", "ec.fetch", "heal.frame",
                  "unattributed"):
        assert (f'{PHASE_MS}_count{{api="heal-object",phase="{phase}"}}'
                in text), phase


def test_a_healthy_object_is_one_classification_under_the_read_lock(node):
    eng, _ = node
    TRACER.reset()
    before = _counts("heal-object")
    res = eng.healer.heal_object("b", "obj")
    assert res.healed_disks == [] and res.after_ok == 6
    (tree,) = _roots("heal-object")
    assert [c["name"] for c in tree["children"]] == [
        "lock.wait", "heal.classify"]
    assert tree["children"][1]["tags"] == {"dry": 1}
    assert _folded(before, _counts("heal-object")) == {
        "lock.wait", "heal.classify", "unattributed"}


def test_a_heal_is_its_own_trace_inside_a_request(node):
    eng, disks = node
    _wipe(disks)
    TRACER.reset()
    req = TRACER.begin("PUT-object", "req-38")
    with req:
        eng.healer.heal_object("b", "obj")
    (tree,) = _roots("heal-object")
    assert tree["traceId"] != "req-38"
    (put,) = _roots("PUT-object")
    assert not put.get("children")


def test_a_lock_wait_that_times_out_is_recorded(node):
    eng, _ = node
    held, release = threading.Event(), threading.Event()

    def writer():
        with eng.ns_lock.write_locked("b", "obj"):
            held.set()
            release.wait(10)

    t = threading.Thread(target=writer)
    t.start()
    try:
        assert held.wait(10)
        TRACER.reset()
        with pytest.raises(TimeoutError):
            eng.healer.heal_object("b", "obj", lock_timeout=0.05)
    finally:
        release.set()
        t.join()
    (tree,) = _roots("heal-object")
    (wait,) = tree["children"]
    assert (wait["name"], wait["tags"]) == ("lock.wait", {"mode": "read"})
    assert wait["durationMs"] >= 40


def test_tracing_off_opens_nothing(node, monkeypatch):
    eng, disks = node
    _wipe(disks)
    monkeypatch.setattr(TRACER, "enabled", False)
    named = _annotations(monkeypatch)
    TRACER.reset()
    before = _counts("heal-object")
    res = eng.healer.heal_object("b", "obj")
    assert res.healed_disks == [1]
    list(AdminHandlers._heal_sweep(eng, "b", "", False))
    assert TRACER.recent() == []
    assert _counts("heal-object") == before
    assert not {n for n, _ in named} & set(PHASES)


def test_a_sweep_lists_under_its_own_root(node):
    eng, disks = node
    eng.put_object("b", "two", b"x" * 1000)
    _wipe(disks, name="two")
    TRACER.reset()
    before = _counts("heal-list")
    items = list(AdminHandlers._heal_sweep(eng, "b", "", False))
    assert [(i["object"], i["healedDisks"]) for i in items] == [
        ("obj", []), ("two", [1])]
    (lst,) = _roots("heal-list")
    assert lst["tags"] == {"bucket": "b"}
    assert [c["name"] for c in lst["children"]] == [
        "heal.bucket", "heal.list"]
    assert _folded(before, _counts("heal-list")) == {
        "heal.bucket", "heal.list", "unattributed"}
    # Every object is a root of its own, after the listing closed.
    objs = _roots("heal-object")
    assert sorted(t["tags"]["object"] for t in objs) == ["obj", "two"]
    assert all(t["traceId"] != lst["traceId"] for t in objs)
    end = lst["start"] + lst["durationMs"] / 1e3
    assert all(t["start"] >= end - 1e-3 for t in objs)


def test_every_heal_phase_is_on_the_profilers_clock(node, monkeypatch):
    eng, disks = node
    own = {"heal.classify", "heal.frame", "heal.bucket", "heal.list"}
    assert own <= set(PHASES)
    _wipe(disks)
    named = _annotations(monkeypatch)
    list(AdminHandlers._heal_sweep(eng, "b", "", False))
    names = {n for n, _ in named}
    # lock.wait is recorded by two clock reads, never entered: no
    # annotation, as on the PUT path.
    assert (REBUILD - {"lock.wait"}) | {"heal.bucket", "heal.list"} \
        <= names
    # The roots are not annotated: a gap on the device's clock is named
    # after the phase that covers it, not the whole heal.
    assert not {"heal-object", "heal-list"} & names


def _shape(tree: dict) -> list[str]:
    """A root and its distinct depth-1 phases in the order first
    opened (phase `other` left out)."""
    out = [tree["name"]]
    for c in tree.get("children", []):
        if c["name"] in PHASES and "  " + c["name"] not in out:
            out.append("  " + c["name"])
    return out


def test_docs_heal_span_trees_are_generated_from_a_real_sweep(node):
    eng, disks = node
    _wipe(disks)
    TRACER.reset()
    list(AdminHandlers._heal_sweep(eng, "b", "", False))
    (lst,) = _roots("heal-list")
    (obj,) = _roots("heal-object")
    block = "\n".join(_shape(lst) + [""] + _shape(obj))
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        doc = f.read()
    begin, end = "<!-- heal-span-trees:begin -->\n```\n", \
        "\n```\n<!-- heal-span-trees:end -->"
    assert begin in doc and end in doc
    have = doc.split(begin, 1)[1].split(end, 1)[0]
    assert have == block, (
        "docs/observability.md's heal span trees drifted from what a "
        "real sweep produces; paste this between the markers:\n" + block)


def test_a_rebuilt_object_reads_back(node):
    """The spans change no byte: the healed copy serves a read that
    needs it."""
    eng, disks = node
    want, _ = eng.get_object("b", "obj")
    _wipe(disks)
    eng.healer.heal_object("b", "obj")
    for i in (2, 3):
        _wipe(disks, i)
    got, _ = eng.get_object("b", "obj")
    assert got == want
