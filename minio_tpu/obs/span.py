"""Span-based request tracing: one tree per request, keyed by the
request id the S3 front end already mints (x-amz-request-id).

The reference traces per-handler wall time only (httpTrace,
cmd/handler-utils.go:349); measurement-first EC papers (arXiv:1709.05365,
arXiv:1504.07038) show per-phase, per-node attribution is what turns EC
tuning into engineering — so every layer here opens child spans: the S3
handler (root), erasure engine phases, TPU kernel invocations, and each
per-disk storage call (local and RPC). The trace id crosses the peer RPC
boundary in a header (rpc/transport.py) and server-side spans come back
in the response, so a distributed PUT stitches into ONE tree.

Cost discipline (acceptance: <= 5% on the bench PUT path):
- no active trace -> ``TRACER.span()`` returns a shared no-op context
  manager after one contextvar read;
- spans are plain objects, two perf_counter() calls each;
- children per span are capped (dropped tail is counted, never grown);
- completed traces land in a bounded ring, oldest evicted.
"""

from __future__ import annotations

import contextvars
import itertools
import os
import sys
import threading
import time
import weakref
from collections import deque

_current: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "minio_tpu_span", default=None)

# Per-span child cap: a streamed multi-GiB PUT must not grow its trace
# without bound — the tail is dropped and counted in `dropped`.
MAX_CHILDREN = 64

# A ROOT holds the request's phases (depth 1), several per streamed
# group (ec.fetch, ec.verify, door.hop x2, door.send): its cap is
# wider. The cap bounds the TREE (slow log, trace endpoint); the
# per-phase times do not read the tree: a root folds each depth-1
# span's interval as it closes (Span._fold_phase), dropped or kept.
MAX_ROOT_CHILDREN = 256

# Per-span event cap (QoS shed/deadline markers): same bounding rule.
MAX_EVENTS = 16


# The per-request PHASES: span names that, opened at depth 1 under a
# request's root, are reduced to minio_tpu_v2_request_phase_ms{api,
# phase} when the root finishes (reduce_phases). A fixed tuple
# keeps the label set bounded; any other depth-1 name folds into
# "other", and what no depth-1 span covers is "unattributed". The
# heal's own roots (erasure/heal.py heal-object, s3/admin.py heal-list)
# reuse the GET / PUT names where they do that work; heal.* is theirs.
PHASES = ("door.hop", "door.recv", "auth.sigv4", "qos.wait", "lock.wait",
          "ec.meta", "ec.fetch", "ec.verify", "ec.decode", "ec.join",
          "ec.encode", "ec.write", "ec.commit", "door.send",
          "mpu.load", "mpu.list", "mpu.stage",
          "heal.classify", "heal.frame", "heal.bucket", "heal.list")
_PHASE_SET = frozenset(PHASES)


def annotation(name: str, **kw):
    """A jax.profiler.TraceAnnotation of `name`: the program's own span
    on the profiler's clock, beside `XLA Modules`, while a profiler
    session runs; an atomic load otherwise. A no-op context where this
    process never imported JAX."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NOOP
    return jax.profiler.TraceAnnotation(name, **kw)


def _merge(ivs: list, lo: float, hi: float) -> None:
    """Insert [lo, hi] into `ivs`, a sorted list of disjoint (lo, hi)
    pairs, merging it with every pair it overlaps or touches. Phases
    close in near time order, so the walk from the end is short."""
    i = len(ivs)
    while i and ivs[i - 1][0] > hi:
        i -= 1
    j = i
    while j and ivs[j - 1][1] >= lo:
        j -= 1
        lo, hi = min(lo, ivs[j][0]), max(hi, ivs[j][1])
    ivs[j:i] = [(lo, hi)]


def reduce_phases(root: "Span") -> dict[str, float]:
    """{phase: ms} of one finished request, from the root's fold: per
    PHASES name the union of its depth-1 spans' intervals on the spans'
    monotonic clock (a streamed PUT overlaps ec.encode with ec.write,
    so lengths are unions, not sums), plus "unattributed" = root
    duration minus the union of ALL depth-1 spans inside the root's
    interval. Every depth-1 span that closed before the root counts,
    whatever the tree kept of it; grafted remote dicts and deeper spans
    are not read."""
    t0 = root._t0
    t1 = t0 + root.duration_ms / 1e3
    with root._fold_mu:
        by = {name: list(ivs) for name, ivs in root._fold.items()}
    # door.hop ends where the root starts: a phase, but no part of
    # the root's own interval.
    inside = sum(min(hi, t1) - max(lo, t0) for lo, hi in by.pop("", ())
                 if hi > t0 and lo < t1)
    out = {name: sum(hi - lo for lo, hi in ivs) * 1e3
           for name, ivs in by.items()}
    out["unattributed"] = max(0.0, root.duration_ms - inside * 1e3)
    return out


class _Noop:
    """Shared do-nothing span context (the untraced fast path)."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


class Span:
    """One timed operation in a trace tree.

    Also a context manager: entering makes it the thread's current span
    (children attach via the contextvar), exiting records the duration
    and restores the previous current span.
    """

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "start",
                 "duration_ms", "tags", "children", "events", "dropped",
                 "root", "_t0", "_token", "_tracer", "_done", "_ann",
                 "_fold", "_fold_mu", "__weakref__")

    _ids = itertools.count(1)    # next() is atomic: no lock on the hot path

    def __init__(self, name: str, trace_id: str, parent_id: str = "",
                 tags: dict | None = None, tracer: "Tracer | None" = None):
        self.span_id = f"{next(Span._ids):x}"
        self.trace_id = trace_id
        self.parent_id = parent_id
        self.name = name
        self.start = time.time()
        self.duration_ms = 0.0
        self.tags = tags or {}
        self.children: list = []  # Span | dict (grafted remote spans)
        self.events: list = []    # point-in-time markers (QoS shed, ...)
        self.dropped = 0
        # weakref to the trace's root span, handed down where a child
        # attaches (a strong one would make every tree a cycle).
        self.root = None
        self._t0 = time.perf_counter()
        self._token = None
        self._tracer = tracer
        self._done = False
        self._ann = None
        # A ROOT's fold (Tracer.begin): per phase name, and under ""
        # for all of them, the merged intervals of the depth-1 spans
        # closed so far; what reduce_phases reads.
        self._fold = None
        self._fold_mu = None

    # -- tree assembly -------------------------------------------------

    def add_child(self, child) -> None:
        """Attach a Span or an already-serialized span dict (remote).
        list.append is GIL-atomic, safe from parallel_map workers; the
        length check here is advisory under concurrency (two workers
        may both pass it) — to_dict() enforces the cap exactly."""
        if len(self.children) >= self._cap():
            self.dropped += 1
            return
        self.children.append(child)

    def _cap(self) -> int:
        return MAX_CHILDREN if self.parent_id else MAX_ROOT_CHILDREN

    def _fold_phase(self, name: str, lo: float, hi: float) -> None:
        """A depth-1 span [lo, hi] of this ROOT has closed (on any
        thread: the pipeline's worker closes ec.encode while the
        request's own closes ec.write)."""
        if name not in _PHASE_SET:
            name = "other"
        with self._fold_mu:
            _merge(self._fold.setdefault(name, []), lo, hi)
            _merge(self._fold.setdefault("", []), lo, hi)

    def add_event(self, name: str, **attrs) -> None:
        """Record a point-in-time marker on this span (admission shed,
        deadline expiry). Bounded like children; append is GIL-atomic."""
        if len(self.events) >= MAX_EVENTS:
            self.dropped += 1
            return
        ev = {"name": name, "time": time.time()}
        if attrs:
            ev.update(attrs)
        self.events.append(ev)

    def to_dict(self) -> dict:
        d = {
            "traceId": self.trace_id, "spanId": self.span_id,
            "parentId": self.parent_id, "name": self.name,
            "start": self.start,
            "durationMs": round(self.duration_ms, 3),
        }
        if self.tags:
            d["tags"] = dict(self.tags)
        if self.events:
            d["events"] = [dict(e) for e in self.events[:MAX_EVENTS]]
        kids = self.children
        dropped = self.dropped
        cap = self._cap()
        if len(kids) > cap:  # racy appends past the cap
            dropped += len(kids) - cap
            kids = kids[:cap]
        if kids:
            d["children"] = [c if isinstance(c, dict) else c.to_dict()
                             for c in kids]
        if dropped:
            d["droppedChildren"] = dropped
        return d

    # -- context management --------------------------------------------

    def __enter__(self) -> "Span":
        self._token = _current.set(self)
        if self.name in _PHASE_SET or self.name.startswith("kernel."):
            # Mirror phases and kernel.* spans (with their tags) onto
            # the profiler's clock, so an idle gap of the device reads
            # `ec.write`, not a JAX internal.
            self._ann = annotation(self.name, **self.tags)
            self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self.finish()
        return False

    def detach_context(self) -> None:
        """Reset the contextvar token WITHOUT finishing the span — for
        handoff points where the entering thread returns to a pool
        while the span stays open (the async front door's streaming
        responses: the drain task carries the span in a copied context
        and calls finish() later, from a context where resetting the
        original token would be illegal)."""
        if self._token is not None:
            _current.reset(self._token)
            self._token = None

    def finish(self) -> dict | None:
        """Close the span; for a ROOT span returns the completed trace
        tree (and lands it in the tracer's ring)."""
        if self._done:
            return None
        self._done = True
        if self._token is not None:
            _current.reset(self._token)
            self._token = None
        self.duration_ms = (time.perf_counter() - self._t0) * 1e3
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self.parent_id:
            root = self.root() if self.root is not None else None
            if root is not None and root.span_id == self.parent_id:
                root._fold_phase(self.name, self._t0,
                                 self._t0 + self.duration_ms / 1e3)
        elif self._tracer is not None:
            return self._tracer._complete(self)
        return None


class Tracer:
    """Process-wide span factory + bounded ring of completed traces."""

    RING_SIZE = 256

    def __init__(self):
        self.enabled = os.environ.get("MINIO_TPU_TRACE", "on") != "off"
        self._ring: deque = deque(maxlen=self.RING_SIZE)
        self._mu = threading.Lock()

    # -- span creation -------------------------------------------------

    @staticmethod
    def current() -> Span | None:
        return _current.get()

    def begin(self, name: str, trace_id: str, **tags) -> Span | None:
        """Open a ROOT span (no context entered yet; pair with
        Span.__enter__/finish). None when tracing is disabled."""
        if not self.enabled:
            return None
        root = Span(name, trace_id, tags=tags or None, tracer=self)
        root.root = weakref.ref(root)
        root._fold, root._fold_mu = {}, threading.Lock()
        return root

    def trace(self, name: str, trace_id: str, **tags):
        """A ROOT as a context manager, for work no request carries (a
        heal): entering makes it current, leaving finishes it; the
        shared no-op, which enters as None, when tracing is disabled."""
        root = self.begin(name, trace_id, **tags)
        if root is None:
            return _NOOP
        return root

    def span(self, name: str, parent: Span | None = None, **tags):
        """Child span context manager. Attaches to `parent` when given
        (cross-thread: parallel_map workers), else to the thread's
        current span; a shared no-op when neither exists."""
        if parent is None:
            parent = _current.get()
            if parent is None:
                return _NOOP
        child = Span(name, parent.trace_id, parent.span_id,
                     tags=tags or None)
        child.root = parent.root
        parent.add_child(child)
        return child

    @staticmethod
    def tag_root(**tags) -> None:
        """Tags on the ROOT of the thread's current trace, from any
        depth under it (the erasure set a key routed to: known three
        layers below where the root was opened). No-op untraced."""
        cur = _current.get()
        root = cur.root() if cur is not None and cur.root else None
        if root is not None:
            root.tags.update(tags)

    @staticmethod
    def record(name: str, parent: Span | None, t0: float, t1: float,
               **tags) -> None:
        """Attach an already-CLOSED child [t0, t1] (perf_counter
        seconds) to `parent`: a wait measured by two clock reads at a
        boundary (worker-pool hop, admission queue, namespace lock),
        where entering a context would cost more than the wait."""
        if parent is None:
            return
        child = Span(name, parent.trace_id, parent.span_id,
                     tags=tags or None)
        child.start = parent.start + (t0 - parent._t0)
        child._t0 = t0
        child.duration_ms = max(0.0, t1 - t0) * 1e3
        child._done = True
        parent.add_child(child)
        if parent._fold is not None:
            parent._fold_phase(name, t0, t0 + child.duration_ms / 1e3)

    # -- completed traces ----------------------------------------------

    def _complete(self, root: Span) -> dict:
        tree = root.to_dict()
        with self._mu:
            self._ring.append(tree)
        from .metrics2 import METRICS2
        METRICS2.inc("minio_tpu_v2_traces_completed_total")
        METRICS2.observe_each("minio_tpu_v2_request_phase_ms",
                              {"api": root.name}, "phase",
                              reduce_phases(root))
        return tree

    def recent(self, n: int = 32) -> list[dict]:
        with self._mu:
            items = list(self._ring)
        return items[-n:]

    def reset(self) -> None:
        with self._mu:
            self._ring.clear()


# Bounds for span trees GRAFTED from peer RPC responses: a remote
# subtree bypasses the local add_child cap (dicts pass through
# to_dict verbatim), and the RPC response body is not covered by the
# request HMAC — so prune depth/fan-out/node count at ingestion.
MAX_REMOTE_DEPTH = 8
MAX_REMOTE_NODES = 256

_SPAN_KEYS = ("traceId", "spanId", "parentId", "name", "start",
              "durationMs", "tags", "droppedChildren")


def sanitize_remote(node, _depth: int = 0,
                    _budget: list | None = None) -> dict | None:
    """Prune an untrusted remote span dict to the same bounds local
    trees obey; None when it isn't a dict or the node budget is spent."""
    if not isinstance(node, dict):
        return None
    if _budget is None:
        _budget = [MAX_REMOTE_NODES]
    if _budget[0] <= 0:
        return None
    _budget[0] -= 1
    out = {k: node[k] for k in _SPAN_KEYS if k in node}
    if isinstance(out.get("name"), str):
        out["name"] = out["name"][:128]
    tags = out.get("tags")
    if isinstance(tags, dict):
        out["tags"] = {
            str(k)[:64]: (v if isinstance(v, (int, float, bool))
                          else str(v)[:256])
            for k, v in list(tags.items())[:16]}
    elif "tags" in out:
        del out["tags"]
    events = node.get("events")
    if isinstance(events, list):
        kept_ev = []
        for e in events[:MAX_EVENTS]:
            if isinstance(e, dict):
                kept_ev.append({
                    str(k)[:64]: (v if isinstance(v, (int, float, bool))
                                  else str(v)[:256])
                    for k, v in list(e.items())[:8]})
        if kept_ev:
            out["events"] = kept_ev
    kids = node.get("children")
    if isinstance(kids, list) and _depth < MAX_REMOTE_DEPTH:
        kept = []
        for c in kids[:MAX_CHILDREN]:
            sc = sanitize_remote(c, _depth + 1, _budget)
            if sc is not None:
                kept.append(sc)
        if kept:
            out["children"] = kept
        if len(kids) > MAX_CHILDREN:
            out["droppedChildren"] = (out.get("droppedChildren", 0)
                                      + len(kids) - MAX_CHILDREN)
    return out


# The process-wide tracer every layer shares.
TRACER = Tracer()


def current_span() -> Span | None:
    return _current.get()
