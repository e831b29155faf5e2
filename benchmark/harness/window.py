"""The window driver and the arithmetic of the client-side metrics.

One thread per client. The window opens for all at once; at its close
no operation starts, those in flight finish (the drain), and every
operation that STARTED in the window counts: in `attempted`, in the
latency percentiles (a failure at the timeout's value), and, if it
succeeded, in the goodput, whose clock runs from the window's opening
to the last of those completions.
"""

from __future__ import annotations

import math
import re
import threading
import time
from dataclasses import dataclass

from .s3client import S3Client
from .traffic import WRITES, ClientStream, Op, Traffic

MiB = 1 << 20


@dataclass
class Rec:
    kind: str
    group: str
    client: int
    key: str
    size: int
    nbytes: int        # user bytes moved if it succeeded
    t_start: float     # seconds after window open (open loop: due time)
    t_end: float
    ok: bool
    why: str = ""      # "", status:<n>, wrong_bytes, unanswered:<exc>


# --- arithmetic (tested on synthetic logs) ------------------------------------


def percentile(sorted_vals: list[float], q: float) -> float | None:
    """Nearest-rank percentile of a sorted list; None when empty."""
    if not sorted_vals:
        return None
    rank = math.ceil(q * len(sorted_vals) / 100.0 - 1e-9)
    return sorted_vals[min(len(sorted_vals) - 1, max(0, rank - 1))]


def latencies_ms(log: list[Rec], kinds: tuple[str, ...],
                 timeout_s: float) -> list[float]:
    """Sorted latencies of every operation of `kinds`; a failed one
    counts as the timeout."""
    return sorted((r.t_end - r.t_start) * 1e3 if r.ok else timeout_s * 1e3
                  for r in log if r.kind in kinds)


def goodput_mibps(log: list[Rec]) -> float | None:
    good = [r for r in log if r.ok]
    if not good:
        return None
    span = max(r.t_end for r in good)
    return sum(r.nbytes for r in good) / MiB / span if span > 0 else None


def summarize(log: list[Rec], timeout_s: float) -> dict:
    """Every client-side number a metric or an earlier line may want."""
    out: dict = {
        "attempted": len(log),
        "failed": sum(1 for r in log if not r.ok),
        "user_bytes": sum(r.nbytes for r in log if r.ok),
        "goodput_mibps": goodput_mibps(log),
        "span_s": max((r.t_end for r in log if r.ok), default=0.0),
    }
    good = sum(1 for r in log if r.ok)
    out["ops_per_s"] = good / out["span_s"] if good else None
    for name, kinds in (("put", WRITES), ("get", ("GET", "RANGE"))):
        lat = latencies_ms(log, kinds, timeout_s)
        out[f"{name}_count"] = len(lat)
        for q in (50, 95, 99):
            out[f"{name}_p{q}_ms"] = percentile(lat, q)
    # Not metrics: the same tails for each group alone (what one
    # group's operations do to the tail of all), and completions per
    # 5 s of the window (where a far-off run stalled).
    out["by_group"] = {
        g: {f"{name}_p95_ms": percentile(latencies_ms(
            [r for r in log if r.group == g], kinds, timeout_s), 95)
            for name, kinds in (("put", WRITES), ("get", ("GET", "RANGE")))}
        for g in sorted({r.group for r in log})}
    buckets: dict[int, list] = {}
    for r in log:
        if r.ok:
            b = buckets.setdefault(int(r.t_end // 5), [0, 0])
            b[0] += 1
            b[1] += r.nbytes
    out["per_5s"] = [[5 * i, *buckets.get(i, [0, 0])]
                     for i in range(max(buckets, default=-1) + 1)]
    table: dict[str, dict] = {}
    for r in log:
        row = table.setdefault(f"{r.kind}/{r.size}", {"lat": [], "failed": 0})
        if r.ok:
            row["lat"].append((r.t_end - r.t_start) * 1e3)
        else:
            row["failed"] += 1
    out["by_size"] = {
        k: {"n": len(v["lat"]) + v["failed"], "failed": v["failed"],
            "p50_ms": percentile(sorted(v["lat"]), 50),
            "max_ms": max(v["lat"], default=None)}
        for k, v in sorted(table.items())}
    kinds = [r.kind for r in log]
    out["by_kind"] = {k: kinds.count(k) for k in sorted(set(kinds))}
    return out


# --- executing operations -----------------------------------------------------


class Expect:
    """What each key has to hold: (size, off) of its last acknowledged
    write, None while a failed write leaves it unknown; `deleted`, the
    keys whose last acknowledged write was a DELETE (nothing)."""

    def __init__(self, base: bytes):
        self.base = memoryview(base)
        self.last: dict[str, tuple[int, int] | None] = {}
        self.in_window: set[str] = set()
        self.deleted: set[str] = set()

    def body(self, size: int, off: int) -> memoryview:
        return self.base[off:off + size]

    def want(self, key: str) -> memoryview | None:
        cur = self.last.get(key)
        return None if cur is None else self.body(*cur)


_UPLOAD_ID = re.compile(rb"<UploadId>([^<]+)</UploadId>")


def _multipart(c: S3Client, path: str, body: memoryview, part: int):
    r = c.request("POST", path, query="uploads")
    if r.status != 200:
        return r
    uid = _UPLOAD_ID.search(r.body).group(1).decode()
    etags = []
    for i, off in enumerate(range(0, len(body), part), start=1):
        r = c.request("PUT", path, query=f"partNumber={i}&uploadId={uid}",
                      body=body[off:off + part])
        if r.status != 200:
            return r
        etags.append(r.headers.get("etag", "").strip('"'))
    doc = "".join(f"<Part><PartNumber>{i}</PartNumber><ETag>\"{e}\"</ETag>"
                  "</Part>" for i, e in enumerate(etags, start=1))
    r = c.request("POST", path, query=f"uploadId={uid}",
                  body=f"<CompleteMultipartUpload>{doc}"
                       "</CompleteMultipartUpload>".encode())
    if r.status == 200 and b"<Error>" in r.body:
        r.status = 500
    return r


class Client:
    """One client: its stream, its connection, its share of the log."""

    def __init__(self, stream: ClientStream, host: str, port: int,
                 access: str, secret: str, bucket: str, expect: Expect,
                 timeout_s: float):
        self.stream = stream
        self.bucket = bucket
        self.expect = expect
        self.s3 = S3Client(host, port, access, secret, timeout=timeout_s)
        self.log: list[Rec] = []

    def execute(self, op: Op, t_open: float, in_window: bool) -> Rec:
        g = self.stream.group
        path = self.s3.key_path(self.bucket, op.key)
        ex = self.expect
        t0 = time.monotonic()
        ok, why, nbytes = False, "", 0
        try:
            if op.kind in WRITES:
                body = ex.body(op.size, op.off)
                ex.last[op.key] = None
                ex.deleted.discard(op.key)
                if op.kind == "PUT":
                    r = self.s3.request("PUT", path, body=body)
                else:
                    r = _multipart(self.s3, path, body, g.part_size)
                ok, nbytes = r.status == 200, op.size
                if ok:
                    ex.last[op.key] = (op.size, op.off)
                    self.stream.written[op.key] = op.size
                    self.stream.last_put = op.key
                    if in_window:
                        ex.in_window.add(op.key)
            elif op.kind == "GET":
                r = self.s3.request("GET", path)
                want = ex.want(op.key)
                ok, nbytes = r.status == 200, len(r.body)
                if ok and want is not None and r.body != want:
                    ok, why = False, "wrong_bytes"
            elif op.kind == "RANGE":
                want = ex.want(op.key)
                n = op.size
                lo = self.stream.rng.randrange(max(1, n - g.range_bytes + 1))
                hi = min(n, lo + g.range_bytes) - 1
                r = self.s3.request("GET", path,
                                    headers={"Range": f"bytes={lo}-{hi}"})
                ok, nbytes = r.status == 206, len(r.body)
                if ok and want is not None and r.body != want[lo:hi + 1]:
                    ok, why = False, "wrong_bytes"
            elif op.kind == "HEAD":
                r = self.s3.request("HEAD", path)
                ok = r.status == 200
                if ok and int(r.headers.get("content-length", -1)) != op.size:
                    ok, why = False, "wrong_bytes"
            else:  # DELETE
                r = self.s3.request("DELETE", path)
                ok = r.status in (200, 204)
                if ok:
                    ex.last.pop(op.key, None)
                    ex.in_window.discard(op.key)
                    ex.deleted.add(op.key)
                    self.stream.written.pop(op.key, None)
                    if self.stream.last_put == op.key:
                        self.stream.last_put = None
            if not ok and not why:
                why = f"status:{r.status}"
        except OSError as exc:   # timeouts, resets: it never answered
            why = f"unanswered:{type(exc).__name__}"
        except Exception as exc:  # noqa: BLE001 - http.client's own errors
            why = f"unanswered:{type(exc).__name__}"
        t1 = time.monotonic()
        start = op.due_s if g.rate_per_s else t0 - t_open
        return Rec(op.kind, g.name, self.stream.client, op.key, op.size,
                   nbytes if ok else 0, start, t1 - t_open, ok, why)

    def run_window(self, gate: threading.Event, clock: dict) -> None:
        gate.wait()
        t_open, seconds = clock["t_open"], clock["seconds"]
        g = self.stream.group
        self.stream.open_window()
        while True:
            op = self.stream.next()
            if g.rate_per_s:
                if op.due_s >= seconds:
                    return
                wait = t_open + op.due_s - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
            elif time.monotonic() - t_open >= seconds:
                return
            self.log.append(self.execute(op, t_open, True))

    def run_ops(self, n: int, sizes: list[int] | None = None) -> list[Rec]:
        """n operations outside any window (preload, warm-up)."""
        out = []
        for i in range(n):
            op = self.stream.next()
            if sizes and op.kind in WRITES:
                op.size = sizes[i % len(sizes)]
            out.append(self.execute(op, time.monotonic(), False))
        return out

    def run_list(self, ops: list[Op]) -> list[Rec]:
        """These operations, in order, outside any window."""
        return [self.execute(op, time.monotonic(), False) for op in ops]


def make_clients(streams: list[ClientStream], traffic: Traffic, host: str,
                 port: int, access: str, secret: str, bucket: str,
                 expect: Expect) -> list[Client]:
    return [Client(s, host, port, access, secret, bucket, expect,
                   traffic.timeout_s) for s in streams]


def in_threads(clients: list[Client], fn) -> list:
    """fn(client) on a thread per client; every result, or the first
    exception."""
    results: list = [None] * len(clients)
    errors: list = []

    def work(i: int, c: Client) -> None:
        try:
            results[i] = fn(c)
        except BaseException as exc:  # noqa: BLE001 - re-raised below
            errors.append(exc)

    threads = [threading.Thread(target=work, args=(i, c), daemon=True)
               for i, c in enumerate(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return results


def run_window(clients: list[Client], seconds: float,
               during=None) -> tuple[list[Rec], float, float]:
    """Open the window for every client at once; returns the merged
    log, the monotonic time the window opened and the drain's end.
    `during(t_open)` runs on this thread while the window is open (the
    traced slice)."""
    gate = threading.Event()
    clock = {"seconds": seconds}
    threads = [threading.Thread(target=c.run_window, args=(gate, clock),
                                daemon=True) for c in clients]
    for t in threads:
        t.start()
    clock["t_open"] = time.monotonic()
    gate.set()
    if during is not None:
        during(clock["t_open"])
    for t in threads:
        t.join()
    t_drained = time.monotonic()
    log = sorted((r for c in clients for r in c.log),
                 key=lambda r: r.t_start)
    return log, clock["t_open"], t_drained
