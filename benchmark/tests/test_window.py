"""Percentile, window and drain arithmetic on synthetic logs."""

import pytest

from harness.window import (MiB, Rec, goodput_mibps, latencies_ms,
                            percentile, summarize)


def rec(kind, t0, t1, ok=True, size=MiB, why=""):
    return Rec(kind, "main", 0, "k", size, size if ok else 0, t0, t1, ok, why)


def steady(n=100, lat=0.1):
    """n PUTs back to back, one client."""
    return [rec("PUT", i * lat, (i + 1) * lat) for i in range(n)]


def test_percentile_is_nearest_rank():
    vals = [float(i) for i in range(1, 101)]
    assert percentile(vals, 50) == 50.0
    assert percentile(vals, 95) == 95.0
    assert percentile(vals, 99) == 99.0
    assert percentile([7.0], 95) == 7.0
    assert percentile([], 95) is None


def test_goodput_runs_to_the_last_completion_of_the_drain():
    log = steady(100, 0.1)                 # 100 MiB in 10 s
    assert goodput_mibps(log) == pytest.approx(10.0)
    # An operation that started in the window and ended 5 s after its
    # close counts, and so does the time it took.
    log.append(rec("PUT", 9.95, 15.0))
    assert goodput_mibps(log) == pytest.approx(101 / 15.0)


def test_a_stall_lowers_goodput_and_raises_the_p95():
    base = steady(100, 0.1)
    # The same 10 s window with a 3 s stall in the middle: fewer ops fit.
    stalled = [rec("PUT", i * 0.1, (i + 1) * 0.1) for i in range(40)]
    stalled.append(rec("PUT", 4.0, 7.0))
    stalled += [rec("PUT", 7.0 + i * 0.1, 7.1 + i * 0.1) for i in range(30)]
    a, b = summarize(base, 30.0), summarize(stalled, 30.0)
    assert b["goodput_mibps"] < a["goodput_mibps"]
    assert b["put_p99_ms"] > a["put_p99_ms"]
    # Eight stalled operations of 71 reach the p95.
    for i in range(7):
        stalled[i] = rec("PUT", i * 0.1, i * 0.1 + 0.9)
    assert summarize(stalled, 30.0)["put_p95_ms"] > a["put_p95_ms"] * 5


def test_a_failure_counts_as_the_timeout_and_moves_no_bytes():
    log = steady(10, 0.1) + [rec("PUT", 1.0, 1.2, ok=False, why="status:503")]
    lat = latencies_ms(log, ("PUT",), timeout_s=30.0)
    assert lat[-1] == 30000.0 and len(lat) == 11
    s = summarize(log, 30.0)
    assert (s["attempted"], s["failed"]) == (11, 1)
    assert s["user_bytes"] == 10 * MiB
    assert s["goodput_mibps"] == pytest.approx(10.0)   # to the last success
    assert s["ops_per_s"] == pytest.approx(10.0)
    assert s["put_p95_ms"] == 30000.0


def test_gets_and_puts_are_kept_apart_and_by_size():
    log = [rec("PUT", 0, 1.0), rec("GET", 1.0, 1.5), rec("GET", 1.5, 2.5),
           rec("RANGE", 2.5, 2.6, size=4096)]
    s = summarize(log, 30.0)
    assert s["put_count"] == 1 and s["get_count"] == 3
    assert s["get_p50_ms"] == pytest.approx(500.0)
    assert s["by_size"][f"GET/{MiB}"]["n"] == 2
    assert summarize([], 30.0)["goodput_mibps"] is None


def test_a_group_alone_and_completions_per_five_seconds_are_beside_it():
    log = [rec("GET", i * 0.1, i * 0.1 + 0.1) for i in range(99)]
    slow = rec("GET", 4.0, 9.5)
    slow.group = "devpath"
    s = summarize(log + [slow], 30.0)
    # The metric is the tail of all requests; one group's own tail is
    # an earlier line that shows what another group did to it.
    assert s["get_p95_ms"] == pytest.approx(100.0)
    assert s["get_p99_ms"] == pytest.approx(100.0)
    assert s["by_group"]["devpath"]["get_p95_ms"] == pytest.approx(5500.0)
    assert s["by_group"]["main"] == {"put_p95_ms": None,
                                     "get_p95_ms": pytest.approx(100.0)}
    assert [b[:2] for b in s["per_5s"]] == [[0, 49], [5, 51]]


class _Store:
    """An S3 answer from a dict: PUT 200, DELETE 204, GET / HEAD the
    object or 404; `fail` answers the next request 500 instead."""

    def __init__(self):
        self.objects: dict[str, bytes] = {}
        self.fail = False

    @staticmethod
    def key_path(bucket, key):
        return f"/{bucket}/{key}"

    def request(self, method, path, body=b"", **_):
        from harness.s3client import Response
        if self.fail:
            self.fail = False
            return Response(500, {}, b"")
        if method == "PUT":
            self.objects[path] = bytes(body)
            return Response(200, {}, b"")
        if method == "DELETE":
            self.objects.pop(path, None)
            return Response(204, {}, b"")
        if path not in self.objects:
            return Response(404, {}, b"")
        data = self.objects[path]
        return Response(200, {"content-length": str(len(data))},
                        b"" if method == "HEAD" else data)


def test_expect_deleted_follows_delete_then_put():
    from harness import traffic, window
    g = traffic.Group("g", 1, [], {"PUT": 1.0}, [4096], 4, "ring")
    ex = window.Expect(bytes(range(256)) * 64)
    c = window.Client.__new__(window.Client)
    c.stream, c.bucket, c.expect, c.s3 = traffic.ClientStream(1, g, 0), \
        "b", ex, _Store()

    def do(kind, key, size=4096, off=7):
        return c.execute(traffic.Op(kind, key, size, off), 0.0, True)

    assert do("PUT", "k1").ok and do("PUT", "k2").ok
    assert ex.deleted == set()
    assert do("DELETE", "k1").ok
    assert ex.deleted == {"k1"} and "k1" not in ex.last
    assert "k1" not in c.stream.written and "k1" not in ex.in_window
    assert do("HEAD", "k2").ok and do("GET", "k2").ok
    # An acknowledged PUT of the key takes it out; so does one that
    # failed (what it holds is then unknown, not known to be nothing).
    assert do("PUT", "k1", off=9).ok
    assert ex.deleted == set() and ex.last["k1"] == (4096, 9)
    assert do("DELETE", "k1").ok and do("DELETE", "k2").ok
    assert ex.deleted == {"k1", "k2"}
    c.s3.fail = True
    assert not do("PUT", "k2").ok
    assert ex.deleted == {"k1"} and ex.last["k2"] is None
    # A DELETE that was not acknowledged records nothing.
    c.s3.fail = True
    assert not do("DELETE", "k3").ok
    assert ex.deleted == {"k1"}


def test_the_realised_mix_is_counted_by_kind():
    log = [rec("GET", 0, 1.0), rec("HEAD", 0, 0.1), rec("GET", 1, 2),
           rec("DELETE", 2, 2.1, ok=False, why="status:500")]
    assert summarize(log, 30.0)["by_kind"] == {"DELETE": 1, "GET": 2,
                                               "HEAD": 1}
