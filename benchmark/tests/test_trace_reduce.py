"""trace_reduce.py on hand-made planes (numbers worked by hand) and on
the small trace recorded on the chip that is kept beside this file."""

import os

import pytest

from harness import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)]) == \
        [(0, 4), (5, 7), (10, 11)]


def planes(programs):
    return [
        {"name": "/device:TPU:0",
         "lines": {"XLA Modules": programs,
                   "Steps": [(0, 100 * MS, "0")]},
         # per-op events, nested under their while: only added up by name
         "by_name": {"XLA Ops": {"%while.2": [3, 38 * MS],
                                 "%copy.4": [3, 2 * MS],
                                 "%fusion.9": [120000, 30 * MS]}}},
        {"name": "/host:CPU",
         "lines": {"python": [(20 * MS, 49 * MS, "np.asarray(jax.Array)"),
                              (60 * MS, 65 * MS, "short")]}, "by_name": {}},
    ]


TWO = [(0, 20 * MS, "jit_hash(1)"), (50 * MS, 60 * MS, "jit_hash(2)")]
FOUR = TWO + [(90 * MS, 100 * MS, "jit_hash(1)"),
              (120 * MS, 140 * MS, "jit_hash(1)")]


def test_under_three_programs_the_whole_slice_is_the_window():
    got = tr.reduce_planes(planes(TWO), window_s=0.2)
    assert got["busy_s"] == pytest.approx(0.030)
    assert got["window_s"] == pytest.approx(0.2)
    assert got["whole_programs"] is False and got["programs"] == 2
    # Without the traced process's clock: first start to last end. The
    # same where a program overhangs a slice shorter than itself.
    assert tr.reduce_planes(planes(TWO))["window_s"] == pytest.approx(0.060)
    short = tr.reduce_planes(planes(TWO), window_s=0.05)
    assert short["window_s"] == pytest.approx(0.060)


def test_three_programs_or_more_are_read_between_program_boundaries():
    got = tr.reduce_planes(planes(FOUR), window_s=0.2)
    # Start of the first (0) to the end of the last (140 ms), whatever
    # the traced process clocked: 20 + 10 + 10 + 20 ms busy.
    assert got["whole_programs"] is True and got["programs"] == 4
    assert got["busy_s"] == pytest.approx(0.060)
    assert got["window_s"] == pytest.approx(0.140)


def test_a_program_running_when_the_trace_began_opens_the_window():
    # The device reports it as a zero-length event at its end (5 ms):
    # a boundary, not an execution.
    mark = [(5 * MS, 5 * MS, "jit_hash(1)")]
    shifted = [(a + 10 * MS, b + 10 * MS, n) for a, b, n in FOUR]
    got = tr.reduce_planes(planes(mark + shifted), window_s=0.2)
    assert got["programs"] == 4
    assert got["busy_s"] == pytest.approx(0.060)
    assert got["window_s"] == pytest.approx(0.145)
    assert tr.reduce_planes(planes(mark))["busy_s"] is None


def test_top_operations_programs_first_then_ops_by_name():
    got = tr.reduce_planes(planes(FOUR))
    names = [n for n, _ in got["device_ops"]]
    assert names == ["jit_hash(1)", "jit_hash(2)", "%while.2", "%fusion.9",
                     "%copy.4"]
    assert got["device_ops"][0][1] == pytest.approx(0.050)
    assert got["census"][0]["lines"]["XLA Ops"] == 120006


def test_gaps_are_named_by_what_the_host_did_or_left_unattributed():
    gaps = tr.reduce_planes(planes(FOUR))["idle_gaps"]
    # [20,50) 30 ms: the host's np.asarray covers 29 of it. [60,90) 30 ms:
    # a 5 ms host event covers under half -> unattributed. [100,120) 20 ms.
    assert [g[1] for g in gaps] == pytest.approx([0.030, 0.030, 0.020])
    assert sorted(g[0] for g in gaps) == [
        "np.asarray(jax.Array)", "unattributed", "unattributed"]


def test_op_line_stands_in_where_there_is_no_program_line():
    p = planes([])
    p[0]["lines"]["XLA Ops"] = [(0, 10 * MS, "a"), (5 * MS, 20 * MS, "b")]
    got = tr.reduce_planes(p)
    assert got["devices"][0]["line"] == "XLA Ops"
    assert got["busy_s"] == pytest.approx(0.020)


def test_no_device_plane_gives_nothing_not_zero():
    got = tr.reduce_planes([{"name": "/host:CPU",
                             "lines": {"t": [(0, 5, "x")]}, "by_name": {}}])
    assert got["busy_s"] is None and got["devices"] == []
    assert got["census"][0]["lines"] == {"t": 1}


def test_recorded_chip_trace():
    path = os.path.join(HERE, "recorded", "tpu_v5e_slice.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace kept yet")
    pytest.importorskip("jax")
    got = tr.reduce_file(path)
    assert got["devices"] and got["devices"][0]["plane"].startswith(
        "/device:TPU:")
    # The large cell's traced slice (seed 541, the window's last 10 s),
    # compiled without the per-operation tracemarks (configs'
    # env_traced): the end of the program that was running when the
    # trace began (zero length), then 82 whole executions, 3.6-3.7 us
    # apart; the host lines cut to their first 400 events.
    assert got["programs"] == 82 and got["whole_programs"] is True
    assert got["busy_s"] == pytest.approx(10.041477129)
    assert got["window_s"] == pytest.approx(10.041654809)
    assert got["devices"][0]["line"] == "XLA Modules"
    assert got["device_ops"][0][0].startswith("jit__hash_chunks_device(")
    assert got["census"][1]["lines"]["XLA Modules"] == 83
    assert got["census"][1]["lines"]["XLA Ops"] == 0
    assert got["idle_gaps"][0] == ["np.asarray(jax.Array)",
                                   pytest.approx(3.7e-06)]
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) == 10
    from metrics.readers import trace
    assert trace.read({"reduce": "idle_share"}, {"trace": got}) == \
        pytest.approx(0.00176943, rel=1e-4)
