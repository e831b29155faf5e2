"""The two readers the four-chip cell brings (`mesh_roofline_share`,
`label_balance`) on hand-made samples, and the first on the trace
recorded on the chip that is kept beside this file, its one device
plane turned into two."""

import copy
import os

import pytest

from harness import prom, trace_reduce as tr
from metrics.readers import label_balance, mesh_roofline_share, \
    prom_ratio, roofline_share

import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
BYTES = "minio_tpu_v2_kernel_backend_bytes_total"
DEVICE = "minio_tpu_v2_mesh_device_bytes_total"
DISPATCH = "minio_tpu_v2_mesh_dispatch_bytes_total"
SETS = "minio_tpu_v2_erasure_set_bytes_total"


def _lane(hh_device_bytes):
    return {(BYTES, (("backend", "device"), ("kernel", "hh256"))):
            hh_device_bytes}


def _ctx(trace, seconds=10.0, gained=8.19e9):
    return {"trace": trace, "notes": {},
            "slice": {"before": _lane(1e9), "after": _lane(1e9 + gained),
                      "seconds": seconds},
            "config": {"data": 8, "parity": 4},
            "device": {"kind": "TPU v5 lite"}}


def _trace(*busy_of_window):
    devices = [{"plane": f"/device:TPU:{i}", "busy_s": b, "window_s": w,
                "programs": 80, "whole_programs": True}
               for i, (b, w) in enumerate(busy_of_window)]
    n = len(devices)
    return {"devices": devices, "whole_programs": True, "programs": 80,
            "busy_s": sum(d["busy_s"] for d in devices) / n,
            "window_s": sum(d["window_s"] for d in devices) / n}


# -- kernels.mesh_roofline_share ---------------------------------------------

def test_one_device_reads_what_roofline_share_reads():
    one = _trace((9.0, 9.5))
    got = mesh_roofline_share.read({}, _ctx(one))
    # 8.19e9 bytes at 819e9 B/s: 10 ms least; busy 9/9.5 of 10 s.
    assert got == pytest.approx(100 * 0.010 / (9.0 / 9.5 * 10.0))
    assert got == pytest.approx(roofline_share.read({}, _ctx(one)))


def test_two_devices_each_doing_half_read_the_same_share():
    one = mesh_roofline_share.read({}, _ctx(_trace((9.0, 9.5))))
    halves = _trace((4.5, 9.5), (4.5, 9.5))
    assert mesh_roofline_share.read({}, _ctx(halves)) == pytest.approx(one)
    # The one-chip formula takes the MEAN busy time: twice the share.
    assert roofline_share.read({}, _ctx(halves)) == pytest.approx(2 * one)


def test_two_devices_doing_all_of_it_twice_read_half():
    one = mesh_roofline_share.read({}, _ctx(_trace((9.0, 9.5))))
    twice = _trace((9.0, 9.5), (9.0, 9.5))
    ctx = _ctx(twice)
    assert mesh_roofline_share.read({}, ctx) == pytest.approx(one / 2)
    assert ctx["notes"]["mesh_roofline"]["busy_chip_s_in_slice"] == \
        pytest.approx(2 * 9.0 / 9.5 * 10.0)


def test_nothing_to_read_is_none_never_zero():
    few = _trace((9.0, 9.5), (0.1, 9.5))
    few["whole_programs"] = False       # under three executions on one
    assert mesh_roofline_share.read({}, _ctx(few)) is None
    assert mesh_roofline_share.read({}, _ctx(None)) is None
    idle = _ctx(_trace((9.0, 9.5)), gained=0.0)   # nothing on the lane
    assert mesh_roofline_share.read({}, idle) is None
    no_slice = _ctx(_trace((9.0, 9.5)))
    no_slice["slice"] = None
    assert mesh_roofline_share.read({}, no_slice) is None


def _recorded_planes():
    path = os.path.join(HERE, "recorded", "tpu_v5e_slice.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace kept")
    pytest.importorskip("jax")
    return tr.read_planes(path)


def _two_devices(planes, split: bool):
    """The recorded trace's device plane as two: every other program
    on either (`split`), or all of them on both."""
    out = []
    for p in planes:
        if not p["name"].startswith(tr.DEVICE_PREFIX):
            out.append(p)
            continue
        events = sorted(p["lines"]["XLA Modules"])
        for i in range(2):
            q = copy.deepcopy(p)
            q["name"] = f"{tr.DEVICE_PREFIX}{i}"
            if split:
                # Both keep the first boundary and the last program's
                # end, so both windows are the slice's.
                q["lines"]["XLA Modules"] = (
                    events[:1] + events[1 + i:-1:2] + events[-1:])
            out.append(q)
    return out


def test_recorded_trace_one_two_halves_two_twice():
    planes = _recorded_planes()
    one = tr.reduce_planes(planes)
    assert len(one["devices"]) == 1
    base = mesh_roofline_share.read({}, _ctx(one))
    assert base == pytest.approx(roofline_share.read({}, _ctx(one)))
    halves = tr.reduce_planes(_two_devices(planes, split=True))
    assert len(halves["devices"]) == 2 and halves["whole_programs"]
    # 82 programs, the last on both devices: 83 program-times of busy
    # chip time where one device had 82.
    assert mesh_roofline_share.read({}, _ctx(halves)) == pytest.approx(
        base * 82 / 83, rel=2e-3)
    twice = tr.reduce_planes(_two_devices(planes, split=False))
    assert mesh_roofline_share.read({}, _ctx(twice)) == pytest.approx(
        base / 2)


# -- label_balance --------------------------------------------------------------

def _sample(name, label, values, **fixed):
    return {(name, tuple(sorted({label: str(k), **fixed}.items()))): v
            for k, v in values.items()}


BALANCE = {"metric": DEVICE, "label": "device", "expect": 4}


def test_label_balance_is_least_over_greatest_increase():
    before = _sample(DEVICE, "device", {0: 100, 1: 100, 2: 100, 3: 100},
                     kernel="hh256")
    after = _sample(DEVICE, "device", {0: 500, 1: 500, 2: 300, 3: 300},
                    kernel="hh256")
    ctx = {"before": before, "after": after}
    assert label_balance.read(BALANCE, ctx) == pytest.approx(50.0)
    # Each value of the label summed over the other labels.
    after.update(_sample(DEVICE, "device", {2: 200, 3: 200},
                         kernel="rs_encode"))
    assert label_balance.read(BALANCE, ctx) == pytest.approx(100.0)
    only = dict(BALANCE, labels={"kernel": "rs_encode"})
    assert label_balance.read(only, ctx) == pytest.approx(0.0)


def test_label_balance_counts_a_value_without_a_series_as_nothing():
    after = _sample(SETS, "set", {0: 700}, op="put")
    spec = {"metric": SETS, "label": "set", "expect": 2}
    assert label_balance.read(spec, {"before": {}, "after": after}) == 0.0
    # Without `expect` one value that moved is nothing to compare.
    del spec["expect"]
    assert label_balance.read(spec, {"before": {}, "after": after}) is None


def test_label_balance_absent_or_still_series_is_none():
    other = _lane(5e9)
    assert label_balance.read(BALANCE, {"before": other,
                                        "after": other}) is None
    still = _sample(DEVICE, "device", {0: 9, 1: 9, 2: 9, 3: 9},
                    kernel="rs_encode")       # the boot's probes only
    assert label_balance.read(BALANCE, {"before": still,
                                        "after": dict(still)}) is None


# -- mesh.redundant_bytes_share, by prom_ratio as it is -------------------------

def _redundant(before, after):
    spec = bench_run.metric_spec("mesh.redundant_bytes_share")
    return prom_ratio.read(spec, {"before": before, "after": after,
                                  "run": {}})


def test_redundant_share_is_zero_sharded_half_on_one_axis_none_absent():
    held = _sample(DEVICE, "device", {0: 250, 1: 250, 2: 250, 3: 250},
                   kernel="hh256")
    sent = _sample(DISPATCH, "placement", {"sharded": 1000}, kernel="hh256")
    assert _redundant({}, {**held, **sent}) == 0.0
    held = _sample(DEVICE, "device", {0: 500, 1: 500, 2: 500, 3: 500},
                   kernel="rs_encode")
    sent = _sample(DISPATCH, "placement", {"replicated": 1000},
                   kernel="rs_encode")
    assert _redundant({}, {**held, **sent}) == pytest.approx(50.0)
    assert _redundant({}, _lane(5e9)) is None
    assert prom.delta({}, _lane(5e9), DEVICE) == 0
