"""roofline.py's arithmetic against hand-worked numbers (TPU v5e:
819 GB/s, 393 TOP/s int8)."""

import pytest

from harness import roofline

GIB = float(1 << 30)


def test_hh256_reads_each_byte_once():
    assert roofline.hh256_work(GIB) == (GIB, 0.0)
    t, bound = roofline.least_seconds([roofline.hh256_work(GIB)],
                                      "TPU v5 lite")
    assert t == pytest.approx(GIB / 819e9)          # 1.311 ms
    assert bound == "hbm"


@pytest.mark.parametrize("k,r,moved,ops", [
    (4, 2, 1.5, 256.0),       # n * 6/4;  2*64*2 per byte
    (8, 4, 1.5, 512.0),       # n * 12/8; 2*64*4
    (16, 4, 1.25, 512.0),     # n * 20/16
])
def test_rs_encode(k, r, moved, ops):
    b, o = roofline.rs_encode_work(GIB, k, r)
    assert b == pytest.approx(moved * GIB)
    assert o == pytest.approx(ops * GIB)
    t, bound = roofline.least_seconds([(b, o)], "TPU v5 lite")
    # By hand: bytes 1.5 GiB / 819e9 = 1.967 ms (1.25: 1.639 ms);
    # ops 512 GiB / 393e12 = 1.399 ms (256: 0.699 ms). HBM binds all.
    assert bound == "hbm"
    assert t == pytest.approx(moved * GIB / 819e9)


def test_rs_reconstruct_two_lost_of_8_plus_4():
    b, o = roofline.rs_reconstruct_work(GIB, 8, 2)
    assert b == pytest.approx(GIB * 10 / 8)
    assert o == pytest.approx(GIB * 256)


def test_items_add_up_each_under_its_own_bound():
    items = [roofline.hh256_work(2e9), (1e6, 393e12)]  # 1 s of int8 ops
    t, bound = roofline.least_seconds(items, "TPU v5 lite")
    assert t == pytest.approx(2e9 / 819e9 + 1.0)
    assert bound == "int8"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("_source")
    with pytest.raises(roofline.UnknownDevice):
        roofline.least_seconds([(1.0, 1.0)], "cpu")


def _sample(hh_device_bytes):
    return {("minio_tpu_v2_kernel_backend_bytes_total",
             (("backend", "device"), ("kernel", "hh256"))): hh_device_bytes,
            ("minio_tpu_v2_kernel_backend_bytes_total",
             (("backend", "native"), ("kernel", "rs_encode"))): 5e9}


def _ctx(trace, slice_):
    return {"trace": trace, "slice": slice_, "notes": {},
            "config": {"data": 8, "parity": 4},
            "device": {"kind": "TPU v5 lite"}}


def test_roofline_share_is_work_in_the_slice_over_busy_time_in_the_slice():
    from metrics.readers import roofline_share, trace
    tr = {"busy_s": 9.0, "window_s": 9.5, "programs": 80,
          "whole_programs": True}
    sl = {"before": _sample(1e9), "after": _sample(1e9 + 8.19e9),
          "seconds": 10.0}
    ctx = _ctx(tr, sl)
    # 8.19e9 bytes at 819e9 B/s: 10 ms least. Busy 9/9.5 of the 10 s
    # between the two reads of the counters: 9.4737 s.
    assert roofline_share.read({}, ctx) == pytest.approx(
        100 * 0.010 / (9.0 / 9.5 * 10.0))
    assert ctx["notes"]["roofline"]["binding"] == "hbm"
    assert trace.read({"reduce": "idle_share"}, ctx) == pytest.approx(
        100 * 0.5 / 9.5)
    # A fragment of a slice (under three program executions), no slice,
    # or nothing on the device lane: nothing, never 0.
    few = dict(tr, programs=2, whole_programs=False)
    assert roofline_share.read({}, _ctx(few, sl)) is None
    assert trace.read({"reduce": "idle_share"}, _ctx(few, sl)) is None
    assert roofline_share.read({}, _ctx(tr, None)) is None
    idle = {"before": _sample(1e9), "after": _sample(1e9), "seconds": 10.0}
    assert roofline_share.read({}, _ctx(tr, idle)) is None
