"""`kernels.roofline_share` for a trace of several devices: chip-seconds
over chip-seconds, in %.

Numerator: the least time ONE chip could take for the codec work the
device lane's byte counters gained in the traced slice
(harness/roofline.py at one chip's peaks; `work_items` of the one-chip
reader: a batch is counted once however many chips it was spread or
repeated over). Denominator: the SUM over the trace's devices of each
one's busy share (harness/trace_reduce.py, `devices`) times the seconds
between the two reads of the counters. On one device that is
`kernels.roofline_share`'s number; four chips that each do a quarter of
the work read the same share as one that does all of it; chips that
repeat each other's work (an axis of a batch left replicated) read
lower by exactly that redundancy. Under three program executions on any
device, or nothing on the device lane: None."""

from __future__ import annotations

from harness import roofline
from metrics.readers.roofline_share import work_items


def read(spec: dict, ctx: dict) -> float | None:
    tr, sl = ctx.get("trace"), ctx.get("slice")
    if not tr or not sl or not tr.get("whole_programs"):
        return None
    chip_s = sum(d["busy_s"] / d["window_s"] for d in tr.get("devices", [])
                 if d["window_s"]) * sl["seconds"]
    cfg = ctx["config"]
    items = work_items(sl["before"], sl["after"], cfg["data"], cfg["parity"])
    if not items or not chip_s:
        return None
    least, bound = roofline.least_seconds(items, ctx["device"]["kind"])
    ctx["notes"]["mesh_roofline"] = {
        "least_s": least, "binding": bound, "busy_chip_s_in_slice": chip_s,
        "slice_s": sl["seconds"],
        "busy_s_per_device": {d["plane"]: d["busy_s"]
                              for d in tr["devices"]}}
    return 100.0 * least / chip_s
