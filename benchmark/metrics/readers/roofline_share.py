"""The least time the chip could take for the codec work its lane
carried in the traced slice, over the time the device was busy there,
in %.

The work is the increase of the child's `backend="device"` byte counters
between the read just before `start_trace` and the read just after
`stop_trace` is asked for, turned into bytes and operations by
harness/roofline.py. Busy time is the device's busy share between the
program boundaries the trace holds (harness/trace_reduce.py: the union
of every program execution on the device; no kernel has a stable name
yet, and the device does nothing but codec work and its copies) times
the seconds between those two reads. The counters move a dispatch at a
time and the trace leaves out a fragment of a program at either end, so
the share is off by up to two programs in as many as the slice holds
(80 in the large cell's 10 s). Under three program executions in the
slice, or nothing on the device lane: None."""

from __future__ import annotations

from harness import prom, roofline

BYTES = "minio_tpu_v2_kernel_backend_bytes_total"


def work_items(before, after, k: int, r: int) -> list[tuple[float, float]]:
    def dev(kernel: str) -> float:
        return prom.delta(before, after, BYTES,
                          {"kernel": kernel, "backend": "device"})
    items = []
    if dev("hh256"):
        items.append(roofline.hh256_work(dev("hh256")))
    if dev("rs_encode"):
        items.append(roofline.rs_encode_work(dev("rs_encode"), k, r))
    if dev("rs_decode"):
        # The counter does not say how many shards were rebuilt; the
        # fewest (one) gives the least work, so the share errs low.
        items.append(roofline.rs_reconstruct_work(dev("rs_decode"), k, 1))
    return items


def read(spec: dict, ctx: dict) -> float | None:
    tr, sl = ctx.get("trace"), ctx.get("slice")
    if not tr or not sl or not tr.get("whole_programs") \
            or not tr.get("busy_s"):
        return None
    cfg = ctx["config"]
    items = work_items(sl["before"], sl["after"], cfg["data"], cfg["parity"])
    if not items:
        return None
    least, bound = roofline.least_seconds(items, ctx["device"]["kind"])
    busy = tr["busy_s"] / tr["window_s"] * sl["seconds"]
    ctx["notes"]["roofline"] = {
        "least_s": least, "binding": bound, "busy_s_in_slice": busy,
        "slice_s": sl["seconds"], "programs_traced": tr["programs"],
        "bytes_moved": sum(b for b, _ in items),
        "ops": sum(o for _, o in items)}
    return 100.0 * least / busy
