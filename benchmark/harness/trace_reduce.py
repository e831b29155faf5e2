"""From a jax.profiler trace (.xplane.pb) to the device numbers: busy
time as the union of the intervals in which a program runs on a device,
what took most of that time under the names the trace gives, and the
longest idle gaps.

What a TPU v5e trace of this server holds (looked at by hand, PR 24,
PERF.md section 5): the device is the plane `/device:TPU:<n>`. Its line
`XLA Modules` has one event per program execution
(`jit__hash_chunks_device(<fingerprint>)`, 125.0 ms for 16 rows of
1.25 MiB). Compiled as the server compiles by default, the line
`XLA Ops` holds one event per executed HLO operation, the body of a
`while` loop included at every iteration: 11 million events per busy
second of HighwayHash, and `stop_trace` then works ~120 s per million.
A traced run therefore gives the child
`LIBTPU_INIT_ARGS=--xla_enable_hlo_trace=false` (the configuration's
`env_traced`): the programs are compiled without the per-operation
tracemarks, `XLA Ops` stays empty, `XLA Modules` stays (125.0 ms where
it was 125.4), and `stop_trace` takes ~2 s per traced second.

What the device does not report: a program that is running when the
trace starts appears only as a zero-length event at its end, and one
that is running when the trace stops does not appear at all. So where
the slice holds MIN_PROGRAMS executions or more, busy time and window
are taken from the first program boundary the device reports to the end
of the last whole execution (the slice less a fragment at either end);
an idle head or tail is left out with them, which errs towards busy by
up to one gap in as many as there are executions. With fewer
executions the window is the slice as the traced process clocked it
and the idle-share and roofline metrics are not reported.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
BUSY_LINES = ("XLA Modules", "XLA Ops")   # the first that has events
OP_LINE = "XLA Ops"
HOST_PREFIX = "/host:"
TOP = 10
NAME = 64                                  # characters of a name kept
MIN_PROGRAMS = 3


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Sorted, merged [start, end) intervals."""
    out: list[tuple[int, int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def reduce_planes(planes: list[dict], window_s: float | None = None) -> dict:
    """planes: [{"name", "lines": {line: [(start_ns, end_ns, name)]},
    "by_name": {line: {name: [count, total_ns]}}}] as `read_planes`
    gives them. `window_s` is the traced slice's length as the traced
    process clocked it (start_trace returned .. stop_trace called)."""
    census = [{"plane": p["name"],
               "lines": {n: len(ev) for n, ev in p["lines"].items()}
               | {n: sum(c for c, _ in names.values())
                  for n, names in p.get("by_name", {}).items()}}
              for p in planes]
    host = [e for p in planes if p["name"].startswith(HOST_PREFIX)
            for ev in p["lines"].values() for e in ev]
    per_dev, programs, ops, gaps = [], {}, {}, []
    for p in planes:
        if not p["name"].startswith(DEVICE_PREFIX):
            continue
        line = next((n for n in BUSY_LINES if p["lines"].get(n)), None)
        events = sorted(p["lines"].get(line, [])) if line else []
        runs = [e for e in events if e[1] > e[0]]
        if not runs:
            continue
        aligned = len(runs) >= MIN_PROGRAMS
        merged = union([(a, b) for a, b, _ in runs])
        busy = sum(b - a for a, b in merged)
        lo, hi = events[0][0], merged[-1][1]
        span = hi - lo
        if not aligned and window_s is not None:
            span = max(span, window_s * 1e9)
        per_dev.append({"plane": p["name"], "line": line,
                        "programs": len(runs), "whole_programs": aligned,
                        "busy_s": busy / 1e9, "window_s": span / 1e9})
        for a, b, name in runs:
            programs[name] = programs.get(name, 0) + (b - a)
        for name, (_, total) in p.get("by_name", {}).get(OP_LINE, {}).items():
            ops[name] = ops.get(name, 0) + total
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i + 1] - edges[i], edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    if not per_dev:
        return {"census": census, "devices": [], "busy_s": None,
                "window_s": None, "programs": 0, "whole_programs": False,
                "device_ops": [], "idle_gaps": []}
    n = len(per_dev)
    top = sorted(programs.items(), key=lambda kv: -kv[1])[:3]
    top += sorted(ops.items(), key=lambda kv: -kv[1])[:TOP - len(top)]
    return {"census": census, "devices": per_dev,
            "busy_s": sum(d["busy_s"] for d in per_dev) / n,
            "window_s": sum(d["window_s"] for d in per_dev) / n,
            "programs": min(d["programs"] for d in per_dev),
            "whole_programs": all(d["whole_programs"] for d in per_dev),
            "device_ops": [[k, v / 1e9 / n] for k, v in top],
            "idle_gaps": [[_host_doing(host, a, b), dur / 1e9]
                          for dur, a, b in sorted(gaps, reverse=True)[:TOP]]}


def _host_doing(host: list[tuple[int, int, str]], a: int, b: int) -> str:
    """The host event that covers most of the gap [a, b), or
    'unattributed': the program writes no span of its own into the
    profiler's trace yet."""
    best, best_cover = "unattributed", 0
    for lo, hi, name in host:
        cover = min(hi, b) - max(lo, a)
        if cover > best_cover:
            best, best_cover = name, cover
    return best if best_cover >= (b - a) // 2 else "unattributed"


def _events(line) -> list[tuple[int, int, str]]:
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name[:NAME])
            for e in line.events]


def read_planes(path: str) -> list[dict]:
    """Events of every line but the device's op line, which is only
    added up by name (it can hold millions of events)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines: dict[str, list] = {}
        by_name: dict[str, dict] = {}
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name == OP_LINE:
                names = by_name.setdefault(line.name, {})
                for e in line.events:
                    acc = names.setdefault(e.name[:NAME], [0, 0])
                    acc[0] += 1
                    acc[1] += int(e.duration_ns)
                continue
            lines.setdefault(line.name, []).extend(_events(line))
        if device and not lines.get(BUSY_LINES[0]):
            # No program line: fall back to the op line's own intervals.
            for line in plane.lines:
                if line.name == OP_LINE:
                    lines[OP_LINE] = _events(line)
        out.append({"name": plane.name, "lines": lines, "by_name": by_name})
    return out


def reduce_file(path: str, window_s: float | None = None) -> dict:
    return reduce_planes(read_planes(path), window_s)


def cut(path: str, out_path: str, per_line: int = 400) -> int:
    """Write a copy of the trace with at most `per_line` events in each
    line (the first ones), small enough to keep: what
    tests/recorded/tpu_v5e_slice.xplane.pb was made with. Returns the
    bytes written."""
    import json

    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    text = []
    for pid, plane in enumerate(data.planes, start=1):
        meta: dict[str, int] = {}
        lines = []
        for lid, line in enumerate(plane.lines, start=1):
            events = []
            for i, e in enumerate(line.events):
                if i >= per_line:
                    break
                mid = meta.setdefault(e.name, len(meta) + 1)
                events.append(
                    f"events {{ metadata_id: {mid} offset_ps: "
                    f"{int(e.start_ns * 1000)} duration_ps: "
                    f"{int(e.duration_ns * 1000)} }}")
            lines.append(f"lines {{ id: {lid} name: {json.dumps(line.name)} "
                         + " ".join(events) + " }")
        metas = [f"event_metadata {{ key: {mid} value {{ id: {mid} name: "
                 f"{json.dumps(name)} }} }}" for name, mid in meta.items()]
        text.append(f"planes {{ id: {pid} name: {json.dumps(plane.name)} "
                    + " ".join(lines + metas) + " }")
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(text))
    with open(out_path, "wb") as f:
        f.write(blob)
    return len(blob)
