"""scale * num / den over the window, each a term:

    {"metric": "<series name>", "labels": {"k": "v" | ["v1", "v2"]}}
        the series' increase between window open and close
    {"run": "user_gib" | "window_s" | "loadgen_cpu_s" | ...}
        a number of the run itself (run.py's `run` record)
    {"const": 1}
    {"sum": [term, ...]}        each term may carry "sign": -1

`den` left out means 1. Nothing to read (a zero denominator: the child
counted no such request, phase or byte) gives None and the metric stays
off the line.
"""

from __future__ import annotations

from harness import prom


def term(spec: dict, ctx: dict) -> float | None:
    if "sum" in spec:
        vals = [term(t, ctx) for t in spec["sum"]]
        if any(v is None for v in vals):
            return None
        return sum(v * t.get("sign", 1) for v, t in zip(vals, spec["sum"]))
    if "const" in spec:
        return float(spec["const"])
    if "run" in spec:
        return ctx["run"].get(spec["run"])
    return prom.delta(ctx["before"], ctx["after"], spec["metric"],
                      spec.get("labels"))


def read(spec: dict, ctx: dict) -> float | None:
    num = term(spec["num"], ctx)
    den = term(spec["den"], ctx) if "den" in spec else 1.0
    if num is None or not den:
        return None
    return spec.get("scale", 1.0) * num / den
