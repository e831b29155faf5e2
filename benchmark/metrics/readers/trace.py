"""Numbers of the traced slice (harness/trace_reduce.py's reduction)."""

from __future__ import annotations


def read(spec: dict, ctx: dict) -> float | None:
    tr = ctx.get("trace")
    if not tr or not tr.get("whole_programs") or not tr.get("window_s"):
        # Under three program executions in the slice: a share of it
        # would be the share of a fragment. Nothing, not a number.
        return None
    if spec["reduce"] == "idle_share":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    raise ValueError(f"unknown trace reduction {spec['reduce']!r}")
