"""A client-side number of the window (harness/window.summarize), read
in the traced run as a per-layer metric: {"reader": "summary", "key":
"put_p95_ms"}. No such operation in the window: None."""

from __future__ import annotations


def read(spec: dict, ctx: dict) -> float | None:
    return ctx["summary"].get(spec["key"])
