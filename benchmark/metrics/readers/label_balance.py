"""How evenly the increase of one series spread over the values of one
of its labels, in %: the least increase over the greatest, 100 = even.

    "metric": "<series name>", "label": "<the label compared>",
    "labels": {...}     optional: fixed values of other labels
    "expect": n         optional: how many values the deployment has
                        (its devices, its sets); one that shows no
                        series at all counts as an increase of 0

The increases are taken between window open and close, each value of
the label summed over the series' other labels. Nothing to read (the
series absent at both reads, no value moved, or, without `expect`,
fewer than two moved) gives None and the metric stays off the line."""

from __future__ import annotations

from harness import prom


def read(spec: dict, ctx: dict) -> float | None:
    name, label = spec["metric"], spec["label"]
    values = {dict(ls).get(label) for sample in (ctx["before"], ctx["after"])
              for (n, ls) in sample if n == name} - {None}
    moved = [prom.delta(ctx["before"], ctx["after"], name,
                        {**spec.get("labels", {}), label: v})
             for v in sorted(values)]
    moved = [m for m in moved if m > 0]
    if not moved:
        return None
    moved += [0.0] * (spec.get("expect", 0) - len(moved))
    if len(moved) < 2:
        return None
    return 100.0 * min(moved) / max(moved)
