"""Reading the serving child's Prometheus text (/minio-tpu/v2/metrics/node)
and taking deltas of it (after chip_smoke.py's `metrics`/`msum`)."""

from __future__ import annotations

Sample = dict[tuple, float]     # {(name, (("label", "value"), ...)): value}


def parse(text: str) -> Sample:
    out: Sample = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, val = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = []
        for part in rest.rstrip("}").split('",'):
            if "=" in part:
                k, v = part.split("=", 1)
                labels.append((k.strip(), v.strip().strip('"')))
        try:
            out[(name, tuple(sorted(labels)))] = float(val)
        except ValueError:
            pass
    return out


def msum(m: Sample, name: str, labels: dict | None = None) -> float:
    """Sum of every series of `name` whose labels match; a label's wanted
    value may be one string or a list of them."""
    want = {k: ([v] if isinstance(v, str) else list(v))
            for k, v in (labels or {}).items()}
    return sum(v for (n, ls), v in m.items() if n == name
               and all(dict(ls).get(k) in vs for k, vs in want.items()))


def delta(before: Sample, after: Sample, name: str,
          labels: dict | None = None) -> float:
    return msum(after, name, labels) - msum(before, name, labels)


def series(m: Sample, prefix: str) -> dict[str, float]:
    """Flat {name{labels}: value} of every series under a prefix (for
    the earlier-line records)."""
    return {n + "{" + ",".join(f'{k}="{v}"' for k, v in ls) + "}": v
            for (n, ls), v in sorted(m.items()) if n.startswith(prefix)}
