"""The eight `heal.*_ms` metrics of `ec12p4_heal` (metrics/*.json read by
readers/prom_ratio.py) over the heal's own span roots: `heal-object`, one
a healed object, and `heal-list`, an admin sweep's listing step
(request_phase_ms{api, phase}). Each against two hand-written samples of
the child's Prometheus text, None where the roots were never observed
(a child without them; tracing off), and a whole traced rehearsal of the
cell on the CPU."""

import json
import os

import pytest

import run
from harness import prom
from metrics.readers import prom_ratio
from .test_heal_cell import CELL, _run

PHASE = "minio_tpu_v2_request_phase_ms"


def _phase(api: str, phase: str, total: float, n: int) -> str:
    lb = f'api="{api}",phase="{phase}"'
    return f"{PHASE}_sum{{{lb}}} {total}\n{PHASE}_count{{{lb}}} {n}\n"


# The warm-up healed 2 objects; the window 48 more and one listing.
BEFORE = (
    _phase("heal-object", "unattributed", 20.0, 2)
    + _phase("heal-object", "heal.classify", 200.0, 2)
    + _phase("heal-object", "ec.fetch", 100.0, 2)
    + _phase("heal-object", "ec.write", 10.0, 2)
    + _phase("heal-object", "ec.commit", 8.0, 2)
    + _phase("heal-list", "heal.bucket", 3.0, 1)
    + _phase("heal-list", "heal.list", 5.0, 1)
    + _phase("heal-list", "unattributed", 1.0, 1))

AFTER = (
    _phase("heal-object", "unattributed", 20.0 + 480.0, 50)
    + _phase("heal-object", "heal.classify", 200.0 + 4800.0, 50)
    + _phase("heal-object", "ec.fetch", 100.0 + 2400.0, 50)
    + _phase("heal-object", "ec.verify", 4320.0, 48)
    + _phase("heal-object", "ec.decode", 3840.0, 48)
    + _phase("heal-object", "heal.frame", 1440.0, 48)
    + _phase("heal-object", "ec.write", 10.0 + 480.0, 50)
    + _phase("heal-object", "ec.commit", 8.0 + 240.0, 50)
    + _phase("heal-object", "lock.wait", 9999.0, 50)          # not read
    + _phase("GET-object", "ec.fetch", 7777.0, 12)            # not read
    + _phase("heal-list", "heal.bucket", 3.0 + 30.0, 2)
    + _phase("heal-list", "heal.list", 5.0 + 1500.0, 2)
    + _phase("heal-list", "unattributed", 1.0 + 2.0, 2))

WANT = {
    "heal.classify_ms": 4800 / 48,
    "heal.fetch_ms": 2400 / 48,
    "heal.verify_ms": 4320 / 48,
    "heal.decode_ms": 3840 / 48,
    "heal.frame_ms": 1440 / 48,
    "heal.write_commit_ms": (480 + 240) / 48,
    "heal.unattributed_ms": 480 / 48,
    "heal.list_ms": 30.0 + 1500.0 + 2.0,
}


def _ctx(before: str, after: str) -> dict:
    return {"before": prom.parse(before), "after": prom.parse(after),
            "run": {"counters_s": 50.0, "window_s": 50.0}}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reads_the_expected_number(name):
    spec = run.metric_spec(name)
    assert spec["name"] == name and spec["reader"] == "prom_ratio"
    assert prom_ratio.read(spec, _ctx(BEFORE, AFTER)) == \
        pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_child_without_the_roots_gives_none(name):
    """The parent commit opens no heal root, nor does a run with
    MINIO_TPU_TRACE=off: the metric stays off the line, nothing
    raises."""
    spec = run.metric_spec(name)
    old = ('minio_tpu_v2_heal_repair_bytes_total'
           '{mode="rs",src="disk"} 7\n')
    assert prom_ratio.read(spec, _ctx(old, old)) is None
    assert prom_ratio.read(spec, _ctx(AFTER, AFTER)) is None


def test_each_is_listed_for_the_heal_cell_alone():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in WANT:
        spec, e = run.metric_spec(name), entries[name]
        assert (e["unit"], e["better"], e["source"], e["layer"],
                e["moves"], e["workloads"]) == (
            "ms", "lower", "program_span", "Object layer, heal", "heal_s",
            [CELL])
        assert (spec["unit"], spec["better"], spec["source"],
                spec["layer"]) == (e["unit"], e["better"], e["source"],
                                   e["layer"])
    for cell in bench["workloads"]:
        got = {m["name"] for m in run.metrics_for(bench, cell["name"],
                                                  "per_layer")}
        assert (set(WANT) <= got) == (cell["name"] == CELL)


def test_traced_rehearsal_reads_the_heals_phases(capfd, monkeypatch):
    result, _ = _run(capfd, monkeypatch, trace=1)
    assert result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(WANT) <= set(got)
    for name in ("heal.classify_ms", "heal.fetch_ms",
                 "heal.write_commit_ms", "heal.list_ms"):
        assert got[name] > 0, name
    # What no phase covers is a small part of an object.
    assert got["heal.unattributed_ms"] < got["heal.object_ms"]
