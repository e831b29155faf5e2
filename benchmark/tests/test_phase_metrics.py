"""The per-phase metrics PR 25 added as data (metrics/*.json read by
readers/prom_ratio.py): each against two hand-written samples of the
child's Prometheus text, and None where the phase was never observed
(the parent commit, a run with MINIO_TPU_TRACE=off, a cell that never
dispatches to the device lane)."""

import json
import os

import pytest

import run
from harness import prom
from metrics.readers import prom_ratio

HERE = os.path.dirname(os.path.abspath(__file__))

PHASE = "minio_tpu_v2_request_phase_ms"
DISP = "minio_tpu_v2_kernel_dispatch_phase_ms"


def _phase(api: str, phase: str, total: float, n: int) -> str:
    lb = f'api="{api}",phase="{phase}"'
    return (f"{PHASE}_sum{{{lb}}} {total}\n"
            f"{PHASE}_count{{{lb}}} {n}\n")


def _disp(kernel: str, backend: str, phase: str, total: float,
          n: int) -> str:
    lb = f'backend="{backend}",kernel="{kernel}",phase="{phase}"'
    return f"{DISP}_sum{{{lb}}} {total}\n{DISP}_count{{{lb}}} {n}\n"


BEFORE = (
    _phase("PUT-object", "unattributed", 10.0, 10)
    + _phase("GET-object", "unattributed", 10.0, 10)
    + _phase("PUT-object", "qos.wait", 5.0, 10)
    + _phase("GET-object", "ec.meta", 20.0, 10)
    + 'minio_tpu_v2_disk_op_duration_ms_sum{op="append_file"} 100\n'
    + 'minio_tpu_v2_disk_op_duration_ms_count{op="append_file"} 50\n'
    + "minio_tpu_v2_process_cpu_seconds_total 30.5\n")

# The window: 100 PUTs and 100 GETs finish; 40 device dispatches.
AFTER = (
    _phase("PUT-object", "unattributed", 10.0 + 300.0, 110)
    + _phase("GET-object", "unattributed", 10.0 + 500.0, 110)
    + _phase("PUT-object", "qos.wait", 5.0 + 100.0, 110)
    + _phase("GET-object", "qos.wait", 50.0, 100)
    + _phase("PUT-object", "door.hop", 200.0, 100)
    + _phase("GET-object", "door.hop", 1000.0, 100)
    + _phase("PUT-object", "lock.wait", 150.0, 100)
    + _phase("GET-object", "lock.wait", 100.0, 100)
    + _phase("HEAD-object", "lock.wait", 7777.0, 5)       # not read
    + _phase("PUT-object", "door.recv", 2000.0, 100)
    + _phase("PUT-object", "auth.sigv4", 1000.0, 100)
    + _phase("GET-object", "auth.sigv4", 5555.0, 100)     # not read
    + _phase("GET-object", "door.send", 4000.0, 100)
    + _phase("GET-object", "ec.meta", 20.0 + 900.0, 110)
    + _phase("GET-object", "ec.fetch", 7000.0, 100)
    + _phase("GET-object", "ec.verify", 12000.0, 100)
    + 'minio_tpu_v2_disk_op_duration_ms_sum{op="append_file"} 2500\n'
    + 'minio_tpu_v2_disk_op_duration_ms_count{op="append_file"} 650\n'
    + 'minio_tpu_v2_disk_op_duration_ms_sum{op="rename_data"} 9000\n'
    + 'minio_tpu_v2_disk_op_duration_ms_count{op="rename_data"} 600\n'
    + 'minio_tpu_v2_disk_op_duration_ms_sum{op="read_file"} 1\n'
    + 'minio_tpu_v2_disk_op_duration_ms_count{op="read_file"} 1\n'
    + _disp("hh256", "device", "prep", 400.0, 40)
    + _disp("hh256", "device", "enqueue", 80.0, 40)
    + _disp("hh256", "device", "wait", 30000.0, 40)
    + _disp("hh256", "native", "wait", 99999.0, 3)        # not read
    + 'minio_tpu_v2_kernel_dispatch_depth_sum'
      '{backend="device",kernel="hh256"} 220\n'
    + 'minio_tpu_v2_kernel_dispatch_depth_count'
      '{backend="device",kernel="hh256"} 40\n'
    + "minio_tpu_v2_process_cpu_seconds_total 90.5\n")

WANT = {
    "frontdoor.wait_ms": (100 + 50 + 200 + 1000 + 150 + 100) / 200,
    "frontdoor.put_recv_auth_ms": (2000 + 1000) / 100,
    "frontdoor.get_send_ms": 4000 / 100,
    "frontdoor.unattributed_ms": (300 + 500) / 200,
    "engine.get_meta_ms": 900 / 100,
    "engine.get_fetch_ms": 7000 / 100,
    "engine.get_verify_ms": 12000 / 100,
    "storage.append_ms": 2400 / 600,
    "storage.rename_ms": 9000 / 600,
    "codec.dispatch_host_ms": (400 + 80) / 40,
    "codec.dispatch_wait_ms": 30000 / 40,
    "codec.dispatch_depth": 220 / 40,
    "server.cpu_cores": 60.0 / 50.0,
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
    # The `.ops` entry reads the same file.
    assert run.metric_spec(name + ".ops") == spec


@pytest.mark.parametrize("name", sorted(set(WANT) - {"server.cpu_cores"}))
def test_nothing_to_read_gives_none(name):
    """A child without the series (the parent commit; tracing off; no
    device dispatch in the window) counts nothing: the metric stays off
    the line and nothing raises."""
    spec = run.metric_spec(name)
    old = ('minio_tpu_v2_api_request_duration_ms_count'
           '{api="PUT-object"} 7\n')
    assert prom_ratio.read(spec, _ctx(old, old)) is None
    assert prom_ratio.read(spec, _ctx(AFTER, AFTER)) is None


def test_cpu_cores_reads_zero_without_the_counter():
    spec = run.metric_spec("server.cpu_cores")
    assert prom_ratio.read(spec, _ctx("", "")) == 0.0


PUT_ONLY = ("frontdoor.put_recv_auth_ms", "storage.append_ms",
            "storage.rename_ms")


def test_every_new_quantity_has_both_entries():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    assert {"Storage drives", "Server process"} <= layers
    for name in WANT:
        spec = run.metric_spec(name)
        for n, moves in ((name, "goodput_mibps"),
                         (name + ".ops", "ops_per_s")):
            e = entries[n]
            assert e["moves"] == moves
            # Every cell that reports what it moves reads it, but three
            # that only a PUT fills: a read-only window (ec8p4_get_2lost,
            # PR 27) has no PUT phase and no write call to read.
            if n in PUT_ONLY:
                assert e["workloads"] == ["ec8p4_large_put_get"]
            else:
                assert "workloads" not in e
            assert (e["unit"], e["better"], e["source"], e["layer"]) == (
                spec["unit"], spec["better"], spec["source"],
                spec["layer"])
