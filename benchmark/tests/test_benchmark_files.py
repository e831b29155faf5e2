"""File checks only, no server, under a second: every cell of
BENCHMARK.json resolves to its configuration, traffic and metric files,
every fault key a mix names is one run.py applies, every per-layer
`workloads` and `moves` names something that exists. (ISSUE 27 asked
for this file in tier-1, tests/; a benchmark PR adds files under the
benchmark's own directories only, so it lives here until a later PR
moves it.)"""

import glob
import inspect
import json
import os

import run as bench_run
from harness import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}


def test_every_cell_resolves_to_its_files():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    assert {w["config"] for w in CELLS.values()} == set(configs)
    for name, cell in CELLS.items():
        _, found, config = bench_run.load_cell(name)
        assert found == cell
        assert config["name"] == cell["config"]
        assert configs[cell["config"]]["file"].startswith("benchmark/")
        assert config["drives"] % config.get("sets", 1) == 0
        assert config["drives"] // config.get("sets", 1) == \
            config["data"] + config["parity"]
        assert cell["chips"] == config["chips"]
        for rehearse in (False, True):
            mix = traffic.load(os.path.join(
                BENCH, "traffic", cell["traffic"] + ".json"),
                cell["traffic"], rehearse)
            drives = [d for key in ("offline_drives", "remove_object_copies",
                                    "remove_drive_copies")
                      for d in mix.faults.get(key, [])]
            assert all(1 <= d <= config["drives"] for d in drives)
            # A set keeps read quorum: no more drives lost than parity.
            assert len(bench_run.lost_drives(mix.faults)) <= config["parity"]
            if not mix.writes:
                assert mix.preload_per_client > 0, "nothing to read"
        ends = bench_run.metrics_for(MANIFEST, name, "end_to_end")
        assert {"setup_s"} < {m["name"] for m in ends}
        assert bench_run.metrics_for(MANIFEST, name, "per_layer")


def test_every_fault_key_of_every_mix_is_one_run_py_applies():
    applied = inspect.getsource(bench_run.apply_faults)
    for key in traffic.FAULTS:
        assert f'"{key}"' in applied, key
    for path in glob.glob(os.path.join(BENCH, "traffic", "*.json")):
        with open(path) as f:
            doc = json.load(f)
        for faults in (doc.get("faults", {}),
                       doc.get("rehearse", {}).get("faults", {})):
            assert set(faults) <= set(traffic.FAULTS), path


def test_every_metric_entry_names_things_that_exist():
    ends = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["end_to_end"]:
        assert set(m.get("workloads", [])) <= set(CELLS), m["name"]
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in ends, m["name"]
        spec = bench_run.metric_spec(m["name"])
        assert os.path.exists(os.path.join(
            BENCH, "metrics", "readers", spec["reader"] + ".py"))
        cells = set(m.get("workloads", []))
        assert cells <= set(CELLS), m["name"]
        # Each listed cell reports the end-to-end metric this one moves.
        moved = ends[m["moves"]].get("workloads", list(CELLS))
        assert cells <= set(moved), m["name"]
    names = [m["name"] for kind in ("end_to_end", "per_layer")
             for m in MANIFEST[kind]]
    assert len(names) == len(set(names))


def test_a_metric_that_needs_a_put_is_listed_only_where_one_is_sent():
    """A per-layer metric without `workloads` has to be on the line of
    every cell that reports what it moves: a window that sends no PUT
    has nothing for a PUT phase or a write call to read."""
    put_only = {"frontdoor.put_recv_auth_ms", "storage.append_ms",
                "storage.rename_ms", "engine.put_encode_ms",
                "engine.put_write_commit_ms"}
    for name, cell in CELLS.items():
        mix = traffic.load(os.path.join(
            BENCH, "traffic", cell["traffic"] + ".json"), cell["traffic"])
        if mix.writes:
            continue
        got = {m["name"] for m in
               bench_run.metrics_for(MANIFEST, name, "per_layer")}
        assert not {g for g in got
                    if g in put_only or g.rsplit(".", 1)[0] in put_only}, name
