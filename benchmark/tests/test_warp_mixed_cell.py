"""`ec8p4_warp_mixed`: warp's mixed workload over a preloaded pool, and
the check that an acknowledged DELETE leaves nothing behind. Whole
rehearsals (the harness's look for a chip skipped): sound, and over a
child whose DELETE answers 204 and removes nothing (`delete_noop`) or
removes xl.meta and leaves the data directory (`delete_orphan`).

The cell is not in BENCHMARK.json (PERF.md section 7: its runs on the
chip spread too widely to hold the bound of `ops_per_s`); its traffic
and metric files are. `with_cell` adds the entries a later PR would
enter, and the runs here read BENCHMARK.json through it.

On the chip's machine, at the cell's own size, the same controls:

    python benchmark/tests/test_warp_mixed_cell.py <fault> <seconds> <seed>...
"""

import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402

FAULTY = os.path.join(HERE, "faulty_child.py")
CELL = "ec8p4_warp_mixed"
WORKLOAD = {
    "name": CELL, "config": "ec8p4_12d", "traffic": "warp_mixed", "chips": 1,
    "why": "warp mixed: 20 closed-loop clients GET 45/HEAD 30/PUT 15/DELETE "
           "10 of 1 KiB-10 MiB (log sizes) over 2,500 preloaded objects: "
           "metadata reads, deletes, host and device paths in one queue"}
NEW = ("frontdoor.head_ms", "frontdoor.delete_ms", "storage.delete_ms")
LISTED = ("frontdoor.put_ms", "frontdoor.get_ms", "client.put_p95_ms",
          "client.get_p95_ms", "engine.put_encode_ms.ops",
          "engine.put_write_commit_ms.ops")
# A seed whose generated stream keeps every kind within 2 points of its
# weight at every length of 40 to 300 operations a client: the mix
# below is then the harness's, not the draw's.
SEED = 4_000_000_008


def with_cell(bench: dict) -> dict:
    """BENCHMARK.json with the cell's entries: the workload, `ops_per_s`
    and six per-layer entries listing it, and one entry for each new
    metric file."""
    bench = copy.deepcopy(bench)
    bench["workloads"].append(WORKLOAD)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "ops_per_s" or m["name"] in LISTED:
            m["workloads"].append(CELL)
    for name in NEW:
        spec = bench_run.metric_spec(name)
        bench["per_layer"].append(
            {k: spec[k] for k in ("name", "unit", "better", "source",
                                  "layer")}
            | {"moves": "ops_per_s", "workloads": [CELL]})
    return bench


def load_cell(name: str, real=bench_run.load_cell):
    """run.load_cell, with the cell entered."""
    if name != CELL:
        return real(name)
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = with_cell(json.load(f))
    entry = next(c for c in bench["configs"]
                 if c["name"] == WORKLOAD["config"])
    with open(os.path.join(bench_run.ROOT, entry["file"])) as f:
        return bench, WORKLOAD, json.load(f)


def _run(capfd, monkeypatch, seconds, trace=0, fault=None):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(bench_run, "load_cell", load_cell)
    argv = ["--workload", CELL, "--seed", str(SEED), "--seconds",
            str(seconds), "--trace", str(trace), "--rehearse"]
    if fault:
        monkeypatch.setenv("BENCH_FAULT", fault)
        # The warm-up's GETs: 3 passes (11 sizes, 4 clients) of one each.
        monkeypatch.setenv("BENCH_FAULT_AFTER", "12")
        rc = bench_run.main(argv, child=FAULTY)
    else:
        rc = bench_run.main(argv)
    out, err = capfd.readouterr()
    assert rc == 0, out[-2000:] + err[-2000:]
    with open(os.path.join(bench_run.ROOT, "chiprun_out",
                           f"{CELL}-{SEED}-{trace}.json")) as f:
        record = json.load(f)
    return json.loads(out.strip().splitlines()[-1]), err, record


def _values(result):
    return {k: v["value"] for k, v in result["checks"].items()}


def test_the_entries_name_the_cell():
    bench, cell, config = load_cell(CELL)
    assert CELL not in {w["name"] for w in bench_run.load_cell(
        "ec8p4_large_put_get")[0]["workloads"]}
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ec8p4_12d", "warp_mixed", 1) and len(cell["why"]) <= 200
    assert (config["data"], config["parity"], config["drives"]) == (8, 4, 12)
    assert [m["name"] for m in bench_run.metrics_for(bench, CELL,
                                                     "end_to_end")] == [
        "ops_per_s", "setup_s"]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "ops_per_s"
    for name in LISTED:
        assert entries[name]["workloads"] == ["ec4p2_small_put_get", CELL]
    # Nothing else comes to the cell's line but the `*` entries of
    # ops_per_s, which attach by themselves.
    got = {m["name"] for m in bench_run.metrics_for(bench, CELL,
                                                    "per_layer")}
    assert got == set(NEW) | set(LISTED) | {
        m["name"] for m in bench["per_layer"]
        if m["moves"] == "ops_per_s" and "workloads" not in m}


def test_sound_traced_rehearsal(capfd, monkeypatch):
    result, err, record = _run(capfd, monkeypatch, 10, trace=1)
    assert result["correct"] is True, err[-2000:]
    assert set(_values(result).values()) == {0}
    assert result["failed"] == 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    for name in NEW + ("frontdoor.put_ms", "frontdoor.get_ms",
                       "client.put_p95_ms", "client.get_p95_ms"):
        assert got[name] > 0, name
    # The realised mix, and every size of the rehearsal's 11 written.
    mix = record["summary"]["by_kind"]
    n = sum(mix.values())
    for kind, w in {"GET": 45, "HEAD": 30, "PUT": 15, "DELETE": 10}.items():
        assert 100 * mix[kind] / n == pytest.approx(w, abs=5), mix
    assert {k.split("/")[1] for k in record["summary"]["by_size"]
            if k.startswith("PUT/")} == {str(1 << i) for i in range(10, 21)}
    # 4 clients x fill 8 before the warm-up; the deleted keys checked.
    assert 0 < record["at_rest"]["deleted_keys_checked"] <= 48
    assert "check deleted_keys_present: value 0 limit 0" in err


@pytest.mark.parametrize("fault", ["delete_noop", "delete_orphan"])
def test_a_delete_that_leaves_something_is_not_correct(capfd, monkeypatch,
                                                       fault):
    result, err, record = _run(capfd, monkeypatch, 6, fault=fault)
    assert result["correct"] is False
    got = _values(result)
    checked = record["at_rest"]["deleted_keys_checked"]
    assert checked > 0
    # xl.meta gone: the key answers 404, its data directory is still there.
    assert (got["deleted_keys_readable"], got["deleted_keys_present"]) == (
        (checked, checked) if fault == "delete_noop" else (0, checked))
    assert {k for k, v in got.items() if v} <= {"deleted_keys_readable",
                                                 "deleted_keys_present"}
    assert "correct: False" in err


def test_an_answer_altered_where_it_is_produced_is_not_correct(capfd,
                                                               monkeypatch):
    result, _, _ = _run(capfd, monkeypatch, 4, fault="get")
    assert result["correct"] is False
    assert _values(result)["get_wrong_bytes"] > 0


if __name__ == "__main__":
    # The chip's machine: the control at the cell's own size and load.
    fault, seconds = sys.argv[1], sys.argv[2]
    os.environ["BENCH_FAULT"] = fault
    bench_run.load_cell = load_cell
    for seed in sys.argv[3:]:
        bench_run.main(["--workload", CELL, "--seed", seed, "--seconds",
                        seconds, "--trace", "0", "--tag", fault],
                       child=FAULTY)
