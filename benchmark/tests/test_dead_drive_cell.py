"""The cell whose drive is dead at the operating system's level (PR 35):
whole --rehearse runs of `ec8p4_1dead_put_get` (sound, plain and traced;
the fault stated and not applied), and its files against the healthy
control's, `ec8p4_large_put_get`: the same deployment and the same
traffic but for the dead drive."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (HERE, os.path.dirname(HERE)):     # run as a script as well
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as bench_run  # noqa: E402

CELL, CONTROL = "ec8p4_1dead_put_get", "ec8p4_large_put_get"
SEED = "3500000077"
DEGRADED = ("degraded.dead_drive_calls_per_op",
            "degraded.heal_attempts_per_put",
            "degraded.background_read_bytes_per_user_byte")


def _run(capfd, monkeypatch, trace=0, seconds="3"):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = bench_run.main(["--workload", CELL, "--seed", SEED, "--seconds",
                         seconds, "--trace", str(trace), "--rehearse"])
    out, err = capfd.readouterr()
    assert rc == 0, out[-2000:] + err[-2000:]
    with open(os.path.join(bench_run.ROOT, "chiprun_out",
                           f"{CELL}-{SEED}-{trace}.json")) as f:
        record = json.load(f)
    return json.loads(out.strip().splitlines()[-1]), record, err


def _values(result):
    return {k: v["value"] for k, v in result["checks"].items()}


def test_files_are_the_controls_but_for_the_dead_drive():
    bench, cell, config = bench_run.load_cell(CELL)
    _, control, healthy = bench_run.load_cell(CONTROL)
    for key in ("drives", "sets", "storage_class", "data", "parity",
                "block_size", "bitrot", "chips", "env", "env_traced"):
        assert config[key] == healthy[key], key
    assert config["dead_drives"] == [5] and config["reduced"] == []
    assert set(healthy["guarantees"]) < set(config["guarantees"])
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["source"] == config["source"] and entry["reduced"] == []
    mixes = []
    for name in (cell["traffic"], control["traffic"]):
        with open(os.path.join(bench_run.HERE, "traffic", name + ".json")) as f:
            mixes.append(json.load(f))
    assert mixes[0].pop("faults") == {"offline_drives": [5]}
    assert mixes[1].pop("faults") == {}
    assert mixes[0] == mixes[1]
    assert bench_run.lost_drives({"offline_drives": [5]}) == {4}
    # One chip, goodput and set-up end to end, the three new entries
    # its own, the control's layers beside them.
    assert cell["chips"] == 1
    assert {m["name"] for m in bench_run.metrics_for(
        bench, CELL, "end_to_end")} == {"goodput_mibps", "setup_s"}
    mine = {m["name"] for m in bench_run.metrics_for(bench, CELL,
                                                     "per_layer")}
    theirs = {m["name"] for m in bench_run.metrics_for(bench, CONTROL,
                                                       "per_layer")}
    assert mine - theirs == set(DEGRADED) | {
        "engine.get_decode_ms", "codec.decode_wall_s_per_gib",
        "codec.decode_device_bytes_share"}
    assert theirs <= mine
    for m in bench["per_layer"]:
        if m["name"] in DEGRADED:
            assert m["workloads"] == [CELL] and m["moves"] == "goodput_mibps"
            assert bench_run.metric_spec(m["name"])["reader"] == "prom_ratio"


def test_dead_drive_cell_is_correct_and_degraded(capfd, monkeypatch):
    result, record, err = _run(capfd, monkeypatch)
    assert result["correct"] is True, err[-2000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(_values(result).values()) == {0}
    assert set(result["metrics"]) == {"goodput_mibps", "setup_s"}
    at_rest = record["at_rest"]
    # 11 shard files an object, none on the dead drive, and the GETs of
    # objects whose shard there is data had to reconstruct: all did.
    assert at_rest["shard_files_checked"] == 11 * at_rest["objects_checked"]
    assert at_rest["lost_copies_present"] == 0
    assert 0 < at_rest["degraded_reads"] <= at_rest["reads_decoded"]
    # No 5xx and no traceback in the server's own log: the dead drive
    # is an event the program expects.
    with open(os.path.join(bench_run.ROOT, "chiprun_out",
                           f"{CELL}-{SEED}-0.server.log")) as f:
        log = f.read()
    assert "Traceback" not in log and " ERROR " not in log, log[-3000:]


def test_traced_run_reads_every_degraded_metric(capfd, monkeypatch):
    result, record, err = _run(capfd, monkeypatch, trace=1, seconds="4")
    assert result["correct"] is True, err[-2000:]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(DEGRADED) <= set(got)
    # The debt is kept, not chased: no heal attempt started, no
    # survivor byte read in the background; calls to the dead drive
    # are bounded by the monitor's 32 and the prober's 3 a tick.
    assert got["degraded.heal_attempts_per_put"] == 0.0
    assert got["degraded.background_read_bytes_per_user_byte"] == 0.0
    assert 0 < got["degraded.dead_drive_calls_per_op"] * \
        result["attempted"] <= 32 + 3 * 3
    assert got["engine.get_decode_ms"] > 0
    assert got["codec.decode_wall_s_per_gib"] > 0


def test_fault_stated_and_not_applied_is_not_correct(capfd, monkeypatch):
    """The control of the cell's own numbers: all 12 drives answer, so
    copies stand where the mix says a drive is dead."""
    monkeypatch.setattr(bench_run, "apply_faults", lambda faults, srv: None)
    result, _, err = _run(capfd, monkeypatch)
    got = _values(result)
    assert result["correct"] is False and "correct: False" in err
    assert got["lost_copies_present"] == 3      # 3 sampled x 1 drive
    # ... and no GET had anything to reconstruct.
    assert {k for k, v in got.items() if v} == {
        "lost_copies_present", "degraded_reads_not_decoded"}
