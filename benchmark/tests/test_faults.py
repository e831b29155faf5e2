"""The whole of a run, the harness's look for a chip skipped
(--rehearse), with the timed path broken underneath: `correct` has to
come out false, once for each fault the cells can have. And once
unbroken, where it has to come out true."""

import json
import os

import pytest

import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
FAULTY = os.path.join(HERE, "faulty_child.py")

CASES = [
    ("get", "get_wrong_bytes"),
    ("stale", "get_wrong_bytes"),
    ("shard", "shard_frames_differ"),
    ("digest", "digest_frames_differ"),
]


def _run(capfd, monkeypatch, cell, fault=None):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    argv = ["--workload", cell, "--seed", "77", "--seconds", "2",
            "--trace", "0", "--rehearse"]
    if fault:
        monkeypatch.setenv("BENCH_FAULT", fault)
        monkeypatch.setenv("BENCH_FAULT_AFTER", "3")   # the warm-up's GETs
        rc = bench_run.main(argv, child=FAULTY)
    else:
        rc = bench_run.main(argv)
    out, err = capfd.readouterr()
    assert rc == 0, out[-2000:] + err[-2000:]
    return json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("fault,number", CASES)
def test_fault_reads_not_correct(capfd, monkeypatch, fault, number):
    result, err = _run(capfd, monkeypatch, "ec8p4_large_put_get", fault)
    assert result["correct"] is False
    assert result["checks"][number]["value"] > result["checks"][number]["limit"]
    assert f"check {number}: value" in err and "correct: False" in err


def test_sound_run_reads_correct(capfd, monkeypatch):
    result, err = _run(capfd, monkeypatch, "ec4p2_small_put_get")
    assert result["correct"] is True
    assert list(result)[-1] == "checks"
    assert err.strip().splitlines()[-1] == "correct: True"
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"ops_per_s", "setup_s"}
