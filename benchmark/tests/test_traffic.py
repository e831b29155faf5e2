"""Traffic from a seed: the same twice, another from another seed, the
same multiset of sizes whatever the seed; every committed mix parses,
and so does what no cell uses yet (open loop, weights, zipf, faults)."""

import glob
import json
import os
import sys

import pytest

from harness import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = sorted(glob.glob(os.path.join(os.path.dirname(HERE), "traffic",
                                      "*.json")))


def ops(seed, mix, n=60, due=False):
    out = []
    for s in traffic.streams(seed, mix):
        for _ in range(n):
            op = s.next()
            if op.kind in traffic.WRITES:      # as if acknowledged
                s.written[op.key] = op.size
                s.last_put = op.key
            out.append((s.group.name, s.client, op.kind, op.key, op.size,
                        op.off) + ((op.due_s,) if due else ()))
    return out


@pytest.mark.parametrize("path", MIXES)
@pytest.mark.parametrize("rehearse", [False, True])
def test_committed_mixes(path, rehearse):
    name = os.path.basename(path)[:-5]
    mix = traffic.load(path, name, rehearse)
    big = 3_000_000_019
    assert ops(big, mix) == ops(big, mix)
    assert ops(big, mix) != ops(big + 1, mix)
    for g in mix.groups:
        a = sorted(o[4] for o in ops(1, mix, 2 * len(g.sizes) * 4)
                   if o[0] == g.name and o[2] == "PUT")
        b = sorted(o[4] for o in ops(2, mix, 2 * len(g.sizes) * 4)
                   if o[0] == g.name and o[2] == "PUT")
        if g.sequence:
            assert a == b                  # same sizes, another order
            continue
        # Weights draw another number of PUTs from each seed: every
        # whole cycle of a client's PUT sizes is the group's multiset.
        for seed in (1, 2):
            for s in traffic.streams(seed, mix):
                if s.group is g:
                    got = [s.next_size() for _ in range(3 * len(g.sizes))]
                    for i in range(0, len(got), len(g.sizes)):
                        assert sorted(got[i:i + len(g.sizes)]) == \
                            sorted(g.sizes)


# sha256 (first 16 hex) of repr(Traffic) of the five accepted mixes,
# plain and --rehearse, as commit 91b2876 (PR 33) parsed them: the
# harness learning a timed heal (PR 34) moved none of them.
PARSED_AT_PR33 = {
    "get_2lost": ("1e494ad198b839ba", "63e8bc7d88d85383"),
    "large_put_get": ("5008afaeb9f5642c", "7fee2b1b93dba6a5"),
    "large_put_get_16c": ("818820ae9cb2f03e", "6ae4c60cfb06bca5"),
    "multipart_put_get": ("f56291a5305f71d7", "2adf77737fb2728f"),
    "small_put_get": ("b1b34b5422bcb347", "d7abc7f9e70d6080"),
}


@pytest.mark.parametrize("name", sorted(PARSED_AT_PR33))
def test_accepted_mixes_parse_to_the_same_traffic(name):
    import hashlib
    path = os.path.join(os.path.dirname(HERE), "traffic", name + ".json")
    got = tuple(hashlib.sha256(repr(traffic.load(path, name, r)).encode())
                .hexdigest()[:16] for r in (False, True))
    assert got == PARSED_AT_PR33[name]
    assert "heal" not in traffic.load(path, name).faults


# sha256 (first 16 hex) of the first 200 operations of every client of
# each accepted mix, plain and --rehearse, seed 3,000,000,019, as the
# parent of the preload `fill` (commit ecbc0ee) drew them: a mix without
# `fill` draws exactly the random numbers it drew before.
STREAMS_BEFORE_FILL = {
    "get_2lost": ("ed09c9b8827ad74f", "9e8a41b703a12304"),
    "heal_one_drive": ("48b141e2d99136c8", "c4e8d7cceeb43c9a"),
    "large_put_get": ("c946201d8d3f4827", "895c8935948c54d6"),
    "large_put_get_16c": ("7abf94536047b76f", "c86ad4db6c5843e5"),
    "large_put_get_1dead": ("c946201d8d3f4827", "895c8935948c54d6"),
    "multipart_put_get": ("dd7ea83a771eb86c", "b00645b4cc4365e8"),
    "small_put_get": ("f98016df6fbe3b50", "94e2e70c22bf82e9"),
}


@pytest.mark.parametrize("name", sorted(STREAMS_BEFORE_FILL))
def test_a_mix_without_fill_draws_the_stream_it_drew(name):
    import hashlib
    path = os.path.join(os.path.dirname(HERE), "traffic", name + ".json")
    got = []
    for r in (False, True):
        mix = traffic.load(path, name, r)
        assert mix.preload_fill == 0
        seq = ops(3_000_000_019, mix, 200, due=True)
        got.append(hashlib.sha256(repr(seq).encode()).hexdigest()[:16])
    assert tuple(got) == STREAMS_BEFORE_FILL[name]


WARP = os.path.join(os.path.dirname(HERE), "traffic", "warp_mixed.json")


def test_preload_fill_gives_n_live_keys_a_client_and_leaves_the_slot_at_n():
    for rehearse, n in ((False, 125), (True, 8)):
        mix = traffic.load(WARP, "warp_mixed", rehearse)
        assert mix.preload_fill == n
        for s in traffic.streams(5_000_000_011, mix):
            fill = s.fill_ops(mix.preload_fill)
            assert [o.kind for o in fill] == ["PUT"] * n
            assert [o.key for o in fill] == [s.key(i) for i in range(n)]
            for o in fill:                 # as if acknowledged
                s.written[o.key] = o.size
            assert len(s.written) == n and s._slot == n
            # The window's PUTs write new names, from slot n on.
            puts = []
            while len(puts) < 3:
                op = s.next()
                if op.kind == "PUT":
                    puts.append(op.key)
                    s.written[op.key] = op.size
            assert puts == [s.key(i) for i in range(n, n + 3)]
    # Sizes and bodies are drawn as a window PUT draws them.
    g = traffic.load(WARP, "warp_mixed").groups[0]
    a, b = traffic.ClientStream(9, g, 0), traffic.ClientStream(9, g, 0)
    fill = a.fill_ops(4)
    assert [(o.size, o.off) for o in fill] == [
        (b.next_size(), b.rng.randrange(traffic.OFFSET_SPAN))
        for _ in range(4)]
    with pytest.raises(traffic.TrafficError, match="fill"):
        traffic.parse("m", {"groups": [{
            "clients": 1, "sequence": ["PUT"], "sizes": {"cycle": [1]},
            "keys": {"ring": 4}}], "preload": {"fill": 5}})


def test_warp_mixed_is_warps_mixed_workload():
    mix = traffic.load(WARP, "warp_mixed")
    (g,) = mix.groups
    assert (mix.loop, g.clients, g.ring, g.read, g.rate_per_s) == (
        "closed", 20, 250, "ring", 0)
    assert g.weights == {"GET": 45, "HEAD": 30, "PUT": 15, "DELETE": 10}
    assert g.sizes == [1 << i for i in range(10, 24)] + [10 << 20]
    assert sum(g.sizes) / len(g.sizes) == pytest.approx(1.733 * (1 << 20),
                                                        rel=1e-3)
    assert (mix.preload_fill, mix.at_rest_sample) == (125, 12)
    assert mix.preload_fill * g.clients == 2500 and mix.faults == {}
    assert mix.writes and mix.timeout_s == 60
    # The stream's mix, over 20 clients x 500 operations.
    kinds = [o[2] for o in ops(7, mix, 500)]
    for k, w in g.weights.items():
        assert 100 * kinds.count(k) / len(kinds) == pytest.approx(w, abs=1.5)
    # Warm-up: one operation of each kind on one new key, written first
    # at the size given, deleted last.
    s = traffic.ClientStream(7, g, 3)
    s.fill_ops(mix.preload_fill)
    warm = s.warm_ops(4 << 20)
    assert [o.kind for o in warm] == ["PUT", "GET", "HEAD", "DELETE"]
    assert {o.key for o in warm} == {s.key(125)}
    assert {o.size for o in warm} == {4 << 20}
    assert s._slot == 126


def test_large_mix_is_the_cell_the_issue_names():
    mix = traffic.load(os.path.join(os.path.dirname(HERE), "traffic",
                                    "large_put_get.json"), "large_put_get")
    (g,) = mix.groups
    assert (g.clients, g.ring, g.sequence) == (8, 8, ["PUT", "GET"])
    assert g.sizes == [10 << 20, 25 << 20, (50 << 20) + 12345]
    s = traffic.ClientStream(9, g, 0)
    put = s.next()
    s.last_put, s.written[put.key] = put.key, put.size
    get = s.next()
    assert (put.kind, get.kind, get.key) == ("PUT", "GET", put.key)


def test_what_no_cell_uses_yet_parses_and_generates():
    doc = {"loop": "open", "timeout_s": 10, "groups": [{
        "name": "mixed", "clients": 4, "rate_per_s": 200,
        "arrivals": "poisson",
        "weights": {"GET": 45, "HEAD": 30, "PUT": 15, "DELETE": 10},
        "sizes": {"weighted": [[4096, 6], [1048576, 2]]},
        "keys": {"ring": 32}, "read": {"zipf": 0.99}}],
        "preload": {"per_client": 32},
        "faults": {"remove_drive_copies": [2, 5]}}
    mix = traffic.parse("warp_mixed_open", doc)
    assert mix.loop == "open" and mix.preload_per_client == 32
    assert mix.faults == {"remove_drive_copies": [2, 5]}
    assert mix.writes
    got = ops(11, mix, 200)
    assert {o[2] for o in got} >= {"GET", "HEAD", "PUT"}
    s = traffic.ClientStream(11, mix.groups[0], 0)
    dues = [s.next().due_s for _ in range(400)]
    assert dues == sorted(dues)
    assert dues[-1] / 400 == pytest.approx(4 / 200, rel=0.25)


def test_fault_keys_and_a_window_that_writes_nothing():
    doc = {"groups": [{"clients": 2, "sequence": ["GET"],
                       "sizes": {"cycle": [4096]}, "keys": {"ring": 2},
                       "read": "ring"}],
           "preload": {"per_client": 2}}
    for key in traffic.FAULTS[:-1]:
        mix = traffic.parse("m", dict(doc, faults={key: [2, 5]}))
        assert mix.faults == {key: [2, 5]} and not mix.writes
    with pytest.raises(traffic.TrafficError, match="unknown fault"):
        traffic.parse("m", dict(doc, faults={"unplug_drive": [2]}))
    # A timed heal is the pair wipe_drive + heal, both numbers stated.
    assert traffic.FAULTS[-1] == "heal"
    heal = {"poll_s": 0.02, "trace_start_s": 1.0}
    mix = traffic.parse("m", dict(doc, faults={"wipe_drive": 2,
                                               "heal": heal}))
    assert mix.faults["heal"] == heal
    for bad in ({"heal": heal}, {"wipe_drive": [2], "heal": heal},
                {"wipe_drive": 2, "heal": dict(heal, retries=3)},
                {"wipe_drive": 2, "heal": {"poll_s": 0.02}},
                {"wipe_drive": 2, "heal": True}):
        with pytest.raises(traffic.TrafficError, match="faults.heal"):
            traffic.parse("m", dict(doc, faults=bad))


def test_degraded_mix_is_the_cell_the_issue_names():
    mix = traffic.load(os.path.join(os.path.dirname(HERE), "traffic",
                                    "get_2lost.json"), "get_2lost")
    (g,) = mix.groups
    assert (g.clients, g.ring, g.sequence, g.read) == (4, 4, ["GET"], "ring")
    assert g.sizes == [25 << 20] and mix.preload_per_client == 4
    assert mix.faults == {"remove_object_copies": [2, 5]}
    assert not mix.writes and mix.at_rest_sample == 6
    # The preload of a group that never writes fills its ring: 4 PUTs of
    # 4 slots a client, 16 objects, and reads only from then on.
    import zlib
    split = [0, 0, 0]
    for s in traffic.streams(7, mix):
        puts = [s.next() for _ in range(mix.preload_per_client)]
        assert [o.kind for o in puts] == ["PUT"] * 4
        assert [o.key for o in puts] == [s.key(i) for i in range(4)]
        for o in puts:
            s.written[o.key] = o.size
        assert {s.next().kind for _ in range(40)} == {"GET"}
        # Key names do not depend on the seed, and shard indices rotate
        # by crc32 of bucket/key: the split of the 16 objects by data
        # shards lost to drives 2 and 5 is the same on every seed.
        for key in s.written:
            start = zlib.crc32(f"bench/{key}".encode()) % 12
            order = [1 + (start + i) % 12 for i in range(1, 13)]
            split[sum(order[d - 1] <= 8 for d in (2, 5))] += 1
    assert split == [1, 8, 7]


@pytest.mark.parametrize("bad", [
    {"groups": []},
    {"loop": "sometimes", "groups": [{"clients": 1, "sequence": ["PUT"],
                                      "sizes": {"cycle": [1]},
                                      "keys": {"ring": 1}}]},
    {"groups": [{"clients": 1, "sequence": ["FROB"],
                 "sizes": {"cycle": [1]}, "keys": {"ring": 1}}]},
    {"loop": "open", "groups": [{"clients": 1, "sequence": ["PUT"],
                                 "sizes": {"cycle": [1]},
                                 "keys": {"ring": 1}}]},
])
def test_a_malformed_mix_is_refused(bad):
    with pytest.raises(traffic.TrafficError):
        traffic.parse("bad", bad)


def test_benchmark_json_finds_every_file():
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        with open(os.path.join(root, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(
            root, "benchmark", "traffic", w["traffic"] + ".json"))
    sys.path.insert(0, os.path.join(root, "benchmark"))
    import run
    ends = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        # Found by name; `<quantity>.<variant>` reads the quantity's file.
        spec = run.metric_spec(m["name"])
        assert m["name"].startswith(spec["name"])
        for key in ("layer", "unit", "better", "source"):
            assert spec[key] == m[key], (m["name"], key)
        # Which end-to-end metric it moves and in which cells is said
        # once, in BENCHMARK.json.
        assert "moves" not in spec and "workloads" not in spec
        assert m["moves"] in ends
        assert os.path.exists(os.path.join(
            root, "benchmark", "metrics", "readers", spec["reader"] + ".py"))


def test_small_mix_is_the_cell_the_issue_names_plus_one_device_path_get():
    mix = traffic.load(os.path.join(os.path.dirname(HERE), "traffic",
                                    "small_put_get.json"), "small_put_get")
    main, dev = mix.groups
    assert (main.clients, main.ring, main.sequence, main.sizes) == \
        (20, 64, ["PUT", "GET"], [1 << 20])
    assert main.rate_per_s == 0
    # One client, GETs only, a preloaded 5 MiB object every 4 s from the
    # window's opening, whatever set-up drew from its stream before.
    assert (dev.clients, dev.sequence, dev.sizes) == (1, ["GET"], [5 << 20])
    assert mix.preload_per_client == 1
    s = traffic.ClientStream(9, dev, 0)
    first = s.next()                      # preload: nothing to read yet
    assert first.kind == "PUT"
    s.written[first.key] = first.size
    assert s.next().kind == "GET"         # warm-up
    s.open_window()
    got = [s.next() for _ in range(13)]
    assert {o.kind for o in got} == {"GET"}
    assert {o.key for o in got} == {first.key}
    assert [o.due_s for o in got] == [4.0 * i for i in range(1, 14)]
    # 12 of them start inside a 50 s window; the last 3 s hold the one
    # that is due at 48 s.
    assert sum(1 for o in got if o.due_s < 50) == 12
    assert mix.trace_slice_s == 3.0


def test_a_cell_reads_the_metrics_that_list_it_or_move_what_it_reports():
    import run
    bench = {
        "end_to_end": [{"name": "goodput", "workloads": ["big"]},
                       {"name": "ops", "workloads": ["small"]},
                       {"name": "setup_s"}],
        "per_layer": [{"name": "a", "moves": "goodput"},
                      {"name": "a.ops", "moves": "ops"},
                      {"name": "b", "moves": "goodput", "workloads": ["big"]},
                      {"name": "c", "moves": "setup_s"}]}

    def names(cell, kind):
        return [m["name"] for m in run.metrics_for(bench, cell, kind)]

    assert names("big", "end_to_end") == ["goodput", "setup_s"]
    assert names("big", "per_layer") == ["a", "b", "c"]
    assert names("small", "per_layer") == ["a.ops", "c"]
    # A cell a later PR adds, reporting goodput: `a` comes with it.
    bench["end_to_end"][0]["workloads"].append("later")
    assert names("later", "per_layer") == ["a", "c"]
