#!/usr/bin/env python3
"""One run of one benchmark cell against the served S3 path.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process is the load generator and never initialises a JAX backend.
It boots the real server as a child that owns the chip
(harness/server_child.py), waits for the codec plan's probe, makes the
bucket, preloads and warms up (set-up), opens the window for --seconds,
lets the operations in flight finish, reads the child's counters, stops
the child (rc 0), compares what the window's PUTs (the preload's, where
the window writes nothing) left on the drives with the plain reference,
and prints one JSON object as its last line. A seeded sample of the keys
whose last acknowledged write was a DELETE is asked for once more by a
HEAD after the drain, and looked for on the drives once the child has
stopped: each has to be gone. A mix whose faults ask
for a timed heal (harness/heal.py) has another window: one drive wiped,
ONE admin heal of the bucket from its request to `done`, no client
operation meanwhile; `--seconds` does not bound it, the mix's
`timeout_s` does.

Everything a cell is made of is data found by name from BENCHMARK.json:
configs/<config>.json, traffic/<traffic>.json, metrics/<metric>.json
(+ metrics/readers/<reader>.py). See README.md.

--rehearse runs every phase at a tiny size on the CPU, names the CPU
truthfully in `device`, and prints no device metric. Without it a run
that finds no accelerator exits 3 and prints no result line.
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import http.client  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from harness import atrest, heal as heal_mod, prom, window  # noqa: E402
from harness import traffic as traffic_mod  # noqa: E402
from harness.server import CHILD, BootFailure, Server  # noqa: E402

GiB = 1 << 30
BUCKET = "bench"
DEVICE_SOURCES = ("device_trace",)
DEVICE_BYTES = "minio_tpu_v2_kernel_backend_bytes_total"
PHASE_COUNT = "minio_tpu_v2_request_phase_ms_count"
DECODED = {"api": "GET-object", "phase": "ec.decode"}


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T_PROCESS:7.1f}s] {msg}", flush=True)


class NoAccelerator(Exception):
    pass


def load_cell(name: str) -> tuple[dict, dict, dict]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    return bench, cell, config


def metrics_for(bench: dict, cell: str, kind: str) -> list[dict]:
    """The cell's metrics of one kind. An end-to-end metric without
    `workloads` is every cell's; a per-layer one without it belongs to
    every cell that reports the end-to-end metric it moves."""
    ends = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    if kind == "end_to_end":
        return [m for m in bench[kind] if m["name"] in ends]
    return [m for m in bench[kind]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in ends)]


def metric_spec(name: str) -> dict:
    """metrics/<name>.json, or the file of the quantity the name is a
    variant of: `codec.dispatch_wall_s_per_gib.ops` reads
    metrics/codec.dispatch_wall_s_per_gib.json. One end-to-end metric
    per entry of BENCHMARK.json, so a quantity read in cells whose
    throughput metrics differ has an entry for each and one file."""
    stem = name
    while stem:
        path = os.path.join(HERE, "metrics", stem + ".json")
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
        stem = stem.rpartition(".")[0]
    raise SystemExit(f"no benchmark/metrics/<name>.json for {name!r}")


def cpu_seconds() -> float:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime + ru.ru_stime


def lost_drives(faults: dict) -> frozenset[int]:
    """Positions (0-based) of the drives on which the mix's faults leave
    a copy expected ABSENT until the child stops."""
    return frozenset(int(d) - 1 for key in traffic_mod.LOST_DRIVE_FAULTS
                     for d in faults.get(key, []))


def apply_faults(faults: dict, srv: Server) -> None:
    """Set-up faults of a traffic mix (harness/traffic.py FAULTS), after
    the preload and before the warm-up.

    offline_drives        the drive's root moved aside, a regular file
                          in its place: every storage call on it fails
                          as a dead drive's does, nothing can be healed
                          onto it. A lost drive.
    remove_object_copies  every entry under <drive>/<bucket>/ removed,
                          the bucket volume kept: the server's new-disk
                          monitor sees nothing to heal. A lost drive.
    remove_drive_copies   <drive>/<bucket> removed, volume and all: the
                          state of a REPLACED drive, which the server's
                          new-disk monitor heals within its interval
                          (10 s) plus the rebuild. Not a lost drive.
    wipe_drive            everything but .minio.sys removed. Likewise.
    heal                  with wipe_drive: the wipe is not made here but
                          as the last step of set-up, and the window is
                          ONE admin heal of the bucket, timed from its
                          request to `done` (harness/heal.py; run()
                          drives it). Not a lost drive: at the end every
                          drive is expected to HOLD its copy."""
    for d in faults.get("offline_drives", []):
        root = srv.drive(int(d))
        aside = os.path.join(srv.work, "offline")
        os.makedirs(aside, exist_ok=True)
        os.rename(root, os.path.join(aside, os.path.basename(root)))
        with open(root, "wb"):
            pass
    for d in faults.get("remove_object_copies", []):
        heal_mod.empty_volume(srv.drive(int(d)), BUCKET)
    for d in faults.get("remove_drive_copies", []):
        shutil.rmtree(os.path.join(srv.drive(int(d)), BUCKET),
                      ignore_errors=True)
    if "wipe_drive" in faults and not faults.get("heal"):
        heal_mod.wipe(srv.drive(int(faults["wipe_drive"])), BUCKET)


def deleted_readable(s3, keys: list[str]) -> int:
    """How many of the deleted keys a HEAD answers otherwise than 404."""
    readable = 0
    for key in keys:
        try:
            readable += s3.request("HEAD", s3.key_path(BUCKET, key)).status \
                != 404
        except (OSError, http.client.HTTPException):  # no answer: not a 404
            readable += 1
    return readable


def degraded_reads(log, drives: list[str], k: int,
                   lost: frozenset[int]) -> int:
    """The window's successful GETs of objects that lost a data shard
    to the fault: each had to reconstruct."""
    hit: dict[str, bool] = {}
    had_to = 0
    for r in log if lost else ():
        if r.ok and r.kind in ("GET", "RANGE"):
            if r.key not in hit:
                hit[r.key] = atrest.lost_data_shards(
                    drives, BUCKET, r.key, k, lost) > 0
            had_to += hit[r.key]
    return had_to


def sample_at_rest(mix, expect, config: dict, seed: int, wiped: int = 0
                   ) -> tuple[list, list]:
    """(candidates, the seeded sample of them) for the at-rest
    comparison: what the window wrote; where it writes nothing, what
    its reads can reach (the preload's objects). After a timed heal of
    drive `wiped` the sample is drawn apart from the objects whose copy
    there is a data shard and from those whose copy is parity."""
    keys = expect.in_window if mix.writes else expect.last
    candidates = [(k, *expect.last[k]) for k in sorted(keys)
                  if expect.last.get(k) is not None]
    stratum = None
    if wiped:
        per_set = config["drives"] // config.get("sets", 1)

        def stratum(key):
            return heal_mod.data_or_parity(BUCKET, key, wiped,
                                           config["data"], per_set)
    return candidates, atrest.sample(candidates, mix.at_rest_sample, seed,
                                     stratum)


def trace_slice(srv: Server, scrape, trace_dir: str, seconds: float, mix,
                start_s: float | None = None):
    """Runs while the window is open: one traced slice, the LAST
    `trace_slice_s` of the window (the traffic mix's number), every
    client still in its loop, so that `stop_trace` works while the
    window drains and not while it is measured. The child's counters
    are read just before the slice and just after it. With `start_s`
    (a timed heal, whose end nobody knows beforehand) the slice starts
    that long after the window opens and lasts `trace_slice_s`."""
    rec: dict = {}

    def during(t_open: float) -> None:
        if start_s is None:
            t_close = t_open + seconds
            t_start = t_close - min(mix.trace_slice_s, 0.5 * seconds)
        else:
            t_start = t_open + start_s
            t_close = t_start + mix.trace_slice_s
        wait = t_start - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        rec["before"], rec["t_before"] = scrape(), time.monotonic()
        rec["start"] = srv.ask(f"trace_start {trace_dir}")
        wait = t_close - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        srv.send("trace_stop")
        rec["t_stop_sent"] = time.monotonic()
        rec["after"], rec["t_after"] = scrape(), time.monotonic()

    return rec, during


def run(args, child: str = CHILD) -> int:
    bench, cell, config = load_cell(args.workload)
    mix = traffic_mod.load(
        os.path.join(HERE, "traffic", cell["traffic"] + ".json"),
        cell["traffic"], rehearse=args.rehearse)
    work = os.path.join(ROOT, ".chip_smoke", "benchmark", cell["name"])
    out_dir = os.path.join(ROOT, "chiprun_out")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    os.makedirs(out_dir, exist_ok=True)
    record: dict = {"cell": cell, "seed": args.seed, "seconds": args.seconds,
                    "traced": args.trace, "rehearse": args.rehearse,
                    "child_env": config["env"]}
    env_extra = {"JAX_PLATFORMS": "cpu"} if args.rehearse else {}
    if args.trace:
        # What only a traced run gives the child; added to a variable
        # that is already set (LIBTPU_INIT_ARGS is a list of flags).
        for k, v in config.get("env_traced", {}).items():
            have = config["env"].get(k, os.environ.get(k, ""))
            env_extra[k] = f"{have} {v}".strip()
        record["child_env_traced"] = config.get("env_traced", {})
    srv = Server(config, work, env_extra, child)
    rc = None
    log: list = []
    summary: dict = {}
    ctx: dict = {}
    at_rest: dict = {}
    lost = lost_drives(mix.faults)
    heal = mix.faults.get("heal")
    wiped = int(mix.faults["wipe_drive"]) if heal else 0
    healing: dict = {}
    gone: list[str] = []
    readable = 0
    fault = ""
    try:
        # -- set-up ------------------------------------------------------
        srv.start()
        dev = srv.device
        record["device_line"] = dev
        record["t_listening_s"] = srv.t_listening
        say(f"listening after {srv.t_listening:.1f} s; device {json.dumps(dev)}")
        if dev["platform"] == "cpu" and not args.rehearse:
            raise NoAccelerator(f"the serving process found {dev}")
        if dev["count"] < cell["chips"] and not args.rehearse:
            raise NoAccelerator(f"cell needs {cell['chips']} chips, "
                                f"the serving process has {dev['count']}")
        admin = srv.client()
        plan = srv.wait_probed(admin)
        record["plan"] = plan["plan"]
        record["probe"] = plan.get("lastProbe")
        record["rs_kernel"] = plan.get("rsKernel")
        say(f"probed; plan {json.dumps(plan['plan'])}")
        say(f"probe ladder GiB/s {json.dumps(plan.get('lastProbe'))}")
        r = admin.request("PUT", f"/{BUCKET}")
        if r.status != 200:
            raise BootFailure(f"make bucket: {r.status} {r.body[:200]!r}")
        expect = window.Expect(
            traffic_mod.base_buffer(args.seed, mix.max_size))
        clients = window.make_clients(
            traffic_mod.streams(args.seed, mix), mix, "127.0.0.1", srv.port,
            srv.access, srv.secret, BUCKET, expect)
        if mix.preload_fill:
            pre = window.in_threads(clients, lambda c: c.run_list(
                c.stream.fill_ops(mix.preload_fill)))
            bad = [r for rs in pre for r in rs if not r.ok]
            if bad:
                raise BootFailure(f"preload fill failed: {bad[0]}")
            say(f"preload: {mix.preload_fill} objects a client, "
                f"{sum(r.nbytes for rs in pre for r in rs) / GiB:.3f} GiB")
        if mix.preload_per_client:
            pre = window.in_threads(
                clients, lambda c: c.run_ops(mix.preload_per_client))
            bad = [r for rs in pre for r in rs if not r.ok]
            if bad:
                raise BootFailure(f"preload failed: {bad[0]}")
        apply_faults(mix.faults, srv)
        # Warm-up: every client at once (the window's concurrency, so the
        # coalescer's batch shapes too) runs its sequence once (a weighted
        # group: one operation of each kind on a key it writes first);
        # client i takes the i-th size of its group, so every size and
        # kind the window will send is sent here first.
        t0 = time.monotonic()
        distinct = {g.name: sorted(set(g.sizes)) for g in mix.groups}
        passes = max(-(-len(distinct[g.name]) // g.clients)
                     for g in mix.groups)

        def warm_one(c, p):
            g = c.stream.group
            size = distinct[g.name][(c.stream.client + p)
                                    % len(distinct[g.name])]
            if g.weights:
                return c.run_list(c.stream.warm_ops(size))
            return c.run_ops(len(g.kinds), sizes=[size])

        for p in range(passes):
            warm = window.in_threads(clients, lambda c: warm_one(c, p))
            bad = [r for rs in warm for r in rs if not r.ok]
            if bad:
                raise BootFailure(f"warm-up failed: {bad[0]}")
        say(f"warm-up: {passes} pass(es) of {len(clients)} clients in "
            f"{time.monotonic() - t0:.1f} s")

        def scrape():
            return srv.scrape(admin)

        before = scrape()
        trace_dir = os.path.join(work, "trace")
        slice_rec, during = ({}, None)
        if args.trace:
            slice_rec, during = trace_slice(
                srv, scrape, trace_dir, args.seconds, mix,
                heal["trace_start_s"] if heal else None)
        cpu0 = cpu_seconds()
        # -- the window --------------------------------------------------
        if heal:
            # Another window (harness/heal.py): set-up's last steps,
            # ONE admin heal, then the sampled objects read back once;
            # their GETs are the log that `correct` reads.
            candidates, chosen = sample_at_rest(mix, expect, config,
                                                args.seed, wiped)
            healing = heal_mod.run_window(
                srv, mix, config, BUCKET, candidates, chosen, clients[0],
                during, slice_rec, say)
            cpu1 = cpu_seconds()
            summary, log = healing.pop("summary"), healing.pop("read_back")
            before, after = healing.pop("before"), healing.pop("after")
            t_open, counters_s = healing["t_open"], healing["counters_s"]
            window_s = summary["span_s"]
        else:
            log, t_open, t_drained = window.run_window(
                clients, args.seconds, during)
            cpu1 = cpu_seconds()
            after = scrape()
            counters_s, window_s = time.monotonic() - t_open, args.seconds
            summary = window.summarize(log, mix.timeout_s)
            say(f"window {args.seconds} s + drain "
                f"{t_drained - t_open - args.seconds:.1f} s: "
                f"{summary['attempted']} ops, {summary['failed']} failed, "
                f"goodput {summary['goodput_mibps']} MiB/s")
            candidates, chosen = sample_at_rest(mix, expect, config,
                                                args.seed)
        summary["setup_s"] = t_open - _T_PROCESS
        for c in clients:
            c.s3.close()
        gone = atrest.sample_keys(expect.deleted, args.seed)
        readable = deleted_readable(admin, gone)
        mem = srv.ask("mem", 30.0)
        plan_after = srv.admin(admin, "codec-plan")["plan"]
        if args.trace:
            stop = srv.answer("trace_stop", 240.0)
            slice_rec["stop"] = stop
            say(f"trace stop answered after "
                f"{time.monotonic() - slice_rec['t_stop_sent']:.1f} s")
        # The child closes a request's span tree just after the client has
        # its last byte: the count of GETs that decoded is read here, some
        # admin calls later, not in the scrape that ends the window.
        settled = scrape()
        admin.close()
        record.update(plan_after=plan_after, memory=mem,
                      plan_changed=plan_after != record["plan"])
        ctx = {
            "before": before, "after": after, "settled": settled,
            "summary": summary,
            "config": config, "device": dev, "notes": {},
            "slice": ({"before": slice_rec["before"],
                       "after": slice_rec["after"],
                       "seconds": slice_rec["t_after"]
                       - slice_rec["t_before"]}
                      if "after" in slice_rec else None),
            "run": {"window_s": window_s,
                    "counters_s": counters_s,
                    "user_gib": summary["user_bytes"] / GiB,
                    "loadgen_cpu_s": cpu1 - cpu0,
                    "span_s": summary["span_s"]},
        }
    except NoAccelerator as exc:
        srv.stop()
        shutil.rmtree(work, ignore_errors=True)
        print(f"benchmark/run.py: no accelerator: {exc}", file=sys.stderr)
        return 3
    except (BootFailure, traffic_mod.TrafficError, heal_mod.HealError,
            OSError) as exc:
        fault = f"{type(exc).__name__}: {exc}"
        say(f"FAILED: {fault}")
        say("server log tail:\n" + srv.log_tail())
    finally:
        rc = srv.stop()
    stem = f"{cell['name']}-{args.seed}-{args.trace}" + (
        f"-{args.tag}" if args.tag else "")
    try:
        shutil.copy(srv.log_path, os.path.join(out_dir, stem + ".server.log"))
    except OSError:
        pass
    if fault:
        shutil.rmtree(work, ignore_errors=True)
        print(f"benchmark/run.py: {fault}", file=sys.stderr)
        return 1

    # -- after the window: the drives against the reference ---------------
    t0 = time.monotonic()
    drives = [srv.drive(i) for i in range(1, config["drives"] + 1)]
    multipart = {g.part_size for g in mix.groups if "MULTIPART" in g.kinds}
    at_rest = atrest.check(drives, BUCKET, chosen, expect.body,
                           config["data"], config["parity"],
                           config["block_size"],
                           part_size=multipart.pop() if multipart else 0,
                           sets=config.get("sets", 1), lost=lost)
    # One-sided: a hedged read may reconstruct where it need not.
    at_rest["degraded_reads"] = degraded_reads(log, drives, config["data"],
                                               lost)
    at_rest["reads_decoded"] = int(prom.delta(
        ctx["before"], ctx["settled"], PHASE_COUNT, DECODED))
    at_rest.update(deleted_keys_checked=len(gone),
                   deleted_keys_readable=readable,
                   deleted_keys_present=atrest.deleted_present(
                       drives, BUCKET, gone))
    at_rest["seconds"] = time.monotonic() - t0
    at_rest["bytes"] = sum(o[1] for o in chosen)
    say(f"at rest: {json.dumps(at_rest)}")

    # -- the trace --------------------------------------------------------
    reduced = None
    if args.trace:
        from harness import trace_reduce
        t0 = time.monotonic()
        path = trace_reduce.find_xplane(trace_dir)
        if path and "stop" in slice_rec and "error" not in slice_rec["stop"]:
            traced_s = (slice_rec["stop"]["t_call"]
                        - slice_rec["start"]["t_done"])
            reduced = trace_reduce.reduce_file(path, traced_s)
            if args.keep_trace:
                trace_reduce.cut(path, os.path.join(
                    out_dir, f"{cell['name']}-{args.seed}.cut.xplane.pb"))
            record["trace"] = {
                "file_bytes": os.path.getsize(path), "census":
                reduced["census"], "devices": reduced["devices"],
                "reduce_seconds": time.monotonic() - t0,
                "traced_s": traced_s, "start": slice_rec["start"],
                "stop": slice_rec["stop"],
                "counters_read_s": ctx["slice"]["seconds"],
                "device_bytes_in_slice": prom.delta(
                    slice_rec["before"], slice_rec["after"], DEVICE_BYTES,
                    {"backend": "device"})}
            say(f"trace: {os.path.getsize(path)} bytes, busy "
                f"{reduced['busy_s']} s of {reduced['window_s']} s, reduced "
                f"in {time.monotonic() - t0:.1f} s")
        else:
            say(f"trace: nothing to read ({slice_rec.get('start')}, "
                f"{slice_rec.get('stop')})")
        ctx["trace"] = reduced
        if "outlasted" in slice_rec:
            ctx["trace"] = ctx["slice"] = None
            ctx["notes"]["heal_trace"] = (
                "the heal ended before the traced slice did: no device "
                "metric", slice_rec["outlasted"])

    # -- metrics ------------------------------------------------------------
    metrics: dict = {}
    on_chip = dev["platform"] not in ("cpu", "none")
    if args.trace:
        for m in metrics_for(bench, cell["name"], "per_layer"):
            spec = metric_spec(m["name"])
            if m["source"] in DEVICE_SOURCES and not on_chip:
                continue
            reader = importlib.import_module(
                "metrics.readers." + spec["reader"])
            value = reader.read(spec, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_for(bench, cell["name"], "end_to_end"):
            value = summary.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # -- correct ------------------------------------------------------------
    why = [r.why.split(":")[0] for r in log if not r.ok]
    wanted = min(mix.at_rest_sample, len(candidates))
    checks = {
        "get_wrong_bytes": [why.count("wrong_bytes"), 0],
        "ops_unanswered": [why.count("unanswered"), 0],
        "ops_error_status": [why.count("status"), 0],
        "shard_files_missing": [at_rest["shard_files_missing"], 0],
        "shard_frames_differ": [at_rest["shard_frames_differ"], 0],
        "digest_frames_differ": [at_rest["digest_frames_differ"], 0],
        "lost_copies_present": [at_rest["lost_copies_present"], 0],
        "degraded_reads_not_decoded": [
            max(0, at_rest["degraded_reads"] - at_rest["reads_decoded"]), 0],
        "at_rest_objects_unchecked": [
            max(0, wanted - at_rest["objects_checked"])
            + (0 if wanted else 1), 0],
        "deleted_keys_readable": [at_rest["deleted_keys_readable"], 0],
        "deleted_keys_present": [at_rest["deleted_keys_present"], 0],
        "server_exit_code": [rc if rc is not None else -1, 0],
    }
    if heal:
        # Before the exit code, which stays every line's last check.
        exit_code = checks.pop("server_exit_code")
        checks.update(heal_mod.checks(
            healing["sweeps"], summary["failed"], drives[wiped - 1], BUCKET,
            candidates, config), server_exit_code=exit_code)
    correct = all(v == lim for v, lim in checks.values())
    peaks = [p for p in (record["memory"].get("peak_bytes_in_use") or [])
             if p is not None]
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": max(peaks) if peaks else 0}
    result: dict = {"correct": correct, "attempted": summary["attempted"],
                    "failed": summary["failed"], "metrics": metrics,
                    "device": device}
    if args.trace and reduced and reduced.get("busy_s") is not None \
            and on_chip:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}

    # -- earlier lines, and the record a cut chip call still leaves ---------
    record.update(
        summary=summary, at_rest=at_rest, result=result, healing=healing,
        notes=ctx.get("notes"), run=ctx["run"],
        failures=[vars(r) for r in log if not r.ok][:50],
        counters={when: prom.series(ctx[when], "minio_tpu_v2_kernel")
                  | prom.series(ctx[when], "minio_tpu_v2_jit")
                  | prom.series(ctx[when], "minio_tpu_v2_hedged")
                  for when in ("before", "after")},
        boot_lines=srv.boot_lines)
    if not heal:
        say(f"per-size latency: {json.dumps(summary['by_size'])}")
        say(f"operations by kind: {json.dumps(summary['by_kind'])}")
        say(f"p95 of each group alone: {json.dumps(summary['by_group'])}")
    say(f"completions [t, ops, bytes] per 5 s: "
        f"{json.dumps(summary['per_5s'])}")
    say(f"generator CPU: {ctx['run']['loadgen_cpu_s']:.2f} s over "
        f"{args.seconds} s; plan changed in window: {record['plan_changed']}")
    if ctx.get("notes"):
        say(f"notes: {json.dumps(ctx['notes'])}")
    with open(os.path.join(out_dir, stem + ".json"), "w") as f:
        json.dump(record, f, indent=1, default=str)
    shutil.rmtree(work, ignore_errors=True)
    if "jax" in sys.modules:
        from jax._src import xla_bridge
        if xla_bridge._backends:
            print("benchmark/run.py initialised a JAX backend: that is a "
                  "fault of the harness", file=sys.stderr)
            return 1
    sys.stdout.flush()
    for k, (v, lim) in checks.items():
        print(f"check {k}: value {v} limit {lim}", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def main(argv: list[str] | None = None, child: str = CHILD) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size")
    ap.add_argument("--tag", default="",
                    help="suffix of this run's record in chiprun_out/ "
                         "(sets.py: a second set keeps the first's records)")
    ap.add_argument("--keep-trace", action="store_true",
                    help="keep a cut-down copy of the traced slice's "
                         ".xplane.pb in chiprun_out/")
    return run(ap.parse_args(argv), child)


if __name__ == "__main__":
    sys.exit(main())
