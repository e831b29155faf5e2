#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served data plane still
starts and does its math on a TPU.

Boots the real server as users do (`python -m minio_tpu server
<dir>/d{1...12} --address 127.0.0.1:0`, MINIO_STORAGE_CLASS_STANDARD=EC:4:
one erasure set, 8+4, default 10 MiB stripe block, drives on a real
directory inside the checkout) as a CHILD process, drives it with the
in-tree SigV4 client, checks every byte that comes back against data
generated from --seed, and proves from the serving process's OWN
counters (admin /codec-plan, /kernel-health, /v2/metrics/node) that the
Pallas RS kernel and the device HighwayHash-256 carried every >= 4 MiB
batch.

One process per chip: the server child owns it. This script never
initialises a JAX backend; the `device` object of its last line is what
the serving process printed on its boot line (`minio-tpu device: {...}`).

Last stdout line, and nothing else in that line:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
Exit code 0 only with "ok": true. Without an accelerator it exits
nonzero and prints no result line — except under --tiny, the CPU
rehearsal, which runs every phase and byte comparison at a tiny size,
reports the device assertions as not met and ends with "ok": false and
the CPU named truthfully (exit code 1).

    python chip_smoke.py              # one chip, >= 1 GiB, ~minutes
    python chip_smoke.py --chips 4    # ONLY the 2x2 serving-mesh path
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # rehearsal, CPU
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import xml.etree.ElementTree as ET
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke")          # git-ignored
OUT = os.path.join(HERE, "chiprun_out")           # what chiprun brings back
MiB = 1 << 20
GiB = 1 << 30
BLOCK = 10 * MiB                                  # erasure/codec.BLOCK_SIZE
K, M = 8, 4
FLOOR = 4 * MiB                                   # static device floor
ACCESS, SECRET = "smokeadmin", "smokesecret123"
NS = "{http://s3.amazonaws.com/doc/2006-03-01/}"
RS_KERNELS = ("rs_encode", "rs_decode")
HOST_LANES = ("native", "host", "xla-cpu")

_T0 = time.monotonic()


def say(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def need(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# --- data ---------------------------------------------------------------------


def gen(seed: int, name: str, size: int) -> bytes:
    """Deterministic object bytes from (--seed, name)."""
    import numpy as np
    h = int.from_bytes(hashlib.sha256(
        f"{seed}/{name}".encode()).digest()[:8], "little")
    return np.random.default_rng(h).bytes(size)


# --- the server child ---------------------------------------------------------


class Server:
    def __init__(self, n_drives: int, tag: str,
                 virtual_devices: int = 0):
        self.virtual_devices = virtual_devices
        self.root = os.path.join(WORK, f"drives-{tag}")
        shutil.rmtree(self.root, ignore_errors=True)
        os.makedirs(self.root)
        self.n_drives = n_drives
        self.log_path = os.path.join(WORK, f"server-{tag}.log")
        self.proc: subprocess.Popen | None = None
        self.port = 0
        self.device: dict | None = None
        self.t_listening = 0.0
        self._listening = threading.Event()
        self._device_seen = threading.Event()

    def drive(self, i: int) -> str:
        return os.path.join(self.root, f"d{i}")

    def start(self) -> None:
        env = dict(os.environ, MINIO_ACCESS_KEY=ACCESS,
                   MINIO_SECRET_KEY=SECRET,
                   MINIO_STORAGE_CLASS_STANDARD=f"EC:{M}",
                   PYTHONUNBUFFERED="1")
        if self.virtual_devices:
            # CPU rehearsal of the mesh path (guide §2, rehearsal 2).
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
                f"device_count={self.virtual_devices}").strip()
        t0 = time.monotonic()
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "minio_tpu", "server",
             os.path.join(self.root, "d{1...%d}" % self.n_drives),
             "--address", "127.0.0.1:0"],
            cwd=HERE, env=env, stdout=subprocess.PIPE,
            stderr=self._log)

        def pump() -> None:
            for raw in self.proc.stdout:
                line = raw.decode(errors="replace").rstrip("\n")
                self._log.write(raw)
                self._log.flush()
                if "listening on" in line and not self.port:
                    self.port = int(line.rsplit(":", 1)[1])
                    self.t_listening = time.monotonic() - t0
                    self._listening.set()
                if line.startswith("minio-tpu device: "):
                    self.device = json.loads(
                        line[len("minio-tpu device: "):])
                    self._device_seen.set()

        threading.Thread(target=pump, daemon=True).start()
        for ev, what in ((self._listening, "listening"),
                         (self._device_seen, "device line")):
            while not ev.wait(0.5):
                need(self.proc.poll() is None,
                     f"server exited rc={self.proc.returncode} before "
                     f"'{what}' (see {self.log_path})")
                need(time.monotonic() - t0 < 600,
                     f"server gave no '{what}' in 600 s")

    def client(self, timeout: float = 900.0):
        from minio_tpu.s3.client import S3Client
        return S3Client("127.0.0.1", self.port, ACCESS, SECRET,
                        timeout=timeout)

    def stop(self) -> int:
        """SIGTERM; the rc (an abort at teardown is a bug here)."""
        if self.proc is None:
            return 0
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        rc = self.proc.returncode
        self._log.close()
        return rc

    def log_tail(self, n: int = 60) -> str:
        try:
            with open(self.log_path, "rb") as f:
                return b"\n".join(f.read().splitlines()[-n:]).decode(
                    errors="replace")
        except OSError:
            return ""


# --- the serving process's own counters ---------------------------------------


def admin(c, route: str, method: str = "GET", query: str = "",
          body: bytes = b"") -> dict:
    r = c.request(method, f"/minio-tpu/admin/v1/{route}", query=query,
                  body=body)
    need(r.status == 200, f"admin {route}: {r.status} {r.body[:300]!r}")
    return json.loads(r.body) if r.body else {}


def metrics(c) -> dict[tuple, float]:
    """{(name, (("label","value"), ...)): value} of /v2/metrics/node."""
    r = c.request("GET", "/minio-tpu/v2/metrics/node", sign=False)
    need(r.status == 200, f"metrics/node: {r.status}")
    out: dict[tuple, float] = {}
    for line in r.body.decode().splitlines():
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


def msum(m: dict, name: str, **want: str) -> float:
    return sum(v for (n, labels), v in m.items() if n == name
               and all(dict(labels).get(k) == x for k, x in want.items()))


def counters(c) -> dict:
    """One reading of everything the assertions compare."""
    m = metrics(c)
    snap: dict = {"bytes": {}, "dispatches": {}, "ms": {}}
    for kern in RS_KERNELS + ("hh256",):
        for lane in ("device",) + HOST_LANES:
            snap["bytes"][kern, lane] = msum(
                m, "minio_tpu_v2_kernel_backend_bytes_total",
                kernel=kern, backend=lane)
            snap["dispatches"][kern, lane] = msum(
                m, "minio_tpu_v2_kernel_dispatch_ms_count",
                kernel=kern, backend=lane)
            snap["ms"][kern, lane] = msum(
                m, "minio_tpu_v2_kernel_dispatch_ms_sum",
                kernel=kern, backend=lane)
    snap["programs"] = msum(m, "minio_tpu_v2_jit_programs_total",
                            result="requested")
    snap["cache_hits"] = msum(m, "minio_tpu_v2_jit_programs_total",
                              result="cache_hit")
    return snap


def host_bytes(snap: dict, kern: str) -> float:
    return sum(snap["bytes"][kern, lane] for lane in HOST_LANES)


def print_counters(tag: str, a: dict, b: dict) -> None:
    for kern in RS_KERNELS + ("hh256",):
        for lane in ("device",) + HOST_LANES:
            db = b["bytes"][kern, lane] - a["bytes"][kern, lane]
            dn = b["dispatches"][kern, lane] - a["dispatches"][kern, lane]
            dms = b["ms"][kern, lane] - a["ms"][kern, lane]
            if dn or db:
                say(f"  {tag} {kern:9s} {lane:7s} dispatches={int(dn):5d} "
                    f"bytes={int(db):12d} wall_ms={dms:10.1f}")


# --- S3 operations, every byte compared ---------------------------------------


def put(c, bucket: str, key: str, body: bytes) -> None:
    r = c.put_object(bucket, key, body)
    need(r.status == 200, f"PUT {key}: {r.status} {r.body[:200]!r}")


def get_check(c, bucket: str, key: str, want: bytes,
              ranges: bool = True) -> None:
    r = c.get_object(bucket, key)
    need(r.status == 200, f"GET {key}: {r.status} {r.body[:200]!r}")
    need(r.body == want, f"GET {key}: {len(r.body)} bytes differ from "
                         f"the {len(want)} generated")
    if not ranges:
        return
    n = len(want)
    # Inside one block, across a block boundary, and the tail.
    spans = [(1, min(n, 4097) - 1), (max(0, n - 1000), n - 1)]
    if n > BLOCK + 5:
        spans.append((BLOCK - 3, min(n - 1, BLOCK + 70000)))
    for lo, hi in spans:
        r = c.get_object(bucket, key,
                         headers={"Range": f"bytes={lo}-{hi}"})
        need(r.status == 206, f"ranged GET {key} {lo}-{hi}: {r.status}")
        need(r.body == want[lo:hi + 1],
             f"ranged GET {key} {lo}-{hi}: bytes differ")


def multipart(c, bucket: str, key: str, parts: list[bytes]) -> None:
    r = c.request("POST", f"/{bucket}/{key}", query="uploads")
    need(r.status == 200, f"initiate multipart: {r.status}")
    uid = ET.fromstring(r.body).findtext(f"{NS}UploadId")
    etags = []
    for i, body in enumerate(parts, start=1):
        r = c.request("PUT", f"/{bucket}/{key}",
                      query=f"partNumber={i}&uploadId={uid}", body=body)
        need(r.status == 200, f"upload part {i}: {r.status}")
        etags.append(r.headers.get("etag", "").strip('"'))
    doc = "".join(
        f"<Part><PartNumber>{i}</PartNumber><ETag>\"{e}\"</ETag></Part>"
        for i, e in enumerate(etags, start=1))
    r = c.request("POST", f"/{bucket}/{key}", query=f"uploadId={uid}",
                  body=f"<CompleteMultipartUpload>{doc}"
                       "</CompleteMultipartUpload>".encode())
    need(r.status == 200 and b"<Error>" not in r.body,
         f"complete multipart: {r.status} {r.body[:200]!r}")


# --- drives -------------------------------------------------------------------


def shard_index(srv: Server, drive: int, bucket: str, key: str) -> int:
    """1-based erasure index this drive holds for the object (<= K: a
    data shard), 0 when the drive has no copy."""
    try:
        with open(os.path.join(srv.drive(drive), bucket, key,
                               "xl.meta"), "rb") as f:
            return int(json.load(f)["versions"][0]["erasure"]["index"])
    except (OSError, KeyError, IndexError, ValueError):
        return 0


def part_files(srv: Server, drive: int, bucket: str, key: str
               ) -> dict[str, str]:
    """{relative part path: sha256} of the object's shard files."""
    base = os.path.join(srv.drive(drive), bucket, key)
    out = {}
    for dirpath, _, files in os.walk(base):
        for fn in files:
            if fn.startswith("part."):
                p = os.path.join(dirpath, fn)
                with open(p, "rb") as f:
                    out[os.path.relpath(p, base)] = hashlib.sha256(
                        f.read()).hexdigest()
    return out


def verify_shard_bitrot(srv: Server, drive: int, bucket: str, key: str
                        ) -> int:
    """Re-hash every [32-byte HighwayHash][sub-block] frame of the
    object's shard files on this drive with the host hash; the number
    of frames checked. Raises on a mismatch."""
    from minio_tpu.erasure import bitrot
    shard = -(-BLOCK // K)
    base = os.path.join(srv.drive(drive), bucket, key)
    frames = 0
    for rel in part_files(srv, drive, bucket, key):
        with open(os.path.join(base, rel), "rb") as f:
            stream = f.read()
        need(bitrot.verify_stream(stream, shard),
             f"bitrot mismatch in healed {drive}:{bucket}/{key}/{rel}")
        frames += -(-len(stream) // (shard + 32))
    return frames


# --- phases -------------------------------------------------------------------


class Run:
    def __init__(self, args):
        self.args = args
        self.tiny = args.tiny
        self.seed = args.seed
        self.phases: list[tuple[str, float]] = []
        self.unmet: list[str] = []
        self.info: dict = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.monotonic()
        say(f"phase {name} ...")
        verdict = "FAILED"
        try:
            yield
            verdict = "ok"
        finally:
            dt = time.monotonic() - t0
            self.phases.append((name, dt))
            say(f"phase {name}: {verdict} in {dt:.1f} s")

    def check(self, ok: bool, what: str) -> None:
        say(f"  assert {'MET    ' if ok else 'NOT MET'} {what}")
        if not ok:
            self.unmet.append(what)

    # sizes -----------------------------------------------------------------

    def sizes(self) -> dict:
        if self.tiny:
            return {"small_n": 8, "small": 256 * 1024,
                    "big": [11 * MiB + 77, 3 * MiB],
                    "parts": [5 * MiB, 5 * MiB, MiB + 13],
                    "off": [11 * MiB + 5, 4 * MiB]}
        return {"small_n": 64, "small": MiB,
                "big": [64 * MiB, 128 * MiB, 256 * MiB],
                "parts": [64 * MiB] * 8,
                "off": [64 * MiB, 128 * MiB + 12345]}

    # boot ------------------------------------------------------------------

    def boot(self, srv: Server, want_count: int) -> None:
        with self.phase("boot"):
            srv.start()
            dev = srv.device
            say(f"  cold start to 'listening': {srv.t_listening:.1f} s "
                f"(port {srv.port})")
            say(f"  serving process reports device: {json.dumps(dev)}")
            self.info["device"] = {"platform": dev["platform"],
                                   "kind": dev["kind"],
                                   "count": dev["count"]}
            say(f"  compile cache in force: {dev.get('compileCache')}"
                f" (JAX_COMPILATION_CACHE_DIR "
                f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'not set'})")
            if dev["platform"] == "cpu" and not self.tiny:
                raise NoAccelerator()
            c = srv.client()
            t0 = time.monotonic()
            while True:
                plan = admin(c, "codec-plan")
                if plan.get("probed"):
                    break
                need(time.monotonic() - t0 < 900,
                     "probe ladder did not finish in 900 s")
                need(srv.proc.poll() is None, "server died while probing")
                time.sleep(0.5)
            say(f"  '\"probed\": true' {time.monotonic() - t0:.1f} s after "
                f"the device line")
            say(f"  probe ladder (GiB/s): {json.dumps(plan['lastProbe'])}")
            say(f"  probe errors: {json.dumps(plan['lastProbeErrors'])}")
            say(f"  plan at defaults: {json.dumps(plan['plan'])}")
            say(f"  rs kernel: {json.dumps(plan['rsKernel'])}")
            self.info["plan_default"] = plan["plan"]
            self.info["probe"] = plan["lastProbe"]
            cn = counters(c)
            say(f"  programs requested so far: {int(cn['programs'])}, "
                f"persistent-cache hits: {int(cn['cache_hits'])}, "
                f"compilations: {int(cn['programs'] - cn['cache_hits'])}")
            self.check(dev["count"] == want_count,
                       f"device count is {want_count} "
                       f"(got {dev['count']})")

    # pass 1: server defaults ----------------------------------------------

    def pass_defaults(self, srv: Server) -> None:
        c = srv.client()
        sz = self.sizes()
        bucket = "smoke-a"
        need(c.make_bucket(bucket).status == 200, "make_bucket smoke-a")
        before = counters(c)
        objs: dict[str, bytes] = {}
        with self.phase("load (a) concurrent small PUTs"):
            small = {f"small/{i:03d}": gen(self.seed, f"s{i}",
                                           sz["small"])
                     for i in range(sz["small_n"])}
            with ThreadPoolExecutor(sz["small_n"]) as ex:
                list(ex.map(lambda kv: put(srv.client(), bucket, *kv),
                            small.items()))
            objs.update(small)
        with self.phase("load (b) large single PUTs"):
            warm = None
            for i, n in enumerate(sz["big"]):
                body = gen(self.seed, f"b{i}", n)
                put(c, bucket, f"big/{i}", body)
                objs[f"big/{i}"] = body
                if self.tiny or i > 1:
                    continue
                # Where the device HH256 lane makes the run
                # impractically slow (a per-packet loop outside the
                # kernel did), say so with the number and shrink THIS
                # phase only — never the assertions. Rated over the
                # second PUT: the first one pays the compiles.
                cn = counters(c)
                if warm is None:
                    warm = cn
                    continue
                b = cn["bytes"]["hh256", "device"] \
                    - warm["bytes"]["hh256", "device"]
                ms = cn["ms"]["hh256", "device"] \
                    - warm["ms"]["hh256", "device"]
                rate = ms / 1e3 / (b / GiB) if b else 0.0
                say(f"  HH256 device lane, second PUT: {rate:.1f} s/GiB")
                if rate > 60.0:
                    say(f"  {rate:.1f} s/GiB > 60: dropping the "
                        f"{sz['big'][2] // MiB} MiB PUT of phase (b) to "
                        f"stay inside the time limit")
                    break
        with self.phase("load (c) multipart upload"):
            parts = [gen(self.seed, f"p{i}", n)
                     for i, n in enumerate(sz["parts"])]
            multipart(c, bucket, "mp/object", parts)
            objs["mp/object"] = b"".join(parts)
            del parts
        total = sum(len(v) for v in objs.values())
        say(f"  loaded {len(objs)} objects, {total / GiB:.3f} GiB")
        self.info["loaded_bytes_defaults"] = total
        with self.phase("query: full + ranged GETs"):
            for i, (key, want) in enumerate(objs.items()):
                get_check(c, bucket, key, want,
                          ranges=not key.startswith("small/") or i < 4)
        after = counters(c)
        print_counters("defaults", before, after)
        plan = admin(c, "codec-plan")
        say(f"  plan after the defaults pass: {json.dumps(plan['plan'])}")
        self.info["plan_after_defaults"] = plan["plan"]

    # pass 2: static device-first policy, asserted -------------------------

    def pass_device(self, srv: Server) -> None:
        c = srv.client()
        sz = self.sizes()
        bucket = "smoke-b"
        need(c.make_bucket(bucket).status == 200, "make_bucket smoke-b")
        # The existing live knob; persisting it is itself a small
        # (host-lane) write, so the baseline reading comes after it.
        admin(c, "set-config-kv", "POST", body=b"codec autotune=off")
        plan0 = admin(c, "codec-plan")
        need(plan0["enabled"] is False, "codec autotune=off not applied")
        time.sleep(1.0)
        before = counters(c)
        sent = {k: 0 for k in RS_KERNELS + ("hh256",)}
        tails = 0
        objs: dict[str, bytes] = {}

        def account(n: int, enc: bool, dec: bool, frames: int) -> None:
            """Bytes of the >= 4 MiB batches an operation on an n-byte
            object sends, per kernel (lower bounds), and the ragged
            tail sub-blocks that hash on the host by design."""
            nonlocal tails
            full, tail = divmod(n, BLOCK)
            shard = -(-BLOCK // K)
            tshard = -(-tail // K)
            if enc:
                sent["rs_encode"] += full * K * shard
                if K * tshard >= FLOOR:
                    sent["rs_encode"] += K * tshard
            if dec:
                sent["rs_decode"] += full * K * shard
                if K * tshard >= FLOOR:
                    sent["rs_decode"] += K * tshard
            sent["hh256"] += full * frames * shard
            tails += tshard * (K + M)

        with self.phase("device pass: large PUTs (autotune=off)"):
            for i, n in enumerate(sz["off"]):
                body = gen(self.seed, f"o{i}", n)
                put(c, bucket, f"off/{i}", body)
                objs[f"off/{i}"] = body
                account(n, True, False, K + M)
        with self.phase("device pass: full + ranged GETs"):
            for key, want in objs.items():
                get_check(c, bucket, key, want)
                account(len(want), False, False, K)

        with self.phase("device pass: GET with two shards lost"):
            key = "off/0"
            data_drives = [d for d in range(1, srv.n_drives + 1)
                           if 1 <= shard_index(srv, d, bucket, key) <= K]
            need(len(data_drives) >= 2, "no two data-shard drives found")
            lost = data_drives[:2]
            for d in lost:
                shutil.rmtree(os.path.join(srv.drive(d), bucket, key))
            say(f"  removed {bucket}/{key} from drives {lost} "
                f"(data shards)")
            get_check(c, bucket, key, objs[key], ranges=False)
            account(len(objs[key]), False, True, K)

        with self.phase("device pass: admin heal of a wiped drive"):
            wiped = next(d for d in range(1, srv.n_drives + 1)
                         if d not in lost)
            golden = {key: part_files(srv, wiped, bucket, key)
                      for key in objs}
            need(all(golden.values()), "wiped drive held no shard files")
            shutil.rmtree(os.path.join(srv.drive(wiped), bucket))
            say(f"  wiped {bucket}/ on drive {wiped}; healing")
            res = admin(c, "heal", "POST", query=f"bucket={bucket}")
            healed = [it for it in res["items"] if it["healedDisks"]]
            say(f"  heal items: {len(res['items'])}, with healed disks: "
                f"{len(healed)}")
            frames = 0
            for key in objs:
                for d in [wiped] + lost:
                    got = part_files(srv, d, bucket, key)
                    if d == wiped or key == "off/0":
                        need(bool(got), f"drive {d} has no shard of "
                                        f"{key} after heal")
                    if d == wiped:
                        need(got == golden[key],
                             f"healed shard files of {key} on drive {d} "
                             f"differ from the originals")
                    if got:
                        frames += verify_shard_bitrot(srv, d, bucket, key)
                account(len(objs[key]), False, True, K)
            say(f"  healed shards byte-identical to the originals; "
                f"{frames} bitrot frames re-hashed on the host: all match")
            for key, want in objs.items():
                get_check(c, bucket, key, want, ranges=False)
                account(len(want), False, False, K)

        time.sleep(1.0)
        after = counters(c)
        print_counters("device-pass", before, after)
        plan = admin(c, "codec-plan")
        health = admin(c, "kernel-health")
        dev = health["backends"]["device"]
        say(f"  kernel-health device: {json.dumps(dev)}")
        say(f"  rs kernel: {json.dumps(plan['rsKernel'])}")
        say(f"  hh kernel: {json.dumps(plan['hhKernel'])}")
        self.info["rs_kernel"] = plan["rsKernel"]

        # The assertions (reported, and fatal to "ok", never skipped).
        probe = plan["lastProbe"].get("device", {})
        self.check(bool(probe) and all(v for v in probe.values())
                   and not plan["lastProbeErrors"].get("device"),
                   "boot ladder measured the device lane at every rung "
                   f"with the known answer ({json.dumps(probe)}, errors "
                   f"{json.dumps(plan['lastProbeErrors'].get('device', {}))})")
        self.check(dev["state"] == "up" and dev["failures"] == 0,
                   f"kernprof device backend up with zero failed "
                   f"dispatches (state {dev['state']}, failures "
                   f"{dev['failures']}, last error {dev['lastError']!r})")
        self.check(plan["rsKernel"]["kernel"] == "pallas",
                   f"the RS kernel that ran is the Pallas one "
                   f"({json.dumps(plan['rsKernel'])})")
        self.check(plan["hhKernel"]["kernel"] == "pallas",
                   f"the HH256 packet loop that ran is the Pallas "
                   f"kernel ({json.dumps(plan['hhKernel'])})")
        for kern in RS_KERNELS + ("hh256",):
            rose = after["bytes"][kern, "device"] \
                - before["bytes"][kern, "device"]
            self.check(rose >= sent[kern] > 0,
                       f"device-lane {kern} bytes rose by {int(rose)} "
                       f">= the {int(sent[kern])} sent in >= 4 MiB "
                       f"batches")
        for kern in RS_KERNELS:
            rose = host_bytes(after, kern) - host_bytes(before, kern)
            # One >= 4 MiB batch on a host lane would add >= 4 MiB.
            self.check(rose < FLOOR,
                       f"no >= 4 MiB {kern} batch on a host lane "
                       f"(host-lane bytes rose by {int(rose)})")
        rose = host_bytes(after, "hh256") - host_bytes(before, "hh256")
        self.check(rose <= tails + FLOOR - 1,
                   f"host-lane hh256 bytes rose by {int(rose)}, within "
                   f"the {int(tails)} of ragged final sub-blocks that "
                   f"hash on the host by design (+ < 4 MiB of small "
                   f"metadata frames)")
        hh_b = after["bytes"]["hh256", "device"]
        hh_ms = after["ms"]["hh256", "device"]
        if hh_b:
            say(f"  HH256 on the device lane: "
                f"{hh_ms / 1e3 / (hh_b / GiB):.3f} s/GiB over "
                f"{hh_b / GiB:.2f} GiB "
                f"({int(after['dispatches']['hh256', 'device'])} "
                f"dispatches, host wall around each dispatch)")
            self.info["hh256_s_per_GiB"] = hh_ms / 1e3 / (hh_b / GiB)
        for kern in RS_KERNELS:
            b = after["bytes"][kern, "device"]
            ms = after["ms"][kern, "device"]
            if b:
                say(f"  {kern} on the device lane: "
                    f"{ms / 1e3 / (b / GiB):.3f} s/GiB over "
                    f"{b / GiB:.2f} GiB "
                    f"({int(after['dispatches'][kern, 'device'])} "
                    f"dispatches)")
        say(f"  programs requested: {int(after['programs'])}, "
            f"persistent-cache hits: {int(after['cache_hits'])}, "
            f"compilations: "
            f"{int(after['programs'] - after['cache_hits'])}")
        self.info["programs"] = int(after["programs"])
        self.info["compilations"] = int(after["programs"]
                                        - after["cache_hits"])

    # four chips: only the serving-mesh path --------------------------------

    def pass_mesh(self, srv: Server) -> None:
        c = srv.client()
        bucket = "smoke-m"
        need(c.make_bucket(bucket).status == 200, "make_bucket smoke-m")
        admin(c, "set-config-kv", "POST", body=b"codec autotune=off")
        plan = admin(c, "codec-plan")
        aff0 = plan["affinity"]
        say(f"  affinity at start: {json.dumps(aff0)}")
        n_sets = srv.n_drives // (K + M)
        # Sizes: full blocks are (1, 8, 1310720) batches — S divides
        # the 'lanes' axis; the tail of EVEN is (1, 8, 524288): divides
        # too; the tail of ODD is (1, 8, 524289): divides NEITHER axis
        # and must land whole on the owning set's home device.
        base = 4 * MiB if self.tiny else 64 * MiB
        even, odd = base, base + K
        if self.tiny:
            even, odd = BLOCK + 4 * MiB, BLOCK + 4 * MiB + K
        objs: dict[str, bytes] = {}
        def set_of(key: str) -> int:
            holders = [d for d in range(1, srv.n_drives + 1)
                       if shard_index(srv, d, bucket, key)]
            need(len(holders) == K + M, f"{key}: {len(holders)} holders")
            return (holders[0] - 1) // (K + M)

        by_set: dict[int, list[str]] = {}
        with self.phase("mesh: concurrent PUTs to both sets"):
            i = 0
            # Keys hash to a set by the deployment's id: send rounds of
            # concurrent PUTs until every set holds an even-tail and an
            # odd-tail object (one round almost always).
            while i < 32 and not (len(by_set) == n_sets and all(
                    {int(k.rsplit("/", 1)[1]) % 2 for k in v} == {0, 1}
                    for v in by_set.values())):
                todo = {}
                for _ in range(4 if self.tiny else 8):
                    n = even if i % 2 == 0 else odd
                    todo[f"m/{i}"] = gen(self.seed, f"m{i}", n)
                    i += 1
                with ThreadPoolExecutor(len(todo)) as ex:
                    list(ex.map(
                        lambda kv: put(srv.client(), bucket, *kv),
                        todo.items()))
                objs.update(todo)
                for key in todo:
                    by_set.setdefault(set_of(key), []).append(key)
        say(f"  objects per erasure set: "
            f"{ {s: len(v) for s, v in sorted(by_set.items())} }")
        self.check(len(by_set) == n_sets,
                   f"objects landed on all {n_sets} erasure sets")
        with self.phase("mesh: GET back, bytes equal the generated data"):
            for key, want in objs.items():
                get_check(c, bucket, key, want)
        with self.phase("mesh: shard files equal the host codec's"):
            from minio_tpu.ops import rs_cpu
            key = "m/1"                      # an ODD-tail object
            want = objs[key]
            shard = -(-BLOCK // K)
            checked = 0
            for d in range(1, srv.n_drives + 1):
                idx = shard_index(srv, d, bucket, key)
                if not idx:
                    continue
                base_dir = os.path.join(srv.drive(d), bucket, key)
                (rel,) = part_files(srv, d, bucket, key)
                with open(os.path.join(base_dir, rel), "rb") as f:
                    stream = f.read()
                # Block 0 and the (ragged) last block, from rs_cpu.
                nblk = -(-len(want) // BLOCK)
                for b in (0, nblk - 1):
                    blk = want[b * BLOCK:(b + 1) * BLOCK]
                    gold = rs_cpu.encode_data(blk, K, M)[idx - 1]
                    off = b * (shard + 32) + 32
                    got = stream[off:off + len(gold)]
                    need(got == bytes(gold),
                         f"{key} shard {idx} block {b} differs from "
                         f"rs_cpu")
                    checked += 1
            say(f"  {checked} shard blocks byte-identical to rs_cpu "
                f"(golden host codec)")
        with self.phase("mesh: heal a wiped drive in each set"):
            for s in range(n_sets):
                d = s * (K + M) + 2
                golden = {key: part_files(srv, d, bucket, key)
                          for key in by_set.get(s, [])}
                shutil.rmtree(os.path.join(srv.drive(d), bucket))
                say(f"  wiped {bucket}/ on drive {d} (set {s})")
            admin(c, "heal", "POST", query=f"bucket={bucket}")
            for s in range(n_sets):
                d = s * (K + M) + 2
                for key in by_set.get(s, []):
                    need(part_files(srv, d, bucket, key),
                         f"no healed shard of {key} on drive {d}")
                    verify_shard_bitrot(srv, d, bucket, key)
            for key, want in objs.items():
                get_check(c, bucket, key, want, ranges=False)
        plan = admin(c, "codec-plan")
        aff = plan["affinity"]
        say(f"  affinity census: {json.dumps(aff)}")
        say(f"  rs kernel: {json.dumps(plan['rsKernel'])}")
        health = admin(c, "kernel-health")
        dev = health["backends"]["device"]
        say(f"  kernel-health device: {json.dumps(dev)}")
        want_n = self.args.chips
        self.check(aff["nDevices"] == want_n,
                   f"serving process sees {want_n} devices "
                   f"(nDevices {aff['nDevices']})")
        homes = sorted(set(aff["assignments"].values()))
        self.check(len(aff["assignments"]) == n_sets
                   and len(homes) == n_sets,
                   f"the {n_sets} erasure sets have different home "
                   f"devices ({json.dumps(aff['assignments'])})")
        touched = {i: v["bytes"] for i, v in aff["dispatches"].items()
                   if v["bytes"] > 0}
        self.check(len(touched) == want_n,
                   f"bytes on all {want_n} device indices "
                   f"({json.dumps(touched)})")
        self.check(dev["state"] == "up" and dev["failures"] == 0,
                   f"kernprof device backend up with zero failed "
                   f"dispatches ({dev['state']}, {dev['failures']}, "
                   f"{dev['lastError']!r})")
        self.check(plan["rsKernel"]["kernel"] == "pallas",
                   f"the RS kernel that ran is the Pallas one "
                   f"({json.dumps(plan['rsKernel'])})")
        self.check(plan["hhKernel"]["kernel"] == "pallas",
                   f"the HH256 packet loop that ran is the Pallas "
                   f"kernel ({json.dumps(plan['hhKernel'])})")
        # The census counts once: what the devices hold in all is the
        # batches' bytes plus what an axis left replicated repeats.
        m = metrics(c)
        held = msum(m, "minio_tpu_v2_mesh_device_bytes_total")
        sent = msum(m, "minio_tpu_v2_mesh_dispatch_bytes_total")
        repeated = msum(m, "minio_tpu_v2_mesh_dispatch_bytes_total",
                        placement="replicated")
        for kernel, census in sorted(aff["kernels"].items()):
            say(f"  {kernel} placements: "
                f"{json.dumps(census['placements'])}")
        self.check(sent <= held <= sent + 3 * repeated and sent > 0,
                   f"the devices hold the dispatched bytes once, plus "
                   f"at most 3 copies of what was left replicated "
                   f"(held {int(held)}, dispatched {int(sent)}, "
                   f"replicated {int(repeated)})")


class NoAccelerator(Exception):
    pass


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at a tiny size; ends ok: false")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run ONLY the 2x2 serving-mesh path")
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--keep", action="store_true",
                    help="keep the drive directories afterwards")
    args = ap.parse_args(argv)

    try:
        import minio_tpu.s3.client  # noqa: F401  (no JAX backend init)
    except ImportError as exc:
        print(f"chip_smoke.py must run from a checkout of the repo: "
              f"{exc}", file=sys.stderr)
        return 2
    os.makedirs(WORK, exist_ok=True)
    os.makedirs(OUT, exist_ok=True)

    build_dir = os.path.join(HERE, "minio_tpu", "native", "build")
    had = set(os.listdir(build_dir)) if os.path.isdir(build_dir) else set()

    run = Run(args)
    mesh = args.chips == 4
    srv = Server((K + M) * (2 if mesh else 1),
                 "mesh" if mesh else "one",
                 virtual_devices=4 if mesh and args.tiny else 0)
    rc = None
    failed = ""
    try:
        run.boot(srv, args.chips)
        if mesh:
            run.pass_mesh(srv)
        else:
            run.pass_defaults(srv)
            run.pass_device(srv)
    except NoAccelerator:
        srv.stop()
        print("chip_smoke.py: the serving process found no accelerator "
              f"(device {json.dumps(srv.device)}); nothing was checked",
              file=sys.stderr)
        return 3
    except Exception as exc:  # noqa: BLE001 - report, then verdict
        failed = f"{type(exc).__name__}: {exc}"
        say(f"FAILED: {failed}")
        say("server log tail:\n" + srv.log_tail())
    finally:
        with run.phase("teardown (SIGTERM, rc must be 0)"):
            rc = srv.stop()
            say(f"  server exit code: {rc}")
        try:
            shutil.copy(srv.log_path, os.path.join(
                OUT, os.path.basename(srv.log_path)))
        except OSError:
            pass
        if not args.keep:
            shutil.rmtree(srv.root, ignore_errors=True)
    run.check(rc == 0, f"server exited 0 on SIGTERM (rc {rc})")

    now = set(os.listdir(build_dir)) if os.path.isdir(build_dir) else set()
    say(f"C++ host library: "
        + (f"built on this machine during this run ({sorted(now - had)})"
           if now - had else
           f"found, keyed to these sources and this CPU ({sorted(now)})"
           if now else "NOT built (no native lane)"))
    for name, dt in run.phases:
        say(f"  wall {dt:8.1f} s  {name}")
    if "jax" in sys.modules:
        from jax._src import xla_bridge
        run.check(not xla_bridge._backends,
                  "this script initialised no JAX backend")
    dev = run.info.get("device") or {"platform": "none", "kind": "",
                                     "count": 0}
    on_chip = dev["platform"] not in ("cpu", "none")
    if not on_chip:
        run.unmet.append("no accelerator: the device assertions "
                         "cannot be met on the CPU")
    ok = bool(not failed and not run.unmet and on_chip)
    if run.unmet:
        say("not met: " + "; ".join(run.unmet))
    summary = dict(run.info, ok=ok, failed=failed, unmet=run.unmet,
                   phases=run.phases, args=vars(args))
    with open(os.path.join(OUT, f"chip_smoke_{'mesh' if mesh else 'one'}"
                                f"{'_tiny' if args.tiny else ''}.json"),
              "w") as f:
        json.dump(summary, f, indent=1, default=str)
    print(json.dumps({"ok": ok, "device": dev}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
