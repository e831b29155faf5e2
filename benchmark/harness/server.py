"""Booting the real server as a child that owns the chip, and talking to
it: S3 wire, admin routes, Prometheus text, and the launcher's control
lines (after chip_smoke.py's `Server`)."""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

from . import prom
from .s3client import S3Client

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CHILD = os.path.join(HERE, "server_child.py")


class BootFailure(Exception):
    pass


class Server:
    def __init__(self, config: dict, work: str, env_extra: dict | None = None,
                 child: str = CHILD):
        self.config = config
        self.work = work
        self.child = child
        self.root = os.path.join(work, "drives")
        shutil.rmtree(self.root, ignore_errors=True)
        os.makedirs(self.root)
        self.log_path = os.path.join(work, "server.log")
        self.env = dict(os.environ)
        self.env.pop("BENCH_RUN", None)
        self.env.update({k: str(v) for k, v in config["env"].items()})
        self.env.update(env_extra or {})
        self.access = self.env["MINIO_ACCESS_KEY"]
        self.secret = self.env["MINIO_SECRET_KEY"]
        self.proc: subprocess.Popen | None = None
        self.port = 0
        self.device: dict | None = None
        self.boot_lines: list[str] = []
        self.t_listening = 0.0
        self._listening = threading.Event()
        self._device_seen = threading.Event()
        self._answers: list[dict] = []
        self._answered = threading.Condition()

    def drive(self, i: int) -> str:
        return os.path.join(self.root, f"d{i}")

    def start(self, timeout_s: float = 900.0) -> None:
        t0 = time.monotonic()
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, self.child, "server",
             os.path.join(self.root, "d{1...%d}" % self.config["drives"]),
             "--address", "127.0.0.1:0"],
            cwd=ROOT, env=self.env, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=self._log)
        threading.Thread(target=self._pump, args=(t0,), daemon=True).start()
        for ev, what in ((self._listening, "listening"),
                         (self._device_seen, "device line")):
            while not ev.wait(0.2):
                if self.proc.poll() is not None:
                    raise BootFailure(
                        f"server exited rc={self.proc.returncode} before "
                        f"'{what}':\n{self.log_tail()}")
                if time.monotonic() - t0 > timeout_s:
                    raise BootFailure(f"no '{what}' in {timeout_s:.0f} s")

    def _pump(self, t0: float) -> None:
        for raw in self.proc.stdout:
            line = raw.decode(errors="replace").rstrip("\n")
            self._log.write(raw)
            self._log.flush()
            if line.startswith("bench-ctl: "):
                with self._answered:
                    self._answers.append(json.loads(line[11:]))
                    self._answered.notify_all()
                continue
            if len(self.boot_lines) < 40:
                self.boot_lines.append(line)
            if "listening on" in line and not self.port:
                self.port = int(line.rsplit(":", 1)[1])
                self.t_listening = time.monotonic() - t0
                self._listening.set()
            if line.startswith("minio-tpu device: "):
                self.device = json.loads(line[len("minio-tpu device: "):])
                self._device_seen.set()

    def client(self, timeout: float = 120.0) -> S3Client:
        return S3Client("127.0.0.1", self.port, self.access, self.secret,
                        timeout=timeout)

    # -- the launcher's control lines ----------------------------------------

    def send(self, line: str) -> None:
        self.proc.stdin.write((line + "\n").encode())
        self.proc.stdin.flush()

    def answer(self, cmd: str, timeout_s: float = 240.0) -> dict:
        """The next answer to `cmd` (sent earlier with `send`)."""
        end = time.monotonic() + timeout_s
        with self._answered:
            while True:
                for i, a in enumerate(self._answers):
                    if a.get("cmd") == cmd:
                        return self._answers.pop(i)
                left = end - time.monotonic()
                if left <= 0 or self.proc.poll() is not None:
                    return {"cmd": cmd, "error": "no answer"}
                self._answered.wait(min(left, 1.0))

    def ask(self, line: str, timeout_s: float = 240.0) -> dict:
        self.send(line)
        return self.answer(line.split()[0], timeout_s)

    # -- the serving process's own routes ------------------------------------

    def admin(self, c: S3Client, route: str, method: str = "GET",
              query: str = "", body: bytes = b"") -> dict:
        r = c.request(method, f"/minio-tpu/admin/v1/{route}", query=query,
                      body=body)
        if r.status != 200:
            raise BootFailure(f"admin {route}: {r.status} {r.body[:300]!r}")
        return json.loads(r.body) if r.body else {}

    def wait_probed(self, c: S3Client, timeout_s: float = 900.0) -> dict:
        t0 = time.monotonic()
        while True:
            plan = self.admin(c, "codec-plan")
            if plan.get("probed"):
                return plan
            if self.proc.poll() is not None:
                raise BootFailure("server died while probing:\n"
                                  + self.log_tail())
            if time.monotonic() - t0 > timeout_s:
                raise BootFailure("probe ladder did not finish")
            time.sleep(0.25)

    def scrape(self, c: S3Client) -> prom.Sample:
        r = c.request("GET", "/minio-tpu/v2/metrics/node", signed=False)
        if r.status != 200:
            raise BootFailure(f"metrics/node: {r.status}")
        return prom.parse(r.body.decode())

    # -- teardown ------------------------------------------------------------

    def stop(self) -> int | None:
        """SIGTERM, wait; the exit code (0 is the only sound one)."""
        if self.proc is None:
            return None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        for pipe in (self.proc.stdin, self.proc.stdout):
            try:
                pipe.close()
            except OSError:
                pass
        self._log.close()
        return self.proc.returncode

    def log_tail(self, n: int = 40) -> str:
        try:
            with open(self.log_path, "rb") as f:
                return b"\n".join(f.read().splitlines()[-n:]).decode(
                    errors="replace")
        except OSError:
            return ""
