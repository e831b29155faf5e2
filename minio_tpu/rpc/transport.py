"""Internal RPC transport: authenticated POST with length-prefixed JSON +
binary framing, and health-gated clients with reconnect (ref
cmd/rest/client.go:62,193 MarkOffline + HealthCheckFn). The client's
connections and its call path live on the RPC event loop (rpc/aio.py).

Wire format per call (everything in the BODY — headers stay tiny):
    POST /minio-tpu/rpc/v1/<service>/<method>
    x-mtpu-auth: hex hmac-sha256(cluster_key,
                   service/method + "\\n" + ts + "\\n" + args_json
                   + "\\n" + sha256(payload))
    x-mtpu-ts:   unix seconds (rejected outside +/- 5 min skew window;
                 bounds replay — cluster ports are expected to run on a
                 trusted network like the reference's)
    body: [4B big-endian args_len][args_json][payload]
Response 200: [4B result_len][result_json][body]; errors are 4xx/5xx with
a JSON {error_type, message} mapped back to storage errors.
"""

from __future__ import annotations

import hashlib
import hmac
import json
import random
import struct
import threading
import time

from ..qos.deadline import (H_DEADLINE, Deadline, DeadlineExceeded,
                            deadline_scope, record_expiry)
from ..storage import errors as serr

RPC_PREFIX = "/minio-tpu/rpc/v1"
MAX_SKEW = 300  # seconds

_ERR_TYPES = {
    "DiskNotFound": serr.DiskNotFound,
    "FaultyDisk": serr.FaultyDisk,
    "VolumeNotFound": serr.VolumeNotFound,
    "VolumeExists": serr.VolumeExists,
    "FileNotFound": serr.FileNotFound,
    "VersionNotFound": serr.VersionNotFound,
    "FileCorrupt": serr.FileCorrupt,
    "DiskFull": serr.DiskFull,
    "DeadlineExceeded": DeadlineExceeded,
}


def sign(cluster_key: bytes, method: str, ts: str, args_json: str,
         payload: bytes) -> str:
    msg = "\n".join([method, ts, args_json,
                     hashlib.sha256(payload).hexdigest()])
    return hmac.new(cluster_key, msg.encode(), hashlib.sha256).hexdigest()


def frame(args_json: bytes, payload: bytes) -> bytes:
    return struct.pack(">I", len(args_json)) + args_json + payload


def unframe(body: bytes) -> tuple[bytes, bytes]:
    if len(body) < 4:
        raise ValueError("short rpc frame")
    n = struct.unpack(">I", body[:4])[0]
    if len(body) < 4 + n:
        raise ValueError("truncated rpc frame")
    return body[4:4 + n], body[4 + n:]


def error_to_wire(e: BaseException) -> tuple[int, bytes]:
    name = type(e).__name__
    if isinstance(e, (serr.FileNotFound, serr.VolumeNotFound,
                      serr.VersionNotFound)):
        status = 404
    elif isinstance(e, DeadlineExceeded):
        status = 503  # retryable: the CALLER's budget ran out
    else:
        status = 500
    return status, json.dumps({"error_type": name,
                               "message": str(e)}).encode()


def wire_to_error(status: int, body: bytes) -> Exception:
    try:
        doc = json.loads(body)
        cls = _ERR_TYPES.get(doc.get("error_type"), serr.FaultyDisk)
        return cls(doc.get("message", f"rpc status {status}"))
    except (ValueError, KeyError):
        return serr.FaultyDisk(f"rpc status {status}: {body[:200]!r}")


class RPCClient:
    """Health-gated RPC caller to one peer: the peer's address, key,
    self-tuning timeout and offline window. Its pooled keep-alive
    connections belong to the RPC loop (rpc/aio.py)."""

    # Seconds a peer stays marked offline before a reconnect probe.
    # Live-reloadable via config-KV `rpc offline_retry=` (the server's
    # apply hook rewrites the CLASS attribute, so every client in the
    # process follows without reconstruction).
    OFFLINE_RETRY = 2.0
    # Reconnect-probe jitter: each offline window is stretched by a
    # random factor in [1, 1 + OFFLINE_JITTER] so a restarted peer
    # sees the cluster's reconnect probes SPREAD over the window
    # instead of a thundering herd at the exact same instant (every
    # node marked it offline within the same failed fan-out).
    OFFLINE_JITTER = 0.5

    def __init__(self, host: str, port: int, cluster_key: bytes,
                 timeout: float = 30.0, tls=None):
        """tls: ssl.SSLContext for https:// cluster endpoints (see
        utils.certs.client_context_from_env); the HMAC signing below
        authenticates every call either way — TLS adds transport
        privacy (ref the reference's TLS-everywhere internode with
        JWT auth on top)."""
        from ..utils.dyntimeout import DynamicTimeout
        self.host = host
        self.port = port
        self.cluster_key = cluster_key
        self.tls = tls
        # Self-tuning timeout: slow peers stretch it, fast ones shrink
        # it back (ref cmd/dynamic-timeouts.go:35). The floor is 2.5s,
        # not the reference's 1s: a peer served by the event-loop
        # front door answers through loop→worker→loop hops whose tail
        # under CPU contention is scheduling-bound, and a spurious
        # sub-second timeout here MARKS THE PEER OFFLINE — one blip
        # then degrades every write to that node for OFFLINE_RETRY,
        # which is how a momentarily-busy box turns into MRF backlog.
        self.dyn_timeout = DynamicTimeout(timeout, minimum=2.5)
        self._offline_until = 0.0
        self._mu = threading.Lock()

    def endpoint(self) -> str:
        return f"{self.host}:{self.port}"

    def is_online(self) -> bool:
        return time.monotonic() >= self._offline_until

    def _mark_offline(self) -> None:
        window = self.OFFLINE_RETRY * (
            1.0 + self.OFFLINE_JITTER * random.random())
        with self._mu:
            self._offline_until = time.monotonic() + window

    @property
    def timeout(self) -> float:
        return self.dyn_timeout.timeout

    def call(self, service: str, method: str, args: dict,
             payload: bytes = b"",
             timeout: float | None = None) -> tuple[dict, bytes]:
        """Returns (result_json, body_bytes); raises storage errors.

        `timeout` overrides the self-tuning data-plane timeout for
        calls that legitimately block server-side (e.g. a 3-30s trace
        long-poll) — such calls neither tune the dynamic timeout nor
        mark the peer offline on expiry, so a slow control-plane poll
        can never knock a healthy peer out of the data plane.

        The call runs on the RPC event loop (rpc/aio.py
        `call_async`) and this thread blocks on its future: no extra
        thread per in-flight call."""
        from . import aio
        return aio.bridge_call(self, service, method, args, payload,
                               timeout)

    def close(self) -> None:
        from . import aio
        aio.close_client(self)


class RPCRegistry:
    """Server side: named services exposing methods.

    A service is an object; exposed methods take (args: dict,
    payload: bytes) and return (result: dict, body: bytes).
    """

    def __init__(self, cluster_key: bytes):
        self.cluster_key = cluster_key
        self._services: dict[str, object] = {}

    def register(self, name: str, service: object) -> None:
        self._services[name] = service

    def handle(self, path: str, headers: dict[str, str],
               body: bytes) -> tuple[int, dict[str, str], bytes]:
        """Dispatch an RPC HTTP request; returns (status, headers, body)."""
        if not path.startswith(RPC_PREFIX + "/"):
            return 404, {}, b"not found"
        rest = path[len(RPC_PREFIX) + 1:]
        if "/" not in rest:
            return 404, {}, b"bad rpc path"
        service_name, method = rest.split("/", 1)
        try:
            args_bytes, payload = unframe(body)
        except ValueError:
            return 400, {}, b"bad rpc frame"
        ts = headers.get("x-mtpu-ts", "")
        try:
            if abs(time.time() - int(ts)) > MAX_SKEW:
                return 403, {}, b"rpc timestamp out of window"
        except ValueError:
            return 403, {}, b"bad rpc timestamp"
        args_json = args_bytes.decode("utf-8", "replace")
        want = sign(self.cluster_key, f"{service_name}/{method}", ts,
                    args_json, payload)
        if not hmac.compare_digest(want,
                                   headers.get("x-mtpu-auth", "")):
            return 403, {}, b"bad rpc signature"
        service = self._services.get(service_name)
        fn = getattr(service, f"rpc_{method}", None) if service else None
        if fn is None:
            return 404, {}, f"no method {service_name}/{method}".encode()
        try:
            args = json.loads(args_json)
            from ..obs.metrics2 import METRICS2
            METRICS2.inc("minio_tpu_v2_rpc_requests_total",
                         {"service": service_name, "method": method})
            # Remaining-budget propagation: refuse work whose caller
            # can no longer use the answer, and re-open the budget so
            # anything this handler calls in turn (disk I/O, nested
            # RPC) keeps decrementing the SAME deadline.
            ddl = None
            ddl_hdr = headers.get(H_DEADLINE, "")
            if ddl_hdr:
                try:
                    rem_ms = float(ddl_hdr)
                except ValueError:
                    rem_ms = None
                if rem_ms is not None:
                    if rem_ms <= 0:
                        record_expiry("rpc-server")
                        raise DeadlineExceeded(
                            f"{service_name}/{method}: caller deadline "
                            "already expired")
                    ddl = Deadline.from_remaining_ms(rem_ms)
            srv_span = None
            trace_hdr = headers.get("x-mtpu-trace", "")
            if trace_hdr and ":" in trace_hdr:
                # Server-side span under the caller's context; its
                # subtree (including local disk-op children) returns in
                # the reserved result key and grafts onto the caller's
                # tree (RPCClient.call pops it).
                from ..obs.span import Span
                tid, _, pid = trace_hdr.partition(":")
                srv_span = Span(f"rpc.server.{service_name}.{method}",
                                tid[:64], pid[:32])
            with deadline_scope(ddl):
                if srv_span is not None:
                    with srv_span:
                        result, rbody = fn(args, payload)
                    if isinstance(result, dict):
                        result = dict(result)
                        result["_trace_spans"] = [srv_span.to_dict()]
                else:
                    result, rbody = fn(args, payload)
            out = frame(json.dumps(result).encode(), rbody)
            return 200, {}, out
        except BaseException as e:  # noqa: BLE001 — serialized to peer
            status, ebody = error_to_wire(e)
            return status, {}, ebody
