"""The load generator's S3 client: SigV4 header auth over one kept-alive
`http.client` connection per client thread, as SDK clients do over
plain HTTP (signed payload hash). Part of the yardstick, so it imports
nothing of the program."""

from __future__ import annotations

import datetime
import hashlib
import hmac
import http.client
import time
import urllib.parse
from dataclasses import dataclass


@dataclass
class Response:
    status: int
    headers: dict[str, str]
    body: bytes
    t_first_byte: float = 0.0   # monotonic, response head read
    t_done: float = 0.0         # monotonic, last body byte read


def _quote(s: str, safe: str) -> str:
    return urllib.parse.quote(s, safe=safe)


def canonical_query(query: str) -> str:
    pairs = urllib.parse.parse_qsl(query, keep_blank_values=True)
    enc = sorted((_quote(k, "-_.~"), _quote(v, "-_.~")) for k, v in pairs)
    return "&".join(f"{k}={v}" for k, v in enc)


def sign(method: str, path: str, query: str, headers: dict[str, str],
         payload_hash: str, access: str, secret: str,
         region: str = "us-east-1",
         now: datetime.datetime | None = None) -> dict[str, str]:
    """`headers` (lower-case keys, with host) plus the SigV4 ones."""
    now = now or datetime.datetime.now(datetime.timezone.utc)
    amz_date = now.strftime("%Y%m%dT%H%M%SZ")
    date = amz_date[:8]
    out = dict(headers)
    out["x-amz-date"] = amz_date
    out["x-amz-content-sha256"] = payload_hash
    signed = sorted(out)
    canon = "\n".join([
        method.upper(), path, canonical_query(query),
        "".join(f"{h}:{' '.join(out[h].split())}\n" for h in signed),
        ";".join(signed), payload_hash])
    scope = f"{date}/{region}/s3/aws4_request"
    to_sign = "\n".join(["AWS4-HMAC-SHA256", amz_date, scope,
                         hashlib.sha256(canon.encode()).hexdigest()])
    key = ("AWS4" + secret).encode()
    for part in (date, region, "s3", "aws4_request"):
        key = hmac.new(key, part.encode(), hashlib.sha256).digest()
    sig = hmac.new(key, to_sign.encode(), hashlib.sha256).hexdigest()
    out["authorization"] = (
        f"AWS4-HMAC-SHA256 Credential={access}/{scope}, "
        f"SignedHeaders={';'.join(signed)}, Signature={sig}")
    return out


class S3Client:
    """One connection, reused; a request that finds it dead reconnects
    once. Not thread-safe: one per client thread."""

    def __init__(self, host: str, port: int, access: str, secret: str,
                 timeout: float = 60.0):
        self.host, self.port = host, port
        self.access, self.secret = access, secret
        self.timeout = timeout
        self._conn: http.client.HTTPConnection | None = None

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def request(self, method: str, path: str, query: str = "",
                body=b"", headers: dict[str, str] | None = None,
                signed: bool = True) -> Response:
        hdrs = {k.lower(): v for k, v in (headers or {}).items()}
        hdrs["host"] = f"{self.host}:{self.port}"
        if signed:
            hdrs = sign(method, path, query, hdrs,
                        hashlib.sha256(body).hexdigest(),
                        self.access, self.secret)
        hdrs["content-length"] = str(len(body))
        url = path + (f"?{query}" if query else "")
        for attempt in (0, 1):
            fresh = self._conn is None
            if fresh:
                self._conn = http.client.HTTPConnection(
                    self.host, self.port, timeout=self.timeout)
            try:
                self._conn.request(method, url, body=body, headers=hdrs)
                resp = self._conn.getresponse()
                t_first = time.monotonic()
                data = resp.read()
                t_done = time.monotonic()
            except (http.client.HTTPException, ConnectionError,
                    BrokenPipeError):
                self.close()
                # A kept-alive connection the server closed while idle
                # fails on first use: that is the connection's age, not
                # the operation. Anything on a fresh one is the answer.
                if fresh or attempt:
                    raise
                continue
            except OSError:
                self.close()
                raise
            if resp.will_close:
                self.close()
            return Response(resp.status,
                            {k.lower(): v for k, v in resp.getheaders()},
                            data, t_first, t_done)
        raise AssertionError("unreachable")

    @staticmethod
    def key_path(bucket: str, key: str) -> str:
        return f"/{bucket}/" + _quote(key, "/-_.~")
