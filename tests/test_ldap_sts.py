"""Federated identity: OpenID RS256/JWKS STS and LDAP STS (ref
cmd/config/identity/openid/jwks.go:30, cmd/config/identity/ldap/,
cmd/sts-handlers.go:78-93).

The OIDC fixture serves a JWKS document over a local HTTP server and
signs tokens with a fixed RSA-1024 key (RSASSA-PKCS1-v1_5/SHA-256,
signed here with pure bignum math — the same math oidc.rs256_verify
inverts). The LDAP fixture is an in-process fake directory speaking
real BER frames, exercising iam/ldap.py's wire client end to end.
"""

from __future__ import annotations

import base64
import http.client
import http.server
import json
import socketserver
import threading
import time
import urllib.parse
import xml.etree.ElementTree as ET

import pytest

from minio_tpu.erasure.engine import ErasureObjects
from minio_tpu.iam import ldap as l
from minio_tpu.iam.iam import ConfigStore, IAMSys
from minio_tpu.iam.ldap import LDAPClient, LDAPError, LDAPIdentity
from minio_tpu.iam.oidc import (OIDCError, OpenIDValidator,
                                emsa_pkcs1_sha256, rs256_verify)
from minio_tpu.s3.admin_client import AdminClient
from minio_tpu.s3.client import S3Client
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.xl import XLStorage

# Fixed RSA-1024 keypair (test fixture only). e = 65537.
RSA_N = 151584288247208891081431231191068013860173273213164682886058720018042589788990215647027465180780941839651172302420247922897058294276671660002090397923343011845589263813735538368405234648413384694590582518539055208821031004741618157313950517238451497189926346285463074794272679536222595170368931512336248142243  # noqa: E501
RSA_E = 65537
RSA_D = 14856125294289068883470906479396827029371087078263526834874271917785183243277601280205950972063963706548659226062304536502552839222714833944083901091927186271934622738487081068102081633075626669037718530478133528016471036991627235213793665121029235005251850172865325992835752544412735676723142580415760769393  # noqa: E501


def _b64u(data: bytes) -> str:
    return base64.urlsafe_b64encode(data).decode().rstrip("=")


def rs256_sign(claims: dict, kid: str = "test-key") -> str:
    header = _b64u(json.dumps({"alg": "RS256", "kid": kid}).encode())
    payload = _b64u(json.dumps(claims).encode())
    msg = f"{header}.{payload}".encode()
    k = (RSA_N.bit_length() + 7) // 8
    em = int.from_bytes(emsa_pkcs1_sha256(msg, k), "big")
    sig = pow(em, RSA_D, RSA_N).to_bytes(k, "big")
    return f"{header}.{payload}.{_b64u(sig)}"


JWKS_DOC = {"keys": [{
    "kty": "RSA", "kid": "test-key", "alg": "RS256", "use": "sig",
    "n": _b64u(RSA_N.to_bytes((RSA_N.bit_length() + 7) // 8, "big")),
    "e": _b64u(RSA_E.to_bytes(3, "big")),
}]}


@pytest.fixture(scope="module")
def jwks_server():
    class H(http.server.BaseHTTPRequestHandler):
        hits = [0]

        def do_GET(self):
            H.hits[0] += 1
            body = json.dumps(JWKS_DOC).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{srv.server_address[1]}/jwks.json", H.hits
    srv.shutdown()


# --- RS256 / JWKS unit level -------------------------------------------------


def test_rs256_verify_roundtrip():
    tok = rs256_sign({"sub": "x", "exp": time.time() + 60})
    h, p, s = tok.split(".")
    msg = f"{h}.{p}".encode()
    sig = base64.urlsafe_b64decode(s + "=" * (-len(s) % 4))
    assert rs256_verify(RSA_N, RSA_E, msg, sig)
    assert not rs256_verify(RSA_N, RSA_E, msg + b"x", sig)
    # A flipped bit, so that the tampered signature always differs (a
    # last byte forced to 0 IS the signature one time in 256).
    assert not rs256_verify(RSA_N, RSA_E, msg,
                            sig[:-1] + bytes([sig[-1] ^ 1]))


def test_openid_validator_rs256(jwks_server):
    url, hits = jwks_server
    v = OpenIDValidator(jwks_url=url)
    claims = v.validate(rs256_sign({"sub": "alice", "policy": "ro",
                                    "exp": time.time() + 300}))
    assert claims["sub"] == "alice"
    # JWKS is cached: another validate must not re-fetch.
    before = hits[0]
    v.validate(rs256_sign({"sub": "bob", "exp": time.time() + 300}))
    assert hits[0] == before

    with pytest.raises(OIDCError):  # expired
        v.validate(rs256_sign({"sub": "a", "exp": time.time() - 10}))
    tok = rs256_sign({"sub": "a", "exp": time.time() + 300})
    h, p, s = tok.split(".")
    with pytest.raises(OIDCError):  # tampered payload
        p2 = _b64u(json.dumps({"sub": "evil",
                               "exp": time.time() + 300}).encode())
        v.validate(f"{h}.{p2}.{s}")
    # HS256 is refused whenever a JWKS URL is configured.
    from minio_tpu.s3.webrpc import jwt_sign
    with pytest.raises(OIDCError):
        v.validate(jwt_sign({"sub": "a", "exp": time.time() + 300},
                            "shared"))


def test_openid_validator_aud_and_nbf(jwks_server):
    url, _ = jwks_server
    v = OpenIDValidator(jwks_url=url, client_id="minio-client")
    ok = rs256_sign({"sub": "a", "aud": "minio-client",
                     "exp": time.time() + 300})
    assert v.validate(ok)["aud"] == "minio-client"
    with pytest.raises(OIDCError):
        v.validate(rs256_sign({"sub": "a", "aud": "other",
                               "exp": time.time() + 300}))
    with pytest.raises(OIDCError):
        v.validate(rs256_sign({"sub": "a", "aud": "minio-client",
                               "nbf": time.time() + 100,
                               "exp": time.time() + 300}))


# --- STS AssumeRoleWithWebIdentity over RS256 --------------------------------


@pytest.fixture(scope="module")
def s3_server(tmp_path_factory):
    root = tmp_path_factory.mktemp("stsdisks")
    disks = [XLStorage(str(root / f"d{i}")) for i in range(4)]
    layer = ErasureObjects(disks, block_size=64 * 1024)
    iam = IAMSys(ConfigStore(disks), "stsroot", "stsroot-secret")
    srv = S3Server(layer, "stsroot", "stsroot-secret", iam=iam)
    port = srv.start()
    yield srv, port
    srv.stop()


def _sts_post(port: int, form: dict) -> tuple[int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", "/", body=urllib.parse.urlencode(form).encode(),
                 headers={"Content-Type":
                          "application/x-www-form-urlencoded"})
    r = conn.getresponse()
    out = r.read()
    conn.close()
    return r.status, out


_STS_NS = {"sts": "https://sts.amazonaws.com/doc/2011-06-15/"}


def _creds(out: bytes) -> tuple[str, str, str]:
    doc = ET.fromstring(out)
    return (doc.findtext(".//sts:AccessKeyId", namespaces=_STS_NS),
            doc.findtext(".//sts:SecretAccessKey", namespaces=_STS_NS),
            doc.findtext(".//sts:SessionToken", namespaces=_STS_NS))


def test_sts_web_identity_rs256(s3_server, jwks_server, monkeypatch):
    srv, port = s3_server
    url, _ = jwks_server
    monkeypatch.setenv("MINIO_IDENTITY_OPENID_JWKS_URL", url)
    monkeypatch.delenv("MINIO_IDENTITY_OPENID_SECRET", raising=False)
    adm = AdminClient("127.0.0.1", port, "stsroot", "stsroot-secret")
    adm.add_policy("jwksro", {"Version": "2012-10-17", "Statement": [
        {"Effect": "Allow",
         "Action": ["s3:GetObject", "s3:ListAllMyBuckets"],
         "Resource": ["arn:aws:s3:::*"]}]})

    token = rs256_sign({"sub": "alice@rsa-idp", "policy": "jwksro",
                        "exp": time.time() + 600})
    status, out = _sts_post(port, {
        "Action": "AssumeRoleWithWebIdentity",
        "WebIdentityToken": token, "Version": "2011-06-15"})
    assert status == 200, out
    ak, sk, st = _creds(out)
    assert ak and sk and st
    c = S3Client("127.0.0.1", port, ak, sk)
    assert c.request("GET", "/", headers={
        "x-amz-security-token": st}).status == 200

    # Tampered token: same signature, evil payload -> refused.
    h, p, s = token.split(".")
    evil = _b64u(json.dumps({"sub": "mallory", "policy": "jwksro",
                             "exp": time.time() + 600}).encode())
    status, _ = _sts_post(port, {
        "Action": "AssumeRoleWithWebIdentity",
        "WebIdentityToken": f"{h}.{evil}.{s}"})
    assert status == 403
    # HS256 dev-mode token refused while a JWKS provider is configured.
    from minio_tpu.s3.webrpc import jwt_sign
    status, _ = _sts_post(port, {
        "Action": "AssumeRoleWithWebIdentity",
        "WebIdentityToken": jwt_sign(
            {"sub": "m", "policy": "jwksro", "exp": time.time() + 600},
            "guessable")})
    assert status == 403


# --- fake LDAP directory -----------------------------------------------------

ALICE_DN = "uid=alice,ou=people,dc=example,dc=com"
BOB_DN = "uid=bob,ou=people,dc=example,dc=com"
ADMIN_GROUP_DN = "cn=storage-admins,ou=groups,dc=example,dc=com"
SVC_DN = "cn=lookup,dc=example,dc=com"

DIRECTORY = {
    ALICE_DN: {"uid": ["alice"], "objectClass": ["person"]},
    BOB_DN: {"uid": ["bob"], "objectClass": ["person"]},
    ADMIN_GROUP_DN: {"cn": ["storage-admins"], "member": [ALICE_DN],
                     "objectClass": ["groupOfNames"]},
}
PASSWORDS = {ALICE_DN: "alice-pass", BOB_DN: "bob-pass",
             SVC_DN: "svc-pass"}


class _FakeLDAPHandler(socketserver.BaseRequestHandler):
    """Speaks just enough RFC 4511 BER for bind + subtree search."""

    def handle(self):
        buf = b""
        while True:
            try:
                tag, val, consumed = l.ber_read(buf, 0)
            except ValueError:
                chunk = self.request.recv(65536)
                if not chunk:
                    return
                buf += chunk
                continue
            buf = buf[consumed:]
            parts = l.ber_read_all(val)
            msg_id = int.from_bytes(parts[0][1], "big")
            op_tag, op_val = parts[1]
            if op_tag == l._APP_BIND_REQ:
                self._bind(msg_id, op_val)
            elif op_tag == l._APP_SEARCH_REQ:
                self._search(msg_id, op_val)
            elif op_tag == l._APP_UNBIND:
                return

    def _result(self, tag: int, code: int) -> bytes:
        return l.ber(tag, l.ber_int(code, 0x0A) + l.ber_str("")
                     + l.ber_str(""))

    def _bind(self, msg_id: int, op: bytes) -> None:
        parts = l.ber_read_all(op)
        dn = parts[1][1].decode()
        password = parts[2][1].decode()
        ok = PASSWORDS.get(dn) == password and password != ""
        self.request.sendall(l.ber_seq(
            l.ber_int(msg_id),
            self._result(l._APP_BIND_RESP, 0 if ok else 49)))

    def _match(self, flt_tag: int, flt_val: bytes, dn: str,
               attrs: dict) -> bool:
        if flt_tag == l._CTX_FILTER_AND:
            return all(self._match(t, v, dn, attrs)
                       for t, v in l.ber_read_all(flt_val))
        if flt_tag == l._CTX_FILTER_EQ:
            kv = l.ber_read_all(flt_val)
            attr, want = kv[0][1].decode(), kv[1][1].decode()
            return want in attrs.get(attr, [])
        if flt_tag == l._CTX_FILTER_PRESENT:
            return flt_val.decode() in attrs
        return False

    def _search(self, msg_id: int, op: bytes) -> None:
        parts = l.ber_read_all(op)
        base = parts[0][1].decode()
        flt_tag, flt_val = parts[6]
        for dn, attrs in DIRECTORY.items():
            if not dn.endswith(base):
                continue
            if not self._match(flt_tag, flt_val, dn, attrs):
                continue
            pattrs = b"".join(
                l.ber_seq(l.ber_str(a),
                          l.ber(0x31, b"".join(l.ber_str(v)
                                               for v in vals)))
                for a, vals in attrs.items())
            entry = l.ber(l._APP_SEARCH_ENTRY,
                          l.ber_str(dn) + l.ber_seq(pattrs))
            self.request.sendall(l.ber_seq(l.ber_int(msg_id), entry))
        self.request.sendall(l.ber_seq(
            l.ber_int(msg_id), self._result(l._APP_SEARCH_DONE, 0)))


@pytest.fixture(scope="module")
def ldap_server():
    srv = socketserver.ThreadingTCPServer(("127.0.0.1", 0),
                                          _FakeLDAPHandler)
    srv.daemon_threads = True
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv.server_address[1]
    srv.shutdown()


def _identity(port: int) -> LDAPIdentity:
    return LDAPIdentity(
        f"127.0.0.1:{port}", SVC_DN, "svc-pass",
        "ou=people,dc=example,dc=com", "(uid=%s)",
        "ou=groups,dc=example,dc=com", "(&(objectClass=groupOfNames)(member=%d))")


def test_ldap_client_bind_and_search(ldap_server):
    with LDAPClient("127.0.0.1", ldap_server) as c:
        c.simple_bind(SVC_DN, "svc-pass")
        hits = c.search("ou=people,dc=example,dc=com",
                        l.filter_eq("uid", "alice"))
        assert [dn for dn, _ in hits] == [ALICE_DN]
    with LDAPClient("127.0.0.1", ldap_server) as c:
        with pytest.raises(LDAPError):
            c.simple_bind(SVC_DN, "wrong")


def test_ldap_identity_authenticate(ldap_server):
    ident = _identity(ldap_server)
    dn, groups = ident.authenticate("alice", "alice-pass")
    assert dn == ALICE_DN
    assert groups == [ADMIN_GROUP_DN]
    dn, groups = ident.authenticate("bob", "bob-pass")
    assert dn == BOB_DN and groups == []
    with pytest.raises(LDAPError):
        ident.authenticate("alice", "wrong-pass")
    with pytest.raises(LDAPError):
        ident.authenticate("alice", "")  # anonymous-bind guard
    with pytest.raises(LDAPError):
        ident.authenticate("nobody", "x")


def test_sts_ldap_identity(s3_server, ldap_server):
    srv, port = s3_server
    srv.ldap_identity = _identity(ldap_server)
    try:
        adm = AdminClient("127.0.0.1", port, "stsroot", "stsroot-secret")
        adm.add_policy("ldaprw", {"Version": "2012-10-17", "Statement": [
            {"Effect": "Allow", "Action": ["s3:*"],
             "Resource": ["arn:aws:s3:::*"]}]})

        # No policy mapped yet -> refused even with good credentials.
        status, _ = _sts_post(port, {
            "Action": "AssumeRoleWithLDAPIdentity",
            "LDAPUsername": "alice", "LDAPPassword": "alice-pass"})
        assert status == 403

        # Map the GROUP to a policy; alice inherits via membership.
        adm.set_sts_policy_map(f"ldap:{ADMIN_GROUP_DN}", ["ldaprw"])
        assert adm.get_sts_policy_map() == {
            f"ldap:{ADMIN_GROUP_DN}": ["ldaprw"]}
        status, out = _sts_post(port, {
            "Action": "AssumeRoleWithLDAPIdentity",
            "LDAPUsername": "alice", "LDAPPassword": "alice-pass",
            "Version": "2011-06-15"})
        assert status == 200, out
        ak, sk, st = _creds(out)
        doc = ET.fromstring(out)
        assert doc.findtext(".//sts:LDAPUserDN",
                            namespaces=_STS_NS) == ALICE_DN
        c = S3Client("127.0.0.1", port, ak, sk)
        r2 = c.request("PUT", "/ldapbkt",
                       headers={"x-amz-security-token": st})
        assert r2.status == 200

        # bob is not in the group: no mapped policy -> refused.
        status, _ = _sts_post(port, {
            "Action": "AssumeRoleWithLDAPIdentity",
            "LDAPUsername": "bob", "LDAPPassword": "bob-pass"})
        assert status == 403
        # Wrong password -> refused.
        status, _ = _sts_post(port, {
            "Action": "AssumeRoleWithLDAPIdentity",
            "LDAPUsername": "alice", "LDAPPassword": "nope"})
        assert status == 403
    finally:
        srv.ldap_identity = None


def test_sts_client_grants(s3_server, jwks_server, monkeypatch):
    """AssumeRoleWithClientGrants: same JWT validation as WebIdentity,
    ClientGrants wire shape (ref the shared JWT handler,
    cmd/sts-handlers.go:86,270-305,427-432)."""
    srv, port = s3_server
    url, _ = jwks_server
    monkeypatch.setenv("MINIO_IDENTITY_OPENID_JWKS_URL", url)
    monkeypatch.delenv("MINIO_IDENTITY_OPENID_SECRET", raising=False)
    adm = AdminClient("127.0.0.1", port, "stsroot", "stsroot-secret")
    adm.add_policy("grantsro", {"Version": "2012-10-17", "Statement": [
        {"Effect": "Allow", "Action": ["s3:ListAllMyBuckets"],
         "Resource": ["arn:aws:s3:::*"]}]})
    token = rs256_sign({"sub": "svc@provider", "policy": "grantsro",
                        "exp": time.time() + 600})
    status, out = _sts_post(port, {
        "Action": "AssumeRoleWithClientGrants", "Token": token,
        "Version": "2011-06-15"})
    assert status == 200, out
    doc = ET.fromstring(out)
    assert doc.tag.endswith("AssumeRoleWithClientGrantsResponse")
    assert doc.find(".//sts:ClientGrantsResult",
                    namespaces=_STS_NS) is not None
    assert doc.findtext(".//sts:SubjectFromToken",
                        namespaces=_STS_NS) == "svc@provider"
    ak, sk, st = _creds(out)
    c = S3Client("127.0.0.1", port, ak, sk)
    assert c.request("GET", "/", headers={
        "x-amz-security-token": st}).status == 200
    status, _ = _sts_post(port, {
        "Action": "AssumeRoleWithClientGrants", "Token": "garbage"})
    assert status == 403
