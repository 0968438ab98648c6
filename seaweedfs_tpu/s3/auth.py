"""AWS Signature V4 verification.

Reference: weed/s3api/auth_signature_v4.go — header-based AUTH
(Authorization: AWS4-HMAC-SHA256 ...) and presigned-URL query auth.
Streaming chunked uploads (STREAMING-AWS4-HMAC-SHA256-PAYLOAD, per
weed/s3api/chunked_reader_v4.go) are verified chunk-by-chunk using the
SigningContext returned by verify_v4_ex.
"""

from __future__ import annotations

import hashlib
import hmac
import os
import threading
import urllib.parse
from collections import OrderedDict
from dataclasses import dataclass
from datetime import datetime, timedelta, timezone


class S3AuthError(Exception):
    def __init__(self, code: str, message: str):
        super().__init__(message)
        self.code = code


@dataclass
class Identity:
    name: str
    access_key: str
    secret_key: str
    actions: tuple[str, ...] = ("Admin",)  # Admin|Read|Write|List|Tagging
    # IAM policy documents (AWS JSON); when present they REPLACE the
    # coarse action model for authorization (reference
    # auth_credentials.go identity -> policy binding)
    policies: tuple = ()
    # STS temporary credentials carry a session token the request must
    # echo in x-amz-security-token
    session_token: str = ""

    def allows(self, action: str) -> bool:
        return "Admin" in self.actions or action in self.actions


class IdentityStore:
    def __init__(self, sts=None):
        self._by_access_key: dict[str, Identity] = {}
        self.allow_anonymous = True
        self.sts = sts  # iam.StsService for temp-credential lookup

    def add(self, ident: Identity) -> None:
        self._by_access_key[ident.access_key] = ident
        self.allow_anonymous = False

    def lookup(self, access_key: str) -> Identity | None:
        ident = self._by_access_key.get(access_key)
        if ident is not None:
            return ident
        if self.sts is not None:
            cred = self.sts.lookup(access_key)
            if cred is not None:
                return Identity(
                    name=f"sts:{cred.role.name}",
                    access_key=cred.access_key,
                    secret_key=cred.secret_key,
                    actions=(),
                    policies=tuple(cred.role.policies),
                    session_token=cred.session_token,
                )
        return None

    @property
    def empty(self) -> bool:
        return not self._by_access_key


def _sha256(b: bytes) -> str:
    return hashlib.sha256(b).hexdigest()


def _parse_amz_date(s: str) -> datetime:
    """``YYYYMMDD'T'HHMMSS'Z'`` -> aware datetime. The strptime this
    replaces cost ~6us per request on the warm path (format-string
    re-interpretation); the fixed-layout slice parse is ~10x cheaper
    with the same refusal behavior (ValueError on anything malformed —
    the datetime constructor still range-checks every field). The
    digit checks are strict — int() alone would admit forms strptime
    refused (signs, padding, non-ASCII digits)."""
    if (
        len(s) != 16
        or s[8] != "T"
        or s[15] != "Z"
        or not s.isascii()
        or not s[0:8].isdigit()
        or not s[9:15].isdigit()
    ):
        raise ValueError(f"malformed amz date {s!r}")
    return datetime(
        int(s[0:4]), int(s[4:6]), int(s[6:8]),
        int(s[9:11]), int(s[11:13]), int(s[13:15]),
        tzinfo=timezone.utc,
    )


def _hmac(key: bytes, msg: str) -> bytes:
    return hmac.new(key, msg.encode(), hashlib.sha256).digest()


# --------------------------------------------------------------- fast path
# The warm-GET ceiling after the byte planes went native (ISSUE 13) is
# this module: every request re-ran the 4-step HMAC key derivation AND
# the full canonical-request reconstruction. Two memoizations close it:
#
# - the DERIVED SIGNING KEY is a pure function of (secret, date, region,
#   service) — one derivation per key/day instead of per request;
# - a bounded VERDICT MEMO over header-auth verifications: the memo key
#   is a digest of EVERY input the verification reads (secret included,
#   so key rotation changes the digest and can never serve a stale
#   verdict), and only SUCCESSFUL verdicts are stored — a 403 is always
#   recomputed. Freshness (the 15-minute skew window), identity
#   existence, and the session-token compare are re-checked on every
#   hit, so a memo hit is bit-identical to a full verification in both
#   result and refusal behavior. Presigned-URL auth and streaming/
#   chunked payloads bypass the memo entirely.
#
# ``SEAWEED_S3_AUTH_MEMO`` sizes the verdict memo (entries; 0 disables).

_SKEY_MAX = 256
_skey_lock = threading.Lock()
_skey_cache: "OrderedDict[tuple, bytes]" = OrderedDict()

_memo_lock = threading.Lock()
_memo: "OrderedDict[bytes, tuple]" = OrderedDict()


def _memo_capacity() -> int:
    try:
        return int(os.environ.get("SEAWEED_S3_AUTH_MEMO", "2048"))
    except ValueError:
        return 2048


def _memo_count(result: str) -> None:
    from ..utils import metrics

    metrics.s3_auth_memo_total.inc(result=result)


def auth_cache_stats() -> dict:
    """Signing-key / verdict-memo occupancy. No status surface reads it:
    only tests/test_warm_path.py does."""
    with _skey_lock:
        skeys = len(_skey_cache)
    with _memo_lock:
        verdicts = len(_memo)
    return {"signing_keys": skeys, "verdicts": verdicts}


def auth_cache_clear() -> None:
    """Drop both caches (tests; never required for correctness — the
    memo digest covers every verification input including the secret)."""
    with _skey_lock:
        _skey_cache.clear()
    with _memo_lock:
        _memo.clear()


def _derive_signing_key(secret: str, date: str, region: str, service: str) -> bytes:
    k = _hmac(("AWS4" + secret).encode(), date)
    k = _hmac(k, region)
    k = _hmac(k, service)
    return _hmac(k, "aws4_request")


def signing_key(secret: str, date: str, region: str, service: str = "s3") -> bytes:
    """Derived SigV4 signing key, cached per (secret, date, region,
    service) — a pure function, so the cache can never go stale; a
    rotated secret is simply a different key.
    ``SEAWEED_S3_AUTH_MEMO=0`` disables this cache too: it is the
    master off-switch for the whole SigV4 fast path (a user-set
    switch with no measurement behind it: ROADMAP Design 4)."""
    if _memo_capacity() <= 0:
        return _derive_signing_key(secret, date, region, service)
    ck = (secret, date, region, service)
    with _skey_lock:
        k = _skey_cache.get(ck)
        if k is not None:
            _skey_cache.move_to_end(ck)
            return k
    k = _derive_signing_key(secret, date, region, service)
    with _skey_lock:
        _skey_cache[ck] = k
        while len(_skey_cache) > _SKEY_MAX:
            _skey_cache.popitem(last=False)
    return k


def sign_v4(
    method: str,
    path: str,
    query: str = "",
    *,
    access_key: str,
    secret_key: str,
    headers: dict | None = None,
    payload_hash: str,
    region: str = "us-east-1",
    service: str = "s3",
    amz_date: str | None = None,
) -> dict:
    """Client-side header-auth SigV4 signer — the mirror image of
    :func:`verify_v4_ex`, built on the SAME canonicalization helpers so
    a canonical-request change lands in one place for both directions.
    Signs `headers` (plus x-amz-date / x-amz-content-sha256, which are
    always added and signed) and returns a new dict with the
    Authorization header merged in. Used by the warm-path tests;
    tests/test_s3.py keeps its own independent signer as the
    cross-implementation check."""
    h = {k.lower(): v for k, v in (headers or {}).items()}
    if amz_date is None:
        amz_date = datetime.now(timezone.utc).strftime("%Y%m%dT%H%M%SZ")
    h["x-amz-date"] = amz_date
    h["x-amz-content-sha256"] = payload_hash
    date = amz_date[:8]
    signed = ";".join(sorted(h))
    canonical_headers = "".join(
        f"{k}:{' '.join((h[k] or '').split())}\n" for k in sorted(h)
    )
    creq = "\n".join(
        [
            method,
            canonical_uri(path),
            canonical_query(query),
            canonical_headers,
            signed,
            payload_hash,
        ]
    )
    scope = f"{date}/{region}/{service}/aws4_request"
    sts = "\n".join(
        ["AWS4-HMAC-SHA256", amz_date, scope, _sha256(creq.encode())]
    )
    sig = hmac.new(
        signing_key(secret_key, date, region, service),
        sts.encode(),
        hashlib.sha256,
    ).hexdigest()
    h["Authorization"] = (
        f"AWS4-HMAC-SHA256 Credential={access_key}/{scope}, "
        f"SignedHeaders={signed}, Signature={sig}"
    )
    return h


def canonical_query(query: str, drop: str | None = None) -> str:
    pairs = urllib.parse.parse_qsl(query, keep_blank_values=True)
    if drop:
        pairs = [(k, v) for k, v in pairs if k != drop]
    enc = [
        (
            urllib.parse.quote(k, safe="-_.~"),
            urllib.parse.quote(v, safe="-_.~"),
        )
        for k, v in pairs
    ]
    return "&".join(f"{k}={v}" for k, v in sorted(enc))


def canonical_uri(path: str) -> str:
    # S3 canonical URI: each path segment URI-encoded (but "/" kept)
    return urllib.parse.quote(urllib.parse.unquote(path), safe="/-_.~")


@dataclass
class SigningContext:
    """Everything needed to verify a chunk-signature chain (reference
    chunked_reader_v4.go: seed signature + derived signing key)."""

    signing_key: bytes
    amz_date: str
    scope: str  # date/region/service/aws4_request
    seed_signature: str


def verify_v4(
    store: IdentityStore,
    method: str,
    path: str,
    query: str,
    headers,
    payload_hash: str,
) -> Identity:
    return verify_v4_ex(store, method, path, query, headers, payload_hash)[0]


def verify_v4_ex(
    store: IdentityStore,
    method: str,
    path: str,
    query: str,
    headers,
    payload_hash: str,
) -> tuple[Identity, SigningContext | None]:
    """Validate the Authorization header; returns the caller identity
    plus the signing context (None for presigned-URL auth)."""
    auth = headers.get("Authorization", "")
    if not auth:
        # presigned query auth
        q = dict(urllib.parse.parse_qsl(query, keep_blank_values=True))
        if "X-Amz-Signature" in q:
            return _verify_presigned(store, method, path, query, headers, q), None
        raise S3AuthError("AccessDenied", "no credentials")
    if not auth.startswith("AWS4-HMAC-SHA256 "):
        raise S3AuthError("AccessDenied", "unsupported auth scheme")
    fields = {}
    for part in auth[len("AWS4-HMAC-SHA256 ") :].split(","):
        k, _, v = part.strip().partition("=")
        fields[k] = v
    try:
        cred = fields["Credential"]
        signed_headers = fields["SignedHeaders"].split(";")
        signature = fields["Signature"]
        access_key, date, region, service, _ = cred.split("/")
    except (KeyError, ValueError):
        raise S3AuthError("AuthorizationHeaderMalformed", "bad Authorization") from None
    ident = store.lookup(access_key)
    if ident is None:
        raise S3AuthError("InvalidAccessKeyId", f"unknown access key {access_key}")

    amz_date = headers.get("x-amz-date", "") or headers.get("Date", "")
    # freshness window (AWS allows 15 min of skew); without it a sniffed
    # signed request replays forever. Re-checked on EVERY request —
    # memo hits included — so a memoized verdict can never outlive the
    # skew window.
    try:
        t0 = _parse_amz_date(amz_date)
    except ValueError:
        raise S3AuthError("AccessDenied", "malformed x-amz-date") from None
    if abs((datetime.now(timezone.utc) - t0).total_seconds()) > 900:
        raise S3AuthError("RequestTimeTooSkewed", "request time too skewed")
    canonical_headers = "".join(
        f"{h}:{' '.join((headers.get(h) or '').split())}\n" for h in signed_headers
    )
    # Verdict memo (fast path): the digest covers EVERY verification
    # input — any changed byte (tampered request, rotated secret) is a
    # different key, so a hit can only replay a verification that would
    # succeed identically. The skew window was already re-checked above;
    # identity existence was re-looked-up; the session token is
    # re-compared below (it may ride an unsigned header, outside the
    # digest). Streaming/chunked payloads bypass (their seed context
    # feeds a chunk chain — keep that path byte-for-byte untouched).
    memo_cap = _memo_capacity()
    mkey = None
    cached = None
    if memo_cap > 0 and not payload_hash.startswith("STREAMING-"):
        mkey = hashlib.sha256(
            "\x00".join(
                [
                    ident.secret_key,
                    access_key,
                    method,
                    path,
                    query,
                    canonical_headers,
                    ";".join(signed_headers),
                    payload_hash,
                    signature,
                    amz_date,
                    f"{date}/{region}/{service}",
                ]
            ).encode()
        ).digest()
        with _memo_lock:
            cached = _memo.get(mkey)
            if cached is not None:
                _memo.move_to_end(mkey)
        _memo_count("hit" if cached is not None else "miss")
    else:
        _memo_count("bypass")
    if cached is not None:
        skey, scope = cached
        if ident.session_token and not hmac.compare_digest(
            headers.get("x-amz-security-token", "") or "", ident.session_token
        ):
            raise S3AuthError("InvalidToken", "missing or wrong session token")
        return ident, SigningContext(
            signing_key=skey,
            amz_date=amz_date,
            scope=scope,
            seed_signature=signature,
        )
    creq = "\n".join(
        [
            method,
            canonical_uri(path),
            canonical_query(query),
            canonical_headers,
            ";".join(signed_headers),
            payload_hash,
        ]
    )
    sts = "\n".join(
        [
            "AWS4-HMAC-SHA256",
            amz_date,
            f"{date}/{region}/{service}/aws4_request",
            _sha256(creq.encode()),
        ]
    )
    skey = signing_key(ident.secret_key, date, region, service)
    want = hmac.new(skey, sts.encode(), hashlib.sha256).hexdigest()
    if not hmac.compare_digest(want, signature):
        raise S3AuthError("SignatureDoesNotMatch", "signature mismatch")
    if ident.session_token and not hmac.compare_digest(
        headers.get("x-amz-security-token", "") or "", ident.session_token
    ):
        raise S3AuthError("InvalidToken", "missing or wrong session token")
    ctx = SigningContext(
        signing_key=skey,
        amz_date=amz_date,
        scope=f"{date}/{region}/{service}/aws4_request",
        seed_signature=signature,
    )
    if mkey is not None:
        # success-only admission: a mismatch raised above, so refusals
        # (bad signature, rotated key, revoked token) are recomputed on
        # every attempt and can never be served from the memo
        with _memo_lock:
            _memo[mkey] = (skey, ctx.scope)
            while len(_memo) > memo_cap:
                _memo.popitem(last=False)
    return ident, ctx


def verify_chunk_signature(
    ctx: SigningContext, prev_signature: str, chunk: bytes
) -> str:
    """Expected signature of one aws-chunked frame (reference
    chunked_reader_v4.go getChunkSignature)."""
    sts = "\n".join(
        [
            "AWS4-HMAC-SHA256-PAYLOAD",
            ctx.amz_date,
            ctx.scope,
            prev_signature,
            _sha256(b""),
            _sha256(chunk),
        ]
    )
    return hmac.new(ctx.signing_key, sts.encode(), hashlib.sha256).hexdigest()


def verify_trailer_signature(
    ctx: SigningContext, prev_signature: str, trailer: bytes
) -> str:
    """Expected x-amz-trailer-signature over the canonical trailer
    block (STREAMING-AWS4-HMAC-SHA256-PAYLOAD-TRAILER)."""
    sts = "\n".join(
        [
            "AWS4-HMAC-SHA256-TRAILER",
            ctx.amz_date,
            ctx.scope,
            prev_signature,
            _sha256(trailer),
        ]
    )
    return hmac.new(ctx.signing_key, sts.encode(), hashlib.sha256).hexdigest()


def _verify_presigned(store, method, path, query, headers, q) -> Identity:
    try:
        cred = q["X-Amz-Credential"]
        access_key, date, region, service, _ = cred.split("/")
        signed_headers = q["X-Amz-SignedHeaders"].split(";")
        signature = q["X-Amz-Signature"]
        amz_date = q["X-Amz-Date"]
        expires = int(q["X-Amz-Expires"])
    except (KeyError, ValueError):
        raise S3AuthError("AuthorizationQueryParametersError", "bad presign") from None
    # AWS rejects out-of-range expiries rather than clamping: a URL
    # signed with a huge X-Amz-Expires must not be honored indefinitely.
    if expires < 1 or expires > 604800:
        raise S3AuthError(
            "AuthorizationQueryParametersError",
            "X-Amz-Expires must be between 1 and 604800 seconds",
        )
    ident = store.lookup(access_key)
    if ident is None:
        raise S3AuthError("InvalidAccessKeyId", f"unknown access key {access_key}")
    try:
        t0 = _parse_amz_date(amz_date)
    except ValueError:
        raise S3AuthError(
            "AuthorizationQueryParametersError", "malformed X-Amz-Date"
        ) from None
    if datetime.now(timezone.utc) > t0 + timedelta(seconds=expires):
        raise S3AuthError("AccessDenied", "request expired")
    canonical_headers = "".join(
        f"{h}:{' '.join((headers.get(h) or '').split())}\n" for h in signed_headers
    )
    creq = "\n".join(
        [
            method,
            canonical_uri(path),
            canonical_query(query, drop="X-Amz-Signature"),
            canonical_headers,
            ";".join(signed_headers),
            "UNSIGNED-PAYLOAD",
        ]
    )
    sts = "\n".join(
        [
            "AWS4-HMAC-SHA256",
            amz_date,
            f"{date}/{region}/{service}/aws4_request",
            _sha256(creq.encode()),
        ]
    )
    want = hmac.new(
        signing_key(ident.secret_key, date, region, service),
        sts.encode(),
        hashlib.sha256,
    ).hexdigest()
    if not hmac.compare_digest(want, signature):
        raise S3AuthError("SignatureDoesNotMatch", "signature mismatch")
    if ident.session_token and not hmac.compare_digest(
        q.get("X-Amz-Security-Token", ""), ident.session_token
    ):
        raise S3AuthError("InvalidToken", "missing or wrong session token")
    return ident
