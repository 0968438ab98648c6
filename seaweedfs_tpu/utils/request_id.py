"""X-Request-ID propagation + per-request HTTP tracing.

Reference: weed/util/request_id — every HTTP hop carries the id; the
first server in the chain mints one. Stored in a contextvar so log
lines and downstream client calls inside one request see it without
threading it through signatures.

:class:`RequestTracingMixin` is also the HTTP end of the flight
recorder (utils/trace.py): when the tracer is armed, every request gets
a ROOT SPAN that adopts the trace id / parent span carried in the
``X-Sw-Trace-Id`` / ``X-Sw-Parent-Span`` request headers (minting a
fresh trace when absent), activates it as the ambient span for the
handler thread (downstream client calls and EC spans nest under it),
echoes the trace id on the response, and finishes it when the response
completes. Armed or not, every request lands in the
``sw_request_seconds{server,op}`` latency histogram — the per-op-class
SLO surface served at ``/debug/slo``.
"""

from __future__ import annotations

import contextvars
import time
import uuid

HEADER = "X-Request-ID"

_current: contextvars.ContextVar[str] = contextvars.ContextVar(
    "request_id", default=""
)


def get() -> str:
    return _current.get()


def ensure(incoming: str | None = None) -> str:
    """Adopt the caller's id or mint one; returns the active id."""
    rid = incoming or uuid.uuid4().hex[:16]
    _current.set(rid)
    return rid


def clear() -> None:
    _current.set("")


def inject(headers: dict) -> dict:
    """Add the active id to outgoing request headers (no-op outside a
    request context)."""
    rid = get()
    if rid:
        headers[HEADER] = rid
    return headers


class RequestTracingMixin:
    """Mix into a BaseHTTPRequestHandler (before it in the MRO): adopts
    or mints the request id when headers are parsed and echoes it on
    every response, so one id follows a request through
    client → filer → volume hops and appears in each server's logs.

    Per-request tracing rides the same hooks: ``parse_request`` opens
    (or adopts, via the ``X-Sw-*`` headers) a root span and installs it
    as the thread's ambient span (under the pooled front end,
    utils/http_pool.py, the span starts where the worker took the
    request up and carries ``ready_wait`` and ``parse`` stages);
    ``handle_one_request`` finishes it after the response and records
    the request into the
    ``sw_request_seconds{server,op}`` SLO histogram. Subclasses set
    ``trace_server_kind`` ("s3", "filer", "volume", "master",
    "webdav") and may refine the op class per request by assigning
    ``self._sw_op`` (defaults to the lowercased HTTP method). A handler
    that knows its server's ``ip:port`` says so in ``trace_addr``, and
    its root spans carry it as ``addr``: WHICH volume server answered,
    where ``server`` only says that one did."""

    trace_server_kind = "http"
    trace_addr = ""

    def parse_request(self):  # type: ignore[override]
        ok = super().parse_request()
        if ok:
            ensure(self.headers.get(HEADER))
            self._sw_t0 = time.perf_counter()
            self._sw_code = 0
            self._sw_op = ""
            self._sw_span = None
            self._sw_token = None
            from . import trace

            if trace.armed:
                sp = trace.start_from_headers(
                    f"http.{self.trace_server_kind}",
                    self.headers,
                    name=f"{self.command} {self.path.split('?', 1)[0]}",
                    server=self.trace_server_kind,
                )
                if sp is not None and self.trace_addr:
                    sp.attrs["addr"] = self.trace_addr
                self._sw_span = sp
                self._sw_token = trace.set_current(sp)
                begun = self.__dict__.pop("_sw_begun", None)
                if sp is not None and begun is not None:
                    # the pooled front end read the clocks before the
                    # request line was: the span starts there, after
                    # whatever the connection waited for a worker
                    queued_ns, t0_ns, cpu0_ns = begun
                    sp.backdate(t0_ns, cpu0_ns)
                    if queued_ns:
                        sp.add_interval("ready_wait", queued_ns, t0_ns)
                    sp.add_interval(
                        "parse", t0_ns, time.perf_counter_ns(),
                        time.thread_time_ns() - cpu0_ns,
                    )
        return ok

    def send_response(self, code, message=None):  # type: ignore[override]
        super().send_response(code, message)
        rid = get()
        if rid:
            self.send_header(HEADER, rid)
        if not getattr(self, "_sw_code", 0):
            self._sw_code = code
        sp = getattr(self, "_sw_span", None)
        if sp is not None:
            from . import trace

            self.send_header(trace.TRACE_ID_HEADER, sp.trace_id)

    def handle_one_request(self):  # type: ignore[override]
        try:
            super().handle_one_request()
        finally:
            self._sw_finish_request()

    def _sw_finish_request(self) -> None:
        t0 = self.__dict__.pop("_sw_t0", None)
        if t0 is None:
            return  # parse failed / idle keep-alive close: no request
        from . import metrics
        from . import trace

        op = getattr(self, "_sw_op", "") or (self.command or "?").lower()
        dur = time.perf_counter() - t0
        metrics.request_seconds.observe(
            dur, server=self.trace_server_kind, op=op
        )
        metrics.request_total.inc(
            server=self.trace_server_kind,
            op=op,
            code=str(getattr(self, "_sw_code", 0) or 0),
        )
        sp = self.__dict__.pop("_sw_span", None)
        token = self.__dict__.pop("_sw_token", None)
        if sp is not None:
            sp.attrs["http_code"] = getattr(self, "_sw_code", 0)
            sp.attrs["op_class"] = op
            trace.finish(sp)
        trace.reset_current(token)

    def serve_slo_endpoint(self, path: str) -> bool:
        """Serve ``/debug/slo`` (this process's per-op-class p50/p99
        from ``sw_request_seconds``); True when the request was
        handled. Open like /metrics — it holds latency stats only.

        Status/control-plane servers only (master, volume, filer): the
        S3 and WebDAV DATA planes deliberately do not call this — a
        bucket literally named ``debug`` must stay addressable, and an
        unauthenticated status response would bypass SigV4. Their op
        classes still appear in ``/metrics`` and in any co-resident
        server's ``/debug/slo`` (the registry is process-wide)."""
        if path == "/debug/gateway":
            return self._serve_debug_json(self._gateway_doc())
        if path != "/debug/slo":
            return False
        from . import metrics

        return self._serve_debug_json(metrics.slo_summary())

    def _gateway_doc(self) -> dict:
        """``/debug/gateway``: the serving-path pressure surface beside
        /debug/slo — this server's HTTP front-end state (worker pool /
        accept budget / rejects) plus the process-wide hot-cache and
        inflight counters (sw_gateway_*)."""
        from . import metrics
        from .http_pool import status_of

        doc = metrics.gateway_summary()
        doc["front_end"] = status_of(self.server)
        return doc

    def _serve_debug_json(self, obj) -> bool:
        import json

        body = json.dumps(obj, sort_keys=True).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)
        return True
