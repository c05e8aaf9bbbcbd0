"""HTTP front-end: serves a Node's RestController over real sockets.

Re-design of the reference's HTTP layer (http/AbstractHttpServerTransport.java
+ modules/transport-netty4 Netty4HttpServerTransport): a threaded stdlib
HTTP server is the bind/dispatch boundary; all routing and error rendering
live in RestController so in-process tests and real HTTP share one path.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from opensearch_tpu.node import Node
from opensearch_tpu.telemetry import TELEMETRY


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    node: Node = None  # set by server factory

    def _do(self, method: str):
        """One request, under the root span of its timeline: the
        always-on ring (telemetry/tracer.py SpanRing) gets `http.request`
        from the first line to after the last byte is written, with
        `http.read_decode` and `http.encode_write` under it; whatever
        the handler records on this thread (rest.search, envelope, ...)
        hangs below through the bound trace, and the whole request goes
        into the ring as one row when it ends. Every exit records.
        `marks` are the clock reads: entry, body decoded, handler
        returned, payload written."""
        marks = [time.monotonic_ns()]
        ring = TELEMETRY.tracer.spans
        trace, sid, parent = ring.enter()
        attrs = {"method": method, "route": "other", "status": 0,
                 "request_bytes": 0, "response_bytes": 0}
        try:
            self._serve(method, marks, attrs)
        finally:
            spans = trace.spans
            spans.append((sid, parent, "http.request", marks[0],
                          time.monotonic_ns(), attrs))
            if len(marks) > 1:
                spans.append((sid + 1, sid, "http.read_decode", marks[0],
                              marks[1], None))
            if len(marks) > 3:
                spans.append((sid + 2, sid, "http.encode_write", marks[2],
                              marks[3], None))
            ring.leave(trace, parent)

    def _serve(self, method: str, marks: list, attrs: dict):
        parsed = urllib.parse.urlsplit(self.path)
        tail = parsed.path.rstrip("/").rsplit("/", 1)[-1]
        if tail in ("_search", "_msearch"):
            attrs["route"] = tail
        params = {k: v[-1] for k, v in
                  urllib.parse.parse_qs(parsed.query,
                                        keep_blank_values=True).items()}
        length = int(self.headers.get("Content-Length") or 0)
        raw = self.rfile.read(length) if length else None
        attrs["request_bytes"] = length
        body = None
        if raw:
            # Content-Type negotiation (libs/x-content XContentType
            # analog): JSON / CBOR / YAML bodies all decode to the same
            # in-process dicts
            from opensearch_tpu.common import xcontent
            ctype = self.headers.get("Content-Type")
            if ctype and xcontent.media_type(ctype) is None:
                # declared but unrecognized media type: reject up front
                # (RestController.dispatchRequest's 406) — decode_body
                # would "fail open" to a None body and the raw binary
                # would fall through into the NDJSON bulk parser
                payload = json.dumps({
                    "error": {
                        "type": "not_acceptable_exception",
                        "reason": f"Content-Type header [{ctype}] is not "
                                  f"supported",
                    },
                    "status": 406,
                }).encode("utf-8")
                attrs["status"] = 406
                attrs["response_bytes"] = len(payload)
                self.send_response(406)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                if method != "HEAD":
                    self.wfile.write(payload)
                return
            try:
                if (xcontent.media_type(ctype) == xcontent.CBOR
                        and parsed.path.rstrip("/").endswith("_bulk")):
                    # bulk bodies are a self-delimiting CBOR value
                    # stream; re-frame as NDJSON for the shared parser
                    # (binary values render as base64, like the
                    # reference's JSON view of binary fields)
                    import base64
                    raw = b"\n".join(
                        json.dumps(v, default=lambda b:
                                   base64.b64encode(bytes(b)).decode()
                                   if isinstance(b, (bytes, bytearray))
                                   else str(b)).encode("utf-8")
                        for v in xcontent.cbor_loads_stream(raw)) + b"\n"
                else:
                    body = xcontent.decode_body(raw, ctype)
            except Exception:
                # undecodable body: surface a request-format error, not
                # raw binary into the NDJSON parser (which would 500)
                body = None
                raw = None
        marks.append(time.monotonic_ns())
        resp = self.node.handle(method, parsed.path, params=params,
                                body=body, raw_body=raw,
                                headers=dict(self.headers.items()))
        marks.append(time.monotonic_ns())
        attrs["status"] = resp.status
        content_type = resp.content_type
        if content_type == "application/json":
            from opensearch_tpu.common import xcontent
            accept = self.headers.get("Accept")
            if xcontent.media_type(accept) in (xcontent.CBOR,
                                               xcontent.YAML):
                payload, content_type = xcontent.encode_body(
                    json.loads(resp.json()), accept)
            else:
                payload = resp.json().encode("utf-8")
        else:
            payload = (resp.body or "").encode("utf-8")
        self.send_response(resp.status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(payload)))
        for name, value in getattr(resp, "headers", {}).items():
            self.send_header(name, value)
        self.end_headers()
        if method != "HEAD":
            self.wfile.write(payload)
        attrs["response_bytes"] = len(payload)
        marks.append(time.monotonic_ns())

    def do_GET(self):
        self._do("GET")

    def do_POST(self):
        self._do("POST")

    def do_PUT(self):
        self._do("PUT")

    def do_DELETE(self):
        self._do("DELETE")

    def do_HEAD(self):
        self._do("HEAD")

    def log_message(self, fmt, *args):  # quiet; the reference logs to file
        pass


class HttpServer:
    """REST port 9200 analog. start() binds; close() shuts down."""

    def __init__(self, node: Node, host: str = "127.0.0.1", port: int = 9200,
                 security=None):
        handler = type("BoundHandler", (_Handler,), {"node": node})
        if security is not None and security.http_tls:
            # TLS on the REST port (reference: the security plugin's
            # http.ssl). The LISTENING socket stays plaintext; each
            # accepted connection wraps with do_handshake_on_connect=False
            # so the handshake happens lazily on first read INSIDE the
            # per-request thread — wrapping the listener would run the
            # handshake on the accept thread, letting one stalled client
            # block the whole REST endpoint.
            sec = security

            class _TlsServer(ThreadingHTTPServer):
                def get_request(self):
                    sock, addr = self.socket.accept()
                    sock.settimeout(30)
                    ctx = sec._http_server
                    return (ctx.wrap_socket(
                        sock, server_side=True,
                        do_handshake_on_connect=False), addr)
            self.server = _TlsServer((host, port), handler)
        else:
            self.server = ThreadingHTTPServer((host, port), handler)
        self.host = self.server.server_address[0]
        self.port = self.server.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start(self):
        self._thread = threading.Thread(target=self.server.serve_forever,
                                        name="http-server", daemon=True)
        self._thread.start()
        return self

    def close(self):
        self.server.shutdown()
        self.server.server_close()
        if self._thread:
            self._thread.join(timeout=5)


def main():  # pragma: no cover - kept for back-compat; launcher supersedes
    """Translates the legacy --port/--host/--data-path flags into launcher
    settings and delegates, so there is exactly one entry-point behavior."""
    import argparse
    p = argparse.ArgumentParser(description="opensearch-tpu node")
    p.add_argument("--port", type=int, default=9200)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--data-path", default=None)
    # launcher-native flags (-c/-E/...) pass through untouched
    args, passthrough = p.parse_known_args()
    overrides = [f"http.port={args.port}", f"http.host={args.host}"]
    if args.data_path:
        overrides.append(f"path.data={args.data_path}")
    from opensearch_tpu.launcher import main as launcher_main
    # legacy-flag translations FIRST: apply_overrides is last-wins, so an
    # explicit passthrough -E must beat the argparse defaults
    raise SystemExit(launcher_main(
        [arg for o in overrides for arg in ("-E", o)] + passthrough))


if __name__ == "__main__":  # pragma: no cover
    main()
