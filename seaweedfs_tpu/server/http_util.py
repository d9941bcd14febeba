"""Tiny stdlib HTTP server framework + client helpers.

Single dependency-free layer used by every server: prefix/exact routing on
ThreadingHTTPServer, JSON responses, multipart/form-data parsing (the
reference's upload format), and urllib-based client calls.
"""

from __future__ import annotations

import json
import os
import re
import socket
import threading
from ..util.locks import make_lock
import time
import urllib.error
import urllib.parse
import urllib.request
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, List, Optional, Tuple

from ..util import config, tracing


class HttpError(Exception):
    def __init__(self, status: int, message: str = ""):
        super().__init__(message)
        self.status = status
        self.message = message


class Request:
    def __init__(self, handler: BaseHTTPRequestHandler):
        self.handler = handler
        parsed = urllib.parse.urlparse(handler.path)
        self.path = parsed.path
        self.raw_query = parsed.query
        self.query: Dict[str, str] = {
            k: v[0] for k, v in
            urllib.parse.parse_qs(parsed.query, keep_blank_values=True).items()}
        self.method = handler.command
        self.headers = handler.headers
        self._body: Optional[bytes] = None

    @property
    def body(self) -> bytes:
        if self._body is None:
            if self._chunked():
                self._body = self._read_chunked()
                return self._body
            try:
                length = int(self.headers.get("Content-Length") or 0)
            except ValueError:
                length = -1
            if length < 0:
                # malformed/negative: framing is unknowable — refuse
                # and sever rather than reading until EOF
                self.handler.close_connection = True
                self._body = b""
                raise HttpError(400, "bad Content-Length header")
            self._body = self.handler.rfile.read(length) if length else b""
        return self._body

    def _chunked(self) -> bool:
        return "chunked" in \
            (self.headers.get("Transfer-Encoding") or "").lower()

    def _next_chunk_size(self) -> int:
        """The size line of the next chunk of a chunked body (the
        framing post_chunked emits); at the last chunk, its trailers
        too. Any framing violation severs the connection:
        resynchronizing a keep-alive stream after a bad chunk header is
        not possible."""
        rfile = self.handler.rfile
        line = rfile.readline(1 << 16)
        if not line or not line.endswith(b"\n"):
            self.handler.close_connection = True
            raise HttpError(400, "truncated chunked body")
        size_s = line.split(b";", 1)[0].strip()
        try:
            size = int(size_s, 16)
        except ValueError:
            self.handler.close_connection = True
            raise HttpError(400, "bad chunk size") from None
        if size == 0:
            # consume optional trailers up to the blank line
            while True:
                t = rfile.readline(1 << 16)
                if t in (b"\r\n", b"\n", b""):
                    break
        return size

    def _read_chunked(self) -> bytes:
        """Decode a chunked transfer-encoded body whole (streaming
        uploads whose size isn't known — or not yet complete — when
        the request line goes out)."""
        rfile = self.handler.rfile
        out: List[bytes] = []
        while True:
            size = self._next_chunk_size()
            if size == 0:
                return b"".join(out)
            data = rfile.read(size)
            if len(data) != size:
                self.handler.close_connection = True
                raise HttpError(400, "truncated chunk")
            out.append(data)
            rfile.read(2)  # chunk-terminating CRLF

    def body_pieces(self, buf: memoryview):
        """The body piece by piece, for a handler that moves it on
        without ever holding it: each piece is a view of ``buf`` (the
        caller's, reused) filled straight from the connection, good
        until the next is asked for. Both framings. A body that ends
        short raises HttpError 400 after the pieces that did arrive,
        and a generator left before its end leaves unread bytes in the
        socket: either way the connection is closed after the
        response. ``.body`` is empty afterwards."""
        if self._body is not None:
            raise RuntimeError("request body already read")
        self._body = b""
        handler = self.handler
        chunked = self._chunked()
        left = 0
        if not chunked:
            try:
                left = int(self.headers.get("Content-Length") or 0)
            except ValueError:
                left = -1
            if left < 0:
                handler.close_connection = True
                raise HttpError(400, "bad Content-Length header")
        keep, handler.close_connection = handler.close_connection, True
        rfile = handler.rfile
        while True:
            if chunked:
                left = self._next_chunk_size()
                if left == 0:
                    break
            while left:
                piece = buf[:min(left, len(buf))]
                # what the reader holds, then socket -> piece directly
                if rfile.readinto(piece) != len(piece):
                    raise HttpError(400, "truncated body")
                left -= len(piece)
                yield piece
            if not chunked:
                break
            rfile.read(2)  # chunk-terminating CRLF
        handler.close_connection = keep

    def drain(self, cap: int = 4 << 20):
        """Discard any unread request body. Keep-alive framing depends
        on this: a handler that never touches .body would otherwise
        leave the payload in the socket, where it prepends itself to
        the next request line on the reused connection. Beyond ``cap``
        the connection is closed instead — reading a rejected
        volume-sized upload to completion would stall the thread for
        the whole transfer (Go's http.Server draws the same line)."""
        if self._body is not None:
            return
        if self._chunked():
            # unread chunked body: total size is unknowable up front, so
            # sever instead of decoding a possibly volume-sized stream
            self.handler.close_connection = True
            self._body = b""
            return
        try:
            left = int(self.headers.get("Content-Length") or 0)
        except ValueError:
            # malformed header: framing is unknowable — sever instead
            # of masking the handler's response with a late error
            self.handler.close_connection = True
            self._body = b""
            return
        if left > cap:
            self.handler.close_connection = True
            self._body = b""
            return
        while left > 0:
            chunk = self.handler.rfile.read(min(left, 1 << 20))
            if not chunk:
                break
            left -= len(chunk)
        self._body = b""

    def json(self) -> dict:
        if not self.body:
            return {}
        return json.loads(self.body)

    def multipart_file(self) -> Optional[Tuple[str, str, bytes]]:
        """Parse the first file part of a multipart/form-data body.
        Returns (filename, content_type, data) or None."""
        ctype = self.headers.get("Content-Type", "")
        if not ctype.startswith("multipart/form-data"):
            return None
        m = re.search(r'boundary="?([^";]+)"?', ctype)
        if not m:
            return None
        boundary = m.group(1).encode()
        parts = self.body.split(b"--" + boundary)
        for part in parts:
            # each inner part is b"\r\n<headers>\r\n\r\n<data>\r\n";
            # strip exactly one CRLF per side — data may itself begin or
            # end with newline bytes that must survive
            if part.startswith(b"\r\n"):
                part = part[2:]
            if part.endswith(b"\r\n"):
                part = part[:-2]
            if not part or part in (b"--", b"--\r\n"):
                continue
            if b"\r\n\r\n" not in part:
                continue
            head, data = part.split(b"\r\n\r\n", 1)
            head_s = head.decode("utf-8", "replace")
            fn = re.search(r'filename="((?:[^"\\]|\\.)*)"', head_s)
            ct = re.search(r"Content-Type:\s*([^\r\n]+)", head_s, re.I)
            if fn is not None:
                name = fn.group(1).replace('\\"', '"') \
                    .replace("\\\\", "\\")
                return (name, ct.group(1).strip() if ct else "",
                        data)
        return None

    def upload_payload(self) -> Tuple[str, str, bytes]:
        """File data from multipart or raw body (reference accepts both)."""
        mp = self.multipart_file()
        if mp is not None:
            return mp
        return ("", self.headers.get("Content-Type", ""), self.body)


Route = Tuple[str, str, bool, Callable]


def traces_handler(req: Request) -> dict:
    """JSON view of the in-process trace ring, shared by every server
    role: ``/admin/traces?n=20`` for the newest traces, or
    ``/admin/traces?trace=<id>`` for one trace's spans."""
    tid = req.query.get("trace")
    if tid:
        return {"trace_id": tid, "spans": tracing.RING.get(tid),
                "dropped_spans": tracing.RING.dropped_of(tid)}
    n = int(req.query.get("n", "20"))
    return {"traces": tracing.RING.recent(n),
            "dropped_spans": tracing.RING.dropped}


def traces_export_handler(req: Request) -> dict:
    """Chrome trace-event JSON for one trace from this node's ring
    (``/admin/traces/export?trace=<id>``) — loadable in Perfetto as-is,
    and carrying enough in event args for shell ``trace.export`` to
    merge several nodes' exports into one skew-normalized timeline."""
    from ..util import trace_export
    tid = req.query.get("trace")
    if not tid:
        raise HttpError(400, "trace query parameter required")
    return trace_export.chrome_trace_events(tracing.RING.get(tid))


# one profile at a time per process — concurrent samplers would double
# the GIL-held stack-walk overhead and interleave their sample counts
_PROFILE_LOCK = make_lock("http_util._profile_lock")


def profile_handler(req: Request) -> "Response":
    """On-demand all-thread sampling profile, shared by every server
    role: ``POST /admin/profile?seconds=N`` samples for N seconds
    (clamped to SW_PROFILE_MAX_S) and returns collapsed stacks as
    text/plain — the folded format flamegraph.pl and speedscope ingest.
    A second request while one is running gets 409 instead of stacking
    sampler threads."""
    from ..util.profiling import SamplingProfiler
    try:
        seconds = float(req.query.get("seconds", "2"))
    except ValueError:
        raise HttpError(400, "seconds must be a number")
    if seconds <= 0:
        raise HttpError(400, "seconds must be > 0")
    seconds = min(seconds, config.env_float("SW_PROFILE_MAX_S"))
    if not _PROFILE_LOCK.acquire(blocking=False):
        raise HttpError(409, "a profile is already running")
    try:
        folded = SamplingProfiler.run_for(seconds)
    finally:
        _PROFILE_LOCK.release()
    return Response(folded.encode("utf-8"), 200,
                    "text/plain; charset=utf-8")


def process_memory_stats() -> dict:
    """Peak RSS of this process (reference statsMemoryHandler).
    ru_maxrss is kilobytes on Linux but BYTES on macOS/BSD."""
    import resource
    import sys
    ru = resource.getrusage(resource.RUSAGE_SELF)
    kb = ru.ru_maxrss // 1024 if sys.platform == "darwin" \
        else ru.ru_maxrss
    return {"maxrss_kb": kb}


class Router:
    def __init__(self):
        self.routes: List[Route] = []
        self.fallback: Optional[Callable] = None
        # runs before every handler (guard checks); may raise HttpError
        self.before: Optional[Callable] = None
        # observe(op_label, seconds, ok) after every request — the
        # servers plug their metric registries in here
        self.observe: Optional[Callable] = None
        # "host:port" of the owning server, set once its port is known;
        # stamped onto every server span so a merged trace export can
        # attribute spans to nodes even when in-process servers share
        # one trace ring
        self.node: Optional[str] = None

    def add(self, method: str, path: str, fn: Callable,
            prefix: bool = False):
        self.routes.append((method, path, prefix, fn))

    def set_fallback(self, fn: Callable):
        self.fallback = fn

    def dispatch(self, req: Request):
        import time as _time
        # continue a remote trace if the caller sent a traceparent; the
        # span becomes the handler thread's current span, so spans made
        # inside the handler (EC phases, peer fetches) link to it
        srv_span = tracing.start_span(
            f"{req.method} {req.path.split('?')[0]}",
            traceparent=req.headers.get(tracing.TRACEPARENT_HEADER))
        if self.node:
            srv_span.tags.setdefault("node", self.node)
        t0 = _time.monotonic()
        label = None
        try:
            label, fn = self._route(req)
            srv_span.name = label
            out = fn(req)
            if self.observe is not None:
                self.observe(label, _time.monotonic() - t0, True)
            return out
        except Exception as e:
            srv_span.tags.setdefault("error", type(e).__name__)
            if self.observe is not None:
                # label stays low-cardinality: the raw path would mint a
                # new Prometheus series per fid/404 probe
                self.observe(label or f"{req.method} unrouted",
                             _time.monotonic() - t0, False)
            raise
        finally:
            tracing.finish_span(srv_span)

    def _dispatch(self, req: Request):
        label, fn = self._route(req)
        return fn(req)

    def _route(self, req: Request):
        """(metric label, handler) for a request; raises 404."""
        if self.before is not None:
            self.before(req)
        for method, path, prefix, fn in self.routes:
            if method != "*" and method != req.method:
                continue
            if (prefix and req.path.startswith(path)) or req.path == path:
                return f"{method} {path}", fn
        if self.fallback is not None:
            return f"{req.method} data", self.fallback
        raise HttpError(404, f"no route for {req.method} {req.path}")


def _make_handler(router: Router):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        # response headers and small bodies go out in separate writes;
        # without NODELAY, Nagle holds the second write hostage to the
        # peer's delayed ACK (millisecond-scale stalls per request)
        disable_nagle_algorithm = True
        # reap idle keep-alive connections: each one pins a handler
        # thread, and pooled clients keep up to 32 per peer open.
        # Applies to socket reads only — a long-poll that WAITS before
        # responding is unaffected; only >75s gaps mid-read close
        timeout = 75

        def log_message(self, fmt, *args):  # quiet
            pass

        def _run(self):
            req = Request(self)
            try:
                try:
                    result = router.dispatch(req)
                finally:
                    req.drain()
            except HttpError as e:
                self._send_json({"error": e.message or str(e)}, e.status)
                return
            except BrokenPipeError:
                return
            except Exception as e:  # noqa: BLE001
                self._send_json({"error": f"{type(e).__name__}: {e}"}, 500)
                return
            if result is None:
                self._send_json({}, 200)
            elif isinstance(result, Response):
                result.send(self)
            else:
                self._send_json(result, 200)

        def _send_json(self, obj, status: int):
            data = json.dumps(obj).encode()
            try:
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                if self.close_connection:
                    # a body left unread: tell a client that keeps its
                    # connection not to send the next request down it
                    self.send_header("Connection", "close")
                self.end_headers()
                self.wfile.write(data)
            except (BrokenPipeError, ConnectionResetError):
                pass

        do_GET = do_POST = do_PUT = do_DELETE = do_HEAD = _run
        # WebDAV verbs (reference weed/server/webdav_server.go uses
        # golang.org/x/net/webdav which handles the same set)
        do_OPTIONS = do_PROPFIND = do_PROPPATCH = do_MKCOL = _run
        do_MOVE = do_COPY = do_LOCK = do_UNLOCK = _run

    return Handler


class Response:
    """Non-JSON response (bytes, custom status/headers).

    content_length overrides the advertised Content-Length — a HEAD
    response must advertise the size a GET would return while sending no
    body (HTTP/1.1 semantics; boto3 and rclone size objects this way)."""

    def __init__(self, body: bytes = b"", status: int = 200,
                 content_type: str = "application/octet-stream",
                 headers: Optional[dict] = None,
                 content_length: Optional[int] = None,
                 body_path: Optional[str] = None,
                 body_range: Optional[tuple] = None):
        self.body = body
        self.status = status
        self.content_type = content_type
        self.headers = headers or {}
        self.content_length = content_length
        # streaming variant: serve (offset, size) of a file without
        # buffering it — bulk pulls (.dat tier/backup) are volume-sized
        self.body_path = body_path
        self.body_range = body_range

    def send(self, handler: BaseHTTPRequestHandler):
        src = None
        if self.body_path is not None:
            # open + stat BEFORE any header goes out: a vanished or
            # shrunken file (compaction / tier-upload race) must become
            # a clean error response, and the advertised Content-Length
            # must be bytes the stream can actually deliver
            try:
                src = open(self.body_path, "rb")
                file_size = os.fstat(src.fileno()).st_size
            except OSError as e:
                if src is not None:
                    src.close()
                handler.send_error(404, str(e))
                return
            off, size = self.body_range or (0, file_size)
            off = min(off, file_size)
            size = min(size, file_size - off)
            length = size
        else:
            length = self.content_length if self.content_length is not None \
                else len(self.body)
        try:
            handler.send_response(self.status)
            handler.send_header("Content-Type", self.content_type)
            handler.send_header("Content-Length", str(length))
            for k, v in self.headers.items():
                handler.send_header(k, v)
            handler.end_headers()
            if handler.command == "HEAD":
                return
            if src is not None:
                src.seek(off)
                left = size
                while left > 0:
                    chunk = src.read(min(1 << 20, left))
                    if not chunk:
                        break
                    handler.wfile.write(chunk)
                    left -= len(chunk)
            else:
                handler.wfile.write(self.body)
        except (BrokenPipeError, ConnectionResetError, OSError):
            pass
        finally:
            if src is not None:
                src.close()


# -- transport security ------------------------------------------------------
# Reference weed/security/tls.go: optional TLS on every surface. One
# process-wide configuration (cert/key for servers, CA for clients) so
# the hundreds of "http://{host}" call sites need no changes: when TLS
# is on, http_call/http_download upgrade the scheme, and every
# HttpServer wraps its socket. Single-scheme by design, like the
# reference's all-or-nothing grpc TLS config.
_TLS = {"cert": "", "key": "", "ca": "", "client_ctx": None,
        "server_ctx": None, "mutual": False}


def configure_tls(cert_file: str = "", key_file: str = "",
                  ca_file: str = "", mutual: bool = False):
    """Enable TLS: servers present cert/key; clients verify against ca
    (or the cert itself for self-signed deployments). A cert without a
    key (or vice versa) is refused outright — the half-configured
    alternative serves plaintext while rewriting outbound URLs to
    https, which only surfaces as baffling handshake errors later.

    ``mutual=True`` is the reference's cluster-plane posture
    (weed/security/tls.go:34-40 ``ClientAuth:
    RequireAndVerifyClientCert``): servers ask every connection for a
    CA-verified client certificate, and the cluster-internal routes
    (heartbeat, admin, raft, watch — require_client_cert call sites)
    reject connections that presented none. Public data routes
    (reads, S3, filer) stay server-TLS on the same listener, which is
    why the socket uses CERT_OPTIONAL + per-route enforcement rather
    than failing every certless handshake outright. Outbound cluster
    calls present cert/key as their client identity
    (tls.go:55-66)."""
    import ssl
    clear_conn_pool()  # drop conns from the previous config
    if bool(cert_file) != bool(key_file):
        raise ValueError("TLS needs BOTH cert and key (got only one); "
                         "pass just ca for a client-only configuration")
    if mutual and not ca_file:
        raise ValueError("mutual TLS needs a CA to verify client "
                         "certificates against")
    _TLS["cert"], _TLS["key"], _TLS["ca"] = cert_file, key_file, ca_file
    _TLS["mutual"] = bool(mutual)
    if cert_file and key_file:
        sctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        sctx.load_cert_chain(cert_file, key_file)
        if mutual:
            # OPTIONAL at the handshake, REQUIRED per-route: a client
            # cert that fails CA verification still aborts the
            # handshake; absence is tolerated here and rejected by
            # require_client_cert on internal routes
            sctx.verify_mode = ssl.CERT_OPTIONAL
            sctx.load_verify_locations(ca_file)
        _TLS["server_ctx"] = sctx
    cctx = ssl.create_default_context(cafile=ca_file or cert_file or None)
    cctx.check_hostname = False  # cluster peers are addressed by ip:port
    if cert_file and key_file:
        # cluster peers authenticate outbound calls with the same
        # keypair they serve with (reference tls.go LoadClientTLS)
        cctx.load_cert_chain(cert_file, key_file)
    _TLS["client_ctx"] = cctx


def reset_tls():
    _TLS.update({"cert": "", "key": "", "ca": "", "client_ctx": None,
                 "server_ctx": None, "mutual": False})
    clear_conn_pool()  # pooled conns carry the previous TLS context


def tls_enabled() -> bool:
    return _TLS["server_ctx"] is not None


def mtls_enabled() -> bool:
    return tls_enabled() and _TLS["mutual"]


def require_client_cert(req: "Request"):
    """Reject a cluster-internal request whose connection presented no
    CA-verified client certificate (no-op unless mutual TLS is on).
    The handshake already aborted any UNverifiable cert, so a
    non-empty peer cert here means CA-verified."""
    if not mtls_enabled():
        return
    conn = req.handler.connection
    cert = conn.getpeercert() if hasattr(conn, "getpeercert") else None
    if not cert:
        raise HttpError(
            403, "client certificate required on cluster-internal "
                 "routes")


def _client_url(url: str) -> str:
    if _TLS["client_ctx"] is not None and url.startswith("http://"):
        return "https://" + url[len("http://"):]
    return url


class _TunedHTTPServer(ThreadingHTTPServer):
    # the stdlib default backlog of 5 drops SYNs under concurrent
    # clients (each drop costs a ~200ms+ retransmit — visible as p99
    # latency spikes); the reference's Go listener uses the OS default
    # (somaxconn)
    request_queue_size = 128
    daemon_threads = True

    def __init__(self, *args, **kwargs):
        self._client_socks: set = set()
        self._conn_lock = make_lock("http_util._conn_lock")
        super().__init__(*args, **kwargs)

    # track live client sockets so stop() can sever keep-alive
    # connections — shutdown() only stops the accept loop, and pooled
    # clients would otherwise keep talking to a "stopped" server
    def get_request(self):
        sock, addr = super().get_request()
        with self._conn_lock:
            self._client_socks.add(sock)
        return sock, addr

    def shutdown_request(self, request):
        with self._conn_lock:
            self._client_socks.discard(request)
        super().shutdown_request(request)

    def close_all_connections(self):
        # shutdown ONLY — never close() a socket another thread may be
        # mid-write on: close frees the fd number, a concurrently
        # opened socket (e.g. this process's own client pool) can
        # reuse it, and the handler's buffered response bytes would
        # land inside an unrelated connection. shutdown wakes the
        # owning handler thread (EOF/EPIPE), which closes the fd
        # exactly once via shutdown_request.
        with self._conn_lock:
            socks = list(self._client_socks)
        for s in socks:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass


class HttpServer:
    def __init__(self, port: int, router: Router, host: str = "127.0.0.1"):
        self.router = router
        self.httpd = _TunedHTTPServer((host, port), _make_handler(router))
        if _TLS["server_ctx"] is not None:
            self.httpd.socket = _TLS["server_ctx"].wrap_socket(
                self.httpd.socket, server_side=True)
        self.port = self.httpd.server_address[1]
        self.host = host
        self._thread: Optional[threading.Thread] = None

    def start(self):
        self._thread = threading.Thread(target=self._serve, daemon=True,
                                        name=f"http-serve-{self.port}")
        self._thread.start()
        return self

    def _serve(self):
        # shutdown() latency is bounded by the accept-loop poll; the
        # tier-1 conftest drops SW_HTTP_POLL_S to ~20 ms so hundreds of
        # per-test server stops don't each eat the stdlib's 0.5 s
        self.httpd.serve_forever(
            poll_interval=max(0.001, config.env_float("SW_HTTP_POLL_S")))

    def stop(self):
        # shutdown() blocks on serve_forever()'s ack; if start() never ran
        # there is no loop to ack and the call would deadlock.
        if self._thread is not None:
            self.httpd.shutdown()
        self.httpd.server_close()
        self.httpd.close_all_connections()


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def parse_range(rng: str, size: int) -> Optional[Tuple[int, int]]:
    """Parse a `bytes=a-b` Range header against a `size`-byte resource.

    Returns (offset, length), or None when the header is absent or not
    a bytes range. Raises HttpError(416) for malformed or unsatisfiable
    ranges. Only the first range of a multi-range spec is honored."""
    if not rng or not rng.startswith("bytes="):
        return None
    spec = rng[6:].split(",")[0]
    s, _, e = spec.partition("-")
    try:
        if s == "":
            offset = max(size - int(e), 0)
            length = size - offset
        else:
            offset = int(s)
            end = min(int(e), size - 1) if e else size - 1
            length = end - offset + 1
    except ValueError:
        raise HttpError(416, f"bad range {rng}") from None
    if length < 0 or (offset >= size and size > 0):
        raise HttpError(416, f"unsatisfiable range {rng}")
    return offset, length


# -- client helpers ---------------------------------------------------------
#
# Cluster-internal calls ride a keep-alive connection pool: urllib opens
# (and tears down) a fresh TCP connection per request, which caps a
# chatty data plane at connection-churn rate (SYN/FIN per needle write,
# TIME_WAIT pileups, Nagle stalls on the two-write request pattern).
# The reference's Go http.Client pools by default; this is the same
# discipline. External endpoints (webhooks, SQS, cloud sinks) keep the
# urllib path — low-rate, and their TLS contexts differ.

import http.client as _httpc

# pool entries are (conn, parked_at) — the park time drives idle-age
# eviction: a peer's keep-alive timeout (or an LB's) closes connections
# we would otherwise only discover stale at reuse, and long-lived shells
# would pin sockets to servers they talked to once
_POOL: Dict[Tuple[str, str], List] = {}
_POOL_LOCK = make_lock("http_util._POOL_LOCK")
_POOL_MAX_PER_HOST = 32
_POOL_MAX_IDLE_ENV = "SW_HTTP_POOL_MAX_IDLE_S"
# churn counters, mirrored into /metrics (http_pool_churn_total{event=})
POOL_STATS = {"created": 0, "reused": 0, "evicted_stale": 0,
              "evicted_idle": 0, "evicted_overflow": 0}
_RETRIABLE_STALE = (_httpc.RemoteDisconnected, _httpc.BadStatusLine,
                    ConnectionResetError, BrokenPipeError)


def _pool_max_idle_s() -> float:
    return config.env_float(_POOL_MAX_IDLE_ENV)


def _pool_count(event: str, n: int = 1):
    with _POOL_LOCK:
        POOL_STATS[event] += n


def pool_stats_snapshot() -> Dict[str, int]:
    with _POOL_LOCK:
        return dict(POOL_STATS)


def _new_conn(scheme: str, netloc: str, timeout: float):
    if scheme == "https":
        return _httpc.HTTPSConnection(netloc, timeout=timeout,
                                      context=_TLS["client_ctx"])
    return _httpc.HTTPConnection(netloc, timeout=timeout)


def _sock_is_stale(sock) -> bool:
    """A pooled idle socket that polls readable has either a FIN (peer
    closed the idle connection — the common post-restart case) or
    unexpected bytes; both mean: don't reuse. One zero-timeout select."""
    import select
    try:
        r, _, _ = select.select([sock], [], [], 0)
        return bool(r)
    except (OSError, ValueError):
        return True


def _pool_get(scheme: str, netloc: str, timeout: float):
    """-> (conn, reused). New connections get TCP_NODELAY on connect.
    Pops newest-first (LIFO keeps hot sockets hot) and evicts entries
    past the idle-age cap or failing the readable-peek stale check."""
    max_idle = _pool_max_idle_s()
    while True:
        with _POOL_LOCK:
            stack = _POOL.get((scheme, netloc))
            entry = stack.pop() if stack else None
        if entry is None:
            _pool_count("created")
            return _new_conn(scheme, netloc, timeout), False
        conn, parked_at = entry
        if max_idle > 0 and time.monotonic() - parked_at > max_idle:
            conn.close()
            _pool_count("evicted_idle")
            continue
        if conn.sock is not None and _sock_is_stale(conn.sock):
            conn.close()
            _pool_count("evicted_stale")
            continue
        if conn.sock is not None:
            conn.sock.settimeout(timeout)
        _pool_count("reused")
        return conn, True


def _pool_put(scheme: str, netloc: str, conn):
    """Park a connection. Also sweeps aged-out entries from the bottom
    of the stack — LIFO reuse means the oldest entries are never popped
    under steady load, so without the sweep they'd pin sockets
    forever."""
    now = time.monotonic()
    max_idle = _pool_max_idle_s()
    aged = []
    overflow = None
    with _POOL_LOCK:
        stack = _POOL.setdefault((scheme, netloc), [])
        if max_idle > 0:
            while stack and now - stack[0][1] > max_idle:
                aged.append(stack.pop(0)[0])
        if len(stack) < _POOL_MAX_PER_HOST:
            stack.append((conn, now))
        else:
            overflow = conn
        POOL_STATS["evicted_idle"] += len(aged)
        if overflow is not None:
            POOL_STATS["evicted_overflow"] += 1
    for c in aged:
        c.close()
    if overflow is not None:
        overflow.close()


def clear_conn_pool():
    """Drop every pooled connection (tests; TLS reconfiguration)."""
    with _POOL_LOCK:
        for stack in _POOL.values():
            for conn, _ in stack:
                conn.close()
        _POOL.clear()


def _nodelay(conn):
    if conn.sock is not None:
        try:
            conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY,
                                 1)
        except OSError:
            pass


def _traced_headers(headers: Optional[dict]) -> dict:
    """Inject the W3C ``traceparent`` on cluster-internal calls so the
    receiving server's span continues this caller's trace (no-op when
    the caller already set one, e.g. a redirect re-entry)."""
    h = dict(headers) if headers else {}
    if tracing.TRACEPARENT_HEADER not in h:
        h[tracing.TRACEPARENT_HEADER] = tracing.outbound_traceparent()
    return h


def _read_body_into(resp, into) -> int:
    """A response's body read off the socket into ``into``, a writable
    buffer the caller expects it to fill: no ``bytes`` of the body's
    size is made. Returns the body's length — less than the buffer's
    where the body came short, more where it ran past it (the excess is
    read and dropped, so that the connection can be kept)."""
    view = memoryview(into).cast("B")
    got = 0
    while got < len(view):
        n = resp.readinto(view[got:])
        if not n:
            break
        got += n
    return got + len(resp.read())


def _pooled_call(method: str, url: str, body, headers: dict,
                 timeout: float, max_redirects: int = 5,
                 want_headers: bool = False,
                 encode_chunked: bool = False, into=None):
    """``into``: a 2xx body goes there (``_read_body_into``) and the
    call returns its length in place of the body."""
    headers = _traced_headers(headers)
    parsed = urllib.parse.urlsplit(url)
    netloc, scheme = parsed.netloc, parsed.scheme
    target = parsed.path or "/"
    if parsed.query:
        target += "?" + parsed.query
    # A stale keep-alive connection fails at send/first-byte; retry once
    # on a fresh connection — but only for idempotent methods with a
    # replayable body. A POST whose server died between processing and
    # responding must NOT silently re-execute (double assign/publish) —
    # Go's http.Client draws the same line. A chunked body from an
    # iterator cannot be re-sent at all, so it always goes out on a
    # FRESH connection. (A caller that still holds its body's buffers
    # and owns the retry keeps a connection of its own: KeptConnection.)
    replayable = not encode_chunked and \
        (body is None or isinstance(body, (bytes, bytearray)))
    idempotent = method in ("GET", "HEAD", "DELETE", "PUT")
    attempts = 2 if (replayable and idempotent) else 1
    for attempt in range(attempts):
        if replayable:
            conn, reused = _pool_get(scheme, netloc, timeout)
        else:
            conn, reused = _new_conn(scheme, netloc, timeout), False
        try:
            if conn.sock is None:
                conn.connect()
                _nodelay(conn)
            conn.request(method, target, body=body, headers=headers,
                         encode_chunked=encode_chunked)
            resp = conn.getresponse()
            if into is not None and 200 <= resp.status < 300:
                data = _read_body_into(resp, into)
            else:
                data = resp.read()
        except _RETRIABLE_STALE:
            conn.close()
            if reused and attempt + 1 < attempts:
                continue
            raise
        except Exception:
            conn.close()
            raise
        if resp.will_close:
            conn.close()
        else:
            _pool_put(scheme, netloc, conn)
        # 307/308 preserve method+body by definition — the native write
        # plane answers off-fast-path POSTs this way (redirect to the
        # owning Python server); other 3xx follow only for GET/HEAD
        follow = method in ("GET", "HEAD") or \
            (resp.status in (307, 308) and replayable)
        if 300 <= resp.status < 400 and resp.getheader("Location") \
                and follow and max_redirects > 0:
            loc = urllib.parse.urljoin(url, resp.getheader("Location"))
            # redirect targets are emitted as plain http (volume read
            # redirects) — re-apply the cluster TLS scheme rewrite
            return _pooled_call(method, _client_url(loc), body, headers,
                                timeout, max_redirects - 1,
                                want_headers, into=into)
        if resp.status >= 400:
            detail = data.decode("utf-8", "replace")[:500]
            raise HttpError(resp.status, f"{method} {url}: {detail}")
        if want_headers:
            return data, dict(resp.getheaders())
        return data
    raise HttpError(503, f"{method} {url}: retries exhausted")


def http_get_with_headers(url: str, timeout: float = 30.0,
                          headers: Optional[dict] = None):
    """Cluster GET returning (body, response headers) — for callers
    that need metadata the body doesn't carry (stored filename in
    Content-Disposition, etags, Content-Range on ranged reads)."""
    url = _client_url(url)
    try:
        return _pooled_call("GET", url, None, headers or {}, timeout,
                            want_headers=True)
    except HttpError:
        raise
    except (OSError, _httpc.HTTPException) as e:
        raise HttpError(503, f"GET {url}: {e}") from None


def http_call(method: str, url: str, body: bytes = None,
              headers: dict = None, timeout: float = 30.0,
              external: bool = False) -> bytes:
    """``external=True`` marks a non-cluster endpoint (webhooks, third
    parties): the URL keeps its scheme and https uses the default
    verified context — the cluster TLS rewrite must not break plain-HTTP
    externals nor weaken hostname checks on real ones. Cluster calls go
    through the keep-alive pool."""
    if not external:
        url = _client_url(url)
        try:
            return _pooled_call(method, url, body, headers or {},
                                timeout)
        except HttpError:
            raise
        except (OSError, _httpc.HTTPException) as e:
            raise HttpError(503, f"{method} {url}: {e}") from None
    req = urllib.request.Request(url, data=body, method=method,
                                 headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=timeout,
                                    context=None) as resp:
            return resp.read()
    except urllib.error.HTTPError as e:
        detail = e.read().decode("utf-8", "replace")[:500]
        raise HttpError(e.code, f"{method} {url}: {detail}") from None
    except (urllib.error.URLError, socket.timeout, ConnectionError) as e:
        raise HttpError(503, f"{method} {url}: {e}") from None


def http_read_into(method: str, url: str, into, headers: dict = None,
                   timeout: float = 30.0) -> int:
    """A cluster call whose reply body is read straight into ``into``
    (a writable buffer: a row of a gather's slab) through the
    keep-alive pool, with http_call's retry on a stale connection,
    status check and errors. Returns the body's length, which the
    caller holds against what it asked for."""
    url = _client_url(url)
    try:
        return _pooled_call(method, url, None, headers or {}, timeout,
                            into=into)
    except HttpError:
        raise
    except (OSError, _httpc.HTTPException) as e:
        raise HttpError(503, f"{method} {url}: {e}") from None


def http_download(url: str, path: str, timeout: float = 600.0) -> int:
    """Stream a GET response straight to a file (volume-sized pulls must
    not transit RAM). Returns bytes written."""
    url = _client_url(url)
    req = urllib.request.Request(url, method="GET",
                                 headers=_traced_headers(None))
    try:
        with urllib.request.urlopen(req, timeout=timeout,
                                    context=_TLS["client_ctx"]) as resp, \
                open(path, "wb") as out:
            total = 0
            while True:
                chunk = resp.read(1 << 20)
                if not chunk:
                    return total
                out.write(chunk)
                total += len(chunk)
    except urllib.error.HTTPError as e:
        detail = e.read().decode("utf-8", "replace")[:500]
        raise HttpError(e.code, f"GET {url}: {detail}") from None
    except (urllib.error.URLError, socket.timeout, ConnectionError) as e:
        raise HttpError(503, f"GET {url}: {e}") from None


def get_json(url: str, timeout: float = 30.0) -> dict:
    return json.loads(http_call("GET", url, timeout=timeout) or b"{}")


def post_json(url: str, obj=None, timeout: float = 30.0) -> dict:
    body = json.dumps(obj or {}).encode()
    out = http_call("POST", url, body,
                    {"Content-Type": "application/json"}, timeout)
    return json.loads(out or b"{}")


def post_chunked(url: str, chunks, headers: Optional[dict] = None,
                 timeout: float = 300.0) -> bytes:
    """POST an iterable of byte chunks with chunked transfer-encoding —
    the body can start flowing before its total size is known. Chunked
    bodies are not replayable, so the call always goes out on a fresh
    connection and is never retried here. (The EC spread knows a run's
    length and holds its buffers, so it sends through a KeptConnection;
    its holders take this framing too, from an older sender.)"""
    url = _client_url(url)
    h = dict(headers or {})
    h["Transfer-Encoding"] = "chunked"
    try:
        return _pooled_call("POST", url, iter(chunks), h, timeout,
                            encode_chunked=True)
    except HttpError:
        raise
    except (OSError, _httpc.HTTPException) as e:
        raise HttpError(503, f"POST {url}: {e}") from None


class KeptConnection:
    """One connection to one cluster peer that its owner keeps open
    across POSTs whose bodies are buffers the owner still holds (the EC
    spread: one a push lane and holder, ec/transport.py). A body goes
    out under a Content-Length as the buffers it is made of — row views
    of a slab are written to the socket as they lie, never joined or
    framed into new bytes — and is therefore replayable: any failure
    closes the connection and raises, and the owner's retry opens the
    next. One thread at a time."""

    def __init__(self, netloc: str, timeout: float = 300.0):
        self.netloc = netloc
        self.timeout = timeout
        self.connects = 0       # connections opened over its life
        self._conn = None

    def _connect(self):
        scheme = "https" if _TLS["client_ctx"] is not None else "http"
        conn = _new_conn(scheme, self.netloc, self.timeout)
        conn.connect()
        _nodelay(conn)
        self.connects += 1
        _pool_count("created")
        return conn

    def post_parts(self, target: str, parts,
                   headers: Optional[dict] = None) -> bytes:
        """POST ``target`` (path + query) with the concatenation of the
        buffers ``parts`` as body; the response body, HttpError on a
        status of 400 and above."""
        try:
            if self._conn is None:
                self._conn = self._connect()
            conn = self._conn
            conn.putrequest("POST", target, skip_accept_encoding=True)
            for k, v in (headers or {}).items():
                conn.putheader(k, v)
            conn.putheader("Content-Length",
                           str(sum(len(p) for p in parts)))
            conn.endheaders()
            for p in parts:
                conn.send(p)
            resp = conn.getresponse()
            data = resp.read()
        except (OSError, _httpc.HTTPException) as e:
            self.close()
            raise HttpError(
                503, f"POST http://{self.netloc}{target}: {e}") from None
        except BaseException:
            self.close()
            raise
        if resp.will_close:
            self.close()
        if resp.status >= 400:
            detail = data.decode("utf-8", "replace")[:500]
            raise HttpError(
                resp.status,
                f"POST http://{self.netloc}{target}: {detail}")
        return data

    def close(self):
        conn, self._conn = self._conn, None
        if conn is not None:
            conn.close()


def _quote_name(name: str) -> str:
    """Escape a filename for a quoted-string header parameter."""
    return name.replace("\\", "\\\\").replace('"', '\\"')


def post_multipart(url: str, filename: str, data: bytes,
                   content_type: str = "application/octet-stream",
                   timeout: float = 60.0,
                   headers: dict = None) -> dict:
    boundary = uuid.uuid4().hex
    body = (f"--{boundary}\r\n"
            f'Content-Disposition: form-data; name="file"; '
            f'filename="{_quote_name(filename or "file")}"\r\n'
            f"Content-Type: {content_type}\r\n\r\n").encode() \
        + data + f"\r\n--{boundary}--\r\n".encode()
    all_headers = {"Content-Type":
                   f"multipart/form-data; boundary={boundary}"}
    all_headers.update(headers or {})
    out = http_call("POST", url, body, all_headers, timeout)
    return json.loads(out or b"{}")


class _ChainReader:
    """read()-able concatenation of byte segments and file objects with
    a known total length — streams a multipart body without building it."""

    def __init__(self, parts):
        self.parts = []
        self.len = 0
        import io as _io
        for p in parts:
            if isinstance(p, bytes):
                self.parts.append(_io.BytesIO(p))
                self.len += len(p)
            else:
                f, size = p
                self.parts.append(f)
                self.len += size
        self.i = 0

    def __len__(self):
        return self.len

    def read(self, n: int = -1) -> bytes:
        out = b""
        while self.i < len(self.parts):
            chunk = self.parts[self.i].read(n if n >= 0 else (1 << 20))
            if chunk:
                out += chunk
                if n >= 0:
                    return out
            else:
                self.i += 1
        return out


def post_multipart_file(url: str, filename: str, fileobj, size: int,
                        content_type: str = "application/octet-stream",
                        timeout: float = 600.0,
                        headers: dict = None) -> dict:
    """post_multipart for file-likes: the body streams, so a
    volume-sized upload never transits RAM whole."""
    boundary = uuid.uuid4().hex
    prologue = (f"--{boundary}\r\n"
                f'Content-Disposition: form-data; name="file"; '
                f'filename="{_quote_name(filename or "file")}"\r\n'
                f"Content-Type: {content_type}\r\n\r\n").encode()
    epilogue = f"\r\n--{boundary}--\r\n".encode()
    body = _ChainReader([prologue, (fileobj, size), epilogue])
    all_headers = {
        "Content-Type": f"multipart/form-data; boundary={boundary}",
        "Content-Length": str(len(body)),
    }
    all_headers.update(headers or {})
    out = http_call("POST", url, body, all_headers, timeout)
    return json.loads(out or b"{}")
