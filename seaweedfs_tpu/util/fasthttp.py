"""Minimal HTTP/1.1 data-plane machinery: a raw asyncio.Protocol server and
a keep-alive client pool.

Why this exists: the serving north star (BASELINE.json config 4 — the
reference's `weed benchmark`, README.md:483-530) is bounded by per-request
framework overhead, not by storage. The reference's data plane is Go
net/http (weed/server/volume_server_handlers_read.go); the Python-general
equivalent (aiohttp) spends ~200µs/request on routing, header objects,
multidicts and response assembly — an order of magnitude more than the
needle read itself. This module is the TPU-framework analogue of the
reference's thin handler loop: a byte-level parser feeding registered fast
handlers, with EVERY other request transparently proxied to the full
aiohttp application (which keeps the long-tail surface: UIs, pprof, tiered
reads, ranges, resizing...). One listening port, two tiers.

Design rules:
- hot handlers may return FALLBACK at any point; the raw request bytes are
  then replayed verbatim against the internal aiohttp listener, so the two
  tiers can never disagree about semantics — the fast tier only ever serves
  requests it fully understands.
- parsing is bytes-only and allocation-light: no multidicts, no URL
  objects, headers lazily split into a plain dict of lower-cased names.
- responses are assembled as one writev-style bytes join with pre-rendered
  static fragments.
"""

from __future__ import annotations

import asyncio
import time
from collections import deque
from typing import Awaitable, Callable, Optional

from . import faults, overload, tenancy, trace
from .backoff import shared_retry_budget
from .metrics import (
    REQUEST_PARSE_SECONDS,
    REQUEST_PROXIED,
    REQUEST_PROXY_SECONDS,
    RESPONSE_BUFFERED_BYTES,
    RESPONSE_BYTES,
    RESPONSE_WRITE_SECONDS,
    RESPONSE_WRITES,
)

_perf = time.perf_counter  # bound once: stamped per parsed request
_cur_tenant = tenancy.current  # bound once: read per client request

FALLBACK = object()  # sentinel: "proxy this request to the full app"
DETACHED = object()  # sentinel: "the handler will write the response itself
# (via req.transport) from a later callback" — used by batch continuations
# so N coalesced responses cost one callback, not N task resumes

_MAX_HEADER = 64 * 1024
_MAX_BODY = 256 << 20  # matches the aiohttp client_max_size

_STATUS_LINES = {
    200: b"HTTP/1.1 200 OK\r\n",
    201: b"HTTP/1.1 201 Created\r\n",
    202: b"HTTP/1.1 202 Accepted\r\n",
    204: b"HTTP/1.1 204 No Content\r\n",
    206: b"HTTP/1.1 206 Partial Content\r\n",
    304: b"HTTP/1.1 304 Not Modified\r\n",
    400: b"HTTP/1.1 400 Bad Request\r\n",
    401: b"HTTP/1.1 401 Unauthorized\r\n",
    403: b"HTTP/1.1 403 Forbidden\r\n",
    404: b"HTTP/1.1 404 Not Found\r\n",
    405: b"HTTP/1.1 405 Method Not Allowed\r\n",
    416: b"HTTP/1.1 416 Range Not Satisfiable\r\n",
    429: b"HTTP/1.1 429 Too Many Requests\r\n",
    500: b"HTTP/1.1 500 Internal Server Error\r\n",
    503: b"HTTP/1.1 503 Service Unavailable\r\n",
}


class FastRequest:
    """One parsed request. Header names are lower-case byte strings.
    `t_arrive` is the perf_counter at parse completion: the admission
    gate charges event-loop backlog (time between parse and dispatch)
    against the request's queue budget — a request that already waited
    past its class deadline is shed before doing work."""

    __slots__ = ("method", "target", "path", "query", "headers", "body", "peer",
                 "raw_head", "transport", "done", "t_arrive")

    def __init__(self, method, target, headers, body, peer, raw_head):
        self.method = method  # str: "GET"
        self.target = target  # str: "/3,0144b9f3d1?x=1" (raw)
        self.headers = headers  # dict[bytes, bytes] lower-cased names
        self.body = body  # bytes
        self.peer = peer  # str remote ip
        self.raw_head = raw_head  # bytes: request line + headers + CRLFCRLF
        q = target.find("?")
        if q < 0:
            self.path = target
            self.query = ""
        else:
            self.path = target[:q]
            self.query = target[q + 1:]


def finish_detached(req: FastRequest, response: bytes) -> None:
    """Write a DETACHED request's response and release its connection's
    request loop (see FastHTTPProtocol._run). Idempotent: a second call
    for the same request is a no-op, never a second response on the
    wire."""
    d = req.done
    if d is True or (d is not None and d is not True and d.done()):
        return
    t = req.transport
    if t is not None and not t.is_closing():
        t.write(response)
    if d is None:
        req.done = True
    else:
        d.set_result(None)


def render_response(
    status: int,
    body: bytes = b"",
    content_type: bytes = b"application/json",
    extra: bytes = b"",
    keep_alive: bool = True,
    head_only: bool = False,
) -> bytes:
    """One response byte string. `extra` is pre-rendered \r\n-terminated
    header lines."""
    return b"".join(
        (
            _STATUS_LINES.get(status) or (
                b"HTTP/1.1 %d X\r\n" % status
            ),
            b"Content-Type: ", content_type, b"\r\n",
            b"Content-Length: %d\r\n" % len(body),
            extra,
            b"Connection: keep-alive\r\n\r\n"
            if keep_alive
            else b"Connection: close\r\n\r\n",
            b"" if head_only else body,
        )
    )


Handler = Callable[[FastRequest], Awaitable[object]]


class _ReqQueue:
    """Single-producer single-consumer request queue: a deque plus one
    waiter future. asyncio.Queue's per-op loop bookkeeping (getter/putter
    deques, loop resolution, wakeup scheduling) was measurable per request
    at serving QPS rates; the protocol's strictly 1:1 shape needs none of
    it."""

    __slots__ = ("_d", "_waiter")

    def __init__(self):
        self._d: deque = deque()
        self._waiter: Optional[asyncio.Future] = None

    def put_nowait(self, item) -> None:
        self._d.append(item)
        w = self._waiter
        if w is not None and not w.done():
            w.set_result(None)

    def empty(self) -> bool:
        return not self._d

    async def get(self):
        while not self._d:
            self._waiter = asyncio.get_event_loop().create_future()
            try:
                await self._waiter
            finally:
                self._waiter = None
        return self._d.popleft()


class FastHTTPProtocol(asyncio.Protocol):
    """HTTP/1.1 server protocol: sequential requests per connection,
    Content-Length bodies (chunked uploads fall back), keep-alive."""

    def __init__(self, server: "FastHTTPServer"):
        self.server = server
        self.transport: Optional[asyncio.Transport] = None
        self.buf = bytearray()
        self.peer = ""
        self._task: Optional[asyncio.Task] = None
        self._queue: _ReqQueue = _ReqQueue()
        self._paused = False
        self._closed = False
        self._continued = False  # 100 Continue sent for the pending request
        self._processing = False  # a request's response is still pending
        self._want_continue = False  # 100 deferred until the conn is idle
        # kernel-buffer flow control (pause_writing/resume_writing): relays
        # await _drain_waiter instead of polling get_write_buffer_size()
        self._write_paused = False
        self._drain_waiter: Optional[asyncio.Future] = None
        # backpressure threshold for the CURRENT partial request: raised by
        # _try_parse once the request's frame size is known, so a request
        # whose total frame slightly exceeds _MAX_BODY (body under the cap,
        # headers on top — ADVICE r4) completes instead of deadlocking in
        # pause_reading with no resume
        self._pause_limit = _MAX_BODY
        # in-progress chunked-body decode state (pos/out/head/...): decoding
        # resumes where it left off so each data_received touches only NEW
        # bytes — a restart-from-scratch walk re-copies every prior chunk
        # and goes quadratic in body size
        self._chunked: Optional[dict] = None

    # -- transport events --
    def connection_made(self, transport):
        self.transport = transport
        transport.set_write_buffer_limits(high=1 << 20)
        peer = transport.get_extra_info("peername")
        self.peer = peer[0] if peer else ""
        self._task = asyncio.ensure_future(self._run())
        self.server._conns.add(self)

    def connection_lost(self, exc):
        self._closed = True
        self._queue.put_nowait(None)
        self.server._conns.discard(self)
        w = self._drain_waiter
        if w is not None and not w.done():
            w.set_result(None)  # waiters wake and see is_closing()
        self._drain_waiter = None
        if self._task is not None:
            self._task.cancel()

    # -- outgoing flow control (transport write-buffer watermarks) --
    def pause_writing(self):
        self._write_paused = True

    def resume_writing(self):
        self._write_paused = False
        w = self._drain_waiter
        if w is not None and not w.done():
            w.set_result(None)
        self._drain_waiter = None

    async def drain(self):
        """Wait until the transport's write buffer falls under the low
        watermark (or the connection dies — callers re-check is_closing).
        The event-driven replacement for sleep-polling
        get_write_buffer_size() in paced relays."""
        if not self._write_paused or self._closed:
            return
        w = self._drain_waiter
        if w is None or w.done():
            w = asyncio.get_event_loop().create_future()
            self._drain_waiter = w
        await w

    def data_received(self, data: bytes):
        with self.server.stages.parse():
            self.buf += data
            self._pump()
        # backpressure: stop reading while too much is queued (never on a
        # transport _fail() just closed — pause_reading would raise and
        # asyncio's fatal-error path discards the buffered 400)
        if (
            len(self.buf) > self._pause_limit
            and not self._paused
            and not self._closed
        ):
            self._paused = True
            self.transport.pause_reading()

    def _pump(self):
        """Slice complete requests out of the buffer into the queue."""
        while True:
            req = self._try_parse()
            if req is None:
                return
            self._queue.put_nowait(req)

    def _try_parse(self):
        if self._chunked is not None:
            return self._resume_chunked()
        buf = self.buf
        end = buf.find(b"\r\n\r\n")
        if end < 0:
            if len(buf) > _MAX_HEADER:
                self._fail(400)
            return None
        head = bytes(buf[: end + 4])
        try:
            line_end = head.index(b"\r\n")
            method, _, rest = head[:line_end].partition(b" ")
            target, _, _version = rest.rpartition(b" ")
            headers: dict = {}
            pos = line_end + 2
            while pos < end:
                nl = head.index(b"\r\n", pos)
                colon = head.index(b":", pos, nl)
                name = head[pos:colon].lower()
                headers[name] = head[colon + 1: nl].strip()
                pos = nl + 2
        except ValueError:
            self._fail(400)
            return None
        te = headers.get(b"transfer-encoding")
        if te is not None:
            # de-chunk Transfer-Encoding bodies (VERDICT r4 missing #1):
            # the reference's Go net/http accepts streaming uploads
            # transparently, so clients sending unknown-length bodies
            # (curl -T from a pipe, SDK streaming modes) must work here
            # too. The assembled body is handed to handlers with a
            # synthesized Content-Length head so FALLBACK replay frames
            # identically on the backend leg.
            if te.lower() != b"chunked":
                self._fail(400)  # gzip/deflate transfer codings: not spoken
                return None
            self._chunked = {
                "pos": 0,
                "out": bytearray(),
                "head": head,
                "method": method,
                "target": target,
                "headers": headers,
                "in_trailer": False,
            }
            del buf[:end + 4]  # head is captured; buf holds framing only
            return self._resume_chunked()
        try:
            clen = int(headers.get(b"content-length", b"0") or 0)
        except ValueError:
            # non-numeric Content-Length must 400, not raise out of
            # data_received and wedge the connection (ADVICE r4)
            self._fail(400)
            return None
        if clen < 0 or clen > _MAX_BODY:
            self._fail(400)
            return None
        total = end + 4 + clen
        if len(buf) < total:
            # the frame is legal but larger than what's buffered: lift the
            # backpressure threshold to the frame's own size (+ header
            # slack) so reading always continues to completion
            self._pause_limit = total + _MAX_HEADER
            if clen:
                self._maybe_send_continue(headers)
            return None
        body = bytes(buf[end + 4: total])
        del buf[:total]
        return self._finish_request(method, target, headers, body, head)

    def _finish_request(self, method, target, headers, body, head):
        """Common tail of a successful parse: reset per-request state,
        resume reading, build the FastRequest."""
        self._pause_limit = _MAX_BODY
        # next request gets its own 100 Continue
        self._continued = False
        self._want_continue = False
        if self._paused and len(self.buf) < self._pause_limit:
            self._paused = False
            self.transport.resume_reading()
        req = FastRequest(
            method.decode("latin1"),
            target.decode("latin1"),
            headers,
            body,
            self.peer,
            head,
        )
        req.transport = self.transport
        req.done = None
        req.t_arrive = _perf()
        return req

    def _resume_chunked(self):
        """Advance the in-progress chunked-body decode; None while
        incomplete. Resumes at the cached buffer position, so every body
        byte is copied exactly once no matter how many TCP segments carry
        it. On completion the request is rebuilt as if it had arrived
        Content-Length-framed: headers and raw_head drop Transfer-Encoding
        and gain the real length, so fast handlers and the FALLBACK replay
        never see chunked framing."""
        st = self._chunked
        buf = self.buf
        out = st["out"]

        def compact() -> None:
            # consumed framing bytes are dropped on every incomplete
            # return (NOT per chunk — that would re-quadratize a large
            # buffered burst), so raw buf stays ~one in-flight chunk
            # instead of shadowing the whole decoded body at 2x memory
            if st["pos"]:
                del buf[:st["pos"]]
                st["pos"] = 0

        while True:
            if st["in_trailer"]:
                # trailer section: zero or more header lines, then CRLF
                while True:
                    tnl = buf.find(b"\r\n", st["pos"])
                    if tnl < 0:
                        if len(buf) - st["pos"] > _MAX_HEADER:
                            self._fail(400)
                        else:
                            compact()
                            self._pause_limit = len(buf) + _MAX_HEADER
                        return None
                    if tnl == st["pos"]:  # blank line ends the message
                        return self._finish_chunked(tnl + 2)
                    st["pos"] = tnl + 2  # trailer line: parsed over, dropped
            nl = buf.find(b"\r\n", st["pos"])
            if nl < 0:
                # cap matches the complete-line tolerance (chunk extensions
                # are legal and can be long) so acceptance never depends on
                # TCP segmentation; Go's chunked reader allows 4096
                if len(buf) - st["pos"] > 4096:
                    self._fail(400)
                else:
                    compact()
                    self._pause_limit = len(buf) + _MAX_BODY + _MAX_HEADER
                    self._maybe_send_continue(st["headers"])
                return None
            if nl - st["pos"] > 4096:
                self._fail(400)
                return None
            token = bytes(buf[st["pos"]:nl]).split(b";")[0]
            # strict RFC 9112 HEXDIG only, no whitespace: Python's
            # int(.., 16) also accepts '0x10'/'+10'/'1_0'/' 5', and a
            # parser more liberal than the strict intermediary in front of
            # it is a smuggling seam
            if not token or any(
                c not in b"0123456789abcdefABCDEF" for c in token
            ):
                self._fail(400)
                return None
            size = int(token, 16)
            if len(out) + size > _MAX_BODY:
                self._fail(400)
                return None
            if size == 0:
                st["in_trailer"] = True
                st["pos"] = nl + 2
                continue
            cstart = nl + 2
            cend = cstart + size
            if len(buf) < cend + 2:
                # grow the backpressure window to what this chunk needs
                shift = st["pos"]
                compact()
                self._pause_limit = (cend - shift) + 2 + _MAX_HEADER
                self._maybe_send_continue(st["headers"])
                return None
            if buf[cend:cend + 2] != b"\r\n":
                self._fail(400)
                return None
            out += buf[cstart:cend]
            st["pos"] = cend + 2

    def _finish_chunked(self, total: int):
        st = self._chunked
        self._chunked = None
        body = bytes(st["out"])
        del self.buf[:total]
        headers = dict(st["headers"])
        del headers[b"transfer-encoding"]
        headers[b"content-length"] = b"%d" % len(body)
        lines = [
            ln for ln in st["head"][:-4].split(b"\r\n")
            if not ln.lower().startswith(
                (b"transfer-encoding:", b"content-length:")
            )
        ]
        lines.append(b"Content-Length: %d" % len(body))
        new_head = b"\r\n".join(lines) + b"\r\n\r\n"
        return self._finish_request(
            st["method"], st["target"], headers, body, new_head
        )

    def _maybe_send_continue(self, headers) -> None:
        """curl (and other clients) gate bodies on a 100 Continue;
        answering immediately avoids their ~1s expectation timeout. Only
        when the connection is otherwise idle — with an earlier response
        still pending, an interim 1xx now would land BEFORE that response
        and desync the client's attribution (deferred sends happen in
        _maybe_continue once the connection drains)."""
        if (
            headers.get(b"expect", b"").lower() == b"100-continue"
            and not self._continued
        ):
            if not self._processing and self._queue.empty():
                self._continued = True
                self.transport.write(b"HTTP/1.1 100 Continue\r\n\r\n")
            else:
                self._want_continue = True

    def _fail(self, status: int):
        self._chunked = None
        if self.transport is not None:
            try:
                self.transport.write(
                    render_response(status, b'{"error":"bad request"}',
                                    keep_alive=False)
                )
            except Exception:
                pass
            self.transport.close()
        self._closed = True
        self._queue.put_nowait(None)

    # -- request loop --
    def _maybe_continue(self) -> None:
        """Fire a deferred 100 Continue now that the connection drained
        (the body the client is withholding is the only way forward)."""
        if (
            self._want_continue
            and not self._continued
            and self.transport is not None
            and not self.transport.is_closing()
        ):
            self._continued = True
            self._want_continue = False
            self.transport.write(b"HTTP/1.1 100 Continue\r\n\r\n")

    async def _run(self):
        detached_prev = None  # last DETACHED request, possibly in flight
        try:
            while True:
                self._processing = False
                if self._queue.empty() and detached_prev is None:
                    self._maybe_continue()
                req = await self._queue.get()
                if req is None or self._closed:
                    return
                self._processing = True
                if detached_prev is not None:
                    # a previous request's response is written from a later
                    # callback; never start the next one before it lands
                    # (pipelining clients would see reordered responses)
                    if detached_prev.done is not True:
                        if detached_prev.done is None:
                            detached_prev.done = (
                                asyncio.get_event_loop().create_future()
                            )
                        try:
                            await detached_prev.done
                        except Exception:
                            pass
                    detached_prev = None
                try:
                    out = await self.server.handler(req)
                except Exception:
                    out = None
                if out is DETACHED:
                    detached_prev = req
                    continue
                if out is FALLBACK:
                    ok = await self._proxy(req)
                    if not ok:
                        return
                    continue
                if out is None:
                    self.transport.write(
                        render_response(
                            500, b'{"error":"internal error"}')
                    )
                    continue
                stages = self.server.stages
                with stages.write():
                    self.transport.write(out)
                if stages.sent is not None:
                    stages.sent.inc(len(out))
                    pending = self.transport.get_write_buffer_size()
                    if pending:  # a small answer goes out at once
                        stages.buffered.inc(pending)
                if self.transport.is_closing():
                    return
        except asyncio.CancelledError:
            pass
        except Exception:
            if self.transport is not None:
                self.transport.close()

    async def _proxy(self, req: FastRequest) -> bool:
        with self.server.stages.proxy():
            resp, has_len = await proxy_request(
                self.server.backend, req, transport=self.transport
            )
        if resp:
            self.transport.write(resp)
        if not has_len:
            self.transport.close()
            return False
        return True


_STREAM_THRESHOLD = 1 << 20  # buffer small responses, stream the rest


async def _relay_paced(
    transport, data: bytes, stall_timeout: float = 60.0
) -> None:
    """Write to a protocol transport without unbounded buffering: after
    each piece, wait for the transport's flow control to signal drained
    (pause_writing fired on write when the buffer crossed the high-water
    mark; resume_writing resolves the protocol's drain future). A client
    that stops reading mid-stream holds the relay in ONE suspended await
    instead of a wakeup loop; the wait is still bounded so the caller's
    except path can drop the connection."""
    if transport.is_closing():
        # a closed client must STOP the relay loop, not look "drained" —
        # otherwise the caller pulls the whole remaining backend body
        # into a dead connection
        raise ConnectionResetError("client connection closed mid-relay")
    transport.write(data)
    proto = transport.get_protocol()
    drain = getattr(proto, "drain", None)
    if drain is not None:
        try:
            await asyncio.wait_for(drain(), stall_timeout)
        except asyncio.TimeoutError:
            raise TimeoutError("client stalled during streamed relay") from None
        if transport.is_closing():
            raise ConnectionResetError("client connection closed mid-relay")
        return
    # transports whose protocol has no drain hook: legacy sleep-poll
    waited = 0.0
    while transport.get_write_buffer_size() > _STREAM_THRESHOLD:
        if transport.is_closing():
            raise ConnectionResetError("client connection closed mid-relay")
        if waited >= stall_timeout:
            raise TimeoutError("client stalled during streamed relay")
        await asyncio.sleep(0.05)
        waited += 0.05


async def proxy_request(
    backend, req: FastRequest, transport=None
) -> tuple[bytes, bool]:
    """Replay `req` verbatim against the internal full-featured listener.
    -> (response_bytes, has_content_length). Connection: close on the
    backend leg keeps framing trivial; callers keep their client-side
    connection alive only when the response is Content-Length-framed.

    With `transport` given, a response that would be large (or has no
    Content-Length at all — e.g. a multi-GB chunked-manifest stream from
    the aiohttp tier) is relayed to it in pieces instead of being
    materialized in proxy memory (ADVICE r4); the return is then
    (b"", has_len) and the bytes are already on the wire."""
    if backend is None:
        return render_response(500, b'{"error":"no fallback app"}'), True
    try:
        r, w = await asyncio.open_connection(*backend)
        # strip any connection header, pin close framing on the backend leg
        lines = req.raw_head.split(b"\r\n")
        # drop Expect too: the body is already in hand, and relaying the
        # backend's own "100 Continue" would give the client a second one
        lines = [
            ln for ln in lines[:-2]
            if not ln.lower().startswith(
                (b"connection:", b"x-forwarded-for:", b"expect:")
            )
        ]
        # the backend sees our loopback socket, not the client: carry the
        # real peer so remote-address checks (whitelist, replicate
        # membership) keep working — util.security.real_remote() trusts
        # this header only on loopback-originated requests
        lines.append(b"X-Forwarded-For: " + req.peer.encode("latin1"))
        lines.append(b"Connection: close")
        w.write(b"\r\n".join(lines) + b"\r\n\r\n" + req.body)
        await w.drain()
        # assemble the FULL response head before classifying it: a single
        # read can legally return a partial head (status line flushed
        # before the rest), and has_len decides whether the client-side
        # connection survives — misclassifying drops pipelined requests
        resp = bytearray()
        head_end = -1
        while True:
            piece = await r.read(1 << 16)
            if not piece:
                break
            resp += piece
            head_end = resp.find(b"\r\n\r\n")
            if head_end >= 0 or len(resp) > _MAX_HEADER:
                break
        if not resp:
            w.close()
            return (
                render_response(500, b'{"error":"empty fallback response"}'),
                True,
            )
        if head_end < 0:
            # never produced a legal head within _MAX_HEADER: relay the
            # WHOLE stream verbatim close-framed (dropping the unread
            # remainder would truncate undetectably)
            rest = await r.read(-1)
            w.close()
            return bytes(resp) + rest, False
        clen = None
        for ln in bytes(resp[:head_end]).lower().split(b"\r\n"):
            if ln.startswith(b"content-length:"):
                try:
                    clen = int(ln.split(b":", 1)[1])
                except ValueError:
                    pass
        has_len = clen is not None
        total = head_end + 4 + clen if has_len else None
        if total is not None and (
            total <= _STREAM_THRESHOLD or total <= len(resp)
        ):
            # small, length-framed: buffer the remainder and return whole
            while len(resp) < total:
                piece = await r.read(total - len(resp))
                if not piece:
                    break
                resp += piece
            w.close()
            if len(resp) < total:
                # backend died mid-body: the declared length can't be
                # honored, so the client connection must not be reused
                return bytes(resp), False
            return bytes(resp), has_len
        if transport is None:
            # no sink: preserve the buffered contract
            rest = await r.read(-1)
            w.close()
            return bytes(resp) + rest, has_len
        # large or unbounded: relay piecewise (ADVICE r4 — never
        # materialize a multi-GB fallback stream in proxy memory)
        sent = len(resp)
        try:
            await _relay_paced(transport, bytes(resp))
            while True:
                piece = await r.read(_STREAM_THRESHOLD)
                if not piece:
                    break
                sent += len(piece)
                await _relay_paced(transport, piece)
        except Exception:
            # bytes are already on the wire: a 500 now would corrupt the
            # stream — drop the connection so the client sees truncation
            try:
                transport.close()
            except Exception:
                pass
            w.close()
            return b"", False
        w.close()
        if total is not None and sent < total:
            # backend truncated a length-framed stream: the client must
            # not reuse a connection mid-body
            return b"", False
        return b"", has_len
    except Exception:
        return render_response(500, b'{"error":"fallback proxy failed"}'), True


def finish_detached_proxy(server: "FastHTTPServer", req: FastRequest) -> None:
    """From a DETACHED continuation that discovered it can't finish the
    request after all: replay it against the full app asynchronously."""

    async def run() -> None:
        resp, has_len = await proxy_request(
            server.backend, req, transport=req.transport
        )
        finish_detached(req, resp)
        if not has_len and req.transport is not None:
            req.transport.close()

    t = asyncio.ensure_future(run())
    server._detached_tasks.add(t)
    t.add_done_callback(server._detached_tasks.discard)


class TierStages:
    """What a fast tier times and counts of its own work on the loop,
    bound once a server (`server` None: events alone, no /metrics
    children): `parse`, slicing requests out of what a socket delivered
    (`http.parse`); `write`, handing a full answer to the transport
    (`http.write`), with the answer's bytes (`sent`) and what the
    transport still buffered right after (`buffered`: what did not go out
    at once and leaves over later turns of the loop); `proxy`, the replay
    of a FALLBACK against the cold tier (held across awaits: a counter
    only)."""

    __slots__ = ("parse", "write", "proxy", "sent", "buffered")

    def __init__(self, server: Optional[str] = None):
        def child(family):
            return None if server is None else family.child(server=server)

        self.parse = trace.stage("http.parse", child(REQUEST_PARSE_SECONDS))
        self.write = trace.stage(
            "http.write", child(RESPONSE_WRITE_SECONDS), child(RESPONSE_WRITES)
        )
        self.proxy = trace.stage(
            "http.proxy", child(REQUEST_PROXY_SECONDS), child(REQUEST_PROXIED),
            annotate=False,
        )
        self.sent = child(RESPONSE_BYTES)
        self.buffered = child(RESPONSE_BUFFERED_BYTES)


class FastHTTPServer:
    """Owns the public listening socket; `handler` is the fast tier,
    `backend` (host, port) the full aiohttp app for everything else."""

    def __init__(self, handler: Handler, backend=None,
                 stages: Optional[TierStages] = None):
        self.handler = handler
        self.backend = backend
        self.stages = stages or TierStages()
        self._server: Optional[asyncio.AbstractServer] = None
        self._conns: set = set()
        self._detached_tasks: set = set()  # strong refs (loop holds weak)

    async def start(self, host: str, port: int):
        loop = asyncio.get_event_loop()
        self._server = await loop.create_server(
            lambda: FastHTTPProtocol(self), host, port, reuse_address=True
        )

    @property
    def serving(self) -> bool:
        return self._server is not None and self._server.is_serving()

    async def stop(self):
        if self._server is not None:
            self._server.close()
        # connections first: since Python 3.12 wait_closed() returns only
        # when every connection is gone, and a keep-alive client never
        # hangs up by itself (five bench legs waited here for ever)
        for conn in list(self._conns):
            try:
                if conn.transport is not None:
                    conn.transport.close()
            except Exception:
                pass
        if self._server is not None:
            try:
                await self._server.wait_closed()
            except Exception:
                pass


# ---------------------------------------------------------------- client --


def parse_retry_after(raw: bytes) -> Optional[float]:
    """Seconds from a Retry-After header value: the delta-seconds form,
    or the IMF-fixdate form (RFC 9110 §10.2.3 — standards-faithful peers
    send an HTTP-date; a quota shed's backoff floor must survive either
    spelling). None when unparseable. Cold path: only consulted on
    503/429 responses."""
    try:
        return float(raw)
    except ValueError:
        pass
    try:
        from email.utils import parsedate_to_datetime

        dt = parsedate_to_datetime(raw.decode("latin1").strip())
    except (TypeError, ValueError, IndexError, UnicodeDecodeError):
        return None
    if dt is None:
        return None
    if dt.tzinfo is None:
        # obsolete asctime form carries no zone: the RFC says GMT
        from datetime import timezone

        dt = dt.replace(tzinfo=timezone.utc)
    return max(0.0, dt.timestamp() - time.time())


class _ClientConn(asyncio.Protocol):
    """Raw-protocol client connection: one buffer, inline response parse,
    exactly ONE await per request (the completion future). The
    StreamReader formulation (readuntil + readexactly = several coroutine
    suspensions per response) was ~20-40us/request of pure machinery at
    serving-benchmark QPS rates."""

    __slots__ = ("transport", "buf", "waiter", "closed", "_loop")

    def __init__(self, loop):
        self._loop = loop
        self.transport = None
        self.buf = bytearray()
        self.waiter: Optional[asyncio.Future] = None
        self.closed = False

    # -- transport events --
    def connection_made(self, transport):
        self.transport = transport

    def data_received(self, data):
        self.buf += data
        w = self.waiter
        if w is not None and not w.done():
            self._try_complete(False)

    def eof_received(self):
        self.closed = True
        w = self.waiter
        if w is not None and not w.done():
            self._try_complete(True)
        return False

    def connection_lost(self, exc):
        self.closed = True
        w = self.waiter
        if w is not None and not w.done():
            if not self._try_complete(True):
                w.set_exception(
                    exc or ConnectionResetError("connection lost")
                )

    # -- request lifecycle --
    def begin(self) -> asyncio.Future:
        self.waiter = self._loop.create_future()
        return self.waiter

    def _try_complete(self, eof: bool) -> bool:
        """Parse one response out of self.buf; resolve the waiter when
        complete. -> True when the waiter was resolved (result OR error)."""
        w = self.waiter
        buf = self.buf
        end = buf.find(b"\r\n\r\n")
        if end < 0:
            if eof:
                w.set_exception(
                    asyncio.IncompleteReadError(bytes(buf), None)
                )
                return True
            return False
        head = bytes(buf[:end])
        lower = head.lower()
        # any header-parse error must resolve the waiter, never escape
        # data_received/connection_lost (an escaped exception kills the
        # transport with the future left pending = request hangs forever)
        try:
            line_end = head.find(b"\r\n")
            if line_end < 0:
                line_end = len(head)  # head excludes the blank line's CRLF
            status = int(head[9:line_end].split(b" ", 1)[0] or 500)
            clen = -1
            chunked = b"transfer-encoding: chunked" in lower
            if not chunked:
                idx = lower.find(b"content-length:")
                if idx >= 0:
                    nl = lower.find(b"\r\n", idx)
                    if nl < 0:
                        nl = len(head)
                    clen = int(head[idx + 15: nl].strip())
        except ValueError:
            w.set_exception(ConnectionError("bad response head"))
            return True
        keep = b"connection: close" not in lower
        retry_after = None
        if status in (503, 429):
            # surface the peer's Retry-After so backoff/breakers honor
            # it — only parsed on shed statuses, the 200 path pays one
            # status compare
            idx = lower.find(b"retry-after:")
            if idx >= 0:
                nl = lower.find(b"\r\n", idx)
                if nl < 0:
                    nl = len(head)
                # delta-seconds or IMF-fixdate (ISSUE 12 satellite):
                # either spelling floors the backoff
                retry_after = parse_retry_after(head[idx + 12: nl].strip())
        if chunked:
            done = self._complete_chunked(end, status, keep, eof, retry_after)
        else:
            if clen >= 0:
                total = end + 4 + clen
                if len(buf) < total:
                    if eof:
                        w.set_exception(
                            asyncio.IncompleteReadError(bytes(buf), total)
                        )
                        return True
                    return False
                body = bytes(buf[end + 4: total])
                del buf[:total]
                w.set_result((status, body, keep, retry_after))
                done = True
            else:
                # length-less: framed by EOF, connection retired
                if not eof:
                    return False
                body = bytes(buf[end + 4:])
                del buf[:]
                w.set_result((status, body, False, retry_after))
                done = True
        if done:
            self.waiter = None
        return done

    def _complete_chunked(self, end, status, keep, eof, retry_after=None) -> bool:
        """Chunked responses re-walk the buffer per attempt: fine for this
        client's shapes (our servers Content-Length-frame the data plane;
        chunked replies are rare, small streams)."""
        buf = self.buf
        w = self.waiter
        pos = end + 4
        out = bytearray()
        while True:
            nl = buf.find(b"\r\n", pos)
            if nl < 0:
                break
            try:
                size = int(bytes(buf[pos:nl]).split(b";")[0].strip(), 16)
            except ValueError:
                w.set_exception(ConnectionError("bad chunk size"))
                return True
            if size == 0:
                tpos = nl + 2
                while True:
                    tnl = buf.find(b"\r\n", tpos)
                    if tnl < 0:
                        if eof:
                            w.set_exception(
                                asyncio.IncompleteReadError(bytes(buf), None)
                            )
                            return True
                        return False
                    if tnl == tpos:
                        del buf[:tnl + 2]
                        w.set_result((status, bytes(out), keep, retry_after))
                        return True
                    tpos = tnl + 2
            cstart = nl + 2
            cend = cstart + size
            if len(buf) < cend + 2:
                break
            out += buf[cstart:cend]
            pos = cend + 2
        if eof:
            w.set_exception(asyncio.IncompleteReadError(bytes(buf), None))
            return True
        return False


def _fire_timeout(conn: "_ClientConn", deadline_s: float) -> None:
    """Per-request deadline: fail the in-flight waiter and drop the
    connection (a half-read response can't be reused). Cheaper than
    wait_for on the hot path — one call_later handle, cancelled on the
    normal return."""
    w = conn.waiter
    if w is not None and not w.done():
        w.set_exception(
            TimeoutError(f"request exceeded {deadline_s}s deadline")
        )
    conn.closed = True
    if conn.transport is not None:
        conn.transport.close()


class FastHTTPClient:
    """Keep-alive HTTP/1.1 client pool. request() -> (status, body).

    Built for the data plane's shapes: small JSON/payload responses framed
    by Content-Length. Responses without a Content-Length are read to EOF
    and the connection retired.

    Overload-plane duties (ISSUE 9): every request carries a deadline
    (default 30s — no unbounded waits on the data plane; pass
    timeout=None ONLY for streaming shapes), consults the peer's circuit
    breaker (an open breaker raises CircuitOpenError in microseconds
    instead of burning the timeout), records the outcome into it, and
    surfaces 503/429 ``Retry-After`` hints via
    `retry_after_remaining(hostport)` so retry loops sleep at least as
    long as the peer asked."""

    def __init__(self, pool_per_host: int = 32):
        self._pool: dict = {}
        self._limit = pool_per_host
        self._breakers: dict = {}  # hostport -> CircuitBreaker | None
        self._retry_after: dict = {}  # hostport -> monotonic deadline

    def _breaker(self, hostport: str):
        try:
            return self._breakers[hostport]
        except KeyError:
            br = self._breakers[hostport] = overload.peer_breaker(hostport)
            return br

    def note_retry_after(self, hostport: str, seconds: float) -> None:
        self._retry_after[hostport] = time.monotonic() + seconds

    def retry_after_remaining(self, hostport: str) -> float:
        """Seconds the peer asked us to stay away (0 when none/expired)
        — retry loops pass this as retry_async's delay_floor."""
        t = self._retry_after.get(hostport)
        if t is None:
            return 0.0
        rem = t - time.monotonic()
        if rem <= 0:
            del self._retry_after[hostport]
            return 0.0
        return rem

    async def _get(
        self, hostport: str, timeout: Optional[float] = None
    ) -> _ClientConn:
        conns = self._pool.setdefault(hostport, [])
        while conns:
            c = conns.pop()
            if not c.closed and not c.transport.is_closing():
                return c
        host, _, port = hostport.rpartition(":")
        loop = asyncio.get_running_loop()
        # the request deadline covers connection establishment too: a
        # SYN-dropping peer (real partition, not the injected seam) must
        # fail within the caller's budget, not the OS connect timeout
        connect = loop.create_connection(
            lambda: _ClientConn(loop), host, int(port)
        )
        if timeout is not None:
            _, proto = await asyncio.wait_for(connect, timeout)
        else:
            _, proto = await connect
        return proto

    def _put(self, hostport: str, conn: _ClientConn):
        conns = self._pool.setdefault(hostport, [])
        if (
            len(conns) < self._limit
            and not conn.closed
            and not conn.transport.is_closing()
        ):
            conns.append(conn)
        else:
            conn.transport.close()

    async def request(
        self,
        method: str,
        hostport: str,
        target: str,
        body: bytes = b"",
        content_type: str = "",
        headers: Optional[dict] = None,
        retried: bool = False,
        timeout: Optional[float] = 30.0,
    ) -> tuple[int, bytes]:
        t0 = time.monotonic()
        br = self._breaker(hostport)
        if br is not None and not br.allow():
            raise overload.CircuitOpenError(
                f"circuit open to {hostport} (peer failing/shedding)"
            )
        plan = faults._PLAN
        if plan is not None:
            # fault-injection seam: latency sleeps, resets raise, and
            # http_error rules synthesize a 5xx as if the peer degraded
            try:
                ev = await faults.async_fault(
                    plan, f"http:{method}", hostport, timeout=timeout
                )
            except asyncio.CancelledError:
                # abandoned mid-sleep (hedge lost its race): no verdict
                # on the peer, but a held half-open probe slot must be
                # returned or the breaker wedges shut
                if br is not None:
                    br.record_cancelled()
                raise
            except Exception:
                if br is not None:
                    br.record_failure()
                raise
            if ev is not None and ev.kind == "http_error":
                # tail sampling: a trace that saw an injected fault is
                # kept (flag is a no-op without an active context)
                trace.flag(trace.FLAG_FAULT)
                if br is not None:
                    if ev.rule.status in (503, 429):
                        br.record_shed()
                    else:
                        # any other synthesized status still proves the
                        # peer answered — and a half-open probe MUST get
                        # an outcome here or it wedges the breaker open
                        # forever (allow() consumed the probe slot)
                        br.record_success()
                return ev.rule.status, b'{"error":"injected fault"}'
        # cross-hop context propagation: an active trace context rides a
        # `traceparent` header so the server side joins the same trace
        # (sampled or not — unsampled contexts still carry promotion
        # flags downstream). The ctx-less path pays one contextvar load.
        ctx = trace._CTX.get()
        # one logical request spends ONE deadline across all its phases:
        # the injected-fault wait above, connect, and the response below
        # are each armed with the REMAINING budget, never a fresh copy
        # of `timeout` (which would stack to ~3x the stated deadline)
        left = timeout
        if timeout is not None:
            left = max(0.001, timeout - (time.monotonic() - t0))
        try:
            conn = await self._get(hostport, left)
        except asyncio.CancelledError:
            if br is not None:
                br.record_cancelled()
            raise
        except (OSError, asyncio.TimeoutError) as e:
            # connect refused/timed out: the canonical dead-peer signal.
            # asyncio.TimeoutError (wait_for's connect deadline) is NOT
            # the builtin TimeoutError until 3.11, so it needs its own
            # arm here — and a translation, so callers catching
            # TimeoutError/OSError see the connect timeout too
            if br is not None:
                br.record_failure()
            if not isinstance(e, OSError):
                raise TimeoutError(
                    f"connect to {hostport} exceeded {timeout}s deadline"
                ) from e
            raise
        # cross-hop tenant propagation (ISSUE 12): a non-default current
        # tenant (set by ServingCore dispatch) rides the explicit header
        # so the downstream server's admission gate sees the SAME
        # principal the gateway derived. One contextvar load per
        # request, the trace-context pattern.
        tenant = _cur_tenant()
        if (
            not body and not content_type and not headers
            and method == "GET" and ctx is None and tenant is None
        ):
            # bodyless GET (the read data plane): one f-string render, no
            # part list/join — measurable at serving QPS rates
            wire = (
                f"GET {target} HTTP/1.1\r\nHost: {hostport}\r\n\r\n".encode()
            )
        else:
            parts = [
                f"{method} {target} HTTP/1.1\r\nHost: {hostport}\r\n".encode()
            ]
            if content_type:
                parts.append(f"Content-Type: {content_type}\r\n".encode())
            if body or method in ("POST", "PUT"):
                parts.append(b"Content-Length: %d\r\n" % len(body))
            if headers:
                for k, v in headers.items():
                    parts.append(f"{k}: {v}\r\n".encode())
            if ctx is not None:
                parts.append(
                    b"traceparent: %s\r\n"
                    % trace.format_traceparent_bytes(ctx)
                )
            if tenant is not None:
                parts.append(
                    b"X-Seaweed-Tenant: %s\r\n"
                    % tenant.encode("latin1", "replace")
                )
            parts.append(b"\r\n")
            if body:
                parts.append(body)
            wire = b"".join(parts)
        th = None
        try:
            fut = conn.begin()
            conn.transport.write(wire)
            if timeout is not None:
                left = max(0.001, timeout - (time.monotonic() - t0))
                th = conn._loop.call_later(
                    left, _fire_timeout, conn, left
                )
            status, resp_body, reusable, retry_after = await fut
        except asyncio.CancelledError:
            # a cancelled request (hedged read losing its race) leaves the
            # response half-read on the wire: the connection must die, not
            # linger open outside the pool — and a held half-open probe
            # slot must be returned, or the breaker wedges shut
            conn.transport.close()
            if br is not None:
                br.record_cancelled()
            raise
        except TimeoutError:
            # deadline fired (TimeoutError is an OSError since 3.10 —
            # this arm must come first): NOT retried, a fresh connection
            # would just burn another full deadline against a hung peer
            if br is not None:
                br.record_failure()
            raise
        except (ConnectionError, asyncio.IncompleteReadError, OSError):
            conn.transport.close()
            if retried:
                if br is not None:
                    br.record_failure()
                raise
            # stale pooled connection: one clean retry on a fresh one,
            # against the REMAINING deadline (one logical request never
            # exceeds its stated budget) — and a promotion flag, so the
            # trace that paid the retry is kept by the tail sampler
            if th is not None:
                th.cancel()
                th = None
            if br is not None:
                # a stale-connection write failure is no verdict on the
                # peer — but if this request holds the half-open probe
                # slot, the recursion's allow() would refuse it (and
                # leak the slot until its lease): hand it back first so
                # the retry becomes the probe
                br.record_cancelled()
            trace.flag(trace.FLAG_RETRY)
            left = timeout
            if timeout is not None:
                left = max(0.001, timeout - (time.monotonic() - t0))
            return await self.request(
                method, hostport, target, body, content_type, headers,
                retried=True, timeout=left,
            )
        finally:
            if th is not None:
                th.cancel()
        if reusable:
            self._put(hostport, conn)
        else:
            conn.transport.close()
        if status in (503, 429):
            if retry_after is not None:
                self.note_retry_after(hostport, retry_after)
            if br is not None:
                br.record_shed(retry_after)
        else:
            if br is not None:
                # any completed response (404s included) proves the peer
                # is up and admitting — only transport failures and
                # sheds count against it
                br.record_success()
            # every completed response is "successful traffic" for the
            # shared retry budget (the gRPC retry-throttling shape:
            # successes deposit ratio, failures withdraw 1 — so the
            # hedges/failovers this client's callers pay for stay capped
            # at a fraction of real throughput and refill as the system
            # heals, not only when a retry_async loop happens to run)
            bud = shared_retry_budget()
            if bud is not None:
                bud.on_success()
        return status, resp_body

    async def close(self):
        for conns in self._pool.values():
            for c in conns:
                try:
                    c.transport.close()
                except Exception:
                    pass
        self._pool.clear()


def build_multipart(
    field: str, data: bytes, filename: str = "file", mime: str = ""
) -> tuple[bytes, str]:
    """(body, content_type) for a single-part multipart/form-data upload."""
    boundary = "seaweedtpu-boundary-7f29a1"
    ct = f"Content-Type: {mime}\r\n" if mime else ""
    head = (
        f"--{boundary}\r\nContent-Disposition: form-data; "
        f'name="{field}"; filename="{filename}"\r\n{ct}\r\n'
    ).encode()
    tail = f"\r\n--{boundary}--\r\n".encode()
    return head + data + tail, f"multipart/form-data; boundary={boundary}"


def parse_multipart(body: bytes, content_type: bytes):
    """Single-pass parse of a multipart/form-data body: the first part
    whose disposition names file/upload (or carries a filename) ->
    (data, filename, mime) — or None when the shape is unexpected (caller
    falls back to the full parser). `data` is a zero-copy memoryview into
    `body` (the write fast path hands it straight to the needle append;
    callers that need bytes call bytes() on it)."""
    idx = content_type.find(b"boundary=")
    if idx < 0:
        return None
    boundary = content_type[idx + 9:].split(b";")[0].strip().strip(b'"')
    delim = b"--" + boundary
    pos = body.find(delim)
    while pos >= 0:
        pos += len(delim)
        if body[pos: pos + 2] == b"--":
            return None  # closing delimiter before a usable part
        head_start = pos + 2  # skip CRLF
        head_end = body.find(b"\r\n\r\n", head_start)
        if head_end < 0:
            return None
        head = body[head_start:head_end].lower()
        orig_head = body[head_start:head_end]
        data_start = head_end + 4
        nxt = body.find(b"\r\n" + delim, data_start)
        if nxt < 0:
            return None
        if (
            b'name="file"' in head
            or b'name="upload"' in head
            or b"filename=" in head
        ):
            filename = ""
            fi = orig_head.find(b"filename=")
            if fi >= 0:
                fn = orig_head[fi + 9:].split(b"\r\n")[0].split(b";")[0]
                filename = fn.strip().strip(b'"').decode("utf-8", "replace")
            mime = ""
            mi = head.find(b"content-type:")
            if mi >= 0:
                mime = (
                    orig_head[mi + 13:]
                    .split(b"\r\n")[0]
                    .strip()
                    .decode("latin1")
                )
            return memoryview(body)[data_start:nxt], filename, mime
        pos = body.find(delim, nxt)
    return None
