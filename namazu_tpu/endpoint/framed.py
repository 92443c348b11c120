"""FramedServer: the ONE keep-alive framed-JSON serve loop — now an
event-driven selector core with a small worker pool.

Three servers grew the same loop independently — the ``uds://`` event
endpoint (endpoint/uds.py), the search/knowledge sidecar (sidecar.py),
and the campaign supervisor's telemetry collector
(obs/federation.TelemetryServer). PR 10 consolidated them here; the
tenancy plane (doc/tenancy.md) forces the next step: one orchestrator
serving 8+ campaigns' connections must not spend one parked thread per
idle connection. The rewrite:

* ONE selector thread owns accept + reads for every connection and
  assembles frames incrementally — an idle connection costs a registry
  entry, not a thread;
* complete frames dispatch to a small fixed **worker pool** (decode,
  handler, reply). Per-connection FIFO order is preserved: one request
  in flight per connection, later frames queue behind it;
* ops that PARK by design (the long-poll ``poll`` op) hand off from
  the worker to a short-lived thread, so parked polls occupy exactly
  one thread per in-flight poll — never a pool slot. Beyond
  ``max_parked`` simultaneous parked ops the handler runs inline in
  the worker (bounded degradation, never an error).

The contract every framed wire shares is unchanged (one frame each
way, ``uint32-LE length + UTF-8 JSON`` — endpoint/agent.py's codec,
binary high-bit negotiated per connection, any number of
request/response pairs per connection):

* EOF or a codec/socket error drops the connection cleanly;
* a valid-JSON **non-object** frame is ANSWERED
  (``{"ok": false, ...}``) so the client's keep-alive stream stays in
  sync, never severed;
* an in-sync garbled payload (``wire.binary.garble``) is answered
  ``{"ok": false, "transient": true}`` — the client's bounded retry
  resends a clean copy;
* a handler exception is answered (``{"ok": false, "error": ...}``),
  logged, and never desyncs the wire;
* **span context** (obs/context.py): a request frame carrying ``ctx``
  has its Lamport clock merged before the handler runs, and the
  response echoes a fresh ``ctx`` stamp; context-less requests get
  byte-identical responses to the pre-context wire;
* **request scope** (obs/spans.py): the selector stamps each frame as
  it completes; for the ops in :data:`SPAN_OPS` the worker opens a
  request scope from that stamp (id from the frame's ``ctx`` when it
  carries one), so the wait for a pool slot is a span like any other;
* per-connection ``codec`` negotiation is answered by the serve loop
  itself, uniformly across every framed wire;
* shutdown severs live connections (a parked long-poll must error and
  reconnect, not keep talking to a dead server), and ``sever()`` alone
  simulates crash death for the chaos harness.

Binding: :meth:`bind_unix` reclaims a listener-less stale socket inode
(probe-connect first; a live listener raises — stealing a served path
would silently split an event stream across two servers; a non-socket
file is never clobbered) and unlinks the path at shutdown;
:meth:`bind_tcp` sets ``SO_REUSEADDR`` so a hard-stopped server can
rebind its port immediately.
"""

from __future__ import annotations

import contextlib
import json
import os
import queue
import selectors
import socket
import stat
import struct
import threading
import time
from collections import deque
from typing import Callable, Dict, Optional

from namazu_tpu.endpoint.agent import (BINARY_FRAME_FLAG, MAX_FRAME,
                                       write_frame)
from namazu_tpu.obs import context as _context
from namazu_tpu.obs import metrics as _metrics
from namazu_tpu.obs import spans as _spans
from namazu_tpu.signal import binary as _binary
from namazu_tpu.utils.log import get_logger

log = get_logger("endpoint.framed")

#: handler(req dict) -> resp dict
Handler = Callable[[dict], dict]
#: decorate(req dict, resp dict) -> None — per-wire piggybacks (the
#: uds endpoint's table_version) applied after the handler, before send
Decorator = Callable[[dict, dict], None]

#: ops that park their handler by design: the long-poll family, plus
#: the tenancy lease ops ("release" waits up to 10s for its
#: namespace's flush to drain; "lease" may replay a journal). These
#: hand off from the worker pool to a per-request thread so a parked
#: op can never starve short ops (post_batch/ack/telemetry) of a pool
#: slot — a campaign winding down several serve slots at once must not
#: convoy every other tenant's wire.
DEFAULT_BLOCKING_OPS = frozenset({"poll", "lease", "release"})

#: ops served under a request scope (obs/spans.py "request-scoped
#: spans"): the request gets an id, its wait between "frame complete"
#: and "worker starts" is the ``queue`` span, the response write the
#: ``reply`` span, and the handler's own phases nest under the id. Only
#: the search plane's requests — the event plane's six-figure frame
#: rates pay one clock read per frame for the stamp and nothing else.
SPAN_OPS = frozenset({"search"})


def reclaim_stale_unix_socket(path: str, what: str = "server") -> None:
    """Unlink a socket inode left by a dead predecessor, IF no live
    listener answers a probe connect. A live listener raises (the path
    is being served); a non-socket path is left alone so the caller's
    bind fails loudly instead of clobbering someone's file."""
    try:
        st = os.stat(path)
    except OSError:
        return  # nothing there
    if not stat.S_ISSOCK(st.st_mode):
        return
    probe = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        probe.settimeout(0.2)
        try:
            probe.connect(path)
        except OSError:
            # no listener: stale — reclaim the path
            try:
                os.unlink(path)
            except OSError:
                pass
            return
    finally:
        try:
            probe.close()
        except OSError:
            pass
    raise RuntimeError(
        f"{what} path {path!r} already has a live listener "
        "(another process?); refusing to take it over")


class _Conn:
    """Per-connection state, owned by the selector thread except where
    noted."""

    __slots__ = ("sock", "rbuf", "wlock", "plock", "busy", "pending")

    #: pipelined-requests bound: a client that floods requests without
    #: reading replies is dropped rather than buffered without limit
    MAX_PENDING = 1024

    def __init__(self, sock: socket.socket) -> None:
        self.sock = sock
        self.rbuf = bytearray()
        #: serializes response writes (workers + poll threads)
        self.wlock = threading.Lock()
        #: guards busy/pending (selector thread + workers)
        self.plock = threading.Lock()
        self.busy = False
        self.pending: deque = deque()


class FramedServer:
    def __init__(self, handler: Handler, name: str = "framed",
                 decorate: Optional[Decorator] = None,
                 workers: int = 4,
                 blocking_ops=DEFAULT_BLOCKING_OPS,
                 max_parked: int = 256) -> None:
        self._handler = handler
        self._name = name
        self._decorate = decorate
        self._workers_n = max(1, int(workers))
        self._blocking_ops = frozenset(blocking_ops or ())
        self._server: Optional[socket.socket] = None
        self._selector_thread: Optional[threading.Thread] = None
        self._worker_threads: list = []
        self._work: "queue.Queue" = queue.Queue()
        self._stop = threading.Event()
        self._conns: set = set()
        self._conns_lock = threading.Lock()
        # parked-op budget: a Semaphore would block; a counter + cap
        # degrades to inline execution instead
        self._parked = 0
        self._parked_cap = max(1, int(max_parked))
        self._parked_lock = threading.Lock()
        # guards the wake-pipe fds: _wake() writes under it and the
        # selector thread nulls them under it before closing, so a
        # late shutdown() can never write into a closed (or recycled)
        # descriptor
        self._wake_lock = threading.Lock()
        self._wake_r: Optional[int] = None
        self._wake_w: Optional[int] = None
        #: AF_UNIX path when bound to one (unlinked at shutdown)
        self.path: Optional[str] = None

    # -- binding -----------------------------------------------------------

    def bind_unix(self, path: str, backlog: int = 64) -> None:
        reclaim_stale_unix_socket(path, what=self._name)
        srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        srv.bind(path)
        srv.listen(backlog)
        self._server = srv
        self.path = path

    def bind_tcp(self, host: str, port: int, backlog: int = 8) -> int:
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind((host, port))
        srv.listen(backlog)
        self._server = srv
        return srv.getsockname()[1]

    @property
    def port(self) -> int:
        assert self._server is not None
        return self._server.getsockname()[1]

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        assert self._server is not None, "bind before start"
        if self._selector_thread is not None:
            return
        self._wake_r, self._wake_w = os.pipe()
        for i in range(self._workers_n):
            t = threading.Thread(target=self._worker_loop,
                                 name=f"{self._name}-worker-{i}",
                                 daemon=True)
            t.start()
            self._worker_threads.append(t)
        self._selector_thread = threading.Thread(
            target=self._selector_loop, name=f"{self._name}-select",
            daemon=True)
        self._selector_thread.start()

    def _wake(self) -> None:
        with self._wake_lock:
            w = self._wake_w
            if w is not None:
                try:
                    os.write(w, b"x")
                except OSError:
                    pass

    def shutdown(self) -> None:
        self._stop.set()
        srv, self._server = self._server, None
        if srv is not None:
            try:
                srv.close()
            except OSError:
                pass
        self._wake()
        with self._conns_lock:
            conns, self._conns = set(self._conns), set()
        for conn in conns:
            try:
                conn.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                conn.sock.close()
            except OSError:
                pass
        for _ in self._worker_threads:
            self._work.put(None)
        if self.path is not None:
            try:
                os.unlink(self.path)
            except OSError:
                pass

    def join(self, timeout: float = 2.0) -> bool:
        """Wait (bounded, after :meth:`shutdown`) for the selector and
        worker threads to exit; True when all did. A process about to
        exit calls this so no server thread outlives the objects its
        handler owns: a daemon thread's exit drops its bound-method
        target, and a thread that thereby frees jax objects while the
        interpreter finalises aborts the process (see
        obs/profiling.ensure_profiler)."""
        deadline = time.monotonic() + timeout
        me = threading.current_thread()
        threads = [t for t in (self._selector_thread,
                               *self._worker_threads)
                   if t is not None and t is not me]
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        return not any(t.is_alive() for t in threads)

    def sever(self) -> int:
        """Cut every live connection WITHOUT stopping the server — the
        chaos harness's in-process stand-in for kill -9: a parked
        client poll must error and reconnect, not keep talking to a
        dead process's handler thread."""
        with self._conns_lock:
            conns = list(self._conns)
        for conn in conns:
            try:
                conn.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        return len(conns)

    # -- selector core -----------------------------------------------------

    def _selector_loop(self) -> None:
        from namazu_tpu.obs import profiling

        profiling.tag_current_thread("wire")
        sel = selectors.DefaultSelector()
        srv = self._server
        if srv is None:
            return
        sel.register(srv, selectors.EVENT_READ, "accept")
        if self._wake_r is not None:
            sel.register(self._wake_r, selectors.EVENT_READ, "wake")
        try:
            while not self._stop.is_set():
                try:
                    events = sel.select(timeout=1.0)
                except OSError:
                    return
                for key, _ in events:
                    kind = key.data
                    if kind == "accept":
                        self._accept(sel)
                    elif kind == "wake":
                        try:
                            os.read(self._wake_r, 4096)
                        except OSError:
                            pass
                    else:
                        self._readable(sel, kind)
        finally:
            try:
                sel.close()
            except OSError:
                pass
            with self._wake_lock:
                fds = (self._wake_r, self._wake_w)
                self._wake_r = self._wake_w = None
            for fd in fds:
                if fd is not None:
                    try:
                        os.close(fd)
                    except OSError:
                        pass

    def _accept(self, sel) -> None:
        srv = self._server
        if srv is None:
            return
        try:
            sock, _ = srv.accept()
        except OSError:
            return
        conn = _Conn(sock)
        with self._conns_lock:
            self._conns.add(conn)
        try:
            sel.register(sock, selectors.EVENT_READ, conn)
        except (OSError, ValueError):
            self._close_conn(None, conn)

    def _readable(self, sel, conn: _Conn) -> None:
        try:
            chunk = conn.sock.recv(1 << 16)
        except OSError:
            self._close_conn(sel, conn)
            return
        if not chunk:
            self._close_conn(sel, conn)  # EOF
            return
        conn.rbuf += chunk
        while True:
            frame = self._extract_frame(conn)
            if frame is None:
                break
            if frame == "broken":
                self._close_conn(sel, conn)
                return
            codec, body = frame
            if not self._enqueue(conn, codec, body, time.monotonic()):
                self._close_conn(sel, conn)
                return

    def _extract_frame(self, conn: _Conn):
        """One complete frame from the connection buffer:
        ``(codec, body_bytes)``, ``None`` when incomplete, or
        ``"broken"`` when the framing layer itself is bad (oversized
        length — the drop-the-connection class)."""
        buf = conn.rbuf
        if len(buf) < 4:
            return None
        (length,) = struct.unpack("<I", bytes(buf[:4]))
        codec = _binary.CODEC_JSON
        if length & BINARY_FRAME_FLAG:
            codec = _binary.CODEC_BINARY
            length &= ~BINARY_FRAME_FLAG
        if length > MAX_FRAME:
            return "broken"
        if len(buf) < 4 + length:
            return None
        body = bytes(buf[4:4 + length])
        del buf[:4 + length]
        return codec, body

    def _enqueue(self, conn: _Conn, codec: str, body: bytes,
                 arrived: float) -> bool:
        """Queue one raw frame (complete at monotonic ``arrived``) for
        processing, preserving per-connection FIFO; False = the client
        pipelined past the bound (drop it)."""
        with conn.plock:
            if conn.busy:
                if len(conn.pending) >= conn.MAX_PENDING:
                    return False
                conn.pending.append((codec, body, arrived))
                return True
            conn.busy = True
        self._work.put((conn, codec, body, arrived))
        return True

    def _finish_task(self, conn: _Conn) -> None:
        """A request finished: start the next queued frame, or go idle."""
        with conn.plock:
            if conn.pending:
                task = conn.pending.popleft()
            else:
                conn.busy = False
                return
        self._work.put((conn, *task))

    def _close_conn(self, sel, conn: _Conn) -> None:
        if sel is not None:
            try:
                sel.unregister(conn.sock)
            except (KeyError, OSError, ValueError):
                pass
        with self._conns_lock:
            self._conns.discard(conn)
        try:
            conn.sock.close()
        except OSError:
            pass

    # -- workers -----------------------------------------------------------

    def _worker_loop(self) -> None:
        # profiling plane: a worker parked on the task queue has no
        # namazu frame on its stack — pin it to the wire plane so its
        # samples classify (obs/profiling.py taxonomy)
        from namazu_tpu.obs import profiling

        profiling.tag_current_thread("wire")
        while True:
            task = self._work.get()
            if task is None:
                return
            conn, codec, body, arrived = task
            try:
                self._process(conn, codec, body, arrived)
            except Exception:  # pragma: no cover - defensive
                log.exception("%s frame processing failed", self._name)
                self._finish_task(conn)

    def _send(self, conn: _Conn, resp: dict, codec: str) -> int:
        with conn.wlock:
            return write_frame(conn.sock, resp, codec=codec)

    def _send_in_codec(self, conn: _Conn, resp: dict, codec: str) -> int:
        """Answer in the codec the request arrived in — per-frame,
        stateless, so mixed-codec clients on one endpoint work. A
        handler value the binary codec cannot carry degrades THIS
        response to JSON rather than desync."""
        try:
            return self._send(conn, resp, codec)
        except TypeError:
            return self._send(conn, resp, _binary.CODEC_JSON)

    def _process(self, conn: _Conn, codec: str, body: bytes,
                 arrived: float) -> None:
        """Decode one frame and answer it (worker thread)."""
        try:
            if codec == _binary.CODEC_BINARY:
                req = _binary.loads(body)
            else:
                req = json.loads(body)
        except ValueError as e:
            # the frame's length prefix was intact, only the payload
            # was garbled: the stream is still in sync — answer it
            # (transient: the client's bounded retry resends a clean
            # copy), never sever the keep-alive connection
            # (wire.binary.garble)
            try:
                self._send(conn, {"ok": False, "transient": True,
                                  "error": f"undecodable {codec} "
                                           f"frame: {e}"},
                           _binary.CODEC_JSON)
            except OSError:
                pass
            self._finish_task(conn)
            return
        if not isinstance(req, dict):
            # answered, not severed: the framed stream stays in sync
            # for the client's next request
            try:
                self._send(conn, {"ok": False,
                                  "error": "frame must be a JSON "
                                           "object"}, codec)
            except OSError:
                pass
            self._finish_task(conn)
            return
        if req.get("op") == "codec":
            # per-connection codec negotiation: answered by the serve
            # loop itself so EVERY framed wire (uds endpoint, sidecar,
            # telemetry collector) speaks it uniformly. A pre-binary
            # server answers this op with its handler's unknown-op
            # error — the client then stays on JSON, loss-free.
            offered = req.get("codecs")
            picked = (_binary.CODEC_BINARY
                      if isinstance(offered, (list, tuple))
                      and _binary.CODEC_BINARY in offered
                      else _binary.CODEC_JSON)
            _spans.codec_negotiated(picked)
            try:
                self._send(conn, {"ok": True, "codec": picked}, codec)
            except OSError:
                pass
            self._finish_task(conn)
            return
        if req.get("op") in self._blocking_ops:
            # long-poll class: hand off so the pool slot frees NOW —
            # one short-lived thread per in-flight parked op, bounded
            # by max_parked (beyond it, run inline: degraded latency
            # for short ops, never an error)
            with self._parked_lock:
                over = self._parked >= self._parked_cap
                if not over:
                    self._parked += 1
            if not over:
                threading.Thread(
                    target=self._answer_parked,
                    args=(conn, req, codec, len(body), arrived),
                    name=f"{self._name}-poll", daemon=True).start()
                return
        self._answer(conn, req, codec, len(body), arrived)
        self._finish_task(conn)

    def _answer_parked(self, conn: _Conn, req: dict, codec: str,
                       n_in: int, arrived: float) -> None:
        try:
            self._answer(conn, req, codec, n_in, arrived)
        finally:
            with self._parked_lock:
                self._parked -= 1
            self._finish_task(conn)

    def _answer(self, conn: _Conn, req: dict, codec: str,
                n_in: int, arrived: float) -> None:
        if req.get("op") not in SPAN_OPS:
            self._answer_scoped(conn, req, codec, n_in, False)
            return
        # the request's id and arrival stamp are the handler's to read
        # (obs.current_request) for as long as this thread serves it
        scoped = _spans.request_begin(req.get(_context.CTX_KEY),
                                      arrived) is not None
        try:
            self._answer_scoped(conn, req, codec, n_in, scoped)
        finally:
            _spans.request_end()

    def _answer_scoped(self, conn: _Conn, req: dict, codec: str,
                       n_in: int, scoped: bool) -> None:
        ctx_seen = self._observe_ctx(req)
        try:
            resp = self._handler(req)
        except Exception as e:  # answer, never desync the wire
            log.exception("%s op failed: %r", self._name,
                          req.get("op"))
            resp = {"ok": False, "error": repr(e)}
        if self._decorate is not None:
            try:
                self._decorate(req, resp)
            except Exception:  # pragma: no cover - defensive
                log.exception("%s response decorator failed",
                              self._name)
        if ctx_seen:
            # echo a fresh stamp so the client's clock merges ours;
            # context-less peers get the pre-context wire byte for byte
            resp.setdefault(_context.CTX_KEY, _context.wire_stamp())
        try:
            with (_spans.search_phase("reply") if scoped
                  else contextlib.nullcontext()):
                n_out = self._send_in_codec(conn, resp, codec)
        except OSError:
            return
        _spans.wire_bytes(codec, str(req.get("op") or "frame"),
                          n_in + n_out)

    @staticmethod
    def _observe_ctx(req: Dict) -> bool:
        """Merge a request frame's span-context clock; True when the
        request carried one (and observability is on)."""
        ctx = req.get(_context.CTX_KEY)
        if ctx is None or not _metrics.enabled():
            return False
        _context.observe_wire(ctx)
        return True
