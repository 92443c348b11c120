"""REST endpoint: the HTTP wire for out-of-process inspectors.

Capability parity with /root/reference/nmz/endpoint/rest
(restendpoint.go:71-223, queue/restqueue.go:20-135), API root ``/api/v3``
(util/rest/restutil.go:16):

* ``POST /api/v3/events/{entity}/{uuid}``   — submit an event (non-blocking)
* ``GET /api/v3/actions/{entity}``          — long-poll the next action;
  idempotent (RFC 7231): repeated GETs return the same head until deleted;
  a newer concurrent poll supersedes an older one (the older returns 204)
* ``DELETE /api/v3/actions/{entity}/{uuid}``— acknowledge/remove an action
* ``POST /api/v3/control?op=enableOrchestration|disableOrchestration``

Batch fast path (doc/performance.md) — the per-event routes above stay
wire-compatible for old inspectors; new transceivers amortize the
per-request overhead across whole batches:

* ``POST /api/v3/events/{entity}/batch``    — submit a JSON array of
  events in one request; each uuid rides the same dedupe ring as the
  per-event route, so a retried batch whose 200 was lost replays
  idempotently (``{"accepted": N, "duplicates": M}``)
* ``GET /api/v3/actions/{entity}?batch=N``  — long-poll up to N queued
  actions in one response (``{"actions": [...]}``; 204 when none)
* ``DELETE /api/v3/actions/{entity}``       — multi-uuid acknowledge,
  body ``{"uuids": [...]}``; unknown uuids are reported, not an error
  (``{"deleted": [...], "missing": [...]}``)

Operator surface at the server root (not under the API root — that is
the inspector wire): ``GET /metrics`` + ``/metrics.json`` (PR 1),
``GET /healthz`` (liveness + active run id), ``GET /traces`` (recorded
run summaries), ``GET /traces/<run_id>`` (Chrome-trace JSON;
``?format=ndjson`` for the diffable line format), and
``GET /analytics`` (cross-run experiment statistics, ``?format=json``
default or ``ndjson``) — doc/observability.md.

Bounded ingress (doc/robustness.md "Chaos plane"): with
``ingress_cap`` > 0, event POSTs arriving while more than that many
events sit undrained in the hub queue are refused with **429 +
Retry-After** (``nmz_ingress_rejections_total``) instead of growing
the queue without limit; the transceiver's bounded retry honors the
header. The ``endpoint.*`` chaos fault points (injected refusals,
long-poll stalls) are seamed through the same handlers.

Implementation: stdlib ThreadingHTTPServer — one thread per in-flight
request, which long-polling requires anyway; no third-party HTTP stack.
"""

from __future__ import annotations

import itertools
import json
import re
import selectors
import socket as _socket
import threading
import time
from collections import OrderedDict, deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional
from urllib.parse import urlparse, parse_qs

from namazu_tpu import chaos, obs, tenancy
from namazu_tpu.endpoint.hub import Endpoint
from namazu_tpu.signal import binary as _binary
from namazu_tpu.signal.action import Action
from namazu_tpu.signal.base import SignalError, signal_from_jsonable
from namazu_tpu.signal.control import Control, ControlOp
from namazu_tpu.signal.event import Event
from namazu_tpu.utils.log import get_logger

log = get_logger("endpoint.rest")

API_ROOT = "/api/v3"

#: the version piggyback header on batch POST / batch poll responses —
#: how an edge notices a table rollover within one batch
#: (doc/performance.md "Zero-RTT dispatch"); re-exported here so wire
#: code has one import site, defined next to the publisher it serves
from namazu_tpu.policy.edge_table import (  # noqa: F401  (re-export)
    TABLE_VERSION_HEADER,
)

_EVENTS_RE = re.compile(rf"^{API_ROOT}/events/([^/]+)/([^/]+)$")
_EVENTS_BATCH_RE = re.compile(rf"^{API_ROOT}/events/([^/]+)/batch$")
_EVENTS_BACKHAUL_RE = re.compile(rf"^{API_ROOT}/events/([^/]+)/backhaul$")
_ACTIONS_RE = re.compile(rf"^{API_ROOT}/actions/([^/]+)(?:/([^/]+))?$")
_CONTROL_RE = re.compile(rf"^{API_ROOT}/control$")
_POLICY_TABLE_RE = re.compile(rf"^{API_ROOT}/policy/table$")
_TELEMETRY_RE = re.compile(rf"^{API_ROOT}/telemetry$")
_TENANCY_RE = re.compile(rf"^{API_ROOT}/tenancy$")
_TRACES_RE = re.compile(r"^/traces(?:/([^/]+))?$")
_CAUSALITY_RE = re.compile(r"^/causality/([^/]+)(?:/([^/]+))?$")
# triage surface (namazu_tpu/triage): dossier list / one dossier by
# failure signature
_TRIAGE_RE = re.compile(r"^/triage(?:/([^/]+))?$")


class ActionQueue:
    """Per-entity deletable action queue with blocking peek.

    Parity: /root/reference/nmz/endpoint/rest/queue/restqueue.go:20-135 —
    ``peek`` blocks until non-empty; a newer concurrent peek supersedes the
    older one; ``delete`` acknowledges by uuid.

    Storage is an insertion-ordered uuid->action dict (dicts preserve
    insertion order), so ``delete`` is O(1) instead of the old linear
    scan — at batch depths a DELETE ack of the queue tail no longer costs
    a walk over every action still in flight.
    """

    def __init__(self) -> None:
        self._items: "Dict[str, Action]" = {}
        self._cond = threading.Condition()
        self._peek_gen = 0

    def put(self, action: Action) -> None:
        with self._cond:
            self._items[action.uuid] = action
            self._cond.notify_all()

    def put_many(self, actions: List[Action]) -> None:
        """Enqueue a whole batch under one lock acquisition + one wakeup
        (the hub's batch fan-through calls this per entity)."""
        if not actions:
            return
        with self._cond:
            for action in actions:
                self._items[action.uuid] = action
            self._cond.notify_all()

    def _wait_nonempty(self, timeout: Optional[float]) -> Optional[int]:
        """Block until non-empty; returns this poller's generation, or
        None on timeout or supersession. Caller holds the lock."""
        self._peek_gen += 1
        my_gen = self._peek_gen
        self._cond.notify_all()  # wake any older poller so it can yield
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            if self._items:
                return my_gen
            if my_gen != self._peek_gen:
                return None  # superseded
            remaining = None if deadline is None \
                else deadline - time.monotonic()
            if remaining is not None and remaining <= 0:
                return None
            self._cond.wait(remaining)

    def peek(self, timeout: float = 30.0) -> Optional[Action]:
        """Return (without removing) the head action, blocking up to
        ``timeout``. Returns None on timeout or when superseded by a newer
        peek."""
        with self._cond:
            if self._wait_nonempty(timeout) is None:
                return None
            return next(iter(self._items.values()))

    def peek_batch(self, max_n: int, timeout: float = 30.0,
                   linger: float = 0.0) -> List[Action]:
        """Return (without removing) up to ``max_n`` head actions,
        blocking like :meth:`peek` until at least one is present. The
        batch GET route's body: whatever is queued NOW ships in one
        response instead of one long-poll round trip per action.

        ``linger`` > 0 trades that many seconds of delivery latency for
        occupancy: after the first action lands, keep collecting until
        the batch is full or the linger expires — at production rates a
        few ms of linger turns per-action round trips into full
        batches."""
        max_n = max(1, max_n)
        with self._cond:
            my_gen = self._wait_nonempty(timeout)
            if my_gen is None:
                return []
            if linger > 0 and len(self._items) < max_n:
                deadline = time.monotonic() + linger
                while len(self._items) < max_n:
                    if self._peek_gen != my_gen:
                        # a newer poll arrived mid-linger: yield to it
                        # (like peek does), or both pollers would be
                        # handed — and dispatch — the same actions
                        return []
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cond.wait(remaining)
            return list(itertools.islice(self._items.values(), max_n))

    def supersede(self) -> None:
        """Unpark every waiting peek NOW (they return empty): the
        simulated-crash path — a kill -9'd process has no parked
        handler threads, so ``sever()`` must not leave pollers parked
        on a dead endpoint's queues for a full poll window. Found as
        the root of the documented crash-restart flake: a transceiver
        whose transparent reconnect raced into the dying listener's
        last milliseconds parked 30s against a zombie handler."""
        with self._cond:
            self._peek_gen += 1
            self._cond.notify_all()

    def delete(self, uuid: str) -> Optional[Action]:
        """Remove and return the action with ``uuid``, or None."""
        with self._cond:
            action = self._items.pop(uuid, None)
            if action is not None:
                self._cond.notify_all()
            return action

    def delete_many(self, uuids: List[str]):
        """Remove a batch of uuids under one lock acquisition; returns
        ``(deleted_actions, missing_uuids)`` — a partial ack (some uuids
        already acked or never queued) is data, not an error."""
        deleted: List[Action] = []
        missing: List[str] = []
        with self._cond:
            for uuid in uuids:
                action = self._items.pop(uuid, None)
                if action is None:
                    missing.append(uuid)
                else:
                    deleted.append(action)
            if deleted:
                self._cond.notify_all()
        return deleted, missing

    def __len__(self) -> int:
        with self._cond:
            return len(self._items)


class QueuedEndpoint(Endpoint):
    """Shared machinery for endpoints built around per-entity
    :class:`ActionQueue` instances and an inbound-uuid dedupe ring —
    the REST wire and the ``uds://`` framed wire (endpoint/uds.py)
    carry the same batch/ack/backhaul semantics over different
    transports, so the queue fan-through, the idempotency ring, and
    the edge-backhaul ingestion live here once."""

    _SEEN_EVENT_CAP = 4096
    #: backhaul uuids get their OWN (larger) ring: the zero-RTT path
    #: runs ~50x the central wire's rate, and sharing one ring would
    #: let a few tens of milliseconds of backhaul evict a central
    #: retry's uuid before its >=0.5s backoff replays it — doubling the
    #: event the ring exists to dedupe. The two populations never
    #: overlap (an event is either edge-decided or centrally posted),
    #: so splitting them loses nothing.
    _SEEN_BACKHAUL_CAP = 65536

    def __init__(self) -> None:
        self._queues: Dict[str, ActionQueue] = {}
        self._queues_lock = threading.Lock()
        # event-uuid dedup ring: the transceiver retries a POST whose
        # ack was lost in flight (doc/robustness.md), so an uuid seen
        # twice means the first attempt already reached the hub — ack
        # without re-posting, or one network blip doubles an event in
        # the trace. Bounded: uuids are unique per event, so a small
        # recent window is enough to cover the retry horizon.
        self._seen_event_uuids: "OrderedDict[str, None]" = OrderedDict()
        self._seen_backhaul_uuids: "OrderedDict[str, None]" = \
            OrderedDict()
        self._seen_lock = threading.Lock()

    def note_event_uuid(self, uuid: str) -> bool:
        """Record an inbound event uuid; True if it was already seen
        (i.e. this POST is a retry duplicate)."""
        with self._seen_lock:
            if uuid in self._seen_event_uuids:
                return True
            self._seen_event_uuids[uuid] = None
            while len(self._seen_event_uuids) > self._SEEN_EVENT_CAP:
                self._seen_event_uuids.popitem(last=False)
            return False

    def note_backhaul_uuid(self, uuid: str) -> bool:
        """The backhaul face of the ring (separate population + cap —
        see _SEEN_BACKHAUL_CAP)."""
        with self._seen_lock:
            if uuid in self._seen_backhaul_uuids:
                return True
            self._seen_backhaul_uuids[uuid] = None
            while len(self._seen_backhaul_uuids) \
                    > self._SEEN_BACKHAUL_CAP:
                self._seen_backhaul_uuids.popitem(last=False)
            return False

    # -- action dispatch -------------------------------------------------

    def _queue_for(self, entity: str, ns: str = "") -> ActionQueue:
        """The action queue of (run namespace, entity). The default
        namespace's key is the bare entity id, so pre-tenancy clients
        poll the exact queues they always did (doc/tenancy.md)."""
        key = tenancy.route_key(ns, entity)
        with self._queues_lock:
            q = self._queues.get(key)
            if q is None:
                q = self._queues[key] = ActionQueue()
            return q

    def send_action(self, action: Action) -> None:
        self._queue_for(action.entity_id,
                        tenancy.ns_of(action)).put(action)

    def send_actions(self, actions: List[Action]) -> None:
        """Batch fan-through: group by (namespace, entity) (order
        preserved within each), resolve every queue under ONE
        ``_queues_lock`` acquisition, then one ``put_many`` (one queue
        lock + one wakeup) per entity — instead of lock/unlock churn
        per action."""
        if len(actions) == 1:
            return self.send_action(actions[0])
        by_key: Dict[str, List[Action]] = {}
        for action in actions:
            by_key.setdefault(tenancy.signal_route_key(action),
                              []).append(action)
        with self._queues_lock:
            queues = {}
            for key in by_key:
                q = self._queues.get(key)
                if q is None:
                    q = self._queues[key] = ActionQueue()
                queues[key] = q
        for key, batch in by_key.items():
            queues[key].put_many(batch)

    def forget_namespace(self, ns: str) -> int:
        """Drop one namespace's action queues (a released/reclaimed
        tenant): a re-lease of the same run name must never poll a dead
        incarnation's undelivered actions, and a long-lived host must
        not leak one queue per entity per lease. Parked pollers on the
        dropped queues are superseded (they return empty and the client
        re-polls into nothing)."""
        if not ns:
            return 0
        prefix = ns + tenancy.ROUTE_SEP
        with self._queues_lock:
            dead = [k for k in self._queues if k.startswith(prefix)]
            queues = [self._queues.pop(k) for k in dead]
        for q in queues:
            q.supersede()
        return len(dead)

    def ack_action(self, entity: str, action: Action) -> None:
        """Observability for one acknowledged (delivered) action."""
        obs.mark(action, "acked")
        obs.record_acked(action)
        obs.rest_ack(entity, obs.latency(action, "dispatched"))
        # every central lifecycle stamp is in hand at the ack: publish
        # the per-segment latency decomposition (queue/decision/
        # parking/dispatch/wire) into nmz_event_stage_seconds — the
        # causality plane's live histogram face (obs/causality.py)
        obs.causality.observe_stage_segments(action)

    # -- zero-RTT edge backhaul (doc/performance.md) ---------------------

    def ingest_backhaul(self, doc, entity: str, ns: str = ""):
        """Decode + dedupe one backhaul request body
        (``{"items": [{"event": ..., "decision": ...}, ...]}``) and
        reconcile the fresh items into the hub. Returns
        ``(accepted, duplicates)``; raises ValueError on a malformed
        body — like the batch POST route, validation is atomic (the
        client retries the whole chunk, the dedupe ring absorbs the
        replay of already-accepted uuids)."""
        items = doc.get("items") if isinstance(doc, dict) else None
        if not isinstance(items, list) or not items:
            raise ValueError(
                "backhaul body must be {\"items\": [{\"event\": ..., "
                "\"decision\": ...}, ...]}")
        pairs = []
        for i, item in enumerate(items):
            if not isinstance(item, dict):
                raise ValueError(f"backhaul item {i} is not an object")
            try:
                sig = signal_from_jsonable(item.get("event"))
            except (SignalError, ValueError, TypeError) as e:
                raise ValueError(f"backhaul item {i}: {e}") from e
            if not isinstance(sig, Event):
                raise ValueError(f"backhaul item {i} is not an event")
            if sig.entity_id != entity:
                raise ValueError(
                    f"backhaul item {i} entity {sig.entity_id!r} does "
                    f"not match url entity {entity!r}")
            decision = item.get("decision")
            if not isinstance(decision, dict) \
                    or "table_version" not in decision:
                raise ValueError(
                    f"backhaul item {i} carries no decision/"
                    "table_version")
            pairs.append((sig, decision))
        fresh = [(ev, d) for ev, d in pairs
                 if not self.note_backhaul_uuid(ev.uuid)]
        if ns:
            for ev, _ in fresh:
                tenancy.set_ns(ev, ns)
        if fresh:
            self.hub.post_edge_backhaul(fresh, self.NAME)
        return len(fresh), len(pairs) - len(fresh)


class _TrackingHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer with (a) connection tracking, so a simulated
    crash (`Orchestrator.abandon`, the chaos harness's in-process
    kill -9) can sever open connections the way real process death
    would, and (b) a BOUNDED handler pool (doc/tenancy.md): connections
    are served by at most ``max_threads`` lazily-spawned workers, with
    overflow connections queued — 8 campaigns' clients hitting one
    orchestrator grow a queue, not an unbounded thread count (the
    stdlib mixin spawned one thread per connection, forever), and (c)
    a serve loop that ``shutdown()`` WAKES (doc/performance.md "Between
    runs"): the loop blocks on the listening socket and one end of a
    socketpair, with no timeout, and ``shutdown()`` writes the other
    end — the stdlib's loop looked at its flag every 0.5 s, which put
    the end of every run on that grid."""

    #: an idle pool worker exits after this long (a short burst's
    #: threads drain back instead of lingering for the process life)
    IDLE_EXIT_S = 30.0

    def __init__(self, *args, max_threads: int = 64, **kw):
        super().__init__(*args, **kw)
        self._open_requests: set = set()
        self._open_lock = threading.Lock()
        self._max_threads = max(1, int(max_threads))
        # condition-based hand-off (NOT a bare Queue): the spawn
        # decision and the idle-waiter accounting happen under ONE
        # lock, so the two lost-wakeup races a stale idle count allows
        # (enqueue beside a worker mid-dequeue, enqueue beside a
        # worker mid-retire) are closed by construction — the put-side
        # invariant is pending <= idle_waiters + spawned workers
        self._conn_cond = threading.Condition()
        self._conn_pending: deque = deque()
        self._idle_waiters = 0
        self._threads_alive = 0
        self._pool_stopped = False
        # the serve loop's wake-up: shutdown() writes _wake_w, the
        # loop selects on _wake_r beside the listener
        self._wake_r, self._wake_w = _socket.socketpair()
        self._stop_requested = False
        self._loop_left = threading.Event()

    def serve_forever(self):
        """``BaseServer.serve_forever`` for this server, less its
        timer: one connection per readable listener, ``service_
        actions`` per round, out when ``shutdown()`` has asked."""
        self._loop_left.clear()
        try:
            with selectors.DefaultSelector() as selector:
                selector.register(self, selectors.EVENT_READ)
                selector.register(self._wake_r, selectors.EVENT_READ)
                while not self._stop_requested:
                    ready = {key.fileobj for key, _ in selector.select()}
                    if self._stop_requested:
                        break
                    if self._wake_r in ready:
                        # a wake no loop took (shutdown() raced a
                        # loop already on its way out): not ours
                        self._wake_r.recv(64)
                    if self in ready:
                        self._handle_request_noblock()
                    self.service_actions()
        finally:
            self._stop_requested = False
            self._loop_left.set()

    def shutdown(self):
        """Stop the serve loop NOW: flag, wake, wait for the loop to
        leave (``BaseServer.shutdown``'s contract: call it while the
        loop runs in another thread, or it waits for one to)."""
        self._stop_requested = True
        try:
            self._wake_w.send(b"\0")
        except OSError:
            pass  # the pair is closed: no loop is left to wake
        self._loop_left.wait()

    def server_close(self):
        super().server_close()
        self._wake_r.close()
        self._wake_w.close()

    def process_request(self, request, client_address):
        with self._open_lock:
            self._open_requests.add(request)
        with self._conn_cond:
            if self._pool_stopped:
                pending = 0
            else:
                self._conn_pending.append((request, client_address))
                pending = len(self._conn_pending)
            # soft cap: whenever queued connections outnumber waiting
            # workers, spawn — beyond max_threads the pool grows like
            # the old thread-per-connection server did (long-lived
            # keep-alive connections, long-polls included, each hold a
            # worker; starving them in the queue would strand
            # entities). The cap's win is burst absorption: short
            # connections reuse pooled workers instead of costing a
            # thread each, and the overflow gauge (nmz_rest_conn_
            # threads vs max) shows sustained pressure.
            spawn = pending > self._idle_waiters
            if spawn:
                self._threads_alive += 1
            alive = self._threads_alive
            self._conn_cond.notify()
        if not pending:
            self.shutdown_request(request)  # stopping: refuse politely
            return
        if spawn:
            threading.Thread(target=self._conn_worker,
                             name="rest-conn", daemon=True).start()
        obs.rest_conn_pool(alive, pending - 1)

    def _next_conn(self):
        """One connection to serve, or None to retire (idle past
        IDLE_EXIT_S, or the pool stopped). All accounting under the
        condition lock."""
        with self._conn_cond:
            deadline = time.monotonic() + self.IDLE_EXIT_S
            while True:
                if self._conn_pending:
                    return self._conn_pending.popleft()
                remaining = deadline - time.monotonic()
                if self._pool_stopped or remaining <= 0:
                    self._threads_alive -= 1
                    return None
                self._idle_waiters += 1
                try:
                    self._conn_cond.wait(remaining)
                finally:
                    self._idle_waiters -= 1

    def _conn_worker(self):
        while True:
            item = self._next_conn()
            if item is None:
                return
            request, client_address = item
            try:
                self.finish_request(request, client_address)
            except Exception:
                self.handle_error(request, client_address)
            finally:
                self.shutdown_request(request)

    def stop_pool(self) -> None:
        """Retire every pool worker and close queued-but-unserved
        connections (shutdown/sever path)."""
        with self._conn_cond:
            self._pool_stopped = True
            drained = list(self._conn_pending)
            self._conn_pending.clear()
            self._conn_cond.notify_all()
        for request, _ in drained:
            self.shutdown_request(request)

    def shutdown_request(self, request):
        with self._open_lock:
            self._open_requests.discard(request)
        super().shutdown_request(request)

    def sever_connections(self) -> int:
        with self._open_lock:
            socks = list(self._open_requests)
        for sock in socks:
            try:
                sock.shutdown(_socket.SHUT_RDWR)
            except OSError:
                pass
        return len(socks)


class RestEndpoint(QueuedEndpoint):
    NAME = "rest"

    def __init__(self, port: int = 10080, host: str = "127.0.0.1",
                 poll_timeout: float = 30.0, ingress_cap: int = 0,
                 retry_after_s: float = 1.0,
                 advertise_codec: bool = True,
                 max_threads: int = 64):
        super().__init__()
        self._host = host
        self._port = port
        self.poll_timeout = poll_timeout
        # the binary-codec negotiation piggyback (doc/performance.md
        # "Binary wire + sharded edge"): advertise X-Nmz-Codec-Accept
        # on every API reply so auto-codec clients upgrade; False
        # simulates a pre-binary server (interop tests)
        self.advertise_codec = bool(advertise_codec)
        # bounded ingress (doc/robustness.md): when more than this many
        # events sit undrained in the hub's queue, new POSTs are refused
        # with 429 + Retry-After instead of growing the queue without
        # limit — the transceiver's bounded retry honors the header.
        # 0 = unbounded (the pre-backpressure behavior).
        self.ingress_cap = max(0, int(ingress_cap))
        self.retry_after_s = max(0.0, float(retry_after_s))
        # bounded connection-handler pool (doc/tenancy.md): beyond this
        # many concurrent connections, new ones queue for a handler
        self.max_threads = max(1, int(max_threads))
        self._server: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._started_mono = time.monotonic()  # /healthz uptime anchor

    # -- lifecycle -------------------------------------------------------

    @property
    def port(self) -> int:
        if self._server is not None:
            return self._server.server_address[1]
        return self._port

    def start(self) -> None:
        endpoint = self
        self._started_mono = time.monotonic()

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # one response = ONE tcp segment: fully buffer the write
            # side (handle_one_request flushes per response) and disable
            # Nagle — header and body written as separate unbuffered
            # segments interlock with the peer's delayed ACK and cost
            # tens of ms per small request/response round trip
            wbufsize = -1
            disable_nagle_algorithm = True

            def log_message(self, fmt, *args):  # route to our logger
                log.debug("http: " + fmt, *args)

            def _entity_ok(self, entity: str) -> bool:
                """False AFTER replying 400 for an entity id that
                would alias a composite route key (tenancy plane:
                '\x1f' is the namespace separator)."""
                if tenancy.ROUTE_SEP in entity:
                    self._reply(400, {"error": "entity id must not "
                                      "contain \x1f"})
                    return False
                return True

            def _req_ns(self):
                """The request's run namespace (the X-Nmz-Run header,
                tenancy plane): '' = the process-default namespace
                (every pre-tenancy client). Returns None AFTER replying
                400 when the header value is malformed."""
                raw = self.headers.get(tenancy.RUN_HEADER)
                if raw is None:
                    return ""
                try:
                    return tenancy.validate_ns(raw.strip())
                except ValueError as e:
                    self._reply(400, {"error": str(e)})
                    return None

            def _req_codec(self) -> str:
                """The request's negotiated codec (the X-Nmz-Codec
                header names the body's codec AND asks for the reply
                in kind; absent = the JSON default wire)."""
                raw = self.headers.get(_binary.CODEC_HEADER)
                if raw is None:
                    return _binary.CODEC_JSON
                return raw.strip()

            def _decode_body(self, raw: bytes):
                """Body -> value tree by the request's codec. Raises
                ValueError; a garbled BINARY payload is tagged so the
                client retries in place instead of downgrading (the
                codec is fine, the bytes were damaged in flight)."""
                if self._req_codec() == _binary.CODEC_BINARY:
                    obs.wire_bytes(_binary.CODEC_BINARY, "ingress",
                                   len(raw))
                    return _binary.loads(raw)
                obs.wire_bytes(_binary.CODEC_JSON, "ingress", len(raw))
                return json.loads(raw)

            def _reply(self, code: int, body: Optional[dict] = None,
                       headers: Optional[Dict[str, str]] = None,
                       codec: Optional[str] = None) -> None:
                """``codec`` (or the request's) picks the body
                serialization; anything binary-incapable degrades to
                JSON per response (the X-Nmz-Codec reply header names
                what was actually used)."""
                codec = self._req_codec() if codec is None else codec
                if body is None:
                    return self._reply_raw(code, b"", "application/json",
                                           headers=headers)
                if codec == _binary.CODEC_BINARY:
                    try:
                        data = _binary.dumps(body)
                    except TypeError:
                        codec = _binary.CODEC_JSON
                    else:
                        headers = dict(headers or {})
                        headers[_binary.CODEC_HEADER] = \
                            _binary.CODEC_BINARY
                        return self._reply_raw(
                            code, data, _binary.CONTENT_TYPE_BINARY,
                            headers=headers)
                self._reply_raw(code, json.dumps(body).encode(),
                                "application/json", headers=headers)

            def _reply_raw(self, code: int, data: bytes,
                           content_type: str,
                           headers: Optional[Dict[str, str]] = None
                           ) -> None:
                obs.rest_request(self.command, code)
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(data)))
                if endpoint.advertise_codec \
                        and self.path.startswith(API_ROOT):
                    # the negotiation piggyback: every API reply tells
                    # the client this server accepts the binary codec
                    self.send_header(_binary.CODEC_ACCEPT_HEADER,
                                     _binary.CODEC_BINARY)
                for name, value in (headers or {}).items():
                    self.send_header(name, value)
                self.end_headers()
                if data:
                    self.wfile.write(data)

            def _reply_badbody(self, e: Exception) -> None:
                """400 for an undecodable body. A garbled BINARY
                payload is tagged retry-in-place: the codec agreement
                is intact, the bytes were damaged in flight — the
                client must NOT downgrade to JSON over it
                (wire.binary.garble chaos contract)."""
                headers = {}
                if self._req_codec() == _binary.CODEC_BINARY:
                    headers["X-Nmz-Codec-Error"] = "garbled"
                self._reply(400, {"error": str(e)}, headers=headers,
                            codec=_binary.CODEC_JSON)

            def _reject_ingress(self, reason: str, status: int = 429,
                                retry_after: Optional[float] = None
                                ) -> None:
                """Refuse an event POST (backpressure or chaos): the
                429/503 + Retry-After contract the transceiver's
                bounded retry honors (doc/robustness.md)."""
                if retry_after is None:
                    retry_after = endpoint.retry_after_s
                obs.ingress_rejected(endpoint.NAME, reason)
                self._reply(
                    status,
                    {"error": f"ingress refused ({reason}); retry after "
                              f"{retry_after:g}s"},
                    headers={"Retry-After": f"{retry_after:g}"})

            def _ingress_refused(self) -> bool:
                """Consult the chaos seam, then the bounded-ingress cap;
                True = a refusal was already sent."""
                fault = chaos.decide("endpoint.ingress.refuse")
                if fault is not None:
                    self._reject_ingress(
                        "chaos", status=int(fault.get("status", 429)),
                        retry_after=float(fault.get("retry_after", 0.05)))
                    return True
                cap = endpoint.ingress_cap
                if cap > 0 and endpoint.hub.event_queue.qsize() >= cap:
                    self._reject_ingress("backpressure")
                    return True
                return False

            def _read_body(self) -> bytes:
                length = int(self.headers.get("Content-Length") or 0)
                return self.rfile.read(length) if length else b""

            def _tv_headers(self, ns: str = "") -> Dict[str, str]:
                """The table-version piggyback (zero-RTT dispatch):
                present on batch POST / batch poll / backhaul replies
                whenever the request's namespace has a table plane —
                the one signal an edge needs to notice a rollover
                within one batch. Namespaced requests see THEIR
                tenant's version (doc/tenancy.md "Per-namespace
                tables"), never the process default's."""
                version = endpoint.hub.table_version(ns)
                if version is None:
                    return {}
                return {TABLE_VERSION_HEADER: str(version)}

            def do_POST(self) -> None:
                url = urlparse(self.path)
                m = _EVENTS_BATCH_RE.match(url.path)
                if m:
                    return self._post_event_batch(m.group(1))
                m = _EVENTS_BACKHAUL_RE.match(url.path)
                if m:
                    return self._post_event_backhaul(m.group(1))
                m = _EVENTS_RE.match(url.path)
                if m:
                    return self._post_event(m.group(1), m.group(2))
                if _TELEMETRY_RE.match(url.path):
                    return self._post_telemetry()
                if _TENANCY_RE.match(url.path):
                    return self._post_tenancy()
                if _CONTROL_RE.match(url.path):
                    return self._post_control(parse_qs(url.query))
                self._reply(404, {"error": f"no route {url.path}"})

            def _post_telemetry(self) -> None:
                """Fleet telemetry push wire (doc/observability.md
                "Fleet telemetry"): one delta-snapshot doc into this
                process's aggregator. Not gated by the event-ingress
                cap — telemetry about an overloaded fleet is exactly
                what must still get through; the doc's seq watermark
                makes a retried push whose 200 was lost idempotent."""
                try:
                    raw = self._read_body()  # always drain (keep-alive)
                except ValueError as e:
                    return self._reply(400, {"error": str(e)})
                try:
                    doc = json.loads(raw)
                except ValueError as e:
                    return self._reply(400, {"error": str(e)})
                try:
                    ack = obs.note_telemetry_push(doc)
                except ValueError as e:
                    return self._reply(400, {"error": str(e)})
                self._reply(200, ack)

            def _post_tenancy(self) -> None:
                """The slot-leasing wire (doc/tenancy.md): one JSON op
                body (lease/renew/release/runs) against this host's
                RunRegistry. 404 on single-run orchestrators — the
                plane simply isn't there."""
                try:
                    raw = self._read_body()  # always drain (keep-alive)
                except ValueError as e:
                    return self._reply(400, {"error": str(e)})
                registry = endpoint.hub.run_registry
                if registry is None:
                    return self._reply(
                        404, {"error": "this orchestrator hosts no "
                              "tenancy plane"})
                try:
                    doc = json.loads(raw)
                except ValueError as e:
                    return self._reply(400, {"error": str(e)})
                if not isinstance(doc, dict):
                    return self._reply(
                        400, {"error": "tenancy body must be a JSON "
                              "object"})
                from namazu_tpu.policy.base import PolicyError
                from namazu_tpu.tenancy.registry import (TenancyError,
                                                         handle_tenancy_op)
                try:
                    resp = handle_tenancy_op(doc, registry)
                except (TenancyError, PolicyError, ValueError) as e:
                    return self._reply(400, {"error": str(e)})
                if resp is None:
                    return self._reply(
                        400, {"error": f"unknown tenancy op "
                              f"{doc.get('op')!r}"})
                self._reply(200, resp)

            def _post_event(self, entity: str, uuid: str) -> None:
                # the body must be READ even when refusing — an unread
                # body desyncs the keep-alive connection (the next
                # request line would parse mid-JSON) — but shed load
                # before the JSON parse, which is the expensive part
                try:
                    raw = self._read_body()
                except ValueError as e:  # malformed Content-Length
                    return self._reply(400, {"error": str(e)})
                if self._ingress_refused():
                    return
                try:
                    sig = signal_from_jsonable(self._decode_body(raw))
                except SignalError as e:
                    return self._reply(400, {"error": str(e)})
                except ValueError as e:
                    return self._reply_badbody(e)
                if not isinstance(sig, Event):
                    return self._reply(400, {"error": "signal is not an event"})
                if sig.entity_id != entity or sig.uuid != uuid:
                    return self._reply(
                        400,
                        {"error": "url entity/uuid do not match event body"},
                    )
                ns = self._req_ns()
                if ns is None or not self._entity_ok(entity):
                    return
                if endpoint.note_event_uuid(sig.uuid):
                    # retry of a POST whose 200 was lost: the event is
                    # already in the hub — idempotent ack
                    return self._reply(200, {"duplicate": True})
                tenancy.set_ns(sig, ns)
                endpoint.hub.post_event(sig, endpoint.NAME)
                self._reply(200, {})

            def _post_event_batch(self, entity: str) -> None:
                """One POST carrying a whole JSON array of events. The
                batch is validated atomically (any malformed item 400s
                the whole request — the client retries the batch, and
                the dedupe ring makes the replay of already-accepted
                uuids idempotent), then fanned into the hub in ONE
                call."""
                try:
                    raw = self._read_body()  # always drain (keep-alive)
                except ValueError as e:  # malformed Content-Length
                    return self._reply(400, {"error": str(e)})
                if self._ingress_refused():
                    return
                try:
                    body = self._decode_body(raw)
                except ValueError as e:
                    return self._reply_badbody(e)
                if isinstance(body, dict):
                    body = body.get("events")
                if not isinstance(body, list) or not body:
                    return self._reply(
                        400, {"error": "batch body must be a non-empty "
                              "JSON array of events (or {\"events\": "
                              "[...]})"})
                events = []
                for i, item in enumerate(body):
                    try:
                        sig = signal_from_jsonable(item)
                    except (SignalError, ValueError, TypeError) as e:
                        return self._reply(
                            400, {"error": f"batch item {i}: {e}"})
                    if not isinstance(sig, Event):
                        return self._reply(
                            400, {"error": f"batch item {i} is not an "
                                  "event"})
                    if sig.entity_id != entity:
                        return self._reply(
                            400, {"error": f"batch item {i} entity "
                                  f"{sig.entity_id!r} does not match url "
                                  f"entity {entity!r}"})
                    events.append(sig)
                ns = self._req_ns()
                if ns is None or not self._entity_ok(entity):
                    return
                fresh = [ev for ev in events
                         if not endpoint.note_event_uuid(ev.uuid)]
                if ns:
                    for ev in fresh:
                        tenancy.set_ns(ev, ns)
                if fresh:
                    endpoint.hub.post_events(fresh, endpoint.NAME)
                self._reply(200, {"accepted": len(fresh),
                                  "duplicates": len(events) - len(fresh)},
                            headers=self._tv_headers(ns))

            def _post_event_backhaul(self, entity: str) -> None:
                """Asynchronous backhaul of edge-decided events
                (doc/performance.md "Zero-RTT dispatch"): the edge
                already dispatched these against a published table;
                this request reconciles their trace records + decision
                detail into the orchestrator. The reply always carries
                the server's current ``table_version`` so a stale edge
                learns of a rollover from its own backhaul."""
                try:
                    raw = self._read_body()  # always drain (keep-alive)
                except ValueError as e:
                    return self._reply(400, {"error": str(e)})
                if self._ingress_refused():
                    return
                try:
                    doc = self._decode_body(raw)
                except ValueError as e:
                    return self._reply_badbody(e)
                ns = self._req_ns()
                if ns is None or not self._entity_ok(entity):
                    return
                try:
                    accepted, duplicates = endpoint.ingest_backhaul(
                        doc, entity, ns=ns)
                except ValueError as e:
                    return self._reply(400, {"error": str(e)})
                self._reply(200, {
                    "accepted": accepted, "duplicates": duplicates,
                    "table_version": endpoint.hub.table_version(ns) or 0,
                }, headers=self._tv_headers(ns))

            def _post_control(self, query: Dict[str, list]) -> None:
                ops = query.get("op") or []
                try:
                    op = ControlOp(ops[0] if ops else "")
                except ValueError:
                    return self._reply(
                        400, {"error": f"bad op {ops!r}; known: "
                              f"{[o.value for o in ControlOp]}"}
                    )
                # tenancy plane: an X-Nmz-Run header scopes the op to
                # that namespace's publisher (one tenant's disable must
                # never suspend a sibling's table); absent = the
                # process-default policy, pre-tenancy behavior
                ns = self._req_ns()
                if ns is None:
                    return
                ctrl = Control(op)
                tenancy.set_ns(ctrl, ns)
                endpoint.hub.post_control(ctrl)
                self._reply(200, {})

            def do_GET(self) -> None:
                url = urlparse(self.path)
                if url.path == "/metrics":
                    # Prometheus text exposition of the process registry
                    return self._reply_raw(
                        200, obs.render_prometheus().encode(),
                        "text/plain; version=0.0.4; charset=utf-8")
                if url.path == "/metrics.json":
                    return self._reply(200, obs.registry_jsonable())
                if url.path == "/healthz":
                    return self._reply(200, {
                        "status": "ok",
                        "run_id": obs.current_run_id(),
                        "uptime_s": round(
                            time.monotonic() - endpoint._started_mono, 3),
                        "endpoint": endpoint.NAME,
                    })
                if url.path == "/analytics":
                    return self._get_analytics(parse_qs(url.query))
                if url.path == "/progress":
                    return self._get_progress()
                if url.path == "/fleet":
                    return self._get_fleet(parse_qs(url.query))
                if url.path == "/profile":
                    return self._get_profile(parse_qs(url.query))
                if _POLICY_TABLE_RE.match(url.path):
                    return self._get_policy_table()
                m = _TRACES_RE.match(url.path)
                if m:
                    return self._get_traces(m.group(1), parse_qs(url.query))
                m = _CAUSALITY_RE.match(url.path)
                if m:
                    return self._get_causality(m.group(1), m.group(2),
                                               parse_qs(url.query))
                m = _TRIAGE_RE.match(url.path)
                if m:
                    return self._get_triage(m.group(1))
                m = _ACTIONS_RE.match(url.path)
                if not (m and m.group(2) is None):
                    return self._reply(404, {"error": f"no route {url.path}"})
                entity = m.group(1)
                query = parse_qs(url.query)
                ns = self._req_ns()
                if ns is None or not self._entity_ok(entity):
                    return
                # chaos seam: stall a long-poll (the inspector's receive
                # loop must ride it out, not die)
                fault = chaos.decide("endpoint.poll.stall")
                if fault is not None:
                    time.sleep(float(fault.get("delay_s", 0.2)))
                raw_batch = (query.get("batch") or [None])[0]
                if raw_batch is None:
                    # per-event wire (pre-batch inspectors): one head
                    # action as the whole body
                    action = endpoint._queue_for(entity, ns).peek(
                        endpoint.poll_timeout)
                    if action is None:
                        return self._reply(204)
                    return self._reply(200, action.to_jsonable())
                try:
                    max_n = int(raw_batch)
                    if max_n <= 0:
                        raise ValueError
                except ValueError:
                    return self._reply(
                        400, {"error": f"bad batch={raw_batch!r} "
                              "(want a positive integer)"})
                raw_linger = (query.get("linger_ms") or ["0"])[0]
                try:
                    # capped: a client must not park this handler
                    # thread for longer than a poll window
                    linger = min(max(0.0, float(raw_linger)),
                                 1000.0) / 1000.0
                except ValueError:
                    return self._reply(
                        400, {"error": f"bad linger_ms={raw_linger!r} "
                              "(want a number)"})
                actions = endpoint._queue_for(entity, ns).peek_batch(
                    max_n, endpoint.poll_timeout, linger=linger)
                if not actions:
                    return self._reply(204, headers=self._tv_headers(ns))
                obs.event_batch("actions_poll", len(actions))
                self._reply(200, {"actions": [a.to_jsonable()
                                              for a in actions]},
                            headers=self._tv_headers(ns))

            def _get_policy_table(self) -> None:
                """The published hash->delay table (zero-RTT dispatch):
                200 + the versioned doc when one is publishable, 204
                (with the version header) when the current version has
                no table — non-table policies, cold start, fault-
                bearing installs, disabled orchestration. An X-Nmz-Run
                header scopes the read to that tenant's OWN publisher
                (doc/tenancy.md "Per-namespace tables"); an unknown or
                expired tenant gets a bare 204 — no version, no
                table."""
                ns = self._req_ns()
                if ns is None:
                    return
                version, doc = endpoint.hub.table_doc(ns)
                headers = self._tv_headers(ns)
                if doc is None:
                    return self._reply(204, headers=headers)
                self._reply(200, doc, headers=headers)

            def _get_analytics(self, query) -> None:
                """Experiment-analytics surface (obs/analytics.py): the
                registered storage's cross-run statistics joined with
                this process's recorded runs — the same payload
                ``nmz-tpu tools report`` renders."""
                fmt = (query.get("format") or ["json"])[0]
                if fmt not in ("json", "ndjson"):
                    return self._reply(
                        400, {"error": f"unknown format {fmt!r}; known: "
                              "json, ndjson"})
                # top/window mirror the CLI's --top/--window so a remote
                # `tools report --url` request is not silently computed
                # with different parameters than a local one
                params = {}
                for name, default in (
                        ("top", obs.analytics.DEFAULT_TOP),
                        ("window", obs.analytics.DEFAULT_WINDOW)):
                    raw = (query.get(name) or [None])[0]
                    try:
                        params[name] = default if raw is None \
                            else max(1, int(raw))
                    except ValueError:
                        return self._reply(
                            400, {"error": f"bad {name}={raw!r} "
                                  "(want a positive integer)"})
                try:
                    payload = obs.analytics_payload(**params)
                except Exception as e:  # never let a stats bug kill ops
                    log.exception("analytics payload failed")
                    return self._reply(
                        500, {"error": f"analytics failed: {e}"})
                if fmt == "ndjson":
                    return self._reply_raw(
                        200, obs.report.render_ndjson(payload).encode(),
                        "application/x-ndjson")
                self._reply(200, payload)

            def _get_progress(self) -> None:
                """Campaign-progress surface (obs/stats.py via
                obs/analytics.progress_stats): the registered storage's
                sequential repro-rate statistics, band verdict, and ETA
                forecasts — always 200, zeros before the first run."""
                try:
                    payload = obs.progress_payload()
                except Exception as e:  # never let a stats bug kill ops
                    log.exception("progress payload failed")
                    return self._reply(
                        500, {"error": f"progress failed: {e}"})
                self._reply(200, payload)

            def _get_fleet(self, query) -> None:
                """Fleet status surface (obs/federation.py): every
                producer process that pushed telemetry here, merged
                under (job, instance) with staleness marking, plus the
                SLO objective table. ``?format=prom`` renders the whole
                fleet as ONE Prometheus exposition so a single scrape
                covers every process."""
                fmt = (query.get("format") or ["json"])[0]
                if fmt not in ("json", "prom"):
                    return self._reply(
                        400, {"error": f"unknown format {fmt!r}; known: "
                              "json, prom"})
                try:
                    if fmt == "prom":
                        return self._reply_raw(
                            200, obs.fleet_prometheus().encode(),
                            "text/plain; version=0.0.4; charset=utf-8")
                    payload = obs.fleet_payload()
                except Exception as e:  # never let a stats bug kill ops
                    log.exception("fleet payload failed")
                    return self._reply(
                        500, {"error": f"fleet failed: {e}"})
                self._reply(200, payload)

            def _get_profile(self, query) -> None:
                """Profiling surface (obs/profiling.py): this process's
                sampling profile — speedscope JSON by default (open the
                body in speedscope.app), ``?format=collapsed`` for
                folded flamegraph text, ``?format=json`` for the raw
                ``nmz-profile-v1`` payload profdiff consumes. 404 when
                the profiler is off (``profile_enabled = false`` /
                ``NMZ_PROFILE=0`` / obs disabled)."""
                fmt = (query.get("format") or ["speedscope"])[0]
                if fmt not in ("speedscope", "collapsed", "json"):
                    return self._reply(
                        400, {"error": f"unknown format {fmt!r}; known: "
                              "speedscope, collapsed, json"})
                try:
                    if not obs.profiling.enabled():
                        return self._reply(
                            404, {"error": "profiler disabled in this "
                                  "process (profile_enabled=false, "
                                  "NMZ_PROFILE=0, or obs off)"})
                    if fmt == "collapsed":
                        return self._reply_raw(
                            200, obs.profile_collapsed().encode(),
                            "text/plain; charset=utf-8")
                    if fmt == "json":
                        return self._reply(200, obs.profile_payload())
                    return self._reply(200, obs.profile_speedscope())
                except Exception as e:  # never let a profile bug kill ops
                    log.exception("profile payload failed")
                    return self._reply(
                        500, {"error": f"profile failed: {e}"})

            def _get_causality(self, run_a, run_b, query) -> None:
                """Causality surface (obs/causality.py): one run's
                happens-before graph + critical-path attribution, or —
                with two run ids — the ordering-relation divergence
                explanation ``nmz-tpu tools why`` renders."""
                raw_top = (query.get("top") or [None])[0]
                try:
                    top = 20 if raw_top is None else max(1, int(raw_top))
                except ValueError:
                    return self._reply(
                        400, {"error": f"bad top={raw_top!r} "
                              "(want a positive integer)"})
                try:
                    if run_b is None:
                        payload = obs.causality_run_payload(run_a)
                    else:
                        payload = obs.causality_why_payload(
                            run_a, run_b, top=top)
                except Exception as e:  # analysis bugs must not kill ops
                    log.exception("causality payload failed")
                    return self._reply(
                        500, {"error": f"causality failed: {e}"})
                if payload is None:
                    return self._reply(
                        404, {"error": "no recorded run "
                              f"{run_a if run_b is None else (run_a, run_b)!r}"})
                self._reply(200, payload)

            def _get_triage(self, signature) -> None:
                """Triage surface (namazu_tpu/triage): the dossier
                summaries this process holds, or one full dossier by
                failure signature — what ``nmz-tpu tools minimize
                --url`` reads."""
                try:
                    from namazu_tpu.triage import store as triage_store

                    if signature is None:
                        return self._reply(
                            200,
                            {"dossiers": triage_store.summaries()})
                    dossier = triage_store.dossier_for(signature)
                except Exception as e:  # stats bugs must not kill ops
                    log.exception("triage payload failed")
                    return self._reply(
                        500, {"error": f"triage failed: {e}"})
                if dossier is None:
                    return self._reply(
                        404, {"error": "no triage dossier for "
                              f"signature {signature!r} (minimize a "
                              "failing run first, or pull it from the "
                              "knowledge pool: tools minimize "
                              "--knowledge)"})
                self._reply(200, {"dossier": dossier})

            def _get_traces(self, run_id, query) -> None:
                """Flight-recorder surface: run list, or one run as
                Chrome-trace JSON / NDJSON (obs/export.py)."""
                if run_id is None:
                    return self._reply(200, {"runs": obs.trace_summaries()})
                run = obs.trace_run(run_id)
                if run is None:
                    return self._reply(
                        404, {"error": f"no recorded run {run_id}"})
                fmt = (query.get("format") or ["chrome"])[0]
                if fmt == "ndjson":
                    return self._reply_raw(
                        200, obs.export.to_ndjson(run).encode(),
                        "application/x-ndjson")
                if fmt != "chrome":
                    return self._reply(
                        400, {"error": f"unknown format {fmt!r}; known: "
                              "chrome, ndjson"})
                self._reply(200, obs.export.chrome_trace(run))

            def do_DELETE(self) -> None:
                url = urlparse(self.path)
                m = _ACTIONS_RE.match(url.path)
                if not m:
                    return self._reply(404, {"error": f"no route {url.path}"})
                entity, uuid = m.group(1), m.group(2)
                ns = self._req_ns()
                if ns is None or not self._entity_ok(entity):
                    return
                if uuid is None:
                    return self._delete_batch(entity, ns)
                action = endpoint._queue_for(entity, ns).delete(uuid)
                if action is not None:
                    self._ack(entity, action)
                    self._reply(200, {})
                else:
                    self._reply(404, {"error": f"no action {uuid} for {entity}"})

            def _ack(self, entity: str, action: Action) -> None:
                endpoint.ack_action(entity, action)

            def _delete_batch(self, entity: str, ns: str = "") -> None:
                """Multi-uuid acknowledge: ``{"uuids": [...]}`` in the
                body, one queue-lock acquisition for the whole batch.
                Unknown uuids come back in ``missing`` with a 200 — a
                replayed ack (the 200 was lost in flight) is a normal
                retry, not a client error."""
                try:
                    body = self._decode_body(self._read_body())
                except ValueError as e:
                    return self._reply_badbody(e)
                uuids = body.get("uuids") if isinstance(body, dict) else None
                if (not isinstance(uuids, list) or not uuids
                        or not all(isinstance(u, str) for u in uuids)):
                    return self._reply(
                        400, {"error": "body must be {\"uuids\": "
                              "[\"...\", ...]}"})
                deleted, missing = \
                    endpoint._queue_for(entity, ns).delete_many(uuids)
                for action in deleted:
                    self._ack(entity, action)
                self._reply(200, {"deleted": [a.uuid for a in deleted],
                                  "missing": missing})

        self._server = _TrackingHTTPServer((self._host, self._port), Handler,
                                           max_threads=self.max_threads)
        self._server.daemon_threads = True
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="rest-endpoint", daemon=True
        )
        self._thread.start()
        log.info("REST endpoint on %s:%d%s", self._host, self.port, API_ROOT)

    def shutdown(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server.stop_pool()
            self._server = None

    def sever(self) -> int:
        """Simulated process death (see :class:`_TrackingHTTPServer`):
        close the LISTENER first (a dead process accepts nothing — a
        client whose transparent reconnect races into the last
        milliseconds must get a refusal, not a fresh socket into the
        corpse), then cut every open connection, then supersede parked
        pollers so their handlers answer into the severed sockets and
        die NOW instead of parking a zombie poll for a full window
        against queues nobody will ever fill. Returns how many
        connections were cut."""
        srv = self._server
        if srv is None:
            return 0
        try:
            srv.shutdown()
            srv.server_close()
        except OSError:  # pragma: no cover - defensive
            pass
        n = srv.sever_connections()
        srv.stop_pool()
        with self._queues_lock:
            queues = list(self._queues.values())
        for q in queues:
            q.supersede()
        return n
