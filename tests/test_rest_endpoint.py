"""REST endpoint + transceiver integration over loopback HTTP.

Parity: /root/reference/nmz/endpoint/endpoint_test.go:36-160 and
rest/restendpoint_test.go — real HTTP on an auto-assigned port, a
MockOrchestrator echoing default actions, mixed local+REST entities,
idempotent GET, DELETE acks, and control ops.
"""

import json
import threading
import urllib.error
import urllib.request

import pytest

from namazu_tpu.endpoint.hub import EndpointHub
from namazu_tpu.endpoint.local import LocalEndpoint
from namazu_tpu.endpoint.rest import ActionQueue, RestEndpoint
from namazu_tpu.inspector.transceiver import new_transceiver
from namazu_tpu.orchestrator import Orchestrator
from namazu_tpu.policy import create_policy
from namazu_tpu.signal import EventAcceptanceAction, NopAction, PacketEvent
from namazu_tpu.utils.config import Config
from namazu_tpu.utils.mock_orchestrator import MockOrchestrator


@pytest.fixture
def rest_hub():
    hub = EndpointHub()
    hub.add_endpoint(LocalEndpoint())
    rest = RestEndpoint(port=0, poll_timeout=2.0)
    hub.add_endpoint(rest)
    mock = MockOrchestrator(hub)
    mock.start()
    yield hub, rest
    mock.shutdown()


def _url(rest, path):
    return f"http://127.0.0.1:{rest.port}/api/v3{path}"


def test_event_action_roundtrip_over_http(rest_hub):
    hub, rest = rest_hub
    trans = new_transceiver(f"http://127.0.0.1:{rest.port}", "r0")
    trans.start()
    try:
        ev = PacketEvent.create("r0", "r0", "peer")
        ch = trans.send_event(ev)
        act = ch.get(timeout=10)
        assert isinstance(act, EventAcceptanceAction)
        assert act.event_uuid == ev.uuid
    finally:
        trans.shutdown()


def test_many_events_multiple_rest_entities(rest_hub):
    hub, rest = rest_hub
    n = 20
    results = {}

    def client(entity):
        trans = new_transceiver(f"http://127.0.0.1:{rest.port}", entity)
        trans.start()
        try:
            chans = []
            for i in range(n):
                chans.append(trans.send_event(PacketEvent.create(entity, entity, "p")))
            results[entity] = [ch.get(timeout=15) for ch in chans]
        finally:
            trans.shutdown()

    entities = [f"rest-{k}" for k in range(3)]
    threads = [threading.Thread(target=client, args=(e,)) for e in entities]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    for e in entities:
        assert len(results[e]) == n


def test_mixed_local_and_rest_entities(rest_hub):
    hub, rest = rest_hub
    lep = hub.endpoint("local")
    local_trans = new_transceiver("local://", "loc0", lep)
    local_trans.start()
    rest_trans = new_transceiver(f"http://127.0.0.1:{rest.port}", "rst0")
    rest_trans.start()
    try:
        ch_l = local_trans.send_event(PacketEvent.create("loc0", "a", "b"))
        ch_r = rest_trans.send_event(PacketEvent.create("rst0", "a", "b"))
        assert isinstance(ch_l.get(timeout=10), EventAcceptanceAction)
        assert isinstance(ch_r.get(timeout=10), EventAcceptanceAction)
    finally:
        rest_trans.shutdown()


def test_get_is_idempotent_until_delete(rest_hub):
    hub, rest = rest_hub
    # post an event via raw HTTP, then GET twice without DELETE
    ev = PacketEvent.create("raw0", "raw0", "peer")
    req = urllib.request.Request(
        _url(rest, f"/events/raw0/{ev.uuid}"),
        data=ev.to_json().encode(),
        method="POST",
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req) as resp:
        assert resp.status == 200

    def get_action():
        with urllib.request.urlopen(_url(rest, "/actions/raw0"), timeout=10) as r:
            assert r.status == 200
            return json.loads(r.read())

    a1 = get_action()
    a2 = get_action()
    assert a1["uuid"] == a2["uuid"]
    # DELETE acks; second DELETE 404s
    del_req = urllib.request.Request(
        _url(rest, f"/actions/raw0/{a1['uuid']}"), method="DELETE"
    )
    with urllib.request.urlopen(del_req) as r:
        assert r.status == 200
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(
            urllib.request.Request(
                _url(rest, f"/actions/raw0/{a1['uuid']}"), method="DELETE"
            )
        )
    assert ei.value.code == 404


def test_malformed_event_rejected(rest_hub):
    hub, rest = rest_hub
    req = urllib.request.Request(
        _url(rest, "/events/x/y"),
        data=b'{"class": "NoSuchEvent", "entity": "x"}',
        method="POST",
    )
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req)
    assert ei.value.code == 400


def test_entity_uuid_mismatch_rejected(rest_hub):
    hub, rest = rest_hub
    ev = PacketEvent.create("correct", "a", "b")
    req = urllib.request.Request(
        _url(rest, "/events/wrong-entity/" + ev.uuid),
        data=ev.to_json().encode(),
        method="POST",
    )
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req)
    assert ei.value.code == 400


def test_control_endpoint_toggles_orchestration():
    cfg = Config({"rest_port": 0, "skip_init_orchestration": True})
    policy = create_policy("dumb")
    orc = Orchestrator(cfg, policy, collect_trace=False)
    orc.start()
    rest = orc.hub.endpoint("rest")
    try:
        assert not orc.enabled
        req = urllib.request.Request(
            _url(rest, "/control?op=enableOrchestration"), method="POST"
        )
        with urllib.request.urlopen(req) as r:
            assert r.status == 200
        import time

        for _ in range(100):
            if orc.enabled:
                break
            time.sleep(0.01)
        assert orc.enabled
        # bad op -> 400
        bad = urllib.request.Request(_url(rest, "/control?op=bogus"), method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad)
        assert ei.value.code == 400
    finally:
        orc.shutdown()


def test_action_queue_newer_peek_supersedes_older():
    q = ActionQueue()
    results = []

    def old_peek():
        results.append(q.peek(timeout=10))

    t = threading.Thread(target=old_peek)
    t.start()
    import time

    time.sleep(0.1)
    # newer peek with short timeout supersedes the old poller
    assert q.peek(timeout=0.05) is None
    t.join(timeout=5)
    assert not t.is_alive()
    assert results == [None]


def test_nop_actions_not_propagated_to_rest(rest_hub):
    """Non-deferred events answered orchestrator-side must not show up in
    the REST action queue."""
    hub, rest = rest_hub
    from namazu_tpu.signal import LogEvent

    ev = LogEvent.create("log0", "something happened")
    req = urllib.request.Request(
        _url(rest, f"/events/log0/{ev.uuid}"),
        data=ev.to_json().encode(),
        method="POST",
    )
    with urllib.request.urlopen(req) as resp:
        assert resp.status == 200
    with urllib.request.urlopen(_url(rest, "/actions/log0"), timeout=10) as r:
        assert r.status == 204


# -- a run's end waits on no poll (ISSUE 47): shutdown() WAKES the serve
# loop. Orderings, not latencies: every bound below is one that loaded
# workers cannot reach and that a tick could not satisfy either.

_WOKE_WITHIN_S = 10.0


def _recording_selectors(monkeypatch):
    """The serve loop's selector, recording ``("enter", timeout)`` /
    ``("leave",)`` around every ``select`` into the returned list."""
    import selectors
    import types

    from namazu_tpu.endpoint import rest as rest_mod

    log = []

    class Recording(selectors.DefaultSelector):
        def select(self, timeout=None):
            log.append(("enter", timeout))
            try:
                return super().select(timeout)
            finally:
                log.append(("leave",))

    monkeypatch.setattr(rest_mod, "selectors", types.SimpleNamespace(
        DefaultSelector=Recording, EVENT_READ=selectors.EVENT_READ))
    return log


def _await(predicate, what):
    import time

    deadline = time.monotonic() + _WOKE_WITHIN_S
    while not predicate():
        assert time.monotonic() < deadline, f"never saw: {what}"
        time.sleep(0.005)


def _refuses(port):
    import socket

    try:
        socket.create_connection(("127.0.0.1", port), timeout=5).close()
    except ConnectionRefusedError:
        return True
    return False


def _finishes(fn):
    """Run ``fn`` on a thread; its result, once it is back within the
    bound (the assertion is "came back", not how fast)."""
    out = []
    t = threading.Thread(target=lambda: out.append(fn()), daemon=True)
    t.start()
    t.join(_WOKE_WITHIN_S)
    assert not t.is_alive(), "still waiting: nothing woke it"
    return out[0]


def _bare_rest(**kw):
    hub = EndpointHub()
    rest = RestEndpoint(port=0, **kw)
    hub.add_endpoint(rest)
    rest.start()
    return rest


def _parked_poll(rest, entity="parked"):
    """A long-poll GET accepted and parked in its handler; returns the
    thread and the list its outcome lands in."""
    outcome = []

    def poll():
        try:
            with urllib.request.urlopen(
                    _url(rest, f"/actions/{entity}"), timeout=30) as r:
                outcome.append(r.status)
        except Exception as e:  # noqa: BLE001 - the outcome IS the error
            outcome.append(e)

    t = threading.Thread(target=poll, daemon=True)
    t.start()
    _await(lambda: entity in rest._queues, "the poll in its handler")
    return t, outcome


def test_shutdown_wakes_the_serve_loop(monkeypatch):
    """The loop blocks with NO timeout (nothing but a connection or a
    wake can end its wait), shutdown() is called while it is blocked,
    and shutdown() returns: it woke the loop, it did not wait a tick."""
    log = _recording_selectors(monkeypatch)
    rest = _bare_rest()
    port = rest.port
    _await(lambda: log and log[-1][0] == "enter", "the loop in its select")
    assert {e[1] for e in log if e[0] == "enter"} == {None}
    log.append(("shutdown",))
    _finishes(rest.shutdown)
    # the select that was blocked when shutdown() came is the one that
    # returned after it, and no other was entered: out at the first look
    assert [e[0] for e in log[-3:]] == ["enter", "shutdown", "leave"]
    assert _refuses(port)


def test_request_accepted_before_shutdown_is_answered():
    rest = _bare_rest(poll_timeout=1.0)
    t, outcome = _parked_poll(rest)
    _finishes(rest.shutdown)
    t.join(_WOKE_WITHIN_S)
    assert outcome == [204]  # its window ran out, answered all the same


def test_sever_closes_the_listener_first_and_cuts_connections():
    rest = _bare_rest(poll_timeout=20.0)
    port = rest.port
    t, outcome = _parked_poll(rest)
    srv = rest._server
    cut, listener_refused = srv.sever_connections, []

    def probing_cut():
        listener_refused.append(_refuses(port))
        return cut()

    srv.sever_connections = probing_cut
    assert _finishes(rest.sever) == 1
    assert listener_refused == [True]
    t.join(_WOKE_WITHIN_S)
    assert not t.is_alive() and len(outcome) == 1
    assert isinstance(outcome[0], Exception), outcome  # cut, not answered
    # process death, then the orchestrator's own hub.shutdown() over it
    # (Orchestrator.abandon): no loop is left to wake, nothing waits
    _finishes(rest.shutdown)


@pytest.mark.parametrize("case", ["twice", "before_start", "sever_twice",
                                  "sever_before_start"])
def test_shutdown_and_sever_where_no_loop_runs(case):
    rest = RestEndpoint(port=0)
    EndpointHub().add_endpoint(rest)
    stop = rest.sever if case.startswith("sever") else rest.shutdown
    if not case.endswith("before_start"):
        rest.start()
        port = rest.port
        _finishes(stop)
        assert _refuses(port)
    _finishes(stop)


def _bare_server():
    from http.server import BaseHTTPRequestHandler

    from namazu_tpu.endpoint.rest import _TrackingHTTPServer

    class Ok(BaseHTTPRequestHandler):
        def do_GET(self):
            self.send_response(204)
            self.end_headers()

        def log_message(self, *a):
            pass

    return _TrackingHTTPServer(("127.0.0.1", 0), Ok)


def test_shutdown_before_the_loop_is_in_waits_for_it():
    """``BaseServer.shutdown``'s contract: a shutdown() that comes
    before the loop's thread has entered waits for the loop, and the
    loop leaves at its first look."""
    srv = _bare_server()
    stopper = threading.Thread(target=srv.shutdown, daemon=True)
    stopper.start()
    loop = threading.Thread(target=srv.serve_forever, daemon=True)
    loop.start()
    for t in (stopper, loop):
        t.join(_WOKE_WITHIN_S)
        assert not t.is_alive()
    srv.server_close()
    srv.stop_pool()


def test_a_second_loop_is_not_spun_by_the_first_one_s_wake(monkeypatch):
    """The wake byte of one shutdown() does not keep a later loop of
    the same server busy, and that loop serves and is woken in turn."""
    log = _recording_selectors(monkeypatch)
    srv = _bare_server()
    port = srv.server_address[1]
    for _ in range(2):
        loop = threading.Thread(target=srv.serve_forever, daemon=True)
        loop.start()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/", timeout=30) as r:
            assert r.status == 204
        _finishes(srv.shutdown)
        loop.join(_WOKE_WITHIN_S)
        assert not loop.is_alive()
    # two connections, two wakes, at most one stale wake drained
    assert sum(e[0] == "enter" for e in log) <= 6
    srv.server_close()
    srv.stop_pool()
