"""Event-plane fast path (doc/performance.md): the batch wire protocol,
the O(1)/batch queue primitives under it, and its semantics guarantees.

Covers the ISSUE-5 acceptance set: mixed old/new inspectors against one
endpoint, partial-batch acks, dedupe-ring correctness when a retried
batch POST replays, a multi-writer concurrency stress asserting no event
loss or duplication, and dispatch-order equivalence between batched and
per-event transport at flush window 0.
"""

import json
import threading
import time
import urllib.request

import pytest

from namazu_tpu import obs
from namazu_tpu.endpoint.hub import EndpointHub
from namazu_tpu.endpoint.local import LocalEndpoint
from namazu_tpu.endpoint.rest import ActionQueue, RestEndpoint
from namazu_tpu.inspector.rest_transceiver import RestTransceiver
from namazu_tpu.obs import federation, metrics, recorder
from namazu_tpu.obs.metrics import MetricsRegistry
from namazu_tpu.orchestrator import Orchestrator
from namazu_tpu.policy import create_policy
from namazu_tpu.signal import EventAcceptanceAction, PacketEvent
from namazu_tpu.utils.config import Config
from namazu_tpu.utils.mock_orchestrator import MockOrchestrator
from namazu_tpu.utils.sched_queue import QueueClosed, ScheduledQueue


@pytest.fixture(autouse=True)
def fresh_obs():
    old_reg = metrics.set_registry(MetricsRegistry())
    metrics.configure(True)
    old_rec = recorder.set_recorder(recorder.FlightRecorder())
    yield
    # an Orchestrator.start() wires the process's self-relay and
    # aggregator (obs/federation.py) and its shutdown leaves them
    federation.reset()
    metrics.set_registry(old_reg)
    metrics.configure(True)
    recorder.set_recorder(old_rec)


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


def _post_batch(rest, entity, events, expect=200):
    req = urllib.request.Request(
        _url(rest, f"/events/{entity}/batch"),
        data=json.dumps([ev.to_jsonable() for ev in events]).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        assert resp.status == expect
        return json.loads(resp.read())


def _get_actions(rest, entity, batch, linger_ms=0):
    url = _url(rest, f"/actions/{entity}?batch={batch}"
                     f"&linger_ms={linger_ms}")
    with urllib.request.urlopen(url, timeout=10) as resp:
        if resp.status == 204:
            return []
        return json.loads(resp.read())["actions"]


def _delete_batch(rest, entity, uuids):
    req = urllib.request.Request(
        _url(rest, f"/actions/{entity}"),
        data=json.dumps({"uuids": uuids}).encode(),
        method="DELETE",
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        assert resp.status == 200
        return json.loads(resp.read())


# -- ActionQueue: O(1) index + batch primitives -------------------------


def _act(entity="e", i=0):
    return PacketEvent.create(entity, entity, "p",
                              hint=f"h{i}").default_action()


def test_action_queue_put_many_peek_batch_delete_many():
    q = ActionQueue()
    actions = [_act(i=i) for i in range(5)]
    q.put_many(actions)
    assert len(q) == 5
    head = q.peek_batch(3, timeout=1)
    assert [a.uuid for a in head] == [a.uuid for a in actions[:3]]
    # peek did not remove
    assert len(q) == 5
    deleted, missing = q.delete_many(
        [actions[0].uuid, "nope", actions[4].uuid])
    assert [a.uuid for a in deleted] == [actions[0].uuid, actions[4].uuid]
    assert missing == ["nope"]
    assert len(q) == 3
    # FIFO preserved across deletions
    assert q.peek(timeout=1).uuid == actions[1].uuid


def test_action_queue_delete_is_uuid_indexed():
    q = ActionQueue()
    actions = [_act(i=i) for i in range(100)]
    q.put_many(actions)
    # delete from the tail: with the dict index this never scans
    for a in reversed(actions):
        assert q.delete(a.uuid) is a
    assert q.delete(actions[0].uuid) is None
    assert len(q) == 0


def test_action_queue_peek_batch_linger_fills_batch():
    q = ActionQueue()
    got = []

    def poller():
        got.extend(q.peek_batch(4, timeout=5, linger=0.5))

    t = threading.Thread(target=poller)
    t.start()
    time.sleep(0.05)
    q.put(_act(i=0))  # wakes the poller, linger window opens
    time.sleep(0.05)
    q.put_many([_act(i=1), _act(i=2), _act(i=3)])  # fills the batch
    t.join(timeout=5)
    assert not t.is_alive()
    assert len(got) == 4  # returned before the full linger elapsed


def test_action_queue_batch_peek_superseded_by_newer():
    q = ActionQueue()
    results = []

    def old_peek():
        results.append(q.peek_batch(8, timeout=10))

    t = threading.Thread(target=old_peek)
    t.start()
    time.sleep(0.1)
    assert q.peek_batch(8, timeout=0.05) == []
    t.join(timeout=5)
    assert not t.is_alive()
    assert results == [[]]


# -- ScheduledQueue: batch put/get --------------------------------------


def test_sched_queue_put_many_fifo_and_single_lock():
    q = ScheduledQueue(seed=0)
    q.put_many([(f"i{k}", 0.0, 0.0) for k in range(10)])
    assert [q.get(timeout=1) for _ in range(10)] == \
        [f"i{k}" for k in range(10)]


def test_sched_queue_put_at_many_matches_put_at_order():
    q = ScheduledQueue(seed=0, time_scale=0.01)
    q.put_at_many([("late", 0.5), ("early", 0.0), ("mid", 0.2)])
    assert [q.get(timeout=5) for _ in range(3)] == \
        ["early", "mid", "late"]


def test_sched_queue_get_batch_drains_ripe_in_order():
    q = ScheduledQueue(seed=0)
    q.put_many([(k, 0.0, 0.0) for k in range(6)])
    batch = q.get_batch(4, timeout=1)
    assert batch == [0, 1, 2, 3]
    assert q.get_batch(10, timeout=1) == [4, 5]


def test_sched_queue_get_batch_never_waits_for_unripe():
    q = ScheduledQueue(seed=0)
    q.put_at("now", 0.0)
    q.put_at("later", 5.0)
    t0 = time.monotonic()
    assert q.get_batch(10, timeout=1) == ["now"]
    assert time.monotonic() - t0 < 1.0  # did not wait for "later"


def test_sched_queue_put_many_raises_after_close():
    q = ScheduledQueue(seed=0)
    q.close()
    with pytest.raises(QueueClosed):
        q.put_many([("x", 0.0, 0.0)])


# -- batch wire protocol over real HTTP ---------------------------------


def test_batch_post_batch_get_multi_delete_roundtrip(rest_hub):
    hub, rest = rest_hub
    events = [PacketEvent.create("b0", "b0", "p", hint=f"h{i}")
              for i in range(5)]
    body = _post_batch(rest, "b0", events)
    assert body == {"accepted": 5, "duplicates": 0}
    deadline = time.time() + 10
    actions = []
    while len(actions) < 5 and time.time() < deadline:
        actions = _get_actions(rest, "b0", batch=10, linger_ms=100)
    assert [a["event_uuid"] for a in actions] == \
        [ev.uuid for ev in events]
    res = _delete_batch(rest, "b0", [a["uuid"] for a in actions])
    assert res["deleted"] == [a["uuid"] for a in actions]
    assert res["missing"] == []
    assert _get_actions(rest, "b0", batch=10) == []


def test_partial_batch_ack_reports_missing(rest_hub):
    hub, rest = rest_hub
    events = [PacketEvent.create("p0", "p0", "p", hint=f"h{i}")
              for i in range(3)]
    _post_batch(rest, "p0", events)
    deadline = time.time() + 10
    actions = []
    while len(actions) < 3 and time.time() < deadline:
        actions = _get_actions(rest, "p0", batch=10, linger_ms=100)
    a1, a2, a3 = actions
    res = _delete_batch(rest, "p0",
                        [a1["uuid"], "bogus-uuid", a3["uuid"]])
    assert res["deleted"] == [a1["uuid"], a3["uuid"]]
    assert res["missing"] == ["bogus-uuid"]
    # the unacked action is still queued, FIFO head
    remaining = _get_actions(rest, "p0", batch=10)
    assert [a["uuid"] for a in remaining] == [a2["uuid"]]


def test_retried_batch_post_dedupes(rest_hub):
    """A replayed batch POST (the 200 was lost in flight) must not
    double any event: every uuid rides the dedupe ring."""
    hub, rest = rest_hub
    events = [PacketEvent.create("d0", "d0", "p", hint=f"h{i}")
              for i in range(4)]
    first = _post_batch(rest, "d0", events)
    assert first == {"accepted": 4, "duplicates": 0}
    replay = _post_batch(rest, "d0", events)
    assert replay == {"accepted": 0, "duplicates": 4}
    # exactly one action per event, despite two POSTs
    deadline = time.time() + 10
    actions = []
    while len(actions) < 4 and time.time() < deadline:
        actions = _get_actions(rest, "d0", batch=100, linger_ms=200)
    assert len(actions) == 4
    _delete_batch(rest, "d0", [a["uuid"] for a in actions])
    assert _get_actions(rest, "d0", batch=100) == []


def test_malformed_batch_item_rejects_whole_batch(rest_hub):
    hub, rest = rest_hub
    good = PacketEvent.create("m0", "m0", "p")
    payload = [good.to_jsonable(), {"class": "NoSuchEvent"}]
    req = urllib.request.Request(
        _url(rest, "/events/m0/batch"),
        data=json.dumps(payload).encode(), method="POST")
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req)
    assert ei.value.code == 400
    # nothing was admitted: the whole batch can be retried verbatim
    res = _post_batch(rest, "m0", [good])
    assert res == {"accepted": 1, "duplicates": 0}


def test_batch_entity_mismatch_rejected(rest_hub):
    hub, rest = rest_hub
    ev = PacketEvent.create("right", "right", "p")
    req = urllib.request.Request(
        _url(rest, "/events/wrong/batch"),
        data=json.dumps([ev.to_jsonable()]).encode(), method="POST")
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req)
    assert ei.value.code == 400


# -- mixed old/new inspectors -------------------------------------------


def test_mixed_legacy_and_batched_inspectors_one_endpoint(rest_hub):
    """A pre-batch inspector (per-event POST/GET/DELETE) and a batched
    one share the endpoint; both get their actions."""
    hub, rest = rest_hub
    base = f"http://127.0.0.1:{rest.port}"
    legacy = RestTransceiver("old0", base, use_batch=False)
    fast = RestTransceiver("new0", base, use_batch=True,
                           flush_window=0.005, poll_linger=0.01)
    legacy.start()
    fast.start()
    try:
        n = 8
        legacy_chans = [legacy.send_event(
            PacketEvent.create("old0", "old0", "p", hint=f"h{i}"))
            for i in range(n)]
        fast_chans = [fast.send_event(
            PacketEvent.create("new0", "new0", "p", hint=f"h{i}"))
            for i in range(n)]
        for ch in legacy_chans + fast_chans:
            act = ch.get(timeout=15)
            assert isinstance(act, EventAcceptanceAction)
    finally:
        legacy.shutdown()
        fast.shutdown()


# -- concurrency stress: no loss, no duplication ------------------------


def test_concurrent_batch_writers_no_loss_no_duplication(rest_hub):
    """>= 4 writer threads, each replaying every batch POST once (the
    lost-200 retry pattern), against one endpoint: every event is
    dispatched exactly once."""
    hub, rest = rest_hub
    n_writers, n_batches, batch_n = 4, 6, 8
    per_writer = n_batches * batch_n
    errors = []
    results = {}

    def writer(w):
        entity = f"w{w}"
        try:
            sent = []
            for b in range(n_batches):
                events = [
                    PacketEvent.create(entity, entity, "p",
                                       hint=f"h{w}-{b}-{k}")
                    for k in range(batch_n)
                ]
                _post_batch(rest, entity, events)
                _post_batch(rest, entity, events)  # retry replay
                sent.extend(ev.uuid for ev in events)
            # drain exactly per_writer actions
            got = []
            deadline = time.time() + 30
            while len(got) < per_writer and time.time() < deadline:
                actions = _get_actions(rest, entity, batch=64,
                                       linger_ms=20)
                if actions:
                    res = _delete_batch(
                        rest, entity, [a["uuid"] for a in actions])
                    assert res["missing"] == []
                    got.extend(a["event_uuid"]
                               for a in actions)
            results[entity] = (sent, got)
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append((entity, repr(e)))

    threads = [threading.Thread(target=writer, args=(w,))
               for w in range(n_writers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    assert not errors, errors
    for entity, (sent, got) in results.items():
        # exactly once, in order: no loss, no duplication
        assert got == sent, f"{entity}: sent {len(sent)}, got {len(got)}"
        # and nothing left over
        assert _get_actions(rest, entity, batch=64) == []


# -- dispatch-order equivalence (acceptance criterion) ------------------


HINTS = [f"h{i}" for i in (3, 11, 7, 0, 9, 5)]
ENTITIES = ("e0", "e1")


def _transport_run(run_id, use_batch):
    """The same scripted workload through a real orchestrator + REST
    wire, per-event or batched at flush window 0 (synchronous flush):
    identical arrival order by construction, so the recorded dispatch
    order must match between transports."""
    cfg = Config({
        "rest_port": 0,
        "run_id": run_id,
        "explore_policy": "tpu_search",
        "explore_policy_param": {
            "search_on_start": False,
            "max_interval": 0,  # zero delays: release order = arrival
            "seed": 7,
        },
    })
    policy = create_policy("tpu_search")
    policy.load_config(cfg)
    orc = Orchestrator(cfg, policy, collect_trace=True)
    orc.start()
    port = orc.hub.endpoint("rest").port
    txs = {
        e: RestTransceiver(e, f"http://127.0.0.1:{port}",
                           use_batch=use_batch, flush_window=0.0,
                           poll_linger=0.005)
        for e in ENTITIES
    }
    for t in txs.values():
        t.start()
    try:
        chans = []
        for hint in HINTS:
            for e in ENTITIES:
                ev = PacketEvent.create(e, e, "peer", hint=hint)
                chans.append(txs[e].send_event(ev))
        for ch in chans:
            assert ch.get(timeout=15) is not None
    finally:
        for t in txs.values():
            t.shutdown()
        orc.shutdown()
    return orc.trace


def test_batched_and_per_event_transport_same_dispatch_order():
    from namazu_tpu.obs import export

    _transport_run("order-perevent", use_batch=False)
    _transport_run("order-batched", use_batch=True)
    run_a = obs.trace_run("order-perevent")
    run_b = obs.trace_run("order-batched")
    assert run_a is not None and run_b is not None
    lines_a = export.order_lines(run_a)
    lines_b = export.order_lines(run_b)
    assert len(lines_a) == len(HINTS) * len(ENTITIES)
    diff = export.diff_order(lines_a, lines_b,
                             "order-perevent", "order-batched")
    assert diff == "", f"dispatch order diverged:\n{diff}"


# -- policy batch entry point -------------------------------------------


def test_tpu_policy_batch_decisions_match_scalar():
    import numpy as np

    from namazu_tpu.policy.tpu import TPUSearchPolicy

    pol = TPUSearchPolicy()
    pol.max_interval = 0.1
    pol.seed = 7
    hints = [f"src->dst:{i}" for i in range(40)]
    # hash-fallback path
    batch = pol._delays_for_many(hints)
    assert [pol._delay_for(h) for h in hints] == \
        pytest.approx(list(batch))
    # installed-table path
    pol.install_table(np.linspace(0.0, 0.05, pol.H))
    batch = pol._delays_for_many(hints)
    assert [pol._delay_for(h) for h in hints] == \
        pytest.approx(list(batch))


def test_tpu_policy_queue_events_delay_mode_emits_all():
    from namazu_tpu.utils.policy_tester import drain_actions

    cfg = Config({"explore_policy_param": {
        "search_on_start": False, "max_interval": 0, "seed": 7}})
    pol = create_policy("tpu_search")
    pol.load_config(cfg)
    events = [PacketEvent.create("qa", "qa", "p", hint=f"h{i}")
              for i in range(20)]
    pol.queue_events(events)
    actions = drain_actions(pol, len(events), timeout=10)
    assert [a.event_uuid for a in actions] == [ev.uuid for ev in events]
    pol.shutdown()


def test_tpu_policy_queue_events_reorder_mode_flushes_on_shutdown():
    cfg = Config({"explore_policy_param": {
        "search_on_start": False, "max_interval": 50, "seed": 7,
        "release_mode": "reorder", "reorder_window": 3600_000,
        "reorder_gap": 0}})
    pol = create_policy("tpu_search")
    pol.load_config(cfg)
    events = [PacketEvent.create("rb", "rb", "p", hint=f"h{i}")
              for i in range(10)]
    pol.queue_events(events)
    # nothing released yet: the window is an hour wide
    assert pol.action_out.qsize() == 0
    pol.shutdown()
    from namazu_tpu.policy.base import POLICY_DONE
    from namazu_tpu.utils.policy_tester import drain_actions

    actions = drain_actions(pol, len(events), timeout=10)
    assert {a.event_uuid for a in actions} == {ev.uuid for ev in events}
    assert pol.action_out.get(timeout=5) is POLICY_DONE


# -- obs: batch histograms ----------------------------------------------


def test_event_batch_and_rtt_histograms_record():
    obs.event_batch("ingress", 17)
    obs.transport_rtt("post_batch", 0.004)
    names = {fam["name"]
             for fam in metrics.registry().to_jsonable()["metrics"]}
    assert "nmz_event_batch_size" in names
    assert "nmz_transport_rtt_seconds" in names


# -- bench: per-metric gating + pipeline smoke --------------------------


def _bench():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(__file__), "..",
                              "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_gate_is_per_metric():
    bench = _bench()
    history = [
        {"platform": "loopback", "metric": "events_dispatched_per_sec",
         "value": 10_000.0},
        # a legacy scorer record (no metric field) on another platform
        {"platform": "tpu", "schedules_per_sec": 5_000_000.0},
    ]
    # same metric, same platform: regression detected
    ok, reasons, baseline = bench.gate_record(
        {"platform": "loopback", "metric": "events_dispatched_per_sec",
         "value": 1_000.0}, history, threshold_pct=30)
    assert not ok and "events_dispatched_per_sec regression" in reasons[0]
    assert baseline["value"] == 10_000.0
    # scorer records never baseline against pipeline records
    ok, reasons, _ = bench.gate_record(
        {"platform": "loopback", "schedules_per_sec": 1.0},
        history, threshold_pct=30)
    assert ok and "no 'loopback' history" in reasons[0]


def test_pipeline_smoke_in_process():
    bench = _bench()
    rate = bench.run_pipeline(32, 2, use_batch=True, flush_window=0.0,
                              batch_max=8, run_id="pysmoke",
                              poll_linger=0.005)
    assert rate > 0


# -- graceful degradation against a pre-batch orchestrator --------------


def test_batch_poll_downgrades_on_single_action_body():
    """A pre-PR server ignores ?batch and answers the per-event wire
    (one action object as the body): the receive path must dispatch it
    and fall back to legacy transport, not kill the receive thread."""
    tx = RestTransceiver("lg0", "http://127.0.0.1:1", use_batch=True)
    action = _act(entity="lg0")
    calls = []

    def fake(method, path, body=None, codec="json"):
        calls.append((method, path))
        if method == "GET":
            return 200, action.to_json().encode()
        assert method == "DELETE" and path.endswith(f"/{action.uuid}")
        return 404, b""  # replayed ack: already gone server-side

    tx._recv_conn.request = fake
    got = tx._poll_once()
    assert [a.uuid for a in got] == [action.uuid]
    assert tx.use_batch is False  # downgraded for the rest of its life


def test_batch_post_downgrades_on_missing_route():
    """A pre-PR server 400s the batch POST (its per-event route reads
    'batch' as a uuid): the chunk must be delivered per-event instead."""
    tx = RestTransceiver("lg1", "http://127.0.0.1:1", use_batch=True,
                         flush_window=0.0)
    posted = []

    def fake(method, path, body=None, codec="json"):
        if path.endswith("/batch"):
            return 400, b'{"error": "url entity/uuid do not match"}'
        posted.append(path)
        return 200, b"{}"

    tx._post_conn.request = fake
    events = [PacketEvent.create("lg1", "lg1", "p", hint=f"h{i}")
              for i in range(3)]
    tx._post_batch_once(events)
    assert len(posted) == 3
    assert all(f"/events/lg1/{ev.uuid}" in p
               for ev, p in zip(events, posted))
    assert tx.use_batch is False


def test_gate_never_compares_transport_modes():
    bench = _bench()
    history = [{"platform": "loopback",
                "metric": "events_dispatched_per_sec",
                "mode": "batched", "value": 1800.0}]
    # a per-event run is ~14x slower by design — not a regression
    ok, reasons, _ = bench.gate_record(
        {"platform": "loopback", "metric": "events_dispatched_per_sec",
         "mode": "per-event", "value": 130.0}, history,
        threshold_pct=30)
    assert ok and "no 'loopback' history" in reasons[0]


def test_queue_events_isolates_poison_event():
    """One poison event in a drained batch must not lose the rest, and
    must be reported so the orchestrator skips its lifecycle marks."""
    from namazu_tpu.policy.base import ExplorePolicy

    class Poisoned(ExplorePolicy):
        NAME = "poison-test"

        def __init__(self):
            super().__init__()
            self.got = []

        def queue_event(self, event):
            if event is poison:
                raise ValueError("poison")
            self.got.append(event)

    events = [PacketEvent.create("x", "x", "p", hint=f"h{i}")
              for i in range(3)]
    poison = events[1]
    pol = Poisoned()
    rejected = pol.queue_events(events)
    assert [e.uuid for e in pol.got] == [events[0].uuid, events[2].uuid]
    assert rejected == [poison]


def test_post_retries_transient_5xx(monkeypatch):
    """A 5xx response rides the bounded POST retry (the pre-batch
    urllib path raised HTTPError for these, which retried)."""
    tx = RestTransceiver("t5", "http://127.0.0.1:1", use_batch=False,
                         backoff_step=0.01, backoff_max=0.02,
                         post_attempts=4)
    calls = []

    def flaky(method, path, body=None):
        calls.append(1)
        return (503, b"") if len(calls) < 3 else (200, b"{}")

    monkeypatch.setattr(tx._post_conn, "request", flaky)
    tx._post(PacketEvent.create("t5", "t5", "p"))  # no raise
    assert len(calls) == 3


def test_flush_groups_cross_entity_events_by_entity(rest_hub):
    """send_event legitimately carries a neighbor entity's events; the
    coalesced flush must route each to its OWN entity's batch route
    instead of 400ing (and wrongly downgrading) on a mixed batch."""
    hub, rest = rest_hub
    tx = RestTransceiver("ce0", f"http://127.0.0.1:{rest.port}",
                         use_batch=True, flush_window=0.0)
    other = RestTransceiver("ce1", f"http://127.0.0.1:{rest.port}",
                            use_batch=True, flush_window=0.0)
    tx.start()
    other.start()  # polls ce1's queue; ce1's events are SENT via tx
    try:
        ch_own = tx.send_event(
            PacketEvent.create("ce0", "ce0", "p", hint="own"))
        ch_cross = tx.send_event(
            PacketEvent.create("ce1", "ce1", "p", hint="cross"))
        assert ch_own.get(timeout=15) is not None
        assert tx.use_batch is True  # no spurious legacy downgrade
        # the cross-entity action routes to ce1's poller, whose
        # transceiver doesn't hold the waiter — just verify delivery
        # happened by draining ce1's queue being empty server-side
        deadline = time.time() + 10
        while len(rest._queue_for("ce1")) and time.time() < deadline:
            time.sleep(0.02)
        assert len(rest._queue_for("ce1")) == 0
    finally:
        tx.shutdown()
        other.shutdown()


def test_action_queue_linger_superseded_mid_linger_yields():
    """A newer poll arriving while an older one lingers supersedes it:
    only one poller is handed the actions (double delivery would ack
    the same action twice across transceiver generations)."""
    q = ActionQueue()
    res = {}

    def old_poll():
        res["old"] = q.peek_batch(8, timeout=5, linger=2.0)

    t = threading.Thread(target=old_poll)
    t.start()
    time.sleep(0.05)
    q.put(_act(i=0))  # old poller enters its linger window
    time.sleep(0.1)
    new = q.peek_batch(8, timeout=1, linger=0.0)  # supersedes
    t.join(timeout=5)
    assert not t.is_alive()
    assert res["old"] == []  # yielded well before the 2s linger
    assert len(new) == 1  # the newer poller got the action
