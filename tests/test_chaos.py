"""Chaos plane (ISSUE 7): deterministic FaultPlan scheduling, the
fault seams (storage atomics, REST ingress backpressure + Retry-After,
wire faults, knowledge-client error classes), the crash-recovery event
journal + orchestrator resume, watchdog release attribution, and the
invariant harness + CLI."""

import json
import os
import socket
import threading
import time

import pytest

from namazu_tpu import chaos
from namazu_tpu.chaos import FaultPlan
from namazu_tpu.chaos.journal import EventJournal
from namazu_tpu.obs import federation, metrics
from namazu_tpu.obs.metrics import MetricsRegistry
from namazu_tpu.signal import PacketEvent
from namazu_tpu.utils import atomic, retry
from namazu_tpu.utils.sched_queue import ScheduledQueue


@pytest.fixture(autouse=True)
def fresh_state():
    """Isolated metrics + NO leftover fault plan, whatever a test did;
    the telemetry wiring an orchestrator or harness scenario started
    (self-relay, aggregator: the process's, obs/federation.py) stops
    with the test that started it."""
    old = metrics.set_registry(MetricsRegistry())
    metrics.configure(True)
    chaos.clear()
    yield
    chaos.clear()
    federation.reset()
    metrics.set_registry(old)
    metrics.configure(True)


# -- FaultPlan ----------------------------------------------------------


def test_fault_schedule_is_pure_and_seeded():
    """Same seed => bit-for-bit identical schedule; different seed =>
    different draws. The decision is a pure function of
    (seed, point, index) — no wall clock, no shared RNG."""
    a = FaultPlan(7, {"p": {"prob": 0.5}})
    b = FaultPlan(7, {"p": {"prob": 0.5}})
    assert a.schedule("p", 64) == b.schedule("p", 64)
    assert any(a.schedule("p", 64))
    assert not all(a.schedule("p", 64))
    c = FaultPlan(8, {"p": {"prob": 0.5}})
    assert c.schedule("p", 64) != a.schedule("p", 64)
    # points draw independently
    two = FaultPlan(7, {"p": {"prob": 0.5}, "q": {"prob": 0.5}})
    assert two.schedule("q", 64) != two.schedule("p", 64)


def test_fault_plan_at_after_max_fires():
    plan = FaultPlan(1, {"a": {"at": [1, 3]},
                         "b": {"prob": 1.0, "after": 2},
                         "c": {"prob": 1.0, "max_fires": 2}})
    assert [bool(plan.decide("a")) for _ in range(5)] == \
        [False, True, False, True, False]
    assert [bool(plan.decide("b")) for _ in range(4)] == \
        [False, False, True, True]
    assert sum(bool(plan.decide("c")) for _ in range(10)) == 2
    report = plan.report()
    assert report["consults"] == {"a": 5, "b": 4, "c": 10}
    assert report["fired"] == {"a": 2, "b": 2, "c": 2}
    # unknown points never fire and are not even counted
    assert plan.decide("nope") is None


def test_decide_disabled_is_noop_and_install_from_env():
    assert chaos.decide("anything") is None
    assert not chaos.enabled()
    env = {chaos.ENV_VAR: chaos.env_value(5, {"pt": {"prob": 1.0}})}
    plan = chaos.install_from_env(env)
    assert chaos.enabled() and plan.seed == 5
    assert chaos.decide("pt")["point"] == "pt"
    # an already-installed plan wins over the environment
    assert chaos.install_from_env(
        {chaos.ENV_VAR: chaos.env_value(9, {})}) is plan
    chaos.clear()
    with pytest.raises(ValueError, match="bad NMZ_CHAOS"):
        chaos.install_from_env({chaos.ENV_VAR: "not json"})


def test_fired_faults_counted_in_metrics():
    chaos.install(FaultPlan(1, {"pt": {"at": [0]}}))
    chaos.decide("pt")
    assert metrics.registry().value(
        "nmz_chaos_faults_injected_total", point="pt") == 1.0


# -- storage seams ------------------------------------------------------


def test_storage_rename_fault_keeps_old_content(tmp_path):
    path = str(tmp_path / "doc.json")
    atomic.atomic_write_json(path, {"gen": 1})
    chaos.install(FaultPlan(1, {"storage.rename": {"at": [0]}}))
    with pytest.raises(OSError, match="rename"):
        atomic.atomic_write_json(path, {"gen": 2})
    with open(path) as f:
        assert json.load(f) == {"gen": 1}
    # the failed write cleaned its temp (only a TORN write leaves one)
    assert [n for n in os.listdir(tmp_path)
            if atomic.is_tmp_artifact(n)] == []
    # next write (fault spent) succeeds
    atomic.atomic_write_json(path, {"gen": 3})
    with open(path) as f:
        assert json.load(f) == {"gen": 3}


def test_storage_tear_fault_leaves_stray_tmp_for_fsck(tmp_path):
    from namazu_tpu.storage import new_storage

    st = new_storage("naive", str(tmp_path / "st"))
    st.create()
    chaos.install(FaultPlan(1, {"storage.tear": {"at": [0]}}))
    with pytest.raises(OSError, match="torn"):
        st.create_new_working_dir()  # the meta rewrite tears
    chaos.clear()
    report = st.fsck(repair=False)
    assert report["tmp_artifacts"], "torn tmp must be a finding"
    st.fsck(repair=True)
    assert st.fsck()["tmp_artifacts"] == []


# -- retry delay hint (Retry-After) -------------------------------------


def test_retry_call_honors_delay_hint_capped_and_jittered():
    sleeps = []

    class Hinted(OSError):
        retry_after = 2.0

    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise Hinted("429")
        return "ok"

    assert retry.retry_call(
        flaky, (OSError,), attempts=4, base=0.01, cap=1.0,
        sleep=sleeps.append,
        delay_hint=lambda e: getattr(e, "retry_after", None)) == "ok"
    assert len(sleeps) == 2
    # hint 2.0: jitter can only LENGTHEN it, then the cap (1.0) wins
    assert all(s == 1.0 for s in sleeps), sleeps

    # uncapped hint: never below the server's stated window, <= +25%
    calls.clear()
    sleeps.clear()
    Hinted.retry_after = 0.2
    assert retry.retry_call(
        flaky, (OSError,), attempts=4, base=0.01, cap=10.0,
        sleep=sleeps.append,
        delay_hint=lambda e: getattr(e, "retry_after", None)) == "ok"
    assert all(0.2 <= s <= 0.25 for s in sleeps), sleeps


def test_transceiver_honors_retry_after_on_429(monkeypatch):
    from namazu_tpu.inspector.rest_transceiver import RestTransceiver

    tx = RestTransceiver("e1", "http://127.0.0.1:1", backoff_step=0.01,
                         backoff_max=5.0, post_attempts=3,
                         use_batch=True, flush_window=0.0)
    calls = []

    def overloaded(method, path, body=None, codec="json"):
        calls.append(path)
        if len(calls) < 2:
            tx._post_conn.last_retry_after = 0.05
            return 429, b'{"error": "ingress refused"}'
        tx._post_conn.last_retry_after = None
        return 200, b'{"accepted": 1, "duplicates": 0}'

    sleeps = []
    monkeypatch.setattr(tx._post_conn, "request", overloaded)
    monkeypatch.setattr(tx._stop, "wait", lambda d: sleeps.append(d))
    tx._post(PacketEvent.create("e1", "e1", "peer"))  # no raise
    assert len(calls) == 2
    # slept >= the server's Retry-After (jitter only lengthens), not
    # the 0.01 backoff
    assert len(sleeps) == 1 and 0.05 <= sleeps[0] <= 0.0625, sleeps
    assert metrics.registry().sample(
        "nmz_transport_retry_after_seconds").count == 1


# -- REST ingress backpressure ------------------------------------------


def test_rest_ingress_cap_rejects_with_retry_after():
    import urllib.request
    import urllib.error

    from namazu_tpu.endpoint.hub import EndpointHub
    from namazu_tpu.endpoint.rest import RestEndpoint

    # a bare endpoint + hub with NO orchestrator draining, so the
    # stuffed queue stays above the cap for the probe
    hub = EndpointHub()
    ep = RestEndpoint(port=0, ingress_cap=1, retry_after_s=0.5)
    hub.add_endpoint(ep)
    ep.start()
    try:
        hub.event_queue.put(PacketEvent.create("x", "x", "p"))
        ev = PacketEvent.create("e1", "e1", "peer")
        req = urllib.request.Request(
            f"http://127.0.0.1:{ep.port}/api/v3/events/e1/{ev.uuid}",
            data=ev.to_json().encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 429
        assert float(ei.value.headers["Retry-After"]) == 0.5
        assert metrics.registry().value(
            "nmz_ingress_rejections_total", endpoint="rest",
            reason="backpressure") == 1.0
        # below the cap the same POST goes through
        hub.event_queue.get_nowait()
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.status == 200
        assert hub.event_queue.qsize() == 1
    finally:
        ep.shutdown()


def test_transceiver_rides_out_429_storm_end_to_end():
    """A chaos 429 storm between a real transceiver and endpoint: every
    event still lands exactly once (the satellite contract: 429 never
    raises into inspector code while attempts remain)."""
    from namazu_tpu.inspector.rest_transceiver import RestTransceiver
    from namazu_tpu.orchestrator import Orchestrator
    from namazu_tpu.policy import create_policy
    from namazu_tpu.utils.config import Config

    cfg = Config({"explore_policy": "dumb", "rest_port": 0})
    orc = Orchestrator(cfg, create_policy("dumb"), collect_trace=True)
    orc.start()
    chaos.install(FaultPlan(3, {"endpoint.ingress.refuse": {
        "at": [0, 2], "status": 429, "retry_after": 0.02}}))
    tx = RestTransceiver(
        "e1", f"http://127.0.0.1:{orc.hub.endpoint('rest').port}",
        backoff_step=0.01, backoff_max=0.1, post_attempts=6,
        use_batch=True, flush_window=0.0)
    tx.start()
    try:
        waiters = [tx.send_event(PacketEvent.create("e1", "e1", "peer",
                                                    hint=f"h{i}"))
                   for i in range(4)]
        for q in waiters:
            assert q.get(timeout=10) is not None
    finally:
        chaos.clear()
        tx.shutdown()
        trace = orc.shutdown()
    assert len(trace) == 4  # exactly once despite the refusals
    assert metrics.registry().value(
        "nmz_ingress_rejections_total", endpoint="rest",
        reason="chaos") == 2.0


# -- event journal + crash recovery -------------------------------------


def _parked_orchestrator(tmp_path, run_id, port=0):
    """Orchestrator with a journal and 60s delays: everything parks."""
    from namazu_tpu.orchestrator import Orchestrator
    from namazu_tpu.policy import create_policy
    from namazu_tpu.utils.config import Config

    cfg = Config({
        "explore_policy": "random", "rest_port": port, "run_id": run_id,
        "event_journal_dir": str(tmp_path),
        "entity_liveness_timeout_s": 0.2,
        "explore_policy_param": {"seed": 0, "min_interval": "60s",
                                 "max_interval": "60s"},
    })
    policy = create_policy("random")
    policy.load_config(cfg)
    orc = Orchestrator(cfg, policy, collect_trace=True)
    return orc, policy


def test_journal_roundtrip_release_filtering_and_torn_tail(tmp_path):
    j = EventJournal(str(tmp_path))
    evs = [PacketEvent.create("e1", "e1", "p", hint=f"h{i}")
           for i in range(4)]
    j.append_events(evs, {"e1": "rest"})
    j.append_releases([evs[0].uuid, evs[3].uuid])
    j.close()
    un = EventJournal(str(tmp_path)).unreleased()
    assert [e.uuid for e, _ in un] == [evs[1].uuid, evs[2].uuid]
    assert all(ep == "rest" for _, ep in un)
    # torn tail (hard kill mid-append): dropped, the rest recovered
    with open(j.path, "ab") as f:
        f.write(b'{"k":"e","p":"rest","ev":{"cl')
    assert len(EventJournal(str(tmp_path)).unreleased()) == 2
    # duplicate event records (a recovery re-journaled) collapse
    j2 = EventJournal(str(tmp_path))
    j2.append_events([evs[1]], {"e1": "rest"})
    j2.close()
    assert len(EventJournal(str(tmp_path)).unreleased()) == 2


def test_orchestrator_recovers_parked_events_from_journal(tmp_path):
    """The crash-recovery loop in-process: kill (abandon) an
    orchestrator with a parked event, restart over the same journal
    dir, and the successor must dispatch it — released by the re-armed
    watchdog, attributed to it in the flight recorder."""
    from namazu_tpu import obs
    from namazu_tpu.obs import recorder as recorder_mod
    from namazu_tpu.obs.recorder import FlightRecorder

    old_rec = recorder_mod.set_recorder(FlightRecorder())
    try:
        orc_a, pol_a = _parked_orchestrator(tmp_path, "crash-a")
        orc_a.start()
        ev = PacketEvent.create("zombie", "zombie", "peer", hint="hx")
        orc_a.hub.post_event(ev, "local")
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and len(pol_a._queue) == 0:
            time.sleep(0.01)
        assert len(pol_a._queue) == 1  # parked (60s delay), journaled
        orc_a.abandon()

        orc_b, pol_b = _parked_orchestrator(tmp_path, "crash-b")
        orc_b.start()
        try:
            # recovered, parked again, then force-released by the
            # watchdog (the entity never speaks again) ~0.2s later
            deadline = time.monotonic() + 10
            trace_len = lambda: len(orc_b.trace)
            while time.monotonic() < deadline and trace_len() == 0:
                time.sleep(0.02)
            assert trace_len() == 1
            assert metrics.registry().value(
                "nmz_journal_recovered_events_total") == 1.0
            run = obs.trace_run("crash-b")
            rec = [e["json"] for e in run.snapshot()["records"]
                   if e["json"]["event"] == ev.uuid]
            assert rec and rec[0]["decision"].get("source") == "watchdog"
        finally:
            trace = orc_b.shutdown()
        assert [a.event_uuid for a in trace] == [ev.uuid]
        # the successor journaled the release: a THIRD orchestrator
        # over the same dir has nothing to recover
        assert EventJournal(str(tmp_path)).unreleased() == []
    finally:
        recorder_mod.set_recorder(old_rec)


def test_clean_shutdown_removes_completed_journal(tmp_path):
    orc, pol = _parked_orchestrator(tmp_path, "clean-a")
    orc.start()
    orc.hub.post_event(PacketEvent.create("e1", "e1", "p"), "local")
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and len(pol._queue) == 0:
        time.sleep(0.01)
    journal_path = orc.journal.path
    assert os.path.exists(journal_path)  # events were journaled
    orc.shutdown()  # flushes the parked event, then removes the WAL:
    # a completed run leaves nothing to recover OR to re-parse/grow
    # across restarts over the same --journal-dir
    assert not os.path.exists(journal_path)
    assert EventJournal(str(tmp_path)).unreleased() == []


# -- watchdog attribution ------------------------------------------------


def test_expedite_collect_returns_items():
    q = ScheduledQueue(seed=1)
    q.put("slow-a", 60.0, 60.0)
    q.put("keep", 60.0, 60.0)
    q.put("slow-b", 60.0, 60.0)
    assert q.expedite(lambda s: s.startswith("slow"),
                      collect=True) == ["slow-a", "slow-b"]
    assert q.expedite(lambda s: False, collect=True) == []
    assert q.expedite(lambda s: s == "keep") == 1  # count form intact


# -- knowledge client error classes -------------------------------------


def _framed_server(behaviors):
    """One-shot-per-connection fake sidecar; each connection pops the
    next behavior: 'half' = send a torn frame and close, 'ok' = answer
    {"ok": true}, 'hang' = read but never reply."""
    from namazu_tpu.endpoint.agent import read_frame, write_frame

    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)
    seen = []

    def loop():
        while behaviors:
            try:
                conn, _ = srv.accept()
            except OSError:
                return
            mode = behaviors.pop(0)
            seen.append(mode)
            try:
                read_frame(conn)
                if mode == "half":
                    conn.sendall(b"\x40\x00\x00\x00{\"ok\"")  # torn
                    conn.close()
                elif mode == "ok":
                    write_frame(conn, {"ok": True, "pong": True})
                    conn.close()
                elif mode == "hang":
                    time.sleep(3.0)
                    conn.close()
            except OSError:
                pass
        srv.close()

    t = threading.Thread(target=loop, daemon=True)
    t.start()
    return srv.getsockname()[1], seen


def test_knowledge_mid_stream_eof_retries_without_cooldown():
    from namazu_tpu.knowledge import KnowledgeClient

    port, seen = _framed_server(["half", "ok"])
    client = KnowledgeClient(f"127.0.0.1:{port}", timeout=5.0,
                             cooldown_s=30.0)
    resp = client.stats()
    assert resp is not None and resp.get("ok")  # transparent retry won
    assert client.available()  # NO cooldown burned
    assert seen == ["half", "ok"]
    client.close()


def test_knowledge_timeout_goes_straight_to_cooldown():
    from namazu_tpu.knowledge import KnowledgeClient

    port, seen = _framed_server(["hang", "ok"])
    client = KnowledgeClient(f"127.0.0.1:{port}", timeout=0.3,
                             cooldown_s=30.0)
    t0 = time.monotonic()
    assert client.stats() is None  # degraded, never raises
    # ONE connection only: a hung service is not re-asked on a fresh
    # socket (that would just double the stall)
    assert seen == ["hang"]
    assert time.monotonic() - t0 < 1.5
    assert not client.available()  # cooldown open
    client.close()


def test_knowledge_chaos_outage_seam_degrades():
    from namazu_tpu.knowledge import KnowledgeClient

    chaos.install(FaultPlan(1, {"knowledge.outage": {"at": [0]}}))
    client = KnowledgeClient("127.0.0.1:1", cooldown_s=0.0)
    assert client.stats() is None
    assert metrics.registry().value(
        "nmz_knowledge_outages_total") == 1.0


# -- harness + CLI -------------------------------------------------------


def test_harness_scenarios_green(tmp_path):
    from namazu_tpu.chaos.harness import run_scenario

    for name in ("wire_dup", "storage_torn", "edge_stale"):
        res = run_scenario(name, 1234, str(tmp_path / name), events=4)
        assert res["ok"], json.dumps(res["invariants"], default=str)
        assert all(v["ok"] for v in res["invariants"].values())


def test_harness_crash_restart_exactly_once(tmp_path):
    from namazu_tpu.chaos.harness import run_scenario

    res = run_scenario("crash_restart", 99, str(tmp_path), events=4)
    assert res["ok"], json.dumps(res["invariants"], default=str)
    inv = res["invariants"]
    assert inv["journal_recovered_all"]["recovered"] == 8  # 2 entities
    assert inv["exactly_once"]["doubles"] == {}


def test_abandon_kills_parked_releases(tmp_path):
    """An abandoned (simulated kill -9) orchestrator's policy must not
    dispatch its parked events when their delays later expire — the
    leaked daemon release worker would otherwise stamp a DEAD run's
    actions into whatever flight-recorder run is current by then
    (found as cross-test record contamination ~30s after the crash
    scenario)."""
    from namazu_tpu.orchestrator import Orchestrator
    from namazu_tpu.policy import create_policy
    from namazu_tpu.utils.config import Config

    cfg = Config({
        "rest_port": 0,
        "run_id": "abandon-zombie",
        "explore_policy": "random",
        "explore_policy_param": {
            "seed": 3, "min_interval": "300ms",
            "max_interval": "300ms",
            "fault_action_probability": 0.0,
            "shell_action_interval": 0},
    })
    policy = create_policy("random")
    policy.load_config(cfg)
    orc = Orchestrator(cfg, policy, collect_trace=True)
    orc.start()
    for i in range(4):
        orc.hub.post_events(
            [PacketEvent.create("z0", "z0", "peer", hint=f"h{i}")],
            "rest")
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and len(policy._queue) < 4:
        time.sleep(0.01)
    assert len(policy._queue) == 4  # parked on their 300ms delays
    orc.abandon()
    assert len(policy._queue) == 0  # taken by the "crash", unreleased
    trace_at_crash = len(orc.trace)
    time.sleep(0.5)  # past the delays: the zombie would fire here
    assert policy.action_out.qsize() == 0
    assert len(orc.trace) == trace_at_crash  # nothing released post-mortem


def test_chaos_cli_list_and_smoke(tmp_path, capsys):
    from namazu_tpu.cli import cli_main

    assert cli_main(["chaos", "--list"]) == 0
    out = capsys.readouterr().out
    assert "crash_restart" in out and "wire_drop" in out
    report_path = str(tmp_path / "report.json")
    rc = cli_main(["chaos", "--seed", "7", "--matrix", "wire_dup",
                   "--events", "4", "--workdir", str(tmp_path / "w"),
                   "--out", report_path])
    assert rc == 0
    report = json.load(open(report_path))
    assert report["ok"] and report["scenarios"][0]["scenario"] == "wire_dup"
    assert cli_main(["chaos", "--matrix", "no_such_scenario"]) == 2
