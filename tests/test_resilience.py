"""Resilience plane (ISSUE 4): atomic writes, crash quarantine + fsck,
retry/backoff, the endpoint hub's unroutable-action accounting, the
liveness watchdog, ScheduledQueue.expedite, the REST transceiver's
bounded POST retry, and run_cmd's clean-in-finally contract."""

import contextlib
import json
import os
import subprocess
import threading
import time
import types
import urllib.error

import pytest

from namazu_tpu.obs import metrics
from namazu_tpu.obs.metrics import MetricsRegistry
from namazu_tpu.signal import PacketEvent
from namazu_tpu.storage import load_storage, new_storage
from namazu_tpu.storage.base import StorageError
from namazu_tpu.utils import atomic, retry
from namazu_tpu.utils.sched_queue import ScheduledQueue
from namazu_tpu.utils.trace import SingleTrace


@pytest.fixture(autouse=True)
def fresh_registry():
    old = metrics.set_registry(MetricsRegistry())
    metrics.configure(True)
    yield
    metrics.set_registry(old)
    metrics.configure(True)


# -- atomic writes ------------------------------------------------------


def test_atomic_write_roundtrip(tmp_path):
    path = str(tmp_path / "doc.json")
    atomic.atomic_write_json(path, {"a": 1})
    with open(path) as f:
        assert json.load(f) == {"a": 1}
    atomic.atomic_write_json(path, {"a": 2})
    with open(path) as f:
        assert json.load(f) == {"a": 2}


def test_atomic_write_survives_rename_failure(tmp_path, monkeypatch):
    """An exception at rename time must leave the previous content
    intact and no temp file behind."""
    path = str(tmp_path / "doc.json")
    atomic.atomic_write_json(path, {"a": 1})

    def boom(src, dst):
        raise OSError("injected rename failure")

    monkeypatch.setattr(os, "replace", boom)
    with pytest.raises(OSError, match="injected"):
        atomic.atomic_write_json(path, {"a": 2})
    monkeypatch.undo()
    with open(path) as f:
        assert json.load(f) == {"a": 1}  # old content intact
    assert [n for n in os.listdir(tmp_path)
            if atomic.is_tmp_artifact(n)] == []


def test_atomic_write_never_exposes_partial(tmp_path):
    """The destination path never holds a prefix of the new content:
    until the rename, reads see the old document."""
    path = str(tmp_path / "doc.json")
    atomic.atomic_write_json(path, {"gen": 0})
    stop = threading.Event()
    bad = []

    def reader():
        while not stop.is_set():
            try:
                with open(path) as f:
                    json.load(f)
            except ValueError:
                bad.append(1)

    t = threading.Thread(target=reader)
    t.start()
    try:
        for gen in range(1, 200):
            atomic.atomic_write_json(path, {"gen": gen, "pad": "x" * 4096})
    finally:
        stop.set()
        t.join()
    assert not bad


# -- retry/backoff ------------------------------------------------------


def test_retry_call_succeeds_after_transients():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert retry.retry_call(flaky, (OSError,), attempts=4,
                            sleep=lambda s: None) == "ok"
    assert len(calls) == 3


def test_retry_call_gives_up_and_raises():
    calls = []

    def always(n=calls):
        n.append(1)
        raise OSError("down")

    with pytest.raises(OSError, match="down"):
        retry.retry_call(always, (OSError,), attempts=3,
                         sleep=lambda s: None)
    assert len(calls) == 3


def test_retry_call_does_not_catch_unlisted():
    with pytest.raises(ValueError):
        retry.retry_call(lambda: (_ for _ in ()).throw(ValueError("x")),
                         (OSError,), attempts=5, sleep=lambda s: None)


def test_backoff_delays_capped_and_jittered():
    import random

    delays = list(retry.backoff_delays(8, base=1.0, cap=4.0,
                                       rng=random.Random(7)))
    assert len(delays) == 8
    assert all(0.0 <= d <= 4.0 for d in delays)


# -- crash quarantine ---------------------------------------------------


def _trace(hints=("h0", "h1")):
    t = SingleTrace()
    for h in hints:
        a = PacketEvent.create("n0", "n0", "peer", hint=h).default_action()
        a.mark_triggered()
        t.append(a)
    return t


def _storage_with_crash(tmp_path):
    """Two complete runs + one with a trace but no result (the signature
    of a run SIGKILLed between record_new_trace and record_result)."""
    from namazu_tpu.signal.base import HINT_SPACE

    st = new_storage("naive", str(tmp_path / "st"))
    st.create()
    for ok in (True, False):
        st.create_new_working_dir()
        st.record_new_trace(_trace())
        # stamped like run_cmd records them, so history ingest (which
        # skips foreign hint spaces) sees the complete runs
        st.record_result(ok, 1.0, metadata={"hint_space": HINT_SPACE})
    st.create_new_working_dir()
    st.record_new_trace(_trace(("h-crash",)))
    # crash: no result, no close
    return str(tmp_path / "st")


def test_init_quarantines_crashed_run(tmp_path):
    path = _storage_with_crash(tmp_path)
    st = load_storage(path)  # init() runs the quarantine sweep
    assert st.quarantined_runs() == [2]
    assert os.path.exists(os.path.join(st.run_dir(2), "INCOMPLETE"))
    with pytest.raises(StorageError, match="quarantined"):
        st.get_stored_history(2)
    with pytest.raises(StorageError, match="quarantined"):
        st.is_successful(2)
    # the complete prefix is untouched
    assert st.nr_stored_histories() == 2
    assert len(st.get_stored_history(0)) == 2


def test_record_result_clears_stale_marker(tmp_path):
    """A concurrent scrape may quarantine the in-flight run in its
    trace-no-result window; the result landing clears the marker."""
    st = new_storage("naive", str(tmp_path / "st"))
    st.create()
    wd = st.create_new_working_dir()
    st.record_new_trace(_trace())
    load_storage(str(tmp_path / "st"))  # the concurrent scrape
    assert os.path.exists(os.path.join(wd, "INCOMPLETE"))
    st.record_result(True, 1.0)
    assert not os.path.exists(os.path.join(wd, "INCOMPLETE"))
    assert st.is_successful(0)


def test_quarantined_runs_invisible_to_analytics(tmp_path):
    from namazu_tpu.obs import analytics

    path = _storage_with_crash(tmp_path)
    st = load_storage(path)
    payload = analytics.compute_payload(storage=st, recorder_runs=[])
    assert payload["reproduction"]["runs"] == 2
    assert payload["reproduction"]["runs_quarantined"] == 1
    assert payload["coverage"]["runs"] == 2
    assert payload["coverage"]["runs_quarantined"] == 1
    # the crashed run's digest must not count toward coverage
    assert payload["coverage"]["unique_interleavings"] == 1


def test_quarantined_runs_invisible_to_history_ingest(tmp_path):
    """The search plane's shared ingest (policy/tpu.py + sidecar) must
    never train on a quarantined run's trace."""
    from namazu_tpu.models.ingest import IngestParams, ingest_history

    class FakeSearch:
        def __init__(self):
            self.executed = []

        def set_occupied_buckets(self, buckets):
            pass

        def seed_population(self, seeds):
            pass

        def has_failure_signature(self, digest):
            return False

        def add_executed_trace(self, enc, reproduced, arrival=None):
            self.executed.append(reproduced)

        def add_failure_trace(self, enc):
            pass

        def embed_batch(self):
            # ingest wraps its adds in the search's batch; a stub has
            # nothing to defer
            return contextlib.nullcontext(types.SimpleNamespace(calls=0, groups=0))

    path = _storage_with_crash(tmp_path)
    st = load_storage(path)
    search = FakeSearch()
    ingest_history(search, st, IngestParams())
    # two complete runs ingested; the quarantined third is invisible
    assert len(search.executed) == 2


def test_fsck_reports_and_repairs(tmp_path):
    path = _storage_with_crash(tmp_path)
    # one more crash mode: a dir allocated but killed before any write
    st0 = load_storage(path)
    st0.create_new_working_dir()
    # and a stray atomic-write temp from a hard kill
    stray = os.path.join(path, "storage.json.123.tmp")
    open(stray, "w").close()

    st = load_storage(path)
    report = st.fsck(repair=False)
    assert report["quarantined"] == [2]
    assert report["incomplete_unmarked"] == [3]
    assert stray in report["tmp_artifacts"]
    assert report["complete"] == 2

    report = st.fsck(repair=True)
    assert report["quarantined"] == [2, 3]
    assert report["repaired_runs"] == [3]
    assert report["incomplete_unmarked"] == []
    assert not os.path.exists(stray)
    # repair is idempotent and the storage stays loadable
    st2 = load_storage(path)
    assert st2.fsck()["quarantined"] == [2, 3]
    assert st2.nr_stored_histories() == 2


def test_tools_fsck_cli(tmp_path, capsys):
    from namazu_tpu.cli import cli_main

    path = _storage_with_crash(tmp_path)
    # the crashed run is auto-quarantined by init() — a HANDLED state,
    # reported but not a failing exit (a campaign that retried an
    # aborted slot must not fail CI's post-campaign fsck)
    assert cli_main(["tools", "fsck", path, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["quarantined"] == [2]
    # an UNMARKED incomplete dir (dir allocated, killed before any
    # write) is a finding: exit 1 until repaired
    load_storage(path).create_new_working_dir()
    assert cli_main(["tools", "fsck", path, "--json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["incomplete_unmarked"] == [3]
    # --repair quarantines it but still exits 1 (the storage NEEDED
    # repair; scripts must notice)
    assert cli_main(["tools", "fsck", path, "--repair", "--json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["repaired_runs"] == [3]
    # after repair everything is handled: clean exit
    assert cli_main(["tools", "fsck", path]) == 0
    # clean storage exits 0
    st = new_storage("naive", str(tmp_path / "clean"))
    st.create()
    st.create_new_working_dir()
    st.record_new_trace(_trace())
    st.record_result(True, 1.0)
    assert cli_main(["tools", "fsck", str(tmp_path / "clean")]) == 0


# -- endpoint hub: unroutable accounting --------------------------------


def test_unroutable_actions_counted_and_warned_once(caplog):
    import logging

    from namazu_tpu.endpoint.hub import EndpointHub

    hub = EndpointHub()
    ev = PacketEvent.create("ghost", "ghost", "peer")
    with caplog.at_level(logging.WARNING, logger="namazu_tpu.endpoint"):
        for _ in range(5):
            hub.send_action(ev.default_action())
    warnings = [r for r in caplog.records if r.levelno >= logging.WARNING]
    assert len(warnings) == 1  # rate-limited: one WARNING per entity
    assert metrics.registry().value(
        "nmz_actions_unroutable_total", entity="ghost") == 5.0


def test_unroutable_warning_rearms_after_event(caplog):
    import logging

    from namazu_tpu.endpoint.hub import EndpointHub
    from namazu_tpu.endpoint.local import LocalEndpoint

    hub = EndpointHub()
    hub.add_endpoint(LocalEndpoint())
    ev = PacketEvent.create("ghost", "ghost", "peer")
    with caplog.at_level(logging.WARNING, logger="namazu_tpu.endpoint"):
        hub.send_action(ev.default_action())      # warn #1
        hub.post_event(ev, "local")               # entity speaks: re-arm
        # remove the route again to force a drop (the routing table is
        # sharded now — tenancy/shard.py; clear every shard's routes)
        for shard in hub._routes._shards:
            with shard.lock:
                shard.route.clear()
        hub.send_action(ev.default_action())      # warn #2
    warnings = [r for r in caplog.records if r.levelno >= logging.WARNING]
    assert len(warnings) == 2


# -- ScheduledQueue.expedite + the liveness watchdog --------------------


def test_sched_queue_expedite():
    q = ScheduledQueue(seed=1)
    q.put("slow-a", 60.0, 60.0)
    q.put("keep", 60.0, 60.0)
    q.put("slow-b", 60.0, 60.0)
    assert q.expedite(lambda item: item.startswith("slow")) == 2
    assert q.get(timeout=1.0) == "slow-a"  # FIFO among expedited
    assert q.get(timeout=1.0) == "slow-b"
    with pytest.raises(TimeoutError):
        q.get(timeout=0.05)  # "keep" still parked
    assert len(q) == 1


def test_watchdog_force_releases_stalled_entity():
    from namazu_tpu.orchestrator import Orchestrator
    from namazu_tpu.policy import create_policy
    from namazu_tpu.utils.config import Config

    cfg = Config({
        "explore_policy": "random",
        # 60 SECONDS (bare numbers are ms): only a force-release can
        # drain the queue within this test's lifetime
        "explore_policy_param": {"min_interval": "60s",
                                 "max_interval": "60s"},
        "entity_liveness_timeout_s": 0.1,
    })
    policy = create_policy("random")
    policy.load_config(cfg)
    orc = Orchestrator(cfg, policy, collect_trace=True)
    orc.start()
    try:
        ev = PacketEvent.create("zombie", "zombie", "peer")
        orc.hub.post_event(ev, "local")
        # wait for the event to pass the event loop into the delay queue
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and len(policy._queue) == 0:
            time.sleep(0.01)
        assert len(policy._queue) == 1
        # entity goes silent past the timeout; the watchdog (or an
        # explicit sweep) declares it dead and releases its event
        time.sleep(0.25)
        orc.sweep_stalled_entities()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and len(policy._queue):
            time.sleep(0.01)
        assert len(policy._queue) == 0  # released ~60s early
        assert metrics.registry().value(
            "nmz_entity_stalled_total", entity="zombie") == 1.0
        # a second sweep must not double-count the same stall
        orc.sweep_stalled_entities()
        assert metrics.registry().value(
            "nmz_entity_stalled_total", entity="zombie") == 1.0
    finally:
        trace = orc.shutdown()
    assert [a.entity_id for a in trace] == ["zombie"]


def test_duplicate_event_post_is_idempotent():
    """The transceiver retries a POST whose 200 was lost after the
    server processed it; the REST endpoint must dedupe by event uuid or
    the retry doubles the event in the trace."""
    import urllib.request

    from namazu_tpu.orchestrator import Orchestrator
    from namazu_tpu.policy import create_policy
    from namazu_tpu.utils.config import Config

    cfg = Config({"explore_policy": "dumb", "rest_port": 0})
    policy = create_policy("dumb")
    orc = Orchestrator(cfg, policy, collect_trace=True)
    orc.start()
    try:
        port = orc.hub.endpoint("rest").port
        ev = PacketEvent.create("e1", "e1", "peer")
        url = f"http://127.0.0.1:{port}/api/v3/events/e1/{ev.uuid}"
        for i in range(2):  # the POST and its retry
            req = urllib.request.Request(
                url, data=ev.to_json().encode(),
                headers={"Content-Type": "application/json"},
                method="POST")
            with urllib.request.urlopen(req, timeout=10) as resp:
                body = json.load(resp)
                assert resp.status == 200
                assert body.get("duplicate", False) is bool(i)
    finally:
        trace = orc.shutdown()
    assert len(trace) == 1  # one event, despite two POSTs


# -- REST transceiver: bounded POST retry -------------------------------


def test_rest_post_retries_transients(monkeypatch):
    from namazu_tpu.inspector.rest_transceiver import RestTransceiver

    tx = RestTransceiver("e1", "http://127.0.0.1:1", backoff_step=0.01,
                         backoff_max=0.02, post_attempts=4,
                         use_batch=False)
    calls = []

    def flaky(method, path, body=None, codec="json"):
        calls.append(path)
        if len(calls) < 3:
            raise ConnectionRefusedError("connection refused")
        return 200, b"{}"

    monkeypatch.setattr(tx._post_conn, "request", flaky)
    tx._post(PacketEvent.create("e1", "e1", "peer"))  # no raise
    assert len(calls) == 3


def test_rest_post_exhausts_and_raises(monkeypatch):
    from namazu_tpu.inspector.rest_transceiver import RestTransceiver

    tx = RestTransceiver("e1", "http://127.0.0.1:1", backoff_step=0.01,
                         backoff_max=0.02, post_attempts=3,
                         use_batch=False)
    calls = []

    def down(method, path, body=None):
        calls.append(1)
        raise ConnectionRefusedError("still down")

    monkeypatch.setattr(tx._post_conn, "request", down)
    with pytest.raises(OSError):
        tx._post(PacketEvent.create("e1", "e1", "peer"))
    assert len(calls) == 3


def test_rest_batch_flush_retries_and_dedupes_serverside(monkeypatch):
    """The batch POST path carries the same bounded-retry policy: a
    flush whose 200 was lost replays the whole batch (the endpoint's
    dedupe ring absorbs the duplicates server-side)."""
    from namazu_tpu.inspector.rest_transceiver import RestTransceiver

    tx = RestTransceiver("e1", "http://127.0.0.1:1", backoff_step=0.01,
                         backoff_max=0.02, post_attempts=4,
                         use_batch=True, flush_window=0.0)
    calls = []

    def flaky(method, path, body=None, codec="json"):
        calls.append((method, path))
        if len(calls) < 3:
            raise ConnectionResetError("peer vanished mid-response")
        return 200, b'{"accepted": 1, "duplicates": 0}'

    monkeypatch.setattr(tx._post_conn, "request", flaky)
    tx._post(PacketEvent.create("e1", "e1", "peer"))  # no raise
    assert len(calls) == 3
    assert all(path.endswith("/events/e1/batch") for _, path in calls)


def test_rest_shutdown_joins_receive_thread(monkeypatch):
    from namazu_tpu.inspector.rest_transceiver import RestTransceiver

    tx = RestTransceiver("e1", "http://127.0.0.1:1", backoff_step=0.01)
    monkeypatch.setattr(tx, "_poll_once",
                        lambda: (_ for _ in ()).throw(OSError("down")))
    tx.start()
    assert tx._thread.is_alive()
    tx.shutdown(join_timeout=5.0)
    assert not tx._thread.is_alive()


# -- run_cmd: clean-in-finally + phase deadlines ------------------------


def _write_experiment(tmp_path, run, validate="true",
                      clean='touch "$NMZ_WORKING_DIR/cleaned"'):
    materials = tmp_path / "materials"
    materials.mkdir(exist_ok=True)
    config = tmp_path / "config.toml"
    config.write_text(
        'explore_policy = "dumb"\n'
        f'run = {json.dumps(run)}\n'
        f'validate = {json.dumps(validate)}\n'
        f'clean = {json.dumps(clean)}\n'
    )
    return config, materials


def test_clean_runs_after_failed_run_script(tmp_path):
    from namazu_tpu.cli import cli_main

    config, materials = _write_experiment(tmp_path, run="false")
    storage = str(tmp_path / "st")
    assert cli_main(["init", str(config), str(materials), storage]) == 0
    assert cli_main(["run", storage]) == 1
    assert os.path.exists(os.path.join(storage, "00000000", "cleaned"))
    # the failed run was not recorded, and the aborted dir marked its
    # own quarantine (fsck: handled, not a finding)
    st = load_storage(storage)
    assert st.nr_stored_histories() == 0
    assert st.is_quarantined(0)
    assert cli_main(["tools", "fsck", storage]) == 0


def test_clean_runs_after_failed_validate(tmp_path):
    from namazu_tpu.cli import cli_main

    config, materials = _write_experiment(tmp_path, run="true",
                                          validate="false")
    storage = str(tmp_path / "st")
    assert cli_main(["init", str(config), str(materials), storage]) == 0
    assert cli_main(["run", storage]) == 0
    assert os.path.exists(os.path.join(storage, "00000000", "cleaned"))
    st = load_storage(storage)
    assert st.nr_stored_histories() == 1
    assert st.is_successful(0) is False


def test_run_deadline_kills_group_and_exits_124(tmp_path):
    """A hung run script hits the phase deadline: the exit status is the
    distinct timeout code, nothing is recorded, clean still runs, and
    the script's WHOLE process group is dead (no orphan children)."""
    from namazu_tpu.cli import cli_main
    from namazu_tpu.cli.run_cmd import EXIT_TIMEOUT

    config, materials = _write_experiment(
        tmp_path,
        run='sleep 300 & echo $! > "$NMZ_WORKING_DIR/orphan.pid"; '
            'sleep 300',
    )
    storage = str(tmp_path / "st")
    assert cli_main(["init", str(config), str(materials), storage]) == 0
    t0 = time.monotonic()
    rc = cli_main(["run", storage, "--run-deadline", "1"])
    assert rc == EXIT_TIMEOUT
    assert time.monotonic() - t0 < 60
    assert load_storage(storage).nr_stored_histories() == 0
    run_dir = os.path.join(storage, "00000000")
    assert os.path.exists(os.path.join(run_dir, "cleaned"))
    with open(os.path.join(run_dir, "orphan.pid")) as f:
        orphan = int(f.read().strip())
    # the forked child died with the group (give the kill a beat)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and _pid_alive(orphan):
        time.sleep(0.1)
    assert not _pid_alive(orphan)


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    # a zombie is reaped by init eventually; treat Z state as dead
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().split(")")[-1].split()[0] != "Z"
    except OSError:
        return False
    return True


def test_kill_process_group_helper(tmp_path):
    from namazu_tpu.utils.cmd import kill_process_group

    proc = subprocess.Popen(["sh", "-c", "sleep 300 & sleep 300"],
                            start_new_session=True)
    time.sleep(0.2)
    kill_process_group(proc, grace=1.0)
    assert proc.poll() is not None


# -- orchestrator kill -9 mid-run (chaos injector) -----------------------


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_orchestrator_sigkill_mid_run_campaign_recovers(
        tmp_path, monkeypatch):
    """kill -9 of the orchestrator mid-run, injected deterministically
    via the chaos plane (NMZ_CHAOS -> orchestrator.crash) instead of
    ad-hoc monkeypatching: the campaign classifies the slot infra and
    retries it, the storage ends up quarantined or journal-recoverable
    (both legal), no testee process is orphaned (the phase.pgid sweep),
    and the pre-crash events are sitting in the run's event journal.

    Deflaked (PR 10): the crash fires on the FIRST journaled event
    batch (``at: [0]``), not the third — under CPU load the event loop
    coalesces inbound posts, so "the third batch" sometimes never
    arrived and the run sailed on to its 60s deadline instead of
    crashing (the timing sensitivity PR 9 noted)."""
    from namazu_tpu import chaos as chaos_mod
    from namazu_tpu.campaign import Campaign, CampaignSpec, EXIT_OK
    from namazu_tpu.chaos.journal import EventJournal
    from namazu_tpu.cli import cli_main

    port = _free_port()
    materials = tmp_path / "materials"
    materials.mkdir()
    (materials / "post_events.py").write_text(
        "import sys, time, urllib.request\n"
        "from namazu_tpu.signal import PacketEvent\n"
        "port = sys.argv[1]\n"
        "for i in range(6):\n"
        "    ev = PacketEvent.create('k9', 'k9', 'peer', hint=f'h{i}')\n"
        "    url = (f'http://127.0.0.1:{port}/api/v3/events/k9/'\n"
        "           f'{ev.uuid}')\n"
        "    req = urllib.request.Request(\n"
        "        url, data=ev.to_json().encode(),\n"
        "        headers={'Content-Type': 'application/json'},\n"
        "        method='POST')\n"
        "    for _ in range(30):\n"
        "        try:\n"
        "            urllib.request.urlopen(req, timeout=5)\n"
        "            break\n"
        "        except Exception:\n"
        "            time.sleep(0.1)\n")
    config = tmp_path / "config.toml"
    config.write_text(
        'explore_policy = "dumb"\n'
        f'rest_port = {port}\n'
        'event_journal = true\n'
        'run = """sleep 300 & echo $! > "$NMZ_WORKING_DIR/orphan.pid"; '
        'python '
        f'"$NMZ_MATERIALS_DIR/post_events.py" {port}; sleep 300"""\n'
        'validate = "true"\n'
    )
    storage = str(tmp_path / "st")
    assert cli_main(["init", str(config), str(materials), storage]) == 0

    # the first journaled event-loop batch SIGKILLs the orchestrator
    # (run child) — batch-count-independent, so load-dependent post
    # coalescing cannot defer the crash past the posting script
    monkeypatch.setenv(chaos_mod.ENV_VAR, chaos_mod.env_value(
        1, {"orchestrator.crash": {"at": [0]}}))
    spec = CampaignSpec(storage_dir=storage, runs=1, retries=1,
                        run_wall_deadline_s=120, run_deadline_s=60,
                        backoff_base_s=0.05, backoff_cap_s=0.1, seed=1,
                        max_consecutive_infra=5)
    rc = Campaign(spec).run(resume=False)
    assert rc == EXIT_OK  # budget spent; the stop rule did not trip

    from namazu_tpu.campaign import load_checkpoint
    slots = load_checkpoint(storage)["slots"]
    assert len(slots) == 1
    # classified infra (signal death) and retried to the budget
    assert slots[0]["class"] == "infra"
    assert len(slots[0]["attempts"]) == 2
    assert all(a["exit_status"] == -9 for a in slots[0]["attempts"])

    # no orphaned testee processes: the sweep killed the run script's
    # session (which SIGKILL of the orchestrator had orphaned)
    for i in (0, 1):
        pid_file = os.path.join(storage, f"{i:08x}", "orphan.pid")
        assert os.path.exists(pid_file), "run script never started"
        with open(pid_file) as f:
            orphan = int(f.read().strip())
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and _pid_alive(orphan):
            time.sleep(0.1)
        assert not _pid_alive(orphan)
        # the pgid breadcrumb was consumed by the sweep
        assert not os.path.exists(
            os.path.join(storage, f"{i:08x}", "phase.pgid"))
        # the pre-crash events survived in the journal: recoverable
        journal = EventJournal(os.path.join(storage, f"{i:08x}"))
        assert journal.exists()
        assert len(journal.unreleased()) >= 1

    # storage: quarantined or journal-recovered are both legal; after
    # fsck --repair the storage must be clean
    monkeypatch.delenv(chaos_mod.ENV_VAR)
    st = load_storage(storage)
    st.fsck(repair=True)
    report = st.fsck()
    assert report["incomplete_unmarked"] == []
    assert report["tmp_artifacts"] == []
    assert cli_main(["tools", "fsck", storage]) == 0
