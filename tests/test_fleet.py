"""Fleet-of-fleets placement plane (doc/tenancy.md "Fleet of fleets"):
capacity-aware scoring, drain/death lease migration with exactly-once
journal recovery, pool-level admission control, and pool-state fsck.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time

import pytest

from namazu_tpu import chaos
from namazu_tpu.chaos.plan import FaultPlan
from namazu_tpu.fleet import placement
from namazu_tpu.fleet.fsck import fsck_pool_state, looks_like_fleet_dir
from namazu_tpu.fleet.service import (
    JOURNALS_DIR,
    LEASES_DIR,
    MANIFEST_NAME,
    MANIFEST_SCHEMA,
    PlacementService,
)
from namazu_tpu.obs import federation, metrics, recorder as recorder_mod
from namazu_tpu.obs.recorder import FlightRecorder
from namazu_tpu.policy import create_policy
from namazu_tpu.signal import PacketEvent
from namazu_tpu.tenancy.host import TenantOrchestrator
from namazu_tpu.utils.config import Config


@contextlib.contextmanager
def _fresh_obs():
    """A registry, a recorder AND federation wiring of this test's own.
    The wiring (aggregator, self-relay, collectors) is the process's:
    every ``Orchestrator.start()`` wires a relay that outlives it, and
    a fleet host serves its aggregator's SLO burn on ``/fleet`` — an
    earlier file's burn >= 1.0 makes every host here ineligible
    (fleet/placement.py), which is what kept tier-1 red from PR 23 to
    PR 27."""
    federation.reset()
    old_reg = metrics.set_registry(metrics.MetricsRegistry())
    metrics.configure(True)
    old_rec = recorder_mod.set_recorder(FlightRecorder(max_runs=32))
    try:
        yield
    finally:
        metrics.set_registry(old_reg)
        recorder_mod.set_recorder(old_rec)
        federation.reset()  # the relays this test's hosts wired


@pytest.fixture(autouse=True)
def fresh_obs():
    with _fresh_obs():
        yield


def _policy_param(seed=7, interval="0ms"):
    return {"seed": seed, "min_interval": interval,
            "max_interval": interval,
            "fault_action_probability": 0.0,
            "shell_action_interval": 0}


def _host(tmp_path, name, **cfg_extra):
    cfg = Config(dict({
        "rest_port": 0,
        "run_id": name,
        "explore_policy": "random",
        "explore_policy_param": _policy_param(),
        # the pool's monitor owns failure detection in these tests
        "tenancy_reap_interval_s": 3600.0,
    }, **cfg_extra))
    policy = create_policy("random")
    policy.load_config(cfg)
    host = TenantOrchestrator(cfg, policy, collect_trace=True)
    host.start()
    return host


def _service(tmp_path, hosts, **kw):
    svc = PlacementService(str(tmp_path / "pool"),
                           default_ttl_s=600.0,
                           monitor_interval_s=0.1, dead_after_s=0.6,
                           host_timeout_s=2.0, **kw)
    for i, host in enumerate(hosts):
        port = host.hub.endpoint("rest").port
        svc.add_host(f"http://127.0.0.1:{port}", name=f"host{i}")
    svc.start()
    return svc


# -- capacity scoring off synthetic snapshots ---------------------------


def _fleet_doc(rate=0.0, parked=0, runs=(), burn=0.0, stale=False):
    return {
        "schema": "nmz-fleet-v1", "instance_count": 1,
        "stale_instances": 1 if stale else 0,
        "instances": [{
            "job": "orchestrator", "instance": "i1", "stale": stale,
            "events_per_sec": rate, "edge_parked": parked,
            "runs": {r: {"events_total": 1, "events_per_sec": None,
                         "parked": 2} for r in runs},
        }],
        "slo": {"objectives": [{"name": "o", "burn": burn,
                                "breached": burn >= 1.0,
                                "breaches": 0}]},
    }


def test_summarize_fleet_doc_synthetic():
    s = placement.summarize_fleet_doc(
        _fleet_doc(rate=120.0, parked=3, runs=("a", "b"), burn=0.4))
    assert s["reachable"] and s["events_per_sec"] == 120.0
    assert s["runs"] == 2 and sorted(s["run_names"]) == ["a", "b"]
    assert s["parked"] == 3 + 2 * 2  # edge_parked + per-run parked
    assert s["max_burn"] == 0.4
    # a stale producer row is history, not load
    stale = placement.summarize_fleet_doc(
        _fleet_doc(rate=999.0, runs=("a",), stale=True))
    assert stale["events_per_sec"] == 0.0 and stale["runs"] == 0
    unreachable = placement.summarize_fleet_doc(None)
    assert not unreachable["reachable"]


def test_score_and_choose_host_synthetic():
    idle = placement.summarize_fleet_doc(_fleet_doc())
    busy = placement.summarize_fleet_doc(
        _fleet_doc(rate=5000.0, parked=400, runs=("a", "b")))
    burning = placement.summarize_fleet_doc(_fleet_doc(burn=1.2))

    # ineligibility: at the run cap, or already violating its SLO
    assert placement.score_host(idle, leased_runs=4,
                                max_runs_per_host=4) is None
    assert placement.score_host(burning, leased_runs=0) is None
    # the least-loaded host scores highest
    s_idle = placement.score_host(idle, leased_runs=0)
    s_busy = placement.score_host(busy, leased_runs=2)
    assert s_idle > s_busy

    cands = [
        {"name": "h-busy", "summary": busy, "leased_runs": 2,
         "eligible": True},
        {"name": "h-idle", "summary": idle, "leased_runs": 0,
         "eligible": True},
        {"name": "h-dead", "summary": idle, "leased_runs": 0,
         "eligible": False},
    ]
    assert placement.choose_host(cands) == "h-idle"
    # journal affinity outweighs a small load difference (a mildly
    # busier previous host keeps its run)...
    mild = placement.summarize_fleet_doc(_fleet_doc(rate=2000.0))
    mild_cands = [
        {"name": "h-mild", "summary": mild, "leased_runs": 0,
         "eligible": True},
        {"name": "h-idle", "summary": idle, "leased_runs": 0,
         "eligible": True},
    ]
    assert placement.choose_host(mild_cands) == "h-idle"
    assert placement.choose_host(mild_cands, affinity_host="h-mild") \
        == "h-mild"
    # ...but a SATURATED previous host still loses to an idle sibling,
    # and affinity never resurrects an ineligible host
    assert placement.choose_host(cands, affinity_host="h-busy") \
        == "h-idle"
    assert placement.choose_host(cands, affinity_host="h-dead") \
        == "h-idle"
    # identical snapshots tie-break deterministically by name
    twins = [{"name": n, "summary": idle, "leased_runs": 0,
              "eligible": True} for n in ("h-b", "h-a", "h-c")]
    assert placement.choose_host(twins) == "h-a"
    assert placement.pool_burn([idle, burning, busy]) == 1.2
    assert placement.pool_burn([placement.summarize_fleet_doc(None)]) \
        == 0.0


# -- drain migration (graceful) -----------------------------------------


def test_drain_migrates_leases_exactly_once(tmp_path):
    from namazu_tpu.inspector.rest_transceiver import RestTransceiver

    hosts = [_host(tmp_path, f"drain-host{i}") for i in range(2)]
    svc = _service(tmp_path, hosts, max_runs_per_host=4)
    tx = None
    try:
        lease = svc.handle_wire({
            "op": "lease", "run": "mig-a", "ttl_s": 600.0,
            "policy": "random",
            "policy_param": _policy_param(interval="2500ms")})
        assert lease["ok"]
        src = lease["host"]
        tx = RestTransceiver("n0", lease["host_url"], use_batch=False,
                             post_attempts=8, run_ns="mig-a")
        tx.start()
        evs = [PacketEvent.create("n0", "n0", "peer", hint=f"m{i}")
               for i in range(5)]
        for ev in evs:
            tx.send_event(ev)
        src_host = hosts[int(src[len("host"):])]
        ns = src_host.registry.namespace("mig-a")
        deadline = time.monotonic() + 10.0
        while ns.parked_depth() < 5 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert ns.parked_depth() == 5

        drained = svc.handle_wire({"op": "drain", "host": src})
        assert drained["ok"] and drained["migrated"] == 1
        pool = svc.pool_payload()
        row = pool["leases"][0]
        assert row["host"] != src and row["state"] == "placed"
        assert row["migrations"] == 1
        assert pool["counters"].get("migrations_drain") == 1
        # a draining host takes no NEW runs
        refused = svc.handle_wire({
            "op": "lease", "run": "mig-b", "ttl_s": 600.0,
            "policy": "random", "policy_param": _policy_param()})
        assert refused["ok"] and refused["host"] == row["host"]
        svc.handle_wire({"op": "release",
                         "lease_id": refused["lease_id"],
                         "trace": False})

        # the reclaimed-then-recovered events dispatch exactly once:
        # the release trace on the NEW host joins the posted uuids
        rel = svc.handle_wire({"op": "release",
                               "lease_id": lease["lease_id"],
                               "trace": True})
        assert rel["ok"]
        traced = sorted(d["event_uuid"] for d in rel["trace"])
        assert traced == sorted(ev.uuid for ev in evs)
        assert all(not h.registry.payload() for h in hosts)
    finally:
        if tx is not None:
            tx.shutdown()
        svc.shutdown()
        for h in hosts:
            h.shutdown()


# -- the process's telemetry wiring --------------------------------------


def test_a_lease_is_granted_after_an_earlier_files_slo_burn(tmp_path):
    """The wiring an earlier file on this xdist worker leaves behind —
    an aggregator whose ``dispatch_p99`` burns because a chaos scenario
    held events past a second — must not reach this file's hosts: they
    would serve that burn on ``/fleet`` and refuse every lease."""
    def e2e(seq, counts):
        return {"schema": federation.SCHEMA, "job": "orchestrator",
                "instance": "earlier-file", "seq": seq, "families": [
                    {"name": "nmz_event_e2e_seconds",
                     "type": "histogram", "labelnames": [],
                     "uppers": [0.1, 1.0, 10.0],
                     "samples": [{"labels": {}, "counts": counts,
                                  "sum": 40.0, "count": sum(counts)}]}]}

    dirty = federation.FleetAggregator()
    dirty.note_push(e2e(1, [1, 0, 0, 0]))
    dirty.note_push(e2e(2, [1, 0, 8, 0]))  # 8 dispatches took > 1 s
    federation.set_aggregator(dirty)
    left_behind = placement.summarize_fleet_doc(federation.aggregator()
                                                .payload())
    assert left_behind["max_burn"] >= 1.0  # no host would be eligible
    with _fresh_obs():
        hosts = [_host(tmp_path, "clean-host0")]
        svc = _service(tmp_path, hosts)
        try:
            lease = svc.handle_wire({
                "op": "lease", "run": "clean-a", "ttl_s": 600.0,
                "policy": "random", "policy_param": _policy_param()})
            assert lease["ok"], lease
            svc.handle_wire({"op": "release",
                             "lease_id": lease["lease_id"],
                             "trace": False})
        finally:
            svc.shutdown()
            for h in hosts:
                h.shutdown()


# -- admission control ---------------------------------------------------


def test_admission_refusal_paths(tmp_path):
    hosts = [_host(tmp_path, "adm-host0")]
    svc = _service(tmp_path, hosts, max_runs_per_host=1,
                   retry_after_s=0.25)
    try:
        # capacity refusal: the only host is at its run cap
        first = svc.handle_wire({"op": "lease", "run": "adm-a",
                                 "ttl_s": 600.0, "policy": "random",
                                 "policy_param": _policy_param()})
        assert first["ok"]
        full = svc.handle_wire({"op": "lease", "run": "adm-b",
                                "ttl_s": 600.0, "policy": "random",
                                "policy_param": _policy_param()})
        assert not full["ok"] and full["status"] == 429
        assert full["retry_after"] == 0.25
        # chaos seam refusal (deterministic 429 + Retry-After)
        chaos.install(FaultPlan(3, {"fleet.admission.refuse": {
            "prob": 1.0, "max_fires": 1, "retry_after": 0.05}}))
        try:
            refused = svc.handle_wire({
                "op": "lease", "run": "adm-c", "ttl_s": 600.0,
                "policy": "random", "policy_param": _policy_param()})
        finally:
            chaos.clear()
        assert not refused["ok"] and refused["status"] == 429
        assert refused["retry_after"] == 0.05
        assert svc.pool_payload()["counters"]["admission_rejections"] \
            == 2
        # migrations are NEVER admission-gated, but a double pool-lease
        # of a live run is refused outright (no retry_after: it's not
        # load, it's a conflict)
        dup = svc.handle_wire({"op": "lease", "run": "adm-a",
                               "ttl_s": 600.0, "policy": "random",
                               "policy_param": _policy_param()})
        assert not dup["ok"] and "already pool-leased" in dup["error"]
        assert "retry_after" not in dup
        svc.handle_wire({"op": "release", "lease_id": first["lease_id"],
                         "trace": False})
    finally:
        svc.shutdown()
        for h in hosts:
            h.shutdown()


def test_campaign_serve_honors_pool_429(tmp_path):
    """``campaign --serve`` pointed at the POOL: admission's
    429 + Retry-After refusals ride the tenancy wire into the
    campaign's deferral loop, which waits and retries — the campaign
    completes with zero failed runs once admission clears."""
    from namazu_tpu.campaign import Campaign, CampaignSpec, summarize
    from namazu_tpu.storage import new_storage

    storage_dir = str(tmp_path / "storage")
    st = new_storage("naive", storage_dir)
    st.create()
    st.close()
    with open(tmp_path / "storage" / "config.json", "w") as f:
        json.dump({"explore_policy": "random"}, f)

    hosts = [_host(tmp_path, "serve-host0")]
    svc = _service(tmp_path, hosts, max_runs_per_host=4)
    sock = str(tmp_path / "fleet.sock")
    svc.serve_unix(sock)
    plan = chaos.install(FaultPlan(9, {"fleet.admission.refuse": {
        "prob": 1.0, "max_fires": 2, "retry_after": 0.05}}))
    try:
        spec = CampaignSpec(
            storage_dir=storage_dir, runs=2, retries=1,
            telemetry_collector="",
            serve_url=f"uds://{sock}", serve_ttl_s=5.0,
            serve_events=16, serve_entities=2,
            serve_policy="random",
            serve_policy_param=_policy_param())
        campaign = Campaign(spec)
        status = campaign.run(resume=False)
        assert status == 0
        summary = summarize(campaign.state)
        assert summary["experiment"] == 2
        assert summary["stopped_reason"] == "done"
        assert plan.fired("fleet.admission.refuse") == 2
        assert hosts[0].registry.active_count() == 0
        assert not svc.pool_payload()["leases"]
    finally:
        chaos.clear()
        svc.shutdown()
        for h in hosts:
            h.shutdown()


# -- double-grant impossibility ------------------------------------------


def test_concurrent_leases_grant_exactly_one(tmp_path):
    hosts = [_host(tmp_path, "race-host0")]
    svc = _service(tmp_path, hosts, max_runs_per_host=8)
    try:
        results = []
        barrier = threading.Barrier(6)

        def racer():
            barrier.wait()
            results.append(svc.handle_wire({
                "op": "lease", "run": "race-a", "ttl_s": 600.0,
                "policy": "random",
                "policy_param": _policy_param()}))

        threads = [threading.Thread(target=racer) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        winners = [r for r in results if r.get("ok")]
        assert len(winners) == 1
        assert all("already pool-leased" in r["error"]
                   for r in results if not r.get("ok"))
        # ONE host-side lease exists — the pool never double-granted
        assert hosts[0].registry.active_count() == 1
        svc.handle_wire({"op": "release",
                         "lease_id": winners[0]["lease_id"],
                         "trace": False})
    finally:
        svc.shutdown()
        for h in hosts:
            h.shutdown()


# -- host death (the chaos scenario) -------------------------------------


def test_pool_host_die_scenario(tmp_path):
    from namazu_tpu.chaos.harness import run_scenario

    res = run_scenario("pool_host_die", seed=5, workdir=str(tmp_path))
    assert res["ok"], res["invariants"]
    assert res["fault_report"]["fired"].get("fleet.host.die") == 1


# -- pool-state fsck -----------------------------------------------------


def _write_state_dir(tmp_path):
    state = tmp_path / "state"
    (state / LEASES_DIR).mkdir(parents=True)
    (state / JOURNALS_DIR).mkdir()
    (state / MANIFEST_NAME).write_text(json.dumps(
        {"schema": MANIFEST_SCHEMA, "pid": 0, "serve_urls": [],
         "hosts": {}, "updated_at": time.time()}))
    return state


def test_fsck_pool_state_sweeps_stale_and_orphans(tmp_path):
    from namazu_tpu.chaos.journal import EventJournal

    state = _write_state_dir(tmp_path)
    now = time.time()
    live_journal = state / JOURNALS_DIR / "live-run-aaaa"
    live_journal.mkdir()

    def record(lease_id, run, journal, expires):
        (state / LEASES_DIR / f"{lease_id}.json").write_text(
            json.dumps({"lease_id": lease_id, "run": run,
                        "journal_dir": journal, "ttl_s": 5.0,
                        "expires_wall": expires, "state": "placed",
                        "migrations": 0}))

    record("live01", "live-run", str(live_journal), now + 600.0)
    record("stale01", "dead-run", "", now - 60.0)
    (state / LEASES_DIR / "torn.json").write_text("{nope")
    # an unreferenced journal WITH unreleased events must survive...
    recoverable = state / JOURNALS_DIR / "crashed-run-bbbb"
    recoverable.mkdir()
    j = EventJournal(str(recoverable))
    j.append_events([PacketEvent.create("n0", "n0", "peer", hint="x")],
                    {"n0": "rest"})
    j.close()
    # ...while an unreferenced EMPTY journal dir is sweepable
    orphan = state / JOURNALS_DIR / "done-run-cccc"
    orphan.mkdir()

    assert looks_like_fleet_dir(str(state))
    report = fsck_pool_state(str(state))
    assert report["manifest_ok"]
    assert report["live_leases"] == ["live01"]
    assert [r["lease_id"] for r in report["stale_leases"]] == ["stale01"]
    assert report["unreadable_records"] == ["torn.json"]
    assert report["orphan_journals"] == ["done-run-cccc"]
    assert [r["journal"] for r in report["recoverable_journals"]] \
        == ["crashed-run-bbbb"]
    assert not report["repaired"]  # report-only without --repair
    assert (state / LEASES_DIR / "stale01.json").exists()

    repaired = fsck_pool_state(str(state), repair=True)
    assert sorted(repaired["repaired"]) == [
        "journal:done-run-cccc", "record:stale01.json",
        "record:torn.json"]
    assert not (state / LEASES_DIR / "stale01.json").exists()
    assert not orphan.exists()
    # never touched: the live lease, its journal, the recoverable one
    assert (state / LEASES_DIR / "live01.json").exists()
    assert live_journal.exists() and recoverable.exists()

    again = fsck_pool_state(str(state))
    assert not again["stale_leases"] and not again["orphan_journals"]
    assert len(again["recoverable_journals"]) == 1


def test_fsck_reconciles_against_live_service(tmp_path):
    """With the service reachable, ITS view decides staleness — a
    record inside its walltime TTL is still swept if the service no
    longer knows the lease (and kept if it does, however old the
    walltime looks)."""
    hosts = [_host(tmp_path, "fsck-host0")]
    svc = _service(tmp_path, hosts, max_runs_per_host=4)
    sock = str(tmp_path / "fleet-fsck.sock")
    svc.serve_unix(sock)
    try:
        lease = svc.handle_wire({"op": "lease", "run": "fsck-a",
                                 "ttl_s": 600.0, "policy": "random",
                                 "policy_param": _policy_param()})
        assert lease["ok"]
        # forge a record the service never granted, walltime still live
        (tmp_path / "pool" / LEASES_DIR / "forged.json").write_text(
            json.dumps({"lease_id": "forged", "run": "ghost",
                        "journal_dir": "", "ttl_s": 600.0,
                        "expires_wall": time.time() + 600.0,
                        "state": "placed", "migrations": 0}))
        report = fsck_pool_state(svc.state_dir, repair=True,
                                 service_url=f"uds://{sock}")
        assert [r["lease_id"] for r in report["stale_leases"]] \
            == ["forged"]
        assert lease["lease_id"] in report["live_leases"]
        svc.handle_wire({"op": "release", "lease_id": lease["lease_id"],
                         "trace": False})
    finally:
        svc.shutdown()
        for h in hosts:
            h.shutdown()


# -- the one surface: CLI ------------------------------------------------


def test_fleet_status_and_top_pool_render(tmp_path, capsys):
    from namazu_tpu.cli import cli_main

    hosts = [_host(tmp_path, "cli-host0")]
    svc = _service(tmp_path, hosts, max_runs_per_host=4)
    sock = str(tmp_path / "fleet-cli.sock")
    svc.serve_unix(sock)
    try:
        lease = svc.handle_wire({"op": "lease", "run": "cli-a",
                                 "ttl_s": 600.0, "policy": "random",
                                 "policy_param": _policy_param()})
        assert lease["ok"]
        assert cli_main(["fleet", "status", "--url",
                         f"uds://{sock}"]) == 0
        text = capsys.readouterr().out
        assert "host0" in text and "cli-a" in text and "live" in text
        # tools top --pool renders the SAME surface
        assert cli_main(["tools", "top", "--pool", "--url",
                         f"uds://{sock}"]) == 0
        top_text = capsys.readouterr().out
        assert "cli-a" in top_text and "host0" in top_text
        assert cli_main(["tools", "top", "--pool", "--json", "--url",
                         f"uds://{sock}"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["schema"] == "nmz-pool-v1"
        assert [l["run"] for l in doc["leases"]] == ["cli-a"]
        svc.handle_wire({"op": "release", "lease_id": lease["lease_id"],
                         "trace": False})
        # tools fsck dispatches on the manifest: clean dir exits 0
        svc_dir = svc.state_dir
    finally:
        svc.shutdown()
        for h in hosts:
            h.shutdown()
    assert cli_main(["tools", "fsck", svc_dir, "--repair"]) in (0, 1)
    assert cli_main(["tools", "fsck", svc_dir]) == 0
    capsys.readouterr()


def test_fleet_drain_cli(tmp_path, capsys):
    from namazu_tpu.cli import cli_main

    hosts = [_host(tmp_path, f"dcli-host{i}") for i in range(2)]
    svc = _service(tmp_path, hosts, max_runs_per_host=4)
    sock = str(tmp_path / "fleet-drain.sock")
    svc.serve_unix(sock)
    try:
        lease = svc.handle_wire({"op": "lease", "run": "dcli-a",
                                 "ttl_s": 600.0, "policy": "random",
                                 "policy_param": _policy_param()})
        assert lease["ok"]
        src = lease["host"]
        assert cli_main(["fleet", "drain", "--url", f"uds://{sock}",
                         src]) == 0
        assert "1 lease(s) re-placed" in capsys.readouterr().out
        row = svc.pool_payload()["leases"][0]
        assert row["host"] != src and row["state"] == "placed"
        svc.handle_wire({"op": "release", "lease_id": lease["lease_id"],
                         "trace": False})
    finally:
        svc.shutdown()
        for h in hosts:
            h.shutdown()
