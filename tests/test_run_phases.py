"""The run's half of a live cycle (obs/spans.py "run-scoped spans",
doc/observability.md "Run phases"): what a run stores of itself, what
its supervisor adds, and what the search home observes of a run it first
meets. Held to names, parents, counts and orderings, never to a wall
time."""

import json
import os
import time

import pytest

from namazu_tpu import obs
from namazu_tpu.campaign import Campaign, CampaignSpec, load_checkpoint
from namazu_tpu.cli import cli_main
from namazu_tpu.models import ingest
from namazu_tpu.models.ingest import (
    IngestParams,
    RunRecordCache,
    ingest_history,
)
from namazu_tpu.models.search import ScheduleSearch
from namazu_tpu.obs import federation, spans
from namazu_tpu.orchestrator import Orchestrator
from namazu_tpu.policy import create_policy
from namazu_tpu.signal.base import HINT_SPACE
from namazu_tpu.storage import load_storage, new_storage
from namazu_tpu.utils.config import Config

from tests.test_ingest_embed_batch import H, cfg, make_run
from tests.test_ingest_run_cache import UnsignedStorage
from tests.test_request_spans import isolated_obs

#: what a run records without a supervisor, in the order it stores them
OWN = ["prepare", "testee", "drain", "search", "endpoints", "validate",
       "record"]
PARENTS = {"search": "drain", "endpoints": "drain"}


@pytest.fixture
def fresh_obs():
    """An empty registry and ring; the relay and profiler a run or a
    campaign wires into the process stop with the test."""
    with isolated_obs() as ring:
        yield ring
    federation.reset()
    obs.profiling.reset()


class CountingClock:
    """Stands in for the ``time`` module inside obs/spans.py."""

    reads = 0

    def monotonic(self):
        self.reads += 1
        return time.monotonic()

    time = staticmethod(time.time)


def init_storage(tmp_path, extra=""):
    materials = tmp_path / "materials"
    materials.mkdir(exist_ok=True)
    config = tmp_path / "config.toml"
    config.write_text('explore_policy = "dumb"\nrest_port = 0\n'
                      'run = "true"\nvalidate = "true"\n' + extra)
    storage = str(tmp_path / "st")
    assert cli_main(["init", str(config), str(materials), storage]) == 0
    return storage


def phase_counts():
    """``{phase: count}`` of this process's ``nmz_run_phase_seconds``."""
    for fam in obs.metrics.registry().to_jsonable()["metrics"]:
        if fam["name"] == spans.RUN_PHASE:
            return {s["labels"]["phase"]: s["value"]["count"]
                    for s in fam["samples"]}
    return {}


def stored_phases(storage, i=0):
    return load_storage(storage).get_metadata(i).get("phases")


@pytest.mark.parametrize("spawned", [False, True])
def test_a_run_stores_its_phases(tmp_path, fresh_obs, monkeypatch,
                                 spawned):
    storage = init_storage(tmp_path)
    monkeypatch.delenv(spans.RUN_SPAWNED_ENV, raising=False)
    if spawned:
        monkeypatch.setenv(spans.RUN_SPAWNED_ENV, repr(time.monotonic()))
    assert cli_main(["run", storage]) == 0
    rows = stored_phases(storage)
    want = (["boot"] if spawned else []) + OWN
    assert [r[0] for r in rows] == want
    assert set(want) <= set(spans.RUN_PHASES)
    by_name = {r[0]: r for r in rows}
    for name, parent, start, seconds in rows:
        assert parent == PARENTS.get(name)
        assert seconds >= 0
    starts = [r[2] for r in rows]
    assert starts == sorted(starts) and starts[0] == 0.0
    drain = by_name["drain"]
    for child in ("search", "endpoints"):
        _, _, start, seconds = by_name[child]
        assert drain[2] <= start
        assert start + seconds <= drain[2] + drain[3] + 1e-5
    # the child observed the same rows, once each, in its own registry
    assert phase_counts() == {name: 1 for name in want}
    # the stamp was this run's: what it spawns later is no child of it
    assert spans.RUN_SPAWNED_ENV not in os.environ
    # and the scope closed with the run
    assert obs.run_end() is None
    with obs.run_phase("drain"):
        pass
    assert phase_counts() == {name: 1 for name in want}


def test_an_unreadable_stamp_is_no_stamp(tmp_path, fresh_obs, monkeypatch):
    storage = init_storage(tmp_path)
    monkeypatch.setenv(spans.RUN_SPAWNED_ENV, "yesterday")
    assert cli_main(["run", storage]) == 0
    assert [r[0] for r in stored_phases(storage)] == OWN


def test_observability_off_stores_and_observes_nothing(tmp_path, fresh_obs,
                                                       monkeypatch):
    storage = init_storage(tmp_path, "obs_enabled = false\n")
    monkeypatch.setenv(spans.RUN_SPAWNED_ENV, repr(time.monotonic()))
    clock = CountingClock()
    monkeypatch.setattr(spans, "time", clock)
    assert cli_main(["run", storage]) == 0
    meta = load_storage(storage).get_metadata(0)
    assert "phases" not in meta and meta["hint_space"] == HINT_SPACE
    assert not obs.metrics.enabled()
    obs.metrics.configure(True)
    assert phase_counts() == {}
    assert fresh_obs.end() == 0
    # obs/spans.py read no clock on the run's behalf
    assert clock.reads == 0


def test_phases_outside_a_run_scope_time_nothing(fresh_obs):
    with obs.run_phase("drain") as attrs:
        attrs["request"] = "x"
    obs.run_phase_since("prepare", time.monotonic())
    assert obs.run_end() is None
    assert phase_counts() == {} and fresh_obs.end() == 0


def test_the_search_row_names_the_sidecar_request(fresh_obs):
    policy = create_policy("dumb")
    config = Config({"explore_policy": "dumb"})
    policy.load_config(config)
    policy.sidecar_request_id = "run-host:41"
    orc = Orchestrator(config, policy, collect_trace=True)
    orc.start()
    obs.run_begin("00000007", obs.run_entered())
    try:
        with obs.run_phase("drain"):
            orc.shutdown()
        rows = fresh_obs.since(0)["rows"]
    finally:
        stored = obs.run_end()
    assert [r[0] for r in stored] == ["drain", "search", "endpoints"]
    by_name = {r[1]: r for r in rows}
    assert {r[0] for r in rows} == {"00000007"}
    assert by_name["search"][7] == {"request": "run-host:41"}
    assert by_name["search"][2] == by_name["endpoints"][2] == "drain"
    assert by_name["endpoints"][7] == {} and by_name["drain"][2] is None


def test_a_campaign_adds_what_only_the_supervisor_sees(tmp_path, fresh_obs):
    storage = init_storage(tmp_path)
    spec = CampaignSpec(storage_dir=storage, runs=2, seed=7,
                        telemetry_collector="")
    assert Campaign(spec).run() == 0
    state = load_checkpoint(storage)
    first, second = [s["attempts"][-1] for s in state["slots"]]
    child = ["boot"] + OWN
    # the first attempt starts cold; the second takes the standby that
    # was started beside the first (tests/test_campaign_standby.py)
    assert (first["start"], second["start"]) == ("cold", "standby")
    assert [r[0] for r in first["phases"]] == child + ["teardown"]
    assert sorted(r[0] for r in second["phases"][:3]) == [
        "boot", "respawn", "standby"]
    assert [r[0] for r in second["phases"][3:]] == OWN + ["teardown"]
    for i, attempt in enumerate((first, second)):
        rows = attempt["phases"]
        # the child's rows are the ones its run stored, untouched (its
        # copy of `respawn`, PR 44, is the supervisor's row again)
        assert [r for r in rows if r[0] not in spans.SUPERVISOR_PHASES] \
            == [r for r in stored_phases(storage, i)
                if r[0] not in spans.SUPERVISOR_PHASES]
        assert all(r[0] in spans.SUPERVISOR_PHASES + spans.RUN_PHASES
                   + (spans.STANDBY_PHASE,) for r in rows)
        # the two rows before 0 each end at 0; from 0 on, in order
        starts = [r[2] for r in rows if r[0] not in ("respawn", "standby")]
        assert starts == sorted(starts) and starts[0] == 0.0
        by_name = {r[0]: r for r in rows}
        teardown, record = by_name["teardown"], by_name["record"]
        assert teardown[1] is None and teardown[3] >= 0
        assert teardown[2] == pytest.approx(record[2] + record[3], abs=1e-5)
    respawn = second["phases"][0]
    assert respawn[1] is None and respawn[3] >= 0
    assert respawn[2] == -respawn[3]
    # the supervisor observed its own two (the children theirs, in
    # processes of their own)
    assert phase_counts() == {"teardown": 2, "respawn": 1}


def test_an_attempt_that_stored_no_run_keeps_what_was_measured(
        tmp_path, fresh_obs):
    storage = init_storage(tmp_path)
    config = tmp_path / "st" / "config.toml"
    config.write_text('explore_policy = "dumb"\nrun = "false"\n'
                      'validate = "true"\n')
    spec = CampaignSpec(storage_dir=storage, runs=1, retries=1, seed=7,
                        backoff_base_s=0.01, backoff_cap_s=0.02,
                        telemetry_collector="")
    Campaign(spec).run()
    first, second = load_checkpoint(storage)["slots"][0]["attempts"]
    assert "phases" not in first
    assert [r[0] for r in second["phases"]] == ["respawn"]


def test_a_campaign_with_observability_off_stamps_nothing(tmp_path,
                                                          fresh_obs):
    storage = init_storage(tmp_path, "obs_enabled = false\n")
    spec = CampaignSpec(storage_dir=storage, runs=2, seed=7,
                        telemetry_collector="")
    assert Campaign(spec).run() == 0
    state = load_checkpoint(storage)
    assert all("phases" not in s["attempts"][-1] for s in state["slots"])
    assert all(stored_phases(storage, i) is None for i in range(2))


# -- the search home's reading -------------------------------------------

PHASES = [["boot", None, 0.0, 0.5], ["prepare", None, 0.5, 0.25],
          ["testee", None, 0.75, 1.0], ["drain", None, 1.75, 0.5],
          ["search", "drain", 1.75, 0.25],
          ["endpoints", "drain", 2.0, 0.25],
          ["validate", None, 2.25, 0.125], ["record", None, 2.375, 0.125]]
PARAMS = IngestParams(H=H, max_interval=0.05)


def make_storage(path, depth, phases=PHASES):
    st = new_storage("naive", str(path))
    st.create()
    for i in range(depth):
        st.create_new_working_dir()
        st.record_new_trace(make_run(i))
        meta = {"hint_space": HINT_SPACE}
        if phases is not None:
            meta["phases"] = phases
        st.record_result(i % 4 != 1, 0.5, metadata=meta)
    return st


@pytest.fixture
def records(monkeypatch):
    cache = RunRecordCache(ingest.RUN_CACHE_BYTES)
    monkeypatch.setattr(ingest, "_RUN_RECORDS", cache)
    return cache


def test_ingest_observes_a_run_where_it_first_meets_it(tmp_path, fresh_obs,
                                                       records):
    st = make_storage(tmp_path / "st", 3)
    search = ScheduleSearch(cfg(), n_devices=1)
    ingest_history(search, st, PARAMS)
    names = [r[0] for r in PHASES]
    assert phase_counts() == {name: 3 for name in names}
    ingest_history(search, st, PARAMS)  # all three kept: nothing new
    assert phase_counts() == {name: 3 for name in names}
    # a fourth run is met once, and alone
    st.create_new_working_dir()
    st.record_new_trace(make_run(3))
    st.record_result(True, 0.5, metadata={"hint_space": HINT_SPACE,
                                          "phases": PHASES[:2]})
    ingest_history(search, st, PARAMS)
    assert phase_counts() == dict({name: 3 for name in names},
                                  boot=4, prepare=4)
    # sums are the rows' seconds, nothing of this process's own clock
    fam = next(f for f in obs.metrics.registry().to_jsonable()["metrics"]
               if f["name"] == spans.RUN_PHASE)
    sums = {s["labels"]["phase"]: s["value"]["sum"] for s in fam["samples"]}
    assert sums["testee"] == 3.0 and sums["boot"] == 2.0
    # an evicted record is met again: the one exception to "once"
    records._records.clear()
    ingest_history(search, st, PARAMS)
    assert phase_counts()["testee"] == 6


def test_ingest_observes_nothing_without_phases_or_signatures(
        tmp_path, fresh_obs, records):
    search = ScheduleSearch(cfg(), n_devices=1)
    # the benchmark's synthesised histories and every older recording
    ingest_history(search, make_storage(tmp_path / "plain", 3, None), PARAMS)
    assert phase_counts() == {}
    # a backend that cannot say whether a run changed keeps no record:
    # it would observe its whole history again at every request
    unsigned = UnsignedStorage(make_storage(tmp_path / "st", 3).dir)
    unsigned.init()
    ingest_history(search, unsigned, PARAMS)
    ingest_history(search, unsigned, PARAMS)
    assert phase_counts() == {}


@pytest.mark.parametrize("rows", [
    "boot", [["boot", None, 0.0]], [["boot", None, 0.0, "soon"]],
    [["boot", None, 0.0, -1.0]], [["boot", None, 0.0, float("inf")]],
    [["boot", None, 0.0, float("nan")]], [["reboot", None, 0.0, 1.0]],
    [None], {"boot": 1.0}])
def test_rows_no_run_wrote_are_passed_over(fresh_obs, rows):
    obs.run_phases_observed(rows)
    assert phase_counts() == {}
    obs.run_phases_observed(json.loads(json.dumps(PHASES)) + [["x"]])
    assert phase_counts() == {r[0]: 1 for r in PHASES}
