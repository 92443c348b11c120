"""The ``respawn`` row travels with the run (doc/observability.md "Run
phases"): the supervisor knows the stretch from the previous reap to
this run's go when it says go, hands it over beside the spawn stamp (the
standby's go line; ``NMZ_RUN_RESPAWN`` on a cold spawn), and the run
stores it as a top-level row that ends at 0, where the search home's
ingest meets it like every other stored row. Held to names, parents and
orderings, never to a wall time."""

import json
import os
import time

import pytest

from namazu_tpu import obs
from namazu_tpu.campaign import Campaign, CampaignSpec, load_checkpoint
from namazu_tpu.cli import cli_main
from namazu_tpu.obs import spans
from namazu_tpu.storage import load_storage

from tests.test_campaign_standby import init_storage as init_with_run
from tests.test_campaign_standby import real_child, wait_at_gate
from tests.test_run_phases import (  # noqa: F401  (fresh_obs: a fixture)
    OWN,
    fresh_obs,
    init_storage,
    phase_counts,
    stored_phases,
)


def ends_at_zero(row, seconds):
    name, parent, start, length = row
    assert (name, parent) == (spans.RESPAWN_PHASE, None)
    assert length == pytest.approx(seconds, abs=1e-6)
    assert start == pytest.approx(-seconds, abs=1e-6)


def test_a_warm_run_stores_the_respawn_its_go_line_states(tmp_path):
    storage = init_with_run(tmp_path, run='env > "$NMZ_WORKING_DIR/env"')
    child = real_child(storage)
    wait_at_gate(child.pid)
    go = {"spawned": time.monotonic(), "respawn": 0.0625, "env": {}}
    out, _ = child.communicate(json.dumps(go).encode() + b"\n", timeout=60)
    assert child.returncode == 0, out
    rows = stored_phases(storage)
    # the three rows that are not the run's own doing, then its own
    assert sorted(r[0] for r in rows[:3]) == ["boot", "respawn", "standby"]
    assert [r[0] for r in rows[3:]] == OWN
    assert [r[0] for r in rows].count("respawn") == 1
    ends_at_zero(next(r for r in rows if r[0] == "respawn"), 0.0625)
    # both of the supervisor's words were this run's alone
    with open(os.path.join(storage, "00000000", "env")) as f:
        env = f.read()
    assert spans.RUN_RESPAWN_ENV not in env
    assert spans.RUN_SPAWNED_ENV not in env


def test_a_cold_run_reads_it_from_its_environment(tmp_path, fresh_obs,
                                                  monkeypatch):
    storage = init_storage(tmp_path)
    monkeypatch.setenv(spans.RUN_SPAWNED_ENV, repr(time.monotonic()))
    monkeypatch.setenv(spans.RUN_RESPAWN_ENV, repr(0.25))
    assert cli_main(["run", storage]) == 0
    rows = stored_phases(storage)
    assert [r[0] for r in rows] == ["respawn", "boot"] + OWN
    ends_at_zero(rows[0], 0.25)
    # from 0 on nothing moved: `boot` starts there as before
    assert rows[1][2] == 0.0
    assert spans.RUN_RESPAWN_ENV not in os.environ
    # the run observed it like its other rows
    assert phase_counts() == {name: 1 for name in ["respawn", "boot"] + OWN}


@pytest.mark.parametrize("stamped", [False, True])
def test_a_run_nobody_respawned_stores_none(tmp_path, fresh_obs,
                                            monkeypatch, stamped):
    """A bare ``nmz-tpu run``, and a campaign's first attempt (a stamp
    and no respawn before it). A respawn without a stamp has no origin
    to end at: no row either."""
    storage = init_storage(tmp_path)
    monkeypatch.delenv(spans.RUN_SPAWNED_ENV, raising=False)
    monkeypatch.delenv(spans.RUN_RESPAWN_ENV, raising=False)
    if stamped:
        monkeypatch.setenv(spans.RUN_SPAWNED_ENV, repr(time.monotonic()))
    else:
        monkeypatch.setenv(spans.RUN_RESPAWN_ENV, repr(0.25))
    assert cli_main(["run", storage]) == 0
    assert [r[0] for r in stored_phases(storage)] \
        == (["boot"] if stamped else []) + OWN
    assert spans.RUN_RESPAWN_ENV not in os.environ


@pytest.mark.parametrize("word", ["soon", "-0.5", "inf", "nan", ""])
def test_a_respawn_that_is_no_length_is_no_row(fresh_obs, monkeypatch, word):
    t = time.monotonic()
    monkeypatch.setenv(spans.RUN_SPAWNED_ENV, repr(t))
    monkeypatch.setenv(spans.RUN_RESPAWN_ENV, word)
    obs.run_begin("r0", t + 0.5)
    assert [r[0] for r in obs.run_end()] == ["boot"]
    assert spans.RUN_RESPAWN_ENV not in os.environ


def test_the_phase_is_named_where_phases_are_listed():
    assert spans.RESPAWN_PHASE == "respawn"
    assert spans.RESPAWN_PHASE in spans.SUPERVISOR_PHASES
    # the benchmark's eight `run_<phase>_s` are RUN_PHASES' names
    assert spans.RESPAWN_PHASE not in spans.RUN_PHASES


def test_a_campaign_hands_every_run_but_its_first_its_respawn(tmp_path,
                                                              fresh_obs):
    storage = init_storage(tmp_path)
    spec = CampaignSpec(storage_dir=storage, runs=3, seed=7,
                        telemetry_collector="")
    assert Campaign(spec).run() == 0
    attempts = [s["attempts"][-1] for s in load_checkpoint(storage)["slots"]]
    assert "respawn" not in [r[0] for r in stored_phases(storage, 0)]
    assert "respawn" not in [r[0] for r in attempts[0]["phases"]]
    for i in (1, 2):
        stored = [r for r in stored_phases(storage, i) if r[0] == "respawn"]
        # one row with the run, and the supervisor's one in campaign.json
        # is the same stretch: the number it sent is the number it kept
        assert [r for r in attempts[i]["phases"] if r[0] == "respawn"] \
            == stored == [attempts[i]["phases"][0]]
        ends_at_zero(stored[0], stored[0][3])
    assert phase_counts() == {"teardown": 3, "respawn": 2}
    # and a reader of the storage meets them where it meets the others
    st = load_storage(storage)
    for i in range(3):
        obs.run_phases_observed(st.get_metadata(i)["phases"])
    assert phase_counts()["respawn"] == 2 + 2
    assert phase_counts()["standby"] == 2
