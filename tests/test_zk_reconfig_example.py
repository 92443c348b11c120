"""Acceptance test over the zk-reconfig example: the ZOOKEEPER-2080 hunt
as upstream's FLE-only inspector sees it — five reconfiguring servers on
ZooKeeper's 3.5 election wire format, the election port behind the
proxy inspector and the quorum port direct, a whole scenario of restarts
and one reconfiguration a run, an oracle of its own.

One worker runs this file (the testee binds 127.1.0.1-5 at ZooKeeper's
own ports and REST port 10986: no address of another example's, so it
runs beside tests/test_zk_zab_example.py)."""

import collections
import json
import os

import pytest

from namazu_tpu.cli import cli_main
from namazu_tpu.storage import load_storage

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLE = os.path.join(REPO, "examples", "zk-reconfig")


@pytest.fixture(scope="module")
def storage(tmp_path_factory):
    """A storage under the recording config: `dumb` at 80 ms."""
    path = str(tmp_path_factory.mktemp("reconfig") / "fuzz")
    assert cli_main(["init", os.path.join(EXAMPLE, "config.toml"),
                     os.path.join(EXAMPLE, "materials"), path]) == 0
    return path


def run_once(storage, monkeypatch, **env):
    for k, v in env.items():
        monkeypatch.setenv(k, str(v))
    n = load_storage(storage).nr_stored_histories()
    assert cli_main(["run", storage]) == 0
    st = load_storage(storage)
    assert st.nr_stored_histories() == n + 1
    run_dir = os.path.join(storage, f"{n:08x}")
    with open(os.path.join(run_dir, "trace.json")) as f:
        trace = json.load(f)
    actions = trace["actions"] if isinstance(trace, dict) else trace
    return st.is_successful(n), actions, run_dir


def test_a_recorded_run_is_a_whole_scenario_on_the_election_stream(
        storage, monkeypatch):
    """THE BUG forced shut (no teardown window): the oracle passes, and
    the run holds 256 deferred events or more, every one an election
    message, each held the source's 80 ms."""
    ok, actions, run_dir = run_once(storage, monkeypatch,
                                    NMZ_CALIB_TEARDOWN_MS=0)
    assert ok
    hints = [a["event_hint"] for a in actions]
    assert len(hints) >= 256, len(hints)
    assert all(":fle:" in h for h in hints)
    kinds = collections.Counter(h.split(":")[2] for h in hints)
    assert set(kinds) == {"init", "notif"}
    # two configuration versions were on the wire, and every server
    # ends in the new one under one leader
    with open(os.path.join(run_dir, "scenario.log")) as f:
        steps = [line for line in f if "formed after" in line]
    assert len(steps) == 16 and not [s for s in steps if "NOT" in s]
    for n in range(1, 6):
        with open(os.path.join(run_dir, f"state{n}")) as f:
            assert "config=200000001" in f.read()
    held = [a["triggered_time"] - a["event_arrived"] for a in actions]
    # (the last few are flushed when the run ends)
    assert 0.079 <= sorted(held)[len(held) // 2] < 0.12
    # the quorum port is not inspected: the reconfiguration went through
    with open(os.path.join(run_dir, "server4.log")) as f:
        assert "reconfiguration 200000001 committed" in f.read()


def test_the_oracle_fails_when_the_bug_is_forced(storage, monkeypatch):
    """A teardown that outlasts the reconfiguration's announcement: the
    newer configuration reaches server 3 inside it, the server wedges,
    the step never forms and the oracle says so."""
    ok, actions, run_dir = run_once(
        storage, monkeypatch, NMZ_CALIB_TEARDOWN_MS=3000,
        NMZ_ZK2080_RECONFIG_DELAY_MS=400, NMZ_ZK2080_STEP_DEADLINE_S=5)
    assert not ok
    assert os.path.exists(os.path.join(run_dir, "timed_out"))
    with open(os.path.join(run_dir, "server3.log")) as f:
        log3 = f.read()
    assert "newer configuration 200000001 from 5" in log3
    # wedged before it followed again: its last election never formed
    assert log3.rindex("elected leader=4") > log3.rindex("formed:")
    assert len(actions) < 256  # the scenario stopped at the race
