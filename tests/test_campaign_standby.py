"""The standby run child (doc/performance.md "Standby run child"): the
campaign supervisor starts the NEXT attempt's ``nmz-tpu run`` while the
current run is going; the child waits, imported, at a gate — one
blocking read of its stdin — before it has read, opened or bound
anything, and the go line carries the spawn stamp and this attempt's
environment. The gate is held with the real child; the supervisor with
a stand-in that speaks the gate's protocol and logs what it sees (no
testee, so the file holds under ``-n 6``). Held to names, orderings and
process tables, never to a wall time."""

import json
import os
import signal
import stat
import subprocess
import sys
import threading
import time

import pytest

from namazu_tpu import obs
from namazu_tpu.campaign import (
    CLASS_EXPERIMENT,
    Campaign,
    CampaignSpec,
    load_checkpoint,
)
from namazu_tpu.cli import cli_main
from namazu_tpu.cli.run_cmd import RUN_STANDBY_ENV
from namazu_tpu.obs import federation, spans
from namazu_tpu.storage import load_storage
from namazu_tpu.utils.cmd import CmdFactory, kill_process_group

from tests.test_request_spans import isolated_obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the stand-in run child: argv as the supervisor builds it
#: (``-m namazu_tpu.cli run <storage> ...``), the gate's protocol, and
#: one line per event into ``<storage>/standin.log``
STANDIN = r'''#!{python}
import json, os, sys, time
storage = sys.argv[4]
gated = bool(os.environ.pop("{gate}", ""))
def log(event, **kw):
    with open(os.path.join(storage, "standin.log"), "a") as f:
        f.write(json.dumps(dict(kw, event=event, pid=os.getpid(),
                                sid=os.getsid(0), gated=gated,
                                t=time.monotonic())) + "\n")
log("start")
go = None
if gated:
    if os.environ.get("STANDIN_DIE_AT_GATE"):
        sys.exit(3)
    line = sys.stdin.readline()
    if not line:
        log("eof")
        sys.exit(0)
    go = json.loads(line)
log("run", go=go, spawned=os.environ.get("NMZ_RUN_SPAWNED"),
    knob=os.environ.get("NMZ_CALIB_KNOB"))
time.sleep(float(os.environ.get("STANDIN_RUN_S", "0.05")))
log("end")
'''


@pytest.fixture
def fresh_obs():
    with isolated_obs() as ring:
        yield ring
    federation.reset()
    obs.profiling.reset()


def init_storage(tmp_path, run="true", extra=""):
    materials = tmp_path / "materials"
    materials.mkdir(exist_ok=True)
    config = tmp_path / "config.toml"
    config.write_text('explore_policy = "dumb"\nrest_port = 0\n'
                      f'run = {json.dumps(run)}\nvalidate = "true"\n'
                      + extra)
    storage = str(tmp_path / "st")
    assert cli_main(["init", str(config), str(materials), storage]) == 0
    return storage


def standin(tmp_path) -> str:
    path = tmp_path / "standin.py"
    path.write_text(STANDIN.format(python=sys.executable,
                                   gate=RUN_STANDBY_ENV))
    path.chmod(path.stat().st_mode | stat.S_IXUSR)
    return str(path)


def events(storage):
    try:
        with open(os.path.join(storage, "standin.log")) as f:
            return [json.loads(line) for line in f]
    except OSError:
        return []


def session_alive(sid: int) -> bool:
    """Whether any process of the session's group is left (a run child
    is its session's and its group's leader: ``start_new_session``)."""
    try:
        os.killpg(sid, 0)
    except (ProcessLookupError, PermissionError):
        return False
    # a zombie nobody reaps any more still answers signal 0
    try:
        with open(f"/proc/{sid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def wait_until(cond, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return cond()


def wait_at_gate(pid: int, timeout=60.0) -> None:
    """Until the process sleeps and burns no CPU over a quarter of a
    second: imported, and blocked in its gate's read."""
    def ticks():
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return fields[0], int(fields[11]) + int(fields[12])

    deadline = time.monotonic() + timeout
    seen = None
    while time.monotonic() < deadline:
        now = ticks()
        if now[0] == "S" and now == seen:
            return
        seen = now
        time.sleep(0.25)


def listing(root):
    """Every path under ``root`` with its mtime and size."""
    out = {}
    for base, dirs, files in os.walk(root):
        for name in dirs + files:
            path = os.path.join(base, name)
            st = os.stat(path)
            out[os.path.relpath(path, root)] = (st.st_mtime_ns, st.st_size)
    out["."] = (os.stat(root).st_mtime_ns, 0)
    return out


def real_child(storage, gated=True, extra_env=None, **popen):
    env = CmdFactory(extra_env=extra_env or {}).env()
    env.pop(RUN_STANDBY_ENV, None)
    env.pop(spans.RUN_SPAWNED_ENV, None)
    if gated:
        env[RUN_STANDBY_ENV] = "1"
    return subprocess.Popen(
        [sys.executable, "-m", "namazu_tpu.cli", "run", storage],
        env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, start_new_session=True, **popen)


def spec_for(storage, tmp_path, **kw):
    kw.setdefault("runs", 2)
    kw.setdefault("retries", 0)
    kw.setdefault("seed", 7)
    kw.setdefault("telemetry_collector", "")
    kw.setdefault("python", standin(tmp_path))
    return CampaignSpec(storage_dir=storage, **kw)


# -- (i) the gate, child side: never wanted ------------------------------


@pytest.mark.parametrize("line", [
    b"", b"\n", b"go\n", b"[]\n", b"{}\n", b'{"spawned": 1.0}\n',
    b'{"spawned": "soon", "env": {}}\n', b'{"spawned": null, "env": 7}\n'],
    ids=["eof", "empty_line", "a_word", "a_list", "no_keys", "no_env",
         "a_stamp_that_is_no_number", "an_env_that_is_no_object"])
def test_what_is_not_the_go_leaves_the_storage_as_it_was(tmp_path, line):
    storage = init_storage(tmp_path)
    before = listing(storage)
    child = real_child(storage)
    out, _ = child.communicate(line, timeout=60)
    assert child.returncode == 0, out
    assert out == b""
    assert listing(storage) == before


# -- (ii) everything a user can change is read after the go --------------


def test_a_config_rewritten_before_the_go_is_the_one_the_run_uses(tmp_path):
    storage = init_storage(tmp_path, run='echo old > "$NMZ_WORKING_DIR/ran"')
    child = real_child(storage, extra_env={"PATH_WAS": "set"})
    # at its gate — and the claim holds wherever it is, since nothing
    # is read before the go
    wait_at_gate(child.pid)
    assert child.poll() is None
    with open(os.path.join(storage, "config.toml"), "w") as f:
        f.write('explore_policy = "dumb"\nrest_port = 0\n'
                'run = "echo new $NMZ_CALIB_KNOB $PATH_WAS '
                '> \\"$NMZ_WORKING_DIR/ran\\""\nvalidate = "true"\n'
                'obs_enabled = true\n')
    go = {"spawned": None,
          "env": {"NMZ_CALIB_KNOB": "7", "PATH_WAS": None}}
    out, _ = child.communicate(json.dumps(go).encode() + b"\n", timeout=60)
    assert child.returncode == 0, out
    with open(os.path.join(storage, "00000000", "ran")) as f:
        assert f.read().split() == ["new", "7"]
    assert load_storage(storage).nr_stored_histories() == 1


# -- (vi) phases ---------------------------------------------------------


def test_a_warm_run_stores_standby_and_a_boot_from_the_go(tmp_path):
    storage = init_storage(tmp_path)
    child = real_child(storage)
    wait_at_gate(child.pid)
    t_go = time.monotonic()
    out, _ = child.communicate(json.dumps(
        {"spawned": t_go, "env": {}}).encode() + b"\n", timeout=60)
    assert child.returncode == 0, out
    rows = load_storage(storage).get_metadata(0)["phases"]
    by_name = {r[0]: r for r in rows}
    standby, boot = by_name["standby"], by_name["boot"]
    assert [r[0] for r in rows[:2]] == ["standby", "boot"]
    assert standby[1] is None and standby[2] < 0
    assert standby[2] == pytest.approx(-standby[3], abs=1e-5)
    # `boot` is what is left after the go: it starts there, and it is
    # shorter than the wait it no longer holds
    assert boot[2] == 0.0 and 0 <= boot[3] < standby[3]
    assert by_name["prepare"][2] == pytest.approx(boot[3], abs=1e-5)


@pytest.mark.parametrize("since, start, seconds", [
    (-2.5, -2.5, 2.5), (0.25, 0.0, 0.0)],
    ids=["at_the_gate_before_the_go", "still_importing_at_the_go"])
def test_the_standby_row_ends_at_the_go(fresh_obs, monkeypatch, since,
                                        start, seconds):
    t_go = time.monotonic()
    monkeypatch.setenv(spans.RUN_SPAWNED_ENV, repr(t_go))
    obs.run_begin("r0", t_go + 0.5, t_go + since)
    rows = obs.run_end()
    assert sorted(r[0] for r in rows) == ["boot", "standby"]
    by_name = {r[0]: r for r in rows}
    assert by_name["standby"][1:] == [None, pytest.approx(start, abs=1e-5),
                                      pytest.approx(seconds, abs=1e-5)]
    assert by_name["boot"][2:] == [0.0, pytest.approx(0.5, abs=1e-5)]


def test_a_cold_run_has_no_standby_row(fresh_obs, monkeypatch):
    t = time.monotonic()
    monkeypatch.setenv(spans.RUN_SPAWNED_ENV, repr(t))
    obs.run_begin("r0", t + 0.5)
    assert [r[0] for r in obs.run_end()] == ["boot"]
    # nor a run nobody spawned, whatever it is handed
    monkeypatch.delenv(spans.RUN_SPAWNED_ENV, raising=False)
    obs.run_begin("r1", t + 0.5, t - 1.0)
    assert obs.run_end() == []


def test_the_new_phase_is_named_where_phases_are_listed():
    # a name of its own beside the eight: the benchmark's
    # ``run_<phase>_s`` are RUN_PHASES' names, one metric each
    assert spans.STANDBY_PHASE == "standby"
    assert spans.STANDBY_PHASE not in spans.RUN_PHASES
    assert spans.STANDBY_PHASE not in spans.SUPERVISOR_PHASES
    with open(os.path.join(REPO, "doc", "observability.md")) as f:
        doc = f.read()
    assert "| `standby` |" in doc.split("#### Run phases", 1)[1]


def test_a_stored_standby_row_is_observed_like_the_others(fresh_obs):
    spans.run_phases_observed([["standby", None, -2.5, 2.5],
                               ["boot", None, 0.0, 0.01]])
    fam = next(f for f in obs.metrics.registry().to_jsonable()["metrics"]
               if f["name"] == spans.RUN_PHASE)
    assert {s["labels"]["phase"]: s["value"]["count"]
            for s in fam["samples"]} == {"standby": 1, "boot": 1}


def test_a_campaign_of_real_runs_starts_cold_then_warm(tmp_path, fresh_obs):
    storage = init_storage(tmp_path)
    campaign = Campaign(spec_for(storage, tmp_path, runs=3,
                                 python=sys.executable))
    # the next slot finds its standby at the gate
    campaign.spec.on_slot = lambda slot, progress: (
        campaign._standby and wait_at_gate(campaign._standby.pid))
    assert campaign.run() == 0
    attempts = [s["attempts"][-1]
                for s in load_checkpoint(storage)["slots"]]
    assert [a["start"] for a in attempts] == ["cold", "standby", "standby"]
    assert [a["class"] for a in attempts] == [CLASS_EXPERIMENT] * 3
    for i, a in enumerate(attempts):
        names = [r[0] for r in a["phases"]]
        stored = [r[0] for r in load_storage(storage).get_metadata(i)[
            "phases"]]
        assert ("standby" in names) == ("standby" in stored) == (i > 0)
        assert "boot" in stored
    for a in attempts[1:]:
        standby = next(r for r in a["phases"] if r[0] == "standby")
        assert standby[2] < 0 and standby[2] == -standby[3]


# -- (vii) a bare run -----------------------------------------------------


def test_a_bare_run_never_reads_its_stdin(tmp_path):
    """The pipe stays open and empty for the whole run: a run that read
    it would not end."""
    storage = init_storage(tmp_path)
    child = real_child(storage, gated=False)
    try:
        assert child.wait(timeout=60) == 0
    finally:
        kill_process_group(child)
    child.stdin.close()
    assert load_storage(storage).nr_stored_histories() == 1
    assert "standby" not in {
        r[0] for r in load_storage(storage).get_metadata(0)["phases"]}


def test_the_gates_variable_is_gone_before_the_run_spawns_anything(
        tmp_path):
    storage = init_storage(
        tmp_path,
        run=f'echo "[${RUN_STANDBY_ENV}]" > "$NMZ_WORKING_DIR/ran"')
    child = real_child(storage)
    out, _ = child.communicate(b'{"spawned": null, "env": {}}\n', timeout=60)
    assert child.returncode == 0, out
    with open(os.path.join(storage, "00000000", "ran")) as f:
        assert f.read().strip() == "[]"


# -- the supervisor, with the stand-in child ------------------------------


def test_every_attempt_after_the_first_takes_the_standby(tmp_path,
                                                         fresh_obs):
    storage = init_storage(tmp_path)
    campaign = Campaign(spec_for(storage, tmp_path, runs=3))
    assert campaign.run() == 0
    attempts = [s["attempts"][-1] for s in campaign.state["slots"]]
    assert [a["start"] for a in attempts] == ["cold", "standby", "standby"]
    log = events(storage)
    runs = [e for e in log if e["event"] == "run"]
    assert [e["gated"] for e in runs] == [False, True, True]
    # the cold child has its stamp from the environment, a standby from
    # the go line: the instant of the go, later than its own start
    assert runs[0]["go"] is None and float(runs[0]["spawned"]) > 0
    for e in runs[1:]:
        started = next(s["t"] for s in log
                       if s["event"] == "start" and s["pid"] == e["pid"])
        assert e["go"]["spawned"] > started
        assert e["go"]["env"] == {}
    # one standby too many was started, and ended at its gate
    assert sum(e["event"] == "start" for e in log) == 4
    assert sum(e["event"] == "eof" for e in log) == 1


def test_the_go_carries_what_moved_in_the_environment(tmp_path, fresh_obs):
    storage = init_storage(tmp_path)
    spec = spec_for(storage, tmp_path, runs=3,
                    extra_env={"NMZ_CALIB_KNOB": "1", "STANDIN_GONE": "x"})

    def on_slot(slot, progress):
        # a probe's next candidate: set after the standby was started
        spec.extra_env["NMZ_CALIB_KNOB"] = str(2 + slot["slot"])
        spec.extra_env.pop("STANDIN_GONE", None)
        return False

    spec.on_slot = on_slot
    assert Campaign(spec).run() == 0
    runs = [e for e in events(storage) if e["event"] == "run"]
    assert [e["go"] and e["go"]["env"] for e in runs] == [
        None, {"NMZ_CALIB_KNOB": "2", "STANDIN_GONE": None},
        {"NMZ_CALIB_KNOB": "3"}]


def test_the_real_gate_puts_the_go_into_the_runs_environment(tmp_path):
    """The other half of the test above, with the real child."""
    storage = init_storage(
        tmp_path, run='echo "$NMZ_CALIB_KNOB" > "$NMZ_WORKING_DIR/ran"')
    child = real_child(storage)
    out, _ = child.communicate(
        b'{"spawned": null, "env": {"NMZ_CALIB_KNOB": "3"}}\n', timeout=60)
    assert child.returncode == 0, out
    with open(os.path.join(storage, "00000000", "ran")) as f:
        assert f.read().strip() == "3"


# -- (iii) the wall deadline counts from the go ---------------------------


def test_a_standby_that_waited_longer_than_the_deadline_is_not_killed(
        tmp_path, fresh_obs):
    storage = init_storage(tmp_path)
    spec = spec_for(storage, tmp_path, runs=2, run_wall_deadline_s=1.0,
                    on_slot=lambda slot, progress: time.sleep(1.5))
    assert Campaign(spec).run() == 0
    second = load_checkpoint(storage)["slots"][1]["attempts"][-1]
    assert second["start"] == "standby"
    assert second["class"] == CLASS_EXPERIMENT
    assert not second["wall_deadline_hit"] and second["wall_s"] < 1.0
    log = events(storage)
    run = [e for e in log if e["event"] == "run"][1]
    started = next(s["t"] for s in log
                   if s["event"] == "start" and s["pid"] == run["pid"])
    assert run["t"] - started > 1.0  # it did wait past the deadline


def test_a_standby_run_past_its_deadline_is_killed_for_it(tmp_path,
                                                          fresh_obs):
    storage = init_storage(tmp_path)
    spec = spec_for(storage, tmp_path, runs=2, run_wall_deadline_s=0.5,
                    max_consecutive_infra=0,
                    extra_env={"STANDIN_RUN_S": "30"})
    Campaign(spec).run()
    attempts = [s["attempts"][-1]
                for s in load_checkpoint(storage)["slots"]]
    assert [(a["start"], a["wall_deadline_hit"]) for a in attempts] == [
        ("cold", True), ("standby", True)]
    assert all(0.5 <= a["wall_s"] < 10 for a in attempts)
    for sid in {e["sid"] for e in events(storage)}:
        assert wait_until(lambda: not session_alive(sid))


# -- (v) a standby that died while waiting --------------------------------


def test_a_standby_that_died_is_noticed_at_the_go(tmp_path, fresh_obs):
    storage = init_storage(tmp_path)
    spec = spec_for(storage, tmp_path, runs=3,
                    extra_env={"STANDIN_DIE_AT_GATE": "1"})
    assert Campaign(spec).run() == 0
    attempts = [s["attempts"][-1]
                for s in load_checkpoint(storage)["slots"]]
    assert [(a["start"], a["class"], a["exit_status"])
            for a in attempts] == [("cold", CLASS_EXPERIMENT, 0)] * 3
    log = events(storage)
    # three cold children ran; three standbys were started and died
    assert [e["gated"] for e in log if e["event"] == "run"] == [False] * 3
    assert sum(e["event"] == "start" and e["gated"] for e in log) == 3


def test_a_standby_that_cannot_be_started_costs_nothing(tmp_path,
                                                        fresh_obs,
                                                        monkeypatch):
    storage = init_storage(tmp_path)
    campaign = Campaign(spec_for(storage, tmp_path, runs=2))
    real = subprocess.Popen

    def popen(argv, **kw):
        if RUN_STANDBY_ENV in kw.get("env", {}):
            raise OSError("no more processes")
        return real(argv, **kw)

    monkeypatch.setattr(subprocess, "Popen", popen)
    assert campaign.run() == 0
    assert [s["attempts"][-1]["start"]
            for s in campaign.state["slots"]] == ["cold", "cold"]


# -- (iv) every way out ends the standby ----------------------------------


def no_session_left(storage):
    sids = {e["sid"] for e in events(storage)}
    assert sids
    for sid in sids:
        assert wait_until(lambda: not session_alive(sid)), sid
    return sids


def test_a_campaign_that_reaches_n_leaves_no_standby(tmp_path, fresh_obs):
    storage = init_storage(tmp_path)
    campaign = Campaign(spec_for(storage, tmp_path, runs=2))
    assert campaign.run() == 0
    assert campaign._standby is None
    assert len(no_session_left(storage)) == 3
    assert [e["event"] for e in events(storage)].count("eof") == 1


def test_an_aborted_campaign_leaves_no_standby(tmp_path, fresh_obs):
    """What the signal handler does on the second signal, done from
    here (a thread's campaign installs no handlers)."""
    storage = init_storage(tmp_path)
    campaign = Campaign(spec_for(storage, tmp_path, runs=5,
                                 extra_env={"STANDIN_RUN_S": "30"}))
    status = []
    thread = threading.Thread(target=lambda: status.append(campaign.run()))
    thread.start()
    assert wait_until(lambda: sum(
        e["event"] == "start" for e in events(storage)) == 2)
    campaign._stop_requested.set()
    campaign._abort.set()
    with campaign._child_lock:
        kill_process_group(campaign._child)
    thread.join(timeout=30)
    assert status == [130]
    assert campaign._standby is None
    assert len(no_session_left(storage)) == 2


def test_an_exception_in_the_loop_leaves_no_standby(tmp_path, fresh_obs):
    storage = init_storage(tmp_path)

    def on_slot(slot, progress):
        raise RuntimeError("a callback's own fault")

    campaign = Campaign(spec_for(storage, tmp_path, runs=5,
                                 on_slot=on_slot))
    with pytest.raises(RuntimeError):
        campaign.run()
    assert campaign._standby is None
    assert len(no_session_left(storage)) == 2


SUPERVISOR = """
import sys
from namazu_tpu.campaign import Campaign, CampaignSpec
sys.exit(Campaign(CampaignSpec(
    storage_dir=sys.argv[1], runs=50, retries=0, python=sys.argv[2],
    telemetry_collector="", run_wall_deadline_s=float(sys.argv[4]),
    extra_env={"STANDIN_RUN_S": sys.argv[3]})).run())
"""


def supervisor(storage, tmp_path, run_s, wall_deadline_s=0.0):
    proc = subprocess.Popen(
        [sys.executable, "-c", SUPERVISOR, storage, standin(tmp_path),
         str(run_s), str(wall_deadline_s)],
        env=CmdFactory().env(), stdin=subprocess.DEVNULL,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        start_new_session=True)
    # one run in flight and one standby at its gate
    assert wait_until(lambda: sum(
        e["event"] == "start" for e in events(storage)) >= 2, 60)
    return proc


@pytest.mark.parametrize("signals, status", [(1, 130), (2, 130)],
                         ids=["one_sigterm_finishes_the_run",
                              "two_sigterms_abort_it"])
def test_a_signalled_supervisor_leaves_no_standby(tmp_path, signals,
                                                  status,
                                                  wall_deadline_s=0.0):
    storage = init_storage(tmp_path)
    proc = supervisor(storage, tmp_path, 1.0 if signals == 1 else 30,
                      wall_deadline_s)
    try:
        for _ in range(signals):
            proc.send_signal(signal.SIGTERM)
            time.sleep(0.2)
        assert proc.wait(timeout=60) == status
    finally:
        kill_process_group(proc)
    no_session_left(storage)
    log = events(storage)
    # the run in flight ended by itself after one signal, and not after two
    last_run = [e for e in log if e["event"] == "run"][-1]
    ended = any(e["event"] == "end" and e["pid"] == last_run["pid"]
                for e in log)
    assert ended == (signals == 1)
    assert [e["event"] for e in log].count("eof") <= 1


def test_a_killed_supervisor_ends_its_standby_through_eof(tmp_path):
    storage = init_storage(tmp_path)
    proc = supervisor(storage, tmp_path, 1.0)
    standby = [e for e in events(storage) if e["event"] == "start"][-1]
    assert standby["gated"] and session_alive(standby["sid"])
    proc.kill()
    proc.wait(timeout=30)
    # nobody closed the pipe and nobody reaps: the kernel's EOF alone
    assert wait_until(lambda: any(
        e["event"] == "eof" and e["pid"] == standby["pid"]
        for e in events(storage)))
    no_session_left(storage)
    assert sum(e["event"] == "run" for e in events(storage)) == 1


# -- the reap (ISSUE 47): the attempt's wait returns on the child's exit,
# under a wall deadline as without one; orderings, no duration -----------


@pytest.mark.parametrize("deadline", [60.0, 0.0],
                         ids=["under_a_wall_deadline", "without_one"])
def test_an_attempt_never_enters_subprocess_s_sleep_loop(
        tmp_path, fresh_obs, monkeypatch, deadline):
    """``Popen.wait(timeout=)`` is a sleep loop backing off to 50 ms;
    the attempt's wait for its run child must block in the kernel
    instead. Every ``time.sleep`` of the subprocess module and every
    ``Popen._wait`` is recorded while an attempt is inside
    ``_one_attempt``: no sleep, and no wait with a timeout."""
    import types

    inside, sleeps, waits = [], [], []
    proxy = types.SimpleNamespace(**{
        k: getattr(time, k) for k in dir(time) if not k.startswith("__")})

    def recording_sleep(seconds):
        if inside:
            sleeps.append(seconds)
        time.sleep(seconds)

    proxy.sleep = recording_sleep
    monkeypatch.setattr(subprocess, "time", proxy)
    plain_wait = subprocess.Popen._wait

    def recording_wait(self, timeout):
        if inside:
            waits.append(timeout)
        return plain_wait(self, timeout)

    monkeypatch.setattr(subprocess.Popen, "_wait", recording_wait)
    plain_attempt = Campaign._one_attempt

    def attempt(self, slot_index=0):
        inside.append(slot_index)
        try:
            return plain_attempt(self, slot_index)
        finally:
            inside.clear()

    monkeypatch.setattr(Campaign, "_one_attempt", attempt)
    storage = init_storage(tmp_path)
    spec = spec_for(storage, tmp_path, runs=3, run_wall_deadline_s=deadline,
                    extra_env={"STANDIN_RUN_S": "0.3"})
    assert Campaign(spec).run() == 0
    slots = load_checkpoint(storage)["slots"]
    assert [s["class"] for s in slots] == [CLASS_EXPERIMENT] * 3
    assert sleeps == []
    assert waits and set(waits) == {None}  # the blocking waitpid alone


@pytest.mark.parametrize("deadline", [None, 60.0],
                         ids=["without_a_deadline", "under_one"])
def test_the_reap_returns_with_the_exit_status(deadline):
    from namazu_tpu.campaign import _reap

    child = subprocess.Popen([sys.executable, "-c", "raise SystemExit(7)"],
                             start_new_session=True)
    _reap(child, deadline)
    assert child.returncode == 7


def test_the_reap_past_its_deadline_raises_what_popen_wait_raises():
    from namazu_tpu.campaign import _reap

    child = subprocess.Popen(["sleep", "600"], start_new_session=True)
    try:
        with pytest.raises(subprocess.TimeoutExpired) as ei:
            _reap(child, 0.2)
        assert ei.value.timeout == 0.2 and ei.value.cmd == child.args
        assert child.poll() is None  # the kill is the caller's
    finally:
        kill_process_group(child)
    # the reaper's own waitpid took the status; nothing is left to reap
    assert wait_until(lambda: child.returncode == -signal.SIGTERM)


@pytest.mark.parametrize("signals", [1, 2], ids=[
    "one_sigterm_finishes_the_run", "two_sigterms_abort_it"])
def test_a_signal_reaches_a_supervisor_waiting_under_a_wall_deadline(
        tmp_path, signals):
    """The supervisor's main thread waits in a join, not in a waitpid:
    the handlers still run there, the second signal still kills the
    run's group, and the campaign still ends 130."""
    test_a_signalled_supervisor_leaves_no_standby(
        tmp_path, signals, 130, wall_deadline_s=120.0)
