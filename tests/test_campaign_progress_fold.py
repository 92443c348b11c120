"""Between two runs the campaign supervisor touches only the run dirs the
attempt it has just reaped created (doc/performance.md "Between runs"):
one storage handle a campaign, the progress document folded run by run
(obs/analytics.py ``ProgressFold``), the pgid sweep over the attempt's
own dirs. Held here to two things. The document: after every slot it is
byte-for-byte what ``progress_stats`` over a fresh ``load_storage`` gives
at that moment, and the counter says ``walk`` exactly where the whole
history was read. The depth: the file operations between a reap and the
next go are the same number on a storage 16 runs deep and 256 runs deep,
and none of them names a run below the watermark.

The run child is a stand-in that speaks the standby gate's protocol and
stores what a plan tells it to (no testee, so the file holds under
``-n 6``); held to counts, names and bytes, never to a wall time."""

import builtins
import json
import os
import re
import shutil
import signal
import stat
import subprocess
import sys

import pytest

from namazu_tpu import obs
from namazu_tpu.campaign import Campaign, CampaignSpec, load_checkpoint
from namazu_tpu.cli.run_cmd import RUN_STANDBY_ENV
from namazu_tpu.obs import analytics, spans
from namazu_tpu.storage import load_storage
from namazu_tpu.utils.trace import SingleTrace

from tests.test_campaign_standby import (  # noqa: F401  (a fixture)
    fresh_obs,
    session_alive,
    wait_until,
)
from tests.test_run_phases import init_storage as init_empty

#: the stand-in run child: argv as the supervisor builds it
#: (``-m namazu_tpu.cli run <storage> ...``), the gate's protocol, and
#: then the next entry of ``<storage>/plan.json``: ``ok`` / ``fail`` (a
#: stored run), ``vclock`` (one with ``virtual_time_s``), ``hang`` (a
#: trace and no result, then a sleep the wall deadline ends), ``infra``
#: (exit 1, nothing allocated), ``crumb`` (a run dir holding the
#: breadcrumb of a group of its own that outlives it, then SIGKILL)
STANDIN = r'''#!{python}
import json, os, signal, subprocess, sys, time
storage = sys.argv[4]
if os.environ.pop("{gate}", ""):
    if not sys.stdin.readline():
        sys.exit(0)
with open(os.path.join(storage, "plan.json")) as f:
    plan = json.load(f)
log = os.path.join(storage, "standin.log")
try:
    with open(log) as f:
        n = len(f.readlines())
except OSError:
    n = 0
act = plan[n]
with open(log, "a") as f:
    f.write(json.dumps(act) + "\n")
if act["kind"] == "infra":
    sys.exit(1)
from namazu_tpu.storage import load_storage
from namazu_tpu.utils.trace import SingleTrace
st = load_storage(storage)
run_dir = st.create_new_working_dir()
if act["kind"] == "crumb":
    orphan = subprocess.Popen(["sleep", "600"], start_new_session=True)
    with open(os.path.join(run_dir, "phase.pgid"), "w") as f:
        f.write(str(os.getpgid(orphan.pid)))
    with open(os.path.join(storage, "orphans"), "a") as f:
        f.write(str(orphan.pid) + "\n")
    os.kill(os.getpid(), signal.SIGKILL)
st.record_new_trace(SingleTrace())
if act["kind"] == "hang":
    time.sleep(600)
meta = dict(hint_space=1)
if act["kind"] == "vclock":
    meta["virtual_time_s"] = act["t"] * 40.0
st.record_result(act["kind"] != "fail", act["t"], metadata=meta)
st.close()
'''

#: one campaign's attempts: a success, a failure, a fast-forwarded run,
#: an attempt the wall deadline kills after its trace (and the retry
#: that stores the slot's run), an infra exit that left no dir (and its
#: retry), and times whose float sum depends on the order of addition
PLAN = [{"kind": "ok", "t": 0.1}, {"kind": "fail", "t": 0.2},
        {"kind": "vclock", "t": 0.30000000000000004},
        {"kind": "hang", "t": 0.0}, {"kind": "ok", "t": 1e-9},
        {"kind": "infra", "t": 0.0}, {"kind": "fail", "t": 1e16},
        {"kind": "ok", "t": 0.7}, {"kind": "ok", "t": 3.3}]
#: the slots those nine attempts make (retries = 1)
SLOTS = 7

CALIBRATION = {"schema": "nmz-calib-v1", "status": "calibrated",
               "band": [0.1, 0.6], "knobs": {"window_ms": 424},
               "rate": 0.25, "rate_ci95": [0.1, 0.5],
               "runs_saved_pct": 61.2}


def init_storage(tmp_path, prefill=0):
    storage = init_empty(tmp_path)
    st = load_storage(storage)
    for i in range(prefill):
        st.create_new_working_dir()
        st.record_new_trace(SingleTrace())
        st.record_result(i % 5 != 2, 0.1 * (i + 1))
    st.close()
    return storage


def standin(tmp_path) -> str:
    path = tmp_path / "standin.py"
    path.write_text(STANDIN.format(python=sys.executable,
                                   gate=RUN_STANDBY_ENV))
    path.chmod(path.stat().st_mode | stat.S_IXUSR)
    return str(path)


def write_plan(storage, plan):
    with open(os.path.join(storage, "plan.json"), "w") as f:
        json.dump(plan, f)
    try:
        os.unlink(os.path.join(storage, "standin.log"))
    except OSError:
        pass


def spec_for(tmp_path, storage, runs, **kw):
    kw.setdefault("retries", 1)
    return CampaignSpec(storage_dir=storage, runs=runs, seed=7,
                        python=standin(tmp_path), telemetry_collector="",
                        run_wall_deadline_s=4.0, backoff_base_s=0.01,
                        backoff_cap_s=0.02, **kw)


def dump(doc) -> str:
    return json.dumps(doc, sort_keys=True, allow_nan=False)


def recomputed(storage) -> str:
    """The progress document from scratch, now: a fresh handle
    (``init()`` and its quarantine), both files read anew, the whole
    history walked."""
    st = load_storage(storage)
    try:
        calib, ckpt = analytics._progress_inputs(storage)
        return dump(analytics.progress_stats(st, calibration=calib,
                                             checkpoint=ckpt))
    finally:
        st.close()


def fold_counts():
    for fam in obs.metrics.registry().to_jsonable()["metrics"]:
        if fam["name"] == spans.CAMPAIGN_PROGRESS_FOLDS:
            return {s["labels"]["path"]: s["value"]
                    for s in fam["samples"]}
    return {}


class Checked:
    """An ``on_slot`` that holds every slot's document to the
    recomputation and keeps how each was made."""

    def __init__(self, storage, then=None):
        self.storage, self.then = storage, then
        self.paths, self.docs = [], []

    def __call__(self, slot, progress):
        assert progress is not None
        assert dump(progress) == recomputed(self.storage)
        self.paths.append(slot["progress_path"])
        self.docs.append(progress)
        if self.then is not None:
            self.then(slot)
        return False


@pytest.mark.parametrize("prefill", [0, 5], ids=["fresh", "five_deep"])
@pytest.mark.parametrize("calibration", [None, CALIBRATION],
                         ids=["default_band", "calibrated"])
def test_the_document_is_the_recomputation_after_every_slot(
        tmp_path, fresh_obs, prefill, calibration):
    storage = init_storage(tmp_path, prefill)
    if calibration is not None:
        with open(os.path.join(storage, "calibration.json"), "w") as f:
            json.dump(calibration, f)
    write_plan(storage, PLAN)
    check = Checked(storage)
    campaign = Campaign(spec_for(tmp_path, storage, SLOTS, on_slot=check))
    assert campaign.run() == 0
    # every kind of attempt happened, in the classes the plan means
    state = load_checkpoint(storage)
    assert [[a["class"] for a in s["attempts"]] for s in state["slots"]] \
        == [["experiment"], ["experiment"], ["experiment"],
            ["timeout", "experiment"], ["infra", "experiment"],
            ["experiment"], ["experiment"]]
    st = load_storage(storage)
    killed = prefill + 3
    assert st.quarantined_runs() == [killed]
    assert st.nr_stored_histories() == prefill + 8  # the infra exit: none
    st.close()
    last = check.docs[-1]
    assert (last["runs"], last["runs_quarantined"]) == (prefill + 7, 1)
    assert last["total_virtual_time_s"] is not None
    assert last["band_source"] == ("calibration" if calibration
                                   else "default")
    # the whole history was read for the first document and for the one
    # the campaign leaves behind, and for no other
    assert check.paths == ["walk"] + ["fold"] * (SLOTS - 1)
    assert fold_counts() == {"walk": 2, "fold": SLOTS - 1}
    assert [s["progress_path"] for s in state["slots"]] == check.paths
    # what `_finish` left in campaign.json rests on no fold, and says so
    # of itself: the campaign's own end is in it
    assert state["progress"]["campaign"]["stopped_reason"] == "done"
    assert dump(state["progress"]) == recomputed(storage)


def test_a_resumed_campaign_walks_once_and_folds_again(tmp_path, fresh_obs):
    storage = init_storage(tmp_path, 3)
    write_plan(storage, PLAN)
    first = Checked(storage)
    assert Campaign(spec_for(tmp_path, storage, 3, on_slot=first)).run() == 0
    assert first.paths == ["walk", "fold", "fold"]
    assert dump(load_checkpoint(storage)["progress"]) == recomputed(storage)
    # the half-done storage, a new supervisor: the rest of the plan
    second = Checked(storage)
    resumed = Campaign(spec_for(tmp_path, storage, SLOTS, on_slot=second))
    assert resumed.run(resume=True) == 0
    assert second.paths == ["walk", "fold", "fold", "fold"]
    assert second.docs[0]["campaign"]["completed_slots"] == 4
    assert fold_counts() == {"walk": 4, "fold": 5}
    assert dump(load_checkpoint(storage)["progress"]) == recomputed(storage)


def shrink(storage, keep):
    """An operator's out-of-band edit: the newest runs deleted, and
    ``storage.json`` put back to match."""
    st = load_storage(storage)
    for i in range(keep, st.refresh()):
        shutil.rmtree(st.run_dir(i))
    st.close()
    with open(os.path.join(storage, "storage.json"), "w") as f:
        json.dump({"type": "naive", "next_run": keep}, f)


def test_a_storage_that_shrank_under_the_watermark_is_walked_again(
        tmp_path, fresh_obs):
    storage = init_storage(tmp_path, 6)
    write_plan(storage, [{"kind": "ok", "t": 0.5}, {"kind": "fail", "t": 0.25},
                         {"kind": "ok", "t": 0.125},
                         {"kind": "fail", "t": 2.0},
                         {"kind": "ok", "t": 1.0}])

    def then(slot):
        if slot["slot"] == 1:  # 8 runs folded: leave 4
            shrink(storage, 4)

    check = Checked(storage, then)
    assert Campaign(spec_for(tmp_path, storage, 5, on_slot=check)).run() == 0
    # slot 2's run is the fifth of a storage that had held eight
    assert check.paths == ["walk", "fold", "walk", "fold", "fold"]
    assert [d["runs"] for d in check.docs] == [7, 8, 5, 6, 7]
    assert fold_counts() == {"walk": 3, "fold": 3}
    assert dump(load_checkpoint(storage)["progress"]) == recomputed(storage)


def test_the_fold_is_the_walk_over_any_split_of_the_history(tmp_path):
    """The arithmetic alone, no campaign: rows folded in two, three or
    N pieces give the document of one walk, whatever the floats."""
    storage = init_storage(tmp_path, 0)
    st = load_storage(storage)
    times = [0.1, 0.2, 0.30000000000000004, 1e16, 1.0, -1e16, 1e-9, 3.3]
    for i, t in enumerate(times):
        st.create_new_working_dir()
        st.record_new_trace(SingleTrace())
        if i == 4:
            st.quarantine_current_run("aborted")
            continue
        meta = {"virtual_time_s": t * 3} if i % 3 == 0 else {}
        st.record_result(i % 2 == 0, t, metadata=meta)
    whole = dump(analytics.progress_stats(st))
    for cuts in ([3], [1, 2, 5], list(range(1, len(times)))):
        fold = analytics.ProgressFold()
        paths = []
        for n in cuts + [len(times)]:
            fold.fold(st, n)
            paths.append(fold.take_path())
        assert paths == ["walk"] + ["fold"] * len(cuts)
        assert fold.take_path() == "fold"
        assert dump(fold.document()) == whole
        assert not fold.contradicted_by(len(times))
        assert fold.contradicted_by(len(times) - 1)
    st.close()


# -- the depth-free property -------------------------------------------------

RUN_DIR = re.compile(r"^[0-9a-f]{8}$")


class FileOps:
    """Every ``os.stat`` / ``open`` / ``os.listdir`` / ``os.scandir``
    this process makes under the storage dir while a window is open: a
    window opens at a reap (the supervisor's first statement after it)
    and closes at the next go."""

    def __init__(self, storage, monkeypatch):
        self.storage = os.path.abspath(storage) + os.sep
        self.windows, self.open_window = [], None
        self.crumbs = []  # every `phase.pgid` looked for, window or not
        for mod, name in ((os, "stat"), (os, "lstat"), (os, "listdir"),
                          (os, "scandir"), (os, "open"), (os, "unlink"),
                          (builtins, "open")):
            monkeypatch.setattr(mod, name, self.counting(
                f"{mod.__name__}.{name}", getattr(mod, name)))

    def counting(self, name, real):
        def call(*args, **kwargs):
            self.saw(name, args[0] if args else kwargs.get("path"))
            return real(*args, **kwargs)
        return call

    def saw(self, name, path):
        if not isinstance(path, (str, bytes, os.PathLike)):
            return
        path = os.path.abspath(os.fsdecode(path))
        if not path.startswith(self.storage):
            return
        if path.endswith("phase.pgid") and name == "builtins.open":
            self.crumbs.append(path)
        if self.open_window is not None:
            self.open_window["ops"].append((name, path))

    def run_indices(self, window):
        out = []
        for _name, path in window["ops"]:
            first = path[len(self.storage):].split(os.sep)[0]
            if RUN_DIR.match(first):
                out.append(int(first, 16))
        return out


class CountingStorage:
    """The campaign's handle with every query that names a run kept."""

    def __init__(self, real, calls):
        self._real, self._calls = real, calls

    def __getattr__(self, name):
        attr = getattr(self._real, name)
        if not callable(attr):
            return attr

        def call(*args, **kwargs):
            if args and isinstance(args[0], int):
                self._calls.append((name, args[0]))
            return attr(*args, **kwargs)
        return call


def watched_campaign(spec, ops):
    """A campaign whose stretch from each reap to the next go is a
    window of ``ops``, with the run count the storage held at the
    window's start (the watermark) and the handle's own calls."""

    class Watched(Campaign):
        attempted = False

        def _open_storage(self):
            if self._storage is None:
                real = super()._open_storage()
                self._storage = CountingStorage(real, self.handle_calls)
            return self._storage

        def _catch_up(self):
            if self.attempted and ops.open_window is None:
                ops.open_window = {"ops": [], "watermark": self._allocated,
                                   "handle": []}
                self.handle_calls = ops.open_window["handle"]
            return super()._catch_up()

        def _take_standby(self, *args):
            if ops.open_window is not None:
                ops.windows.append(ops.open_window)
                ops.open_window = None
                self.handle_calls = []
            self.attempted = True
            return super()._take_standby(*args)

    campaign = Watched(spec)
    campaign.handle_calls = []
    return campaign


def between_runs(tmp_path, monkeypatch, depth):
    (tmp_path / f"d{depth}").mkdir()
    storage = init_storage(tmp_path / f"d{depth}", depth)
    runs = 5
    write_plan(storage, [{"kind": "ok", "t": 0.5},
                         {"kind": "fail", "t": 0.25}] * runs)
    ops = FileOps(storage, monkeypatch)
    campaign = watched_campaign(spec_for(tmp_path, storage, runs), ops)
    assert campaign.run() == 0
    state = load_checkpoint(storage)
    assert [s["progress_path"] for s in state["slots"]] \
        == ["walk"] + ["fold"] * (runs - 1)
    # the campaign's one walk came before its first run was wanted; the
    # window after the last slot never closes (no next go)
    assert len(ops.windows) == runs - 1
    return storage, ops, ops.windows


def test_between_a_reap_and_the_next_go_nothing_depends_on_the_depth(
        tmp_path, fresh_obs, monkeypatch):
    counts = {}
    for depth in (16, 256):
        with monkeypatch.context() as patch:
            storage, ops, windows = between_runs(tmp_path, patch, depth)
        assert len(windows) == 4
        for k, window in enumerate(windows):
            assert window["watermark"] == depth + k
            # one run made by the attempt, and nothing older named: not
            # by a path, not by a query on the handle
            named = ops.run_indices(window) + [i for _, i in window["handle"]]
            assert named and set(named) == {window["watermark"]}
            assert not any(name.endswith(("listdir", "scandir"))
                           for name, _ in window["ops"])
        counts[depth] = [
            (len(w["ops"]), len(w["handle"]),
             sorted(name for name, _ in w["ops"])) for w in windows]
    assert counts[16] == counts[256]
    assert counts[16][0][0] > 0


def test_the_sweep_looks_where_the_attempt_wrote(tmp_path, fresh_obs,
                                                 monkeypatch):
    depth = 12
    storage = init_storage(tmp_path, depth)
    # a breadcrumb from before this campaign, in an OLD dir: a
    # supervisor that was killed with its run left a group behind
    orphan = subprocess.Popen(["sleep", "600"], start_new_session=True)
    old_crumb = os.path.join(storage, f"{3:08x}", "phase.pgid")
    with open(old_crumb, "w") as f:
        f.write(str(os.getpgid(orphan.pid)))
    plan = [{"kind": "ok", "t": 0.5}, {"kind": "crumb", "t": 0.0},
            {"kind": "ok", "t": 0.5}, {"kind": "ok", "t": 0.5}]
    write_plan(storage, plan)
    try:
        with monkeypatch.context() as patch:
            ops = FileOps(storage, patch)
            campaign = Campaign(spec_for(tmp_path, storage, 3))
            assert campaign.run() == 0
        # the old one: swept, once, where the campaign started
        assert orphan.wait(timeout=10) == -signal.SIGKILL
        assert not os.path.exists(old_crumb)
        assert ops.crumbs.count(old_crumb) == 1
        # after an attempt: the dirs it created and no other — every
        # entry of the storage once at the start, then one a new dir
        entries = len(os.listdir(storage))
        new_dirs = [os.path.join(storage, f"{i:08x}", "phase.pgid")
                    for i in range(depth, depth + len(plan))]
        start, after = ops.crumbs[:-len(plan)], ops.crumbs[-len(plan):]
        assert after == new_dirs
        assert old_crumb in start and not set(start) & set(new_dirs)
        assert len(start) <= entries
        # the killed attempt's own breadcrumb was found there and its
        # group ended; the slot retried and the campaign went on
        with open(os.path.join(storage, "orphans")) as f:
            (pid,) = [int(line) for line in f]
        assert not os.path.exists(new_dirs[1])
        assert wait_until(lambda: not session_alive(pid))
        state = load_checkpoint(storage)
        assert [[a["class"] for a in s["attempts"]]
                for s in state["slots"]] == [
            ["experiment"], ["infra", "experiment"], ["experiment"]]
    finally:
        if orphan.poll() is None:
            orphan.kill()
            orphan.wait()
