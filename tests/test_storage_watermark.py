"""A stored history is opened from a watermark, not from run 0
(storage/naive.py, "Settled"): ``init()`` and ``refresh()`` look for a
crashed run only among the runs not yet seen with a result or a
quarantine marker, the watermark travels in ``storage.json`` beside
``next_run`` and is written by the calls that allocate a run and by no
reader, and a storage that carries none is walked whole once. What an
open visits is read off the ``stat``s it makes, as
tests/test_ingest_run_cache.py reads a signature's; no duration is
asserted anywhere."""

import json
import os
import re
import shutil
import sys

import pytest

from namazu_tpu.storage import StorageError, load_storage, new_storage
from namazu_tpu.storage.naive import INCOMPLETE_MARKER
from namazu_tpu.utils.trace import SingleTrace

from tests.test_campaign_progress_fold import shrink

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)

import history  # noqa: E402

RUN_FILE = re.compile(r"/([0-9a-f]{8})/[^/]+$")


class Stats:
    """Every ``os.stat`` this process makes under the storage dir
    (``os.path.exists`` is one), and the run dirs they looked into."""

    def __init__(self, storage_dir, monkeypatch):
        self.root = os.path.abspath(storage_dir) + os.sep
        self.paths = []
        real = os.stat

        def stat(path, *args, **kwargs):
            if isinstance(path, str) and path.startswith(self.root):
                self.paths.append(path)
            return real(path, *args, **kwargs)

        monkeypatch.setattr(os, "stat", stat)

    def visited(self):
        """The runs looked into since the last call, in order."""
        runs = sorted({int(m.group(1), 16) for m in
                       map(RUN_FILE.search, self.paths) if m})
        del self.paths[:]
        return runs


def meta_of(storage_dir):
    with open(os.path.join(storage_dir, "storage.json")) as f:
        return json.load(f)


def write_meta(storage_dir, **doc):
    with open(os.path.join(storage_dir, "storage.json"), "w") as f:
        json.dump(dict(doc, type="naive"), f)


def store_run(st, ok=True):
    st.create_new_working_dir()
    st.record_new_trace(SingleTrace())
    st.record_result(ok, 0.5)


def make_storage(path, depth):
    """``depth`` complete runs, stored as ``nmz-tpu run`` stores them:
    every run by a handle of its own."""
    new_storage("naive", str(path)).create()
    for i in range(depth):
        store_run(load_storage(str(path)), ok=i % 4 != 1)
    return str(path)


def in_flight(storage_dir):
    """A run allocated and nothing recorded yet; returns (its writer,
    its index)."""
    writer = load_storage(storage_dir)
    run_dir = writer.create_new_working_dir()
    return writer, int(os.path.basename(run_dir), 16)


def crash_after_trace(writer):
    """The writer dies between its trace and its result."""
    writer.record_new_trace(SingleTrace())


# -- (a) an open visits the runs past the watermark --------------------------


@pytest.mark.parametrize("depth", [1, 7, 40])
def test_an_open_visits_only_the_runs_past_the_persisted_watermark(
        tmp_path, monkeypatch, depth):
    d = make_storage(tmp_path / "st", depth)
    # the last writer saw every run before its own settled
    assert meta_of(d) == {"type": "naive", "next_run": depth,
                          "settled": depth - 1}
    stats = Stats(d, monkeypatch)
    st = load_storage(d)
    assert stats.visited() == [depth - 1]
    assert st.last_open == (depth, 1)
    # what the handle learnt travels with its next allocation
    store_run(st)
    assert meta_of(d)["settled"] == depth
    stats.visited()
    load_storage(d)
    assert stats.visited() == [depth]
    # a kept handle goes on from its own watermark: the run it stored
    # itself it has not yet seen settled
    assert st.refresh() == depth + 1
    assert stats.visited() == [depth] and st.last_open == (depth + 1, 1)
    assert st.refresh() == depth + 1
    assert stats.visited() == [] and st.last_open == (depth + 1, 0)
    store_run(load_storage(d))
    stats.visited()
    assert st.refresh() == depth + 2
    assert stats.visited() == [depth + 1]
    assert st.last_open == (depth + 2, 1)


def synthesised(path, depth):
    """A history as benchmarks/history.py writes one: the files by hand,
    ``storage.json`` rewritten with the keys it had."""
    templates = history.load_templates(os.path.join(
        BENCH, "configs", "zk2212-fle3.history.json"))
    new_storage("naive", str(path)).create()
    history.fill_storage(str(path), templates, depth, 2, seed=2**31 + 11)
    return str(path)


def without_the_field(path, depth):
    d = make_storage(path, depth)
    write_meta(d, next_run=depth)
    return d


def shrunk_by_hand(path, depth):
    d = make_storage(path, depth + 3)
    shrink(d, depth)
    return d


@pytest.mark.parametrize("make", [without_the_field, synthesised,
                                  shrunk_by_hand])
def test_a_storage_without_a_watermark_is_walked_whole_once(
        tmp_path, monkeypatch, make):
    depth = 9
    d = make(tmp_path / "st", depth)
    assert meta_of(d).get("settled", 0) == 0
    stats = Stats(d, monkeypatch)
    # readers: each walks the whole history, and none leaves a mark
    for _ in range(2):
        st = load_storage(d)
        assert stats.visited() == list(range(depth))
        assert st.last_open == (depth, depth)
    assert meta_of(d).get("settled", 0) == 0
    # the next writer walks it too, and persists what it found
    store_run(load_storage(d))
    assert stats.visited() == list(range(depth + 1))
    assert meta_of(d) == {"type": "naive", "next_run": depth + 1,
                          "settled": depth}
    load_storage(d)
    assert stats.visited() == [depth]


@pytest.mark.parametrize("settled", [8, 99, -1])
def test_a_watermark_past_the_allocated_runs_is_not_trusted(
        tmp_path, monkeypatch, settled):
    d = make_storage(tmp_path / "st", 7)
    write_meta(d, next_run=5, settled=settled)
    stats = Stats(d, monkeypatch)
    st = load_storage(d)
    assert stats.visited() == list(range(5))
    assert st.last_open == (5, 5)
    for i in (5, 6):  # the dirs the edit left behind
        shutil.rmtree(st.run_dir(i))
    store_run(st)
    assert meta_of(d) == {"type": "naive", "next_run": 6, "settled": 5}


# -- (b) a run that is not settled holds the watermark -----------------------


def reopened(st):
    return load_storage(st.dir)


def refreshed(st):
    st.refresh()
    return st


@pytest.mark.parametrize("again", [reopened, refreshed])
def test_a_run_in_flight_at_one_open_and_crashed_by_the_next_is_quarantined(
        tmp_path, monkeypatch, again):
    """The hole of a ``refresh()`` that started at the old ``next_run``:
    the handle had counted the run in, and never looked at it again."""
    d = make_storage(tmp_path / "st", 4)
    writer, crashed = in_flight(d)
    stats = Stats(d, monkeypatch)
    st = load_storage(d)
    assert stats.visited() == [4]
    assert not st.is_quarantined(crashed)
    # two more runs come and go while it is in flight
    store_run(load_storage(d))
    store_run(load_storage(d))
    stats.visited()
    st = again(st)
    assert stats.visited() == [4, 5, 6]
    assert not st.is_quarantined(crashed)
    crash_after_trace(writer)
    stats.visited()
    st = again(st)
    assert st.is_quarantined(crashed)
    assert st.quarantined_runs() == [crashed]
    with pytest.raises(StorageError, match="quarantined"):
        st.get_stored_history(crashed)
    # marked is settled: an open after the next writer starts past it
    store_run(load_storage(d))
    stats.visited()
    st = again(st)
    assert stats.visited() == [7]
    assert st.last_open == (8, 1)


def settle_by_result(writer):
    writer.record_new_trace(SingleTrace())
    writer.record_result(True, 0.25)


def settle_by_marker(writer):
    writer.quarantine_current_run("deadline")


@pytest.mark.parametrize("settle", [settle_by_result, settle_by_marker])
def test_an_unsettled_run_pins_the_watermark_until_it_settles(
        tmp_path, monkeypatch, settle):
    d = make_storage(tmp_path / "st", 3)
    writer, pinned = in_flight(d)
    for _ in range(3):
        store_run(load_storage(d))
    # every writer since persisted the same watermark: the pinned run
    assert meta_of(d) == {"type": "naive", "next_run": 7, "settled": pinned}
    stats = Stats(d, monkeypatch)
    kept = load_storage(d)
    for _ in range(2):
        assert stats.visited() == [3, 4, 5, 6]
        assert kept.last_open == (7, 4)
        kept.refresh()
    stats.visited()
    settle(writer)
    kept.refresh()
    assert stats.visited() == [3, 4, 5, 6]
    kept.refresh()
    assert stats.visited() == [] and kept.last_open == (7, 0)
    load_storage(d)
    assert stats.visited() == [3, 4, 5, 6]  # the file still says 3 ...
    store_run(load_storage(d))
    assert meta_of(d)["settled"] == 7       # ... until the next writer
    stats.visited()
    load_storage(d)
    assert stats.visited() == [7]


# -- (c) who writes storage.json ---------------------------------------------


def test_a_reader_never_rewrites_storage_json(tmp_path, monkeypatch):
    """A reader that wrote the file back would put a ``next_run`` it
    read a moment ago over the one a run child has just allocated, and
    the next child would be handed a dir that exists."""
    d = make_storage(tmp_path / "st", 5)
    write_meta(d, next_run=5)  # an old storage: the reader learns a lot
    meta_path = os.path.join(d, "storage.json")
    written = []
    real = os.replace
    monkeypatch.setattr(os, "replace", lambda src, dst, **kw: (
        written.append(dst), real(src, dst, **kw))[1])
    reader = load_storage(d)
    allocated = 5
    for step in range(6):
        writer = load_storage(d)
        before = os.stat(meta_path).st_ino
        # the reader moves between the writer's read and its write ...
        (reader.refresh if step % 2 else reader.init)()
        assert os.stat(meta_path).st_ino == before
        assert meta_path not in written
        writer.create_new_working_dir()
        allocated += 1
        assert written.count(meta_path) == 1
        # ... and between its allocation and its records
        load_storage(d)
        assert reader.refresh() == allocated
        assert written.count(meta_path) == 1
        del written[:]
        writer.record_new_trace(SingleTrace())
        writer.record_result(True, 0.1)
        assert meta_of(d)["next_run"] == allocated
    assert allocated == 11
    assert sorted(n for n in os.listdir(d) if len(n) == 8) == [
        f"{i:08x}" for i in range(11)]
    assert load_storage(d).fsck()["complete"] == 11


def test_a_handle_of_a_storage_that_went_backwards_refuses_to_refresh(
        tmp_path):
    d = make_storage(tmp_path / "st", 6)
    kept = load_storage(d)
    shrink(d, 4)
    with pytest.raises(StorageError, match="back to 4"):
        kept.refresh()
    assert load_storage(d).last_open == (4, 4)
    shutil.rmtree(d)
    with pytest.raises(OSError):
        kept.refresh()


# -- (d) fsck visits every run, whatever the watermark says ------------------


def mixed_storage(path):
    """Complete runs, an aborted one, a crashed one, one in flight, and
    a complete one below the watermark that then LOST its result."""
    d = make_storage(path, 4)
    aborted, _ = in_flight(d)
    aborted.quarantine_current_run("deadline")
    crashed, _ = in_flight(d)
    crash_after_trace(crashed)
    in_flight(d)
    store_run(load_storage(d))
    os.unlink(os.path.join(d, f"{1:08x}", "result.json"))
    with open(os.path.join(d, f"{2:08x}", "result.json.tmp"), "w"):
        pass
    return d


@pytest.mark.parametrize("forget_the_watermark", [False, True])
def test_fsck_reports_what_it_reported_before(tmp_path, forget_the_watermark):
    d = mixed_storage(tmp_path / "st")
    assert meta_of(d)["settled"] == 6  # past the run that lost its result
    if forget_the_watermark:
        write_meta(d, next_run=8)
    st = load_storage(d)
    # the one narrowing: settled once, run 1 is the fsck's to find
    assert st.quarantined_runs() == ([1, 4, 5] if forget_the_watermark
                                     else [4, 5])
    tmp = os.path.join(st.run_dir(2), "result.json.tmp")
    lost = [] if forget_the_watermark else [1]
    assert st.fsck() == {
        "dir": st.dir, "next_run": 8,
        "complete": 4, "quarantined": st.quarantined_runs(),
        "incomplete_unmarked": lost + [6], "missing_dirs": [],
        "tmp_artifacts": [tmp], "repaired": False, "repaired_runs": []}
    repaired = st.fsck(repair=True)
    assert repaired["repaired_runs"] == lost + [6]
    assert repaired["quarantined"] == [1, 4, 5, 6]
    assert not os.path.exists(tmp)
    again = load_storage(d).fsck()
    assert again["quarantined"] == [1, 4, 5, 6]
    assert again["incomplete_unmarked"] == [] and again["complete"] == 4
    # and the marker settles the run that held the watermark
    store_run(load_storage(d))
    assert meta_of(d)["settled"] == 8
    assert os.path.exists(os.path.join(d, f"{6:08x}", INCOMPLETE_MARKER))
