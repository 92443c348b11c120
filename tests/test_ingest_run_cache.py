"""The encoded-run records of history ingest (models/ingest.py
``RunRecordCache``, ``HistoryStorage.run_signature``): a request reads and
encodes only the stored runs that are new to the process, and what
reaches the search — rings, labels, pairs, seeds, references — is what
re-reading the whole history gave. The whole-history form is kept HERE,
as a storage that never gives a signature (the path before the cache)."""

import json
import logging
import os
import sys
import threading

import numpy as np
import pytest

from namazu_tpu import obs
from namazu_tpu.models import ingest
from namazu_tpu.models.ingest import (
    IngestParams,
    RunRecordCache,
    ingest_history,
)
from namazu_tpu.models.search import ScheduleSearch
from namazu_tpu.obs import export, spans
from namazu_tpu.signal.base import HINT_SPACE
from namazu_tpu.storage import load_storage, new_storage
from namazu_tpu.storage.naive import INCOMPLETE_MARKER, NaiveStorage
from namazu_tpu.utils.atomic import (
    atomic_write_json,
    atomic_write_text,
)

from tests.test_ingest_embed_batch import H, cfg, make_run
from tests.test_request_spans import isolated_obs

PARAMS = IngestParams(H=H, max_interval=0.05)


class UnsignedStorage(NaiveStorage):
    """The same files behind a backend that cannot say whether a run
    changed: every request re-reads every run, as before the cache."""

    def run_signature(self, i):
        return None


def unsigned(st):
    plain = UnsignedStorage(st.dir)
    plain.init()
    return plain


def record(st, trace, ok, stamp=HINT_SPACE):
    st.create_new_working_dir()
    st.record_new_trace(trace)
    st.record_result(ok, 0.5, metadata={"hint_space": stamp})


def make_storage(path, depth, first_seed=0):
    """``depth`` recorded runs, every fourth one a failure."""
    st = new_storage("naive", str(path))
    st.create()
    for i in range(depth):
        record(st, make_run(first_seed + i), i % 4 != 1)
    return st


def rewrite_result(st, i, ok):
    atomic_write_json(os.path.join(st.run_dir(i), "result.json"), {
        "successful": ok, "required_time": 0.5,
        "metadata": {"hint_space": HINT_SPACE}})


def rewrite_trace(st, i, trace):
    atomic_write_text(os.path.join(st.run_dir(i), "trace.json"),
                      trace.to_json())


def ingested(storage, params=PARAMS, search=None):
    """One ingest into ``search`` (a fresh one unless given): everything
    the search and the caller hold of the history afterwards."""
    s = search if search is not None else ScheduleSearch(cfg(), n_devices=1)
    refs = ingest_history(s, storage, params)
    return {
        "archive": s.archive.copy(), "labels": s.archive_labels.copy(),
        "failures": s.failures.copy(), "pairs": np.array(s.pairs),
        "counts": (s._archive_n, s._failure_n),
        "digests": list(s._failure_digests),
        "references": [(r.hint_ids.copy(), r.entity_ids.copy(),
                        r.arrival.copy(), r.mask.copy(),
                        r.faultable.copy()) for r in refs],
    }


def assert_same(a, b):
    assert a.keys() == b.keys()
    for name in ("archive", "labels", "failures", "pairs"):
        assert np.array_equal(a[name], b[name]), name
    assert a["counts"] == b["counts"]
    assert a["digests"] == b["digests"]
    assert len(a["references"]) == len(b["references"])
    for ra, rb in zip(a["references"], b["references"]):
        for xa, xb in zip(ra, rb):
            assert xa.dtype == xb.dtype and np.array_equal(xa, xb)


@pytest.fixture(autouse=True)
def records(monkeypatch):
    """Every test starts with no record kept (the cache is the
    process's, and so is the test worker)."""
    cache = RunRecordCache(ingest.RUN_CACHE_BYTES)
    monkeypatch.setattr(ingest, "_RUN_RECORDS", cache)
    return cache


@pytest.fixture
def parsed(monkeypatch):
    """The runs whose ``trace.json`` a storage parsed, in order."""
    seen = []
    real = NaiveStorage.get_stored_history

    def counting(self, i):
        seen.append(i)
        return real(self, i)

    monkeypatch.setattr(NaiveStorage, "get_stored_history", counting)
    return seen


@pytest.fixture
def fresh_obs():
    with isolated_obs() as ring:
        yield ring


# -- (a) an unchanged history is not read again ------------------------------


def test_a_second_ingest_of_an_unchanged_storage_parses_nothing(
        tmp_path, parsed):
    st = make_storage(tmp_path / "st", 9)
    ingested(st)
    assert parsed == list(range(9))
    del parsed[:]
    # the sidecar loads its storage anew for every request
    ingested(load_storage(st.dir))
    assert parsed == []


@pytest.mark.parametrize("against", ["cold", "unsigned"])
def test_a_warm_ingest_leaves_what_a_cold_one_leaves(tmp_path, monkeypatch,
                                                     parsed, against):
    """Archive, failure ring, labels, counts, digests, pairs and the
    references returned: bit for bit those of an ingest that read
    everything — with the cache empty, and down the path of a backend
    without signatures."""
    st = make_storage(tmp_path / "st", 12)
    ingested(st)
    del parsed[:]
    warm = ingested(st)
    assert parsed == []
    if against == "cold":
        monkeypatch.setattr(ingest, "_RUN_RECORDS",
                            RunRecordCache(ingest.RUN_CACHE_BYTES))
        other = ingested(st)
    else:
        other = ingested(unsigned(st))
    assert parsed == list(range(12))
    assert_same(warm, other)
    assert warm["counts"] == (12, 3) and len(warm["references"]) == 4


def test_a_persistent_search_fills_its_rings_alike(tmp_path):
    """Two requests into ONE search (the sidecar's resident search):
    the second, all hits, writes the rows the first one wrote."""
    st = make_storage(tmp_path / "st", 9)
    cached = ScheduleSearch(cfg(), n_devices=1)
    plain = ScheduleSearch(cfg(), n_devices=1)
    for _ in range(2):
        a = ingested(st, search=cached)
        b = ingested(unsigned(st), search=plain)
    assert_same(a, b)
    assert a["counts"] == (18, 2)


def test_appending_one_run_parses_exactly_that_one(tmp_path, parsed):
    st = make_storage(tmp_path / "st", 8)
    ingested(st)
    del parsed[:]
    record(st, make_run(50), False)
    warm = ingested(st)
    assert parsed == [8]
    assert_same(warm, ingested(unsigned(st)))


# -- (b) what changes a run's signature --------------------------------------


@pytest.mark.parametrize("what", ["result", "trace"])
def test_a_rewritten_run_is_read_again_and_only_that_one(tmp_path, parsed,
                                                         what):
    st = make_storage(tmp_path / "st", 8)
    before = ingested(st)
    del parsed[:]
    if what == "result":
        rewrite_result(st, 2, False)  # success -> failure
    else:
        rewrite_trace(st, 2, make_run(77, n_events=21))
    after = ingested(st)
    assert parsed == [2]
    assert_same(after, ingested(unsigned(st)))
    assert not np.array_equal(before["archive"], after["archive"]) \
        or not np.array_equal(before["labels"], after["labels"])
    if what == "result":
        assert after["counts"] == (8, before["counts"][1] + 1)


def test_a_run_quarantined_between_requests_disappears(tmp_path, parsed):
    st = make_storage(tmp_path / "st", 6)
    assert ingested(st)["counts"] == (6, 2)
    atomic_write_text(os.path.join(st.run_dir(1), INCOMPLETE_MARKER), "x\n")
    del parsed[:]
    after = ingested(st)
    # the quarantined run goes down the ordinary queries, which refuse it
    assert parsed == [1]
    assert after["counts"] == (5, 1)
    assert_same(after, ingested(unsigned(st)))
    os.unlink(os.path.join(st.run_dir(1), INCOMPLETE_MARKER))
    assert ingested(st)["counts"] == (6, 2)


def test_a_signature_is_three_stats_and_tells_every_rewrite(tmp_path,
                                                            monkeypatch):
    st = make_storage(tmp_path / "st", 2)
    sig = st.run_signature(0)
    assert sig == st.run_signature(0) == load_storage(st.dir).run_signature(0)
    assert sig != st.run_signature(1)
    hash(sig)
    rewrite_result(st, 0, True)  # the same bytes: a new inode all the same
    assert st.run_signature(0) != sig
    sig = st.run_signature(0)
    rewrite_trace(st, 0, make_run(0))
    assert st.run_signature(0) != sig
    os.unlink(os.path.join(st.run_dir(1), "trace.json"))
    assert st.run_signature(1) is None
    assert st.run_signature(7) is None  # no such run
    atomic_write_text(os.path.join(st.run_dir(0), INCOMPLETE_MARKER), "x\n")
    assert st.run_signature(0) is None
    opened = []
    monkeypatch.setattr("builtins.open", lambda *a, **k: opened.append(a))
    st.run_signature(0)
    assert opened == []


def test_a_run_s_result_is_parsed_once_for_verdict_and_metadata(
        tmp_path, monkeypatch):
    st = make_storage(tmp_path / "st", 2)
    loads = []
    real = json.load
    monkeypatch.setattr(json, "load",
                        lambda f: loads.append(f.name) or real(f))
    assert st.is_successful(0) is True
    assert st.get_metadata(0) == {"hint_space": HINT_SPACE}
    assert st.get_required_time(0) == 0.5
    assert len(loads) == 1
    assert st.is_successful(1) is False
    assert len(loads) == 2
    rewrite_result(st, 1, True)
    assert st.is_successful(1) is True
    assert len(loads) == 3


# -- (c) the hint-space guard, the parameters, the other backends ------------


def test_a_run_of_another_hint_space_is_skipped_and_warned_on_a_hit(
        tmp_path, parsed, caplog):
    st = make_storage(tmp_path / "st", 4)
    record(st, make_run(40), False, stamp="content-v0")
    record(st, make_run(41), True)
    for request in (1, 2):
        del parsed[:]
        caplog.clear()
        with caplog.at_level(logging.WARNING, logger="nmz.models.ingest"):
            got = ingested(st)
        assert parsed == ([] if request == 2 else list(range(6)))
        assert got["counts"] == (5, 1)
        warned = [r.getMessage() for r in caplog.records
                  if "another hint space" in r.getMessage()]
        assert len(warned) == 1 and warned[0].startswith("1 stored run(s)")
    assert_same(got, ingested(unsigned(st)))


@pytest.mark.parametrize("other", [
    PARAMS._replace(H=H // 2), PARAMS._replace(L=16),
    PARAMS._replace(max_interval=0.001)], ids=["H", "cap", "max_interval"])
def test_records_of_other_parameters_are_not_taken(tmp_path, parsed, other):
    st = make_storage(tmp_path / "st", 5)
    ingested(st)
    del parsed[:]
    s = ScheduleSearch(cfg(H=other.H, K=16 if other.H != H else cfg().K),
                   n_devices=1)
    got = ingested(st, other, search=s)
    assert parsed == list(range(5))
    plain = ScheduleSearch(s.cfg, n_devices=1)
    assert_same(got, ingested(unsigned(st), other, search=plain))
    # and the first parameters' records are still there
    del parsed[:]
    ingested(st)
    assert parsed == []


def test_a_cap_that_pads_and_one_that_cuts_keep_records_apart(
        tmp_path, parsed):
    """An explicit ``trace_length`` equal to ``order_mode_max_l`` pads
    every run to it; the reorder default holds the same cap and encodes
    a run at its own quantum. One run directory, one process: a record
    of each, and neither request is handed the other's length."""
    st = make_storage(tmp_path / "st", 3)
    cuts = PARAMS._replace(release_mode="reorder", order_mode_max_l=256,
                           reference_mode="recent")
    pads = cuts._replace(L=256)
    for params, want_L, want_parsed in (
            (cuts, 128, [0, 1, 2]), (pads, 256, [0, 1, 2]),
            (cuts, 128, []), (pads, 256, [])):
        del parsed[:]
        got = ingested(st, params)
        assert parsed == want_parsed
        assert {r[0].shape[0] for r in got["references"]} == {want_L}
        assert_same(got, ingested(unsigned(st), params))


def test_a_backend_without_signatures_keeps_nothing(tmp_path, records,
                                                    parsed, fresh_obs):
    st = make_storage(tmp_path / "st", 5)
    plain = unsigned(st)
    for _ in range(2):
        del parsed[:]
        ingested(plain)
        assert parsed == list(range(5))
    assert len(records) == 0
    assert obs.metrics.registry().value(spans.INGEST_CACHED_RUNS) == 0


# -- (d) the records themselves ----------------------------------------------


def test_the_arrays_of_a_kept_record_refuse_writes(tmp_path):
    st = make_storage(tmp_path / "st", 3)
    ingested(st)
    s = ScheduleSearch(cfg(), n_devices=1)
    refs = ingest_history(s, st, PARAMS)
    for ref in refs:
        for a in (ref.hint_ids, ref.entity_ids, ref.arrival, ref.mask,
                  ref.faultable):
            with pytest.raises(ValueError, match="read-only"):
                a[0] = 1
    (key, (_sig, rec, _size)), = [
        kv for kv in ingest._RUN_RECORDS._records.items()
        if kv[0][0] == st.run_dir(1)]
    assert not rec.ok and rec.n_events == 17
    with pytest.raises(ValueError, match="read-only"):
        rec.seed[0] = 1.0
    with pytest.raises(ValueError, match="read-only"):
        rec.enc_rt.arrival[0] = 1.0


def test_the_byte_bound_evicts_the_least_recently_used(tmp_path,
                                                       monkeypatch, parsed):
    st = make_storage(tmp_path / "st", 6)
    probe = RunRecordCache(1 << 30)
    monkeypatch.setattr(ingest, "_RUN_RECORDS", probe)
    ingested(st)
    sizes = [size for _sig, _rec, size in probe._records.values()]
    assert probe._bytes == sum(sizes) and len(sizes) == 6
    assert min(sizes) > RunRecordCache.RECORD_OVERHEAD
    # room for four of the six: an in-order walk of a history larger
    # than the bound evicts every record before it is asked for again
    small = RunRecordCache(sum(sizes[:4]))
    monkeypatch.setattr(ingest, "_RUN_RECORDS", small)
    for _ in range(2):
        del parsed[:]
        ingested(st)
        assert parsed == list(range(6))
        assert len(small) == 4 and small._bytes <= small.max_bytes
        assert [k[0] for k in small._records] == [
            st.run_dir(i) for i in (2, 3, 4, 5)]
    # a history inside the bound is kept whole, most recent last
    del parsed[:]
    short = make_storage(tmp_path / "short", 3)
    ingested(short)
    ingested(short)
    assert parsed == [0, 1, 2]
    assert [k[0] for k in small._records][-3:] == [
        short.run_dir(i) for i in range(3)]
    # a record larger than the whole bound is not kept at all
    tiny = RunRecordCache(16)
    monkeypatch.setattr(ingest, "_RUN_RECORDS", tiny)
    ingested(short)
    assert len(tiny) == 0 and tiny._bytes == 0


def test_four_threads_on_four_storages_agree_with_four_in_turn(tmp_path):
    """The sidecar's four framed workers: different keys at once, one
    process-wide cache — and two of them on the SAME storage."""
    stores = [make_storage(tmp_path / f"st{k}", 10 + k, first_seed=20 * k)
              for k in range(3)]
    stores.append(stores[0])
    want = [ingested(unsigned(st)) for st in stores]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for _round in range(3):  # cold, then warm twice
            got = [None] * 4
            errors = []

            def work(k):
                try:
                    got[k] = ingested(load_storage(stores[k].dir))
                except BaseException as e:  # surfaced below
                    errors.append(e)

            threads = [threading.Thread(target=work, args=(k,))
                       for k in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
            assert errors == []
            for g, w in zip(got, want):
                assert_same(g, w)
    finally:
        sys.setswitchinterval(old)
    assert len(ingest._RUN_RECORDS) == 10 + 11 + 12


# -- (e) the tracing ---------------------------------------------------------


def test_cached_runs_are_counted_and_named_on_the_encode_row(tmp_path,
                                                             fresh_obs):
    st = make_storage(tmp_path / "st", 7)
    s = ScheduleSearch(cfg(), n_devices=1)
    value = obs.metrics.registry().value
    ingest_history(s, st, PARAMS)
    assert value(spans.INGEST_CACHED_RUNS) == 0
    record(st, make_run(60), True)
    ingest_history(s, st, PARAMS)
    assert value(spans.INGEST_CACHED_RUNS) == 7
    assert value(spans.INGEST_RUNS) == 7 + 8
    # the events of every run ingested, parsed or not
    assert value(spans.INGEST_EVENTS) == 17 * (7 + 8)
    rows = fresh_obs.since(0)["rows"]
    encode = [r[7] for r in rows if r[1] == "ingest_encode"]
    assert encode == [{"pieces": 7, "events": 17 * 7, "cached": 0},
                      {"pieces": 8, "events": 17 * 8, "cached": 7}]
    # both stages are charged for every stored run, hit or miss
    read = [r for r in rows if r[1] == "ingest_read"]
    assert [r[7]["pieces"] for r in read] == [7 + 1, 8 + 1]
    assert all(r[5] > 0 for r in read)
    text = export.render_span_trees(rows)
    assert "cached=0" in text and "cached=7" in text


def test_with_observability_off_nothing_is_counted(tmp_path):
    st = make_storage(tmp_path / "st", 3)
    with isolated_obs():
        obs.metrics.configure(False)
        ingested(st)
        ingested(st)
        assert obs.metrics.registry().value(spans.INGEST_CACHED_RUNS) \
            is None
