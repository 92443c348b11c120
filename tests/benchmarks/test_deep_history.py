"""``zk2212-fle3-hunt5k``'s copy of the plain reference: a history deeper
than twice the rings, held slot for slot.

``benchmarks/reference.py`` already states what a ring does past its
capacity (slots modulo capacity, the last write of a slot kept; the
failure ring keyed by signature, a held signature passed over, a new
one evicting its slot's). Here the program is held to it where no cell
was: in process at small rings over histories that go round both of
them several times, and through the harness — the live path of the
rehearsal testee on a storage 1,030 runs deep, the shipped rings of
512 and 64 rows — with the two rules broken underneath, each of which
has to come out ``correct: false`` on its own number."""

import json
import os
import sys
import textwrap

import numpy as np
import pytest

import tiny_root

sys.path.insert(0, tiny_root.BENCH)
if tiny_root.REPO not in sys.path:
    sys.path.insert(0, tiny_root.REPO)

import reference  # noqa: E402

CELL = "zk2212-fle3-hunt5k.live-d1024"
ROWS_GAP_LIMIT = 1e-5  # PERF.md section 2; run.py's own
#: the deep rehearsal: more than twice the archive's 512 rows and the
#: failure ring's 64, with the cell's own 68 stored failures; the
#: rehearsal testee on a port of this file's own (test_cells_live.py
#: may be driving it on another worker)
DEPTH, FAILURES, PORT = 1030, 68, 10969


# -- in process, small rings --------------------------------------------------

H, K, ARCHIVE_ROWS, FAILURE_ROWS = 32, 16, 8, 4
SEARCH_PARAMS = {"H": H, "K": K, "seed": 3, "tau": 0.005,
                 "w_novelty": 1.0, "w_bug": 1.0, "w_delay_cost": 0.1,
                 "max_interval": 0.05, "release_mode": "delay"}
INGEST_PARAMS = {"H": H, "max_interval": 0.05,
                 "reference_mode": "envelope"}


def store_run(st, order, ok, scale=1.0):
    """One stored run of six events ``n0``..``n5`` arriving in
    ``order``, 10 ms apart: a failure's signature is its order."""
    from namazu_tpu.signal import PacketEvent
    from namazu_tpu.signal.base import HINT_SPACE
    from namazu_tpu.utils.trace import SingleTrace

    st.create_new_working_dir()
    trace, base = SingleTrace(), 1.7e9
    for k, i in enumerate(order):
        ev = PacketEvent.create(f"n{i}", f"n{i}", "peer", hint=f"n{i}")
        ev.mark_arrived(base + 0.010 * k * scale)
        action = ev.default_action()
        action.mark_triggered(base + 0.010 * k * scale + 0.001 * (i % 3))
        trace.append(action)
    st.record_new_trace(trace)
    st.record_result(ok, 0.5, metadata={"hint_space": HINT_SPACE})


def orders(n):
    """``n`` distinct orders of six events."""
    import itertools

    return list(itertools.islice(itertools.permutations(range(6)), n))


#: failures of each history, as indices into ``orders``: one signature
#: per index, so a repeated index is a repeated signature
HISTORIES = {
    # nine signatures for four rows: the ring goes round twice inside
    # every request, each signature evicted before the walk comes back
    "more_than_twice_the_failure_ring": list(range(9)),
    # one more than the ring holds: the smallest wrap
    "one_signature_past_the_failure_ring": list(range(5)),
    # three signatures twelve times over: every repeat is passed over
    "repeated_signatures_under_the_ring": [0, 1, 2] * 4,
    # a repeat that arrives after its signature was evicted is new again
    "a_repeat_after_its_eviction": [0, 1, 2, 3, 4, 0, 5, 1],
}


def resident_of(search, refs):
    _encs, trace, pairs, archive, failures = \
        search._device_inputs_fused(refs)
    return {"pairs": pairs, "archive": archive, "failures": failures,
            "labels": search.archive_labels, "hint_ids": trace.hint_ids,
            "arrival": trace.arrival, "mask": trace.mask,
            "archive_n": search._archive_n,
            "failure_n": search._failure_n}


@pytest.mark.parametrize("history", sorted(HISTORIES))
def test_rings_past_capacity_agree_slot_for_slot(tmp_path, history):
    from namazu_tpu.models.ingest import IngestParams, ingest_history
    from namazu_tpu.models.search import ScheduleSearch, SearchConfig
    from namazu_tpu.models.search import make_score_weights
    from namazu_tpu.storage import new_storage

    st = new_storage("naive", str(tmp_path / "st"))
    st.create()
    failed = [orders(9)[i] for i in HISTORIES[history]]
    # successes between the failures: twenty-odd runs for eight rows
    for k, order in enumerate(failed):
        store_run(st, range(6), True, scale=1 + 0.01 * k)
        store_run(st, order, False)
        store_run(st, range(6), True, scale=1 - 0.01 * k)
    while st.nr_stored_histories() <= 2 * ARCHIVE_ROWS:
        store_run(st, range(6), True, scale=1.1)
    search = ScheduleSearch(SearchConfig(
        H=H, K=K, population=32, migrate_k=2, seed=3, fused_chunk=2,
        archive_size=ARCHIVE_ROWS, failure_size=FAILURE_ROWS,
        surrogate_topk=0,
        weights=make_score_weights(
            release_mode="delay", w_novelty=1.0, w_bug=1.0,
            w_delay_cost=0.1, w_fault_cost=0.0, tau=0.005,
            reorder_gap=0.0, reorder_window=0.0)))
    state = reference.SearchState(SEARCH_PARAMS, INGEST_PARAMS,
                                  ARCHIVE_ROWS, FAILURE_ROWS)
    depth = st.nr_stored_histories()
    assert depth > 2 * ARCHIVE_ROWS
    # three requests: the same history twice (the second finds the
    # device mirrors staged and scatters into them), then two more runs
    for extra in (None, None, (failed[0], orders(9)[8])):
        for order in extra or ():
            store_run(st, order, False)
        depth = st.nr_stored_histories()
        refs = ingest_history(search, st, IngestParams(**INGEST_PARAMS))
        search.run(refs, generations=2)
        state.ingest(reference.read_runs(st.dir, depth, H))
        gaps = reference.resident_gap(state, resident_of(search, refs))
        exact = {k: v for k, v in gaps.items() if not k.endswith("_gap")}
        assert set(exact.values()) == {0}, (depth, gaps)
        assert gaps["archive_rows_gap"] <= ROWS_GAP_LIMIT, gaps
        assert gaps["failure_rows_gap"] <= ROWS_GAP_LIMIT, gaps
        assert gaps["reference_times_gap"] == 0, gaps
        # the host rings are what the mirrors mirror
        np.testing.assert_array_equal(
            np.asarray(search._dev_mirrors["archive"]), search.archive)
        np.testing.assert_array_equal(
            np.asarray(search._dev_mirrors["failures"]), search.failures)
        # fill counts: every stored run takes an archive slot at every
        # request; the failure ring's signatures are the reference's
        assert search._archive_n == state.archive_n > 2 * ARCHIVE_ROWS
        assert search.distinct_failure_signatures() == sum(
            1 for s in state._slot_sig if s)
    distinct = len(set(HISTORIES[history])) + 1  # + orders(9)[8]
    assert search.distinct_failure_signatures() == min(distinct,
                                                       FAILURE_ROWS)


# -- through the harness, the shipped rings -----------------------------------


@pytest.fixture(scope="module")
def deep_root(tmp_path_factory):
    root = tiny_root.build(tmp_path_factory.mktemp("bench_deep"))
    mini = os.path.join(root, "examples", "mini")
    for rel in ("config.toml", "config_search.toml",
                os.path.join("materials", "run.sh")):
        with open(os.path.join(mini, rel)) as f:
            text = f.read()
        with open(os.path.join(mini, rel), "w") as f:
            f.write(text.replace("10967", str(PORT)))

    def edit(path, change):
        with open(path) as f:
            doc = json.load(f)
        change(doc)
        with open(path, "w") as f:
            json.dump(doc, f)

    configs = os.path.join(root, "benchmarks", "configs")
    for name in os.listdir(configs):
        edit(os.path.join(configs, name),
             lambda c: c["testee"].update(ports=[PORT]))
    edit(os.path.join(root, "benchmarks", "traffic", "live-d1024.json"),
         lambda m: m.update(prefill_runs=DEPTH, prefill_failures=FAILURES))
    return root


def test_the_deep_cell_is_held_to_the_reference(deep_root):
    rc, result, out, err = tiny_root.run_cell(
        deep_root, CELL, 1, trace=1, seconds=4.0)
    assert rc == 0, err[-3000:]
    facts = tiny_root.tagged(out, "facts: ")
    checks = result["checks"]
    over = {k for k, c in checks.items() if c["value"] > c["limit"]}
    # a loaded worker may complete no cycle in 4 s (test_cells_live.py)
    assert over <= {"no_cycle_completed", "answers_missing"}, checks
    for name in ("archive_rows_gap", "failure_rows_gap"):
        assert checks[name]["value"] <= ROWS_GAP_LIMIT, checks
    for name in ("ring_counts_differ", "labels_differ", "pairs_differ",
                 "reference_buckets_differ", "window_compiles"):
        assert checks[name]["value"] == 0, checks
    assert facts["depth_at_open"] == DEPTH + 1  # + 1 warm-up run
    held = facts["searches"][0]
    # every request re-feeds the whole history: both direct requests,
    # the warm-up run's and each cycle's, 1,028 runs and up each
    requests = 3 + facts["installs_in_window"]
    assert held["archive_n"] >= requests * (DEPTH - 2) > 2 * 512
    # the rehearsal testee's one failure template is one signature
    # (times are no part of it), so 67 of 68 failures are passed over
    # at every request and the ring holds one row per sequence seen
    assert 1 <= held["failure_n"] <= 1 + facts["reproductions"]
    m = result["metrics"]
    # every write of the window landed on a live archive row
    assert m["archive_overwrite_share"] == {"value": 100.0, "unit": "%"}
    assert m["window_compiles"]["value"] == 0


BREAKS = {
    # a ring writer that keeps the FIRST write of a slot in a request
    # and drops the later ones
    "first_write_of_a_slot_kept": """
        from namazu_tpu.models import search as _s
        _flush = _s.SearchBase._flush
        def _first_kept(self, batch):
            for which, writes in batch.writes.items():
                first = {}
                for slot, row in writes:
                    first.setdefault(slot, row)
                batch.writes[which] = list(first.items())
            return _flush(self, batch)
        _s.SearchBase._flush = _first_kept
        """,
    # a dedupe that holds nothing: a repeated signature takes the next
    # slot, and past 64 of them evicts a distinct one
    "repeated_signature_takes_a_slot": """
        import itertools
        from namazu_tpu.models import failure_pool as _fp
        _n = itertools.count()
        _fp.trace_digest = lambda enc: f"never-twice-{next(_n)}"
        """,
}
CAUGHT_BY = {"first_write_of_a_slot_kept": "archive_rows_gap",
             "repeated_signature_takes_a_slot": "ring_counts_differ"}


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_a_broken_ring_rule_is_not_correct(deep_root, tmp_path, how):
    site = tmp_path / "site"
    site.mkdir()
    (site / "sitecustomize.py").write_text(
        "import sys\n"
        "if any(a.endswith('sidecar_main.py') for a in sys.argv):\n"
        f"    sys.path.insert(0, {tiny_root.REPO!r})\n"
        + textwrap.indent(textwrap.dedent(BREAKS[how]), "    "))
    rc, result, out, err = tiny_root.run_cell(
        deep_root, CELL, 1, seconds=3.0,
        extra_env={"PYTHONPATH": str(site)})
    assert rc == 0, err[-3000:]
    assert result["correct"] is False, out[-2000:]
    checks = result["checks"]
    caught = CAUGHT_BY[how]
    assert checks[caught]["value"] > checks[caught]["limit"], checks
    # its own number: the other ring's rows still agree
    other = {"archive_rows_gap": "failure_rows_gap",
             "ring_counts_differ": "archive_rows_gap"}[caught]
    assert checks[other]["value"] <= checks[other]["limit"], checks
