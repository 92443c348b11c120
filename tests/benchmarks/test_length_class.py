"""One trace length per search, held to the plain reference.

A history whose stored runs pad to two lengths (``zk2212-zab5-live``:
L 256 and L 384) used to give the fused step, the re-rank and the embed
program the padded length of whichever runs a request happened to hold.
The search now holds ONE length, its length class, and a shorter run's
tail is masked. No answer may move for that: here a mixed-length
history is replayed request by request, in process at a small width,
and after every request the rings, labels, pair sample, resident
reference traces and the reply's fitness are held to
``benchmarks/reference.py`` inside the benchmark's own limits — and to
the numbers the per-length path gave for the same history on the
parent commit (``PARENT``)."""

import sys

import numpy as np

import tiny_root

sys.path.insert(0, tiny_root.BENCH)
if tiny_root.REPO not in sys.path:
    sys.path.insert(0, tiny_root.REPO)

import reference  # noqa: E402

FITNESS_GAP_LIMIT, ROWS_GAP_LIMIT = 0.05, 1e-5  # run.py's own
H, K, ARCHIVE_ROWS, FAILURE_ROWS = 32, 16, 16, 4
SEARCH_PARAMS = {"H": H, "K": K, "seed": 3, "tau": 0.005,
                 "w_novelty": 1.0, "w_bug": 1.0, "w_delay_cost": 0.1,
                 "max_interval": 0.05, "release_mode": "delay"}
INGEST_PARAMS = {"H": H, "max_interval": 0.05, "reference_mode": "recent"}
#: events of a run that pads to L 128 and to L 256
SHORT, LONG = 40, 150
#: the stored history, oldest first, as (events, passed): both lengths
#: among the successes AND the failures, and then one run a request —
#: the reference window (the four newest successes) goes from two long
#: runs to none and back to one
STORED = [(SHORT, True), (LONG, True), (SHORT, False), (LONG, True),
          (LONG, False), (SHORT, True)]
ARRIVE = [(SHORT, True), (SHORT, True), (SHORT, True), (LONG, True),
          (SHORT, False)]
#: per request: the reply's fitness and the archive's checksum as the
#: PARENT commit (9dedb04: one padded length per request and per embed
#: group) gave them for this history on this backend (XLA:CPU,
#: float32), read by running this file's ``replay`` against a copy of
#: that commit: equal to the last bit here. The tolerance is a few
#: float32 ulps and not 0 because the masked tail adds no event but
#: lengthens the reductions over a trace's events, which another XLA
#: build may vectorise another way; what a moved event or a wrong row
#: does is 1e-3 or more (PERF.md section 2).
PARENT = [
    [-0.0023529401514679193, 126.08148956298828, 32.01191329956055],
    [-0.0021669927518814802, 124.09896850585938, 32.01191329956055],
    [-0.0019553101155906916, 123.54252624511719, 32.01191329956055],
    [0.18716461956501007, 123.57002258300781, 32.01191329956055],
    [0.40576353669166565, 122.219970703125, 32.01191329956055],
    [0.4531483054161072, 123.38233947753906, 32.2823371887207],
]
PARENT_RTOL, PARENT_ATOL = 1e-6, 1e-7


def store_run(st, n_events, ok, k):
    """One stored run of ``n_events`` events over 24 hints, its order a
    rotation by ``k`` (a failure's signature is its order), 0.3 ms
    apart."""
    from namazu_tpu.signal import PacketEvent
    from namazu_tpu.signal.base import HINT_SPACE
    from namazu_tpu.utils.trace import SingleTrace

    st.create_new_working_dir()
    trace, base = SingleTrace(), 1.7e9
    for i in range(n_events):
        hint = f"n{(i * 7 + k) % 24}"
        ev = PacketEvent.create(hint, hint, "peer", hint=hint)
        ev.mark_arrived(base + 0.0003 * i * (1 + 0.01 * k))
        action = ev.default_action()
        action.mark_triggered(base + 0.0003 * i * (1 + 0.01 * k)
                              + 0.001 * ((i + k) % 3))
        trace.append(action)
    st.record_new_trace(trace)
    st.record_result(ok, 0.5, metadata={"hint_space": HINT_SPACE})


def replay(tmp_path):
    """Yields ``(search, state, refs, reply)`` after each request."""
    from namazu_tpu.models.ingest import IngestParams, ingest_history
    from namazu_tpu.models.search import ScheduleSearch, SearchConfig
    from namazu_tpu.models.search import make_score_weights
    from namazu_tpu.storage import new_storage

    st = new_storage("naive", str(tmp_path / "st"))
    st.create()
    for k, (n, ok) in enumerate(STORED):
        store_run(st, n, ok, k)
    search = ScheduleSearch(SearchConfig(
        H=H, K=K, population=32, migrate_k=2, seed=3, fused_chunk=2,
        archive_size=ARCHIVE_ROWS, failure_size=FAILURE_ROWS,
        surrogate_topk=4,
        weights=make_score_weights(
            release_mode="delay", w_novelty=1.0, w_bug=1.0,
            w_delay_cost=0.1, w_fault_cost=0.0, tau=0.005,
            reorder_gap=0.0, reorder_window=0.0)),
        n_devices=1)  # PARENT's numbers are one island's
    state = reference.SearchState(SEARCH_PARAMS, INGEST_PARAMS,
                                  ARCHIVE_ROWS, FAILURE_ROWS)
    for k, arrives in enumerate([None] + ARRIVE):
        if arrives is not None:
            store_run(st, *arrives, 10 + k)
        depth = st.nr_stored_histories()
        refs = ingest_history(search, st, IngestParams(**INGEST_PARAMS))
        reply = search.run(refs, generations=4)
        state.ingest(reference.read_runs(st.dir, depth, H))
        yield search, state, refs, reply


def resident_of(search, refs):
    _encs, trace, pairs, archive, failures = \
        search._device_inputs_fused(refs)
    return {"pairs": pairs, "archive": archive, "failures": failures,
            "labels": search.archive_labels, "hint_ids": trace.hint_ids,
            "arrival": trace.arrival, "mask": trace.mask,
            "archive_n": search._archive_n,
            "failure_n": search._failure_n}


def readings(tmp_path):
    """What ``PARENT`` records, from whichever tree is imported."""
    return [[float(reply.fitness), float(search.archive.sum()),
             float(search.failures.sum())]
            for search, _state, _refs, reply in replay(tmp_path)]


def test_a_mixed_length_history_agrees_with_the_reference(tmp_path):
    lengths = set()
    for n, (search, state, refs, reply) in enumerate(replay(tmp_path)):
        resident = resident_of(search, refs)
        gaps = reference.resident_gap(state, resident)
        exact = {k: v for k, v in gaps.items() if not k.endswith("_gap")}
        assert set(exact.values()) == {0}, (n, gaps)
        assert gaps["archive_rows_gap"] <= ROWS_GAP_LIMIT, (n, gaps)
        assert gaps["failure_rows_gap"] <= ROWS_GAP_LIMIT, (n, gaps)
        assert gaps["reference_times_gap"] == 0, (n, gaps)
        want = float(state.score(reply.delays)[0])
        assert abs(reply.fitness - want) <= FITNESS_GAP_LIMIT, (n, want)
        # one shape, whichever runs the four references are
        assert np.asarray(resident["hint_ids"]).shape == (
            len(refs), search.length_class)
        lengths.add(max(e.hint_ids.shape[0] for e in refs))
        assert search._archive_n == state.archive_n
        got = [reply.fitness, search.archive.sum(), search.failures.sum()]
        np.testing.assert_allclose(got, PARENT[n], rtol=PARENT_RTOL,
                                   atol=PARENT_ATOL, err_msg=str(n))
    assert search.length_class == 256
    # the regime: windows under the class and windows at it
    assert lengths == {128, 256}
    assert n == len(ARRIVE) == len(PARENT) - 1


if __name__ == "__main__":
    # how PARENT was read: PYTHONPATH=<a copy of the parent commit>
    import pathlib
    import tempfile

    print(readings(pathlib.Path(tempfile.mkdtemp())))
