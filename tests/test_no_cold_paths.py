"""Nothing a run can bring lowers a program after the request that
built the search (models/search.py: the resident traces' row update is
the one way a row gets into their buffers, the first staging included;
the rings' scatter is what stages the device mirrors): a moved reference envelope, a first
failure, an overwritten live archive slot, a new hint bucket — each
adds 0 to ``nmz_compiles_total`` (``window_compiles`` of the benchmark
is that count over a window; it read 3 for PR 36 and PR 42, the row
update's three dtypes).

Not covered, and said so in PERF.md section 7: the re-rank's own start
(the request whose labelled archive first holds three runs of each
outcome builds and lowers the surrogate's programs)."""

import pytest

from namazu_tpu import obs
from namazu_tpu.models.ingest import IngestParams, ingest_history
from namazu_tpu.models.search import (
    ScheduleSearch,
    SearchConfig,
    _ResidentTraces,
)
from namazu_tpu.obs import spans
from namazu_tpu.signal import PacketEvent
from namazu_tpu.signal.base import HINT_SPACE
from namazu_tpu.storage import new_storage
from namazu_tpu.utils.trace import SingleTrace

from tests.test_request_spans import fresh_obs  # noqa: F401

OFFSETS = (0.0, 0.010, 0.020, 0.030, 0.040, 0.050)
INGEST = IngestParams(H=32, max_interval=0.05, reference_mode="envelope")


def store_run(st, offsets, ok=True, new_hint=False):
    """One stored run: event ``i`` (hint ``n<i>``) arrives ``offsets[i]``
    after the run's first."""
    st.create_new_working_dir()
    trace, base = SingleTrace(), 1.7e9
    hints = [f"n{i}" for i in range(len(offsets))]
    if new_hint:
        hints[-1] = "never-seen"
    for i, (hint, off) in enumerate(zip(hints, offsets)):
        ev = PacketEvent.create(hint, hint, "peer", hint=hint)
        ev.mark_arrived(base + off)
        action = ev.default_action()
        action.mark_triggered(base + off + 0.001 * (i % 3))
        trace.append(action)
    st.record_new_trace(trace)
    st.record_result(ok, 0.5, metadata={"hint_space": HINT_SPACE})


def scaled(factor):
    return [o * factor for o in OFFSETS]


def counter(name, **labels):
    s = obs.metrics.registry().sample(name, **labels)
    return 0 if s is None else s.value


def request(search, st):
    """One request's ingest and evolve; the names of what it lowered."""
    ring = spans.span_ring()
    cursor, before = ring.end(), counter(spans.COMPILES)
    search.run(ingest_history(search, st, INGEST), generations=4)
    lowered = [r[7].get("fun_name") for r in ring.since(cursor)["rows"]
               if r[1] == "compile"]
    assert counter(spans.COMPILES) - before == len(lowered)
    return lowered


def moved_envelope(st, search):
    appends = search._traces.appends
    # every event but the first a fifth earlier: new per-bucket minima
    store_run(st, [0.0] + scaled(0.8)[1:])
    yield
    assert search._traces.appends == appends + 1
    assert search._traces.rebuilds == 1


def first_failure(st, search):
    assert search._failure_n == 0
    store_run(st, scaled(1.02), ok=False)
    yield
    assert search._failure_n == 1
    assert counter(spans.RING_ROWS_WRITTEN, ring="failure") == 1


def live_archive_slot_overwritten(st, search):
    # four stored runs into an archive of four rows: the ring is full,
    # and the next request's first write lands on a live row
    assert search._archive_n == 4
    assert counter(spans.RING_ROWS_OVERWRITTEN, ring="archive") == 0
    yield
    assert search._archive_n == 8
    assert counter(spans.RING_ROWS_WRITTEN, ring="archive") == 8
    assert counter(spans.RING_ROWS_OVERWRITTEN, ring="archive") == 4


def same_history_again(st, search):
    # the first request found no device mirrors (they are staged by its
    # evolve); this one is the first whose ingest scatters into them
    yield
    assert search._dev_mirrors["archive"] is not None


def new_hint_bucket(st, search):
    pairs = search.pairs
    store_run(st, scaled(1.04), new_hint=True)
    yield
    # the pair sample was refitted and every device input staged anew
    assert search.pairs is not pairs


def failure_signature_already_held(st, search):
    store_run(st, scaled(1.02), ok=False)
    request(search, st)
    assert counter(spans.FAILURE_SIGNATURES_DEDUPED) == 0
    yield
    assert search._failure_n == 1
    assert counter(spans.FAILURE_SIGNATURES_DEDUPED) == 1


CASES = [moved_envelope, first_failure, live_archive_slot_overwritten,
         same_history_again, new_hint_bucket,
         failure_signature_already_held]


@pytest.fixture
def nothing_lowered_yet():
    """The lowering caches are the process's: emptied, so that every
    case meets its programs as a fresh sidecar would."""
    import jax

    jax.clear_caches()


@pytest.mark.parametrize("case", CASES, ids=[c.__name__ for c in CASES])
def test_a_request_after_the_first_lowers_nothing(
        fresh_obs, nothing_lowered_yet, tmp_path, case):  # noqa: F811
    st = new_storage("naive", str(tmp_path / "st"))
    st.create()
    for k in range(4):
        store_run(st, scaled(1 + 0.01 * k))
    search = ScheduleSearch(SearchConfig(
        H=32, K=32, population=64, migrate_k=2, seed=5, fused_chunk=2,
        archive_size=4, failure_size=4, surrogate_topk=4))
    first = request(search, st)
    # the request that builds the search pays for the row update of
    # every dtype the resident traces hold, and for the rings' scatter
    assert first.count("jit(row_update)") == len(
        {a.dtype for a in search._traces.bufs.values()}) == 3
    assert first.count("jit(rows_scatter)") == 1
    what_it_brings = case(st, search)
    next(what_it_brings)
    assert request(search, st) == []
    assert next(what_it_brings, None) is None


def test_rows_put_one_by_one_are_the_host_stackers_rows():
    import numpy as np

    from namazu_tpu.ops import trace_encoding as te

    def enc(scale):
        hint_ids = np.arange(1, 7, dtype=np.int32)
        return te.EncodedTrace(
            hint_ids, hint_ids.copy(),
            np.asarray(OFFSETS, np.float32) * scale,
            np.ones(6, bool), faultable=np.arange(6) % 2 == 0)

    encs = [enc(1.0), enc(0.9)]
    resident = _ResidentTraces()
    view = resident.view(encs)
    h, _e, a, m, fb = te.stack_traces(encs)
    for got, want in zip(view, (h, a, m, fb)):
        np.testing.assert_array_equal(np.asarray(got), want)
