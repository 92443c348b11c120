"""Nothing a run can bring lowers a program after the request that
built the search (models/search.py: the resident traces' row update is
the one way a row gets into their buffers, the first staging included;
the rings' scatter is what stages the device mirrors): a moved reference envelope, a first
failure, an overwritten live archive slot, a new hint bucket — each
adds 0 to ``nmz_compiles_total`` (``window_compiles`` of the benchmark
is that count over a window; it read 3 for PR 36 and PR 42, the row
update's three dtypes). Nor does a run's LENGTH: a search holds one
trace length, its length class (the longest padded length among the
stored runs it has met), so a reference window of short runs, one with
a long run in it, a new short run and a new long one all meet the
programs the building request lowered; only a run PAST the class steps
it, once, counted, and the request after that lowers nothing again.

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


def request(search, st, ingest=INGEST):
    """One request's ingest and evolve; the names of what it lowered."""
    ring = spans.span_ring()
    cursor, before = ring.end(), counter(spans.COMPILES)
    search.run(ingest_history(search, st, ingest), generations=4)
    lowered = [r[7].get("fun_name") for r in ring.since(cursor)["rows"]
               if r[1] == "compile"]
    assert counter(spans.COMPILES) - before == len(lowered)
    return lowered


def small_search(archive_size):
    return ScheduleSearch(SearchConfig(
        H=32, K=32, population=64, migrate_k=2, seed=5, fused_chunk=2,
        archive_size=archive_size, failure_size=4, surrogate_topk=4))


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
    search = small_search(archive_size=4)
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


# -- a run's length ----------------------------------------------------------

#: the four newest successes are the references, as in zk2212-zab5
RECENT = IngestParams(H=32, max_interval=0.05, reference_mode="recent")
#: events of a run that pads to 128, to 256 and to 384
SHORT, LONG, PAST = 6, 130, 260
#: stored histories, oldest first, that hold both lengths: the building
#: request's reference window is four short runs (under the class), or
#: has a long run in it (at the class)
BUILT = {"under_the_class": (LONG, SHORT, SHORT, SHORT, SHORT),
         "at_the_class": (SHORT, SHORT, LONG, SHORT, SHORT)}


def store_run_of(st, n_events, k):
    store_run(st, [0.0004 * i * (1 + 0.01 * k) for i in range(n_events)])


@pytest.mark.parametrize("brings", [(), (SHORT,), (LONG,),
                                    (SHORT, SHORT, SHORT), (LONG, SHORT)],
                         ids=lambda b: "+".join(map(str, b)) or "nothing")
@pytest.mark.parametrize("built", sorted(BUILT))
def test_a_runs_length_lowers_nothing_after_the_first_request(
        fresh_obs, nothing_lowered_yet, tmp_path, built, brings):  # noqa: F811
    st = new_storage("naive", str(tmp_path / "st"))
    st.create()
    for k, n in enumerate(BUILT[built]):
        store_run_of(st, n, k)
    search = small_search(archive_size=16)
    assert request(search, st, RECENT) != []
    assert search.length_class == 256  # the longest STORED run's
    # each run the history gains is a request of its own: ingested
    # alone, and the newest of the four references
    for k, n in enumerate(brings or (None,)):
        if n is not None:
            store_run_of(st, n, 10 + k)
        assert request(search, st, RECENT) == []
    assert search.length_class == search._traces.L == 256
    assert search._traces.rebuilds == 1
    assert counter(spans.LENGTH_CLASS_STEPS) == 0
    # every request embedded the whole stored history at the class,
    # and the runs under it were counted
    history, base = list(BUILT[built]) + list(brings), len(BUILT[built])
    depths = [base] + [base + k for k in range(1, len(brings) + 1) or [0]]
    assert counter(spans.EMBED_TRACES) == sum(depths)
    assert counter(spans.EMBED_TRACES_BELOW_CLASS) == sum(
        history[:d].count(SHORT) for d in depths)


def test_a_run_past_the_class_steps_it_once(
        fresh_obs, nothing_lowered_yet, tmp_path):  # noqa: F811
    st = new_storage("naive", str(tmp_path / "st"))
    st.create()
    for k, n in enumerate(BUILT["at_the_class"]):
        store_run_of(st, n, k)
    search = small_search(archive_size=16)
    request(search, st, RECENT)
    restaged = counter(spans.RESIDENT_TRACE_ROWS, op="restage")
    assert restaged == 4 and search.length_class == 256
    store_run_of(st, PAST, 20)
    stepped = request(search, st, RECENT)
    # the step: the resident rows staged anew at the new length, and
    # the programs that take a trace lowered at it, each named
    assert search.length_class == search._traces.L == 384
    assert counter(spans.LENGTH_CLASS_STEPS) == 1
    assert counter(spans.RESIDENT_TRACE_ROWS, op="restage") == restaged + 4
    assert stepped.count("jit(row_update)") == 3
    assert "jit(rows)" in stepped  # the embed program, at the new length
    # ... once: the same history again, a short run and a long one
    for n in (None, SHORT, LONG):
        if n is not None:
            store_run_of(st, n, 30 + n)
        assert request(search, st, RECENT) == []
    assert counter(spans.LENGTH_CLASS_STEPS) == 1
    encode = [r[7] for r in fresh_obs.since(0)["rows"] if r[1] == "encode"]
    assert [a["length_class"] for a in encode] == [256, 384, 384, 384, 384]


@pytest.mark.parametrize("length", [6, 10, 128],
                         ids=["Lmax", "past_Lmax", "a_quantum"])
def test_rows_put_one_by_one_are_the_host_stackers_rows(length):
    """At the references' own longest length the view IS the host
    stacker's batch; at a search's class past it, the same rows with
    the stacker's pad fills for a tail, and the scorer gives every
    table the same fitness and features against either."""
    import jax.numpy as jnp
    import numpy as np

    from namazu_tpu.ops import schedule as sch
    from namazu_tpu.ops import trace_encoding as te

    def enc(scale, n=6):
        hint_ids = np.arange(1, n + 1, dtype=np.int32)
        return te.EncodedTrace(
            hint_ids, hint_ids.copy(),
            np.asarray(OFFSETS[:n], np.float32) * scale,
            np.ones(n, bool), faultable=np.arange(n) % 2 == 0)

    encs = [enc(1.0), enc(0.9), enc(1.1, n=4)]
    view = _ResidentTraces().view(encs, length)
    stacked = te.stack_traces(encs)
    h, _e, a, m, fb = stacked
    for got, want in zip(view, (h, a, m, fb)):
        got = np.asarray(got)
        assert got.shape == (3, length)
        np.testing.assert_array_equal(got[:, :6], want)
        assert not got[:, 6:].any()  # 0 / 0.0 / False: pad_trace_row's
    rng = np.random.RandomState(0)
    delays = jnp.asarray(rng.uniform(0, 0.05, (8, 16)), jnp.float32)
    pairs = jnp.asarray(te.sample_pairs(8, 16, 0))
    rings = [jnp.asarray(rng.uniform(0, 1, (4, 8)), jnp.float32)
             for _ in range(2)]
    at_lmax = sch.score_population_multi(
        delays, sch.TraceArrays(*map(jnp.asarray, (h, a, m)), None),
        pairs, *rings)
    at_class = sch.score_population_multi(
        delays, sch.TraceArrays(*view[:3], None), pairs, *rings)
    for got, want in zip(at_class, at_lmax):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
