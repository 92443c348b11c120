"""The embed stage of history ingest (models/ingest.py, the ring writers
of models/search.py, ``ops.schedule.batched_trace_features``): a
request's stored runs go to the device ``EMBED_CHUNK`` at a time, and
what lands in the rings — rows, slots, labels, counts, digests, device
mirrors — is what one ``trace_features`` round trip per run left there.
The per-run form is kept HERE, as the reference the batch is held to."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from namazu_tpu import obs
from namazu_tpu.models.failure_pool import pool_add, pool_load, trace_digest
from namazu_tpu.models.ga import GAConfig
from namazu_tpu.models.ingest import IngestParams, ingest_history
from namazu_tpu.models.search import (
    EMBED_CHUNK,
    ScheduleSearch,
    SearchConfig,
)
from namazu_tpu.obs import spans
from namazu_tpu.ops import schedule as sch
from namazu_tpu.ops import trace_encoding as te
from namazu_tpu.signal import PacketEvent
from namazu_tpu.signal.base import HINT_SPACE
from namazu_tpu.storage.base import HistoryStorage
from namazu_tpu.utils.trace import SingleTrace

from tests.test_request_spans import isolated_obs

H = K = 32
#: float32 rows against float32 rows of the same function: an ulp or
#: two of a sigmoid in (0, 1), far under the benchmark's 1e-5
ROW_TOL = 1e-6


def cfg(**kw):
    base = SearchConfig(H=H, K=K, archive_size=128, failure_size=16,
                        population=64, migrate_k=2, seed=3,
                        ga=GAConfig(max_delay=0.05))
    return base._replace(**kw)


def enc_of(n, seed):
    rng = np.random.RandomState(seed)
    return te.encode_event_stream(
        [f"h{rng.randint(12)}" for _ in range(n)],
        arrivals=sorted(rng.rand(n).tolist()), H=H)


def one_run_row(enc, pairs, tau):
    """The parent commit's ``_feats_of``: uploads, the eager
    ``trace_features``, a fetch — the reference for every row."""
    trace = sch.TraceArrays(jnp.asarray(enc.hint_ids),
                            jnp.asarray(enc.arrival),
                            jnp.asarray(enc.mask))
    return np.asarray(sch.trace_features(trace, jnp.asarray(pairs), tau, H))


def make_run(seed, n_events=17):
    """A recorded run: ``n_events`` packets of three nodes, each released
    a jittered 0-3 ms after it arrived."""
    rng = np.random.RandomState(seed)
    t = SingleTrace()
    now = 1_000.0 + seed
    for i in range(n_events):
        node = "abc"[rng.randint(3)]
        ev = PacketEvent.create(node, node, "peer", hint=f"{node}:{i % 4}")
        a = ev.default_action()
        a.event_arrived = now + i * 0.002
        a.mark_triggered(now + i * 0.002 + rng.rand() * 0.003)
        t.append(a)
    return t


class ListStorage(HistoryStorage):
    """Stored runs in memory: ``(trace, successful)`` in order (no
    ``run_signature``: nothing of it is ever cached)."""

    def __init__(self, runs):
        self.runs = list(runs)

    def nr_stored_histories(self):
        return len(self.runs)

    def get_stored_history(self, i):
        return self.runs[i][0]

    def is_successful(self, i):
        return self.runs[i][1]

    def get_metadata(self, i):
        return {"hint_space": HINT_SPACE}


def history(depth, every_nth_fails=4, long_from=None):
    """``depth`` runs; every n-th one failed; from ``long_from`` on the
    runs have 140 events (L 256 instead of 128)."""
    return ListStorage(
        (make_run(i, 140 if long_from is not None and i >= long_from
                  else 17), i % every_nth_fails != 1)
        for i in range(depth))


class PerRunReplay:
    """The rings as the per-run loop fills them: one row per stored run
    per request in stored order, pooled entries first, failures deduped
    by digest, slot-aligned digests, a pair refit clears everything."""

    def __init__(self, c):
        self.c = c
        self.pairs = None
        self._clear()

    def _clear(self):
        c = self.c
        self.archive = np.full((c.archive_size, c.K), 0.5, np.float32)
        self.labels = np.zeros((c.archive_size,), np.float32)
        self.failures = np.full((c.failure_size, c.K), 0.5, np.float32)
        self.archive_n = self.failure_n = 0
        self.digests = [""] * c.failure_size

    def _executed(self, enc, reproduced):
        slot = self.archive_n % self.c.archive_size
        self.archive[slot] = one_run_row(enc, self.pairs, self.c.weights.tau)
        self.labels[slot] = 1.0 if reproduced else 0.0
        self.archive_n += 1

    def _failure(self, enc):
        digest = trace_digest(enc)
        if digest in self.digests:
            return
        slot = self.failure_n % self.c.failure_size
        self.failures[slot] = one_run_row(enc, self.pairs,
                                          self.c.weights.tau)
        self.digests[slot] = digest
        self.failure_n += 1

    def request(self, storage, pairs, pooled=()):
        if self.pairs is None or not np.array_equal(pairs, self.pairs):
            self.pairs = np.array(pairs)
            self._clear()
        for e in pooled:
            if e.digest in self.digests:
                continue
            self._executed(e.realized, True)
            self._failure(e.realized)
        for i in range(storage.nr_stored_histories()):
            _enc, enc_rt = te.encode_trace_views(
                storage.get_stored_history(i), H=H)
            ok = storage.is_successful(i)
            self._executed(enc_rt, not ok)
            if not ok:
                self._failure(enc_rt)

    def check(self, s):
        assert s._archive_n == self.archive_n
        assert s._failure_n == self.failure_n
        assert s._failure_digests == self.digests
        assert s._failure_digest_set == {d for d in self.digests if d}
        assert np.array_equal(s.archive_labels, self.labels)
        assert np.abs(s.archive - self.archive).max() <= ROW_TOL
        assert np.abs(s.failures - self.failures).max() <= ROW_TOL


@pytest.fixture
def fresh_obs():
    with isolated_obs() as ring:
        yield ring


def embed_calls():
    return obs.metrics.registry().value(spans.INGEST_EMBED_CALLS) or 0


# -- (a) the batched rows are the one-run rows -------------------------------


@pytest.mark.parametrize("n", [1, 3, 64, 65, 130])
def test_batched_rows_equal_the_one_run_rows(n):
    s = ScheduleSearch(cfg(), n_devices=1)
    # ragged: 17-event traces pad to L 128, 140-event ones to 256, mixed
    encs = [enc_of(140 if i % 3 == 2 else 17, 100 + i) for i in range(n)]
    assert {e.hint_ids.shape[0] for e in encs} <= {128, 256}
    rows = s._embed(encs)
    assert rows.shape == (n, K) and rows.dtype == np.float32
    want = np.stack([one_run_row(e, s.pairs, s.cfg.weights.tau)
                     for e in encs])
    assert np.abs(rows - want).max() <= ROW_TOL
    # the one-run form is the batch of one
    for i in (0, n - 1):
        assert np.array_equal(s._feats_of(encs[i]), rows[i])


# -- (b) r requests at depth d against the per-run replay --------------------


@pytest.mark.parametrize("rings, depths", [
    ((128, 16), (10, 11, 13)),       # the rings hold every run
    ((8, 8), (20, 21)),              # a ring of 8 under 20 runs
    ((16, 2), (40,)),                # N > archive_size, failures wrap
    ((64, 4), (66, 75)),             # more than one chunk a request
], ids=["rings_hold_all", "ring_of_8_under_20", "more_rows_than_ring",
        "two_chunks"])
def test_requests_fill_the_rings_as_the_per_run_loop_did(rings, depths):
    c = cfg(archive_size=rings[0], failure_size=rings[1])
    s = ScheduleSearch(c, n_devices=1)
    replay = PerRunReplay(c)
    for depth in depths:
        st = history(depth)
        ingest_history(s, st, IngestParams(H=H))
        replay.request(st, s.pairs)
        replay.check(s)
    assert s._archive_n == sum(depths)


def test_pooled_signatures_go_in_first_in_the_same_batch(tmp_path):
    c = cfg(archive_size=32, failure_size=8)
    pool = str(tmp_path / "pool")
    # another campaign's failures, pooled
    for i in (50, 51, 52):
        enc, enc_rt = te.encode_trace_views(make_run(i), H=H)
        pool_add(pool, enc_rt, enc, None, H)
    s = ScheduleSearch(c, n_devices=1)
    replay = PerRunReplay(c)
    for depth in (6, 7):
        st = history(depth)
        ingest_history(s, st, IngestParams(H=H, failure_pool=pool))
        own = {trace_digest(te.encode_trace_views(
            st.get_stored_history(i), H=H)[1])
            for i in range(depth) if not st.is_successful(i)}
        replay.request(st, s.pairs, pool_load(pool, H, exclude=own))
        replay.check(s)
    # 3 pooled + 2 own failures, the pooled ones in the first slots;
    # the second request re-embedded every run and spent no new slot
    assert s._failure_n == 5 and s._archive_n == (3 + 6) + 7
    assert set(s._failure_digests[3:5]) == own


# -- (c) the device mirrors follow the host rings ----------------------------


@pytest.mark.parametrize("n_devices", [1, 4])
def test_device_mirrors_equal_the_host_rings_after_a_batched_ingest(
        n_devices):
    c = cfg(archive_size=16, failure_size=4)
    s = ScheduleSearch(c, n_devices=n_devices)
    refs = ingest_history(s, history(10), IngestParams(H=H))
    s.run(refs, generations=2)  # the fused run builds the mirrors
    assert all(m is not None for m in s._dev_mirrors.values())
    for depth in (13, 30):  # within the ring, then around it twice
        ingest_history(s, history(depth), IngestParams(H=H))
        assert all(m is not None for m in s._dev_mirrors.values())
        assert np.array_equal(np.asarray(s._dev_mirrors["archive"]),
                              s.archive)
        assert np.array_equal(np.asarray(s._dev_mirrors["failures"]),
                              s.failures)
    # and what the next fused run takes are those buffers
    _encs, _trace, _pairs, archive, failures = \
        s._device_inputs_fused(refs)
    assert archive is s._dev_mirrors["archive"]
    assert failures is s._dev_mirrors["failures"]
    s.run(refs, generations=2)


def test_without_mirrors_only_the_host_rings_are_written():
    s = ScheduleSearch(cfg(), n_devices=1)
    refs = ingest_history(s, history(5), IngestParams(H=H))
    s.run(refs, generations=2)
    s._mirror_invalidate()  # checkpoint restore, pair refit
    ingest_history(s, history(6), IngestParams(H=H))
    assert s._dev_mirrors == {"archive": None, "failures": None}
    assert s._archive_n == 11
    # the next fused run stages the host rings whole
    _e, _t, _p, archive, _f = s._device_inputs_fused(refs)
    assert np.array_equal(np.asarray(archive), s.archive)


# -- (d) one shape whatever the depth ----------------------------------------


@pytest.mark.parametrize("mirrors", ["built", "unbuilt"])
def test_depths_10_23_66_75_lower_the_embed_program_once(mirrors,
                                                         fresh_obs):
    # a tau of this test's own: the jitted embed is cached per (tau, H)
    # for the whole process, and this one has to start cold
    weights = sch.ScoreWeights(
        tau=0.00512 if mirrors == "built" else 0.00513)
    s = ScheduleSearch(cfg(weights=weights), n_devices=1)
    refs = ingest_history(s, history(10), IngestParams(H=H))
    if mirrors == "built":
        s.run(refs, generations=2)  # mirrors: the scatter compiles too
        ingest_history(s, history(10), IngestParams(H=H))
    embed = sch.batched_trace_features(weights.tau, H)
    assert embed._cache_size() == 1
    lowered = obs.metrics.registry().value(spans.COMPILES)
    for depth in (23, 66, 75):
        ingest_history(s, history(depth), IngestParams(H=H))
    assert embed._cache_size() == 1
    assert obs.metrics.registry().value(spans.COMPILES) == lowered


# -- (e) the counter and the stage's pieces ----------------------------------


@pytest.mark.parametrize("short, long", [
    (10, 0), (64, 0), (65, 0), (70, 3), (130, 66)])
def test_embed_calls_are_ceil_n_over_chunk_whatever_the_lengths(
        short, long, fresh_obs):
    # one length per search: the short and the long runs of a history
    # share the chunks of ONE program (before: a chunk sequence, and a
    # compiled embed, per padded length)
    s = ScheduleSearch(cfg(archive_size=256), n_devices=1)
    st = history(short + long, long_from=short)
    want = math.ceil((short + long) / EMBED_CHUNK)
    for request in (1, 2):
        ingest_history(s, st, IngestParams(H=H))
        assert embed_calls() == request * want
    assert obs.metrics.registry().value(spans.INGEST_RUNS) \
        == 2 * (short + long)
    rows = [r for r in fresh_obs.since(0)["rows"] if r[1] == "ingest_embed"]
    assert [r[7]["pieces"] for r in rows] == [want, want]
    assert [r[2] for r in rows] == ["ingest", "ingest"]


# -- (f) a new failure's row is its archive row ------------------------------


def test_a_new_failures_row_is_its_archive_row(fresh_obs):
    c = cfg()
    s = ScheduleSearch(c, n_devices=1)
    st = history(64)  # 16 failures of 64 runs: 80 rows if embedded twice
    ingest_history(s, st, IngestParams(H=H))
    assert embed_calls() == 1
    failed = [i for i in range(64) if not st.is_successful(i)]
    assert s._failure_n == len(failed) == 16
    for slot, i in enumerate(failed):
        assert np.array_equal(s.failures[slot], s.archive[i])
    # re-ingest: every run again a row of the archive, no failure slot
    digests = list(s._failure_digests)
    ingest_history(s, st, IngestParams(H=H))
    assert (s._archive_n, s._failure_n) == (128, 16)
    assert s._failure_digests == digests
    assert embed_calls() == 2


def test_the_one_run_methods_are_the_batch_of_one(fresh_obs):
    s = ScheduleSearch(cfg(), n_devices=1)
    enc = enc_of(17, 7)
    s.add_executed_trace(enc, reproduced=True)
    s.add_failure_trace(enc)
    s.add_failure_trace(enc)  # deduped: no embed, no slot
    assert embed_calls() == 2
    assert (s._archive_n, s._failure_n) == (1, 1)
    want = one_run_row(enc, s.pairs, s.cfg.weights.tau)
    assert np.abs(s.archive[0] - want).max() <= ROW_TOL
    assert np.array_equal(s.failures[0], s.archive[0])
    assert s.archive_labels[0] == 1.0
    # nested batches flush once, at the outermost exit
    with s.embed_batch() as outer:
        s.add_executed_trace(enc_of(17, 8))
        with s.embed_batch() as inner:
            s.add_executed_trace(enc_of(17, 9))
        assert inner is outer and embed_calls() == 2
    assert embed_calls() == 3 and outer.calls == 1
    assert s._archive_n == 3 and s._batch is None


# -- (g) guidance on ---------------------------------------------------------


def test_guidance_fragments_stay_slot_aligned(fresh_obs):
    c = cfg(archive_size=8)
    s = ScheduleSearch(c, n_devices=1)
    st = history(11)  # around a ring of 8
    ingest_history(s, st, IngestParams(H=H, guidance=True))
    assert s.guidance is not None and s.guidance.runs_observed == 11
    for i in range(3, 11):  # the runs the ring still holds
        enc, enc_rt = te.encode_trace_views(st.get_stored_history(i), H=H)
        slot = i % 8
        assert np.array_equal(s.guidance_feats[slot],
                              s._guidance_feats_of(enc_rt, enc))
        assert np.abs(s.archive[slot] - one_run_row(
            enc_rt, s.pairs, c.weights.tau)).max() <= ROW_TOL
    feats, labels = s.labeled_archive()
    assert feats.shape == (8, K + s.guidance_feats.shape[1])
    assert len(labels) == 8


# -- (i) long traces ---------------------------------------------------------


def test_a_long_trace_embeds_blockwise_to_the_same_row():
    s = ScheduleSearch(cfg(), n_devices=1)
    long = enc_of(sch.LONG_TRACE_THRESHOLD + 60, 5)
    L = long.hint_ids.shape[0]
    assert L > sch.LONG_TRACE_THRESHOLD
    tau = s.cfg.weights.tau
    embed = sch.batched_trace_features(tau, H)
    shaped = [jax.ShapeDtypeStruct((EMBED_CHUNK, L), d)
              for d in (np.int32, np.float32, bool)]
    text = str(jax.make_jaxpr(embed)(
        *shaped, jax.ShapeDtypeStruct((K, 2), np.int32)))
    assert "scan" in text  # the blockwise branch, chosen per static L
    rows = s._embed([long, enc_of(17, 6)])
    # the dense path's row of the same trace
    trace = sch.TraceArrays(jnp.asarray(long.hint_ids),
                            jnp.asarray(long.arrival),
                            jnp.asarray(long.mask))
    first = sch.first_occurrence(
        sch.release_times(jnp.zeros((H,), jnp.float32), trace), trace, H)
    dense = np.asarray(sch.precedence_features(
        first, jnp.asarray(s.pairs), tau))
    assert np.abs(rows[0] - dense).max() <= ROW_TOL
    assert np.abs(rows[0] - one_run_row(long, s.pairs, tau)).max() \
        <= ROW_TOL


# -- the surrogate push takes the same rows ----------------------------------


def test_surrogate_examples_come_from_one_batched_call(fresh_obs):
    from namazu_tpu.models.ingest import _push_surrogate_examples

    class Client:
        def push(self, **kw):
            self.pushed = kw

    s = ScheduleSearch(cfg(), n_devices=1)
    st = history(9)
    encoded = []
    for i in range(9):
        enc, enc_rt = te.encode_trace_views(st.get_stored_history(i), H=H)
        encoded.append((enc, enc_rt, st.is_successful(i), None))
    client = Client()
    _push_surrogate_examples(client, s, encoded)
    assert embed_calls() == 1
    examples = client.pushed["examples"]
    assert [e["label"] for e in examples] == [
        0.0 if ok else 1.0 for _, _, ok, _ in encoded]
    for e, (_, enc_rt, _, _) in zip(examples, encoded):
        assert e["digest"] == trace_digest(enc_rt)
        assert np.abs(np.asarray(e["feats"], np.float32) - one_run_row(
            enc_rt, s.pairs, s.cfg.weights.tau)).max() <= ROW_TOL


# -- (h) events, L groups and the scorer branch (zk2212-zab5) ----------------


@pytest.mark.parametrize("short, long, groups", [
    (10, 0, 1), (7, 3, 2)])
def test_ingest_counts_events_and_length_groups(short, long, groups,
                                                fresh_obs):
    """``nmz_ingest_events_total`` and the ``ingest_encode`` row's
    ``events=`` count the events of the stored runs a request encoded;
    the ``ingest_embed`` row's ``groups=`` the padded lengths among
    them, all embedded at the search's length class by ONE program:
    ``pieces=`` (device calls) is ``ceil(N / EMBED_CHUNK)`` whatever
    the lengths."""
    s = ScheduleSearch(cfg(archive_size=256), n_devices=1)
    st = history(short + long, long_from=short)
    events = 17 * short + 140 * long
    for request in (1, 2):
        ingest_history(s, st, IngestParams(H=H))
        assert obs.metrics.registry().value(spans.INGEST_EVENTS) \
            == request * events
    rows = fresh_obs.since(0)["rows"]
    encode = [r[7] for r in rows if r[1] == "ingest_encode"]
    assert encode == [{"pieces": short + long, "events": events,
                       "cached": 0}] * 2
    embed = [r[7] for r in rows if r[1] == "ingest_embed"]
    assert [e["groups"] for e in embed] == [groups, groups]
    assert [e["pieces"] for e in embed] == [1, 1]
    assert s.length_class == te._auto_length(140 if long else 17)


@pytest.mark.parametrize("n_events, scorer", [
    (17, "dense"), (sch.LONG_TRACE_THRESHOLD, "dense"),
    (sch.LONG_TRACE_THRESHOLD + 1, "blockwise")])
@pytest.mark.parametrize("fused_chunk", [16, 1])
def test_an_evolve_is_counted_under_the_branch_its_step_compiled(
        n_events, scorer, fused_chunk, fresh_obs):
    """``nmz_evolve_requests_total{scorer}``: one per completed evolve
    — however many dispatches it took — by ``scorer_branch`` of the
    references' padded length, the rule ``_genome_features`` itself
    dispatches on."""
    s = ScheduleSearch(cfg(fused_chunk=fused_chunk), n_devices=1)
    refs = [enc_of(n_events, 1), enc_of(17, 2)]
    L = max(e.hint_ids.shape[0] for e in refs)
    assert sch.scorer_branch(L) == scorer
    assert sch.scorer_branch(L, order_mode=True) == "order"
    for _ in range(2):
        s.run(refs, generations=2)
    reg = obs.metrics.registry()
    other = ({"dense", "blockwise"} - {scorer}).pop()
    assert reg.value(spans.EVOLVE_REQUESTS, scorer=scorer) == 2
    assert not reg.value(spans.EVOLVE_REQUESTS, scorer=other)
    # delay mode at either length: first occurrences from per-trace
    # tables, one count per evolve
    assert reg.value(spans.EVOLVE_TABLE_REQUESTS) == 2
    evolves = [r for r in fresh_obs.since(0)["rows"] if r[1] == "evolve"]
    assert len(evolves) == 2


# -- reorder mode (PR 32): own length quantum, a branch of its own ----------


def order_weights():
    from namazu_tpu.models.search import make_score_weights

    return make_score_weights(
        release_mode="reorder", w_novelty=0.3, w_bug=1.0,
        w_delay_cost=0.0005, w_fault_cost=0.05, tau=0.005,
        reorder_gap=0.002, reorder_window=0.01)


@pytest.mark.parametrize("fused_chunk", [16, 1])
def test_an_order_mode_evolve_is_counted_as_order(fused_chunk, fresh_obs):
    """In reorder mode the step compiles the order branch at every
    length, and ``nmz_evolve_requests_total{scorer}`` says so."""
    s = ScheduleSearch(cfg(fused_chunk=fused_chunk,
                           weights=order_weights()), n_devices=1)
    refs = [enc_of(sch.LONG_TRACE_THRESHOLD + 1, 1), enc_of(17, 2)]
    for _ in range(2):
        s.run(refs, generations=2)
    reg = obs.metrics.registry()
    assert reg.value(spans.EVOLVE_REQUESTS, scorer="order") == 2
    for other in ("dense", "blockwise"):
        assert not reg.value(spans.EVOLVE_REQUESTS, scorer=other)
    assert not reg.value(spans.EVOLVE_TABLE_REQUESTS)  # per-event branch


@pytest.mark.parametrize("n_events, explicit_L, want_L, cut", [
    (17, 0, 128, 0),       # its own quantum, not the cap
    (140, 0, 256, 0),
    (300, 0, 256, 44),     # over the cap: still cut at the cap
    (17, 512, 512, 0),     # an explicit trace_length pads to itself
    (600, 512, 512, 88)])
def test_a_reorder_run_is_encoded_at_its_own_quantum(
        n_events, explicit_L, want_L, cut, fresh_obs):
    """``order_mode_max_l`` is the cap over which a run is cut (the
    benchmark's reference refuses such a history), not the length every
    stored run is padded to."""
    s = ScheduleSearch(cfg(weights=order_weights()), n_devices=1)
    storage = ListStorage([(make_run(1, n_events), True),
                           (make_run(2, 17), False)])
    refs = ingest_history(s, storage, IngestParams(
        H=H, L=explicit_L, release_mode="reorder",
        order_mode_max_l=256, reference_mode="recent"))
    assert [r.hint_ids.shape[0] for r in refs] == [want_L]
    assert refs[0].truncated == cut
    assert int(refs[0].mask.sum()) == min(n_events, want_L)
