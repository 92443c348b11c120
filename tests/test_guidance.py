"""Guidance plane (ISSUE 12): relation-coverage signatures, the
CoverageMap's novelty accounting, coverage-guided pick + mutation bias,
ingest/knowledge wiring with the degradation contract, determinism of
the signature derivation, the obs_enabled=false blind degrade, the
``tools coverage`` / ``tools ab-guided`` CLIs, and the seeded
guided-vs-blind A/B acceptance."""

import json
import os
import socket

import numpy as np
import pytest

from namazu_tpu import obs
from namazu_tpu.guidance import (
    CoverageMap,
    GUIDANCE_DIMS,
    bucket_sequence_from_docs,
    bucket_sequence_from_trace,
    dag_shape_features,
    hint_bucket,
    occurrence_index,
    pair_bit,
    relation_pairs,
    reverse_signature_bits,
    signature_bits,
)
from namazu_tpu.obs import metrics, recorder, spans
from namazu_tpu.obs.metrics import MetricsRegistry
from namazu_tpu.ops import trace_encoding as te
from namazu_tpu.signal import PacketEvent
from namazu_tpu.signal.action import EventAcceptanceAction
from namazu_tpu.storage.base import HistoryStorage
from namazu_tpu.utils.trace import SingleTrace


@pytest.fixture(autouse=True)
def fresh_obs():
    old_reg = metrics.set_registry(MetricsRegistry())
    metrics.configure(True)
    old_rec = recorder.set_recorder(recorder.FlightRecorder())
    yield
    metrics.set_registry(old_reg)
    metrics.configure(True)
    recorder.set_recorder(old_rec)


H = K = 16


class FakeStorage(HistoryStorage):
    def __init__(self, runs):
        self.runs = runs

    def nr_stored_histories(self):
        return len(self.runs)

    def get_stored_history(self, i):
        return self.runs[i][0]

    def is_successful(self, i):
        return self.runs[i][1]

    def get_metadata(self, i):
        return {"hint_space": te.HINT_SPACE}


def make_trace(seed, fail_delay=0.0, n=10):
    rng = np.random.RandomState(seed)
    t, now = SingleTrace(), 1000.0
    for i in range(n):
        ev = PacketEvent.create(f"n{rng.randint(3)}", "a", "b",
                                hint=f"m{i % 5}")
        a = EventAcceptanceAction.for_event(ev)
        now += float(rng.rand() * 1e-3)
        a.event_arrived = now
        a.triggered_time = now + fail_delay * ((i % 3) / 3.0)
        t.append(a)
    return t


def make_search(surrogate_topk=4, guidance=False):
    from namazu_tpu.models.search import ScheduleSearch, SearchConfig

    s = ScheduleSearch(SearchConfig(
        H=H, K=K, population=16, archive_size=16, failure_size=8,
        surrogate_topk=surrogate_topk), n_devices=1)
    if guidance:
        s.enable_guidance()
    return s


# -- signature derivation (determinism satellite) -------------------------


def test_signature_pure_and_direction_sensitive():
    seq = [1, 2, 3, 1, 2, 3]
    a = signature_bits(seq)
    assert np.array_equal(a, signature_bits(list(seq)))
    assert np.array_equal(a, signature_bits(np.asarray(seq)))
    # direction is part of the relation identity
    assert not np.array_equal(a, signature_bits(seq[::-1]))
    # the reverse signature is where each relation's FLIP would land:
    # for a repeat-free sequence, executing it reversed covers exactly
    # those bits (with repeats the occurrence indices reassign, so the
    # identity only holds bucket-occurrence-wise, not sequence-wise)
    distinct = [4, 9, 2, 7]
    rev = reverse_signature_bits(distinct)
    fwd_of_reversed = signature_bits(distinct[::-1])
    assert set(int(b) for b in rev) == set(int(b)
                                           for b in fwd_of_reversed)
    assert list(occurrence_index(seq)) == [0, 0, 0, 1, 1, 1]
    # scalar pair_bit agrees with the vectorized signature
    bits = {int(b) for b in signature_bits(seq, width=512)}
    for p in relation_pairs(seq):
        assert pair_bit(*p, width=512) in bits


def test_signature_bit_identical_across_doc_replays():
    """The satellite: a pure function of the flight-recorder docs —
    two parses/derivations of the same recorded run are bit-identical,
    regardless of dict key order."""
    docs = [
        {"event": f"u{i}", "entity": f"e{i % 2}", "hint": f"h{i % 3}",
         "event_class": "PacketEvent",
         "t": {"intercepted": i * 1.0, "dispatched": 10.0 - i}}
        for i in range(8)
    ]
    text = "\n".join(json.dumps(d, sort_keys=(i % 2 == 0))
                     for i, d in enumerate(docs))
    parsed_a = [json.loads(line) for line in text.splitlines()]
    parsed_b = [json.loads(line) for line in reversed(
        text.splitlines())]
    # dispatch STAMPS define the order, not doc order on the wire
    seq_a = bucket_sequence_from_docs(parsed_a, H)
    seq_b = bucket_sequence_from_docs(parsed_b, H)
    assert np.array_equal(seq_a, seq_b)
    assert np.array_equal(signature_bits(seq_a), signature_bits(seq_b))
    # hint-less docs fall back to class:entity, deterministically
    bare = [{"event": "x", "entity": "e0", "event_class": "PacketEvent",
             "t": {"dispatched": 1.0}}]
    assert bucket_sequence_from_docs(bare, H)[0] == hint_bucket(
        "PacketEvent:e0", H)


def test_signature_from_recorded_pipeline_replays(tmp_path):
    """End to end over a REAL recorded run (the chaos harness's
    seeded pipeline): deriving twice from the dump is bit-identical,
    and the seeded-divergent second run covers different relations."""
    from namazu_tpu.chaos.harness import record_divergent_pair
    from namazu_tpu.obs import causality

    text_a, text_b = record_divergent_pair(str(tmp_path), seed=5,
                                           events=4)
    docs_a1, _, _ = causality.split_ndjson(text_a)
    docs_a2, _, _ = causality.split_ndjson(text_a)
    docs_b, _, _ = causality.split_ndjson(text_b)
    bits_a1 = signature_bits(bucket_sequence_from_docs(docs_a1, 256))
    bits_a2 = signature_bits(bucket_sequence_from_docs(docs_a2, 256))
    assert np.array_equal(bits_a1, bits_a2)
    bits_b = signature_bits(bucket_sequence_from_docs(docs_b, 256))
    assert not np.array_equal(bits_a1, bits_b)


def test_dag_shape_features_shape_and_determinism():
    buckets = np.asarray([1, 2, 3, 4, 1, 2])
    tp = np.arange(6.0)
    td = np.asarray([0.0, 2.0, 1.0, 3.0, 5.0, 4.0])
    f = dag_shape_features(buckets, tp, td)
    assert f.shape == (GUIDANCE_DIMS,) and f.dtype == np.float32
    assert np.array_equal(f, dag_shape_features(buckets, tp, td))
    # identical orders -> zero crossing/displacement scalars
    flat = dag_shape_features(buckets, tp, tp)
    assert flat[GUIDANCE_DIMS - 4] == 0.0
    assert flat[GUIDANCE_DIMS - 3] == 0.0
    # a reordering shows up in the crossing scalar
    assert f[GUIDANCE_DIMS - 4] > 0.0
    assert len(dag_shape_features(np.asarray([]), np.asarray([]),
                                  np.asarray([]))) == GUIDANCE_DIMS


# -- CoverageMap ----------------------------------------------------------


def test_coverage_map_novelty_accounting():
    m = CoverageMap(H=8, width=4096)
    d1 = m.observe([1, 2, 3, 1])
    assert d1.interesting and d1.new_bits > 0 and d1.flipped == 0
    d2 = m.observe([1, 2, 3, 1])
    assert not d2.interesting and d2.new_bits == 0
    # the FLIP of a known relation is novel (first-covers + flips)
    d3 = m.observe([3, 2, 1, 1])
    assert d3.interesting and d3.flipped > 0
    assert m.runs_observed == 3
    assert m.curve == sorted(m.curve)  # cumulative, monotone
    assert 0 < m.occupancy() < 1


def test_coverage_map_gain_frontier_and_bias():
    m = CoverageMap(H=8, width=4096)
    m.observe([1, 2, 3])
    assert m.predicted_gain([1, 2, 3]) == 0.0
    assert m.predicted_gain([5, 6, 7]) == 1.0
    assert m.predicted_gain([]) == 0.0
    rows = m.one_sided()
    assert rows and all(r["flip_score"] > 0 for r in rows)
    assert m.one_sided_count() == len(rows)
    assert m.one_sided(top=1) == rows[:1]
    bias = m.mutation_bias(max_boost=4.0)
    assert bias.shape == (8,) and bias.min() >= 1.0
    assert bias.max() == pytest.approx(4.0)
    # participating buckets are the boosted ones
    hot = {b for r in rows for b in r["buckets"]}
    for b in range(8):
        assert (bias[b] > 1.0) == (b in hot)
    # covering the flips empties the frontier and flattens the bias
    m.observe([3, 2, 1])
    assert np.array_equal(CoverageMap(H=8).mutation_bias(),
                          np.ones(8, np.float32))


def test_coverage_map_merge_bits_warm_start():
    m = CoverageMap(H=8, width=128)
    fresh = m.merge_bits([1, 5, 5, 127, 999, -3])
    assert fresh == 3  # dedupe + out-of-range dropped
    assert m.merge_bits([1, 5]) == 0
    assert m.covered() == 3
    # fleet-covered relations no longer count as candidate gain
    bits = signature_bits([1, 2], width=128)
    m2 = CoverageMap(H=8, width=128)
    m2.merge_bits([int(b) for b in bits])
    assert m2.predicted_gain([1, 2]) == 0.0


def test_coverage_map_pair_overflow_counted():
    m = CoverageMap(H=64, width=4096, max_pairs=4)
    m.observe(list(range(10)))
    assert m.pair_overflow > 0
    assert len(m._pairs) == 4


# -- GA mutation bias -----------------------------------------------------


def test_ga_bias_ones_is_bit_identical_and_boost_differs():
    import jax
    import jax.numpy as jnp

    from namazu_tpu.models.ga import GAConfig, ga_generation, \
        init_population

    cfg = GAConfig()
    key = jax.random.PRNGKey(0)
    pop = init_population(jax.random.PRNGKey(1), 16, 8, cfg)
    fit = jnp.arange(16.0)
    a = ga_generation(key, pop, fit, cfg)
    b = ga_generation(key, pop, fit, cfg, delay_bias=jnp.ones((8,)))
    assert np.array_equal(np.asarray(a.delays), np.asarray(b.delays))
    assert np.array_equal(np.asarray(a.faults), np.asarray(b.faults))
    c = ga_generation(key, pop, fit, cfg,
                      delay_bias=jnp.full((8,), 4.0))
    assert not np.array_equal(np.asarray(a.delays),
                              np.asarray(c.delays))
    # the fault half is NOT biased (ordering coverage says nothing
    # about which events exist)
    assert np.array_equal(np.asarray(a.faults), np.asarray(c.faults))


def test_island_step_threads_mutation_bias():
    import jax
    import jax.numpy as jnp

    from namazu_tpu.models.ga import GAConfig
    from namazu_tpu.ops.schedule import ScoreWeights, TraceArrays
    from namazu_tpu.parallel.islands import (
        init_island_state,
        make_fused_island_step,
    )
    from namazu_tpu.parallel.mesh import make_mesh

    cfg = GAConfig()
    fused = make_fused_island_step(make_mesh(1), cfg, ScoreWeights(),
                                   migrate_k=2, generations=1)

    def step(*args):
        # the step donates its state: a fresh one (same key) per call
        state = init_island_state(jax.random.PRNGKey(2), 8, 8, cfg)
        return fused(state, *args)[0]

    trace = TraceArrays(jnp.zeros((4,), jnp.int32), jnp.arange(4.0),
                        jnp.ones((4,), bool))
    args = (jax.random.PRNGKey(0), trace, jnp.zeros((4, 2), jnp.int32),
            jnp.full((4, 4), 0.5), jnp.full((4, 4), 0.5))
    s_none = step(*args)
    s_ones = step(*args, None, None, jnp.ones((8,)))
    assert np.array_equal(np.asarray(s_none.pop.delays),
                          np.asarray(s_ones.pop.delays))
    s_hot = step(*args, None, None, jnp.full((8,), 4.0))
    assert not np.array_equal(np.asarray(s_none.pop.delays),
                              np.asarray(s_hot.pop.delays))


# -- search integration ---------------------------------------------------


def test_candidate_guidance_ranks_reordering_tables():
    s = make_search(guidance=True)
    st = FakeStorage([(make_trace(0), True)])
    from namazu_tpu.models.ingest import IngestParams, ingest_history

    refs = ingest_history(s, st, IngestParams(H=H, guidance=True))
    assert refs
    zero = np.zeros((H,), np.float32)
    shuffle = np.zeros((H,), np.float32)
    # delay half the buckets far enough to invert the ~1ms arrivals
    shuffle[::2] = 0.05
    gains, frags = s._candidate_guidance(
        np.stack([zero, shuffle]), refs)
    # the zero table replays the natural (observed) order: no gain;
    # the reordering table is predicted to cover new relations
    assert gains[0] == 0.0
    assert gains[1] > 0.0
    assert frags.shape == (2, GUIDANCE_DIMS)


def test_guided_run_smoke_and_archive_widening():
    s = make_search(guidance=True)
    st = FakeStorage([(make_trace(0), True),
                      (make_trace(1, 0.05), False),
                      (make_trace(2), True)])
    from namazu_tpu.models.ingest import IngestParams, ingest_history

    refs = ingest_history(s, st, IngestParams(H=H, guidance=True))
    assert s.guidance is not None and s.guidance.runs_observed == 3
    best = s.run(refs, generations=2)
    assert np.isfinite(best.fitness)
    feats, labels = s.labeled_archive()
    assert feats.shape[1] == K + GUIDANCE_DIMS
    assert s._surrogate_input_dims() == K + GUIDANCE_DIMS
    # the relation-coverage gauge was published with the scenario label
    val = metrics.registry().value(spans.RELATION_COVERAGE,
                                   scenario="local")
    assert val is not None and val > 0
    assert metrics.registry().value(spans.RELATION_ONE_SIDED,
                                    scenario="local") > 0


def test_ingest_coverage_is_deterministic():
    st = FakeStorage([(make_trace(0), True), (make_trace(1, 0.05),
                                              False)])
    from namazu_tpu.models.ingest import IngestParams, ingest_history

    maps = []
    for _ in range(2):
        s = make_search(guidance=True)
        ingest_history(s, st, IngestParams(H=H, guidance=True))
        maps.append(s.guidance)
    assert maps[0].bits_list() == maps[1].bits_list()
    assert maps[0].one_sided() == maps[1].one_sided()


def test_repeated_ingest_rebuilds_map_not_accumulates():
    """A persistent (sidecar-cached) search serving repeated requests
    re-feeds the whole history each time; the map must rebuild fresh,
    not double-observe — runs_observed tracks the HISTORY, per
    ingest."""
    from namazu_tpu.models.ingest import IngestParams, ingest_history

    st = FakeStorage([(make_trace(0), True),
                      (make_trace(1, 0.05), False)])
    s = make_search(guidance=True)
    for _ in range(3):
        ingest_history(s, st, IngestParams(H=H, guidance=True))
    assert s.guidance.runs_observed == 2
    assert len(s.guidance.curve) == 2


def test_guidance_off_search_is_unchanged():
    """Without a map, the pick path and the mutation kernel are the
    pre-guidance ones — same tables out of the same seed."""
    from namazu_tpu.models.ingest import IngestParams, ingest_history

    st = FakeStorage([(make_trace(0), True),
                      (make_trace(1, 0.05), False)])
    tables = []
    for _ in range(2):
        s = make_search(guidance=False)
        refs = ingest_history(s, st, IngestParams(H=H))
        best = s.run(refs, generations=2)
        tables.append(best.delays)
        assert s.guidance is None and s.guidance_feats is None
    assert np.array_equal(tables[0], tables[1])


def test_midlife_guidance_toggle_retrains_surrogate():
    """Guidance wired onto a LIVE search that already trained a
    K-width surrogate (obs toggled on between rounds): the widened
    feature space must invalidate the old model + unfragmented archive
    rows — the next round retrains at K+G instead of shape-crashing."""
    from namazu_tpu.models.ingest import IngestParams, ingest_history

    st = FakeStorage([(make_trace(i, 0.05 * (i % 2)), i % 2 == 0)
                      for i in range(8)])
    s = make_search(guidance=False)
    refs = ingest_history(s, st, IngestParams(H=H))
    s.run(refs, generations=2)
    assert s._surrogate is not None  # trained at width K
    refs = ingest_history(s, st, IngestParams(H=H, guidance=True))
    assert s.guidance is not None
    best = s.run(refs, generations=2)  # pre-fix: jax shape error
    assert np.isfinite(best.fitness)
    feats, _ = s.labeled_archive()
    assert feats.shape[1] == K + GUIDANCE_DIMS


def test_checkpoint_roundtrip_and_pre_guidance_drop(tmp_path):
    from namazu_tpu.models.ingest import IngestParams, ingest_history

    st = FakeStorage([(make_trace(0), True),
                      (make_trace(1, 0.05), False)])
    s = make_search(guidance=True)
    ingest_history(s, st, IngestParams(H=H, guidance=True))
    ck = str(tmp_path / "g.npz")
    s.save(ck)
    s2 = make_search(guidance=True)
    s2.load(ck)
    assert np.array_equal(s2.guidance_feats, s.guidance_feats)
    assert s2._archive_n == s._archive_n
    # a PRE-guidance checkpoint loaded into a guided search drops the
    # archive (fragments would be zero-garbage); re-ingest refills it
    s_off = make_search(guidance=False)
    ingest_history(s_off, st, IngestParams(H=H))
    ck2 = str(tmp_path / "off.npz")
    s_off.save(ck2)
    s3 = make_search(guidance=True)
    s3.load(ck2)
    assert s3._archive_n == 0
    ingest_history(s3, st, IngestParams(H=H, guidance=True))
    assert s3._archive_n > 0


# -- policy wiring + the obs_enabled=false degrade ------------------------


def _policy(tmp_path, extra=None):
    from namazu_tpu.policy import create_policy
    from namazu_tpu.utils.config import Config

    param = {
        "max_interval": 30, "generations": 2, "population": 16,
        "hint_buckets": H, "feature_pairs": K, "seed": 3,
        "search_on_start": False,
        "checkpoint": str(tmp_path / "search.npz"),
    }
    param.update(extra or {})
    policy = create_policy("tpu_search")
    policy.load_config(Config({"explore_policy_param": param}))
    return policy


def test_policy_guidance_knobs_and_obs_gate(tmp_path):
    pol = _policy(tmp_path, {"guidance": True, "guidance_bonus": 0.7,
                             "guidance_bitmap_width": 1024})
    assert pol.guidance_enabled and pol.guidance_bonus == 0.7
    assert pol._guidance_active()
    search = pol._build_search()
    assert search.guidance is not None
    assert search.guidance.width == 1024
    assert search.cfg.guidance_bonus == 0.7
    # the sidecar/ingest params carry the active knobs
    assert pol._search_params()["guidance"] is True
    assert pol._ingest_params().guidance is True
    # obs_enabled=false: guidance degrades to the pre-guidance blind
    # search — no map, no bias, no widened features — not a crash
    metrics.configure(False)
    try:
        assert not pol._guidance_active()
        blind = pol._build_search()
        assert blind.guidance is None and blind.guidance_feats is None
        assert pol._search_params()["guidance"] is False
        assert pol._ingest_params().guidance is False
    finally:
        metrics.configure(True)


def test_policy_guidance_default_off(tmp_path):
    pol = _policy(tmp_path)
    assert not pol.guidance_enabled
    search = pol._build_search()
    assert search.guidance is None


def test_sidecar_builder_wires_guidance():
    from namazu_tpu.models.search import build_search_from_params

    base = {"H": H, "K": K, "population": 16, "seed": 1}
    s = build_search_from_params(dict(base, guidance=True,
                                      guidance_width=512))
    assert s.guidance is not None and s.guidance.width == 512
    s2 = build_search_from_params(base)
    assert s2.guidance is None


# -- knowledge wire (v2 coverage extension) -------------------------------


def test_knowledge_coverage_roundtrip_and_persistence(tmp_path):
    from namazu_tpu.knowledge import KnowledgeService

    pool = str(tmp_path / "pool")
    svc = KnowledgeService(pool)
    assert svc.VERSION >= 2  # v3 added triage dossiers (test_triage.py)
    push = svc.handle({"op": "pool_push", "tenant": "a",
                       "scenario": "sc",
                       "coverage": {"H": 16, "w": 128, "win": 8,
                                    "bits": [1, 5, 9]}})
    assert push["ok"]
    # union on re-push from another tenant
    svc.handle({"op": "pool_push", "tenant": "b", "scenario": "sc",
                "coverage": {"H": 16, "w": 128, "win": 8,
                             "bits": [5, 11]}})
    pull = svc.handle({"op": "pool_pull", "scenario": "sc", "H": 0,
                       "max_entries": 0,
                       "coverage_space": {"H": 16, "w": 128, "win": 8}})
    assert pull["coverage"]["bits"] == [1, 5, 9, 11]
    # space mismatch serves nothing (bits don't translate)
    miss = svc.handle({"op": "pool_pull", "scenario": "sc", "H": 0,
                       "max_entries": 0,
                       "coverage_space": {"H": 16, "w": 256, "win": 8}})
    assert "coverage" not in miss
    # v1-style pull (no coverage_space) is byte-compatible
    v1 = svc.handle({"op": "pool_pull", "scenario": "sc", "H": 0,
                     "max_entries": 0})
    assert "coverage" not in v1
    # malformed pushes cost the push, never the stored state
    svc.handle({"op": "pool_push", "tenant": "a", "scenario": "sc",
                "coverage": {"H": 16, "w": 128, "win": 8,
                             "bits": [99999]}})
    svc.handle({"op": "pool_push", "tenant": "a", "scenario": "sc",
                "coverage": {"w": "banana"}})
    # a DIFFERENT space accumulates side by side — it must never wipe
    # the fleet's frontier in the original space
    svc.handle({"op": "pool_push", "tenant": "c", "scenario": "sc",
                "coverage": {"H": 16, "w": 256, "win": 8,
                             "bits": [7]}})
    again = svc.handle({"op": "pool_pull", "scenario": "sc", "H": 0,
                        "max_entries": 0,
                        "coverage_space": {"H": 16, "w": 128,
                                           "win": 8}})
    assert again["coverage"]["bits"] == [1, 5, 9, 11]
    stats = svc.handle({"op": "stats"})
    assert stats["coverage"]["sc@16x128x8"]["covered_bits"] == 4
    assert stats["coverage"]["sc@16x256x8"]["covered_bits"] == 1
    svc.close()
    # crash-safe persistence: a restarted service serves the same bits
    svc2 = KnowledgeService(pool)
    pull2 = svc2.handle({"op": "pool_pull", "scenario": "sc", "H": 0,
                         "max_entries": 0,
                         "coverage_space": {"H": 16, "w": 128,
                                            "win": 8}})
    assert pull2["coverage"]["bits"] == [1, 5, 9, 11]
    svc2.close()


def test_knowledge_coverage_client_and_ingest_e2e(tmp_path):
    from namazu_tpu.knowledge import (
        KnowledgeClient,
        KnowledgeService,
    )
    from namazu_tpu.models.ingest import IngestParams, ingest_history
    from namazu_tpu.sidecar import SidecarServer

    svc = KnowledgeService(str(tmp_path / "pool"))
    srv = SidecarServer(port=0, knowledge=svc)
    srv.start()
    addr = f"127.0.0.1:{srv.port}"
    try:
        st = FakeStorage([(make_trace(0), True),
                          (make_trace(1, 0.05), False)])
        # campaign A ingests with guidance: its coverage lands pooled
        sA = make_search(guidance=True)
        ingest_history(sA, st, IngestParams(
            H=H, guidance=True, knowledge=addr,
            knowledge_tenant="A", knowledge_scenario="gsc"))
        bits_a = sA.guidance.bits_list()
        assert bits_a
        client = KnowledgeClient(addr, tenant="probe", scenario="gsc")
        pulled = client.pull_coverage(sA.guidance.H,
                                      sA.guidance.width,
                                      sA.guidance.window)
        assert pulled == bits_a
        # a COLD campaign with a DIFFERENT history warm-starts its
        # frontier: fleet-covered relations are not novel to it
        sB = make_search(guidance=True)
        ingest_history(sB, FakeStorage([(make_trace(9), True)]),
                       IngestParams(
                           H=H, guidance=True, knowledge=addr,
                           knowledge_tenant="B",
                           knowledge_scenario="gsc"))
        assert set(bits_a) <= set(sB.guidance.bits_list())
        installs = metrics.registry().value(
            spans.KNOWLEDGE_WARMSTART, kind="coverage")
        assert installs is not None and installs > 0
        client.close()
    finally:
        srv.shutdown()


def test_knowledge_outage_degrades_to_local_coverage(tmp_path, caplog):
    """The degradation contract (satellite): a dead service costs one
    warning and nothing else — local-only coverage, no exception into
    campaign code."""
    import logging

    from namazu_tpu.models.ingest import IngestParams, ingest_history

    # a port with nothing listening (bind-then-close reserves one)
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    dead_addr = f"127.0.0.1:{probe.getsockname()[1]}"
    probe.close()
    st = FakeStorage([(make_trace(0), True),
                      (make_trace(1, 0.05), False)])
    s = make_search(guidance=True)
    with caplog.at_level(logging.WARNING,
                         logger="namazu_tpu.knowledge.client"):
        refs = ingest_history(s, st, IngestParams(
            H=H, guidance=True, knowledge=dead_addr,
            knowledge_tenant="out", knowledge_scenario="osc"))
    assert refs  # the ingest itself succeeded
    assert s.guidance.runs_observed == 2  # local coverage intact
    warnings = [r for r in caplog.records
                if "degrading to local-only" in r.getMessage()]
    assert len(warnings) == 1  # one warning, then the cooldown


# -- analytics + report + CLI ---------------------------------------------


def _build_ab_storage(tmp_path):
    from namazu_tpu.guidance.ab import run_ab

    rep = run_ab(str(tmp_path / "ab"), seed=11, runs=24)
    return rep, str(tmp_path / "ab")


def test_analytics_relation_curve_fields(tmp_path):
    from namazu_tpu.obs import analytics
    from namazu_tpu.storage import new_storage

    st = new_storage("naive", str(tmp_path / "st"))
    st.create()
    for i in range(6):
        st.create_new_working_dir()
        st.record_new_trace(make_trace(i % 2, fail_delay=0.01 * (i % 2)))
        st.record_result(True, 1.0)
    cov = analytics.coverage_stats(st, window=2)
    assert cov["relation_width"] == analytics.RELATION_WIDTH
    assert cov["relation_bits"] > 0
    assert len(cov["relation_curve"]) == cov["runs"]
    assert cov["relation_curve"] == sorted(cov["relation_curve"])
    assert len(cov["relation_novelty_per_window"]) == 3
    # two distinct timing realizations repeating -> relations saturate
    assert cov["relation_saturated"]
    assert cov["relation_frontier_bits"] >= 0
    # gauges published on payload computation
    analytics.compute_payload(storage=st, window=2)
    assert metrics.registry().value(spans.RELATION_COVERAGE,
                                    scenario="storage") is not None
    # cache: second pass memoized per (dir, index)
    cached = [k for k in analytics._relation_cache
              if k[0] == st.dir]
    assert len(cached) == 6


def test_report_renders_relation_section(tmp_path):
    from namazu_tpu.obs import analytics, report
    from namazu_tpu.storage import new_storage

    st = new_storage("naive", str(tmp_path / "st"))
    st.create()
    st.create_new_working_dir()
    st.record_new_trace(make_trace(0))
    st.record_result(True, 1.0)
    text = report.render_markdown(
        analytics.compute_payload(storage=st, publish=False))
    assert "- relation coverage:" in text
    assert "- relation-coverage growth:" in text
    assert "- relation saturated:" in text


def test_tools_coverage_cli(tmp_path, capsys):
    from namazu_tpu.cli import cli_main
    from namazu_tpu.storage import new_storage

    st_dir = str(tmp_path / "st")
    st = new_storage("naive", st_dir)
    st.create()
    for seed in (0, 1):
        st.create_new_working_dir()
        st.record_new_trace(make_trace(seed, fail_delay=0.01 * seed))
        st.record_result(True, 1.0)
    st.close()
    assert cli_main(["tools", "coverage", st_dir,
                     "--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == "nmz-coverage-v1"
    assert doc["stats"]["covered_bits"] > 0
    assert doc["stats"]["runs_observed"] == 2
    assert isinstance(doc["one_sided_top"], list)
    assert doc["one_sided_top"][0]["flip_score"] >= \
        doc["one_sided_top"][-1]["flip_score"]
    # markdown face renders the frontier table
    out = str(tmp_path / "cov.md")
    assert cli_main(["tools", "coverage", st_dir, "--out", out]) == 0
    capsys.readouterr()
    with open(out) as f:
        text = f.read()
    assert "# Relation coverage" in text
    assert "Top uncovered relations" in text


def test_tools_coverage_cli_url(tmp_path, capsys):
    """--url reads the relation section of a live /analytics payload."""
    from namazu_tpu.cli import cli_main
    from namazu_tpu.obs import analytics
    from namazu_tpu.orchestrator import Orchestrator
    from namazu_tpu.policy import create_policy
    from namazu_tpu.storage import new_storage
    from namazu_tpu.utils.config import Config

    st_dir = str(tmp_path / "st")
    st = new_storage("naive", st_dir)
    st.create()
    st.create_new_working_dir()
    st.record_new_trace(make_trace(0))
    st.record_result(True, 1.0)
    st.close()
    analytics.set_storage_dir(st_dir)
    orc = Orchestrator(Config({"rest_port": 0, "run_id": "cov-url"}),
                       create_policy("dumb"))
    orc.start()
    try:
        port = orc.hub.endpoint("rest").port
        assert cli_main(["tools", "coverage", "--url",
                         f"http://127.0.0.1:{port}",
                         "--format", "json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["stats"]["covered_bits"] > 0
        assert doc["stats"]["runs_observed"] == 1
        assert "one_sided_top" not in doc  # aggregates only over --url
    finally:
        orc.shutdown()
        analytics.set_storage_dir(None)


# -- the A/B acceptance (tentpole + satellite) ----------------------------


def test_ab_guided_acceptance_full(tmp_path):
    """The CI criteria at the CI budget: >= 1.25x relation coverage,
    curve dominance, time-to-first-failure no worse — pinned seed."""
    from namazu_tpu.guidance.ab import run_ab

    rep = run_ab(str(tmp_path / "ab"), seed=11, runs=72)
    assert rep["ok"], rep
    assert rep["coverage_ratio"] >= 1.25
    assert rep["curve_dominance"] >= 0.95
    assert rep["ttff_ok"]


def test_ab_guided_structure_and_analytics_decoupling(tmp_path):
    """A small-budget run still produces the full report shape, real
    per-arm storages, and the analytics decoupling: the digest curve
    saturates while the relation curve still grows."""
    rep, workdir = _build_ab_storage(tmp_path)
    assert rep["schema"] == "nmz-guidance-ab-v1"
    for name in ("blind", "guided"):
        arm = rep["arms"][name]
        assert len(arm["bits_curve"]) == 24
        assert os.path.exists(os.path.join(workdir, name,
                                           "storage.json"))
        ana = arm["analytics_coverage"]
        # the motivating regime on the artifact: digest novelty reads
        # saturated while the ordering frontier is still open
        assert ana["saturated"] is True
        assert ana["digests_saturated_relations_growing"] is True
        assert ana["relation_curve"][-1] > ana["relation_curve"][0]
    # guided covers at least as much as blind at every budget point
    ca = rep["arms"]["blind"]["bits_curve"]
    cb = rep["arms"]["guided"]["bits_curve"]
    assert sum(1 for x, y in zip(ca, cb) if y >= x) >= len(ca) * 0.95


def test_ab_guided_cli(tmp_path, capsys):
    from namazu_tpu.cli import cli_main

    out = str(tmp_path / "ab.json")
    rc = cli_main(["tools", "ab-guided", "--seed", "11",
                   "--runs", "24", "--workdir",
                   str(tmp_path / "w"), "--out", out])
    printed = capsys.readouterr().out
    assert "coverage ratio" in printed
    with open(out) as f:
        rep = json.load(f)
    assert rep["schema"] == "nmz-guidance-ab-v1"
    assert rc == (0 if rep["ok"] else 1)
