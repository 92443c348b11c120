"""Permutation ("order mode") genomes — BASELINE config 3.

Covers: order_release_times semantics (priority table permutes events
regardless of arrival spacing — the interleavings literal delays cannot
reach), feature consistency, GA search in order mode, and the tpu_search
policy's reorder-window release realizing the scored permutation through
a real in-process orchestrator.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from namazu_tpu.ops import trace_encoding as te
from namazu_tpu.ops.schedule import (
    BIG,
    ScoreWeights,
    TraceArrays,
    order_release_times,
    schedule_features,
    score_population_multi,
)
from tests.scoring import score_one, stack

H, L, K = 16, 32, 32


def trace_of(hints, arrivals):
    enc = te.encode_event_stream(hints, arrivals=arrivals, L=L, H=H)
    return TraceArrays(
        jnp.asarray(enc.hint_ids), jnp.asarray(enc.arrival),
        jnp.asarray(enc.mask),
    ), enc


def test_order_release_inverts_arrival_order():
    """Priorities can put a *much later* arrival first — literal delays
    (t = arrival + d >= arrival) can never do that."""
    trace, enc = trace_of(["a", "b"], [0.0, 10.0])
    ha, hb = enc.hint_ids[0], enc.hint_ids[1]
    prio = jnp.zeros((H,), jnp.float32).at[ha].set(1.0).at[hb].set(0.0)
    t = order_release_times(prio, trace, gap=0.001)
    # b (arrival 10.0, priority 0) is released before a (arrival 0.0)
    assert float(t[1]) < float(t[0])
    assert float(t[0]) == pytest.approx(0.001)
    assert float(t[1]) == 0.0
    # masked tail stays BIG
    assert float(t[2]) == BIG


def test_order_release_ties_break_by_arrival():
    trace, enc = trace_of(["a", "a", "a"], [0.0, 1.0, 2.0])
    prio = jnp.zeros((H,), jnp.float32)
    t = np.asarray(order_release_times(prio, trace, gap=0.5))
    # equal priorities: stable in arrival order
    assert t[0] < t[1] < t[2]
    np.testing.assert_allclose(t[:3], [0.0, 0.5, 1.0])


def test_order_features_distinguish_permutations():
    trace, enc = trace_of(["a", "b", "c", "a"],
                          [0.0, 0.001, 0.002, 0.003])
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    w_gap, tau = 0.001, 0.0005
    id_prio = jnp.linspace(0.0, 1.0, H)
    rev_prio = 1.0 - id_prio
    f1 = schedule_features(id_prio, trace, pairs, tau, order_mode=True,
                           order_gap=w_gap)
    f2 = schedule_features(rev_prio, trace, pairs, tau, order_mode=True,
                           order_gap=w_gap)
    assert not np.allclose(np.asarray(f1), np.asarray(f2))


@pytest.mark.parametrize("T", [1, 3])
def test_order_mode_population_scoring_and_ga(T):
    """GA in order mode finds a priority table matching a target
    permutation's features better than the population average, against
    one trace and against a stack of three that differ: the order
    branch under the trace ``vmap`` scores each trace as it does
    alone."""
    from namazu_tpu.models.ga import GAConfig, ga_generation, init_population

    singles = [trace_of([f"h{(i * (t + 1)) % 8}" for i in range(24)],
                        [i * 1e-3 for i in range(24)])[0]
               for t in range(T)]
    trace, traces = singles[0], stack(*singles)
    pairs = jnp.asarray(te.sample_pairs(K, H, 1))
    w = ScoreWeights(order_mode=True, order_gap=0.001, tau=0.0005,
                     delay_cost=0.0)
    # target: the reverse-priority permutation's features as the "bug"
    target = schedule_features(jnp.linspace(1.0, 0.0, H), trace, pairs,
                               w.tau, order_mode=True, order_gap=w.order_gap)
    failures = jnp.tile(target[None], (4, 1))
    archive = jnp.full((8, K), 0.5, jnp.float32)

    cfg = GAConfig(max_delay=1.0)
    pop = init_population(jax.random.PRNGKey(0), 128, H, cfg)
    fit0, feats0 = score_population_multi(pop.delays, traces, pairs,
                                          archive, failures, w)
    # scoring is genome-sensitive (guards against the rank computation
    # silently collapsing): different genomes -> different features
    assert float(jnp.std(feats0, axis=0).max()) > 0.0
    alone = [score_one(pop.delays, tr, pairs, archive, failures, w)
             for tr in singles]
    for t, (_fit, feats) in enumerate(alone):
        np.testing.assert_array_equal(feats0[:, t], feats)
    np.testing.assert_allclose(
        fit0, np.mean([f for f, _ in alone], axis=0), rtol=1e-5, atol=1e-6)
    mean0 = float(fit0.mean())
    key = jax.random.PRNGKey(1)
    for g in range(10):
        fit, _ = score_population_multi(pop.delays, traces, pairs,
                                        archive, failures, w)
        key, k = jax.random.split(key)
        pop = ga_generation(k, pop, fit, cfg)
    fitN, _ = score_population_multi(pop.delays, traces, pairs, archive,
                                     failures, w)
    assert float(fitN.max()) > mean0


def test_order_release_rejects_batched_trace():
    trace, _ = trace_of(["a", "b"], [0.0, 1.0])
    batched = TraceArrays(trace.hint_ids[None], trace.arrival[None],
                          trace.mask[None])
    with pytest.raises(ValueError, match="single"):
        order_release_times(jnp.zeros((H,)), batched, gap=0.001)


def test_windowed_order_only_permutes_co_pending_events():
    """Events in different reorder windows keep their window order: the
    scorer must not promise permutations the buffer cannot realize."""
    # windows of 0.1s: events at 0.01 and 0.02 share window 0; the event
    # at 5.0 is in a much later window
    trace, enc = trace_of(["a", "b", "c"], [0.01, 0.02, 5.0])
    ha, hb, hc = enc.hint_ids[:3]
    # priority says c first, then b, then a
    prio = jnp.zeros((H,), jnp.float32).at[ha].set(2.0).at[hb].set(
        1.0).at[hc].set(0.0)
    t = np.asarray(order_release_times(prio, trace, gap=0.001,
                                       window=0.1))
    # within window 0: b before a (priorities honored)
    assert t[1] < t[0]
    # across windows: c stays after both despite priority 0
    assert t[2] > t[0] and t[2] > t[1]
    # window close time: window-0 events release at >= 0.1
    assert t[1] == pytest.approx(0.1)


# -- control plane: reorder window through a real orchestrator -----------


def test_policy_reorder_release_realizes_priority_order():
    from namazu_tpu.orchestrator import Orchestrator
    from namazu_tpu.inspector.transceiver import new_transceiver
    from namazu_tpu.policy import create_policy
    from namazu_tpu.signal import PacketEvent
    from namazu_tpu.utils.config import Config

    cfg = Config({
        "explore_policy": "tpu_search",
        "explore_policy_param": {
            "seed": 1, "release_mode": "reorder",
            "reorder_window": 40, "reorder_gap": 5,
            "search_on_start": False, "hint_buckets": H,
        },
    })
    pol = create_policy("tpu_search")
    pol.load_config(cfg)
    # install a known priority table: bucket of hint "late" gets priority
    # 0 (first), "early" gets 1 (second)
    from namazu_tpu.policy.replayable import fnv64a

    # the policy buckets the event's full replay hint, which for packets
    # is flow-qualified ("src->dst:<parser hint>")
    table = np.ones((H,), np.float32)
    table[fnv64a(b"a->b:late") % H] = 0.0
    table[fnv64a(b"a->b:early") % H] = 1.0
    pol.install_table(table)

    orc = Orchestrator(cfg, pol, collect_trace=True)
    orc.start()
    tr = new_transceiver("local://", "n0", orc.local_endpoint)
    tr.start()
    # "early" arrives first, "late" second — priorities must invert them
    e1 = PacketEvent.create("n0", "a", "b", hint="early")
    e2 = PacketEvent.create("n0", "a", "b", hint="late")
    ch1 = tr.send_event(e1)
    time.sleep(0.005)
    ch2 = tr.send_event(e2)
    a1 = ch1.get(timeout=10)
    a2 = ch2.get(timeout=10)
    assert a2.triggered_time < a1.triggered_time, (
        "reorder window must release by priority, not arrival"
    )
    orc.shutdown()


def test_policy_reorder_flushes_on_shutdown():
    from namazu_tpu.orchestrator import Orchestrator
    from namazu_tpu.inspector.transceiver import new_transceiver
    from namazu_tpu.policy import create_policy
    from namazu_tpu.signal import PacketEvent
    from namazu_tpu.utils.config import Config

    cfg = Config({
        "explore_policy": "tpu_search",
        "explore_policy_param": {
            "seed": 2, "release_mode": "reorder",
            "reorder_window": 10_000,  # window far beyond the test
            "search_on_start": False, "hint_buckets": H,
        },
    })
    pol = create_policy("tpu_search")
    pol.load_config(cfg)
    orc = Orchestrator(cfg, pol, collect_trace=True)
    orc.start()
    tr = new_transceiver("local://", "n0", orc.local_endpoint)
    tr.start()
    chans = [tr.send_event(PacketEvent.create("n0", "a", "b",
                                              hint=f"h{i}"))
             for i in range(4)]
    trace = orc.shutdown()  # must flush the pending window, loss-free
    assert len(trace.actions) >= 4
    for ch in chans:
        assert ch.get(timeout=1) is not None


def test_release_mode_validation():
    from namazu_tpu.policy import create_policy
    from namazu_tpu.utils.config import Config

    pol = create_policy("tpu_search")
    with pytest.raises(ValueError):
        pol.load_config(Config({
            "explore_policy": "tpu_search",
            "explore_policy_param": {"release_mode": "bogus"},
        }))


def test_policy_realized_order_equals_scored_order():
    """Crafted arrival pattern through a real orchestrator: the realized
    release order must equal the permutation order_release_times scores
    for the same arrivals — including the window boundary (co-window
    events permute, cross-window events do not)."""
    from namazu_tpu.orchestrator import Orchestrator
    from namazu_tpu.inspector.transceiver import new_transceiver
    from namazu_tpu.policy import create_policy
    from namazu_tpu.signal import PacketEvent
    from namazu_tpu.utils.config import Config
    from namazu_tpu.policy.replayable import fnv64a

    # generous CI margins: sends are ≥500 ms from any window boundary, so
    # a scheduling stall between time.sleep and the policy's queue_event
    # timestamp would need to exceed half a second to flip the window
    # assignment (advisor finding, round 2: 150 ms margins were flakable)
    window = 1.2
    cfg = Config({
        "explore_policy": "tpu_search",
        "explore_policy_param": {
            "seed": 3, "release_mode": "reorder",
            "reorder_window": int(window * 1000), "reorder_gap": 2,
            "search_on_start": False, "hint_buckets": H,
        },
    })
    pol = create_policy("tpu_search")
    pol.load_config(cfg)
    # priorities invert arrival order inside a window; the policy buckets
    # the flow-qualified replay hint ("a->b:<hint>")
    hints = ["pA", "pB", "pC", "pD"]
    full = [f"a->b:{h}" for h in hints]
    prios = {"a->b:pA": 3.0, "a->b:pB": 2.0, "a->b:pC": 1.0, "a->b:pD": 0.0}
    table = np.full((H,), 10.0, np.float32)
    for h, p in prios.items():
        table[fnv64a(h.encode()) % H] = p
    pol.install_table(table)

    orc = Orchestrator(cfg, pol, collect_trace=True)
    orc.start()
    tr = new_transceiver("local://", "n0", orc.local_endpoint)
    tr.start()
    # A, B, C inside window 0; D well into window 1 — despite D having
    # the lowest priority it must stay last
    offsets = [0.0, 0.15, 0.3, 1.7]
    chans = []
    t0 = time.monotonic()
    for hint, off in zip(hints, offsets):
        dt = t0 + off - time.monotonic()
        if dt > 0:
            time.sleep(dt)
        chans.append((hint, tr.send_event(
            PacketEvent.create("n0", "a", "b", hint=hint))))
    acts = [(h, ch.get(timeout=10)) for h, ch in chans]
    orc.shutdown()
    realized = [h for h, a in sorted(acts,
                                     key=lambda x: x[1].triggered_time)]

    # scored permutation for the same arrivals (same bucket space as the
    # policy: the flow-qualified hints)
    trace, enc = trace_of(full, offsets)
    prio_vec = jnp.asarray(table)
    t = np.asarray(order_release_times(prio_vec, trace, gap=0.002,
                                       window=window))
    scored = [hints[i] for i in np.argsort(t[:4], kind="stable")]
    assert realized == scored == ["pC", "pB", "pA", "pD"], (
        realized, scored)


# -- the counting order scorer (PR 32) ---------------------------------------
#
# order_release_times assigns slots by COUNTING (tiles of ORDER_TILE
# events, a pairwise part inside the tile, bucket-count contractions
# across tiles); these cases hold it, event by event, to a sort written
# out in numpy and to the benchmark's independent reference.


def _sorted_release(prio, hint_ids, arrival, mask, gap, window):
    """(window, priority, arrival, position) order by an explicit sort."""
    prio = np.asarray(prio, np.float32)
    arrival = np.asarray(arrival, np.float32)
    t = np.full(len(hint_ids), BIG, np.float32)
    if window > 0:
        win = np.floor(arrival / np.float32(window)).astype(np.int64)
    else:
        win = np.zeros(len(hint_ids), np.int64)
    live = [e for e in range(len(hint_ids)) if mask[e]]
    live.sort(key=lambda e: (win[e], prio[hint_ids[e]], arrival[e], e))
    seen: dict = {}
    for e in live:
        r = seen.get(win[e], 0)
        seen[win[e]] = r + 1
        t[e] = (np.float32(win[e]) + np.float32(1.0)) \
            * np.float32(window) + np.float32(r) * np.float32(gap)
    return t


def _case(name):
    """(hint_ids, arrival, mask, tables [S, H], H, window) by name."""
    import zlib

    rng = np.random.RandomState(zlib.crc32(name.encode()))
    Hc = 16
    n, Lc, window = 200, 256, 0.5
    hints = rng.randint(0, Hc, n)
    arrival = np.sort(rng.uniform(0, 3.0, n)).astype(np.float32)
    tables = rng.uniform(0, 0.1, (6, Hc)).astype(np.float32)
    if name == "one_window":
        window = 0.0
    elif name == "event_on_a_window_edge":
        arrival[10], arrival[11], arrival[12] = 0.5, 0.5, 1.0
        arrival = np.sort(arrival)
    elif name == "equal_priorities_interleaved":
        hints = np.tile([3, 7, 3, 7, 5], n // 5)
        tables[:, 7] = tables[:, 3]  # two buckets tie in every table
        tables[0, :] = 0.05  # and one table ties every bucket
    elif name == "colliding_hints_one_bucket":
        hints[:] = 4  # every hint collides into one bucket
    elif name == "masked_padding":
        n = 70  # Lc - n masked slots, some of them INSIDE the trace
    elif name == "bucket_over_256_in_one_window":
        n, Lc, window = 700, 768, 10.0
        hints = np.where(rng.uniform(size=n) < 0.6, 2,
                         rng.randint(0, Hc, n))
        arrival = np.sort(rng.uniform(0, 9.0, n)).astype(np.float32)
    elif name == "table_clipped_at_0_and_max":
        tables = np.clip(rng.normal(0.05, 0.08, (6, Hc)), 0.0,
                         0.1).astype(np.float32)
    elif name == "negative_priorities_and_signed_zeros":
        # the pairwise part compares int32 keys made of the floats' bits
        tables = rng.uniform(-0.05, 0.05, (6, Hc)).astype(np.float32)
        tables[:, 3], tables[:, 7], tables[:, 5] = -0.0, 0.0, -0.0
    elif name == "burst_spanning_tiles":
        arrival = np.sort(np.concatenate([
            rng.uniform(0, 0.49, 150), rng.uniform(0.5, 3.0, n - 150)
        ])).astype(np.float32)
    elif name != "many_windows":
        raise KeyError(name)
    hint_ids = np.zeros(Lc, np.int32)
    arr = np.zeros(Lc, np.float32)
    mask = np.zeros(Lc, bool)
    where = np.arange(n)
    if name == "masked_padding":
        where = np.sort(rng.choice(Lc, n, replace=False))
    hint_ids[where] = hints[:n]
    arr[where] = arrival[:n]
    mask[where] = True
    return hint_ids, arr, mask, tables, Hc, window


ORDER_CASES = ["one_window", "many_windows", "event_on_a_window_edge",
               "equal_priorities_interleaved",
               "colliding_hints_one_bucket", "masked_padding",
               "bucket_over_256_in_one_window",
               "table_clipped_at_0_and_max", "burst_spanning_tiles",
               "negative_priorities_and_signed_zeros"]


@pytest.mark.parametrize("T", [1, 4])
@pytest.mark.parametrize("name", ORDER_CASES)
def test_counting_order_scorer_equals_a_sort(name, T):
    """Every event's release time under every table, against the numpy
    sort and against benchmarks/reference.py::ordered_release; T traces
    go through the same nesting of vmaps as score_population_multi."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    import reference

    hint_ids, arr, mask, tables, Hc, window = _case(name)
    gap = 0.08
    traces = []
    for t in range(T):  # T > 1: the same events shifted, another order
        traces.append((np.roll(hint_ids, 3 * t) if t else hint_ids,
                       arr, np.roll(mask, 0)))
    stacked = TraceArrays(
        jnp.asarray(np.stack([h for h, _, _ in traces])),
        jnp.asarray(np.stack([a for _, a, _ in traces])),
        jnp.asarray(np.stack([m for _, _, m in traces])))
    got = np.asarray(jax.vmap(lambda tr: jax.vmap(
        lambda p: order_release_times(p, tr, gap, window))(
            jnp.asarray(tables)))(stacked))  # [T, S, L]
    for t, (h, a, m) in enumerate(traces):
        ref = reference.ordered_release(tables, h, a, m, gap, window)
        for s in range(len(tables)):
            want = _sorted_release(tables[s], h, a, m, gap, window)
            np.testing.assert_array_equal(got[t, s], want)
            np.testing.assert_array_equal(got[t, s], ref[s])
    if name == "bucket_over_256_in_one_window":
        assert (hint_ids[mask] == 2).sum() > 256
    if name == "equal_priorities_interleaved":
        # table 0 ties EVERY bucket: the release order is the arrival
        # order, so the buckets' slots interleave as their arrivals do
        first = mask & (arr < window)
        assert first.sum() > 10
        assert (np.diff(got[0, 0][first]) > 0).all()


def test_trace_tables_are_built_once_not_per_genome():
    """What depends on the trace alone stays outside the population
    vmap: the compiled scorer holds ONE sort, of [L] keys, whatever the
    population."""
    hint_ids, arr, mask, tables, Hc, window = _case("many_windows")
    trace = TraceArrays(jnp.asarray(hint_ids), jnp.asarray(arr),
                        jnp.asarray(mask))
    pop = jnp.asarray(np.tile(tables, (8, 1)))
    text = jax.jit(lambda pp, tr: jax.vmap(
        lambda p: order_release_times(p, tr, 0.08, window))(pp)
    ).lower(pop, trace).compile().as_text()
    sorts = [ln for ln in text.splitlines()
             if " sort(" in ln and "ENTRY" not in ln]
    assert sorts, "the static order is a sort of the trace"
    assert all(f"[{len(pop)}," not in ln.split(" sort(")[0]
               for ln in sorts), sorts


@pytest.mark.parametrize("gens", [2, 5])
def test_fused_step_in_order_mode_is_chunk_independent(gens):
    """G generations in one dispatch = G dispatches of one, to the bit,
    with the counting order scorer inside the step (what
    tests/test_fused_loop.py holds for delay mode), at the island level
    and end to end across ``fused_chunk``."""
    from namazu_tpu.models.ga import GAConfig
    from namazu_tpu.models.search import (
        ScheduleSearch, SearchConfig, make_score_weights)
    from namazu_tpu.parallel.islands import (
        init_island_state, make_fused_island_step)
    from namazu_tpu.parallel.mesh import make_mesh

    weights = make_score_weights(
        release_mode="reorder", w_novelty=0.3, w_bug=1.0,
        w_delay_cost=0.0005, w_fault_cost=0.05, tau=0.005,
        reorder_gap=0.08, reorder_window=0.5)
    assert weights.order_mode and weights.tau == pytest.approx(0.04)
    hint_ids, arr, mask, _t, Hc, _w = _case("burst_spanning_tiles")
    trace = TraceArrays(jnp.asarray(hint_ids), jnp.asarray(arr),
                        jnp.asarray(mask))
    pairs = jnp.asarray(te.sample_pairs(K, Hc, 0))
    archive = jnp.full((16, K), 0.5, jnp.float32)
    failures = jnp.full((4, K), 0.5, jnp.float32)
    mesh, cfg, key = make_mesh(8), GAConfig(max_delay=0.1), \
        jax.random.PRNGKey(1)
    one = make_fused_island_step(mesh, cfg, weights, migrate_k=2,
                                 generations=1)
    s_one = init_island_state(jax.random.PRNGKey(0), 64, Hc, cfg)
    hist_one = []
    for _ in range(gens):
        s_one, h = one(s_one, key, trace, pairs, archive, failures)
        hist_one.append(np.asarray(h))
    fused = make_fused_island_step(mesh, cfg, weights, migrate_k=2,
                                   generations=gens)
    s_fu, hist = fused(init_island_state(jax.random.PRNGKey(0), 64, Hc,
                                         cfg),
                       key, trace, pairs, archive, failures)
    assert np.array_equal(np.concatenate(hist_one), np.asarray(hist))
    for a, b in ((s_one.pop.delays, s_fu.pop.delays),
                 (s_one.best_fitness, s_fu.best_fitness),
                 (s_one.best_delays, s_fu.best_delays)):
        assert np.array_equal(np.asarray(a), np.asarray(b))

    def enc_of(n, seed):
        rng = np.random.RandomState(seed)
        return te.encode_event_stream(
            [f"h{rng.randint(12)}" for _ in range(n)],
            arrivals=sorted((3 * rng.rand(n)).tolist()), H=Hc)

    base = SearchConfig(H=Hc, K=K, archive_size=16, failure_size=8,
                        population=64, migrate_k=2, seed=3,
                        ga=GAConfig(max_delay=0.1), weights=weights)
    a = ScheduleSearch(base._replace(fused_chunk=1))
    b = ScheduleSearch(base._replace(fused_chunk=16))
    refs = [enc_of(140, 1), enc_of(90, 2)]
    for s in (a, b):
        s.add_executed_trace(enc_of(100, 5))
        s.add_failure_trace(enc_of(120, 6))
    ra, rb = (s.run(refs, generations=gens + 3) for s in (a, b))
    assert np.array_equal(ra.delays, rb.delays)
    assert ra.fitness == rb.fitness
