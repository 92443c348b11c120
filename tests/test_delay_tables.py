"""Delay-mode first occurrences from per-trace tables (PR 38):
``first[h] = delays[h] + earliest_arrival[h]``, the tables built once
per trace outside the population ``vmap`` (in the fused island step once
a dispatch, outside the generation loop). The per-event functions that
stay in ``ops/schedule.py`` are the oracle: equal to the bit at float32,
faults included; and the fused island step holds no per-event op that
carries the population dimension."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from namazu_tpu.models.ga import GAConfig, Population
from namazu_tpu.ops import schedule as sch
from namazu_tpu.ops import trace_encoding as te
from namazu_tpu.ops.schedule import ScoreWeights, TraceArrays
from namazu_tpu.parallel.islands import IslandState, make_fused_island_step
from namazu_tpu.parallel.mesh import make_mesh
from tests.scoring import stack

H, K = 32, 48
TAU = 0.005
LENGTHS = {32: "dense", 384: "dense", 1536: "blockwise", 2048: "blockwise",
           1300: "blockwise"}  # 1300: ragged, the scan pads it to 1536


def make_trace(L, faultable, seed=0):
    """A trace with what the identity could trip on: buckets no event
    falls in (H - 6 .. H - 1), duplicate arrivals (a grid of 7 ms, and
    bucket 3 twice at the same instant), a fully masked tail of a fifth
    of L whose arrivals are EARLIER than any live one, and one masked
    event inside the live part."""
    rng = np.random.default_rng(seed + L)
    live = L - L // 5
    hint = rng.integers(0, H - 6, size=L).astype(np.int32)
    arr = np.sort(rng.integers(1, 400, size=L)).astype(np.float32) * 0.007
    arr[live:] = 0.0  # the padding's arrivals: must not count
    hint[1], hint[2] = 3, 3
    arr[1] = arr[2]
    mask = np.arange(L) < live
    mask[5] = False
    fb = {"none": None,
          "mixed": rng.random(L) < 0.5,
          "all_false": np.zeros(L, bool)}[faultable]
    return TraceArrays(jnp.asarray(hint), jnp.asarray(arr),
                       jnp.asarray(mask),
                       None if fb is None else jnp.asarray(fb))


def genome(seed, with_faults):
    rng = np.random.default_rng(seed)
    delays = jnp.asarray(rng.random(H).astype(np.float32) * 0.1)
    delays = delays.at[4].set(0.0)
    if not with_faults:
        return delays, None, None
    faults = jnp.asarray(rng.random(H).astype(np.float32))
    return delays, faults, jnp.asarray(te.fault_coin(seed, H))


def per_event(delays, trace, faults, coin):
    """(first, ndrop) by the dense per-event path of the parent."""
    eff = sch.apply_faults(trace, faults, coin)
    first = sch.first_occurrence(sch.release_times(delays, eff), eff, H)
    ndrop = (jnp.sum(trace.mask) - jnp.sum(eff.mask)).astype(jnp.int32)
    return first, ndrop


@pytest.mark.parametrize("faultable", ["none", "mixed", "all_false"])
@pytest.mark.parametrize("with_faults", [False, True],
                         ids=["delays_only", "fault_half"])
@pytest.mark.parametrize("L", sorted(LENGTHS))
def test_table_first_occurrence_equals_the_per_event_paths(
        L, with_faults, faultable):
    assert sch.scorer_branch(L) == LENGTHS[L]
    trace = make_trace(L, faultable)
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    for seed in (1, 2):
        delays, faults, coin = genome(seed, with_faults)
        tables = sch._delay_tables(trace, H, with_faults)
        assert (tables.earliest_fixed is None) == (not with_faults)
        first, ndrop = sch._table_first_occurrence(delays, tables, faults,
                                                   coin)
        want_first, want_ndrop = per_event(delays, trace, faults, coin)
        np.testing.assert_array_equal(first, want_first)
        np.testing.assert_array_equal(ndrop, want_ndrop)
        block_first, block_ndrop = sch.first_occurrence_blockwise(
            delays, trace.hint_ids, trace.arrival, trace.mask,
            faults=faults, coin=coin, faultable=trace.faultable)
        np.testing.assert_array_equal(first, block_first)
        np.testing.assert_array_equal(ndrop, block_ndrop)
        feats, nd = sch._genome_features(delays, trace, pairs, TAU,
                                         faults=faults, coin=coin)
        np.testing.assert_array_equal(
            feats, sch.precedence_features(want_first, pairs, TAU))
        np.testing.assert_array_equal(nd, want_ndrop)
        if with_faults:
            assert int(want_ndrop) > 0 or faultable == "all_false"
    first = np.asarray(first)
    assert (first[H - 6:] == np.float32(sch.BIG)).all()  # empty buckets
    assert (first[:H - 6] < 10.0).any()


def _per_event_population(delays, trace, pairs, weights, faults=None,
                          coin=None, tables=None):
    """The parent's population features: the per-event path under the
    ``vmap`` over genomes (``tables`` not read)."""

    def one(d, f):
        first, ndrop = per_event(d, trace, f, coin)
        return sch.precedence_features(first, pairs, weights.tau), ndrop

    return jax.vmap(one, in_axes=(0, None if faults is None else 0))(
        delays, faults)


@pytest.mark.parametrize("with_faults", [False, True],
                         ids=["delays_only", "fault_half"])
@pytest.mark.parametrize("L", [128, 1536], ids=["dense", "blockwise"])
@pytest.mark.parametrize("T", [1, 4])
def test_population_scores_equal_the_per_event_oracle(T, L, with_faults,
                                                      monkeypatch):
    """``score_population_multi`` at T 1 and T 4, P 64: fitness and
    features equal, to the bit, to the same function with the parent's
    per-event features in the tables' place."""
    P_ = 64
    rng = np.random.default_rng(L + T)
    traces = [make_trace(L, "mixed" if with_faults else "none", seed=t)
              for t in range(T)]
    pairs = jnp.asarray(te.sample_pairs(K, H, 0))
    archive = jnp.asarray(rng.random((16, K)).astype(np.float32))
    fails = jnp.asarray(rng.random((4, K)).astype(np.float32))
    delays = jnp.asarray(rng.random((P_, H)).astype(np.float32) * 0.1)
    faults = coin = None
    if with_faults:
        faults = jnp.asarray(rng.random((P_, H)).astype(np.float32))
        coin = jnp.asarray(te.fault_coin(3, H))
    stacked = stack(*traces)

    def score(d, f):
        return sch.score_population_multi(
            d, stacked, pairs, archive, fails, ScoreWeights(),
            faults=f, coin=coin)

    # each side under a jit of its own: traced anew, so the patched
    # name is what the oracle's side calls
    fit, feats = jax.jit(score)(delays, faults)
    monkeypatch.setattr(sch, "_population_features", _per_event_population)
    want_fit, want_feats = jax.jit(lambda d, f: score(d, f))(delays, faults)
    np.testing.assert_array_equal(feats, want_feats)
    np.testing.assert_array_equal(fit, want_fit)
    assert np.ptp(np.asarray(fit)) > 0
    # the tables handed in, as the fused step hands them in once a
    # dispatch: the same answer
    monkeypatch.undo()
    tables = sch.trace_tables(stacked, H, ScoreWeights(), with_faults)
    held_fit, held_feats = jax.jit(
        lambda d, f, tb: sch.score_population_multi(
            d, stacked, pairs, archive, fails, ScoreWeights(),
            faults=f, coin=coin, tables=tb))(delays, faults, tables)
    np.testing.assert_array_equal(held_feats, want_feats)
    np.testing.assert_array_equal(held_fit, want_fit)


# -- structure: what the compiled step holds --------------------------------

# sizes no two of which coincide, so that a dimension names its axis:
# population 48, H 32, K 40, archive 24 / failures 8, T 4 traces of
# L 1536 = 3 chunks of 512
SP, SH, SK, SA, SF, ST, SL = 48, 32, 40, 24, 8, 4, 1536
ORDER = ScoreWeights(order_mode=True, order_gap=0.08, order_window=0.5,
                     tau=0.04, delay_cost=0.0)


def lowered_step(weights=ScoreWeights(), max_fault=0.0):
    """StableHLO text of the fused island step, lowered and not run."""
    S = jax.ShapeDtypeStruct
    f32 = jnp.float32
    fused = make_fused_island_step(
        make_mesh(1), GAConfig(max_delay=0.1, max_fault=max_fault),
        weights, migrate_k=2, generations=2)
    state = IslandState(
        pop=Population(delays=S((SP, SH), f32), faults=S((SP, SH), f32)),
        gen=S((), jnp.int32), best_fitness=S((), f32),
        best_delays=S((SH,), f32), best_faults=S((SH,), f32))
    fault = max_fault > 0
    trace = TraceArrays(S((ST, SL), jnp.int32), S((ST, SL), f32),
                        S((ST, SL), jnp.bool_),
                        S((ST, SL), jnp.bool_) if fault else None)
    return fused.lower(
        state, S((2,), jnp.uint32), trace, S((SK, 2), jnp.int32),
        S((SA, SK), f32), S((SF, SK), f32),
        S((SH,), f32) if fault else None, S((), f32), None).as_text()


def op_signatures(text, op):
    """The types of every ``op`` of the module: a ``while``'s carried
    types end its own line, a scatter's or gather's ``(operands) ->
    result`` closes the op's region where it has one."""
    lines = text.splitlines()
    sigs = []
    for i, ln in enumerate(lines):
        if f"stablehlo.{op}" not in ln:
            continue
        if op != "while":
            while ") -> " not in lines[i]:
                i += 1
        sigs.append(lines[i][lines[i].rfind(" : "):])
    return sigs


def dims(sig):
    return [tuple(int(d) for d in m.split("x")[:-1])
            for m in re.findall(r"tensor<([0-9x]+x[a-z0-9]+)>", sig)]


def carries(sig, size):
    return any(size in shape for shape in dims(sig))


def per_event_ops_with_population(text):
    """Scatters and gathers over the events axis (L, or a chunk of 512)
    whose operand, indices or update also carries the population."""
    return [sig for op in ("scatter", "gather")
            for sig in op_signatures(text, op)
            if (carries(sig, SL) or carries(sig, sch.LONG_TRACE_CHUNK))
            and carries(sig, SP)]


@pytest.mark.parametrize("max_fault", [0.0, 0.2],
                         ids=["delays_only", "fault_half"])
def test_no_per_event_op_of_the_step_carries_the_population(max_fault):
    text = lowered_step(max_fault=max_fault)
    assert per_event_ops_with_population(text) == []
    # the tables are still the blockwise scan in chunks of 512, per
    # trace: its loop carries the chunked trace and no population
    scans = [s for s in op_signatures(text, "while")
             if (SL // sch.LONG_TRACE_CHUNK, ST, sch.LONG_TRACE_CHUNK)
             in dims(s)]
    assert len(scans) == 1 and not carries(scans[0], SP), scans
    # one function, called once a table: earliest_all and, with a fault
    # half, earliest_fixed
    assert len(re.findall(r"call @first_occurrence_blockwise\(", text)) \
        == (2 if max_fault else 1)
    # and what is left per genome: first = delays + earliest, [T, P, H]
    assert any((ST, SP, SH) in dims(ln) for ln in text.splitlines()
               if "stablehlo.add" in ln)
    # the tables are built once a dispatch: the generation's body (the
    # function that calls the GA) holds no per-event scatter or gather
    # and no call of the scan
    (body,) = [f for f in text.split("func.func ")
               if "call @ga_generation(" in f]
    assert "call @first_occurrence_blockwise(" not in body
    assert not [sig for op in ("scatter", "gather")
                for sig in op_signatures(body, op)
                if carries(sig, SL) or carries(sig, sch.LONG_TRACE_CHUNK)]


def test_the_order_branch_keeps_its_per_event_scatter_under_the_vmap():
    """Order mode is not touched: its times depend on the whole
    priority table, so its scatter-min stays per genome."""
    text = lowered_step(weights=ORDER)
    held = per_event_ops_with_population(text)
    assert any((ST, SP, SL) in dims(s) and (ST, SP, SH) in dims(s)
               for s in held), held
    assert "first_occurrence_blockwise" not in text
