"""Pure-JAX schedule scoring: the search plane's inner loop.

A *schedule genome* is a per-hint-bucket delay table ``delays f32[H]``
(seconds) plus a per-hint fault-probability table ``faults f32[H]``. Given
a recorded trace, the counterfactual interleaving under a genome is defined
by release times ``t[e] = arrival[e] + delays[hint_ids[e]]`` — exactly what
the control plane's ScheduledQueue realizes when the policy replays the
genome (namazu_tpu/policy/tpu.py), so scored schedules and executed
schedules agree by construction.

Scoring (vmapped over a population [P, H]):

1. first-occurrence time per hint bucket, ``first f32[H]``: in delay
   mode ``delays + earliest arrival``, the earliest arrivals a per-trace
   table built once outside the vmap (:class:`_DelayTables`); in order
   mode a scatter-min of the genome's release times;
2. precedence features over K sampled bucket pairs:
   ``feat[k] = sigmoid((first[v_k] - first[u_k]) / tau)`` — a smooth
   "does u happen before v" indicator in (0,1); buckets absent from the
   trace get BIG times, making their pairs a neutral 0.5;
3. novelty = min squared L2 distance to an archive of previously executed
   schedules' features (one [P,K]x[K,A] matmul — MXU work);
4. bug affinity = -min squared distance to the features of traces that
   actually reproduced the bug (failure archive);
5. fitness = w_novelty * novelty + w_bug * bug_affinity
   - w_delay_cost * mean(delays)  (prefer fast schedules, tie-break).

This plane generalizes the reference's whole exploration stack: the random
policy samples ONE schedule per wall-clock run (~minutes); here millions
are scored per second between runs, and only the argmax is paid for with
wall-clock (SURVEY.md section 6, BASELINE.json north star).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

BIG = 1e9  # "never happens" release time
# min-identity used to mask archive rows out of a distance min (padded
# rows and rows past a ring's occupancy): large enough that a masked row
# can never win against any real feature distance (features live in
# (0,1)^K so real d2 <= K), small enough that f32 arithmetic on it stays
# finite
MASK_BIG = 3.4e38


class TraceArrays(NamedTuple):
    """Static-shape view of one encoded trace on device.

    ``faultable`` marks events whose cause class supports a fault action
    (packet drop / EIO); ``None`` means "treat everything as faultable"
    (pre-faultable encodes, and fault-off scoring where it is unused).
    """

    hint_ids: jax.Array  # int32[L]
    arrival: jax.Array  # float32[L]
    mask: jax.Array  # bool[L]
    faultable: Optional[jax.Array] = None  # bool[L] or None


class ScoreWeights(NamedTuple):
    novelty: float = 1.0
    bug: float = 1.0
    delay_cost: float = 0.01
    tau: float = 0.005  # precedence smoothing, seconds
    # cost per dropped event (as a fraction of live events): dropping
    # *everything* is maximally novel, so fault search needs a
    # counterweight that scales with how much of the trace the genome
    # erases (reference: faults are rare, faultActionProbability ~ 0.0,
    # randompolicy.go:300-317)
    fault_cost: float = 0.05
    # order mode (BASELINE config 3, "permutation+delay genomes"): the
    # genome table is interpreted as per-hint *priorities* realized by the
    # policy's reorder window, not as literal delays. Events are bucketed
    # into arrival windows of order_window seconds (0 = one global
    # window) and permuted by (priority, arrival) *within* each window —
    # exactly the set of interleavings the control plane's windowed
    # reorder buffer can realize, so scored schedules stay executable.
    order_mode: bool = False
    order_gap: float = 0.001  # seconds between consecutive releases
    order_window: float = 0.0  # reorder-window size; 0 = whole trace


def normalize_fault_trace(trace: TraceArrays,
                          coin: Optional[jax.Array]) -> TraceArrays:
    """One home for the faultable-flag contract at scoring entry points:
    without a fault coin the flag is never consumed, so it is stripped
    (keeps the fault-off pytree and jit cache entry flag-free); with a
    coin but no flag, everything is faultable (pre-flag behavior)."""
    if coin is None:
        return trace._replace(faultable=None)
    if trace.faultable is None:
        return trace._replace(faultable=jnp.ones_like(trace.mask))
    return trace


def replicated_trace_specs():
    """(fault, nofault) TraceArrays PartitionSpec pytrees for shard_map
    entry points that replicate the trace: the fault variant ships the
    per-event faultable flag, the fault-off variant never does."""
    from jax.sharding import PartitionSpec as P

    return (
        TraceArrays(hint_ids=P(), arrival=P(), mask=P(), faultable=P()),
        TraceArrays(hint_ids=P(), arrival=P(), mask=P()),
    )


def release_times(delays: jax.Array, trace: TraceArrays) -> jax.Array:
    """t[e] = arrival[e] + delays[hint_ids[e]] (masked -> BIG)."""
    t = trace.arrival + delays[trace.hint_ids]
    return jnp.where(trace.mask, t, BIG)


def drop_mask(faults: jax.Array, coin: jax.Array,
              trace: TraceArrays) -> jax.Array:
    """bool[L]: events the genome's fault table removes from the
    counterfactual interleaving.

    The control plane's fault decision is a deterministic per-bucket coin
    (policy/tpu.py _fault_for): event e is dropped iff
    ``coin[hint_ids[e]] < faults[hint_ids[e]]``, so the scored
    counterfactual and the replayed schedule agree by construction. A
    dropped packet never arrives (PacketFaultAction, reference
    action_fault_packet.go:29-46); EIO-style filesystem faults are
    approximated the same way — the op's normal effect vanishes from the
    interleaving.

    The control plane only realizes a drop when the event supports a
    fault action (``default_fault_action() is not None``); a hint-bucket
    hash collision between a faultable and a non-faultable hint must not
    produce scored drops that never replay, so non-faultable events are
    masked out of the drop set when the trace carries the flag.
    """
    d = trace.mask & (coin[trace.hint_ids] < faults[trace.hint_ids])
    if trace.faultable is not None:
        d = d & trace.faultable
    return d


def apply_faults(trace: TraceArrays, faults: Optional[jax.Array],
                 coin: Optional[jax.Array]) -> TraceArrays:
    """Trace with fault-dropped events masked out (identity when the
    genome has no fault half)."""
    if faults is None:
        return trace
    dropped = drop_mask(faults, coin, trace)
    return TraceArrays(trace.hint_ids, trace.arrival,
                       trace.mask & ~dropped, trace.faultable)


# events per tile of the order scorer's counting scheme (see
# :func:`order_release_times`): the pairwise part costs L * ORDER_TILE
# comparisons a genome on the VPU, the table part H * H * L / ORDER_TILE
# multiply-adds on the MXU. At most 256, so that a tile's count of one
# bucket stays small.
ORDER_TILE = 64
_I32_MAX = 2 ** 31 - 1


class _OrderTables(NamedTuple):
    """What the order scorer needs of ONE trace and no table: its events
    in the static order ``(window, arrival, position)``, cut into tiles
    of :data:`ORDER_TILE`, and per tile the per-bucket counts of the
    window's events that lie in OTHER tiles. Built from the trace alone,
    so under a population ``vmap`` it is computed once, not per genome.
    """

    hs: jax.Array  # i32[Nt, B] bucket of the event at each sorted slot
    same: jax.Array  # bool[Nt, B, B] [i, j]: j live, in i's window
    before: jax.Array  # i32[B, B] [i, j]: 1 where j < i
    head: jax.Array  # f32[Nt, B, H] one-hot of the event's bucket if it
    tail: jax.Array  # is live and in the tile's first / last window
    prev: jax.Array  # f32[Nt, H] earlier tiles' events of the head window
    nxt: jax.Array  # f32[Nt, H] later tiles' events of the tail window
    slot: jax.Array  # i32[L] sorted slot of each event of the trace
    base: jax.Array  # f32[L] close of each event's window


def _order_tables(trace: TraceArrays, window: float, H: int
                  ) -> _OrderTables:
    L = trace.hint_ids.shape[0]
    B = min(ORDER_TILE, L)
    nt = -(-L // B)
    pad = nt * B - L
    if window > 0:
        win = jnp.floor(trace.arrival / window).astype(jnp.int32)
    else:
        win = jnp.zeros((L,), jnp.int32)
    win = jnp.where(trace.mask, win, _I32_MAX)
    base = (win.astype(jnp.float32) + 1.0) * window  # window close time
    # window-major, then arrival, then position (the sort is stable):
    # the order in which buckets of EQUAL priority are released
    order = jnp.lexsort((trace.arrival, win)).astype(jnp.int32)
    slot = jnp.zeros((L,), jnp.int32).at[order].set(
        jnp.arange(L, dtype=jnp.int32))
    hs = jnp.pad(trace.hint_ids[order], (0, pad)).reshape(nt, B)
    ws = jnp.pad(win[order], (0, pad),
                 constant_values=_I32_MAX).reshape(nt, B)
    live = jnp.pad(trace.mask[order], (0, pad)).reshape(nt, B)
    same = live[:, None, :] & (ws[:, :, None] == ws[:, None, :])
    before = jnp.tril(jnp.ones((B, B), jnp.int32), -1)  # [i, j]: j < i
    head_w, tail_w = ws[:, 0], ws[:, -1]
    is_head = live & (ws == head_w[:, None])
    is_tail = live & (ws == tail_w[:, None])
    onehot = hs[:, :, None] == jnp.arange(H, dtype=jnp.int32)
    c_head = jnp.sum(onehot & is_head[:, :, None], axis=1,
                     dtype=jnp.int32)  # [Nt, H]
    c_tail = jnp.sum(onehot & is_tail[:, :, None], axis=1,
                     dtype=jnp.int32)
    # a window that spans tiles: what tile t's head window holds in
    # EARLIER tiles (every one of them ends in that window), and what
    # its tail window holds in LATER tiles (each starts in it)
    ti = jnp.arange(nt)
    m_prev = (ti[None, :] < ti[:, None]) & (
        tail_w[None, :] == head_w[:, None])
    m_next = (ti[None, :] > ti[:, None]) & (
        head_w[None, :] == tail_w[:, None])
    prev = jnp.sum(jnp.where(m_prev[:, :, None], c_tail[None], 0), axis=1)
    nxt = jnp.sum(jnp.where(m_next[:, :, None], c_head[None], 0), axis=1)
    return _OrderTables(
        hs, same, before,
        (onehot & is_head[:, :, None]).astype(jnp.float32),
        (onehot & is_tail[:, :, None]).astype(jnp.float32),
        prev.astype(jnp.float32), nxt.astype(jnp.float32), slot, base)


def _exact_dot(a: jax.Array, b: jax.Array, dims) -> jax.Array:
    """A float32 contraction of small integers with a 0/1 operand that
    is exact on the MXU: ``Precision.HIGH`` splits each float32 operand
    into a high and a low bfloat16 part and keeps the three leading
    products; an integer below 2**16 is the sum of its two parts
    exactly, the 0/1 operand has no low part, and the float32
    accumulation of integers is exact below 2**24. (One bfloat16 pass
    would round a count over 256.)"""
    return jax.lax.dot_general(
        a, b, dims, precision=jax.lax.Precision.HIGH,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("gap", "window"))
def order_release_times(prio: jax.Array, trace: TraceArrays,
                        gap: float, window: float = 0.0) -> jax.Array:
    """Counterfactual release times under *windowed permutation*
    scheduling — what the policy's reorder buffer (policy/tpu.py
    release_mode "reorder") actually realizes: events are batched into
    arrival windows of ``window`` seconds and each batch is released in
    ``(prio[hint], arrival)`` order (ties by position in the trace),
    ``gap`` seconds apart, starting at the window's end. ``window=0``
    scores one global window (the upper bound of reachable
    permutations). Only co-pending events can be permuted, so scored
    interleavings stay executable.

    An event's slot is a COUNT, not a position in a sort: the events of
    its window whose ``(priority, arrival, position)`` is smaller. The
    trace's events are put in their static order (window, arrival,
    position) once and cut into tiles of :data:`ORDER_TILE`
    (:func:`_order_tables`: nothing there depends on the table, so a
    population ``vmap`` computes it once per trace). A genome then
    pays (a) inside a tile, a pairwise comparison of the tile's
    priorities, (b) across tiles, two contractions of its ``[H, H]``
    priority comparisons (``<`` against events in later tiles, ``<=``
    against events in earlier ones: equal priorities go by the static
    order) with per-tile per-bucket counts, ``H * H * 2 L / ORDER_TILE``
    multiply-adds that the MXU takes, and (c) one gather per event.
    No sort, scan or scatter of L events per genome.

    Exactness: the counts are built in int32 and enter the contraction
    as float32 at ``Precision.HIGH`` (:func:`_exact_dot`: one
    bfloat16 pass holds integers only to 256, and a window may hold
    more events of one bucket), accumulate in float32, exact below
    2**24, and come back as int32; the pairwise part is summed in
    int32.

    1-D trace only (vmap over genomes; use score_population_multi for
    stacked traces). Masked positions are absent and stay BIG.
    Jitted in its own right, as :func:`first_occurrence_blockwise` is
    and for the same reason: a caller may run the scorer op by op;
    inside a compiled program (the island step) the jit is inlined.
    """
    if trace.hint_ids.ndim != 1:
        raise ValueError(
            "order_release_times takes a single [L] trace; got shape "
            f"{trace.hint_ids.shape}"
        )
    with jax.named_scope("nmz_score"):
        H = prio.shape[0]
        tb = _order_tables(trace, window, H)
        nt, B = tb.hs.shape
        # (a) inside the tile: j is released before i. The priorities
        # go as int32 keys of the same order (the float's bits, the
        # negative ones flipped; -0.0 first made +0.0), so that "<= for
        # an earlier j, < for a later one" is ONE comparison, against
        # the key plus one where j is earlier
        bits = jax.lax.bitcast_convert_type(
            jnp.where(prio == 0.0, 0.0, prio), jnp.int32)
        key = (bits ^ ((bits >> 31) & 0x7FFFFFFF))[tb.hs]  # [Nt, B]
        rank = jnp.sum(
            tb.same & (key[:, None, :] < key[:, :, None] + tb.before),
            axis=-1, dtype=jnp.int32)  # [Nt, B]
        if nt > 1:
            # (b) the window's events in other tiles, by bucket: two
            # [Nt, H] tables per genome, then each event's entry by a
            # one-hot contraction (a gather would have XLA transpose
            # the whole table first)
            lt = (prio[None, :] < prio[:, None]).astype(jnp.float32)
            le = (prio[None, :] <= prio[:, None]).astype(jnp.float32)
            table = (((1,), (1,)), ((), ()))  # [h, h']: h' before h
            entry = (((2,), (1,)), ((0,), (0,)))  # tile by tile
            other = (_exact_dot(tb.head, _exact_dot(tb.prev, le, table),
                                entry)
                     + _exact_dot(tb.tail, _exact_dot(tb.nxt, lt, table),
                                  entry))  # [Nt, B]
            rank = rank + other.astype(jnp.int32)
        within = rank.reshape(nt * B)[tb.slot]  # (c) back to trace order
        t = tb.base + within.astype(jnp.float32) * gap
        return jnp.where(trace.mask, t, BIG)


def first_occurrence(t: jax.Array, trace: TraceArrays, H: int) -> jax.Array:
    """Earliest release time per hint bucket, BIG where absent."""
    return jnp.full((H,), BIG, t.dtype).at[trace.hint_ids].min(
        jnp.where(trace.mask, t, BIG)
    )


def precedence_features(
    first: jax.Array, pairs: jax.Array, tau: float
) -> jax.Array:
    """feat[k] = sigmoid((first[v_k] - first[u_k]) / tau) in (0,1)."""
    du = first[pairs[:, 0]]
    dv = first[pairs[:, 1]]
    # clip the argument so BIG-vs-finite saturates instead of overflowing
    z = jnp.clip((dv - du) / tau, -30.0, 30.0)
    return jax.nn.sigmoid(z)


class _DelayTables(NamedTuple):
    """What delay-mode scoring needs of ONE trace and no genome: inside
    a bucket ``delays[h]`` is one number and float addition of one
    number is monotone, so ``min_l fl(arrival[l] + d) == fl(min_l
    arrival[l] + d)`` exactly, and a fault drop is decided per bucket
    (:func:`drop_mask`). Built from the trace alone, before the
    population ``vmap``: once per trace, not per genome (in the fused
    island step once a dispatch: :func:`trace_tables`).
    """

    earliest_all: jax.Array  # f32[H] min arrival of the bucket's events
    # the fault half's two tables (None where no fault half is scored)
    earliest_fixed: Optional[jax.Array] = None  # f32[H] non-faultable only
    faultable_count: Optional[jax.Array] = None  # i32[H] faultable events


def _earliest_arrival(trace: TraceArrays, mask: jax.Array,
                      H: int) -> jax.Array:
    """Earliest arrival per bucket over the events of ``mask`` (BIG
    where none), by the per-event functions at zero delay (``a + 0.0 ==
    a`` exactly): the blockwise scan for a long trace, the dense
    scatter-min below it (:func:`scorer_branch`)."""
    zero = jnp.zeros((H,), jnp.float32)
    if scorer_branch(trace.hint_ids.shape[-1]) == "blockwise":
        first, _ = first_occurrence_blockwise(
            zero, trace.hint_ids, trace.arrival, mask)
        return first
    tr = trace._replace(mask=mask)
    return first_occurrence(release_times(zero, tr), tr, H)


def _delay_tables(trace: TraceArrays, H: int,
                  with_faults: bool = False) -> _DelayTables:
    """The per-trace tables of delay-mode scoring. ``trace.faultable is
    None`` reads "every event may be dropped", as :func:`drop_mask`
    reads it."""
    earliest_all = _earliest_arrival(trace, trace.mask, H)
    if not with_faults:
        return _DelayTables(earliest_all)
    if trace.faultable is None:
        droppable = trace.mask
        earliest_fixed = jnp.full((H,), BIG, jnp.float32)
    else:
        droppable = trace.mask & trace.faultable
        earliest_fixed = _earliest_arrival(
            trace, trace.mask & ~trace.faultable, H)
    count = jnp.zeros((H,), jnp.int32).at[trace.hint_ids].add(
        droppable.astype(jnp.int32))
    return _DelayTables(earliest_all, earliest_fixed, count)


def _table_first_occurrence(
    delays: jax.Array, tables: _DelayTables,
    faults: Optional[jax.Array] = None,
    coin: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """(first-occurrence times f32[H], dropped-event count i32) of one
    delay-mode genome from its trace's tables: O(H), no gather or
    scatter over the events. A dropped bucket keeps its non-faultable
    events; the ``minimum`` is the scatter-min's BIG initial value."""
    if faults is None:
        earliest = tables.earliest_all
        ndrop = jnp.zeros((), jnp.int32)
    else:
        dropped = coin < faults
        earliest = jnp.where(dropped, tables.earliest_fixed,
                             tables.earliest_all)
        ndrop = jnp.sum(jnp.where(dropped, tables.faultable_count, 0))
    first = jnp.where(earliest < BIG,
                      jnp.minimum(earliest + delays, BIG), BIG)
    return first, ndrop


def _genome_features(
    delays: jax.Array, trace: TraceArrays, pairs: jax.Array, tau: float,
    order_mode: bool = False, order_gap: float = 0.001,
    order_window: float = 0.0,
    faults: Optional[jax.Array] = None,
    coin: Optional[jax.Array] = None,
    tables: Optional[_DelayTables] = None,
) -> tuple[jax.Array, jax.Array]:
    """(features f32[K], dropped-event count i32) for one genome.

    Delay mode: ``first = delays + earliest arrival`` from the trace's
    :class:`_DelayTables` (``tables``: built by the caller before its
    population ``vmap``, here for one genome alone); the padded length
    decides only how those are built (:func:`scorer_branch`: the
    blockwise scan above ``LONG_TRACE_THRESHOLD``, the dense
    scatter-min below). Order mode has a per-event branch of its own.
    The dispatch is on static shape and mode, so each jit
    specialization compiles exactly one branch."""
    H = delays.shape[0]
    if not order_mode:
        if tables is None:
            tables = _delay_tables(trace, H, faults is not None)
        first, ndrop = _table_first_occurrence(delays, tables, faults, coin)
        return precedence_features(first, pairs, tau), ndrop
    eff = apply_faults(trace, faults, coin)
    if faults is None:
        ndrop = jnp.zeros((), jnp.int32)
    else:
        ndrop = (jnp.sum(trace.mask) - jnp.sum(eff.mask)).astype(jnp.int32)
    t = order_release_times(delays, eff, order_gap, order_window)
    first = first_occurrence(t, eff, H)
    return precedence_features(first, pairs, tau), ndrop


def _population_features(
    delays: jax.Array,  # [P, H]
    trace: TraceArrays,  # one [L] trace
    pairs: jax.Array, weights: ScoreWeights,
    faults: Optional[jax.Array] = None,  # [P, H]
    coin: Optional[jax.Array] = None,
    tables: Optional[_DelayTables] = None,
) -> tuple[jax.Array, jax.Array]:
    """(features f32[P, K], dropped-event counts i32[P]) of a population
    against one trace. In delay mode the trace's ``tables`` come from
    the caller, built before this ``vmap`` over genomes, so that no
    per-event op carries the population dimension."""
    return jax.vmap(
        lambda d, f: _genome_features(d, trace, pairs, weights.tau,
                                      weights.order_mode, weights.order_gap,
                                      weights.order_window, faults=f,
                                      coin=coin, tables=tables),
        in_axes=(0, None if faults is None else 0))(delays, faults)


def schedule_features(
    delays: jax.Array, trace: TraceArrays, pairs: jax.Array, tau: float,
    order_mode: bool = False, order_gap: float = 0.001,
    order_window: float = 0.0,
    faults: Optional[jax.Array] = None,
    coin: Optional[jax.Array] = None,
) -> jax.Array:
    """One genome -> feature vector f32[K]. In order mode the genome is a
    priority table and tau should be of the order of order_gap so adjacent
    ranks still produce saturated precedence features. When ``faults`` (and
    the per-bucket ``coin``) are given, fault-dropped events vanish from
    the counterfactual before first-occurrence — the fault half of the
    genome shapes the features (BASELINE config 4)."""
    feats, _ = _genome_features(delays, trace, pairs, tau, order_mode,
                                order_gap, order_window, faults, coin)
    return feats


def trace_features(
    trace: TraceArrays, pairs: jax.Array, tau: float, H: int
) -> jax.Array:
    """Feature vector of a trace *as recorded* (zero extra delay) — used to
    embed executed runs (including failures) into the same space."""
    zero = jnp.zeros((H,), jnp.float32)
    return schedule_features(zero, trace, pairs, tau)


@functools.lru_cache(maxsize=None)
def batched_trace_features(tau: float, H: int):
    """The compiled, batched :func:`trace_features`:
    ``f(hint_ids [C, L], arrival [C, L], mask [C, L], pairs [K, 2]) ->
    f32[C, K]``, runs mapped and the pair sample shared. It is the
    same function under ``jax.vmap`` and ``jax.jit``, so the same
    float32 arithmetic, and the dense / blockwise branch
    (:data:`LONG_TRACE_THRESHOLD`) is still chosen per static L. A row
    whose mask is all False embeds to the neutral 0.5 — what a padded
    row of a short chunk reads. One jitted function per ``(tau, H)``
    for the life of the process, shared by every search in it (a
    sidecar's tenants compile the embed once, not once each)."""

    def rows(hint_ids, arrival, mask, pairs):
        return jax.vmap(
            lambda h, a, m: trace_features(TraceArrays(h, a, m), pairs,
                                           tau, H)
        )(hint_ids, arrival, mask)

    return jax.jit(rows)


def _matmul_dtype():
    """bf16 on TPU (MXU-native), f32 elsewhere (the CPU backend has no
    bf16xbf16->f32 dot)."""
    return jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32


def min_sq_distance(feats: jax.Array, archive: jax.Array,
                    valid_n: Optional[jax.Array] = None) -> jax.Array:
    """min_a ||f_p - a||^2 via the matmul expansion (MXU-friendly).

    feats [P,K], archive [A,K] -> [P]. bf16 inputs on TPU, f32 accumulation.

    ``valid_n`` (optional TRACED i32 scalar) is the archive's occupancy:
    rows at index >= valid_n are masked with :data:`MASK_BIG` so they
    never win the min — equivalent to calling with ``archive[:n]``
    while keeping the buffer shape fixed, so a caller that holds a
    fixed-capacity ring can grow its occupancy without a new jit
    specialization per size (compile-count pinned by
    tests/test_fused_loop.py). ``None`` keeps the pre-occupancy graph:
    every row is live — the in-repo search passes None, because its
    rings deliberately treat unoccupied slots as neutral 0.5 feature
    points (ScheduleSearch), and masking them out would change fitness.
    """
    dt = _matmul_dtype()
    f16 = feats.astype(dt)
    a16 = archive.astype(dt)
    cross = jax.lax.dot_general(
        f16, a16,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [P, A]
    f2 = jnp.sum(feats * feats, axis=-1, keepdims=True)  # [P,1]
    a2 = jnp.sum(archive * archive, axis=-1)  # [A]
    if valid_n is not None:
        a2 = jnp.where(jnp.arange(archive.shape[0]) < valid_n, a2,
                       MASK_BIG)
    d2 = f2 + a2[None, :] - 2.0 * cross
    return jnp.maximum(jnp.min(d2, axis=-1), 0.0)


def _min_sq_distance_best(feats: jax.Array, archive: jax.Array,
                          valid_n: Optional[jax.Array] = None) -> jax.Array:
    """The Pallas fused-min kernel on TPU (~10% whole-scorer win at
    production sizes, no [P,A] HBM round-trip), plain XLA elsewhere.
    Dispatch lives in pallas_score; lazily imported because that module
    imports this one."""
    from namazu_tpu.ops.pallas_score import min_sq_distance_auto

    return min_sq_distance_auto(feats, archive, valid_n=valid_n)


def _min_sq_pair_best(feats: jax.Array, archive: jax.Array,
                      failures: jax.Array,
                      archive_n: Optional[jax.Array] = None,
                      failure_n: Optional[jax.Array] = None
                      ) -> tuple[jax.Array, jax.Array]:
    """(novelty d2 [P], bug d2 [P]) against both archives in one pass:
    the Pallas pair kernel on TPU streams each feats tile through BOTH
    distance mins (one kernel launch, no [P] intermediate round-trips
    between them — the fused score epilogue of doc/performance.md
    "Fused search loop"); two XLA mins elsewhere. An occupancy of zero
    yields a neutral 0.0 distance instead of the mask identity: an
    empty ring carries no information, not an infinitely-far one."""
    from namazu_tpu.ops.pallas_score import min_sq_distance_pair_auto

    nov, bug = min_sq_distance_pair_auto(feats, archive, failures,
                                         archive_n=archive_n,
                                         failure_n=failure_n)
    if archive_n is not None:
        nov = jnp.where(archive_n > 0, nov, 0.0)
    if failure_n is not None:
        bug = jnp.where(failure_n > 0, bug, 0.0)
    return nov, bug


# -- population scoring -----------------------------------------------------


def trace_tables(traces: TraceArrays, H: int, weights: ScoreWeights,
                 with_faults: bool) -> Optional[_DelayTables]:
    """What :func:`score_population_multi` builds of its stacked traces
    ``[T, L]`` and of no population: in delay mode their
    :class:`_DelayTables` (leading dimension T), in order mode nothing
    (its per-trace tables depend on the static window and are built
    where they are used). A program that scores many populations
    against the same traces — the fused island step, G generations a
    dispatch — builds them once and hands them in as ``tables``."""
    if weights.order_mode:
        return None
    return jax.vmap(lambda tr: _delay_tables(tr, H, with_faults))(traces)


def score_population_multi(
    delays: jax.Array,  # [P, H]
    traces: TraceArrays,  # arrays with leading trace dim [T, L]
    pairs: jax.Array,  # [K, 2]
    archive: jax.Array,  # [A, K]
    failure_feats: jax.Array,  # [F, K]
    weights: ScoreWeights = ScoreWeights(),
    faults: Optional[jax.Array] = None,  # [P, H]
    coin: Optional[jax.Array] = None,  # [H]
    novelty_scale: Optional[jax.Array] = None,  # dynamic f32 scalar
    archive_n: Optional[jax.Array] = None,  # dynamic i32 occupancy
    failure_n: Optional[jax.Array] = None,  # dynamic i32 occupancy
    tables: Optional[_DelayTables] = None,  # trace_tables(traces, ...)
) -> tuple[jax.Array, jax.Array]:
    """The population scorer: fitness averaged over T recorded traces
    (novelty against ONE run is mostly its noise): (fitness [P], feats
    [P, T, K]). ONE compiled program on concrete arrays (the re-rank),
    inline under a trace. ``tables``: the traces' :func:`trace_tables`
    where the caller built them already; built here otherwise.

    With ``faults``/``coin``, the genome's fault half is part of the
    counterfactual: dropped events reshape the features, and a
    ``fault_cost`` per dropped event (each trace's dropped share,
    averaged over the traces) keeps "drop everything" from being the
    novelty optimum. In delay mode no per-event op runs under the
    population ``vmap``: the traces' tables are built once, blockwise
    for a long trace (see :func:`_population_features`).

    ``novelty_scale`` multiplies ``weights.novelty`` as a *traced*
    scalar — the novelty-anneal lever (exploration weight decays as the
    failure archive accumulates distinct signatures) without a new jit
    specialization per annealed value. ``None`` keeps the pre-anneal
    graph.

    ``archive_n``/``failure_n`` (traced i32 scalars) are ring
    occupancies for fixed-capacity archive buffers: rows past the
    occupancy are masked out of the distance min, equivalent to slicing
    ``archive[:n]`` but shape-stable, so one compiled specialization
    serves every occupancy (compile-count pinned by test). The search
    passes ``None`` (the default, and the pre-occupancy graphs) on
    purpose — its rings treat unoccupied slots as neutral 0.5 feature
    points, and masking them would change fitness."""
    args = (delays, traces, pairs, archive, failure_feats, weights, faults,
            coin, novelty_scale, archive_n, failure_n, tables)
    if _all_concrete(args):
        return _score_population_multi_jit(*args)
    if tables is None:
        tables = trace_tables(traces, delays.shape[-1], weights,
                              faults is not None)

    def per_trace(tr: TraceArrays, tb: Optional[_DelayTables]):
        """(feats [P, K], drop fraction [P]) against one trace."""
        f, ndrop = _population_features(delays, tr, pairs, weights,
                                        faults, coin, tables=tb)
        return f, ndrop / jnp.maximum(jnp.sum(tr.mask), 1)

    feats, frac = jax.vmap(per_trace)(traces, tables)  # [T, P, K], [T, P]
    feats = jnp.swapaxes(feats, 0, 1)  # [P, T, K]
    P, T, K = feats.shape
    flat = feats.reshape(P * T, K)
    nov_d2, bug_d2 = _min_sq_pair_best(flat, archive, failure_feats,
                                       archive_n=archive_n,
                                       failure_n=failure_n)
    novelty = nov_d2.reshape(P, T).mean(axis=1)
    bug = -bug_d2.reshape(P, T).mean(axis=1)
    delay_cost = jnp.mean(delays, axis=-1)
    fault_pen = (0.0 if faults is None
                 else weights.fault_cost * frac.mean(axis=0))
    w_nov = (weights.novelty if novelty_scale is None
             else weights.novelty * novelty_scale)
    fitness = (
        w_nov * novelty
        + weights.bug * bug
        - weights.delay_cost * delay_cost
        - fault_pen
    )
    return fitness, feats


_score_population_multi_jit = jax.jit(score_population_multi,
                                      static_argnames=("weights",))


def _all_concrete(args) -> bool:
    """No array of ``args`` a tracer, and jit not disabled (where the
    compiled twin would call straight back)."""
    return not (jax.config.jax_disable_jit or any(
        isinstance(x, jax.core.Tracer) for x in jax.tree.leaves(args)))


# -- long traces: blockwise first-occurrence --------------------------------

# how a delay-mode trace's per-trace tables (:func:`_delay_tables`) are
# built: blockwise past this length, below it the dense path is cheaper
# (one fused gather + scatter-min). Either way once per trace, not per
# genome. Order mode has a per-genome branch of its own at every
# length: its release times come from :func:`order_release_times`
# (counts over the whole trace), then one dense scatter-min.
LONG_TRACE_THRESHOLD = 1024
LONG_TRACE_CHUNK = 512


def scorer_branch(L: int, order_mode: bool = False) -> str:
    """The first-occurrence branch a step compiled for padded trace
    length ``L`` takes: ``"order"`` | ``"blockwise"`` | ``"dense"`` (in
    delay mode: how the trace's tables are built). One home for the
    rule, so that what the search counts per evolve
    (``nmz_evolve_requests_total{scorer}``) is what was compiled."""
    if order_mode:
        return "order"
    return "blockwise" if L > LONG_TRACE_THRESHOLD else "dense"


@functools.partial(jax.jit, static_argnames=("chunk",))
def first_occurrence_blockwise(
    delays: jax.Array,  # [H]
    hint_ids: jax.Array,  # [L], any length (padded internally)
    arrival: jax.Array,  # [L]
    mask: jax.Array,  # [L]
    chunk: int = LONG_TRACE_CHUNK,
    faults: Optional[jax.Array] = None,  # [H]
    coin: Optional[jax.Array] = None,  # [H]
    faultable: Optional[jax.Array] = None,  # [L]
) -> tuple[jax.Array, jax.Array]:
    """(first-occurrence times f32[H], dropped-event count i32) over an
    arbitrarily long trace via lax.scan.

    min is associative, so the [H] running minimum is a scan carry and the
    peak live buffer is one [chunk] block instead of the whole trace —
    the long-sequence analogue of blockwise attention for this workload
    (SURVEY.md section 5.7: schedule genomes over long event traces are
    this framework's long sequences). Fault drops are applied per chunk so
    a vmapped population never materialises a [P, L] drop mask.

    Jitted in its own right: a caller may run the scorer op by op
    (``jax.disable_jit``, a bare ``vmap`` of ``schedule_features``),
    and a bare ``lax.scan`` dispatched eagerly is lowered anew at every
    call — its body is a fresh closure, so no cache holds it. Under a
    jit of its own the scan is traced once per shape, eager ``vmap``s
    included; inside a compiled program (the island step, the reply's
    re-rank) the jit is inlined.
    """
    H = delays.shape[0]
    L = hint_ids.shape[0]
    n_chunks = -(-L // chunk)
    pad = n_chunks * chunk - L
    hint_ids = jnp.pad(hint_ids, (0, pad))
    arrival = jnp.pad(arrival, (0, pad))
    mask = jnp.pad(mask, (0, pad))
    if faultable is None:
        faultable = jnp.ones_like(mask)
    else:
        faultable = jnp.pad(faultable, (0, pad))

    def step(carry, blk):
        first, ndrop = carry
        h, a, m, fb = blk
        if faults is not None:
            # one home for the "non-faultable events never drop"
            # invariant: the same drop_mask the dense path uses
            drop = drop_mask(faults, coin, TraceArrays(h, a, m, fb))
            m = m & ~drop
            ndrop = ndrop + jnp.sum(drop)
        t = jnp.where(m, a + delays[h], BIG)
        first = first.at[h].min(t)
        return (first, ndrop), None

    init = (jnp.full((H,), BIG, jnp.float32), jnp.zeros((), jnp.int32))
    (first, ndrop), _ = jax.lax.scan(
        step,
        init,
        (
            hint_ids.reshape(n_chunks, chunk),
            arrival.reshape(n_chunks, chunk),
            mask.reshape(n_chunks, chunk),
            faultable.reshape(n_chunks, chunk),
        ),
    )
    return first, ndrop
