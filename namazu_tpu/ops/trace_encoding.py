"""Host-side trace featurization: recorded runs -> fixed-shape arrays.

The control plane records variable-length action traces (JSON). The search
plane needs static shapes for XLA, so each trace is encoded as:

* ``hint_ids``  int32[L] — replay hint hashed (fnv64a) into H buckets; the
  hint bucket is the unit the genome's delay table indexes, generalizing
  the replayable policy's ``hash(seed, hint) % max`` delays;
* ``entity_ids`` int32[L] — entity index (stable per experiment);
* ``arrival``   float32[L] — event arrival offset in seconds from run start
  (triggered/arrival times when recorded; index spacing otherwise);
* ``mask``      bool[L] — valid positions (traces are padded/truncated).

Precedence *pairs* are sampled over hint buckets (not positions) so the
feature space is comparable across runs — a failed run's trace and a
candidate schedule's counterfactual interleaving land in the same space.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from namazu_tpu.policy.replayable import fnv64a
# the hint-format version lives with the signal classes that define the
# hints (stdlib-only, so the control plane can stamp runs without numpy);
# re-exported here because the search plane reads it alongside encoding
from namazu_tpu.signal.base import HINT_SPACE  # noqa: F401  (re-export)
from namazu_tpu.utils.trace import SingleTrace

DEFAULT_L = 256  # default length quantum for encoded traces
DEFAULT_H = 256  # hint buckets (genome length)
DEFAULT_K = 256  # precedence pairs (feature dimension)



def checkpoint_hint_space(z) -> str:
    """Hint-space tag of a checkpoint npz mapping; checkpoints predating
    the tag were built from bare content hints ("content-v1"). One home
    for the default so the fast-install path (policy/tpu.py) and the
    full load (models/search.py) can never disagree on compatibility."""
    return str(z["hint_space"]) if "hint_space" in z else "content-v1"

# encoded lengths are rounded up to a multiple of this so XLA sees a
# handful of static shapes instead of one per run length
L_QUANTUM = 128


def _auto_length(n: int) -> int:
    """Padded length for an n-event trace: next multiple of L_QUANTUM,
    at least one quantum. No truncation — a real ZooKeeper run produces
    thousands of packet events and the search must see all of them
    (long traces score blockwise, ops/schedule.py)."""
    return max(L_QUANTUM, -(-n // L_QUANTUM) * L_QUANTUM)


def hint_bucket(hint: str, n_buckets: int = DEFAULT_H) -> int:
    return fnv64a(hint.encode()) % n_buckets


def fault_coin(seed: int, H: int = DEFAULT_H) -> np.ndarray:
    """Deterministic per-bucket fault coin f32[H] in [0, 1).

    The policy drops an event iff ``coin[bucket] < faults[bucket]``
    (policy/tpu.py _fault_for) and the scorer removes exactly those events
    from the counterfactual (ops/schedule.py drop_mask) — same formula,
    same coin, so a searched fault table replays to the interleaving it
    was scored as."""
    return np.array(
        [fnv64a(f"{seed}|fault|{h}".encode()) % 10_000 / 10_000.0
         for h in range(H)],
        np.float32,
    )


def class_supports_fault(class_name: str) -> bool:
    """Whether events of this signal class carry a fault action (packet
    drop / EIO) — i.e. whether the control plane can actually realize a
    drop for them (policy/tpu.py _action_for checks
    ``default_fault_action() is not None``). Unknown or unrecorded
    classes are treated as faultable (the pre-flag behavior)."""
    if not class_name:
        return True
    cached = _FAULTABLE_CACHE.get(class_name)
    if cached is not None:
        return cached
    from namazu_tpu.signal.base import SignalError, get_signal_class
    from namazu_tpu.signal.event import Event

    try:
        cls = get_signal_class(class_name)
    except SignalError:
        result = True
    else:
        result = (isinstance(cls, type) and issubclass(cls, Event)
                  and cls.default_fault_action
                  is not Event.default_fault_action)
    _FAULTABLE_CACHE[class_name] = result
    return result


_FAULTABLE_CACHE: Dict[str, bool] = {}


class EncodedTrace:
    """One trace in array form (plain numpy; converted to jnp at the device
    boundary)."""

    def __init__(self, hint_ids, entity_ids, arrival, mask, truncated=0,
                 faultable=None):
        self.hint_ids = np.asarray(hint_ids, np.int32)
        self.entity_ids = np.asarray(entity_ids, np.int32)
        self.arrival = np.asarray(arrival, np.float32)
        self.mask = np.asarray(mask, bool)
        self.truncated = int(truncated)  # events beyond an explicit L cap
        # events whose cause class supports a fault action; defaults to
        # all-faultable (pre-flag encodes score exactly as before)
        self.faultable = (np.ones_like(self.mask) if faultable is None
                          else np.asarray(faultable, bool))

    @property
    def length(self) -> int:
        return int(self.mask.sum())


def encode_trace(
    trace: SingleTrace,
    L: Optional[int] = None,
    H: int = DEFAULT_H,
    entity_index: Optional[Dict[str, int]] = None,
    realized: bool = False,
) -> EncodedTrace:
    """Encode a recorded action trace.

    Each action's preserved cause-event hint (``action.event_hint``, set by
    ``Action.for_event``) is the semantic identity; actions recorded
    without one (e.g. traces from before a semantic parser was attached)
    fall back to cause-event class + entity.

    ``realized=True`` timestamps each event at its RELEASE
    (``triggered_time`` — where the recording policy actually placed it in
    the interleaving) instead of its arrival. This is the right view for
    *embedding* executed runs into feature space: a failure induced by
    injected delays carries its signature in the release times, while its
    arrivals look like any healthy run's — arrival-anchored failure
    features would let the zero-delay genome sit at distance ~0 from the
    failure archive and the search would feel no pressure to inject
    anything. Counterfactual *reference* traces keep the default
    (arrival) anchoring: candidate release times are
    ``arrival + delay``, so both sides of the feature distance live in
    release-time space.

    ``L=None`` (default) sizes the arrays to the whole trace — nothing is
    ever silently dropped. An explicit ``L`` is a hard cap for callers
    that want to bound device memory; events past it are truncated (the
    returned ``EncodedTrace.truncated`` says how many).
    """
    views = encode_trace_views(trace, L=L, H=H, entity_index=entity_index)
    return views[1] if realized else views[0]


def encode_trace_views(
    trace: SingleTrace,
    L: Optional[int] = None,
    H: int = DEFAULT_H,
    entity_index: Optional[Dict[str, int]] = None,
) -> Tuple[EncodedTrace, EncodedTrace]:
    """Both time views of one trace in a single pass:
    ``(arrival_view, realized_view)``.

    Identity arrays (hint buckets, entities, mask, faultable flags) are
    computed once and SHARED between the two EncodedTraces; only the
    time vectors differ. Callers that need both views (the policy's
    history ingest encodes the counterfactual reference from arrivals
    and the archive embedding from releases) pay one encode instead of
    two.
    """
    entity_index = entity_index if entity_index is not None else {}
    if L is None:
        L = _auto_length(len(trace))
    hint_ids = np.zeros(L, np.int32)
    entity_ids = np.zeros(L, np.int32)
    arrival = np.zeros(L, np.float32)
    released = np.zeros(L, np.float32)
    mask = np.zeros(L, bool)
    faultable = np.ones(L, bool)

    # Arrival view: anchor on the cause event's ARRIVAL at the
    # orchestrator when the trace recorded it (Action.event_arrived,
    # round-3 field; reference semantics: BasicSignal.Arrived,
    # signal.go:75-191) — triggered_time contains the recording
    # policy's own injected delay, so a counterfactual anchored on it
    # would evolve against the recorder's jitter instead of the
    # system's natural interleaving. Realized view: the opposite
    # preference — release times ARE the interleaving the run executed.
    # Either view falls back to the other's timestamp when one was not
    # recorded.
    arr_times: List[float] = []
    rel_times: List[float] = []
    for a in trace:
        arrived = getattr(a, "event_arrived", None) or 0.0
        rel = a.triggered_time or 0.0
        arr_times.append(arrived if arrived else rel)
        rel_times.append(rel if rel else arrived)
    a0 = min((t for t in arr_times if t), default=0.0)
    r0 = min((t for t in rel_times if t), default=0.0)

    for i, action in enumerate(trace):
        if i >= L:
            break
        ent = action.entity_id
        if ent not in entity_index:
            entity_index[ent] = len(entity_index)
        hint = getattr(action, "event_hint", "") or \
            f"{action.event_class or action.class_name()}:{ent}"
        hint_ids[i] = hint_bucket(hint, H)
        entity_ids[i] = entity_index[ent]
        arrival[i] = (arr_times[i] - a0) if arr_times[i] else i * 1e-3
        released[i] = (rel_times[i] - r0) if rel_times[i] else i * 1e-3
        mask[i] = True
        faultable[i] = class_supports_fault(
            getattr(action, "event_class", ""))
    truncated = max(0, len(trace) - L)
    return (
        EncodedTrace(hint_ids, entity_ids, arrival, mask,
                     truncated=truncated, faultable=faultable),
        EncodedTrace(hint_ids, entity_ids, released, mask,
                     truncated=truncated, faultable=faultable),
    )


def encode_event_stream(
    hints: Sequence[str],
    arrivals: Optional[Sequence[float]] = None,
    entities: Optional[Sequence[str]] = None,
    L: Optional[int] = None,
    H: int = DEFAULT_H,
) -> EncodedTrace:
    """Encode a live event stream (the TPU policy's view of the current
    run) from raw replay hints. ``L=None`` sizes to the whole stream."""
    if L is None:
        L = _auto_length(len(hints))
    n = min(len(hints), L)
    hint_ids = np.zeros(L, np.int32)
    entity_ids = np.zeros(L, np.int32)
    arrival = np.zeros(L, np.float32)
    mask = np.zeros(L, bool)
    ent_index: Dict[str, int] = {}
    for i in range(n):
        hint_ids[i] = hint_bucket(hints[i], H)
        if entities is not None:
            e = entities[i]
            if e not in ent_index:
                ent_index[e] = len(ent_index)
            entity_ids[i] = ent_index[e]
        arrival[i] = arrivals[i] if arrivals is not None else i * 1e-3
        mask[i] = True
    return EncodedTrace(hint_ids, entity_ids, arrival, mask,
                        truncated=max(0, len(hints) - L))


def sample_pairs(
    K: int = DEFAULT_K, H: int = DEFAULT_H, seed: int = 0
) -> np.ndarray:
    """Deterministically sample K ordered hint-bucket pairs (u != v); the
    precedence of bucket-u's first event vs bucket-v's first event is one
    feature dimension."""
    rng = np.random.RandomState(seed)
    u = rng.randint(0, H, size=K).astype(np.int32)
    v = rng.randint(0, H - 1, size=K).astype(np.int32)
    v = np.where(v >= u, v + 1, v).astype(np.int32)  # ensure u != v
    return np.stack([u, v], axis=1)  # [K, 2]


def informative_pairs(
    occupied: Sequence[int],
    K: int = DEFAULT_K,
    H: int = DEFAULT_H,
    seed: int = 0,
) -> np.ndarray:
    """K ordered hint-bucket pairs concentrated on the buckets that
    actually occur in the recorded traces.

    ``sample_pairs`` draws uniformly over all H buckets; with H=64 and
    ~8 occupied buckets the expected number of informative pairs (both
    ends occupied) is < 1, making the failure signature invisible in
    feature space. Enumerating the occupied-bucket pairs first makes
    every realizable precedence a feature dimension; the remainder (if
    any) is filled with uniform pairs so future, unseen buckets still
    project somewhere."""
    occ = sorted({int(b) for b in occupied})
    pairs = [(u, v) for u in occ for v in occ if u != v]
    rng = np.random.RandomState(seed)
    if len(pairs) >= K:
        idx = rng.choice(len(pairs), size=K, replace=False)
        return np.array([pairs[i] for i in sorted(idx)], np.int32)
    fill = sample_pairs(K - len(pairs), H, seed)
    if not pairs:
        return fill
    return np.concatenate([np.array(pairs, np.int32), fill])


def envelope_trace(encs: Sequence[EncodedTrace]) -> EncodedTrace:
    """Per-bucket minimum-arrival envelope of several encoded traces.

    The scorer's features depend only on each hint bucket's FIRST
    occurrence (ops/schedule.py first_occurrence), so a synthetic trace
    with one event per observed bucket at its minimum arrival over the
    inputs is feature-equivalent to the tightest lower envelope of those
    runs. Used as the counterfactual anchor for repro-rate search:
    recorded arrivals include whatever delays the recording policy
    injected, and the min over several runs is the best available proxy
    for the *natural* (uninspected) arrival the next run will produce —
    so a delay table evolved against the envelope transfers."""
    firsts: Dict[int, float] = {}
    ents: Dict[int, int] = {}
    flts: Dict[int, bool] = {}
    for e in encs:
        hid = e.hint_ids[e.mask]
        arr = e.arrival[e.mask]
        ent = e.entity_ids[e.mask]
        flt = e.faultable[e.mask]
        for b, t, en, fb in zip(hid, arr, ent, flt):
            b = int(b)
            if b not in firsts or t < firsts[b]:
                firsts[b] = float(t)
                ents[b] = int(en)
                flts[b] = bool(fb)
    items = sorted(firsts.items(), key=lambda kv: kv[1])
    L = _auto_length(len(items))
    hint_ids = np.zeros(L, np.int32)
    entity_ids = np.zeros(L, np.int32)
    arrival = np.zeros(L, np.float32)
    mask = np.zeros(L, bool)
    faultable = np.ones(L, bool)
    for i, (b, t) in enumerate(items):
        hint_ids[i] = b
        entity_ids[i] = ents[b]
        arrival[i] = t
        mask[i] = True
        faultable[i] = flts[b]
    return EncodedTrace(hint_ids, entity_ids, arrival, mask,
                        faultable=faultable)


def pad_trace_row(enc: EncodedTrace, L: int) -> Dict[str, np.ndarray]:
    """One trace's scoring arrays right-padded to ``L`` — 0 for
    ids/times, False for the mask/faultable flags. The ONE home for the
    pad fills, shared by :func:`stack_traces` and the fused loop's
    device-resident trace store (models/search.py ``_ResidentTraces``):
    a resident row sliced back to a batch's length must be
    value-identical to the host stacker's padding, or the island step
    and the host-staged scorers would diverge on the pad region."""
    def pad(a, fill):
        n = L - a.shape[0]
        if n <= 0:
            return a
        return np.concatenate([a, np.full((n,), fill, a.dtype)])

    return {
        "hint": pad(enc.hint_ids, 0),
        "ent": pad(enc.entity_ids, 0),
        "arr": pad(enc.arrival, 0),
        "mask": pad(enc.mask, False),
        "flt": pad(enc.faultable, False),
    }


def stack_traces(traces: Sequence[EncodedTrace]) -> Tuple[np.ndarray, ...]:
    """Stack encoded traces into batched arrays [T, L]
    ``(hint_ids, entity_ids, arrival, mask, faultable)``, right-padding
    ragged lengths to the longest (auto-length encodes make ragged
    batches the normal case). Pad fills live in :func:`pad_trace_row`."""
    L = max(t.hint_ids.shape[0] for t in traces)
    rows = [pad_trace_row(t, L) for t in traces]
    return (
        np.stack([r["hint"] for r in rows]),
        np.stack([r["ent"] for r in rows]),
        np.stack([r["arr"] for r in rows]),
        np.stack([r["mask"] for r in rows]),
        np.stack([r["flt"] for r in rows]),
    )
