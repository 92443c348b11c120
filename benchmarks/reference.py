"""The plain reference: what a search request has to answer, worked out
from the storage files and the configuration alone, in straightforward
numpy. Imports nothing of the program and takes nothing the program has
made: no archive rows, no encoded traces, no pair sample.

Two parts.

**The state a request meets** (``SearchState``): the stored runs of a
campaign storage, read from ``trace.json`` / ``result.json``; each run's
hint buckets (fnv64a of the replay hint), arrival and release offsets;
the precedence-pair sample refitted to the occupied buckets; the novelty
ring (one row per stored run per request, oldest overwritten, neutral
0.5 where never written), the failure ring (one row per distinct
hint/entity sequence) and the reference trace(s) the counterfactual is
anchored on. This is what ``models/ingest.py`` and
``ops/trace_encoding.py`` have to produce; the device-resident rows of
the timed search are compared with it row for row.

**The scorer** (copied from ``bench.py::numpy_score`` and widened to
several reference traces, the configured weights and the annealed
novelty scale):

    fitness = w_nov * scale * mean_t min_a d2(f_t, a)
              - w_bug * mean_t min_f d2(f_t, fl)
              - w_delay * mean(delays)
    d2(f, c) = |f|^2 + |c|^2 - 2 f.c,  clamped at 0 after the min

**Release modes.** A search's table is read in one of two ways, and the
request states which (``search_params.release_mode``; read in ONE place,
``SearchState.__init__``, everything below asks the state):

* ``delay``: per-bucket delays, ``t = arrival + table[bucket]``;
* ``reorder``: per-bucket *priorities* in ``[0, max_interval]``, realized
  by the policy's reorder buffer. Events of a reference trace are
  batched into arrival windows of ``reorder_window`` seconds (index
  ``floor(arrival / window)``, taken in float32 from the float32
  arrivals the device holds; ``window = 0`` is one global window); a
  batch is released in ``(priority[bucket], arrival)`` order, ``gap``
  apart, starting at the window's end:
  ``t = (win + 1) * window + rank_in_window * gap``; masked events are
  absent. ``gap = max(reorder_gap, 1e-4)``, ``tau = gap / 2``, and the
  delay-cost weight is 0 (a uniform shift of priorities permutes
  nothing). Stored runs are embedded from their REALIZED releases in
  both modes, at the mode's ``tau``.

Not covered, and refused by name: a fault half (``max_fault > 0``),
guidance, a failure pool, the knowledge service.

The configuration states its precision: the f.c operands are rounded to
bfloat16 on the TPU (float32 elsewhere), products and sums are float32.
``score(..., operand=...)`` rounds exactly those operands (round to
nearest even, ``ml_dtypes``) and keeps everything else in float64, so
the reference exists at float32, at the stated precision and at the
step below (float8), which serves as the control.

What is compared (PERF.md has the readings): every answer the timed
path gave in the window -- the table and fitness of each reply, and the
fused island step's own best table and fitness where that request
improved it -- against the reference for that table in the state that
request met. An answer passes against EITHER the float32 reference or
the one at the stated operand precision, so a scorer more accurate than
the stated type is not refused:

    number = min(max_i |x_i - ref32_i|, max_i |x_i - ref_stated_i|)

in fitness units. PR 21's six-sigma tolerance against the float32
reference alone is not used: on campaign arrays the bfloat16 rounding
errors are coherent and the argmax selects for them (my chip runs,
PR 23: chip vs float32 up to 0.2 on fitness values of 0.2-0.4, float8
from 0.09), so it does not separate the stated type from the one below.
"""

from __future__ import annotations

import json
import os

import numpy as np

BIG = 1e9  # "never happens" release time, as the scorer's
NEUTRAL = 0.5  # a ring slot never written: "no information"
L_QUANTUM = 128  # encoded lengths are padded to a multiple of this


def _round_operand(x: np.ndarray, operand: str) -> np.ndarray:
    if operand == "float32":
        return x.astype(np.float32).astype(np.float64)
    import ml_dtypes  # ships with jax; only the controls need it

    return x.astype(np.float32).astype(
        getattr(ml_dtypes, operand)).astype(np.float64)


def window_index(arrival, window: float) -> np.ndarray:
    """The arrival window of each event: ``floor(arrival / window)`` in
    float32, as the float32 arrivals on the device are divided (float64
    here would move an event that sits on a window's edge);
    ``window = 0`` is one global window."""
    arrival = np.asarray(arrival, np.float32)
    if window <= 0:
        return np.zeros(arrival.shape, np.int64)
    return np.floor(arrival / np.float32(window)).astype(np.int64)


def events_on_a_window_edge(arrival, mask, window: float) -> int:
    """How many live events sit within 2 ulp of a window's edge: there a
    division that is not correctly rounded may put the event in the
    neighbouring window, which is no fault of the scorer's."""
    a = np.asarray(arrival, np.float32)[np.asarray(mask, bool)]
    if window <= 0 or not a.size:
        return 0
    q = a / np.float32(window)
    return int(((q != 0) & (np.abs(q - np.rint(q))
                            <= 2 * np.spacing(np.abs(q)))).sum())


def ordered_release(prio, hint_ids, arrival, mask, gap, window):
    """Release times f32[S, L] of one trace under ``S`` priority tables
    (module docstring, ``reorder``). The release slots of a window are
    the same under every table -- its end, then ``gap`` apart; a table
    only decides which of the window's events takes which slot."""
    prio = np.asarray(prio, np.float32)
    hint_ids = np.asarray(hint_ids)
    arrival = np.asarray(arrival, np.float32)
    t = np.full((len(prio), len(hint_ids)), BIG, np.float32)
    live = np.flatnonzero(np.asarray(mask, bool))
    win = window_index(arrival[live], window)
    for w in np.unique(win):
        batch = live[win == w]
        # by arrival (ties: position in the trace) once, then stably by
        # each table's priorities: (priority, arrival) order
        batch = batch[np.argsort(arrival[batch], kind="stable")]
        slots = ((np.float32(w) + np.float32(1.0)) * np.float32(window)
                 + np.arange(len(batch), dtype=np.float32)
                 * np.float32(gap))
        rank = np.argsort(prio[:, hint_ids[batch]], axis=-1, kind="stable")
        np.put_along_axis(t, batch[rank], slots[None, :], axis=1)
    return t


def features(delays, hint_ids, arrival, mask, pairs, tau, order=None):
    """Precedence features f64[S, K] of ``S`` tables against one
    encoded trace: the release time of every event (``order`` None: the
    table is delays; ``(gap, window)``: priorities), first release per
    hint bucket (the least over that bucket's events, bucket by bucket),
    then sigmoid((first[v] - first[u]) / tau)."""
    delays = np.asarray(delays, np.float32)
    S, H = delays.shape
    if order is None:
        t = (np.asarray(arrival, np.float32)[None, :]
             + delays[:, hint_ids]).astype(np.float32)
        t = np.where(np.asarray(mask, bool)[None, :], t, np.float32(BIG))
    else:
        t = ordered_release(delays, hint_ids, arrival, mask, *order)
    first = np.full((S, H), BIG, np.float32)
    for b in np.unique(hint_ids):
        first[:, b] = t[:, hint_ids == b].min(axis=1)
    du = first[:, pairs[:, 0]].astype(np.float64)
    dv = first[:, pairs[:, 1]].astype(np.float64)
    z = np.clip((dv - du) / tau, -30.0, 30.0)
    return 1.0 / (1.0 + np.exp(-z))


def _min_d2(feats, centres, operand):
    f = np.asarray(feats, np.float64)
    c = np.asarray(centres, np.float64)
    cross = _round_operand(f, operand) @ _round_operand(c, operand).T
    d2 = (f * f).sum(-1)[:, None] + (c * c).sum(-1)[None, :] - 2.0 * cross
    return np.maximum(d2.min(axis=1), 0.0)


def score(delays, traces, pairs, archive, failures, weights,
          novelty_scale=1.0, operand="float32"):
    """Fitness f64[S] of ``S`` tables. ``traces`` is a list of
    ``(hint_ids, arrival, mask)``; ``weights`` has ``novelty``, ``bug``,
    ``delay_cost``, ``tau`` and, where the tables are priorities,
    ``order`` = ``(gap, window)``. With a tuple of operand types, a
    dict of one fitness vector each (the features are worked out
    once)."""
    delays = np.asarray(delays, np.float32)
    operands = (operand,) if isinstance(operand, str) else tuple(operand)
    feats = [features(delays, np.asarray(hint_ids), arrival, mask,
                      np.asarray(pairs), weights["tau"],
                      weights.get("order"))
             for hint_ids, arrival, mask in traces]
    out = {}
    for op in operands:
        nov = sum(_min_d2(f, archive, op) for f in feats) / len(traces)
        bug = sum(_min_d2(f, failures, op) for f in feats) / len(traces)
        out[op] = (weights["novelty"] * novelty_scale * nov
                   - weights["bug"] * bug - weights["delay_cost"]
                   * delays.astype(np.float64).mean(-1))
    return out[operand] if isinstance(operand, str) else out


#: the precision below each stated operand type
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


# -- stored runs --------------------------------------------------------------


def fnv64a(data: bytes) -> int:
    h = 0xCBF29CE484222325
    for b in data:
        h = ((h ^ b) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return h


def stored_depth(storage_dir: str) -> int:
    """How many runs a storage holds: one past the last allocated run
    that has a result."""
    with open(os.path.join(storage_dir, "storage.json")) as f:
        allocated = int(json.load(f)["next_run"])
    depth = 0
    for i in range(allocated):
        if os.path.exists(os.path.join(storage_dir, f"{i:08x}",
                                       "result.json")):
            depth = i + 1
    return depth


class Run:
    """One stored run: per action its hint bucket, its entity (numbered
    by first appearance), its arrival and its release, both as offsets
    from the run's first; and whether validation passed."""

    def __init__(self, actions: list, ok: bool, H: int,
                 index: int = 0) -> None:
        self.ok, self.index = bool(ok), index
        arr, rel, hints, ents, seen = [], [], [], [], {}
        for a in actions:
            arrived = float(a.get("event_arrived") or 0.0)
            released = float(a.get("triggered_time") or 0.0)
            arr.append(arrived or released)
            rel.append(released or arrived)
            hint = a.get("event_hint") or (
                f"{a.get('event_class') or a['class']}:{a['entity']}")
            hints.append(fnv64a(hint.encode()) % H)
            ents.append(seen.setdefault(a["entity"], len(seen)))
        a0 = min((t for t in arr if t), default=0.0)
        r0 = min((t for t in rel if t), default=0.0)
        self.hint_ids = np.asarray(hints, np.int32)
        self.entity_ids = np.asarray(ents, np.int32)
        self.arrival = np.asarray(
            [t - a0 if t else i * 1e-3 for i, t in enumerate(arr)],
            np.float32)
        self.released = np.asarray(
            [t - r0 if t else i * 1e-3 for i, t in enumerate(rel)],
            np.float32)

    def signature(self) -> bytes:
        """Two runs that interleaved the same events in the same order
        are one failure signature (timing excluded)."""
        return self.hint_ids.tobytes() + b"|" + self.entity_ids.tobytes()


def read_runs(storage_dir: str, depth: int, H: int) -> list:
    """The first ``depth`` stored runs, each with its ``index``; one
    without a trace or a result is skipped, as an incomplete run is."""
    runs = []
    for i in range(depth):
        run = os.path.join(storage_dir, f"{i:08x}")
        try:
            with open(os.path.join(run, "trace.json")) as f:
                actions = json.load(f)
            with open(os.path.join(run, "result.json")) as f:
                ok = json.load(f)["successful"]
        except (OSError, ValueError, KeyError):
            continue
        runs.append(Run(actions, ok, H, index=i))
    return runs


# -- the pair sample ----------------------------------------------------------


def sample_pairs(K: int, H: int, seed: int) -> np.ndarray:
    """K ordered bucket pairs (u != v), uniform over all H buckets."""
    rng = np.random.RandomState(seed)
    u = rng.randint(0, H, size=K).astype(np.int32)
    v = rng.randint(0, H - 1, size=K).astype(np.int32)
    v = np.where(v >= u, v + 1, v).astype(np.int32)
    return np.stack([u, v], axis=1)


def informative_pairs(occupied, K: int, H: int, seed: int) -> np.ndarray:
    """K ordered pairs over the occupied buckets first (a seeded choice
    of K where they are more), filled up with uniform pairs."""
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


# -- the state a request meets ------------------------------------------------


class Refused(ValueError):
    """A search the reference does not cover, or a history it may not
    be held to: no comparison is made, the run gives no result."""


class SearchState:
    """The search state of one campaign storage, request after request.
    ``params`` are the request's stated ``search_params`` and
    ``ingest_params`` (K, H, seed, release mode, tau, weights, reference
    mode, anneal, trace cap); ``archive_rows`` / ``failure_rows`` the
    configuration's ring sizes. Delay- and reorder-mode searches,
    fault-free, unguided and pool-free."""

    def __init__(self, search_params: dict, ingest_params: dict,
                 archive_rows: int, failure_rows: int) -> None:
        sp, ip = search_params, ingest_params
        refused = [what for what, stated in (
            ("a fault half (max_fault > 0)", sp.get("max_fault", 0.0) > 0),
            ("guidance", sp.get("guidance") or ip.get("guidance")),
            ("a failure pool (failure_pool)", ip.get("failure_pool")),
            ("the knowledge service (knowledge)", ip.get("knowledge")),
        ) if stated]
        if refused:
            raise Refused("the reference does not cover "
                          + ", ".join(refused))
        self.K, self.H, self.seed = int(sp["K"]), int(sp["H"]), int(sp["seed"])
        # the ONE place the release mode is read (module docstring)
        self.release_mode = sp.get("release_mode", "delay")
        #: events of a stored run past which ``ingest`` refuses the
        #: history (0: never; a delay-mode run the program cut shows in
        #: the numbers compared, as it always did)
        self.trace_cap = 0
        if self.release_mode == "delay":
            self.order = None
            tau, delay_cost = float(sp["tau"]), float(sp["w_delay_cost"])
        elif self.release_mode == "reorder":
            gap = max(float(sp["reorder_gap"]), 1e-4)
            self.order = (gap, max(float(sp["reorder_window"]), 0.0))
            tau, delay_cost = gap * 0.5, 0.0
            self.trace_cap = int(ip.get("L", 0)
                                 or ip["order_mode_max_l"])
        else:
            raise Refused("the reference does not cover release_mode "
                          f"{self.release_mode!r}")
        self.weights = {"novelty": float(sp["w_novelty"]),
                        "bug": float(sp["w_bug"]),
                        "delay_cost": delay_cost, "tau": tau,
                        "order": self.order}
        self.max_interval = float(sp["max_interval"])
        self.min_signatures = int(sp.get("min_failure_signatures", 0))
        self.novelty_floor = float(sp.get("novelty_floor", 0.25))
        self.reference_mode = ip.get("reference_mode", "recent")
        self.max_references = int(ip.get("max_reference_traces", 4))
        self.pairs = sample_pairs(self.K, self.H, self.seed)
        self.archive = np.full((archive_rows, self.K), NEUTRAL)
        self.labels = np.zeros(archive_rows)
        self.failures = np.full((failure_rows, self.K), NEUTRAL)
        self._clear()
        self.traces: list = []

    def _clear(self) -> None:
        self._rows: dict = {}  # run index -> its row under these pairs
        self.archive[:] = NEUTRAL
        self.labels[:] = 0.0
        self.failures[:] = NEUTRAL
        self.archive_n = self.failure_n = 0
        self._slot_sig = [b""] * len(self.failures)

    def _row(self, run: Run) -> np.ndarray:
        """An executed run in feature space: its REALIZED releases,
        no further delay."""
        if run.index not in self._rows:
            self._rows[run.index] = features(
                np.zeros((1, self.H), np.float32), run.hint_ids,
                run.released, np.ones(len(run.hint_ids), bool),
                self.pairs, self.weights["tau"])[0]
        return self._rows[run.index]

    def ingest(self, runs: list) -> None:
        """One request: the whole stored history is fed again."""
        for run in runs:
            if self.trace_cap and len(run.hint_ids) > self.trace_cap:
                raise Refused(
                    f"stored run {run.index} has {len(run.hint_ids)} "
                    f"events, the request caps a trace at "
                    f"{self.trace_cap}: the program would score a cut "
                    "trace, and a cut hunt is not the hunt")
        occupied = {int(b) for r in runs for b in r.hint_ids}
        pairs = informative_pairs(occupied, self.K, self.H, self.seed)
        if not np.array_equal(pairs, self.pairs):
            self.pairs = pairs  # every stored feature was in the old space
            self._clear()
        for run in runs:
            row = self._row(run)
            slot = self.archive_n % len(self.archive)
            self.archive[slot] = row
            self.labels[slot] = 0.0 if run.ok else 1.0
            self.archive_n += 1
            if not run.ok and run.signature() not in self._slot_sig:
                slot = self.failure_n % len(self.failures)
                self.failures[slot] = row
                self._slot_sig[slot] = run.signature()
                self.failure_n += 1
        ok = [r for r in runs if r.ok]
        pool = ok or [r for r in runs if not r.ok]
        if self.reference_mode == "envelope" and ok:
            self.traces = [envelope(ok)]
        else:
            self.traces = [
                (r.hint_ids, r.arrival, np.ones(len(r.hint_ids), bool))
                for r in pool[::-1][:self.max_references]]

    def novelty_scale(self) -> float:
        n = sum(1 for s in self._slot_sig if s)
        if self.min_signatures <= 0 or n < self.min_signatures:
            return 1.0
        return max(self.novelty_floor, self.min_signatures / n)

    def held_mode(self) -> list:
        """What a search built from this request has to hold."""
        return [self.release_mode] + list(self.order or ())

    def events_on_a_window_edge(self) -> int:
        if self.order is None:
            return 0
        return sum(events_on_a_window_edge(arrival, mask, self.order[1])
                   for _hint_ids, arrival, mask in self.traces)

    def score(self, delays, operand="float32"):
        return score(np.atleast_2d(np.asarray(delays, np.float32)),
                     self.traces, self.pairs, self.archive, self.failures,
                     self.weights, self.novelty_scale(), operand)


def envelope(runs: list) -> tuple:
    """One event per observed bucket at its earliest arrival over the
    runs: ``(hint_ids, arrival, mask)``."""
    first: dict = {}
    for r in runs:
        for b, t in zip(r.hint_ids.tolist(), r.arrival.tolist()):
            if b not in first or t < first[b]:
                first[b] = t
    items = sorted(first.items(), key=lambda kv: kv[1])
    return (np.asarray([b for b, _ in items], np.int32),
            np.asarray([t for _, t in items], np.float32),
            np.ones(len(items), bool))


# -- what is compared ---------------------------------------------------------


def resident_gap(state: SearchState, resident: dict) -> dict:
    """The device-resident inputs of the timed search after its last
    request (``resident``: ``pairs``, ``archive``, ``labels``,
    ``failures``, ``archive_n``, ``failure_n`` and the reference traces
    ``hint_ids`` / ``arrival`` / ``mask`` [T, L]) against the state
    worked out from the storage: the largest row gap, and counts of
    what has to match exactly."""
    out = {"pairs_differ": int((np.asarray(resident["pairs"])
                                != state.pairs).sum()),
           "labels_differ": int((np.asarray(resident["labels"])
                                 != state.labels).sum()),
           "ring_counts_differ": int(
               int(resident["archive_n"]) != state.archive_n)
           + int(int(resident["failure_n"]) != state.failure_n)}
    out["archive_rows_gap"] = float(np.abs(
        np.asarray(resident["archive"], np.float64) - state.archive).max())
    out["failure_rows_gap"] = float(np.abs(
        np.asarray(resident["failures"], np.float64)
        - state.failures).max())
    # reference traces: bucket -> earliest time, trace by trace
    h, a, m = (np.asarray(resident[k]) for k in
               ("hint_ids", "arrival", "mask"))
    gap, differ = 0.0, abs(len(h) - len(state.traces))
    for t, (rh, ra, rm) in enumerate(state.traces[:len(h)]):
        got: dict = {}
        for b, x in zip(h[t][m[t]].tolist(), a[t][m[t]].tolist()):
            got[b] = min(x, got.get(b, BIG))
        want: dict = {}
        for b, x in zip(rh[rm].tolist(), ra[rm].tolist()):
            want[b] = min(x, want.get(b, BIG))
        differ += len(set(got) ^ set(want))
        gap = max([gap] + [abs(got[b] - want[b])
                           for b in set(got) & set(want)])
    out["reference_buckets_differ"] = int(differ)
    out["reference_times_gap"] = float(gap)
    return out


def fitness_gap(got, ref32, ref_stated) -> float:
    """The number compared for a set of answers (module docstring)."""
    got = np.asarray(got, np.float64)
    if got.size == 0:
        return 0.0
    if not np.isfinite(got).all():
        return float("inf")
    return float(min(np.abs(got - np.asarray(ref32)).max(),
                     np.abs(got - np.asarray(ref_stated)).max()))
