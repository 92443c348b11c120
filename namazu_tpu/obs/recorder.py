"""Flight recorder: bounded per-run event-timeline capture.

PR 1's metrics registry answers "how many / how fast"; this module
answers "what order did run X actually execute, and which policy
decision caused it". Every event that crosses a choke point the metrics
plane already instruments (hub interception, orchestrator enqueue/
decide, policy release, action dispatch, REST ack) also lands one
structured :class:`EventRecord` in the current run's trace, keyed by the
event's uuid so all lifecycle stamps join on one record. The search
plane contributes its own track: one :class:`RunTrace` entry per
``search.run()`` round plus one per schedule install, and every policy
decision is tagged with the schedule-generation id active when it was
made — the causal link from "this event was delayed 80 ms" back to "by
the table evolved in generations 64..128".

Memory is bounded twice: a ring of ``max_runs`` runs (oldest evicted
whole) and ``max_records`` event records per run (later events count in
``dropped_records`` instead of allocating). Everything is thread-safe —
hub/orchestrator/policy/REST threads stamp concurrently while an
exporter snapshots.

The hot path honors ``obs_enabled = false`` exactly like the metrics
helpers: every recording function bails on the first
``metrics.enabled()`` check, and with no run begun (e.g. a bare
MockOrchestrator hub) recording is a no-op too — no run, no allocation.

Correlation key: the ``run_id``. :func:`begin_run` pushes it into
``namazu_tpu/utils/log.py`` so every log line carries the same id the
trace records and ``GET /traces/<run_id>`` serve; logs, metrics, and
traces join on one key. Exporters live in ``namazu_tpu/obs/export.py``;
the record schema is documented in doc/observability.md.
"""

from __future__ import annotations

import threading
import time
import uuid as _uuid
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from namazu_tpu.obs import metrics
from namazu_tpu.utils import log as _log

__all__ = [
    "EventRecord", "RunTrace", "FlightRecorder",
    "recorder", "set_recorder", "reset",
    "begin_run", "end_run", "current_run_id", "current_generation_id",
    "record_intercepted", "record_enqueued", "record_decided",
    "record_decision", "record_released", "record_dispatched",
    "record_acked", "record_edge", "record_generation", "record_install",
    "record_annotation",
]

#: lifecycle stamp names, in causal order (export sorts tracks by the
#: first present stamp; the acceptance invariant "monotonic per-track
#: timestamps" holds because each stage stamps with time.monotonic()).
#: ``reconciled`` exists only on edge-decided records: the moment their
#: async backhaul folded back into the orchestrator — the anchor of the
#: causality plane's ``backhaul`` latency segment (obs/causality.py)
STAGES = ("intercepted", "enqueued", "decided", "released",
          "dispatched", "acked", "reconciled")


class EventRecord:
    """One event's full lifecycle through the control plane."""

    __slots__ = ("event_id", "entity", "endpoint", "event_class", "hint",
                 "policy", "decision", "action_class", "action_kind", "t",
                 "ctx")

    def __init__(self, event_id: str, entity: str = "",
                 endpoint: str = "", event_class: str = "",
                 hint: str = "") -> None:
        self.event_id = event_id
        self.entity = entity
        self.endpoint = endpoint
        self.event_class = event_class
        self.hint = hint
        self.policy = ""
        #: what the policy chose: delay/priority, table source
        #: ("hash" | "table"), schedule generation id, fault flag, ...
        self.decision: Dict[str, Any] = {}
        self.action_class = ""
        self.action_kind = ""
        #: stage -> monotonic stamp (subset of STAGES)
        self.t: Dict[str, float] = {}
        #: the event's span context in wire form (obs/context.py):
        #: causal parent, Lamport clock at mint, origin process — None
        #: for events from pre-context clients
        self.ctx: Optional[Dict[str, Any]] = None

    def copy(self) -> "EventRecord":
        """Deep-enough copy for lock-free export: writers keep mutating
        the live ``t``/``decision`` dicts after a snapshot, and
        iterating those concurrently would race."""
        dup = EventRecord(self.event_id, self.entity, self.endpoint,
                          self.event_class, self.hint)
        dup.policy = self.policy
        dup.decision = dict(self.decision)
        dup.action_class = self.action_class
        dup.action_kind = self.action_kind
        dup.t = dict(self.t)
        dup.ctx = dict(self.ctx) if self.ctx else None
        return dup

    def first_stamp(self) -> Optional[float]:
        for name in STAGES:
            if name in self.t:
                return self.t[name]
        return None

    def to_jsonable(self, anchor: float = 0.0) -> Dict[str, Any]:
        """Record as a plain dict; timestamps become offsets (seconds,
        µs precision) from ``anchor`` so two runs' dumps diff cleanly."""
        doc = {
            "event": self.event_id,
            "entity": self.entity,
            "endpoint": self.endpoint,
            "event_class": self.event_class,
            "hint": self.hint,
            "policy": self.policy,
            "decision": dict(self.decision),
            "action_class": self.action_class,
            "action_kind": self.action_kind,
            "t": {name: round(self.t[name] - anchor, 6)
                  for name in STAGES if name in self.t},
        }
        # additive: context-less records (old clients, obs-off mints)
        # serialize exactly as before, so existing dumps stay diffable
        if self.ctx:
            doc["ctx"] = dict(self.ctx)
        return doc


class RunTrace:
    """One run's bounded record table plus the search-plane round log."""

    def __init__(self, run_id: str, max_records: int = 4096,
                 now: Optional[float] = None,
                 wall: Optional[float] = None) -> None:
        self.run_id = run_id
        self.max_records = max_records
        self.started_mono = time.monotonic() if now is None else now
        self.started_wall = time.time() if wall is None else wall
        self.ended_mono: Optional[float] = None
        #: record-creation attempts refused by the ``max_records`` cap.
        #: Counts attempts, not distinct events (a dropped event's later
        #: lifecycle stamps each count one more): > 0 means the trace is
        #: incomplete, and the magnitude tracks how much traffic arrived
        #: after the cap — without a per-uuid dropped set to maintain.
        self.dropped_records = 0
        #: search-plane entries: {"kind": "generation"|"install", ...}
        self.generations: List[Dict[str, Any]] = []
        self._records: "OrderedDict[str, EventRecord]" = OrderedDict()
        self._lock = threading.Lock()

    def record_for(self, event_id: str, create: bool = True,
                   decision: Optional[Dict[str, Any]] = None,
                   **fields: Any) -> Optional[EventRecord]:
        """The record keyed by ``event_id``, created on first use (unless
        the per-run cap is hit, which counts in ``dropped_records``).
        ``fields`` fill still-empty identity attributes; ``decision``
        entries merge into the record's decision dict — under the run
        lock, so a concurrent :meth:`snapshot` can never copy a
        half-written decision (plain ``dict.update`` outside the lock
        inserts key by key)."""
        with self._lock:
            rec = self._records.get(event_id)
            if rec is None:
                if not create:
                    return None
                if len(self._records) >= self.max_records:
                    self.dropped_records += 1
                    return None
                rec = self._records[event_id] = EventRecord(event_id)
            for name, value in fields.items():
                if value and not getattr(rec, name):
                    setattr(rec, name, value)
            if decision:
                rec.decision.update(decision)
            return rec

    def stamp(self, event_id: str, stage: str,
              now: Optional[float] = None, **fields: Any) -> None:
        rec = self.record_for(event_id, **fields)
        if rec is not None:
            rec.t[stage] = time.monotonic() if now is None else now

    def add_generation(self, entry: Dict[str, Any],
                       cap: int = 1024) -> None:
        with self._lock:
            if len(self.generations) < cap:
                self.generations.append(entry)

    def __len__(self) -> int:
        with self._lock:
            return len(self._records)

    def snapshot(self) -> Dict[str, Any]:
        """Consistent COPY for the exporters (records in interception
        order, the generation log, the run envelope): writers keep
        mutating live records after this returns, so everything handed
        out is copied under the lock."""
        with self._lock:
            copies = [rec.copy() for rec in self._records.values()]
            generations = [dict(g) for g in self.generations]
            dropped = self.dropped_records
        records = [{"rec": rec, "json": rec.to_jsonable(self.started_mono)}
                   for rec in copies]
        return {
            "run_id": self.run_id,
            "started_wall": self.started_wall,
            "started_mono": self.started_mono,
            "ended_mono": self.ended_mono,
            "records": records,
            "generations": generations,
            "dropped_records": dropped,
        }

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            n, dropped = len(self._records), self.dropped_records
            n_gen = len(self.generations)
        return {
            "run_id": self.run_id,
            "started": self.started_wall,
            "records": n,
            "dropped_records": dropped,
            "search_rounds": n_gen,
            "ended": self.ended_mono is not None,
        }


class FlightRecorder:
    """Ring of the last ``max_runs`` :class:`RunTrace` instances plus
    the process-wide schedule-generation counter."""

    def __init__(self, max_runs: int = 8, max_records: int = 4096) -> None:
        self.max_runs = max_runs
        self.max_records = max_records
        self._lock = threading.Lock()
        self._runs: "OrderedDict[str, RunTrace]" = OrderedDict()
        self._current: Optional[RunTrace] = None
        # tenancy plane (doc/tenancy.md): namespace tag -> concurrently
        # OPEN RunTrace. Pinned runs record in parallel with (and
        # independent of) the `_current` run; signals tagged with a
        # namespace resolve here, untagged ones keep resolving to
        # `_current` — so N tenants' records never interleave
        self._pinned: Dict[str, RunTrace] = {}
        # cumulative GA generations evolved in this process; decisions
        # snapshot it so a replayed delay points back at the search
        # round that produced its table
        self._gen_seq = 0

    # -- run lifecycle ----------------------------------------------------

    def begin_run(self, run_id: Optional[str] = None,
                  now: Optional[float] = None,
                  wall: Optional[float] = None) -> str:
        """Open (and make current) a new run trace; evicts the oldest
        run beyond ``max_runs``. Always tags the log plane with the run
        id; allocates a trace only while observability is enabled."""
        rid = run_id or _uuid.uuid4().hex[:12]
        _log.set_run_id(rid)
        if not metrics.enabled():
            with self._lock:
                self._current = None
            return rid
        run = RunTrace(rid, self.max_records, now=now, wall=wall)
        with self._lock:
            self._runs[rid] = run
            self._runs.move_to_end(rid)
            self._evict_runs()
            self._current = run
        return rid

    def _evict_runs(self) -> None:
        """Ring eviction; caller holds the lock. Still-OPEN pinned runs
        are never evicted (a tenant's live trace must not vanish under
        it because seven siblings started later) — the ring can
        temporarily exceed ``max_runs`` by the number of live pins,
        which the lease table bounds."""
        if len(self._runs) <= self.max_runs:
            return
        protected = {run.run_id for run in self._pinned.values()}
        for rid in list(self._runs):
            if len(self._runs) <= self.max_runs:
                return
            if rid in protected or self._runs[rid] is self._current:
                continue
            del self._runs[rid]

    # -- pinned (tenancy) runs --------------------------------------------

    def begin_pinned(self, tag: str, run_id: Optional[str] = None,
                     now: Optional[float] = None,
                     wall: Optional[float] = None) -> str:
        """Open a run trace for namespace ``tag`` WITHOUT making it the
        process-current run (tenancy plane: N runs record concurrently).
        Returns the run id; with observability disabled no trace is
        allocated and namespaced recording stays a no-op."""
        rid = run_id or _uuid.uuid4().hex[:12]
        if not metrics.enabled():
            with self._lock:
                self._pinned.pop(tag, None)
            return rid
        run = RunTrace(rid, self.max_records, now=now, wall=wall)
        with self._lock:
            self._runs[rid] = run
            self._runs.move_to_end(rid)
            self._pinned[tag] = run
            self._evict_runs()
        return rid

    def end_pinned(self, tag: str, now: Optional[float] = None) -> None:
        with self._lock:
            run = self._pinned.pop(tag, None)
            if run is not None:
                run.ended_mono = time.monotonic() if now is None else now

    def pinned(self, tag: str) -> Optional[RunTrace]:
        return self._pinned.get(tag)

    def pinned_run_id(self, tag: str) -> Optional[str]:
        run = self._pinned.get(tag)
        return None if run is None else run.run_id

    def end_run(self, run_id: Optional[str] = None,
                now: Optional[float] = None) -> None:
        closed = False
        with self._lock:
            run = self._current
            if run is not None and (run_id is None or run.run_id == run_id):
                run.ended_mono = time.monotonic() if now is None else now
                self._current = None
                closed = True
        # clear the log tag only when this call actually ended the run
        # it names: with two orchestrators in one process, A's shutdown
        # must not strip the [run-id] tag off B's still-active run. The
        # log-tag comparison covers the obs-disabled case, where a run
        # id exists for correlation but no trace was allocated.
        if closed or run_id is None or _log.get_run_id() == run_id:
            _log.set_run_id(None)

    def current(self) -> Optional[RunTrace]:
        return self._current

    def run(self, run_id: str) -> Optional[RunTrace]:
        with self._lock:
            if run_id == "latest":
                return next(reversed(self._runs.values()), None)
            return self._runs.get(run_id)

    def runs(self) -> List[RunTrace]:
        with self._lock:
            return list(self._runs.values())

    def summaries(self) -> List[Dict[str, Any]]:
        return [r.summary() for r in self.runs()]

    # -- search-plane counter ---------------------------------------------

    def advance_generations(self, n: int) -> int:
        with self._lock:
            self._gen_seq += int(n)
            return self._gen_seq

    def generation_id(self) -> int:
        return self._gen_seq


_recorder = FlightRecorder()


def recorder() -> FlightRecorder:
    return _recorder


def set_recorder(r: FlightRecorder) -> FlightRecorder:
    """Swap the process-global recorder (tests); returns the old one."""
    global _recorder
    old, _recorder = _recorder, r
    return old


def reset(max_runs: int = 8, max_records: int = 4096) -> FlightRecorder:
    """Fresh empty recorder (tests)."""
    set_recorder(FlightRecorder(max_runs, max_records))
    return _recorder


def _trace_for(sig) -> Optional[RunTrace]:
    """The run trace a signal's records belong to: signals tagged with
    a tenancy namespace (``sig._ns``, set at the ingress edge and
    propagated event -> action) resolve to that namespace's PINNED run;
    untagged signals keep resolving to the process-current run. A
    namespaced signal with no pinned run records NOWHERE — leaking a
    tenant's records into the default run would break the isolation
    the tenancy plane promises (doc/tenancy.md)."""
    tag = getattr(sig, "_ns", "")
    if tag:
        return _recorder.pinned(tag)
    return _recorder.current()


def begin_run(run_id: Optional[str] = None) -> str:
    # a new run means a new search: clear the stall detector's
    # fitness/novelty windows so run A's final plateau (or its absolute
    # fitness scale) cannot read as run B's stall during B's healthy
    # early rounds — the ab harness runs many experiments per process
    from namazu_tpu.obs import analytics

    analytics.reset_stall_detector()
    return _recorder.begin_run(run_id)


def end_run(run_id: Optional[str] = None) -> None:
    _recorder.end_run(run_id)


def current_run_id() -> Optional[str]:
    """The active run's id. Falls back to the log-plane tag so the id
    survives ``obs_enabled = false`` (no trace is allocated then, but
    /healthz and log correlation still name the run — liveness is not
    telemetry)."""
    run = _recorder.current()
    if run is not None:
        return run.run_id
    rid = _log.get_run_id()
    return None if rid == "-" else rid


def current_generation_id() -> int:
    return _recorder.generation_id()


# -- recording hooks (control plane) --------------------------------------
#
# Each takes the signal at its choke point; all are no-ops when
# observability is disabled or no run is open.

def record_intercepted(event, endpoint: str,
                       now: Optional[float] = None) -> None:
    if not metrics.enabled():
        return
    run = _trace_for(event)
    if run is None:
        return
    run.stamp(event.uuid, "intercepted", now=now,
              entity=event.entity_id, endpoint=endpoint,
              event_class=event.class_name(), hint=event.replay_hint(),
              ctx=getattr(event, "_obs_ctx", None))


def record_enqueued(event, policy: str,
                    now: Optional[float] = None) -> None:
    if not metrics.enabled():
        return
    run = _trace_for(event)
    if run is None:
        return
    run.stamp(event.uuid, "enqueued", now=now,
              entity=event.entity_id, policy=policy)


def record_decided(event, policy: str,
                   now: Optional[float] = None) -> None:
    if not metrics.enabled():
        return
    run = _trace_for(event)
    if run is None:
        return
    run.stamp(event.uuid, "decided", now=now,
              entity=event.entity_id, policy=policy)


def record_decision(event, policy: str, **detail: Any) -> None:
    """Attach the policy's choice (delay/priority, table source,
    schedule-generation id, fault flag, ...) to the event's record."""
    if not metrics.enabled():
        return
    run = _trace_for(event)
    if run is None:
        return
    run.record_for(event.uuid, entity=event.entity_id, policy=policy,
                   decision=detail)


def record_released(event, policy: str,
                    now: Optional[float] = None) -> None:
    """The policy's delay queue released the event (dwell is over)."""
    if not metrics.enabled():
        return
    run = _trace_for(event)
    if run is None:
        return
    run.stamp(event.uuid, "released", now=now,
              entity=event.entity_id, policy=policy)


def record_edge(event, endpoint: str, policy: str, action,
                decision: Dict[str, Any]) -> None:
    """One edge-decided event's COMPLETE record in a single pass
    (zero-RTT backhaul reconciliation, doc/performance.md): identity,
    decision detail (``decision_source="edge"``, ``table_version``,
    delay), the synthesized action, and every lifecycle stamp from the
    edge's own clocks — one run-lock acquisition instead of the six a
    stage-by-stage replay would cost per event."""
    if not metrics.enabled():
        return
    run = _trace_for(event)
    if run is None:
        return
    detail = {name: decision[name] for name in
              ("delay", "source", "decision_source", "table_version",
               "lc", "o")
              if name in decision}
    rec = run.record_for(
        event.uuid, entity=event.entity_id, endpoint=endpoint,
        event_class=event.class_name(), hint=event.replay_hint(),
        policy=policy, decision=detail,
        action_class=action.class_name(), action_kind="edge",
        ctx=getattr(event, "_obs_ctx", None))
    if rec is None:
        return
    now = time.monotonic()
    t0 = decision.get("t_intercepted")
    t1 = decision.get("t_dispatched")
    t0 = now if t0 is None else float(t0)
    t1 = now if t1 is None else float(t1)
    # dict assignment is GIL-atomic and snapshot copies under the run
    # lock, so stamping outside record_for's lock is race-free enough
    # (the same contract stamp() relies on). ``reconciled`` = THIS
    # moment — the backhaul-lag anchor the causality plane attributes
    # the async window to.
    rec.t.update(intercepted=t0, enqueued=t0, decided=t0,
                 released=t1, dispatched=t1, reconciled=now)


def record_dispatched(action, kind: str,
                      now: Optional[float] = None) -> None:
    """The answering action left the orchestrator action loop. Keyed by
    the cause event's uuid when the action has one (so all stamps land
    on one record), else by the action's own (shell/nop injections)."""
    if not metrics.enabled():
        return
    run = _trace_for(action)
    if run is None:
        return
    key = action.event_uuid or action.uuid
    run.stamp(key, "dispatched", now=now,
              entity=action.entity_id,
              event_class=action.event_class, hint=action.event_hint,
              action_class=action.class_name(), action_kind=kind)


def record_acked(action, now: Optional[float] = None) -> None:
    """The inspector acknowledged the action over REST."""
    if not metrics.enabled():
        return
    run = _trace_for(action)
    if run is None:
        return
    run.stamp(action.event_uuid or action.uuid, "acked", now=now,
              entity=action.entity_id)


# -- recording hooks (search plane) ---------------------------------------

def record_generation(backend: str, generations: int, elapsed: float,
                      best_fitness: float,
                      now: Optional[float] = None,
                      archive_entries: Optional[int] = None,
                      failure_entries: Optional[int] = None,
                      distinct_failures: Optional[int] = None,
                      host_io_s: Optional[float] = None,
                      fit_curve: Optional[list] = None) -> None:
    """One ``search.run()`` round: advances the process generation
    counter and logs the round on the run's search track. The optional
    archive occupancies feed the experiment plane's convergence/stall
    analysis (obs/analytics.py convergence_stats) — recorded only when
    the caller supplies them, so pre-existing traces and exporters see
    the same entries as before."""
    if not metrics.enabled():
        return
    gen_end = _recorder.advance_generations(generations)
    run = _recorder.current()
    if run is None:
        return
    end = time.monotonic() if now is None else now
    entry = {
        "kind": "generation",
        "backend": backend,
        "gen_start": gen_end - generations,
        "gen_end": gen_end,
        "t_start": end - elapsed,
        "t_end": end,
        "best_fitness": best_fitness,
    }
    if archive_entries is not None:
        entry["archive_entries"] = int(archive_entries)
    if failure_entries is not None:
        entry["failure_entries"] = int(failure_entries)
    if distinct_failures is not None:
        entry["distinct_failures"] = int(distinct_failures)
    if host_io_s is not None:
        # fused-loop rounds: wall time spent in the overlapped host-I/O
        # lane — the experiment plane derives the per-generation
        # host-gap share from it (obs/analytics.py convergence_stats)
        entry["host_io_s"] = round(float(host_io_s), 6)
    if fit_curve:
        # fused-loop rounds: the PER-GENERATION global-best history the
        # host lane drained (one point per generation, not per round) —
        # intra-round convergence at a resolution the round-level
        # fitness_curve cannot see. Tail-capped like the other curves.
        entry["fit_curve"] = [round(float(v), 6) for v in fit_curve[-64:]]
    run.add_generation(entry)


def record_install(source: str, generation: Optional[int] = None,
                   now: Optional[float] = None) -> None:
    """A delay/fault table was installed on the policy hot path."""
    if not metrics.enabled():
        return
    run = _recorder.current()
    if run is None:
        return
    run.add_generation({
        "kind": "install",
        "source": source,
        "generation": (_recorder.generation_id()
                       if generation is None else generation),
        "t": time.monotonic() if now is None else now,
    })


def record_annotation(kind: str, now: Optional[float] = None,
                      **fields: Any) -> None:
    """Stamp an out-of-band annotation onto the current run's search
    track (e.g. an SLO breach transition, obs/slo.py). Annotations ride
    the same bounded ``generations`` list the exporters already carry;
    consumers dispatch on ``kind`` and ignore unknown kinds, so new
    annotation kinds never break existing traces."""
    if not metrics.enabled():
        return
    run = _recorder.current()
    if run is None:
        return
    entry = {"kind": str(kind),
             "t": time.monotonic() if now is None else now}
    entry.update(fields)
    run.add_generation(entry)
