"""Event-lifecycle spans + the domain metric vocabulary.

One home for every metric name the system emits (the schema
``doc/observability.md`` documents), and the span-stamping helpers that
thread an event's lifecycle through the stack:

====================  =====================================================
span                  stamped by
====================  =====================================================
``intercepted``       EndpointHub.post_event — the moment an inspector's
                      event enters the orchestrator process
``enqueued``          Orchestrator._event_loop — handed to the active
                      policy (queue-dwell starts here)
``decided``           Orchestrator._event_loop — queue_event returned,
                      i.e. the policy chose this event's delay/priority
``dispatched``        Orchestrator._action_loop — the answering action
                      left for its endpoint (or ran orchestrator-side)
``acked``             RestEndpoint DELETE — the inspector acknowledged
                      the action over the wire
====================  =====================================================

Spans are monotonic-clock floats stored in a per-signal dict
(``sig._obs_spans``); :func:`carry` copies them from the cause event onto
its answering action (signal/action.py ``Action.for_event``) so latencies
survive the event->action hand-off. Every helper here starts with the
``metrics.enabled()`` check — the disabled per-event cost is one global
read and a function call, nothing else (the micro-assert in
tests/test_obs.py pins this down).
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import os
import threading
import time
from typing import Optional

from namazu_tpu.obs import metrics
from namazu_tpu.utils import timesource

SPANS_ATTR = "_obs_spans"

# -- metric name schema (see doc/observability.md) ----------------------

EVENTS_INTERCEPTED = "nmz_events_intercepted_total"
QUEUE_DWELL = "nmz_event_queue_dwell_seconds"
POLICY_DECISIONS = "nmz_policy_decisions_total"
DECISION_LATENCY = "nmz_policy_decision_latency_seconds"
ACTIONS_DISPATCHED = "nmz_actions_dispatched_total"
EVENT_E2E = "nmz_event_e2e_seconds"
REST_REQUESTS = "nmz_rest_requests_total"
REST_ACKS = "nmz_rest_acks_total"
REST_ACK_LATENCY = "nmz_rest_ack_latency_seconds"
SCHED_QUEUE_DEPTH = "nmz_sched_queue_depth"
SCHED_QUEUE_WAIT = "nmz_sched_queue_wait_seconds"
SEARCH_GENERATIONS = "nmz_search_generations_total"
SEARCH_GEN_RATE = "nmz_search_generations_per_sec"
SEARCH_BEST_FITNESS = "nmz_search_best_fitness"
SEARCH_ARCHIVE = "nmz_search_archive_entries"
SEARCH_INSTALLS = "nmz_search_installs_total"
SCORER_THROUGHPUT = "nmz_scorer_schedules_per_sec"
SEARCH_PHASE = "nmz_search_phase_seconds"
RUN_PHASE = "nmz_run_phase_seconds"
SEARCH_HOST_GAP = "nmz_search_host_gap_share"
SEARCH_DEVICE_TRACES = "nmz_search_device_traces_total"
SPAN_ROWS_DROPPED = "nmz_span_rows_dropped_total"
INGEST_RUNS = "nmz_ingest_runs_total"
INGEST_EMBED_CALLS = "nmz_ingest_embed_calls_total"
INGEST_EVENTS = "nmz_ingest_events_total"
INGEST_CACHED_RUNS = "nmz_ingest_cached_runs_total"
# a stored history opened from its watermark (storage/naive.py): runs
# allocated at each open or refresh, and those of them it did not visit
STORAGE_OPEN_RUNS = "nmz_storage_open_runs_total"
STORAGE_OPEN_SETTLED_RUNS = "nmz_storage_open_settled_runs_total"
EVOLVE_REQUESTS = "nmz_evolve_requests_total"
EVOLVE_TABLE_REQUESTS = "nmz_evolve_table_requests_total"
RERANK_REQUESTS = "nmz_rerank_requests_total"
RING_ROWS_WRITTEN = "nmz_ring_rows_written_total"
RING_ROWS_OVERWRITTEN = "nmz_ring_rows_overwritten_total"
FAILURE_SIGNATURES_DEDUPED = "nmz_failure_signatures_deduped_total"
# one trace length per search (models/search.py ``_hold_length``): rows
# the resident reference store took, gave up or staged anew; the traces
# embedded and those of them shorter than the search's class; and how
# often a run past the class stepped it
RESIDENT_TRACE_ROWS = "nmz_resident_trace_rows_total"
EMBED_TRACES = "nmz_embed_traces_total"
EMBED_TRACES_BELOW_CLASS = "nmz_embed_traces_below_class_total"
LENGTH_CLASS_STEPS = "nmz_length_class_steps_total"
# the policy's reorder buffer (release_mode "reorder"): windows drained,
# those whose paced drain ended after the NEXT window's boundary (the
# scorer assumes a window's slots run from its own close), and how many
# events a drained window held
REORDER_WINDOWS = "nmz_reorder_windows_total"
REORDER_WINDOW_OVERRUNS = "nmz_reorder_window_overruns_total"
REORDER_WINDOW_EVENTS = "nmz_reorder_window_events"
COMPILES = "nmz_compiles_total"
COMPILE_SECONDS = "nmz_compile_seconds"
#: the jax.monitoring event of one jaxpr->MLIR lowering
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
SEARCH_STALL = "nmz_search_stall"
SIDECAR_REQUESTS = "nmz_sidecar_requests_total"
ENTITY_LABEL_OVERFLOW = "nmz_entity_label_overflow_total"

# event-plane fast path (doc/performance.md): how full the batches
# actually run, and what each client-side HTTP round trip costs
EVENT_BATCH = "nmz_event_batch_size"
TRANSPORT_RTT = "nmz_transport_rtt_seconds"

# the negotiated wire codec (doc/performance.md "Binary wire + sharded
# edge"): payload bytes by codec + op — the JSON-vs-binary byte savings
# made visible on /fleet — and how many connections negotiated what
WIRE_BYTES = "nmz_wire_bytes_total"
CODEC_NEGOTIATIONS = "nmz_codec_negotiations_total"
SHM_RING_FULL = "nmz_shm_ring_full_total"

#: power-of-two batch-occupancy buckets — the interesting question is
#: "are batches amortizing anything" (1 vs 2-8 vs full), not sub-unit
#: latency resolution
BATCH_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0,
                 512.0, 1024.0)

#: event-stage latency buckets: the decision/dispatch segments run in
#: the tens of microseconds at edge rates, so the default 500µs floor
#: made HOTSTAGE and stage-p99 bucket-floor artifacts — sub-millisecond
#: bounds restore resolution where the serving plane actually lives.
#: The federation merge segregates (warns, never blends) pushes from
#: producers still on the old layout (obs/federation.py).
STAGE_BUCKETS = (0.000025, 0.00005, 0.0001, 0.00025, 0.0005, 0.001,
                 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                 1.0, 2.5)

# resilience plane (doc/robustness.md): unroutable-action drops and
# liveness-watchdog stall declarations, by entity
ACTIONS_UNROUTABLE = "nmz_actions_unroutable_total"
ENTITY_STALLED = "nmz_entity_stalled_total"

# zero-RTT edge dispatch (doc/performance.md "Zero-RTT dispatch"):
# events decided at the edge against a published delay table (counted
# when their backhaul reconciles into the orchestrator), and the
# monotonic version of the currently published table
EDGE_DECISIONS = "nmz_edge_decisions_total"
TABLE_VERSION = "nmz_table_version"

# edge observability (doc/observability.md "Fleet telemetry"): how far
# behind the async backhaul runs (edge decision stamp -> orchestrator
# reconcile stamp, same-host CLOCK_MONOTONIC), how stale the edge's
# held table is vs its last server confirmation, the edge's parked-heap
# depth, and the table version the edge currently decides with
EDGE_BACKHAUL_LAG = "nmz_edge_backhaul_lag_seconds"
EDGE_TABLE_STALENESS = "nmz_edge_table_staleness_seconds"
EDGE_PARKED = "nmz_edge_parked_events"
EDGE_TABLE_VERSION_HELD = "nmz_edge_table_version"
# search-install -> edge-decision propagation (ROADMAP item 3): the
# TablePublisher stamps each published table with its install time and
# every edge sync that adopts the table observes the gap (same-host
# CLOCK_MONOTONIC) — the first-class histogram behind `tools top`'s
# SKEW column, which shows versions-behind but not seconds-behind
TABLE_PROPAGATION = "nmz_table_propagation_seconds"

# fleet telemetry federation (doc/observability.md "Fleet telemetry"):
# relay push outcomes (producer side), fleet occupancy (aggregator
# side), SLO burn rates + breach transitions, and campaign slot
# outcomes (the supervisor's own producer metrics)
TELEMETRY_PUSHES = "nmz_telemetry_pushes_total"
TELEMETRY_FORWARD_DROPPED = "nmz_telemetry_forward_dropped_total"
FLEET_INSTANCES = "nmz_fleet_instances"
FLEET_STALE_INSTANCES = "nmz_fleet_stale_instances"
SLO_BURN = "nmz_slo_burn"
SLO_BREACHES = "nmz_slo_breaches_total"
CAMPAIGN_SLOTS = "nmz_campaign_slots_total"
# progress documents a campaign supervisor published, by path: folded
# from the slot's own runs, or walked over the whole stored history
CAMPAIGN_PROGRESS_FOLDS = "nmz_campaign_progress_folds_total"
# tenancy plane (doc/tenancy.md): per-namespace serving telemetry —
# the `run` label is the namespace name, the /fleet RUN dimension
TENANCY_EVENTS = "nmz_tenancy_events_total"
TENANCY_PARKED = "nmz_tenancy_parked"
TENANCY_RUNS = "nmz_tenancy_runs"
TENANCY_RECLAIMS = "nmz_tenancy_reclaims_total"
REST_CONN_THREADS = "nmz_rest_conn_threads"
REST_CONNS_QUEUED = "nmz_rest_conns_queued"
# fleet placement plane (doc/tenancy.md "Fleet of fleets"): pool-level
# lease migrations by reason (drain = operator-requested, death = TTL /
# staleness declared the host dead), admission-control refusals, and the
# placement service's live occupancy (hosts by liveness, pool leases,
# placements still waiting for an eligible host)
FLEET_MIGRATIONS = "nmz_fleet_migrations_total"
FLEET_ADMISSION_REJECTIONS = "nmz_fleet_admission_rejections_total"
FLEET_POOL_HOSTS = "nmz_fleet_pool_hosts"
FLEET_POOL_LEASES = "nmz_fleet_pool_leases"
FLEET_POOL_PENDING = "nmz_fleet_pool_pending_placements"

# chaos + survivability plane (doc/robustness.md "Chaos plane"):
# injected faults by point, ingress backpressure rejections, the
# server-requested Retry-After delays the transceiver honored, and the
# crash-recovery journal's traffic
CHAOS_FAULTS = "nmz_chaos_faults_injected_total"
INGRESS_REJECTIONS = "nmz_ingress_rejections_total"
TRANSPORT_RETRY_AFTER = "nmz_transport_retry_after_seconds"
JOURNAL_EVENTS = "nmz_journal_events_total"
JOURNAL_RECOVERED = "nmz_journal_recovered_events_total"

# global failure-knowledge plane (doc/knowledge.md): cross-campaign
# pool traffic, warm-start installs, the shared surrogate's training
# cadence, and the service's tenant/pool occupancy
KNOWLEDGE_PUSHES = "nmz_knowledge_pushes_total"
KNOWLEDGE_PULLS = "nmz_knowledge_pulls_total"
KNOWLEDGE_DEDUPE = "nmz_knowledge_dedupe_hits_total"
KNOWLEDGE_WARMSTART = "nmz_knowledge_warmstart_installs_total"
KNOWLEDGE_SURROGATE_ROUNDS = "nmz_knowledge_surrogate_train_rounds_total"
KNOWLEDGE_TENANTS = "nmz_knowledge_tenants"
KNOWLEDGE_POOL = "nmz_knowledge_pool_entries"
KNOWLEDGE_OUTAGES = "nmz_knowledge_outages_total"
# knowledge fan-in (M orchestrator hosts pushing concurrently): requests
# currently inside the service handler, and how long each waited for the
# shared-state lock — the serialize-behind-one-lock regression detector
KNOWLEDGE_FANIN_INFLIGHT = "nmz_knowledge_fanin_inflight"
KNOWLEDGE_FANIN_LOCK_WAIT = "nmz_knowledge_fanin_lock_wait_seconds"

# triage plane (doc/observability.md "Triage"): minimization probe
# traffic split by mode (simulated = free predicted_gain scoring,
# replayed = real campaign-runner executions), the last minimization's
# size ratio (minimal flips / candidate flips), dossier pulls against
# the knowledge wire, and how many failure signatures this process
# holds dossiers for (the /fleet SIGS column)
TRIAGE_PROBES = "nmz_triage_probes_total"
TRIAGE_MINIMIZATION_RATIO = "nmz_triage_minimization_ratio"
TRIAGE_DOSSIER_PULLS = "nmz_triage_dossier_pulls_total"
TRIAGE_SIGNATURES = "nmz_triage_signatures"

# causality plane (doc/observability.md "Causality"): each event's
# intercepted->acked span decomposed into named segments — queue (hub
# queue dwell), decision (policy), parking (the injected delay),
# dispatch (action loop), wire (dispatch -> inspector ack); edge events
# contribute edge_parking (local decide -> local release) and backhaul
# (edge dispatch -> orchestrator reconcile). The central segments
# telescope: their sum IS the intercepted->acked span, so "where does
# the millisecond go" is a histogram query, not a bench run.
EVENT_STAGE = "nmz_event_stage_seconds"

# guidance plane (doc/search.md): relation-coverage occupancy of the
# campaign's CoverageMap (covered bits / bitmap width) and the size of
# its one-sided frontier — the live face of the relation-coverage curve
# /analytics serves post-hoc
RELATION_COVERAGE = "nmz_relation_coverage"
RELATION_ONE_SIDED = "nmz_relation_one_sided"

# experiment plane (cross-run aggregates, set by obs/analytics.py when a
# payload is computed — GET /analytics, nmz-tpu tools report)
EXPERIMENT_RUNS = "nmz_experiment_runs"
EXPERIMENT_FAILURES = "nmz_experiment_failures"
EXPERIMENT_FAILURE_RATE = "nmz_experiment_failure_rate"
EXPERIMENT_UNIQUE = "nmz_experiment_unique_interleavings"
EXPERIMENT_COVERAGE = "nmz_experiment_interleaving_coverage"
EXPERIMENT_NOVELTY = "nmz_experiment_novelty_last_window"
EXPERIMENT_TTFF = "nmz_experiment_time_to_first_failure_seconds"
EXPERIMENT_RUNS_TO_REPRO = "nmz_experiment_mean_runs_to_reproduce"

# campaign progress plane (obs/stats.py sequential statistics, published
# live by the campaign supervisor after every slot and by the analytics
# fold — doc/observability.md "Calibration & progress"): the measured
# repro rate with its Wilson bounds, throughput in repros/hour, the
# next-repro ETA forecast, how many more runs a target-width CI needs,
# and the band SPRT's in/out-of-band verdict (1 in band, 0 out, unset
# while undecided). Federated through /fleet as the RATE and ETA columns
CAMPAIGN_RATE = "nmz_campaign_repro_rate"
CAMPAIGN_RATE_CI_LOW = "nmz_campaign_repro_rate_ci_low"
CAMPAIGN_RATE_CI_HIGH = "nmz_campaign_repro_rate_ci_high"
CAMPAIGN_REPROS_PER_HOUR = "nmz_campaign_repros_per_hour"
CAMPAIGN_ETA_NEXT = "nmz_campaign_eta_next_repro_seconds"
CAMPAIGN_RUNS_TO_CI = "nmz_campaign_runs_to_ci_width"
CAMPAIGN_IN_BAND = "nmz_campaign_in_band"
CAMPAIGN_REPROS_PER_HOUR_VIRTUAL = "nmz_campaign_repros_per_hour_virtual"

# virtual-clock plane (doc/performance.md "Virtual clock"): how much
# wall time the discrete-event fast-forward saved (virtual elapsed /
# wall elapsed) and how long the pinning rule held the clock at wall
# rate (real I/O, running entities, busy queues). Wall-denominated
# surfaces (SPRT budgets, calibration artifacts) NEVER read these
VCLOCK_SPEEDUP = "nmz_vclock_speedup_ratio"
VCLOCK_PINNED = "nmz_vclock_pinned_seconds_total"


#: distinct ``entity`` label values admitted per registry before new
#: entities fold into "_other" — inspectors can mint an entity per
#: observed process/connection, and unbounded label cardinality would
#: grow the registry (and every /metrics scrape) without limit over a
#: long experiment
MAX_ENTITY_LABELS = 64

_entity_lock = threading.Lock()


def _entity_label(reg, entity: str) -> str:
    # locked: hub/orchestrator/policy/REST threads all admit entities
    # concurrently, and a racy lazy-init or check-then-add would split
    # one entity's samples across its own series and "_other"
    with _entity_lock:
        seen = getattr(reg, "_obs_entity_labels", None)
        if seen is None:
            seen = reg._obs_entity_labels = set()
        if entity in seen:
            return entity
        if len(seen) >= MAX_ENTITY_LABELS:
            # the fold is itself observable: a dashboard showing flat
            # per-entity series while this counter climbs is sampling a
            # collapsed label space, not a quiet system
            reg.counter(
                ENTITY_LABEL_OVERFLOW,
                "entity label admissions folded into _other "
                "(MAX_ENTITY_LABELS cap hit)",
            ).inc()
            return "_other"
        seen.add(entity)
        return entity


# -- span stamping ------------------------------------------------------

def mark(sig, name: str, now: Optional[float] = None) -> None:
    """Stamp ``sig`` with the monotonic time of lifecycle point ``name``.

    Stamps read the process TimeSource — ``time.monotonic()`` under the
    default wall source, the jumpable virtual clock under
    ``run --virtual-clock`` — so every span delta (and the queue-dwell a
    shutdown drain attributes to still-resident events) is denominated
    in the same domain the delays themselves were scheduled in
    (doc/performance.md "Virtual clock")."""
    if not metrics.enabled():
        return
    spans = getattr(sig, SPANS_ATTR, None)
    if spans is None:
        spans = {}
        setattr(sig, SPANS_ATTR, spans)
    spans[name] = timesource.get().now() if now is None else now


def span(sig, name: str) -> Optional[float]:
    spans = getattr(sig, SPANS_ATTR, None)
    return spans.get(name) if spans else None


def latency(sig, since: str, now: Optional[float] = None) -> Optional[float]:
    """Seconds elapsed since span ``since`` was stamped, or None."""
    t0 = span(sig, since)
    if t0 is None:
        return None
    return (timesource.get().now() if now is None else now) - t0


def span_delta(sig, since: str, until: str) -> Optional[float]:
    """Seconds between two already-stamped spans, or None when either
    is missing — the per-segment read the stage attribution uses."""
    spans = getattr(sig, SPANS_ATTR, None)
    if not spans:
        return None
    t0 = spans.get(since)
    t1 = spans.get(until)
    if t0 is None or t1 is None:
        return None
    return t1 - t0


def carry(dst, src) -> None:
    """Attach the cause event's span dict to its answering action.

    The dict is SHARED, not copied: the orchestrator's event loop may
    still be stamping ``decided`` while a zero-delay dequeue is already
    constructing the action on another thread — sharing makes every
    stamp visible on both signals regardless of that race (dict access
    is GIL-atomic)."""
    if not metrics.enabled():
        return
    spans = getattr(src, SPANS_ATTR, None)
    if spans is not None:
        setattr(dst, SPANS_ATTR, spans)


# -- recording helpers (control plane) ----------------------------------

def event_intercepted(endpoint: str, entity: str, n: int = 1) -> None:
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.counter(
        EVENTS_INTERCEPTED,
        "events entering the orchestrator, by transport endpoint",
        ("endpoint", "entity"),
    ).labels(endpoint=endpoint, entity=_entity_label(reg, entity)).inc(n)


def policy_decision(policy: str, entity: str,
                    decision_latency: Optional[float]) -> None:
    """One policy decision (delay/priority chosen for an event)."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.counter(
        POLICY_DECISIONS,
        "events a policy decided a schedule for",
        ("policy", "entity"),
    ).labels(policy=policy, entity=_entity_label(reg, entity)).inc()
    if decision_latency is not None:
        reg.histogram(
            DECISION_LATENCY,
            "interception -> policy decision (hub queue + queue_event)",
            ("policy",),
        ).labels(policy=policy).observe(decision_latency)


def queue_dwell(policy: str, entity: str,
                seconds: Optional[float]) -> None:
    """How long an event sat in the policy's delay queue (the injected
    fuzz delay plus scheduling overhead)."""
    if seconds is None or not metrics.enabled():
        return
    reg = metrics.get()
    reg.histogram(
        QUEUE_DWELL,
        "policy enqueue -> release (injected delay + overhead)",
        ("policy", "entity"),
    ).labels(policy=policy,
             entity=_entity_label(reg, entity)).observe(seconds)


def action_dispatched(kind: str, e2e: Optional[float],
                      n: int = 1) -> None:
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.counter(
        ACTIONS_DISPATCHED,
        "actions leaving the orchestrator action loop",
        ("kind",),
    ).labels(kind=kind).inc(n)
    if e2e is not None:
        reg.histogram(
            EVENT_E2E,
            "interception -> action dispatch, end to end",
        ).observe(e2e)


def action_unroutable(entity: str) -> None:
    """An action dropped because no endpoint ever carried an event for
    its entity (EndpointHub.send_action) — the counter that replaces
    silent log-and-drop during long experiments."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.counter(
        ACTIONS_UNROUTABLE,
        "actions dropped for lack of an entity -> endpoint route",
        ("entity",),
    ).labels(entity=_entity_label(reg, entity)).inc()


def entity_stalled(entity: str) -> None:
    """The liveness watchdog declared an entity dead (no event within
    the configured timeout while events sat parked on its behalf)."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.counter(
        ENTITY_STALLED,
        "liveness-watchdog stall declarations (parked events force-"
        "released)",
        ("entity",),
    ).labels(entity=_entity_label(reg, entity)).inc()


def edge_decision(entity: str, n: int = 1) -> None:
    """``n`` edge-decided events reconciled into the orchestrator via
    asynchronous backhaul (the zero-RTT dispatch path) — every one was
    dispatched at the edge without a central round trip."""
    if n <= 0 or not metrics.enabled():
        return
    reg = metrics.get()
    reg.counter(
        EDGE_DECISIONS,
        "events decided and dispatched at the edge against a "
        "published delay table",
        ("entity",),
    ).labels(entity=_entity_label(reg, entity)).inc(n)


def table_version(version: int) -> None:
    """The monotonic version of the currently published delay table
    (bumped on every search-plane install, withdrawal, or
    suspend/resume — policy/edge_table.py)."""
    if not metrics.enabled():
        return
    metrics.get().gauge(
        TABLE_VERSION,
        "monotonic version of the published hash->delay table",
    ).set(version)


def edge_backhaul_lag(entity: str, seconds: float) -> None:
    """One edge-decided event's decision->reconcile lag, observed at
    ``Orchestrator._ingest_edge_batch`` (the edge stamps and the
    orchestrator clock share CLOCK_MONOTONIC on one host)."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.histogram(
        EDGE_BACKHAUL_LAG,
        "edge decision stamp -> orchestrator backhaul reconcile",
        ("entity",),
    ).labels(entity=_entity_label(reg, entity)).observe(max(0.0, seconds))


def edge_table_staleness(entity: str, seconds: float) -> None:
    """Seconds since this edge last confirmed its held table version
    against the server (0 while on the central wire — central dispatch
    cannot be stale)."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.gauge(
        EDGE_TABLE_STALENESS,
        "seconds since the edge's held table was last confirmed "
        "against the server",
        ("entity",),
    ).labels(entity=_entity_label(reg, entity)).set(max(0.0, seconds))


def edge_parked(entity: str, depth: int) -> None:
    """Events parked in the edge dispatcher's delayed-release heap."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.gauge(
        EDGE_PARKED,
        "events parked in the edge dispatcher's delayed-release heap",
        ("entity",),
    ).labels(entity=_entity_label(reg, entity)).set(depth)


def edge_table_version_held(entity: str, version: int) -> None:
    """The table version this edge currently decides with (0 = central
    fallback); the fleet view diffs it against ``nmz_table_version`` to
    surface table-version skew."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.gauge(
        EDGE_TABLE_VERSION_HELD,
        "table version the edge currently decides with (0 = central)",
        ("entity",),
    ).labels(entity=_entity_label(reg, entity)).set(version)


# -- fleet telemetry federation (doc/observability.md) --------------------

def telemetry_push(ok: bool) -> None:
    """One relay push cycle's outcome (producer side)."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        TELEMETRY_PUSHES, "telemetry relay push cycles", ("ok",),
    ).labels(ok=str(bool(ok)).lower()).inc()


def telemetry_forward_dropped(n: int = 1) -> None:
    """Foreign telemetry docs dropped from a full forward buffer (the
    federation hop stayed bounded through an upstream outage)."""
    if n <= 0 or not metrics.enabled():
        return
    metrics.get().counter(
        TELEMETRY_FORWARD_DROPPED,
        "forwarded telemetry docs dropped by the bounded buffer",
    ).inc(n)


def fleet_occupancy(instances: int, stale: int) -> None:
    """Aggregator-side view: producers currently merged, and how many
    have gone silent past their staleness window."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.gauge(FLEET_INSTANCES,
              "producer instances in the fleet aggregator").set(instances)
    reg.gauge(FLEET_STALE_INSTANCES,
              "fleet producers silent past their staleness window",
              ).set(stale)


def slo_burn(name: str, burn: float) -> None:
    """Current burn rate of one declared SLO (>= 1 = the objective is
    being violated over its window; obs/slo.py)."""
    if not metrics.enabled():
        return
    metrics.get().gauge(
        SLO_BURN,
        "SLO burn rate (>= 1 = objective violated over its window)",
        ("slo",),
    ).labels(slo=name).set(burn)


def slo_breach(name: str) -> None:
    """One breach TRANSITION (burn crossed 1.0 upward) of an SLO."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        SLO_BREACHES, "SLO breach transitions", ("slo",),
    ).labels(slo=name).inc()


def campaign_slot(cls: str) -> None:
    """One finished campaign run slot, by outcome class (the supervisor
    process's own producer metrics for the fleet view)."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        CAMPAIGN_SLOTS, "campaign run slots finished, by class",
        ("slot_class",),
    ).labels(slot_class=cls).inc()


def campaign_progress_fold(path: str) -> None:
    """One progress document published by a campaign supervisor, by how
    it was made: ``fold`` = only the runs of the slot just finished were
    read and folded into the rows kept, ``walk`` = the whole stored
    history was read (a campaign's first document, a watermark the
    storage contradicts, the document a campaign leaves behind)."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        CAMPAIGN_PROGRESS_FOLDS,
        "campaign progress documents published, by fold or whole walk",
        ("path",),
    ).labels(path=path).inc()


def tenancy_events(run: str, n: int = 1) -> None:
    """Events ingested for one tenant namespace (the per-run events/s
    numerator of the /fleet RUN table)."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        TENANCY_EVENTS, "events ingested per tenant run namespace",
        ("run",),
    ).labels(run=run).inc(n)


def tenancy_parked(run: str, depth: int) -> None:
    """One namespace's parked-event depth (its policy's ScheduledQueue
    residency) — refreshed on ingest and on the host's reaper tick."""
    if not metrics.enabled():
        return
    metrics.get().gauge(
        TENANCY_PARKED, "parked events per tenant run namespace",
        ("run",),
    ).labels(run=run).set(depth)


def tenancy_runs(n: int) -> None:
    """How many run namespaces this orchestrator currently leases."""
    if not metrics.enabled():
        return
    metrics.get().gauge(
        TENANCY_RUNS, "active leased run namespaces").set(n)


def tenancy_reclaim(run: str) -> None:
    """A lease expired and its namespace was reclaimed (the crashed-
    tenant transition; parked events stay journaled for the re-lease)."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        TENANCY_RECLAIMS,
        "tenant namespaces reclaimed after lease expiry", ("run",),
    ).labels(run=run).inc()


def fleet_migration(reason: str, n: int = 1) -> None:
    """``n`` pool leases re-placed onto a replacement host, by reason
    (``drain`` = operator-requested graceful evacuation, ``death`` =
    the monitor declared the host dead)."""
    if n <= 0 or not metrics.enabled():
        return
    metrics.get().counter(
        FLEET_MIGRATIONS,
        "pool leases migrated to a replacement host, by reason",
        ("reason",),
    ).labels(reason=reason).inc(n)


def fleet_admission_rejected(reason: str) -> None:
    """The placement service refused a pool lease (``slo_burn`` = the
    pool's SLO burn gate tripped, ``capacity`` = no eligible host had a
    free slot, ``chaos`` = the fleet.admission.refuse seam fired)."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        FLEET_ADMISSION_REJECTIONS,
        "pool lease requests refused by admission control", ("reason",),
    ).labels(reason=reason).inc()


def fleet_pool_stats(hosts: int, dead: int, leases: int,
                     pending: int) -> None:
    """The placement service's occupancy gauges, refreshed on every
    monitor tick: pool hosts by liveness, granted pool leases, and
    placements still waiting for an eligible host."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    g = reg.gauge(FLEET_POOL_HOSTS,
                  "orchestrator hosts in the placement pool, by state",
                  ("state",))
    g.labels(state="live").set(max(0, hosts - dead))
    g.labels(state="dead").set(dead)
    reg.gauge(FLEET_POOL_LEASES,
              "pool leases the placement service has granted",
              ).set(leases)
    reg.gauge(FLEET_POOL_PENDING,
              "pool leases waiting for an eligible host").set(pending)


def rest_conn_pool(active: int, queued: int) -> None:
    """The REST endpoint's bounded ingress pool: handler threads alive
    vs connections queued waiting for one (doc/tenancy.md — 8 campaigns'
    clients must not mean unbounded thread growth)."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.gauge(REST_CONN_THREADS,
              "REST connection handler threads alive").set(active)
    reg.gauge(REST_CONNS_QUEUED,
              "REST connections queued for a handler thread").set(queued)


def chaos_fault_injected(point: str) -> None:
    """A chaos fault point fired (namazu_tpu/chaos): the injected-fault
    ledger a scenario report joins against its invariants."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        CHAOS_FAULTS,
        "chaos-plane faults injected, by fault point",
        ("point",),
    ).labels(point=point).inc()


def ingress_rejected(endpoint: str, reason: str) -> None:
    """The REST endpoint refused an event POST — backpressure (the
    bounded ingress queue is full) or an injected chaos refusal."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        INGRESS_REJECTIONS,
        "event POSTs refused with 429/503 (backpressure or chaos)",
        ("endpoint", "reason"),
    ).labels(endpoint=endpoint, reason=reason).inc()


def transport_retry_after(seconds: float) -> None:
    """The transceiver honored a server-sent Retry-After before its
    next POST attempt (capped + jittered; doc/robustness.md)."""
    if not metrics.enabled():
        return
    metrics.get().histogram(
        TRANSPORT_RETRY_AFTER,
        "server-requested Retry-After delays honored by the transceiver",
    ).observe(seconds)


def journal_events(n: int) -> None:
    if n <= 0 or not metrics.enabled():
        return
    metrics.get().counter(
        JOURNAL_EVENTS,
        "inbound events appended to the crash-recovery journal",
    ).inc(n)


def journal_recovered(n: int) -> None:
    if n <= 0 or not metrics.enabled():
        return
    metrics.get().counter(
        JOURNAL_RECOVERED,
        "parked events recovered from the journal after a restart",
    ).inc(n)


def event_batch(stage: str, size: int) -> None:
    """One batch moved through an event-plane stage (``ingress`` = REST
    batch POST -> hub, ``dispatch`` = orchestrator action fan-out,
    ``actions_poll`` = batch GET response, ``flush`` = transceiver
    client-side coalescing flush)."""
    if not metrics.enabled():
        return
    metrics.get().histogram(
        EVENT_BATCH,
        "events per batch through the event-plane fast path",
        ("stage",),
        buckets=BATCH_BUCKETS,
    ).labels(stage=stage).observe(size)


_EVENT_STAGE_HELP = ("per-event latency by lifecycle segment (queue/"
                     "decision/parking/dispatch/wire; edge_parking/"
                     "backhaul on the edge path)")


def event_stage(stage: str, seconds: Optional[float]) -> None:
    """One event's time through one lifecycle segment (the critical-
    path attribution's histogram face; None = the bounding stamps were
    absent, e.g. wire-less local transports — observe nothing rather
    than a fake 0)."""
    if seconds is None or not metrics.enabled():
        return
    metrics.get().histogram(
        EVENT_STAGE, _EVENT_STAGE_HELP, ("stage",),
        buckets=STAGE_BUCKETS,
    ).labels(stage=stage).observe(max(0.0, seconds))


def event_stage_many(stage: str, values) -> None:
    """Batch face of :func:`event_stage`: ONE registry/label
    resolution for a whole burst's samples — the edge-backhaul
    reconcile runs at zero-RTT rates, where a per-event family lookup
    would tax the serving plane it measures."""
    if not values or not metrics.enabled():
        return
    child = metrics.get().histogram(
        EVENT_STAGE, _EVENT_STAGE_HELP, ("stage",),
        buckets=STAGE_BUCKETS,
    ).labels(stage=stage)
    for v in values:
        child.observe(max(0.0, v))


def wire_bytes(codec: str, op: str, n: int) -> None:
    """``n`` payload bytes moved over a signal-carrying wire under
    ``codec`` ("json"/"nmzb1") for ``op`` (post_batch/poll/ack/
    backhaul/table/frame). Counted once per message at the side that
    built/parsed it — the byte-savings ledger of the negotiated
    binary codec."""
    if not metrics.enabled() or n <= 0:
        return
    metrics.get().counter(
        WIRE_BYTES,
        "wire payload bytes by codec and operation",
        ("codec", "op"),
    ).labels(codec=codec, op=op).inc(n)


def shm_ring_full(entity: str) -> None:
    """One burst that could not fit the shm ring and fell back to the
    acked uds op wire — the ring-sizing backpressure signal."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.counter(
        SHM_RING_FULL,
        "shm-ring-full fallbacks onto the acked op wire",
        ("entity",),
    ).labels(entity=_entity_label(reg, entity)).inc()


def codec_negotiated(codec: str) -> None:
    """One per-connection codec negotiation settled on ``codec``."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        CODEC_NEGOTIATIONS,
        "per-connection codec negotiations by outcome",
        ("codec",),
    ).labels(codec=codec).inc()


def transport_rtt(op: str, seconds: float) -> None:
    """Client-side wall time of one transceiver HTTP round trip
    (``post`` / ``post_batch`` / ``poll`` / ``ack``)."""
    if not metrics.enabled():
        return
    metrics.get().histogram(
        TRANSPORT_RTT,
        "transceiver-side HTTP round-trip time",
        ("op",),
    ).labels(op=op).observe(seconds)


def rest_request(method: str, code: int) -> None:
    if not metrics.enabled():
        return
    metrics.get().counter(
        REST_REQUESTS, "REST endpoint requests", ("method", "code"),
    ).labels(method=method, code=str(code)).inc()


def rest_ack(entity: str, ack_latency: Optional[float]) -> None:
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.counter(
        REST_ACKS, "actions acknowledged over REST", ("entity",),
    ).labels(entity=_entity_label(reg, entity)).inc()
    if ack_latency is not None:
        reg.histogram(
            REST_ACK_LATENCY,
            "action dispatch -> REST DELETE acknowledgment",
        ).observe(ack_latency)


def sched_queue_depth(queue: str, depth: int) -> None:
    if not metrics.enabled():
        return
    metrics.get().gauge(
        SCHED_QUEUE_DEPTH, "items pending in a ScheduledQueue", ("queue",),
    ).labels(queue=queue).set(depth)


def sched_queue_wait(queue: str, seconds: float) -> None:
    if not metrics.enabled():
        return
    metrics.get().histogram(
        SCHED_QUEUE_WAIT,
        "realized put -> get delay inside a ScheduledQueue",
        ("queue",),
    ).labels(queue=queue).observe(seconds)


# -- recording helpers (search plane) -----------------------------------

def search_round(backend: str, generations: int, elapsed: float,
                 schedules: float, best_fitness: float,
                 archive_entries: int, failure_entries: int,
                 distinct_failures: int,
                 host_io_s: Optional[float] = None) -> None:
    """One search.run() call's worth of progress. ``host_io_s`` is the
    wall time the round spent in the fused loop's overlapped host-I/O
    lane (doc/performance.md "Fused search loop"): published as the
    ``nmz_search_host_gap_share{backend}`` gauge (host seconds per
    evolve second — the number the fusion exists to drive toward 0)."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    if host_io_s is not None and elapsed > 0:
        reg.gauge(
            SEARCH_HOST_GAP, "host-I/O share of the last fused search "
            "round (host_io seconds / evolve seconds)", ("backend",),
        ).labels(backend=backend).set(host_io_s / elapsed)
    reg.counter(
        SEARCH_GENERATIONS, "GA generations run",
        ("backend",),
    ).labels(backend=backend).inc(generations)
    if elapsed > 0:
        reg.gauge(
            SEARCH_GEN_RATE, "generations/sec of the last search round",
            ("backend",),
        ).labels(backend=backend).set(generations / elapsed)
        reg.gauge(
            SCORER_THROUGHPUT,
            "schedules scored per second by the jitted scorer",
            ("source",),
        ).labels(source=backend).set(schedules / elapsed)
    reg.gauge(
        SEARCH_BEST_FITNESS, "best fitness seen so far", ("backend",),
    ).labels(backend=backend).set(best_fitness)
    arch = reg.gauge(
        SEARCH_ARCHIVE, "archive ring occupancy", ("backend", "archive"),
    )
    arch.labels(backend=backend, archive="novelty").set(archive_entries)
    arch.labels(backend=backend, archive="failure").set(failure_entries)
    arch.labels(backend=backend,
                archive="failure_distinct").set(distinct_failures)
    # live stall detection (obs/analytics.py): fitness + novelty sliding
    # window per backend; trips nmz_search_stall and a run-tagged
    # warning while the experiment is still running. Lazy import — the
    # analytics module imports this one for the metric vocabulary.
    from namazu_tpu.obs import analytics

    analytics.note_search_round(backend, best_fitness, distinct_failures)


def search_progress(backend: str, best_fitness: float) -> None:
    """Live best-fitness update from the fused loop's host lane — the
    cheap per-chunk publication that keeps the gauge moving while one
    ``run()`` is still evolving (search_round refreshes it at the end
    of the round as before)."""
    if not metrics.enabled():
        return
    metrics.get().gauge(
        SEARCH_BEST_FITNESS, "best fitness seen so far", ("backend",),
    ).labels(backend=backend).set(best_fitness)


def search_stall(backend: str, stalled: bool) -> None:
    """Mirror the live stall detector's verdict (obs/analytics.py) into
    ``nmz_search_stall{backend}`` (1 = novelty and fitness both flat
    over the detector window, 0 = progressing)."""
    if not metrics.enabled():
        return
    metrics.get().gauge(
        SEARCH_STALL,
        "search-plane stall detector (1 = fitness and novelty both "
        "flatlined over the detector window)",
        ("backend",),
    ).labels(backend=backend).set(1.0 if stalled else 0.0)


def experiment_stats(runs: int, failures: int, failure_rate: float,
                     unique_interleavings: int, coverage: float,
                     novelty_last_window: Optional[float],
                     time_to_first_failure_s: Optional[float],
                     mean_runs_to_reproduce: Optional[float]) -> None:
    """Publish one analytics payload's cross-run aggregates as gauges
    (None values leave their gauge untouched rather than faking a 0)."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.gauge(EXPERIMENT_RUNS,
              "completed runs in the analyzed storage").set(runs)
    reg.gauge(EXPERIMENT_FAILURES,
              "failed (= bug-reproducing) runs in the analyzed storage",
              ).set(failures)
    reg.gauge(EXPERIMENT_FAILURE_RATE,
              "failure rate over the analyzed storage").set(failure_rate)
    reg.gauge(EXPERIMENT_UNIQUE,
              "distinct interleavings (trace_digest) recorded",
              ).set(unique_interleavings)
    reg.gauge(EXPERIMENT_COVERAGE,
              "unique interleavings / runs").set(coverage)
    if novelty_last_window is not None:
        reg.gauge(EXPERIMENT_NOVELTY,
                  "new-interleaving rate of the last analytics window",
                  ).set(novelty_last_window)
    if time_to_first_failure_s is not None:
        reg.gauge(EXPERIMENT_TTFF,
                  "cumulative run time until the first failure",
                  ).set(time_to_first_failure_s)
    if mean_runs_to_reproduce is not None:
        reg.gauge(EXPERIMENT_RUNS_TO_REPRO,
                  "runs per reproduction (inverse failure rate)",
                  ).set(mean_runs_to_reproduce)


def campaign_progress(rate: Optional[float],
                      ci: Optional[Any] = None,
                      repros_per_hour: Optional[float] = None,
                      eta_next_repro_s: Optional[float] = None,
                      runs_to_ci: Optional[float] = None,
                      in_band: Optional[int] = None,
                      repros_per_hour_virtual: Optional[float] = None,
                      ) -> None:
    """Publish one campaign-progress document's live face (obs/stats.py
    via obs/analytics.progress_stats) as ``nmz_campaign_*`` gauges. A
    None value leaves its gauge untouched rather than faking a 0 — a
    young campaign has no rate yet, not a zero rate; an undecided SPRT
    has no in/out-of-band verdict."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    if rate is not None:
        reg.gauge(CAMPAIGN_RATE,
                  "measured repro (failure) rate of the campaign's "
                  "storage").set(rate)
    if ci is not None and len(ci) == 2:
        reg.gauge(CAMPAIGN_RATE_CI_LOW,
                  "Wilson 95% lower bound of the repro rate").set(ci[0])
        reg.gauge(CAMPAIGN_RATE_CI_HIGH,
                  "Wilson 95% upper bound of the repro rate").set(ci[1])
    if repros_per_hour is not None:
        reg.gauge(CAMPAIGN_REPROS_PER_HOUR,
                  "reproductions per hour of run time").set(
                      repros_per_hour)
    if eta_next_repro_s is not None:
        reg.gauge(CAMPAIGN_ETA_NEXT,
                  "forecast seconds of run time to the next repro",
                  ).set(eta_next_repro_s)
    if runs_to_ci is not None:
        reg.gauge(CAMPAIGN_RUNS_TO_CI,
                  "additional runs forecast to reach the target CI "
                  "width").set(runs_to_ci)
    if in_band is not None:
        reg.gauge(CAMPAIGN_IN_BAND,
                  "band SPRT verdict (1 = measured rate in the target "
                  "band, 0 = out of band)").set(in_band)
    if repros_per_hour_virtual is not None:
        reg.gauge(CAMPAIGN_REPROS_PER_HOUR_VIRTUAL,
                  "reproductions per hour of VIRTUAL run time "
                  "(fast-forwarded campaigns; wall-denominated "
                  "surfaces keep nmz_campaign_repros_per_hour)").set(
                      repros_per_hour_virtual)


def vclock_speedup(ratio: float) -> None:
    """One run's virtual/wall elapsed ratio (virtual-clock plane)."""
    if not metrics.enabled():
        return
    metrics.get().gauge(
        VCLOCK_SPEEDUP,
        "virtual elapsed / wall elapsed of the last virtual-clock run",
    ).set(ratio)


def vclock_pinned(seconds: float) -> None:
    """Wall seconds the pinning rule held the virtual clock at wall
    rate during the last run (accumulates across runs)."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        VCLOCK_PINNED,
        "wall seconds the virtual clock spent pinned to wall rate "
        "(busy queues, running entities, real I/O)",
    ).inc(seconds)


def relation_coverage(scenario: str, covered: int, width: int,
                      one_sided: Optional[int] = None) -> None:
    """Publish one campaign's relation-coverage frontier (guidance
    plane, doc/search.md): bitmap occupancy in [0, 1] plus the count of
    one-sided relations still waiting for their flip (None = the
    caller's derivation doesn't track pair identities — leave that
    gauge untouched rather than faking a 0)."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.gauge(
        RELATION_COVERAGE,
        "relation-coverage bitmap occupancy of the campaign's "
        "guidance CoverageMap",
        ("scenario",),
    ).labels(scenario=scenario).set(
        covered / float(width) if width > 0 else 0.0)
    if one_sided is not None:
        reg.gauge(
            RELATION_ONE_SIDED,
            "directed ordering relations observed in one direction "
            "only (the guided search's mutation frontier)",
            ("scenario",),
        ).labels(scenario=scenario).set(one_sided)


def schedule_install(source: str) -> None:
    """A delay/fault table was installed on the policy hot path."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        SEARCH_INSTALLS, "delay-table installs on the policy", ("source",),
    ).labels(source=source).inc()


def scorer_throughput(source: str, rate: float) -> None:
    """Jitted-scorer throughput sample (bench.py and the search plane
    publish through the same gauge so they can never disagree)."""
    if not metrics.enabled():
        return
    metrics.get().gauge(
        SCORER_THROUGHPUT,
        "schedules scored per second by the jitted scorer",
        ("source",),
    ).labels(source=source).set(rate)


def scorer_throughput_value(source: str) -> Optional[float]:
    return metrics.registry().value(SCORER_THROUGHPUT, source=source)


#: cached jax.profiler.TraceAnnotation class, resolved lazily so the
#: control plane never imports jax (policy/base.py's contract); False =
#: probed and unavailable (no-op fallback, e.g. CPU-only builds)
_trace_annotation_cls = None


def _trace_annotation(name: str, rid: Optional[str] = None):
    global _trace_annotation_cls
    cls = _trace_annotation_cls
    if cls is None:
        try:
            from jax.profiler import TraceAnnotation as cls
        except Exception:  # pragma: no cover - jax-less deployments
            cls = False
        _trace_annotation_cls = cls
    if cls is False:
        return contextlib.nullcontext()
    return cls(name) if rid is None else cls(name, rid=rid)


# -- request-scoped spans (search plane) and run-scoped spans -------------
#
# ``search_phase`` is the search plane's ONE span source: a histogram
# observation, a profiler annotation and a row in a bounded in-memory
# ring, all carrying the id of the request being served. The framed
# server opens a request scope around its handler (``request_begin`` /
# ``request_end``, endpoint/framed.py); inside it every phase nests
# under the phase that is open on the same thread. Rows are read over
# the framed ``spans`` op (obs/federation.py) and rendered by
# ``nmz-tpu tools spans``.
#
# ``run_phase`` is the same source under a second histogram family,
# ``nmz_run_phase_seconds``, for the phases of one ``nmz-tpu run``
# (doc/observability.md "Run phases"): ``run_begin`` opens the scope
# under the run's id where ``request_begin`` opens one under a
# request's, the rows go to the same ring, and ``run_end`` hands them
# to the run's stored metadata. The control plane imports no jax, so a
# run phase carries no profiler annotation.

#: the stamp a campaign supervisor hands its ``run`` child:
#: ``time.monotonic()`` at the moment it wants the run — just before the
#: spawn of a cold child (this variable in its environment), at the go
#: of a standby one (the go line, which the child's gate writes here:
#: cli/run_cmd.py). CLOCK_MONOTONIC is host-wide on Linux, so the
#: child's stamps are on the same clock
RUN_SPAWNED_ENV = "NMZ_RUN_SPAWNED"

#: its neighbour: how long the supervisor took from the previous
#: attempt's reap to that stamp (seconds, ``repr`` of a float), which it
#: knows at the stamp and hands over the same two ways. The run records
#: it as its own ``respawn`` row, so the stretch between two runs
#: travels with the run that waited for it. Absent on a campaign's first
#: attempt and while the campaign is not observed
RUN_RESPAWN_ENV = "NMZ_RUN_RESPAWN"

#: what a run records of itself from that stamp on, the wait of a
#: standby child before it (a row of the run's too, kept out of
#: RUN_PHASES because the benchmark's eight ``run_<phase>_s`` are that
#: tuple's names), and what only the supervisor sees — of which
#: ``respawn`` is handed to the run (``RUN_RESPAWN_ENV``) and stored
#: with it, and ``teardown`` ends after the run has stored its rows
RUN_PHASES = ("boot", "prepare", "testee", "drain", "search", "endpoints",
              "validate", "record")
STANDBY_PHASE = "standby"
RESPAWN_PHASE = "respawn"
SUPERVISOR_PHASES = ("teardown", RESPAWN_PHASE)
_STORED_PHASES = frozenset(RUN_PHASES + (STANDBY_PHASE, RESPAWN_PHASE))

_PHASE_HELP = {
    SEARCH_PHASE: "wall time per search-plane phase",
    RUN_PHASE: "wall time per phase of one campaign run",
}

#: rows the span ring keeps; overflow drops the oldest
SPAN_RING_ROWS = 8192


class SpanRing:
    """Span rows ``(rid, name, parent, t_wall, t_mono, seconds, thread,
    attrs)``, newest last, read by a cursor that counts every row ever
    appended (so a reader that falls behind sees a gap, not a
    repeat)."""

    def __init__(self, rows: int = SPAN_RING_ROWS) -> None:
        self._rows: collections.deque = collections.deque(maxlen=rows)
        self._lock = threading.Lock()
        self._appended = 0

    def append(self, row: tuple) -> None:
        with self._lock:
            full = len(self._rows) == self._rows.maxlen
            self._rows.append(row)
            self._appended += 1
        if full:
            metrics.get().counter(
                SPAN_ROWS_DROPPED,
                "span rows pushed out of the ring by newer ones").inc()

    def end(self) -> int:
        """The cursor past the newest row: rows appended from now on
        are ``since(end())``."""
        with self._lock:
            return self._appended

    def since(self, cursor: int = 0, limit: int = 1024) -> dict:
        """Rows from ``cursor`` on (at most ``limit``), the cursor to
        pass next, the rows lost to overflow so far, and a
        ``{wall, mono}`` anchor read in one call."""
        with self._lock:
            first = self._appended - len(self._rows)
            start = min(max(int(cursor), first), self._appended)
            rows = list(itertools.islice(
                self._rows, start - first, start - first + max(0, limit)))
            dropped = first
        return {"rows": [list(r) for r in rows],
                "next": start + len(rows), "dropped": dropped,
                "anchor": {"wall": time.time(), "mono": time.monotonic()}}


_span_ring = SpanRing()
#: per-thread request scope: ``rid`` / ``arrived`` (set by the framed
#: server) and ``stack`` (names of the phases open on this thread)
_scope = threading.local()
_request_counter = itertools.count(1)


def span_ring() -> SpanRing:
    return _span_ring


def reset_span_ring(rows: int = SPAN_RING_ROWS) -> SpanRing:
    """Fresh empty ring (tests)."""
    global _span_ring
    _span_ring = SpanRing(rows)
    return _span_ring


def request_begin(ctx, arrived: float) -> Optional[str]:
    """Open the calling thread's request scope: mint the request id —
    ``"<o>:<lc>"`` of the frame's ``ctx`` stamp when it carries one
    (obs/context.py ``wire_stamp``), so a client's id survives the hop,
    else a per-process counter — and record the ``queue`` span from
    ``arrived`` (monotonic, stamped where the frame completed) to now.
    Returns the id; None (and no scope) while observability is off."""
    if not metrics.enabled():
        return None
    if (isinstance(ctx, dict) and ctx.get("o")
            and isinstance(ctx.get("lc"), int)):
        rid = f"{ctx['o']}:{ctx['lc']}"
    else:
        from namazu_tpu.obs import context

        rid = f"{context.origin()}:r{next(_request_counter)}"
    _scope.rid, _scope.arrived = rid, arrived
    search_phase_observed("queue", time.monotonic() - arrived, arrived)
    return rid


def request_end() -> None:
    _scope.__dict__.clear()


def current_request() -> Optional[tuple]:
    """``(rid, arrived)`` of the request this thread is serving."""
    rid = _scope.__dict__.get("rid")
    return None if rid is None else (rid, _scope.arrived)


def _open_phase() -> Optional[str]:
    """The innermost phase open on this thread."""
    stack = _scope.__dict__.get("stack")
    return stack[-1] if stack else None


def _append_row(name: str, seconds: float, t_mono: float,
                parent: Optional[str], attrs: dict) -> None:
    _span_ring.append((
        _scope.__dict__.get("rid"), name, parent,
        time.time() - (time.monotonic() - t_mono), t_mono, seconds,
        threading.current_thread().name, attrs))


def _observe_phase(family: str, phase: str, seconds: float) -> None:
    metrics.get().histogram(
        family, _PHASE_HELP[family], ("phase",),
    ).labels(phase=phase).observe(seconds)


def _record_phase(family: str, phase: str, seconds: float, t_mono: float,
                  parent: Optional[str], attrs: dict) -> None:
    _observe_phase(family, phase, seconds)
    _append_row(phase, seconds, t_mono, parent, attrs)


@contextlib.contextmanager
def _phase(family: str, phase: str, attrs: dict, annotate: bool):
    scope = _scope.__dict__
    stack = scope.setdefault("stack", [])
    parent = stack[-1] if stack else None
    stack.append(phase)
    t0 = time.monotonic()
    try:
        with (_trace_annotation(f"nmz:{phase}", scope.get("rid"))
              if annotate else contextlib.nullcontext()):
            yield attrs
    finally:
        stack.pop()
        _record_phase(family, phase, time.monotonic() - t0, t0, parent,
                      attrs)


def search_phase(phase: str, **attrs):
    """Time one search-plane phase into
    ``nmz_search_phase_seconds{phase=...}``, annotate the region into
    any active device profile via ``jax.profiler.TraceAnnotation``
    (``nmz:<phase>``, with the request id as ``rid``; no-op without a
    profiler session, no-op fallback when jax is absent) and append one
    row to the span ring under the current request and parent phase.
    Yields ``attrs`` so the body can add what it learns (``runs=``).
    The phase vocabulary is doc/observability.md "Request spans".
    Finer-grained in-step phases (mutate/score/select/migrate) are
    annotated with ``jax.named_scope`` inside the jitted island step
    (parallel/islands.py), where host-side timers cannot reach."""
    if not metrics.enabled():
        return contextlib.nullcontext(attrs)
    return _phase(SEARCH_PHASE, phase, attrs, annotate=True)


def search_phase_observed(phase: str, seconds: float, t_mono_start: float,
                          **attrs) -> None:
    """Record a phase that was measured across threads or accumulated
    over a loop: histogram + row like :func:`search_phase`, under the
    phase open on this thread, but no profiler annotation. A phase
    accumulated over a loop states ``pieces=<n>``: its row starts at
    the first piece and is ``seconds`` long, so it may overlap its
    siblings, and readers charge it by its length (obs/export.py
    ``span_trees``)."""
    if not metrics.enabled():
        return
    _record_phase(SEARCH_PHASE, phase, seconds, t_mono_start,
                  _open_phase(), attrs)


# -- run-scoped spans (campaign supervisor, cli/run_cmd.py) ---------------

def run_entered() -> Optional[float]:
    """The stamp ``nmz-tpu run`` takes once its config has said whether
    the run is observed at all; None (and no clock read) while it is
    not."""
    return time.monotonic() if metrics.enabled() else None


def run_begin(run_id: str, entered: Optional[float],
              standby_since: Optional[float] = None) -> None:
    """Open the calling thread's run scope under ``run_id`` (the run
    directory's name). The run's rows start at the supervisor's spawn
    stamp (``RUN_SPAWNED_ENV``), and ``boot`` is the row from there to
    ``entered``; a bare ``nmz-tpu run`` has no such stamp, records no
    ``boot`` and counts from ``entered``. A run whose child stood by
    (``standby_since``: its arrival at the gate) has ``standby`` before
    that: the wait from the arrival to the stamp, which is then the
    go's, so the row starts before 0 and a child the go found still
    importing has one of no length. Where the supervisor also said how
    long it took to get from the previous reap to the stamp
    (``RUN_RESPAWN_ENV``), that is the run's ``respawn`` row: top-level,
    ending at 0 like ``standby``, before the origin and so outside the
    closure of the rows from 0 on. Both variables are taken out of
    the environment: what this run spawns is no child of that stamp.
    No scope while observability is off (``entered`` None)."""
    stamp = os.environ.pop(RUN_SPAWNED_ENV, None)
    gap = os.environ.pop(RUN_RESPAWN_ENV, None)
    if entered is None or not metrics.enabled():
        return
    try:
        spawned = float(stamp) if stamp else None
    except ValueError:
        spawned = None
    try:
        respawn = float(gap) if gap and spawned is not None else None
    except ValueError:
        respawn = None
    _scope.rid = run_id
    # the scope: where the run's clock starts and where its rows do
    _scope.run = (entered if spawned is None else spawned,
                  _span_ring.end())
    if spawned is not None:
        if respawn is not None and 0.0 <= respawn < float("inf"):
            _record_phase(RUN_PHASE, RESPAWN_PHASE, respawn,
                          spawned - respawn, None, {})
        if standby_since is not None:
            waited = max(0.0, spawned - standby_since)
            _record_phase(RUN_PHASE, STANDBY_PHASE, waited,
                          spawned - waited, None, {})
        _record_phase(RUN_PHASE, "boot", entered - spawned, spawned,
                      None, {})


def run_phase(phase: str, **attrs):
    """Time one phase of the run whose scope is open on this thread
    into ``nmz_run_phase_seconds{phase=...}`` and the span ring, nested
    like :func:`search_phase`; outside a run scope (an orchestrator no
    ``nmz-tpu run`` drives) and while observability is off it times
    nothing."""
    if not metrics.enabled() or "run" not in _scope.__dict__:
        return contextlib.nullcontext(attrs)
    return _phase(RUN_PHASE, phase, attrs, annotate=False)


def run_phase_since(phase: str, t_mono_start: Optional[float]) -> None:
    """Record the run phase that began at ``t_mono_start`` and ends
    now (``prepare``: too long a stretch of ``run`` to indent under a
    ``with``)."""
    if (t_mono_start is None or not metrics.enabled()
            or "run" not in _scope.__dict__):
        return
    _record_phase(RUN_PHASE, phase, time.monotonic() - t_mono_start,
                  t_mono_start, _open_phase(), {})


def run_end() -> Optional[list]:
    """Close the run scope and return the run's rows as
    ``[name, parent, start_s, seconds]``, ``start_s`` counted from the
    scope's origin: what ``cli/run_cmd.py`` stores as
    ``metadata["phases"]``. None without a scope."""
    scope = _scope.__dict__
    run = scope.get("run")
    if run is None:
        return None
    origin, cursor = run
    rid = scope.get("rid")
    rows = [[name, parent, round(t_mono - origin, 6), round(seconds, 6)]
            for r, name, parent, _wall, t_mono, seconds, _thread, _attrs
            in _span_ring.since(cursor, SPAN_RING_ROWS)["rows"]
            if r == rid and name in _STORED_PHASES]
    # the ring holds a phase where it ended: a parent before its children
    rows.sort(key=lambda row: (row[2], -row[3]))
    request_end()
    return rows


def run_phases_observed(rows) -> None:
    """Observe stored run rows (``metadata["phases"]`` of a
    ``result.json``, or a supervisor's ``teardown`` / ``respawn``) into
    this process's ``nmz_run_phase_seconds{phase}``: the histogram
    alone, the rows live where they were stored. The rows come from a
    file: one that is no ``[name, parent, start_s, seconds]`` with a
    known name and a finite, non-negative length is passed over."""
    if not rows or not metrics.enabled():
        return
    for row in rows:
        try:
            name, _parent, _start, seconds = row
            seconds = float(seconds)
        except (TypeError, ValueError):
            continue
        if (name in _STORED_PHASES or name in SUPERVISOR_PHASES) \
                and 0.0 <= seconds < float("inf"):
            _observe_phase(RUN_PHASE, name, seconds)


_compile_listener_on = False


def ensure_compile_listener() -> None:
    """Register, once per process, the search plane's lowering
    listener: every jaxpr->MLIR lowering counts into
    ``nmz_compiles_total`` and ``nmz_compile_seconds{phase}`` with the
    innermost phase open on the compiling thread (``none`` outside
    one), and leaves a ``compile`` row under the current request —
    which step recompiled, for which request, and under ``fun_name``
    the name jax gives the lowered module (``jit(<function>)``; absent
    where a jax passes none). Called by the search's constructor
    (the first thing in a process that can compile)."""
    global _compile_listener_on
    if _compile_listener_on:
        return
    _compile_listener_on = True
    import jax.monitoring

    def on_duration(event, duration, fun_name=None, **_kw):
        if event != LOWERING_EVENT or not metrics.enabled():
            return
        phase = _open_phase()
        reg = metrics.get()
        reg.counter(COMPILES, "jaxpr->MLIR lowerings (each one a "
                              "compile or a cache load)").inc()
        reg.histogram(
            COMPILE_SECONDS, "lowering time by the search phase that "
            "was open on the compiling thread", ("phase",),
        ).labels(phase=phase or "none").observe(duration)
        _append_row("compile", float(duration),
                    time.monotonic() - duration, phase,
                    {} if fun_name is None else {"fun_name": str(fun_name)})

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def ingest_runs(n: int) -> None:
    """Stored runs one ingest walked (the divisor of the per-run
    ingest stage times)."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        INGEST_RUNS, "stored runs walked by history ingests").inc(n)


def ingest_embed_call() -> None:
    """One device call of the batched embed program
    (``ScheduleSearch._embed_chunks``): up to ``EMBED_CHUNK`` executed
    traces embedded at once. ``nmz_ingest_runs_total`` over this is
    how full the chunks run."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        INGEST_EMBED_CALLS,
        "device calls of the batched trace-embed program").inc()


def ingest_events(n: int) -> None:
    """Events of the stored runs one ingest encoded (the divisor of
    the per-event ingest stage times: a stored run is 18 events in one
    hunt and 1,500 in another)."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        INGEST_EVENTS, "events of the stored runs history ingests "
                       "encoded").inc(n)


def ingest_cached_runs(n: int) -> None:
    """Stored runs one ingest took from the encoded-run records it keeps
    (``models/ingest.py`` ``RunRecordCache``) in place of reading and
    encoding them; over ``nmz_ingest_runs_total``: the share of a
    request's history that was not new to this process."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        INGEST_CACHED_RUNS, "stored runs history ingests took from "
                            "their encoded-run records").inc(n)


def storage_open(runs: int, visited: int) -> None:
    """One ``init()`` / ``refresh()`` of a stored history
    (storage/naive.py): ``runs`` allocated, ``visited`` of them looked
    at for a crash to quarantine — the runs from the watermark on; the
    rest were seen settled by an earlier open and cost this one
    nothing. Settled over runs is how much of a history an open no
    longer walks."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.counter(STORAGE_OPEN_RUNS, "runs allocated in the stored "
                "histories opened or refreshed").inc(runs)
    # written at 0 too: a history walked whole reads 0 %, not nothing
    reg.counter(STORAGE_OPEN_SETTLED_RUNS, "runs of them that an open "
                "did not visit: seen settled before").inc(runs - visited)


def reorder_window_drained(policy: str, events: int,
                           overran: bool) -> None:
    """One window of the policy's reorder buffer released (``events``
    of it, paced ``reorder_gap`` apart). ``overran``: its last release
    came after the next window's boundary, so the next window's first
    slot is later than the scorer's ``close + gap * rank`` says."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.counter(REORDER_WINDOWS, "reorder windows drained",
                ("policy",)).labels(policy=policy).inc()
    if overran:
        reg.counter(
            REORDER_WINDOW_OVERRUNS,
            "reorder windows whose paced drain ended after the next "
            "window's boundary", ("policy",)).labels(policy=policy).inc()
    reg.histogram(
        REORDER_WINDOW_EVENTS, "events per drained reorder window",
        ("policy",), buckets=BATCH_BUCKETS,
    ).labels(policy=policy).observe(events)


def evolve_request(scorer: str) -> None:
    """One evolve of the search, by the first-occurrence branch
    its compiled step took for the request's padded trace length
    (``ops/schedule.py::scorer_branch``: ``dense`` | ``blockwise`` |
    ``order``)."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        EVOLVE_REQUESTS, "evolve requests by the scorer branch of the "
                         "compiled step", ("scorer",),
    ).labels(scorer=scorer).inc()


def evolve_table_request() -> None:
    """One evolve whose compiled step took its first occurrences from
    per-trace tables (``ops/schedule.py::_delay_tables``: ``first =
    delays + earliest arrival``, no per-event op under the population
    ``vmap``): every delay-mode evolve, no order-mode one."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        EVOLVE_TABLE_REQUESTS, "evolve requests whose compiled step took "
                               "first occurrences from per-trace tables",
    ).inc()


def rerank_request(path: str) -> None:
    """One reply re-ranked from the population's fitness top-k
    (``models/search.py::_surrogate_pick``): ``compiled`` when the pick
    finished on the device (one fetch of the winner), ``host`` when a
    remote surrogate or a guidance map took the k rows to the host.
    Nothing to re-rank with counts nothing."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        RERANK_REQUESTS, "re-ranked replies by where the pick finished",
        ("path",)).labels(path=path).inc()


def ring_rows(ring: str, written: int, overwritten: int) -> None:
    """Rows one embed batch gave slots of a search's ring (``archive``
    | ``failure``), counted where the slot is decided
    (``models/search.py`` ``add_executed_trace`` / ``add_failure_trace``)
    and written here once per batch: ``written`` of them in all,
    ``overwritten`` onto a slot that held a live row (the ring had gone
    round). Overwritten over written is the regime a search is in: 0 %
    while the history fits the ring, 100 % from the request on whose
    history is past capacity before it starts."""
    if not metrics.enabled() or not written:
        return
    reg = metrics.get()
    reg.counter(RING_ROWS_WRITTEN, "rows given a slot of a search's "
                "ring", ("ring",)).labels(ring=ring).inc(written)
    # written at 0 too: a share over a sample that is not there reads
    # as nothing to read, not as 0 %
    reg.counter(RING_ROWS_OVERWRITTEN, "rows given a slot that held a "
                "live row", ("ring",)).labels(ring=ring).inc(overwritten)


def failure_signatures_deduped(n: int = 1) -> None:
    """Failures whose signature the failure ring already holds, passed
    over by ``add_failure_trace``: what keeps a re-fed history from
    spending a slot per request on each stored failure."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        FAILURE_SIGNATURES_DEDUPED, "failure traces passed over because "
        "their signature is already in the failure ring").inc(n)


def resident_trace_rows(op: str, n: int = 1) -> None:
    """Rows of a search's device-resident reference traces
    (``models/search.py::_ResidentTraces``), counted where the store
    decides: ``append`` — a reference new to the store uploaded into a
    row (under ``reference_mode = "recent"`` every passing run brings
    one); ``evict`` — the append took the row of the oldest trace no
    longer referenced; ``restage`` — rows written by a staging of the
    whole store (its first, a window wider than its capacity, a step
    of the search's length class)."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        RESIDENT_TRACE_ROWS, "rows of the device-resident reference "
        "traces by what the store did", ("op",)).labels(op=op).inc(n)


def embed_traces(n: int, below_class: int) -> None:
    """Traces one flush handed the batched embed program
    (``ScheduleSearch._embed_chunks``) and, of them, those whose own padded
    length is under the search's length class: the runs a per-length
    embed would have grouped apart, each group a program of its own.
    Both samples are written, the second at 0 where none is shorter: a
    share over a sample that is not there reads as nothing to read,
    not as 0 %."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.counter(
        EMBED_TRACES, "traces handed to the batched embed program").inc(n)
    reg.counter(
        EMBED_TRACES_BELOW_CLASS, "embedded traces whose own padded length "
        "is under the search's length class").inc(below_class)


def length_class_step() -> None:
    """A search met a trace longer than its length class and stepped
    the class to it: its programs lower once more at the new length
    (the ``compile`` rows of that request name them)."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        LENGTH_CLASS_STEPS, "steps of a search's length class: a trace "
        "past it re-staged the resident rows and lowered the programs "
        "at its length").inc()


def search_device_trace(path: str) -> None:
    """One completed ``jax.profiler`` device-trace capture dumped into
    ``path`` (the sidecar's ``device_trace`` op): counted and stamped
    into the flight recorder so the trace directory correlates with the
    run that produced it."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        SEARCH_DEVICE_TRACES,
        "completed jax.profiler device-trace captures").inc()
    from namazu_tpu.obs import recorder

    recorder.record_annotation("device_trace", path=str(path))


def sidecar_request(op: str, ok: bool) -> None:
    if not metrics.enabled():
        return
    metrics.get().counter(
        SIDECAR_REQUESTS, "search sidecar requests", ("op", "ok"),
    ).labels(op=op, ok=str(bool(ok)).lower()).inc()


# -- global failure-knowledge plane (doc/knowledge.md) -------------------

def knowledge_push(ok: bool, accepted: int = 0, duplicates: int = 0) -> None:
    """One pool_push round trip: entries the service newly stored vs
    content-keyed dedupe hits (the same signature already pooled)."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.counter(
        KNOWLEDGE_PUSHES, "knowledge-service pool_push requests", ("ok",),
    ).labels(ok=str(bool(ok)).lower()).inc()
    if duplicates > 0:
        reg.counter(
            KNOWLEDGE_DEDUPE,
            "pushed signatures the pool already held (content-keyed "
            "dedupe)",
        ).inc(duplicates)


def knowledge_pull(ok: bool) -> None:
    # pulled-entry VOLUME is deliberately not counted here: the entries
    # that matter (new to the pulling search) land in
    # nmz_knowledge_warmstart_installs_total{kind="archive"}
    if not metrics.enabled():
        return
    metrics.get().counter(
        KNOWLEDGE_PULLS, "knowledge-service pool_pull requests", ("ok",),
    ).labels(ok=str(bool(ok)).lower()).inc()


def knowledge_warmstart(kind: str, n: int = 1) -> None:
    """A cold run installed fleet knowledge: ``kind`` = what landed
    (``archive`` = pooled signatures folded into the failure archive,
    ``table`` = a scenario's best delay table installed on the hot
    path)."""
    if not metrics.enabled() or n <= 0:
        return
    metrics.get().counter(
        KNOWLEDGE_WARMSTART,
        "warm-start installs from the knowledge service", ("kind",),
    ).labels(kind=kind).inc(n)


def knowledge_surrogate_round() -> None:
    if not metrics.enabled():
        return
    metrics.get().counter(
        KNOWLEDGE_SURROGATE_ROUNDS,
        "shared-surrogate training rounds on the knowledge service",
    ).inc()


def knowledge_service_stats(tenants: int, pool_entries: int) -> None:
    """Service-side occupancy gauges (published on every handled op)."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.gauge(
        KNOWLEDGE_TENANTS,
        "distinct tenants the knowledge service has seen",
    ).set(tenants)
    reg.gauge(
        KNOWLEDGE_POOL,
        "failure signatures in the global knowledge pool",
    ).set(pool_entries)


def knowledge_fanin(inflight: int,
                    lock_wait_s: Optional[float] = None) -> None:
    """One request entering/leaving the knowledge service handler:
    ``inflight`` concurrent requests right now, plus (entry only) how
    long this one waited for the shared-state lock. A 3-host pool
    pushing concurrently should show lock waits in the microseconds —
    milliseconds here mean the fan-in is serializing again."""
    if not metrics.enabled():
        return
    reg = metrics.get()
    reg.gauge(
        KNOWLEDGE_FANIN_INFLIGHT,
        "requests currently inside the knowledge service handler",
    ).set(max(0, inflight))
    if lock_wait_s is not None:
        reg.histogram(
            KNOWLEDGE_FANIN_LOCK_WAIT,
            "knowledge-service shared-state lock acquisition wait",
        ).observe(max(0.0, lock_wait_s))


def knowledge_outage() -> None:
    """The knowledge service was unreachable/stale; the caller degraded
    to local-only search (an outage must never fail a campaign)."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        KNOWLEDGE_OUTAGES,
        "knowledge-service outages degraded to local-only search",
    ).inc()


# -- triage plane (doc/observability.md "Triage") ------------------------

def table_propagation(seconds: Optional[float]) -> None:
    """One published table's search-install -> edge-adoption gap
    (publisher install stamp -> edge sync, same-host CLOCK_MONOTONIC;
    None/negative = the doc predates the stamp or crossed hosts —
    observe nothing rather than a fake 0)."""
    if seconds is None or seconds < 0.0 or not metrics.enabled():
        return
    metrics.get().histogram(
        TABLE_PROPAGATION,
        "delay-table search-install -> edge-decision propagation",
    ).observe(seconds)


def triage_probe(mode: str, n: int = 1) -> None:
    """Minimization probes by cost class: ``simulated`` = scored free
    through the guidance plane's predicted_gain, ``replayed`` = a real
    campaign-runner execution."""
    if not metrics.enabled() or n <= 0:
        return
    metrics.get().counter(
        TRIAGE_PROBES, "delta-debugging minimization probes", ("mode",),
    ).labels(mode=mode).inc(n)


def triage_minimized(ratio: float) -> None:
    """Size of the latest minimized reproducer relative to its
    candidate flip set (0 = everything shed, 1 = nothing shed)."""
    if not metrics.enabled():
        return
    metrics.get().gauge(
        TRIAGE_MINIMIZATION_RATIO,
        "latest minimization's minimal-flips / candidate-flips ratio",
    ).set(max(0.0, min(1.0, float(ratio))))


def triage_dossier_pull(ok: bool) -> None:
    """One dossier fetch against the knowledge wire (v3 triage_pull);
    ok = a dossier came back (miss and outage both count false)."""
    if not metrics.enabled():
        return
    metrics.get().counter(
        TRIAGE_DOSSIER_PULLS,
        "triage dossier pulls against the knowledge service", ("ok",),
    ).labels(ok=str(bool(ok)).lower()).inc()


def triage_signatures(n: int) -> None:
    """Distinct failure signatures this process holds dossiers for
    (the /fleet SIGS column's source gauge)."""
    if not metrics.enabled():
        return
    metrics.get().gauge(
        TRIAGE_SIGNATURES,
        "failure signatures with a local triage dossier",
    ).set(n)
