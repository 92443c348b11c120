"""Observability plane: metrics registry + spans + flight recorder.

``namazu_tpu.obs`` is the one import the rest of the stack uses:

* :mod:`namazu_tpu.obs.metrics` — thread-safe registry (counters,
  gauges, fixed-bucket histograms), Prometheus text renderer, global
  enable/disable with a shared no-op fallback;
* :mod:`namazu_tpu.obs.spans` — lifecycle stamping (interception ->
  decision -> dispatch -> ack), the domain metric vocabulary, and the
  search-plane phase profiler (``search_phase``);
* :mod:`namazu_tpu.obs.recorder` — the flight recorder: bounded per-run
  event-timeline capture with run-correlated structured records;
* :mod:`namazu_tpu.obs.export` — Chrome-trace/Perfetto + NDJSON
  exporters and the dispatch-order differ over recorded runs;
* :mod:`namazu_tpu.obs.analytics` — the experiment plane: cross-run
  exploration coverage, reproduction-rate stats, search convergence +
  stall detection, fault-localization ranking;
* :mod:`namazu_tpu.obs.report` — Markdown/NDJSON renderers for the
  analytics payload.

Exposure: ``GET /metrics`` + ``/metrics.json``, ``GET /traces`` +
``/traces/<run_id>``, ``GET /analytics``, and ``GET /healthz`` on the
REST endpoint (endpoint/rest.py), plus ``nmz-tpu tools metrics``,
``nmz-tpu tools trace {list,dump,diff,export}``, and ``nmz-tpu tools
report`` (cli/tools_cmd.py). Disable with ``obs_enabled = false`` in
the experiment config. Metric names, the trace record schema, the
analytics payload schema, and run-id correlation rules are documented
in doc/observability.md.
"""

from __future__ import annotations

from namazu_tpu.obs import (  # noqa: F401
    analytics,
    causality,
    context,
    export,
    federation,
    metrics,
    profdiff,
    profiling,
    recorder,
    report,
    slo,
)
from namazu_tpu.obs.recorder import (  # noqa: F401
    FlightRecorder,
    begin_run,
    current_generation_id,
    current_run_id,
    end_run,
    record_acked,
    record_annotation,
    record_decided,
    record_decision,
    record_dispatched,
    record_edge,
    record_enqueued,
    record_generation,
    record_install,
    record_intercepted,
    record_released,
)
from namazu_tpu.obs.metrics import (  # noqa: F401
    DEFAULT_BUCKETS,
    MetricError,
    MetricsRegistry,
    NullRegistry,
    configure,
    enabled,
    get,
    registry,
    reset,
    set_registry,
)
from namazu_tpu.obs.spans import (  # noqa: F401
    action_dispatched,
    action_unroutable,
    campaign_progress,
    campaign_progress_fold,
    campaign_slot,
    carry,
    chaos_fault_injected,
    codec_negotiated,
    edge_backhaul_lag,
    edge_decision,
    edge_parked,
    edge_table_staleness,
    edge_table_version_held,
    entity_stalled,
    event_batch,
    event_intercepted,
    event_stage,
    event_stage_many,
    experiment_stats,
    fleet_admission_rejected,
    fleet_migration,
    fleet_occupancy,
    fleet_pool_stats,
    ingress_rejected,
    journal_events,
    journal_recovered,
    knowledge_fanin,
    knowledge_outage,
    knowledge_pull,
    knowledge_push,
    knowledge_service_stats,
    knowledge_surrogate_round,
    knowledge_warmstart,
    latency,
    mark,
    policy_decision,
    queue_dwell,
    relation_coverage,
    reorder_window_drained,
    rest_ack,
    rest_request,
    sched_queue_depth,
    sched_queue_wait,
    shm_ring_full,
    schedule_install,
    scorer_throughput,
    scorer_throughput_value,
    current_request,
    embed_traces,
    ensure_compile_listener,
    evolve_request,
    evolve_table_request,
    failure_signatures_deduped,
    ingest_cached_runs,
    ingest_embed_call,
    ingest_events,
    ingest_runs,
    length_class_step,
    rerank_request,
    resident_trace_rows,
    ring_rows,
    run_begin,
    run_end,
    run_entered,
    run_phase,
    run_phase_since,
    run_phases_observed,
    search_device_trace,
    search_phase,
    search_phase_observed,
    search_progress,
    search_round,
    search_stall,
    sidecar_request,
    slo_breach,
    slo_burn,
    storage_open,
    tenancy_events,
    tenancy_parked,
    tenancy_reclaim,
    tenancy_runs,
    rest_conn_pool,
    span,
    span_delta,
    table_propagation,
    table_version,
    telemetry_forward_dropped,
    telemetry_push,
    transport_retry_after,
    transport_rtt,
    triage_dossier_pull,
    triage_minimized,
    triage_probe,
    vclock_pinned,
    vclock_speedup,
    triage_signatures,
    wire_bytes,
)


def configure_from_config(config) -> None:
    """Apply the ``obs_enabled`` config key to the process-global flag
    (called by the orchestrator before any endpoint starts).

    Only an EXPLICIT key touches the flag: the switch is process-global
    (default on), and in multi-orchestrator processes — the ab harness,
    the test suite — a second orchestrator built from a default config
    must not silently re-enable telemetry someone disabled (or freeze
    the counters a live ``/metrics`` is serving)."""
    if config.is_set("obs_enabled"):
        metrics.configure(bool(config.get("obs_enabled")))
        if not metrics.enabled():
            # the profiler rides the obs switch: turning the plane off
            # also stops an already-started sampler (obs/profiling.py)
            profiling.reset()
    # fleet telemetry federation keys (telemetry_enabled, SLO specs,
    # staleness/eviction windows) — same explicit-keys-only rule
    federation.configure_from_config(config)


def render_prometheus() -> str:
    """Prometheus text of the default registry (the /metrics body).
    Sampled gauges (edge staleness/parked depth, knowledge occupancy)
    are refreshed first — a direct read must not serve values up to a
    relay push interval old."""
    federation.run_collectors()
    return metrics.registry().render_prometheus()


def registry_jsonable() -> dict:
    """JSON form of the default registry (the /metrics.json body and
    the ``nmz-tpu tools metrics`` dump); sampled gauges refreshed
    first, same as :func:`render_prometheus`."""
    federation.run_collectors()
    return metrics.registry().to_jsonable()


def trace_summaries() -> list:
    """Recorded-run summaries (the ``GET /traces`` body)."""
    return recorder.recorder().summaries()


def trace_run(run_id: str):
    """The recorded :class:`~namazu_tpu.obs.recorder.RunTrace` for
    ``run_id`` ("latest" = most recently begun), or None."""
    return recorder.recorder().run(run_id)


def set_analytics_storage(dir_path) -> None:
    """Register the experiment storage dir the live ``GET /analytics``
    route aggregates over (``nmz-tpu run`` calls this with its storage;
    None unregisters)."""
    analytics.set_storage_dir(dir_path)


def set_knowledge_address(addr) -> None:
    """Register the knowledge-service address whose pool/tenant stats
    the live analytics payload folds in (``run --knowledge`` calls
    this; None unregisters)."""
    analytics.set_knowledge_address(addr)


def analytics_payload(top: int = analytics.DEFAULT_TOP,
                      window: int = analytics.DEFAULT_WINDOW) -> dict:
    """The experiment-analytics document (the ``GET /analytics`` body):
    the registered storage joined with this process's recorded runs."""
    return analytics.payload(top=top, window=window)


def progress_payload() -> dict:
    """The campaign-progress document (the ``GET /progress`` body):
    sequential repro-rate statistics, band verdict, and ETA forecasts
    over the registered storage — always served, zeros before the first
    run lands."""
    return analytics.progress_payload()


def causality_run_payload(run_id: str):
    """The ``GET /causality/<run_id>`` body (happens-before graph +
    critical-path attribution), or None for an unknown run."""
    run = recorder.recorder().run(run_id)
    if run is None:
        return None
    return causality.run_payload(run)


#: memoized fault-localization ranking for the why route:
#: (storage dir, run count, top) -> analyzer ranking. analyze_storage
#: reads every stored run's coverage file — repeating that per
#: GET /causality/<a>/<b> would turn a ranking hint into full-storage
#: I/O in the request handler; the ranking only changes when a run
#: completes, which the run count witnesses.
_why_suspicious_cache: dict = {}


def _why_suspicious(top: int):
    d = analytics.storage_dir()
    if not d:
        return None
    try:
        from namazu_tpu.analyzer import analyze_storage
        from namazu_tpu.storage import load_storage

        st = load_storage(d)
        try:
            key = (d, st.nr_stored_histories(), top)
            if key in _why_suspicious_cache:
                return _why_suspicious_cache[key]
            ranking = analyze_storage(st, top=top)
        finally:
            st.close()
        _why_suspicious_cache.clear()  # one storage, one live key
        _why_suspicious_cache[key] = ranking
        return ranking
    except Exception:  # localization is a ranking hint, never a 500
        return None


def causality_why_payload(run_a: str, run_b: str, top: int = 20):
    """The ``GET /causality/<a>/<b>`` body (ordering-relation flips +
    per-run causality summaries), or None when either run is unknown.
    The analyzer's fault-localization ranking (from the registered
    analytics storage, when one exists) feeds the flip scoring."""
    a = recorder.recorder().run(run_a)
    b = recorder.recorder().run(run_b)
    if a is None or b is None:
        return None
    docs_a, _, rid_a = causality.docs_of_run(a)
    docs_b, _, rid_b = causality.docs_of_run(b)
    return causality.why_payload(docs_a, docs_b, rid_a, rid_b,
                                 top=top,
                                 suspicious=_why_suspicious(top))


def note_telemetry_push(doc) -> dict:
    """Merge one pushed telemetry doc into this process's fleet
    aggregator (the ``POST /api/v3/telemetry`` body; raises ValueError
    on a malformed doc). A disabled plane acks-and-discards — the
    ``telemetry_enabled = false`` kill switch holds on the serving
    side too."""
    if not federation.enabled():
        return {"ok": True, "disabled": True}
    return federation.aggregator().note_push(doc)


def fleet_payload() -> dict:
    """The fleet status document (the ``GET /fleet`` body)."""
    return federation.aggregator().payload()


def fleet_prometheus() -> str:
    """The whole fleet as one Prometheus text exposition (the
    ``GET /fleet?format=prom`` body)."""
    return federation.aggregator().prometheus()


def profile_payload():
    """This process's sampling profile as the ``nmz-profile-v1``
    payload (the ``GET /profile?format=json`` body), or None when the
    profiler is off."""
    return profiling.payload()


def profile_collapsed() -> str:
    """This process's profile as folded collapsed-stack text (the
    ``GET /profile?format=collapsed`` body); empty when off."""
    return profiling.render_collapsed()


def profile_speedscope():
    """This process's profile as a speedscope JSON document (the
    default ``GET /profile`` body), or None when the profiler is
    off."""
    return profiling.speedscope_doc()
