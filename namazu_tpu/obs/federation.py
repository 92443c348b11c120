"""Fleet telemetry federation: cross-process metrics push + one view.

PRs 4-8 deliberately broke the one-process observability assumption:
campaigns supervise N ``run`` children, edge dispatchers decide events
locally and reconcile via async backhaul, a uds endpoint serves
same-host inspectors, and the knowledge sidecar runs as its own
process. Each of those processes has its own PR 1 metrics registry —
and until this module, no single surface could answer "is the fleet
healthy, how stale are the edges, where is the latency going".

Three pieces (doc/observability.md "Fleet telemetry"):

* :class:`TelemetryRelay` — producer side. A background thread that
  walks the process registry every ``interval_s`` and pushes one
  ``nmz-telemetry-v1`` doc containing the samples that **changed since
  the last acknowledged push** (counters/histograms as absolute
  cumulatives — the aggregator derives monotonic deltas itself, so a
  replayed push whose ack was lost can never double-count; gauges as
  last-write). A failed push degrades to local-only metrics with ONE
  warning; the unsent samples simply remain changed-vs-acked and ride
  the next push — bounded by the series count, no queue to overflow.
  Pushes travel over the existing wires: ``POST /api/v3/telemetry`` on
  the REST endpoint, the ``telemetry`` op on the uds endpoint / the
  campaign supervisor's collector (the sidecar's framed-JSON codec).

* :class:`FleetAggregator` — consumer side, hosted by the orchestrator
  and/or the campaign supervisor. Merges pushes under ``(job,
  instance)`` keys with a per-instance ``seq`` watermark (replays and
  out-of-order duplicates are acked but not merged), evicts silent
  instances, caps post-merge label cardinality, feeds the SLO layer
  (obs/slo.py) with histogram bucket deltas, and serves the whole
  fleet as one document: ``GET /fleet`` (JSON, or ``?format=prom`` for
  a single Prometheus scrape covering every process) and ``nmz-tpu
  tools top``.

* **Federation hop** — a relay with an upstream target also forwards
  the foreign docs its local aggregator received (campaign ``run``
  children forward their inspectors' pushes to the supervisor), each
  doc keeping its own ``(job, instance, seq)`` identity so upstream
  dedupe still holds. The forward buffer is bounded; drops are counted
  (docs carry absolutes, so a dropped hop costs freshness, never
  correctness).

Cost contract: mirroring ``obs_enabled``, a disabled plane
(:func:`configure`, config key ``telemetry_enabled``) is one global
read — ``TelemetryRelay.start`` refuses to spawn its thread and no
seam touches the event hot path at all (the relay is the only moving
part, and it runs off-path at push cadence).
"""

from __future__ import annotations

import atexit
import json
import os
import socket as _socket
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from namazu_tpu import chaos
from namazu_tpu.obs import metrics, slo, spans
from namazu_tpu.utils.log import get_logger

log = get_logger("obs.federation")

__all__ = [
    "SCHEMA", "FLEET_SCHEMA", "TelemetryRelay", "FleetAggregator",
    "TelemetryServer", "default_instance", "pusher_for", "fetch",
    "handle_obs_op", "register_collector", "unregister_collector",
    "run_collectors",
    "configure", "enabled", "configure_from_config", "aggregator",
    "set_aggregator", "ensure_self_relay", "self_relay", "slo_summary",
    "reset",
]

SCHEMA = "nmz-telemetry-v1"
FLEET_SCHEMA = "nmz-fleet-v1"


def default_instance(prefix: str = "") -> str:
    """``[prefix.]pid@host`` — unique per producer process (a restart
    is a NEW instance, which is what makes absolute-cumulative merge
    semantics safe)."""
    base = f"{os.getpid()}@{_socket.gethostname()}"
    return f"{prefix}.{base}" if prefix else base


# -- producer side ---------------------------------------------------------

#: sampled-at-push-time gauges (edge table staleness age, parked-heap
#: depth): producers register a refresh callable instead of racing a
#: timer of their own — the relay runs them right before each encode,
#: so the pushed values are as fresh as the push itself
_collectors: List[Callable[[], None]] = []
_collectors_lock = threading.Lock()


def register_collector(fn: Callable[[], None]) -> None:
    with _collectors_lock:
        if fn not in _collectors:
            _collectors.append(fn)


def unregister_collector(fn: Callable[[], None]) -> None:
    with _collectors_lock:
        try:
            _collectors.remove(fn)
        except ValueError:
            pass


def run_collectors() -> None:
    """Refresh every registered sampled gauge (the relay's pre-encode
    hook; also callable directly before a local registry read)."""
    with _collectors_lock:
        fns = list(_collectors)
    for fn in fns:
        try:
            fn()
        except Exception:  # a gauge refresh must never kill a push
            log.debug("telemetry collector failed", exc_info=True)


class DeltaEncoder:
    """Change-tracking encoder over a metrics registry.

    Each :meth:`encode` returns the families whose samples changed
    since the last :meth:`mark_acked` — the "delta snapshot" on the
    wire. Sample VALUES are absolute cumulatives (bit-identical to the
    local registry); only the *selection* is differential, so an
    unacked sample is automatically re-sent with fresh values on the
    next cycle and a replay merges idempotently."""

    def __init__(self, registry=None) -> None:
        self._registry = registry
        self._acked: Dict[Tuple[str, Tuple[str, ...]], Any] = {}

    def _reg(self):
        return self._registry if self._registry is not None \
            else metrics.registry()

    def encode(self):
        """``(families, fingerprints)``: wire-form families holding the
        changed samples, and the fingerprint dict to pass to
        :meth:`mark_acked` once the push is acknowledged."""
        families: List[Dict[str, Any]] = []
        fps: Dict[Tuple[str, Tuple[str, ...]], Any] = {}
        for fam in self._reg().families():
            samples = []
            uppers: Optional[List[float]] = None
            for key, child in fam.items():
                skey = (fam.name, key)
                if isinstance(child, metrics.Histogram):
                    u, counts, s, n = child.raw_state()
                    uppers = list(u)
                    fp: Any = (n, s)
                    if self._acked.get(skey) == fp:
                        continue
                    samples.append({
                        "labels": dict(zip(fam.labelnames, key)),
                        "counts": counts, "sum": s, "count": n})
                else:
                    v = child.value
                    fp = v
                    if self._acked.get(skey) == fp:
                        continue
                    samples.append({
                        "labels": dict(zip(fam.labelnames, key)),
                        "value": v})
                fps[skey] = fp
            if samples:
                fdoc = {"name": fam.name, "type": fam.cls.KIND,
                        "help": fam.help,
                        "labelnames": list(fam.labelnames),
                        "samples": samples}
                if uppers is not None:
                    fdoc["uppers"] = uppers
                families.append(fdoc)
        return families, fps

    def mark_acked(self, fps: Dict) -> None:
        self._acked.update(fps)

    def reset(self) -> None:
        """Forget every ack: the next encode re-sends full state
        (absolutes merge idempotently, so a full resend is always
        safe)."""
        self._acked.clear()


class TelemetryRelay:
    """One producer's push loop; see the module header for semantics.

    ``push`` is any callable ``doc -> ack_dict`` that raises on failure
    (a transceiver's ``push_telemetry``, :func:`pusher_for`'s client);
    ``local`` is a :class:`FleetAggregator` merged synchronously (the
    orchestrator's self-relay feeds its own ``/fleet`` this way);
    ``forward_source`` enables the federation hop."""

    def __init__(self, job: str, instance: Optional[str] = None,
                 push: Optional[Callable[[dict], Any]] = None,
                 local: Optional["FleetAggregator"] = None,
                 interval_s: float = 2.0, registry=None,
                 forward_source: Optional["FleetAggregator"] = None,
                 target_desc: str = "") -> None:
        self.job = str(job)
        self.instance = instance or default_instance()
        self.interval_s = max(0.05, float(interval_s))
        self.local = local
        self._push = push
        self._target_desc = target_desc or "upstream"
        self.forward_source = forward_source
        if forward_source is not None and push is not None:
            forward_source.enable_forwarding()
        self._encoder = DeltaEncoder(registry)
        # profile delta (obs/profiling.py): lazily bound so a process
        # without a profiler pays one None check per cycle
        self._prof_delta = None
        self._seq = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._cycle_lock = threading.Lock()
        self._warned = False

    def set_upstream(self, push: Callable[[dict], Any],
                     forward_source: Optional["FleetAggregator"] = None,
                     target_desc: str = "") -> None:
        """Late-bind an upstream target (the single process-global
        self-relay may learn its push url after creation)."""
        # under the cycle lock: an in-flight push-less cycle must not
        # mark_acked into the freshly-reset encoder (that would record
        # series as delivered that the new upstream never saw)
        with self._cycle_lock:
            self._push = push
            # every sample acked during the push-less era was acked
            # LOCALLY only — the new upstream has never seen any of
            # it, so the next cycle must re-send full state (quiescent
            # series would otherwise stay invisible upstream forever)
            self._encoder.reset()
            if self._prof_delta is not None:
                self._prof_delta.reset()
            if target_desc:
                self._target_desc = target_desc
            if forward_source is not None:
                self.forward_source = forward_source
                forward_source.enable_forwarding()

    def start(self) -> "TelemetryRelay":
        if not enabled():
            return self  # disabled plane: no thread, no cost
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name=f"telemetry-{self.job}",
                daemon=True)
            self._thread.start()
        return self

    def _loop(self) -> None:
        # push IMMEDIATELY on start: short-lived producers (a 2-second
        # `run` child) must appear in the fleet view at all
        while True:
            self.flush()
            if self._stop.wait(self.interval_s):
                return

    def flush(self) -> None:
        """One push cycle NOW; never raises (a telemetry failure must
        never reach inspector/policy/campaign code — the knowledge-
        client cooldown contract, doc/knowledge.md)."""
        try:
            with self._cycle_lock:
                self._cycle()
        except Exception:  # pragma: no cover - defensive
            log.debug("telemetry cycle failed", exc_info=True)

    def _profile_delta(self):
        """Encode the profiler's changed stacks (obs/profiling.py) —
        same differential-selection contract as the metric encoder:
        absolutes on the wire, fingerprints advance only on ack."""
        from namazu_tpu.obs import profiling

        prof = profiling.profiler()
        if prof is None:
            return None, {}
        enc = self._prof_delta
        if enc is None or enc._prof is not prof:
            enc = self._prof_delta = profiling.ProfileDelta(prof)
        return enc.encode()

    def _cycle(self) -> None:
        families: List[dict] = []
        fps: Dict = {}
        prof_payload, prof_fps = None, {}
        if metrics.enabled():
            run_collectors()
            families, fps = self._encoder.encode()
            prof_payload, prof_fps = self._profile_delta()
        self._seq += 1
        doc = {"schema": SCHEMA, "job": self.job,
               "instance": self.instance, "seq": self._seq,
               "interval_s": self.interval_s, "families": families}
        if prof_payload is not None:
            doc["profile"] = prof_payload
        if metrics.enabled():
            # causality plane (obs/context.py): stamp the push so the
            # aggregator's logical clock merges every producer's —
            # federation hops keep the original producer's stamp
            from namazu_tpu.obs import context as _context

            doc["ctx"] = _context.wire_stamp()
        if self.local is not None:
            try:
                # forward=False: our own doc must not land in the
                # forward buffer we ourselves drain — it already goes
                # upstream first-hand below
                self.local.note_push(doc, forward=False)
            except Exception:
                log.debug("local telemetry merge failed", exc_info=True)
        if self._push is None:
            self._encoder.mark_acked(fps)
            if self._prof_delta is not None:
                self._prof_delta.mark_acked(prof_fps)
            return
        try:
            # chaos seam (doc/robustness.md): a dropped push must
            # degrade exactly like a dead collector
            if chaos.decide("telemetry.push.drop") is not None:
                raise OSError("chaos: telemetry push dropped")
            self._push(doc)
        except Exception as e:
            spans.telemetry_push(False)
            if not self._warned:
                self._warned = True
                log.warning(
                    "telemetry push to %s failed (%s); metrics stay "
                    "local-only and unsent samples ride the next push "
                    "(bounded — never an error into host code)",
                    self._target_desc, e)
            else:
                log.debug("telemetry push still failing: %s", e)
            return
        self._warned = False
        self._encoder.mark_acked(fps)
        if self._prof_delta is not None:
            self._prof_delta.mark_acked(prof_fps)
        spans.telemetry_push(True)
        src = self.forward_source
        if src is not None:
            docs = src.drain_forward()
            for i, fdoc in enumerate(docs):
                try:
                    self._push(fdoc)
                except Exception as e:
                    # requeue EVERY undelivered doc, in order (the cap
                    # inside requeue_forward counts any overflow) — a
                    # failed hop must never silently discard the rest
                    # of the drained buffer
                    for d in reversed(docs[i:]):
                        src.requeue_forward(d)
                    log.debug("telemetry forward failed (%s); %d "
                              "doc(s) re-queued", e, len(docs) - i)
                    break

    def shutdown(self) -> None:
        """Stop the loop and perform one final flush so a producer's
        last interval of samples reaches the fleet before exit."""
        self._stop.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=2.0)
        self.flush()


# -- consumer side ---------------------------------------------------------

class _FamilyState:
    __slots__ = ("type", "help", "labelnames", "uppers", "samples",
                 "alt")

    def __init__(self, typ: str, help: str, labelnames: Tuple[str, ...],
                 uppers: Optional[List[float]]) -> None:
        self.type = typ
        self.help = help
        self.labelnames = labelnames
        self.uppers = uppers
        #: labelkey tuple -> float (counter/gauge) or
        #: (raw counts, sum, count) (histogram)
        self.samples: "OrderedDict[Tuple[str, ...], Any]" = OrderedDict()
        #: mixed-bucket-layout segregation (doc/observability.md):
        #: a push whose histogram layout differs from the first-seen
        #: one lands here, keyed by its uppers tuple — warned about and
        #: counted, NEVER blended into the primary samples (quantiles
        #: over mixed layouts would be fiction). Lazy: None until a
        #: mismatch actually happens.
        self.alt: Optional[Dict[Tuple[float, ...],
                                "OrderedDict[Tuple[str, ...], Any]"]] \
            = None


class _InstanceState:
    __slots__ = ("job", "instance", "last_seq", "last_seen", "first_seen",
                 "interval_s", "pushes", "duplicates", "families",
                 "rates", "run_rates", "profile")

    def __init__(self, job: str, instance: str, now: float) -> None:
        self.job = job
        self.instance = instance
        self.last_seq = 0
        self.last_seen = now
        self.first_seen = now
        self.interval_s = 2.0
        self.pushes = 0
        self.duplicates = 0
        self.families: Dict[str, _FamilyState] = {}
        #: profiling plane (obs/profiling.py): last-write absolute
        #: collapsed-stack counts from the instance's profile deltas,
        #: or None for producers without a profiler
        self.profile: Optional[Dict[str, Any]] = None
        #: counter name -> (t, total, rate) for the summary rates
        self.rates: Dict[str, Tuple[float, float, Optional[float]]] = {}
        #: tenancy plane: run namespace -> (t, total, rate) derived
        #: from nmz_tenancy_events_total{run} (the /fleet RUN rows)
        self.run_rates: Dict[str, Tuple[float, float,
                                        Optional[float]]] = {}


class FleetAggregator:
    """Merge point for telemetry pushes; see the module header."""

    #: distinct label-value series admitted per (instance, family)
    #: AFTER the merge — the producer-side entity cap (spans.py) is the
    #: primary defense, this is the aggregator's own bound against a
    #: misbehaving producer
    MAX_SAMPLES_PER_FAMILY = 128
    #: federation-hop buffer bound (docs, not samples)
    FORWARD_CAP = 256
    #: distinct collapsed stacks held per instance's profile state
    MAX_PROFILE_STACKS = 1024
    #: counters whose per-instance rate the summary derives
    RATE_COUNTERS = (spans.EVENTS_INTERCEPTED, spans.EDGE_DECISIONS)

    def __init__(self, stale_after_s: float = 0.0,
                 evict_after_s: float = 120.0) -> None:
        #: 0 = auto: max(5s, 3x the instance's own push interval)
        self.stale_after_s = max(0.0, float(stale_after_s))
        self.evict_after_s = max(0.0, float(evict_after_s))
        self._lock = threading.Lock()
        self._instances: "OrderedDict[Tuple[str, str], _InstanceState]" \
            = OrderedDict()
        self._forward: deque = deque()
        self._forwarding = False
        self._forward_dropped = 0
        self._series_folded = 0
        self._layouts_segregated = 0
        self._layout_warned: set = set()
        self._slo = slo.SLOEvaluator(slo.DEFAULT_SLOS, explicit=False)
        self._last_slo_eval = 0.0

    # -- configuration ----------------------------------------------------

    def set_slos(self, specs, explicit: bool = True) -> None:
        self._slo = slo.SLOEvaluator(specs, explicit=explicit)

    @property
    def slo_evaluator(self) -> slo.SLOEvaluator:
        return self._slo

    def enable_forwarding(self) -> None:
        self._forwarding = True

    # -- ingest -----------------------------------------------------------

    def note_push(self, doc: Any, forward: bool = True,
                  now: Optional[float] = None) -> Dict[str, Any]:
        """Merge one telemetry doc; returns the ack. Raises ValueError
        on a malformed doc (the wire surfaces turn that into a 400 /
        ``ok: false``)."""
        if not isinstance(doc, dict) or doc.get("schema") != SCHEMA:
            raise ValueError(f"telemetry doc must carry schema "
                             f"{SCHEMA!r}")
        job = str(doc.get("job") or "")
        instance = str(doc.get("instance") or "")
        if not job or not instance:
            raise ValueError("telemetry doc needs job + instance")
        try:
            seq = int(doc.get("seq"))
        except (TypeError, ValueError):
            raise ValueError("telemetry doc needs an integer seq") \
                from None
        now = time.monotonic() if now is None else now
        if metrics.enabled():
            # merge the producer's logical clock (obs/context.py) —
            # the aggregator is a receive point like any other wire
            from namazu_tpu.obs import context as _context

            _context.observe_wire(doc.get("ctx"))
        hist_deltas: List[Tuple[str, List[float], List[int]]] = []
        with self._lock:
            st = self._instances.get((job, instance))
            if st is None:
                st = self._instances[(job, instance)] = \
                    _InstanceState(job, instance, now)
            st.last_seen = now
            try:
                st.interval_s = float(doc.get("interval_s")
                                      or st.interval_s)
            except (TypeError, ValueError):
                pass
            if seq <= st.last_seq:
                # replay of a push whose ack was lost (or an out-of-
                # order duplicate): acknowledge WITHOUT merging — this
                # is the exactly-once half of the idempotence contract
                st.duplicates += 1
                return {"ok": True, "duplicate": True,
                        "last_seq": st.last_seq}
            st.last_seq = seq
            st.pushes += 1
            self._merge(st, doc.get("families") or [], hist_deltas)
            self._merge_profile(st, doc.get("profile"))
            self._update_rates(st, now)
            # evict on INGEST too, not only when /fleet is read: an
            # unattended aggregator (a supervisor nobody scrapes) must
            # not accumulate one dead pid-scoped instance per finished
            # run child forever
            self._evict(now)
            dropped = 0
            if forward and self._forwarding:
                self._forward.append(doc)
                while len(self._forward) > self.FORWARD_CAP:
                    self._forward.popleft()
                    dropped += 1
                if dropped:
                    self._forward_dropped += dropped
            n_instances = len(self._instances)
        # SLO windows + gauges outside the lock: they must never block
        # a concurrent producer's merge
        if dropped:
            spans.telemetry_forward_dropped(dropped)
        for metric, uppers, deltas in hist_deltas:
            self._slo.note_hist_delta(metric, uppers, deltas, now)
        # evaluate on INGEST (throttled): burn gauges, breach
        # transitions, and the flight-recorder annotation must fire
        # even in a deployment nobody reads over JSON — a Prometheus-
        # only scrape (or no scrape at all) would otherwise leave the
        # SLO plane silently green while objectives burn
        if now - self._last_slo_eval >= 1.0:
            self._last_slo_eval = now
            self._slo.evaluate(self.max_gauge, now)
        spans.fleet_occupancy(n_instances, self._stale_count(now))
        return {"ok": True, "last_seq": seq}

    def _merge(self, st: _InstanceState, families: List[Any],
               hist_deltas: List) -> None:
        """Merge one doc's families into ``st`` (caller holds the
        lock). Absolute-cumulative semantics: counters/histograms
        last-write their instance's cumulative state (the producer is
        monotonic per instance — a pid is part of the instance key),
        gauges last-write by definition. Bucket deltas vs the stored
        previous state are computed here for the SLO layer."""
        for f in families:
            if not isinstance(f, dict):
                continue
            name = str(f.get("name") or "")
            if not name:
                continue
            labelnames = tuple(str(n) for n in f.get("labelnames") or ())
            uppers = f.get("uppers")
            try:
                inc_uppers = ([float(u) for u in uppers]
                              if uppers else None)
            except (TypeError, ValueError):
                inc_uppers = None
            fs = st.families.get(name)
            if fs is None:
                fs = st.families[name] = _FamilyState(
                    str(f.get("type") or "gauge"),
                    str(f.get("help") or ""), labelnames, inc_uppers)
            # mixed bucket layouts (a fleet mid-rollout: old producers
            # on the pre-sub-ms nmz_event_stage_seconds bounds replay
            # through a forward hop into an instance slot that already
            # saw the new layout): WARN AND SEGREGATE — the layout's
            # samples are kept in a side table keyed by its uppers,
            # counted in /fleet as hist_layouts_segregated, and never
            # blended into primary quantiles
            alt_samples = None
            if (fs.type == "histogram" and fs.uppers is not None
                    and inc_uppers is not None
                    and inc_uppers != fs.uppers):
                wkey = (st.job, st.instance, name)
                if wkey not in self._layout_warned:
                    self._layout_warned.add(wkey)
                    log.warning(
                        "telemetry: %s/%s pushed %s with a different "
                        "bucket layout (%d vs %d bounds); segregating "
                        "— mixed layouts are never blended into one "
                        "quantile", st.job, st.instance, name,
                        len(inc_uppers), len(fs.uppers))
                if fs.alt is None:
                    fs.alt = {}
                alt_samples = fs.alt.setdefault(
                    tuple(inc_uppers), OrderedDict())
            watched = fs.type == "histogram" \
                and self._slo.watches(name) and fs.uppers \
                and alt_samples is None
            fam_delta = [0] * (len(fs.uppers) + 1) if watched else None
            for s in f.get("samples") or []:
                if not isinstance(s, dict):
                    continue
                labels = s.get("labels") or {}
                key = tuple(str(labels.get(n, ""))
                            for n in fs.labelnames)
                existing = fs.samples.get(key)
                if existing is None \
                        and len(fs.samples) >= self.MAX_SAMPLES_PER_FAMILY:
                    # post-merge cardinality cap: the sample is dropped
                    # and COUNTED — a fold that silently summed
                    # absolutes from different series would double-
                    # count on every push
                    self._series_folded += 1
                    continue
                if fs.type == "histogram":
                    try:
                        counts = [int(c) for c in s.get("counts") or []]
                        hsum = float(s.get("sum", 0.0))
                        hcount = int(s.get("count", 0))
                    except (TypeError, ValueError):
                        continue
                    if alt_samples is not None:
                        # segregated layout: last-write into its own
                        # side table, never the primary samples
                        if len(counts) == len(inc_uppers) + 1:
                            if key not in alt_samples:
                                self._layouts_segregated += 1
                            alt_samples[key] = (counts, hsum, hcount)
                        continue
                    if fs.uppers is None \
                            or len(counts) != len(fs.uppers) + 1:
                        # shape mismatch without a declared layout:
                        # still warn-and-count, never silently vanish
                        wkey = (st.job, st.instance, name)
                        if wkey not in self._layout_warned:
                            self._layout_warned.add(wkey)
                            log.warning(
                                "telemetry: %s/%s pushed %s with "
                                "%d bucket counts against %s bounds; "
                                "sample segregated (counted, not "
                                "merged)", st.job, st.instance, name,
                                len(counts),
                                "no" if fs.uppers is None
                                else str(len(fs.uppers)))
                        self._layouts_segregated += 1
                        continue
                    if fam_delta is not None:
                        prev = existing[0] if existing else [0] * len(counts)
                        for i, c in enumerate(counts):
                            # clamp: a producer-side registry reset
                            # shows as a regressed cumulative
                            fam_delta[i] += max(0, c - prev[i])
                    fs.samples[key] = (counts, hsum, hcount)
                else:
                    try:
                        fs.samples[key] = float(s.get("value", 0.0))
                    except (TypeError, ValueError):
                        continue
            if fam_delta is not None and any(fam_delta):
                hist_deltas.append((name, fs.uppers, fam_delta))

    def _merge_profile(self, st: _InstanceState, prof: Any) -> None:
        """Merge one push's profile delta (obs/profiling.py wire
        payload; caller holds the lock). Same absolute-cumulative
        last-write semantics as counters — a full resend after a lost
        ack merges idempotently, and the seq watermark upstream already
        discarded duplicate docs."""
        if not isinstance(prof, dict) \
                or not isinstance(prof.get("stacks"), list):
            return
        pstate = st.profile
        if pstate is None:
            pstate = st.profile = {"stacks": OrderedDict(),
                                   "samples_total": 0, "dropped": 0,
                                   "interval_s": 0.01}
        stacks = pstate["stacks"]
        for s in prof["stacks"]:
            if not isinstance(s, dict):
                continue
            try:
                key = (str(s.get("plane") or "other"),
                       tuple(str(x) for x in s.get("stack") or ()))
                cnt = int(s.get("count", 0))
            except (TypeError, ValueError):
                continue
            if not key[1]:
                continue
            if key not in stacks \
                    and len(stacks) >= self.MAX_PROFILE_STACKS:
                continue
            stacks[key] = cnt
        try:
            pstate["samples_total"] = int(
                prof.get("samples_total", pstate["samples_total"]))
            pstate["dropped"] = int(
                prof.get("dropped", pstate["dropped"]))
            pstate["interval_s"] = float(
                prof.get("interval_s", pstate["interval_s"]))
        except (TypeError, ValueError):
            pass

    def _profile_top(self, st: _InstanceState
                     ) -> Optional[Tuple[str, float]]:
        """Dominant self-time frame of an instance's merged profile
        (leaf with the most samples) — the /fleet PROF column (caller
        holds the lock)."""
        p = st.profile
        if not p or not p["stacks"]:
            return None
        selfs: Dict[str, int] = {}
        for (_plane, stack), c in p["stacks"].items():
            leaf = stack[-1]
            selfs[leaf] = selfs.get(leaf, 0) + c
        total = sum(selfs.values())
        if total <= 0:
            return None
        frame, cnt = max(selfs.items(), key=lambda kv: kv[1])
        return frame, cnt / total

    def _update_rates(self, st: _InstanceState, now: float) -> None:
        for name in self.RATE_COUNTERS:
            fs = st.families.get(name)
            if fs is None or fs.type != "counter":
                continue
            total = sum(v for v in fs.samples.values()
                        if isinstance(v, float))
            prev = st.rates.get(name)
            rate: Optional[float] = None
            if prev is not None and now > prev[0]:
                # floor the denominator at half the push interval: a
                # drained forward backlog merges queued docs ms apart,
                # and dividing each doc's interval-worth of delta by
                # that gap would report absurd rates (the floor bounds
                # the overshoot at ~2x until the next steady push)
                dt = max(now - prev[0], 0.5 * st.interval_s)
                rate = max(0.0, total - prev[1]) / dt
            elif prev is not None:
                rate = prev[2]
            st.rates[name] = (now, total, rate)
        # per-run-namespace rates (tenancy plane): same derivation,
        # one series per `run` label value
        by_run = self._counter_by(st, spans.TENANCY_EVENTS, "run")
        for run, total in by_run.items():
            prev = st.run_rates.get(run)
            rate = None
            if prev is not None and now > prev[0]:
                dt = max(now - prev[0], 0.5 * st.interval_s)
                rate = max(0.0, total - prev[1]) / dt
            elif prev is not None:
                rate = prev[2]
            st.run_rates[run] = (now, total, rate)
        # runs that vanished from the push (released/reclaimed
        # namespaces) drop their stale rate rows
        for run in [r for r in st.run_rates if r not in by_run]:
            del st.run_rates[run]

    # -- federation hop ---------------------------------------------------

    def drain_forward(self) -> List[dict]:
        with self._lock:
            docs, self._forward = list(self._forward), deque()
        return docs

    def requeue_forward(self, doc: dict) -> None:
        with self._lock:
            self._forward.appendleft(doc)
            dropped = 0
            while len(self._forward) > self.FORWARD_CAP:
                # evict the OLDEST doc (the left end, where requeues
                # land) — same freshness-first rule as the ingest-path
                # overflow; dropping the right end would discard the
                # newest arrivals in favor of superseded snapshots
                self._forward.popleft()
                dropped += 1
            if dropped:
                self._forward_dropped += dropped
        if dropped:
            spans.telemetry_forward_dropped(dropped)

    # -- read side --------------------------------------------------------

    def _stale_after(self, st: _InstanceState) -> float:
        if self.stale_after_s > 0:
            return self.stale_after_s
        return max(5.0, 3.0 * st.interval_s)

    def _stale_count(self, now: float) -> int:
        with self._lock:
            return sum(1 for st in self._instances.values()
                       if now - st.last_seen > self._stale_after(st))

    def _counter_total(self, st: _InstanceState,
                       name: str) -> Optional[float]:
        fs = st.families.get(name)
        if fs is None:
            return None
        return sum(v for v in fs.samples.values()
                   if isinstance(v, float))

    def _counter_by(self, st: _InstanceState, name: str,
                    label: str) -> Dict[str, float]:
        """Per-label-value totals of one counter family (the codec
        byte ledger's ``nmz_wire_bytes_total{codec}`` read), merged
        across the family's other labels."""
        fs = st.families.get(name)
        if fs is None:
            return {}
        try:
            idx = fs.labelnames.index(label)
        except ValueError:
            return {}
        out: Dict[str, float] = {}
        for key, v in fs.samples.items():
            if isinstance(v, float):
                out[key[idx]] = out.get(key[idx], 0.0) + v
        return out

    def _gauge_max(self, st: _InstanceState,
                   name: str) -> Optional[float]:
        fs = st.families.get(name)
        if fs is None or not fs.samples:
            return None
        vals = [v for v in fs.samples.values() if isinstance(v, float)]
        return max(vals) if vals else None

    def _gauge_sum(self, st: _InstanceState,
                   name: str) -> Optional[float]:
        """For additive per-entity gauges (parked-heap depth): an
        instance running 4 edges with 100 parked each holds 400, not
        100 — max is only right for worst-of gauges (staleness,
        version)."""
        fs = st.families.get(name)
        if fs is None or not fs.samples:
            return None
        vals = [v for v in fs.samples.values() if isinstance(v, float)]
        return sum(vals) if vals else None

    def _runs_section(self, st: _InstanceState) -> Dict[str, Any]:
        """``{"runs": {run: {...}}}`` for one instance, or ``{}`` when
        it serves no tenant namespaces (caller holds the lock)."""
        totals = self._counter_by(st, spans.TENANCY_EVENTS, "run")
        if not totals:
            return {}
        parked = self._counter_by(st, spans.TENANCY_PARKED, "run")
        out: Dict[str, Any] = {}
        for run, total in sorted(totals.items()):
            rate = st.run_rates.get(run, (0, 0, None))[2]
            out[run] = {
                "events_total": round(total),
                "events_per_sec": (round(rate, 1)
                                   if rate is not None else None),
                "parked": round(parked.get(run, 0)),
            }
        return {"runs": out}

    def _hist_quantile(self, st: _InstanceState, name: str,
                       q: float) -> Optional[float]:
        fs = st.families.get(name)
        if fs is None or fs.type != "histogram" or fs.uppers is None:
            return None
        merged = [0] * (len(fs.uppers) + 1)
        for v in fs.samples.values():
            counts = v[0]
            for i, c in enumerate(counts):
                merged[i] += c
        total = sum(merged)
        if total <= 0:
            return None
        target = q * total
        acc = 0
        for i, c in enumerate(merged):
            acc += c
            if acc >= target:
                # the +Inf overflow reports the highest finite bound
                # (the Prometheus histogram_quantile convention)
                return fs.uppers[min(i, len(fs.uppers) - 1)]
        return fs.uppers[-1]

    def _hist_quantile_by(self, st: _InstanceState, name: str,
                          label: str, q: float) -> Dict[str, float]:
        """Per-label-value quantiles of one histogram family (the
        causality plane's ``nmz_event_stage_seconds{stage}`` read):
        label value -> q-quantile upper bound, merged across the
        family's other labels."""
        fs = st.families.get(name)
        if fs is None or fs.type != "histogram" or fs.uppers is None:
            return {}
        try:
            idx = fs.labelnames.index(label)
        except ValueError:
            return {}
        merged: Dict[str, List[int]] = {}
        for key, v in fs.samples.items():
            counts = v[0]
            acc = merged.setdefault(key[idx],
                                    [0] * (len(fs.uppers) + 1))
            for i, c in enumerate(counts):
                acc[i] += c
        out: Dict[str, float] = {}
        for value, counts in merged.items():
            total = sum(counts)
            if total <= 0:
                continue
            target = q * total
            acc = 0
            for i, c in enumerate(counts):
                acc += c
                if acc >= target:
                    out[value] = fs.uppers[min(i, len(fs.uppers) - 1)]
                    break
            else:  # pragma: no cover - defensive
                out[value] = fs.uppers[-1]
        return out

    def max_gauge(self, name: str) -> Optional[float]:
        """Fleet-wide max of a gauge (the staleness-SLO resolver)."""
        best: Optional[float] = None
        with self._lock:
            for st in self._instances.values():
                v = self._gauge_max(st, name)
                if v is not None and (best is None or v > best):
                    best = v
        return best

    def _evict(self, now: float) -> None:
        """Drop instances silent past the eviction window (caller
        holds the lock). Staleness is surfaced first — /fleet marks an
        instance stale instead of serving frozen numbers, then forgets
        it entirely."""
        if self.evict_after_s <= 0:
            return
        dead = [key for key, st in self._instances.items()
                if now - st.last_seen > self.evict_after_s]
        for key in dead:
            del self._instances[key]

    def payload(self, now: Optional[float] = None) -> Dict[str, Any]:
        """The ``/fleet`` JSON document."""
        now = time.monotonic() if now is None else now
        rows: List[Dict[str, Any]] = []
        # rows are built UNDER the lock: the per-family sample dicts
        # mutate on every concurrent push, and iterating them outside
        # would intermittently raise mid-read exactly when the fleet is
        # growing
        with self._lock:
            self._evict(now)
            snapshot = list(self._instances.values())
            fleet_version = 0.0
            for st in snapshot:
                for name in (spans.TABLE_VERSION,
                             spans.EDGE_TABLE_VERSION_HELD):
                    v = self._gauge_max(st, name)
                    if v is not None and v > fleet_version:
                        fleet_version = v
            stale_n = 0
            for st in snapshot:
                age = now - st.last_seen
                stale = age > self._stale_after(st)
                stale_n += stale
                version = self._gauge_max(st, spans.TABLE_VERSION)
                edge_version = self._gauge_max(
                    st, spans.EDGE_TABLE_VERSION_HELD)
                held = (edge_version if edge_version is not None
                        else version)
                ev_rate = st.rates.get(spans.EVENTS_INTERCEPTED,
                                       (0, 0, None))[2]
                prof_top = self._profile_top(st)
                rows.append({
                    "job": st.job,
                    "instance": st.instance,
                    "seq": st.last_seq,
                    "pushes": st.pushes,
                    "duplicate_pushes": st.duplicates,
                    "interval_s": st.interval_s,
                    "last_seen_age_s": round(age, 3),
                    "stale": stale,
                    "events_per_sec": (round(ev_rate, 1)
                                       if ev_rate is not None else None),
                    "events_total": self._counter_total(
                        st, spans.EVENTS_INTERCEPTED),
                    "edge_decisions_total": self._counter_total(
                        st, spans.EDGE_DECISIONS),
                    "queue_dwell_p99_s": self._hist_quantile(
                        st, spans.QUEUE_DWELL, 0.99),
                    "dispatch_p99_s": self._hist_quantile(
                        st, spans.EVENT_E2E, 0.99),
                    "backhaul_lag_p99_s": self._hist_quantile(
                        st, spans.EDGE_BACKHAUL_LAG, 0.99),
                    # per-lifecycle-segment p99s (queue/decision/
                    # parking/dispatch/wire/edge_parking/backhaul) —
                    # the causality plane's "where does the
                    # millisecond go", federated (obs/causality.py)
                    "stage_p99_s": self._hist_quantile_by(
                        st, spans.EVENT_STAGE, "stage", 0.99),
                    # the negotiated-codec byte ledger
                    # (nmz_wire_bytes_total{codec}): what this
                    # instance's wires actually moved, by codec — the
                    # tools-top CODEC column and the /fleet face of the
                    # JSON-vs-binary savings (doc/performance.md)
                    "wire_bytes_by_codec": {
                        k: round(v) for k, v in self._counter_by(
                            st, spans.WIRE_BYTES, "codec").items()},
                    "table_version": held,
                    "table_skew": (round(fleet_version - held)
                                   if held is not None else None),
                    # SKEW's time-domain twin: the measured publish->
                    # edge-install propagation p99
                    # (nmz_table_propagation_seconds, obs/spans.py)
                    "table_propagation_p99_s": self._hist_quantile(
                        st, spans.TABLE_PROPAGATION, 0.99),
                    # triage plane: distinct failure signatures this
                    # instance holds a dossier for (the tools-top SIGS
                    # column; doc/observability.md "Triage")
                    "triage_signatures": self._gauge_max(
                        st, spans.TRIAGE_SIGNATURES),
                    # campaign progress plane (obs/stats.py via the
                    # supervisor's per-slot publication): measured
                    # repro rate, pace, next-repro ETA, and the band
                    # SPRT verdict — the tools-top RATE/ETA columns
                    "repro_rate": self._gauge_max(
                        st, spans.CAMPAIGN_RATE),
                    "repros_per_hour": self._gauge_max(
                        st, spans.CAMPAIGN_REPROS_PER_HOUR),
                    # the virtual-clock twin (None on wall campaigns):
                    # same pace formula over VIRTUAL elapsed — shown
                    # beside the wall rate, never in place of it
                    "repros_per_hour_virtual": self._gauge_max(
                        st, spans.CAMPAIGN_REPROS_PER_HOUR_VIRTUAL),
                    "vclock_speedup": self._gauge_max(
                        st, spans.VCLOCK_SPEEDUP),
                    "eta_next_repro_s": self._gauge_max(
                        st, spans.CAMPAIGN_ETA_NEXT),
                    "campaign_in_band": self._gauge_max(
                        st, spans.CAMPAIGN_IN_BAND),
                    "edge_table_staleness_s": self._gauge_max(
                        st, spans.EDGE_TABLE_STALENESS),
                    "edge_parked": self._gauge_sum(
                        st, spans.EDGE_PARKED),
                    # profiling plane (obs/profiling.py): the
                    # instance's dominant self-time frame and its share
                    # of all self samples — the tools-top PROF column
                    "prof_top_frame": (prof_top[0] if prof_top
                                       else None),
                    "prof_top_share": (round(prof_top[1], 4)
                                       if prof_top else None),
                    # tenancy plane (doc/tenancy.md): one row per run
                    # namespace this instance serves — events, rate,
                    # and parked depth per tenant, the `tools top` RUN
                    # table. Instances without tenancy metrics carry
                    # no key (pre-tenancy payload shape preserved).
                    **self._runs_section(st),
                })
        rows.sort(key=lambda r: (r["job"], r["instance"]))
        spans.fleet_occupancy(len(rows), stale_n)
        return {
            "schema": FLEET_SCHEMA,
            "instance_count": len(rows),
            "stale_instances": stale_n,
            "fleet_table_version": fleet_version,
            "series_folded": self._series_folded,
            "forward_dropped": self._forward_dropped,
            "hist_layouts_segregated": self._layouts_segregated,
            "instances": rows,
            "slo": {
                "explicit": self._slo.explicit,
                "objectives": self._slo.evaluate(self.max_gauge, now),
            },
        }

    def slo_summary(self) -> Optional[Dict[str, Any]]:
        """The analytics fold (obs/analytics.payload): only EXPLICIT
        objectives — fleets that never declared SLOs keep a payload
        byte-identical to ``compute_payload`` (the REST-vs-CLI parity
        the analytics tests pin)."""
        if not self._slo.explicit:
            return None
        return {"objectives": self._slo.evaluate(self.max_gauge)}

    def prometheus(self) -> str:
        """Every merged sample as one Prometheus text exposition, with
        ``job``/``instance`` labels injected — one scrape covers the
        whole fleet."""
        esc = metrics._escape_label_value
        fmt = metrics._format_value
        # a prom-only deployment's scrape cadence drives SLO
        # evaluation too (fresh nmz_slo_burn in the host registry,
        # breach transitions), same as the JSON payload() path
        self._slo.evaluate(self.max_gauge)
        # sample dicts are copied UNDER the lock (the stored values —
        # floats and already-replaced-wholesale histogram tuples — are
        # never mutated in place, so a shallow copy is a consistent
        # snapshot); rendering then happens lock-free
        with self._lock:
            snapshot = []
            for st in self._instances.values():
                copies = {}
                for name, fs in st.families.items():
                    c = _FamilyState(fs.type, fs.help, fs.labelnames,
                                     fs.uppers)
                    c.samples = OrderedDict(fs.samples)
                    copies[name] = c
                snapshot.append((st.job, st.instance, copies))
        by_name: "OrderedDict[str, List]" = OrderedDict()
        for job, instance, families in snapshot:
            for name in sorted(families):
                by_name.setdefault(name, []).append(
                    (job, instance, families[name]))
        lines: List[str] = []
        for name, rows in by_name.items():
            fs0 = rows[0][2]
            if fs0.help:
                lines.append(f"# HELP {name} {fs0.help}")
            else:
                lines.append(f"# HELP {name}")
            lines.append(f"# TYPE {name} {fs0.type}")
            for job, instance, fs in rows:
                base = (f'job="{esc(job)}",instance="{esc(instance)}"')
                for key, value in fs.samples.items():
                    pairs = base
                    for n, v in zip(fs.labelnames, key):
                        pairs += f',{n}="{esc(v)}"'
                    if fs.type != "histogram":
                        lines.append(f"{name}{{{pairs}}} {fmt(value)}")
                        continue
                    counts, hsum, hcount = value
                    acc = 0
                    for upper, c in zip(fs.uppers or [], counts):
                        acc += c
                        lines.append(
                            f'{name}_bucket{{{pairs},'
                            f'le="{fmt(upper)}"}} {acc}')
                    lines.append(
                        f'{name}_bucket{{{pairs},le="+Inf"}} {hcount}')
                    lines.append(f"{name}_sum{{{pairs}}} {fmt(hsum)}")
                    lines.append(f"{name}_count{{{pairs}}} {hcount}")
        return "\n".join(lines) + ("\n" if lines else "")


# -- wire clients ----------------------------------------------------------

class _FramedPushClient:
    """Persistent framed-JSON push client (the sidecar codec) with one
    transparent reconnect — the ``uds://`` / ``tcp://`` face of
    :func:`pusher_for`. ``target`` is an AF_UNIX path, or
    ``(host, port)`` for the sidecar's TCP wire."""

    def __init__(self, target, timeout: float = 10.0) -> None:
        self._target = target
        self._timeout = timeout
        self._sock: Optional[_socket.socket] = None
        self._lock = threading.Lock()

    def _close(self) -> None:
        sock, self._sock = self._sock, None
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass

    def request(self, req: dict) -> dict:
        from namazu_tpu.endpoint.agent import read_frame, write_frame

        with self._lock:
            last_exc: Optional[BaseException] = None
            for attempt in (0, 1):
                sock = self._sock
                if sock is None:
                    family = (_socket.AF_INET
                              if isinstance(self._target, tuple)
                              else _socket.AF_UNIX)
                    sock = _socket.socket(family, _socket.SOCK_STREAM)
                    sock.settimeout(self._timeout)
                    try:
                        sock.connect(self._target)
                    except OSError as e:
                        try:
                            sock.close()
                        except OSError:
                            pass
                        last_exc = e
                        continue
                    self._sock = sock
                try:
                    write_frame(sock, req)
                    resp = read_frame(sock)
                    if resp is None:
                        raise OSError("connection closed mid-reply")
                    return resp
                except (OSError, ValueError) as e:
                    self._close()
                    last_exc = e
            raise last_exc  # type: ignore[misc]

    def push(self, doc: dict) -> dict:
        resp = self.request({"op": "telemetry", "doc": doc})
        if not resp.get("ok"):
            raise RuntimeError(resp.get("error", "telemetry refused"))
        return resp


def fetch(url: str, op: str, fmt: str = "") -> Any:
    """Read side of the fleet surfaces for the CLI (``tools metrics`` /
    ``tools top``): one ``fleet`` or ``metrics`` read against a live
    process. ``http(s)://`` hits the REST routes (``/fleet``,
    ``/metrics.json``); ``uds://`` speaks the framed obs ops — the
    same-host fleets without a TCP port. Returns the parsed JSON doc,
    or the exposition text when ``fmt == "prom"``."""
    if op not in ("fleet", "metrics", "profile"):
        raise ValueError(f"unknown obs read {op!r} "
                         "(want fleet|metrics|profile)")
    if url.startswith(("http://", "https://")):
        import urllib.request

        route = {"fleet": "/fleet", "metrics": "/metrics.json",
                 "profile": "/profile?format=json"}[op]
        if op == "fleet" and fmt == "prom":
            route += "?format=prom"
        with urllib.request.urlopen(url.rstrip("/") + route,
                                    timeout=10) as r:
            raw = r.read()
        return raw.decode() if fmt == "prom" else json.loads(raw)
    target = _framed_target(url)
    if target is not None:
        client = _FramedPushClient(target)
        try:
            req: Dict[str, Any] = {"op": op}
            if fmt == "prom":
                req["format"] = "prom"
            resp = client.request(req)
        finally:
            client._close()
        if not resp.get("ok"):
            raise RuntimeError(resp.get("error", f"{op} refused"))
        if fmt == "prom":
            return resp.get("text", "")
        return resp.get(op)
    raise ValueError(f"unsupported obs url {url!r} "
                     "(want http(s)://, uds:// or tcp://)")


def _framed_target(url: str):
    """The framed-wire connect target for a telemetry url, or None when
    the url is not a framed scheme: ``uds://path`` (a uds endpoint, a
    campaign supervisor's collector) or ``tcp://host:port`` (the
    sidecar's framed wire)."""
    if url.startswith("uds://"):
        return url[len("uds://"):]
    if url.startswith("tcp://"):
        host, _, port = url[len("tcp://"):].rpartition(":")
        return (host or "127.0.0.1", int(port))
    return None


def pusher_for(url: str) -> Callable[[dict], Any]:
    """A push callable for a telemetry target url: ``http(s)://`` =
    ``POST /api/v3/telemetry`` on an orchestrator's REST endpoint,
    ``uds://path`` / ``tcp://host:port`` = the framed ``telemetry`` op
    (uds endpoint, the campaign supervisor's collector, the sidecar's
    framed wire)."""
    if url.startswith(("http://", "https://")):
        import urllib.request

        target = url.rstrip("/") + "/api/v3/telemetry"

        def push(doc: dict) -> dict:
            req = urllib.request.Request(
                target, data=json.dumps(doc).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as r:
                return json.loads(r.read() or b"{}")

        return push
    target = _framed_target(url)
    if target is not None:
        return _FramedPushClient(target).push
    raise ValueError(f"unsupported telemetry url {url!r} "
                     "(want http(s)://, uds:// or tcp://)")


# -- wire surface (shared by UdsEndpoint + TelemetryServer) ----------------

def handle_obs_op(req: dict,
                  agg: Optional[FleetAggregator] = None
                  ) -> Optional[dict]:
    """Answer one framed observability op (``telemetry`` / ``fleet`` /
    ``metrics`` / ``profile`` / ``spans``); None = not an obs op (the
    caller keeps dispatching).
    Both framed wires — the uds event endpoint and the campaign
    supervisor's collector — route here, so the fleet surface is
    identical wherever the aggregator is hosted."""
    op = req.get("op")
    if op == "telemetry":
        if not enabled():
            # the kill switch holds on the SERVING side too: a fleet
            # with telemetry_enabled = false acks-and-discards pushes
            # from producers that didn't read the config, rather than
            # growing an aggregator nobody asked for
            return {"ok": True, "disabled": True}
        try:
            ack = (agg or aggregator()).note_push(req.get("doc"))
        except ValueError as e:
            return {"ok": False, "error": str(e)}
        return dict(ack, ok=True)
    if op == "fleet":
        a = agg or aggregator()
        if req.get("format") == "prom":
            return {"ok": True, "text": a.prometheus()}
        return {"ok": True, "fleet": a.payload()}
    if op == "metrics":
        # sampled gauges (edge staleness/parked, knowledge occupancy)
        # refresh on a relay cadence; a DIRECT registry read must not
        # serve values up to a push interval old (or never-set, when
        # the relay is disabled)
        run_collectors()
        return {"ok": True, "metrics": metrics.registry().to_jsonable()}
    if op == "profile":
        # this process's own sampling profile (obs/profiling.py) —
        # the framed twin of GET /profile
        from namazu_tpu.obs import profiling

        if req.get("format") == "collapsed":
            return {"ok": True, "text": profiling.render_collapsed()}
        return {"ok": True, "profile": profiling.payload()}
    if op == "spans":
        # this process's request-scoped span rows (obs/spans.py), paged
        # by the cursor the previous reply returned as ``next``
        from namazu_tpu.obs import spans

        try:
            since = int(req.get("since") or 0)
            limit = int(req.get("limit") or 1024)
        except (TypeError, ValueError):
            return {"ok": False, "error": "since/limit must be integers"}
        return dict(spans.span_ring().since(since, limit), ok=True)
    return None


class TelemetryServer:
    """The campaign supervisor's collector: the shared framed-JSON
    serve loop (endpoint/framed.py) over AF_UNIX answering
    :func:`handle_obs_op` (plus ``ping``) — same-host ``run`` children
    and ``tools top --url uds://...`` speak to it without the
    supervisor growing an HTTP stack or a TCP port."""

    def __init__(self, path: str,
                 agg: Optional[FleetAggregator] = None) -> None:
        self.path = path
        self._agg = agg
        self._server = None

    def aggregator(self) -> FleetAggregator:
        return self._agg if self._agg is not None else aggregator()

    def _handle(self, req: dict) -> dict:
        resp = handle_obs_op(req, self.aggregator())
        if resp is None:
            resp = ({"ok": True, "server": "telemetry"}
                    if req.get("op") == "ping" else
                    {"ok": False,
                     "error": f"unknown op {req.get('op')!r}"})
        return resp

    def start(self) -> None:
        if self._server is not None:
            return
        # lazy: obs modules must stay importable without the endpoint
        # package resolving at module load
        from namazu_tpu.endpoint.framed import FramedServer

        srv = FramedServer(self._handle, name="telemetry-collector")
        # bind_unix reclaims only a LISTENER-LESS stale socket inode
        # (same rule as the uds event endpoint): a live listener means
        # another collector owns this path, and raises
        srv.bind_unix(self.path, backlog=32)
        srv.start()
        self._server = srv
        log.info("fleet telemetry collector on %s", self.path)

    def shutdown(self) -> None:
        srv, self._server = self._server, None
        if srv is not None:
            srv.shutdown()


# -- process-global wiring -------------------------------------------------

_enabled = True
_aggregator: Optional[FleetAggregator] = None
_self_relay: Optional[TelemetryRelay] = None
# reentrant: ensure_self_relay resolves aggregator() (which may lazily
# create under this same lock) while wiring the relay
_wiring_lock = threading.RLock()


def configure(on: bool) -> None:
    """Process-global switch (config key ``telemetry_enabled``):
    disabled, :meth:`TelemetryRelay.start` spawns no thread and
    :func:`ensure_self_relay` is a no-op — the ``obs_enabled`` cost
    contract."""
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def aggregator() -> FleetAggregator:
    """The process's fleet aggregator (lazily created — a process that
    never serves nor pushes telemetry allocates nothing)."""
    global _aggregator
    a = _aggregator
    if a is None:
        with _wiring_lock:
            a = _aggregator
            if a is None:
                a = _aggregator = FleetAggregator()
    return a


def set_aggregator(a: Optional[FleetAggregator]
                   ) -> Optional[FleetAggregator]:
    """Swap the process-global aggregator (tests); returns the old."""
    global _aggregator
    old, _aggregator = _aggregator, a
    return old


def slo_summary() -> Optional[Dict[str, Any]]:
    """The analytics fold: None unless an aggregator exists AND its
    objectives were declared explicitly in config."""
    a = _aggregator
    return None if a is None else a.slo_summary()


def configure_from_config(config) -> None:
    """Apply the fleet-telemetry config keys (called with the
    experiment config by ``obs.configure_from_config``). Only explicit
    keys touch process-global state — same multi-orchestrator rule as
    ``obs_enabled``."""
    if config.is_set("telemetry_enabled"):
        configure(bool(config.get("telemetry_enabled")))
    touched = (config.is_set("fleet_stale_after_s")
               or config.is_set("fleet_evict_after_s")
               or config.is_set("slo"))
    if not touched:
        return
    agg = aggregator()
    if config.is_set("fleet_stale_after_s"):
        agg.stale_after_s = max(0.0, float(
            config.get("fleet_stale_after_s") or 0))
    if config.is_set("fleet_evict_after_s"):
        agg.evict_after_s = max(0.0, float(
            config.get("fleet_evict_after_s") or 0))
    if config.is_set("slo"):
        agg.set_slos(slo.specs_from_config(config.get("slo") or []),
                     explicit=True)


def ensure_self_relay(job: str, push_url: str = "",
                      interval_s: float = 2.0,
                      instance: Optional[str] = None
                      ) -> Optional[TelemetryRelay]:
    """The ONE self-relay per process: walks the process registry and
    merges into the local aggregator (and upstream when ``push_url``
    is set). Idempotent — a second orchestrator in the same process
    reuses the first relay (two encoders over one shared registry
    would each report full state and double the fleet's view). A
    late-arriving ``push_url`` upgrades the existing relay."""
    global _self_relay
    if not _enabled:
        return None
    with _wiring_lock:
        relay = _self_relay
        if relay is None:
            push = pusher_for(push_url) if push_url else None
            relay = _self_relay = TelemetryRelay(
                job=job, instance=instance,
                push=push, local=aggregator(),
                forward_source=aggregator() if push else None,
                interval_s=interval_s, target_desc=push_url)
            relay.start()
            # final flush at interpreter exit: a 2-second `run` child
            # must deliver its last interval of samples
            atexit.register(relay.shutdown)
        elif push_url and relay._push is None:
            relay.set_upstream(pusher_for(push_url),
                               forward_source=aggregator(),
                               target_desc=push_url)
        return relay


def self_relay() -> Optional[TelemetryRelay]:
    return _self_relay


def reset() -> None:
    """Fresh wiring (tests): stops the self-relay, drops the
    aggregator, and forgets registered collectors (an abandoned
    component's bound-method collector would otherwise keep its whole
    object graph alive across resets and write stale gauges into the
    next test's registry)."""
    global _aggregator, _self_relay, _enabled
    with _wiring_lock:
        relay, _self_relay = _self_relay, None
        _aggregator = None
        _enabled = True
    with _collectors_lock:
        del _collectors[:]
    if relay is not None:
        relay._stop.set()
        t = relay._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=2.0)
