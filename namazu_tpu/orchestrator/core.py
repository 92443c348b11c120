"""The orchestrator runtime.

Capability parity with /root/reference/nmz/orchestrator/orchestrator.go:
three worker threads around queues —

* **event thread**: pulls merged inbound events from the EndpointHub and
  feeds the active policy (the configured one while orchestration is
  enabled, an always-instantiated passthrough ``dumb`` policy while
  disabled — parity orchestrator.go:43-45, 84-94);
* **action thread**: drains policy actions, stamps ``triggered_time``,
  executes orchestrator-side actions in-process, forwards the rest to the
  hub for dispatch, and appends everything to the trace when
  ``collect_trace`` (parity orchestrator.go:96-179);
* **control thread**: toggles enable/disable from REST ``/control``
  (parity orchestrator.go:181-199; config key ``skip_init_orchestration``).

``shutdown()`` stops the loops and returns the accumulated
:class:`SingleTrace` (parity orchestrator.go:207-220).
"""

from __future__ import annotations

import os
import queue
import signal as _signal
import threading
import time
import uuid as _uuid
from typing import Optional

from namazu_tpu import chaos, obs, tenancy
from namazu_tpu.endpoint.hub import EndpointHub
from namazu_tpu.endpoint.local import LocalEndpoint
from namazu_tpu.policy.base import POLICY_DONE, ExplorePolicy, create_policy
from namazu_tpu.signal.action import Action
from namazu_tpu.signal.control import ControlOp
from namazu_tpu.utils.config import Config
from namazu_tpu.utils.log import get_logger
from namazu_tpu.utils.trace import SingleTrace

log = get_logger("orchestrator")

_STOP = object()
_FWD_DONE = object()


class FlushMarker:
    """Rides the merged action queue behind a namespace's final
    actions (tenancy plane): the action loop fires it at the END of the
    batch that carried it — i.e. after those actions were dispatched
    AND their releases journaled — so a lease release can wait for its
    namespace's drain deterministically."""

    __slots__ = ("done",)

    def __init__(self) -> None:
        self.done = threading.Event()


class Orchestrator:
    def __init__(
        self,
        config: Config,
        policy: ExplorePolicy,
        collect_trace: bool = False,
        hub: Optional[EndpointHub] = None,
    ):
        self.config = config
        obs.configure_from_config(config)
        # the correlation key for this run's logs, metrics and flight-
        # recorder trace (GET /traces/<run_id>); `run` passes the run
        # dir's name so on-disk artifacts join on the same id
        self.run_id = str(config.get("run_id") or "") \
            or _uuid.uuid4().hex[:12]
        self.policy = policy
        self.collect_trace = collect_trace
        self.trace = SingleTrace()
        # the passthrough policy used while orchestration is disabled
        self.dumb = create_policy("dumb")
        self.enabled = not bool(config.get("skip_init_orchestration"))
        self.hub = hub or self._default_hub(config)
        self.local_endpoint: Optional[LocalEndpoint] = None
        ep = self.hub.endpoint("local")
        if isinstance(ep, LocalEndpoint):
            self.local_endpoint = ep
        self._threads: dict[str, threading.Thread] = {}
        self._merged_actions: "queue.Queue[object]" = queue.Queue()
        self._n_policies = 2  # policy + dumb; the action loop exits after
        # receiving this many _FWD_DONE markers
        self._started = False
        self._shut_down = False
        # liveness watchdog (doc/robustness.md): entities silent past
        # the timeout are declared dead and their parked events force-
        # released, so one hung/killed testee process cannot park the
        # run behind delays nobody will ever observe. 0 = disabled.
        self.liveness_timeout_s = float(
            config.get("entity_liveness_timeout_s", 0) or 0)
        # crash-recovery event journal (doc/robustness.md "Chaos
        # plane"): a write-ahead log of inbound events + dispatched
        # releases in the run's dir, so a killed-and-restarted
        # orchestrator resumes its parked events instead of losing the
        # run. Off unless the config names a dir ("" = the
        # pre-journal behavior, zero hot-path cost).
        journal_dir = str(config.get("event_journal_dir", "") or "")
        self.journal = None
        if journal_dir:
            from namazu_tpu.chaos.journal import EventJournal

            self.journal = EventJournal(journal_dir)
        self._watchdog_stop = threading.Event()
        # entities currently declared dead; an entity leaves the set
        # when it is seen again (metric + warning fire per transition,
        # not per sweep)
        self._stalled: set = set()
        # zero-RTT dispatch (doc/performance.md): a policy that
        # publishes its delay table (policy/edge_table.py) plugs its
        # publisher into the hub so endpoints can serve/version it;
        # suspended while orchestration is disabled — edges must not
        # keep deciding with a table the passthrough policy would not
        # have applied
        pub = getattr(policy, "table_publisher", None)
        self.hub.table_publisher = pub
        if pub is not None and not self.enabled:
            pub.suspend()
        # virtual clock (doc/performance.md "Virtual clock"): when the
        # process runs under a VirtualTimeSource (`run --virtual-clock`
        # installed it before this constructor), the orchestrator's
        # queues become the coordinator's busy probes — an event or
        # action anywhere in flight between intake and dispatch vetoes
        # fast-forward, so a jump can never overtake work that is about
        # to park a new deadline. Wall time: zero cost, nothing
        # registered.
        from namazu_tpu.utils import timesource

        self.time_source = timesource.get()
        if self.time_source.is_virtual:
            self.time_source.add_busy_probe(
                lambda: not self.hub.event_queue.empty())
            self.time_source.add_busy_probe(
                lambda: not self._merged_actions.empty())
            self.time_source.add_busy_probe(
                lambda: not self.policy.action_out.empty())
            self.time_source.add_busy_probe(
                lambda: not self.dumb.action_out.empty())

    @staticmethod
    def _default_hub(config: Config) -> EndpointHub:
        """Local endpoint always; REST / guest-agent endpoints when their
        ports are enabled (parity: endpoint.StartAll, endpoint.go:63-97)."""
        hub = EndpointHub()
        hub.add_endpoint(LocalEndpoint())
        rest_port = int(config.get("rest_port", -1))
        if rest_port >= 0:
            from namazu_tpu.endpoint.rest import RestEndpoint

            hub.add_endpoint(RestEndpoint(
                port=rest_port,
                # the long-poll window; configurable pre-start so a
                # successor orchestrator's first parked poll cannot
                # ride a 30s default before a test/operator shrinks it
                poll_timeout=float(
                    config.get("rest_poll_timeout", 30.0) or 30.0),
                # bounded ingress (doc/robustness.md): 0 = unbounded
                ingress_cap=int(config.get("rest_ingress_cap", 0) or 0),
                # bounded connection-handler pool (doc/tenancy.md):
                # beyond this many concurrent connections, new ones
                # queue for a handler instead of growing a thread each
                max_threads=int(
                    config.get("rest_max_threads", 64) or 64)))
        uds_path = str(config.get("uds_path", "") or "")
        if uds_path:
            from namazu_tpu.endpoint.uds import UdsEndpoint

            # same hub, same bound: the ingress cap protects the
            # orchestrator's event queue, whichever wire feeds it
            hub.add_endpoint(UdsEndpoint(
                uds_path,
                ingress_cap=int(config.get("rest_ingress_cap", 0) or 0)))
        agent_port = int(config.get("agent_port", -1))
        if agent_port >= 0:
            try:
                from namazu_tpu.endpoint.agent import AgentEndpoint
            except ImportError as e:
                raise NotImplementedError(
                    "guest-agent endpoint not available in this build"
                ) from e
            hub.add_endpoint(AgentEndpoint(port=agent_port))
        return hub

    def _add_thread(self, target, name: str) -> None:
        t = threading.Thread(target=target, name=f"orc-{name}", daemon=True)
        t.start()
        self._threads[name] = t

    # -- lifecycle -------------------------------------------------------

    def start(self) -> None:
        if self._started:
            return
        self._started = True
        obs.begin_run(self.run_id)
        # recover BEFORE the endpoints open: the dedupe ring must know
        # the journaled uuids before an inspector's reconnect-and-
        # replay can reach the wire, or the replay doubles every
        # recovered event
        self._recover_journal()
        self.hub.start()
        self.policy.start()
        self.dumb.start()
        self._add_thread(self._event_loop, "events")
        self._add_thread(self._action_loop, "actions")
        self._add_thread(self._control_loop, "control")
        self._add_thread(self._forward_loop_factory(self.policy), "fwd-policy")
        self._add_thread(self._forward_loop_factory(self.dumb), "fwd-dumb")
        if self.liveness_timeout_s > 0:
            self._add_thread(self._watchdog_loop, "watchdog")
        # fleet telemetry (doc/observability.md "Fleet telemetry"): this
        # process is a producer — its registry rides the process relay
        # into the local aggregator (serving GET /fleet here) and, when
        # an upstream collector is named (config telemetry_url, or the
        # NMZ_TELEMETRY_URL a campaign supervisor exports to its run
        # children), pushed + forwarded upstream too. ensure_self_relay
        # is idempotent: a CLI layer that already named this process's
        # job (e.g. `run`) wins.
        push_url = str(self.config.get("telemetry_url", "") or "") \
            or os.environ.get("NMZ_TELEMETRY_URL", "")
        obs.federation.ensure_self_relay(
            "orchestrator", push_url=push_url,
            interval_s=float(
                self.config.get("telemetry_interval_s", 2.0) or 2.0))
        # continuous profiling (doc/observability.md "Profiling"):
        # idempotent like the relay — a CLI layer that already started
        # the sampler under its own job name wins
        obs.profiling.ensure_profiler("orchestrator", cfg=self.config)
        log.debug("orchestrator started (enabled=%s)", self.enabled)

    def _recover_journal(self) -> None:
        """Reload parked events a killed predecessor journaled but
        never dispatched (doc/robustness.md): seed the REST dedupe ring
        with their uuids (an inspector-side replay must ack idempotent,
        not double), then re-post them through the hub — which restores
        the entity routes AND the liveness bookkeeping, so the re-armed
        watchdog force-releases events whose entity never speaks
        again."""
        if self.journal is None:
            return
        recovered = self.journal.unreleased()
        if not recovered:
            return
        rest = self.hub.endpoint("rest")
        if rest is not None and hasattr(rest, "note_event_uuid"):
            for event, _ in recovered:
                rest.note_event_uuid(event.uuid)
        for event, endpoint_name in recovered:
            self.hub.post_event(event, endpoint_name or "local")
        obs.journal_recovered(len(recovered))
        log.warning(
            "recovered %d parked event(s) from the event journal; "
            "resuming the run (liveness watchdog %s)", len(recovered),
            f"re-armed at {self.liveness_timeout_s:.1f}s"
            if self.liveness_timeout_s > 0 else "disabled")

    def shutdown(self) -> SingleTrace:
        """Stop all loops, flushing in dependency order so no action is
        lost: event intake first, then policies (which release their still-
        delayed events immediately and emit POLICY_DONE), then the forward
        and action loops drain everything before exiting."""
        if self._shut_down:
            return self.trace
        self._shut_down = True
        if not self._started:
            return self.trace
        # 1. stop event intake (events already inbound are forwarded first)
        self.hub.event_queue.put(_STOP)  # type: ignore[arg-type]
        self._threads["events"].join(timeout=10)
        # 2. flush the policies; their dequeue workers emit remaining
        #    actions and then POLICY_DONE
        # (`search`: the run's join on its end-of-run search request,
        # under the id the policy stamped on it — the same id the
        # sidecar's span tree of that request carries)
        with obs.run_phase("search") as attrs:
            self.policy.shutdown()
            if self.policy.sidecar_request_id:
                attrs["request"] = self.policy.sidecar_request_id
        self.dumb.shutdown()
        # 3. forward loops exit on POLICY_DONE after draining; the action
        #    loop exits after both _FWD_DONE markers
        self._threads["fwd-policy"].join(timeout=10)
        self._threads["fwd-dumb"].join(timeout=10)
        self._threads["actions"].join(timeout=10)
        # 4. watchdog, control loop + transports
        self._watchdog_stop.set()
        if "watchdog" in self._threads:
            self._threads["watchdog"].join(timeout=10)
        self.hub.control_queue.put(_STOP)  # type: ignore[arg-type]
        self._threads["control"].join(timeout=10)
        with obs.run_phase("endpoints"):
            self.hub.shutdown()
        if self.journal is not None:
            # every parked event was flushed above and its release
            # journaled: the run completed, so remove the file — a
            # later orchestrator over the same dir must not re-parse
            # (or endlessly grow) a fully-released history. A crash
            # ANYWHERE before this line leaves the journal for
            # recovery, which is the point.
            self.journal.remove()
        log.debug("orchestrator shut down; trace length %d", len(self.trace))
        # close the flight-recorder run LAST: the drains above still
        # stamp released/dispatched records against it
        obs.end_run(self.run_id)
        return self.trace

    def abandon(self) -> None:
        """Die WITHOUT the graceful drain — the in-process stand-in for
        ``kill -9`` the chaos harness's crash scenarios use: endpoints
        are torn down so the ports free up and a successor can bind
        them, but policies are NOT flushed, parked events are NOT
        released, and the journal gets no further records. Everything a
        real SIGKILL would leak (daemon worker threads parked on their
        queues) leaks here too; only a journal-recovering successor can
        resume the run."""
        self._shut_down = True
        self._watchdog_stop.set()
        # sever live connections first, like process death would: an
        # inspector's keep-alive long-poll must error and reconnect (to
        # the successor), not keep talking to zombie handler threads
        for name in ("rest",):
            ep = self.hub.endpoint(name)
            if ep is not None and hasattr(ep, "sever"):
                ep.sever()
        self.hub.shutdown()
        # a real SIGKILL takes the policy's delay queue with it: close
        # + drain WITHOUT releasing, or the still-parked items would be
        # dispatched by the leaked (daemon) release worker when their
        # delays expire — a dead orchestrator's policy emitting actions
        # minutes later, stamping records into whatever flight-recorder
        # run is current by then. The items die here; only the journal-
        # recovering successor resurrects them.
        for pol in (self.policy, self.dumb):
            q = getattr(pol, "_queue", None)
            if q is not None:
                try:
                    q.close()
                    q.drain_remaining()
                except Exception:  # pragma: no cover - best effort
                    pass
        if self.journal is not None:
            self.journal.close()
        obs.end_run(self.run_id)
        log.warning("orchestrator abandoned (simulated crash); parked "
                    "events remain journaled but undispatched")

    # -- loops -----------------------------------------------------------

    #: greedy-drain cap for the event and action loops: bounds how much
    #: one batch can delay the loop's shutdown sentinel check, and the
    #: largest batch a policy's vectorized decision sees at once
    BATCH_MAX = 256

    def _event_loop(self) -> None:
        while True:
            ev = self.hub.event_queue.get()
            if ev is _STOP:
                return
            # greedy drain: everything already inbound rides ONE policy
            # call (the batch POST route enqueues whole batches, so
            # under load this recovers them; when idle the batch is 1
            # and behavior is exactly the sequential path)
            batch = [ev]
            stop = False
            while len(batch) < self.BATCH_MAX:
                try:
                    nxt = self.hub.event_queue.get_nowait()
                except queue.Empty:
                    break
                if nxt is _STOP:
                    stop = True
                    break
                batch.append(nxt)
            # edge-decided events (backhaul reconciliation,
            # doc/performance.md "Zero-RTT dispatch") never reach the
            # policy OR the journal: the edge already decided and
            # dispatched them — they only need their trace records and
            # synthetic actions. Partitioned BEFORE the journal append,
            # or recovery would re-dispatch an already-answered event.
            edge_batch = [ev for ev in batch
                          if getattr(ev, "_edge_decision", None)]
            if edge_batch:
                batch = [ev for ev in batch
                         if getattr(ev, "_edge_decision", None) is None]
                self._ingest_edge_batch(edge_batch)
                if not batch:
                    if stop:
                        return
                    continue
            self._dispatch_central_batch(batch)
            if stop:
                return

    def _dispatch_central_batch(self, batch: list) -> None:
        """Journal + feed one drained central batch to its policy. The
        single-run body; TenantOrchestrator overrides to partition the
        batch by run namespace first (doc/tenancy.md)."""
        # chaos seam (profiling plane): a seeded slowdown parks the
        # decision stage in a distinctively-named frame the sampling
        # profiler must localize — the CI seeded-slowdown smoke
        chaos.stage_slowdown("orchestrator.stage.slow")
        self._journal_and_queue(batch, self.journal,
                                self.policy if self.enabled else self.dumb)

    def _routes_for_ns(self, ns: str) -> dict:
        """One namespace's entity -> endpoint routes (bare entity
        keys): what its journal persists — a journal is a single-tenant
        artifact, so recovery resolves entities without knowing about
        route-key prefixes (and never sees other tenants' routes)."""
        out = {}
        for key, endpoint_name in self.hub.routes().items():
            key_ns, entity = tenancy.split_route_key(key)
            if key_ns == ns:
                out[entity] = endpoint_name
        return out

    def _journal_and_queue(self, batch: list, journal,
                           target: ExplorePolicy,
                           routes: Optional[dict] = None) -> None:
        if journal is not None:
            # write-ahead: the batch is durable BEFORE the policy
            # sees it, so a crash from here on can lose nothing
            try:
                journal.append_events(
                    batch, routes if routes is not None
                    else self._routes_for_ns(""))
                obs.journal_events(len(batch))
            except OSError:
                log.exception("event journal append failed; "
                              "continuing without durability")
            # chaos seam: die like kill -9 WOULD — after the journal
            # write, before dispatch (the recovery window the crash
            # scenarios exercise)
            if chaos.decide("orchestrator.crash") is not None:
                log.error("chaos: orchestrator.crash fired; "
                          "SIGKILLing this process")
                os.kill(os.getpid(), _signal.SIGKILL)
        for ev in batch:
            obs.mark(ev, "enqueued")
            obs.record_enqueued(ev, target.name)
        try:
            if len(batch) == 1:
                target.queue_event(batch[0])
                rejected = ()
            else:
                # queue_events isolates per-event failures itself
                # and reports them (policy/base.py contract);
                # reaching this except means a batch-level failure
                # (e.g. queue closed at shutdown)
                rejected = target.queue_events(batch) or ()
        except Exception:
            log.exception("policy %s rejected a batch of %d events "
                          "(first: %r)", target.name, len(batch),
                          batch[0])
        else:
            # queue_event(s) returning means the policy chose the
            # batch's delays/priorities — the decision point.
            # Rejected events get no marks, exactly like a scalar
            # rejection: batched and per-event telemetry stay
            # identical
            rejected_ids = {id(ev) for ev in rejected}
            for ev in batch:
                if id(ev) in rejected_ids:
                    continue
                obs.mark(ev, "decided")
                obs.record_decided(ev, target.name)
                obs.policy_decision(target.name, ev.entity_id,
                                    obs.latency(ev, "intercepted"))

    def _ingest_edge_batch(self, events: list) -> None:
        """Reconcile backhauled edge decisions: one complete flight-
        recorder record per event with the EDGE's own lifecycle stamps
        (same host, shared CLOCK_MONOTONIC) and the decision detail
        (``decision_source="edge"``, ``table_version``, delay), plus
        the synthesized accepting action appended straight to the
        collected trace — the edge already delivered the real action,
        so nothing is forwarded, journaled, or queued through the
        policy/action loops."""
        policy_name = (self.policy if self.enabled else self.dumb).name
        now_mono = time.monotonic()
        lags = []
        parkings = []
        for ev in events:
            d = ev._edge_decision
            action = ev.default_action()
            action.mark_triggered(now=d.get("triggered_wall"))
            obs.record_edge(ev, getattr(ev, "_edge_endpoint", ""),
                            policy_name, action, d)
            # backhaul reconciliation lag: the edge's dispatch stamp ->
            # this reconcile, both CLOCK_MONOTONIC on one host — the
            # fleet-level answer to "is the 151k/s edge plane keeping
            # its async-backhaul promise" (doc/observability.md)
            stamp = d.get("t_dispatched")
            if isinstance(stamp, (int, float)):
                obs.edge_backhaul_lag(ev.entity_id, now_mono - stamp)
                lags.append(now_mono - stamp)
                t0 = d.get("t_intercepted")
                if isinstance(t0, (int, float)):
                    parkings.append(stamp - t0)
            self._trace_append(action)
        # causality-plane stage attribution (obs/causality.py): the
        # edge path's two segments, observed batch-wise (one family
        # resolution per burst — this loop runs at zero-RTT rates)
        obs.event_stage_many("backhaul", lags)
        obs.event_stage_many("edge_parking", parkings)
        obs.action_dispatched("edge", None, n=len(events))

    def _forward_loop_factory(self, policy: ExplorePolicy):
        def loop() -> None:
            while True:
                action = policy.action_out.get()
                if action is POLICY_DONE:
                    self._merged_actions.put(_FWD_DONE)
                    return
                self._merged_actions.put(action)

        return loop

    def _action_loop(self) -> None:
        done = 0
        while True:
            raw = [self._merged_actions.get()]
            while len(raw) < self.BATCH_MAX:
                try:
                    raw.append(self._merged_actions.get_nowait())
                except queue.Empty:
                    break
            # an item is one action, a released burst (list — the
            # action_out contract, policy/base.py), or a sentinel
            batch: list = []
            for item in raw:
                if isinstance(item, list):
                    batch.extend(item)
                else:
                    batch.append(item)
            # forwardable actions accumulate and fan through the hub in
            # one send_actions call (one route-lock + one queue-lock per
            # endpoint/entity); orchestrator-side actions act as flush
            # barriers so in-process execution keeps its place in the
            # release order
            forward: list = []
            released: list = []  # (uuid, namespace) pairs
            markers: list = []
            for item in batch:
                if item is _FWD_DONE:
                    done += 1
                    continue
                if isinstance(item, FlushMarker):
                    # fired at the END of this batch (after dispatch +
                    # release journaling), where its namespace's
                    # preceding actions are fully accounted
                    markers.append(item)
                    continue
                action: Action = item  # type: ignore[assignment]
                released.append((action.event_uuid or action.uuid,
                                 getattr(action, "_ns", "")))
                action.mark_triggered()
                obs.mark(action, "dispatched")
                kind = ("orchestrator" if action.orchestrator_side_only
                        else "forwarded")
                obs.record_dispatched(action, kind)
                obs.action_dispatched(kind,
                                      obs.latency(action, "intercepted"))
                self._trace_append(action)
                if action.orchestrator_side_only:
                    if forward:
                        self.hub.send_actions(forward)
                        forward = []
                    try:
                        action.execute_on_orchestrator()
                    except Exception:
                        log.exception(
                            "orchestrator-side action failed: %r", action)
                else:
                    forward.append(action)
            if forward:
                self.hub.send_actions(forward)
            if released:
                # release records land AFTER dispatch: the crash window
                # between the two is at-least-once, which the endpoint
                # dedupe + waiter-keyed dispatch absorb; the reverse
                # order would lose events (chaos/journal.py)
                self._journal_releases(released)
            for marker in markers:
                marker.done.set()
            if done >= self._n_policies:
                return

    def _trace_append(self, action: Action) -> None:
        """Collected-trace hook; TenantOrchestrator routes namespaced
        actions to their namespace's own trace."""
        if self.collect_trace:
            self.trace.append(action)

    def _journal_releases(self, released: list) -> None:
        """Append ``(uuid, namespace)`` release records; the base class
        owns only the default namespace's journal."""
        if self.journal is None:
            return
        uuids = [u for u, ns in released if not ns]
        if not uuids:
            return
        try:
            self.journal.append_releases(uuids)
        except OSError:
            log.exception("event journal release append failed")

    def _watchdog_loop(self) -> None:
        """Liveness sweep: declare entities silent past the timeout dead
        and force-release their parked events from both policies' delay
        queues, surfacing each transition in ``nmz_entity_stalled_total``
        and one WARNING — instead of the run silently waiting out delays
        for a testee that no longer exists."""
        interval = max(min(self.liveness_timeout_s / 4.0, 1.0), 0.05)
        while not self._watchdog_stop.wait(interval):
            self.sweep_stalled_entities()

    def sweep_stalled_entities(self) -> int:
        """One watchdog pass (public for tests and embedded callers);
        returns how many parked events were force-released."""
        stalled = self.hub.stalled_entities(self.liveness_timeout_s)
        released = 0
        for key, silent_for in stalled.items():
            ns, entity = tenancy.split_route_key(key)
            n = 0
            for pol in self._policies_for(ns):
                try:
                    n += pol.force_release_entity(entity)
                except Exception:
                    log.exception("force-release for entity %s failed "
                                  "in policy %s", entity, pol.name)
            released += n
            if key not in self._stalled:
                self._stalled.add(key)
                obs.entity_stalled(entity)
                log.warning(
                    "entity %s declared dead (silent %.1fs > %.1fs); "
                    "force-released %d parked event(s)",
                    entity, silent_for, self.liveness_timeout_s, n)
        # entities that spoke again re-arm their stall transition
        self._stalled &= set(stalled)
        return released

    def _policies_for(self, ns: str):
        """The policies that may hold parked events of one namespace;
        TenantOrchestrator overrides for non-default namespaces."""
        return (self.policy, self.dumb)

    def _control_loop(self) -> None:
        while True:
            ctrl = self.hub.control_queue.get()
            if ctrl is _STOP:
                return
            ns = tenancy.ns_of(ctrl)
            if ns:
                # a namespace-scoped op (X-Nmz-Run / framed `run`)
                # touches exactly that tenant's serving state — the
                # process-default flag and publisher stay untouched
                self._control_namespace(ns, ctrl.op)
                continue
            pub = self.hub.table_publisher
            if ctrl.op is ControlOp.ENABLE_ORCHESTRATION:
                self.enabled = True
                if pub is not None:
                    pub.resume()
            elif ctrl.op is ControlOp.DISABLE_ORCHESTRATION:
                self.enabled = False
                if pub is not None:
                    # edges must stop deciding with the table: central
                    # decisions now come from the passthrough policy
                    pub.suspend()
            log.info("orchestration enabled=%s", self.enabled)

    def _control_namespace(self, ns: str, op: ControlOp) -> None:
        """Apply one namespace-scoped control op; the base orchestrator
        hosts no namespaces (TenantOrchestrator overrides)."""
        log.warning("control op %s for run %r ignored: this "
                    "orchestrator hosts no run namespaces", op.value, ns)


class AutopilotOrchestrator(Orchestrator):
    """Embedded orchestrator for `local://` inspectors.

    Parity: NewAutopilotOrchestrator
    (/root/reference/nmz/util/orchestrator/orchestratorutil.go:26-38):
    builds policy from config, local endpoint only, no trace collection.
    """

    def __init__(self, config: Config):
        policy = create_policy(config.get("explore_policy"))
        policy.load_config(config)
        hub = EndpointHub()
        hub.add_endpoint(LocalEndpoint())
        super().__init__(config, policy, collect_trace=False, hub=hub)
