"""tpu_search policy: replay the best schedule found by the TPU search.

The BASELINE.json north-star component: behind the same ``register_policy``
plugin boundary as every other policy, but its delays are not random — they
come from a per-hint-bucket delay table evolved by the island GA
(namazu_tpu/models/search.py) against the experiment's recorded history.

Division of labor (latency budget, SURVEY.md section 7):

* **off the critical path**: at policy start (and between runs), a
  background thread featurizes stored traces, adds them to the novelty/
  failure archives, runs GA generations on the device mesh, and installs
  the best ``delays[H]`` / ``faults[H]`` tables atomically;
* **on the critical path**: each event costs one fnv64a hash + one table
  lookup, then rides the same ScheduledQueue as every other policy. Until
  the first search finishes, delays fall back to the replayable policy's
  hash(seed, hint) — so the policy is never worse than `replayable`.

Fault decisions are deterministic per (seed, hint) so a found schedule
replays exactly.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

from namazu_tpu import obs
from namazu_tpu.models import SEARCH_DEFAULTS, refuse_search_backend
from namazu_tpu.policy.base import QueueBackedPolicy, register_policy
from namazu_tpu.policy.edge_table import TablePublisher
from namazu_tpu.policy.replayable import (
    fnv64a,
    fnv64a_many,
    hint_delay,
    hint_delays,
)
from namazu_tpu.signal.action import ProcSetSchedAction
from namazu_tpu.signal.event import Event, ProcSetEvent
from namazu_tpu.policy.proc_subpolicies import create_proc_subpolicy
from namazu_tpu.utils.config import parse_duration
from namazu_tpu.utils.log import get_logger

log = get_logger("policy.tpu")


_MCTS_GONE = "the MCTS backend was removed; the island GA searches"

#: ``tpu_search`` keys that went with the code they selected, and what a
#: config that still sets one gets instead (``load_config`` says so once
#: per key: a removed key must not fail silently)
REMOVED_KEYS = {
    "fused": "the search always runs the fused generation loop "
             "(fused_chunk = 1 is one dispatch per generation)",
    "migrate_every": "the island ring migrates every generation",
    "dcn_migrate_every": "there is no cross-host ring; the island ring "
                         "migrates every generation",
    "dcn_hosts": "the search runs on a flat mesh over this process's "
                 "chips (devices = N takes the first N)",
    "search_backend": "there is one search, the island GA (any other "
                      "value is refused)",
    "mcts_simulations": _MCTS_GONE,
    "mcts_tree_depth": _MCTS_GONE,
    "mcts_levels": _MCTS_GONE,
    "mcts_rollouts": _MCTS_GONE,
}


def _device_str(device) -> str:
    """``parallel.mesh.device_summary()`` (or a sidecar reply's
    ``device`` field) as the log's "which device searched" suffix."""
    if not device:
        return "an unreported device"
    return f"{device['platform']}/{device['kind']} x{device['count']}"


class TPUSearchPolicy(QueueBackedPolicy):
    NAME = "tpu_search"

    def __init__(self) -> None:
        super().__init__()
        # the search knobs start from the one table of their defaults
        # (namazu_tpu/models/__init__.py, where each is described);
        # _search_params() states them back under the table's names
        d = SEARCH_DEFAULTS
        self.seed = d["seed"]
        self.max_interval = d["max_interval"]
        self.generations = 64
        self.population = d["population"]
        self.H = d["H"]
        self.L = d["L"]
        self.K = d["K"]
        self.migrate_k = d["migrate_k"]
        # generations per dispatch of the island step (doc/performance.md
        # "Fused search loop"): a dispatch-shape choice, results do not
        # depend on it (pinned by test)
        self.fused_chunk = d["fused_chunk"]
        self.n_devices: Optional[int] = d["devices"]
        self.checkpoint_path = ""
        self.search_on_start = True
        self.search_join_timeout = 120.0  # shutdown waits this long
        # persistent search sidecar address ("host:port"; "" = search
        # in-process). The sidecar (namazu_tpu/sidecar.py) holds the
        # compiled search and device state across `run` processes, so a
        # per-run search request costs one ingest + warm generations
        # instead of rebuild + jit warm-up.
        self.sidecar = ""
        # evolve every Nth run (1 = every run). The installed schedule
        # always comes from the checkpoint (cheap np.load), but the
        # evolve+ingest+save cycle costs seconds of wall-clock per `run`
        # process; on experiments whose runs last ~2 s that overhead
        # halves repros/hour. N>1 amortizes it: N-1 install-only runs,
        # then one evolution over the batch of new outcomes.
        self.search_every = 1
        self.max_fault = d["max_fault"]
        # release modes (BASELINE config 3): "delay" replays the table as
        # literal per-hint delays; "reorder" treats it as per-hint
        # *priorities* — events buffered for reorder_window seconds are
        # released in priority order, a true permutation even when delays
        # could not invert the arrivals
        self.release_mode = d["release_mode"]
        self.reorder_window = d["reorder_window"]
        self.reorder_gap = d["reorder_gap"]
        # (prio, seq, t_arrive, event) under _pending_lock
        self._pending: list = []
        self._pending_lock = threading.Lock()
        self._pending_seq = 0
        self._reorder_thread: Optional[threading.Thread] = None
        self._stop_reorder = threading.Event()
        # window clock anchor = monotonic arrival of the FIRST queued
        # event; the scorer anchors windows at the trace's first arrival
        # (ops/schedule.py order_release_times), so both planes cut
        # window boundaries at the same offsets
        self._anchor: Optional[float] = None
        self._anchor_set = threading.Event()
        # injectable clock: every reorder-window decision (arrival
        # stamping, window-boundary ticks, closed-window drains) reads
        # time through this hook, so the realized-vs-scored order
        # invariant is testable with a scripted clock and zero real
        # sleeps instead of margin-widened wall-clock waits
        self._now = time.monotonic
        self.surrogate_topk = d["surrogate_topk"]
        # cross-batch failure-signature pool directory ("" = off); see
        # models/failure_pool.py. Relative paths anchor to the PARENT of
        # the storage dir (sibling experiments share one pool; anchoring
        # inside the storage would make every batch an island again).
        self.failure_pool = ""
        # knowledge-service address "host:port" ("" = off): the global
        # failure-knowledge plane (doc/knowledge.md). A cold run pulls
        # the fleet's warm-start (pooled signatures + the scenario's
        # best delay table) before its own history exists; every ingest
        # streams failures up; the shared surrogate ranks candidates
        # during the local model's cold-start window. Outages degrade to
        # local-only search — never to a failed run.
        self.knowledge = ""
        # scenario fingerprint override; "" = derived from the config's
        # run/validate scripts + hint space + H + release mode, so N
        # campaigns of one example land on one warm-start key without
        # coordination
        self.knowledge_scenario = ""
        self.scenario = ""
        # novelty anneal (GA backend): explore at full w_novelty until
        # the failure archive holds this many DISTINCT signatures, then
        # scale novelty down as the archive grows (SearchConfig docs).
        # 0 = static weights (pre-anneal behavior).
        self.min_failure_signatures = d["min_failure_signatures"]
        self.novelty_floor = d["novelty_floor"]
        # causality guidance (doc/search.md): make relation coverage —
        # which happens-before orderings the campaign has exercised —
        # a search objective. Off by default, and active only while the
        # obs plane is on (obs_enabled = false degrades to the exact
        # pre-guidance blind search — the guidance plane consumes
        # recorded structure, and with recording off it must cost and
        # change nothing).
        self.guidance_enabled = d["guidance"]
        self.guidance_bonus = d["guidance_bonus"]
        self.guidance_width = d["guidance_width"]
        self.guidance_window = d["guidance_window"]
        # fitness weights (ops/schedule.py ScoreWeights). For pure
        # repro-rate maximization set w_novelty=0 so the search chases
        # the failure signature alone; the defaults balance exploration
        # (novel interleavings) against exploitation (bug affinity).
        self.w_novelty = d["w_novelty"]
        self.w_bug = d["w_bug"]
        self.w_delay_cost = d["w_delay_cost"]
        self.w_fault_cost = d["w_fault_cost"]
        # precedence smoothing (seconds): the temporal resolution of the
        # feature embedding. Match it to the bug class's timing scale —
        # ms-level tau saturates on any ordering match, so the search
        # feels no pressure to reproduce the failure's timing MAGNITUDES
        # (a leader-election window is hundreds of ms, not an RTT)
        self.tau = d["tau"]
        # counterfactual anchor: "recent" = most recent success traces
        # (multi-trace averaging, good for novelty search); "envelope" =
        # per-bucket min-arrival envelope over successes. Traces now
        # record true event ARRIVALS (Action.event_arrived), so either
        # mode anchors on the system's interleaving, not the recording
        # policy's jitter; envelope remains useful as the tightest
        # cross-run lower bound for repro-rate maximization.
        self.reference_mode = "recent"
        self.proc_policy_name = "mild"
        import random as _random

        self._rng = _random.Random(0)
        self._proc_policy = create_proc_subpolicy("mild", self._rng)
        # installed schedule tables: ONE (delays, faults, version)
        # snapshot, rebound atomically — a decision reading it pairs a
        # delay with the version of the exact table that produced it,
        # even mid-install (the _delays/_faults properties are derived
        # views for the non-decision call sites)
        self._installed = None
        # zero-RTT dispatch (doc/performance.md): the versioned
        # publication of the installed delay table. The orchestrator
        # plugs this into its hub; endpoints serve it to edges. Every
        # install (eligible or not) bumps the version, so edges notice
        # staleness within one batch.
        self.table_publisher = TablePublisher()
        self._fault_coin = None  # cached per-(seed, H), see _coin_table
        self._search = None
        self._search_thread: Optional[threading.Thread] = None
        self._search_lock = threading.Lock()
        # set when the run is ending (shutdown/wait_for_search): the
        # sidecar evolve parks on this so it never competes with the
        # testee for CPU during the decisive window — its product is for
        # the NEXT run, which install-from-checkpoint covers
        self._run_ending = threading.Event()

    # -- config ----------------------------------------------------------

    def load_config(self, config) -> None:
        p = config.policy_param
        self.seed = int(p("seed", self.seed))
        self._rng.seed(self.seed)
        self._fault_coin = None  # seed/H may change below
        self.max_interval = parse_duration(
            p("max_interval", self.max_interval * 1000))
        self.generations = int(p("generations", self.generations))
        self.population = int(p("population", self.population))
        self.H = int(p("hint_buckets", self.H))
        self.L = int(p("trace_length", self.L))
        self.K = int(p("feature_pairs", self.K))
        self.migrate_k = int(p("migrate_k", self.migrate_k))
        self.fused_chunk = max(1, int(p("fused_chunk", self.fused_chunk)))
        refuse_search_backend(p("search_backend", None))
        for key, instead in REMOVED_KEYS.items():
            if p(key, None) is not None:
                log.warning("tpu_search key %r was removed and is "
                            "ignored: %s", key, instead)
        nd = p("devices", None)
        self.n_devices = int(nd) if nd is not None else None
        self.checkpoint_path = str(p("checkpoint", "") or "")
        self.search_on_start = bool(p("search_on_start", True))
        self.search_join_timeout = parse_duration(
            p("search_join_timeout", self.search_join_timeout * 1000))
        self.search_every = max(1, int(p("search_every", self.search_every)))
        self.sidecar = str(p("sidecar", self.sidecar) or "")
        if self.sidecar and not str(p("checkpoint", "") or ""):
            # the sidecar evolve runs at end-of-run and its product ships
            # to the NEXT run via the checkpoint; without one every
            # request is wasted work whose install lands in an exiting
            # process. Fail fast like the other config knobs.
            raise ValueError(
                "sidecar mode requires a checkpoint (the evolved schedule "
                "reaches the next run through it); set checkpoint = "
                "\"search.npz\""
            )
        self.max_fault = float(p("max_fault", self.max_fault))
        self.surrogate_topk = int(p("surrogate_topk", self.surrogate_topk))
        self.failure_pool = os.path.expanduser(os.path.expandvars(
            str(p("failure_pool", self.failure_pool) or "")))
        self.knowledge = str(p("knowledge", self.knowledge) or "")
        self.knowledge_scenario = str(
            p("knowledge_scenario", self.knowledge_scenario) or "")
        self.min_failure_signatures = int(
            p("min_failure_signatures", self.min_failure_signatures))
        self.novelty_floor = float(p("novelty_floor", self.novelty_floor))
        self.guidance_enabled = bool(p("guidance", self.guidance_enabled))
        self.guidance_bonus = float(
            p("guidance_bonus", self.guidance_bonus))
        self.guidance_width = int(
            p("guidance_bitmap_width", self.guidance_width))
        self.guidance_window = int(
            p("guidance_window", self.guidance_window))
        self.w_novelty = float(p("w_novelty", self.w_novelty))
        self.w_bug = float(p("w_bug", self.w_bug))
        self.w_delay_cost = float(p("w_delay_cost", self.w_delay_cost))
        self.w_fault_cost = float(p("w_fault_cost", self.w_fault_cost))
        self.tau = parse_duration(p("tau", self.tau * 1000))
        self.reference_mode = str(p("reference_mode", self.reference_mode))
        if self.reference_mode not in ("recent", "envelope"):
            raise ValueError(
                f"unknown reference_mode {self.reference_mode!r} "
                "(expected 'recent' or 'envelope')"
            )
        self.release_mode = str(p("release_mode", self.release_mode))
        if self.release_mode not in ("delay", "reorder"):
            raise ValueError(
                f"unknown release_mode {self.release_mode!r} "
                "(expected 'delay' or 'reorder')"
            )
        self.reorder_window = parse_duration(
            p("reorder_window", self.reorder_window * 1000))
        self.reorder_gap = parse_duration(
            p("reorder_gap", self.reorder_gap * 1000))
        if self.release_mode == "reorder" and self.reorder_window <= 0:
            # window=0 would mean "one global window" to the scorer but a
            # busy-spinning, continuously-draining loop to the control
            # plane — maximal scored/executed disagreement plus a pegged
            # CPU. Fail fast like the other enum knobs.
            raise ValueError(
                "reorder_window must be > 0 in reorder mode "
                f"(got {self.reorder_window})"
            )
        name = str(p("proc_policy", self.proc_policy_name))
        self.proc_policy_name = name
        self._proc_policy = create_proc_subpolicy(name, self._rng)
        self._proc_policy.load_params(p("proc_policy_param", {}) or {})
        # last: the fingerprint folds in knobs parsed above (H,
        # release_mode), so it must see their final values
        self.scenario = (self.knowledge_scenario
                         or self._scenario_fingerprint(config))

    # -- hot path ---------------------------------------------------------

    def _bucket(self, hint: str) -> int:
        return fnv64a(hint.encode()) % self.H

    @property
    def _delays(self):
        installed = self._installed
        return installed[0] if installed is not None else None

    @property
    def _faults(self):
        installed = self._installed
        return installed[1] if installed is not None else None

    def _decision_ctx(self):
        """ONE atomic read of the installed snapshot, shared by a whole
        decision (or decision batch): ``(snapshot_or_None, source tag,
        record extra)``. Deriving the delay AND the recorded
        ``table_version`` from the same snapshot means a concurrent
        install can never produce a record whose version belongs to a
        different table than its delay."""
        installed = self._installed
        if installed is None:
            return None, "hash", {}
        return installed, "table", {"table_version": installed[2]}

    def _delay_from(self, installed, hint: str) -> float:
        if installed is None:
            return hint_delay(str(self.seed), hint, self.max_interval)
        return float(installed[0][self._bucket(hint)])

    def _delay_for(self, hint: str) -> float:
        return self._delay_from(self._installed, hint)

    def _delays_from_many(self, installed, hints):
        """Vectorized :meth:`_delay_for` over a batch of hints: one
        fnv64a pass over the whole batch (numpy loop over byte
        positions, policy/replayable.py fnv64a_many) and one fancy-index
        gather from the installed table — value-identical to the scalar
        path, without its per-event Python hash loop. Returns a float
        ndarray of shape ``[len(hints)]``."""
        import numpy as _np

        if installed is None:
            return hint_delays(str(self.seed), hints, self.max_interval)
        buckets = fnv64a_many([h.encode() for h in hints]) \
            % _np.uint64(self.H)
        return _np.asarray(installed[0])[buckets.astype(_np.int64)]

    def _delays_for_many(self, hints):
        return self._delays_from_many(self._installed, hints)

    def _coin_table(self):
        """Per-bucket fault coin, computed once per (seed, H) — the SAME
        array the scorer's drop_mask uses (one source of truth in
        ops/trace_encoding.fault_coin), so the replayed drops are the
        drops the schedule was scored with, and the hot path pays one
        table lookup instead of a string hash per event."""
        cached = self._fault_coin
        if cached is None or cached.shape[0] != self.H:
            from namazu_tpu.ops.trace_encoding import fault_coin

            cached = self._fault_coin = fault_coin(self.seed, self.H)
        return cached

    def _fault_for(self, hint: str) -> bool:
        faults = self._faults
        if faults is None or self.max_fault <= 0:
            return False
        bucket = self._bucket(hint)
        p = float(faults[bucket])
        if p <= 0:
            return False
        return float(self._coin_table()[bucket]) < p

    def _table_source(self) -> str:
        """Where the current hot-path table values come from — the
        flight recorder's causal tag for each decision."""
        return "hash" if self._delays is None else "table"

    # -- table install + publication (zero-RTT dispatch) -----------------

    def _install_tables(self, delays, faults, source: str) -> None:
        """The ONE install seam: publish for the edge plane first (that
        mints the version), then swap the hot-path snapshot — table and
        its version rebound together, so decisions racing the install
        see either the old pair or the new pair, never a mix."""
        obs.schedule_install(source)
        obs.record_install(source)
        version = self._publish_table(delays, faults)
        self._installed = (delays, faults, version)

    def install_table(self, delays, faults=None,
                      source: str = "manual") -> None:
        """Public install (bench/tests/chaos harness): installs
        ``delays`` exactly like a search-plane install would, including
        the edge publication."""
        import numpy as _np

        delays = _np.asarray(delays, dtype=_np.float64)
        if delays.shape != (self.H,):
            raise ValueError(
                f"delays shape {delays.shape} != (H={self.H},)")
        self._install_tables(delays, faults, source)

    def _publish_table(self, delays, faults) -> int:
        """Publish ``delays`` when it is edge-eligible — the
        steady-state decision must be the pure hint->delay function the
        edge replicates. Fault-bearing or reorder-mode installs publish
        a *withdrawal* instead (version bump, no doc): edges fall back
        to the central wire, loss-free. Returns the minted version."""
        eligible = (delays is not None and self.release_mode == "delay"
                    and (faults is None or self.max_fault <= 0))
        if eligible:
            return self.table_publisher.publish(delays, self.H,
                                                self.max_interval)
        return self.table_publisher.publish_none()

    def queue_event(self, event: Event) -> None:
        self.start()
        if isinstance(event, ProcSetEvent):
            attrs = self._proc_policy.attrs_for(event.pids)
            obs.record_decision(event, self.name, kind="procset",
                                proc_policy=self.proc_policy_name)
            self._emit(ProcSetSchedAction.for_procset(event, attrs))
            return
        if self.release_mode == "reorder":
            # table value = priority (hash fallback until a search lands);
            # the window thread releases pending events in priority order
            if self._stop_reorder.is_set():
                # raced with shutdown's final flush: release immediately
                # so no transceiver hangs on a never-emitted action
                self._emit(self._action_for(event))
                return
            installed, source, extra = self._decision_ctx()
            prio = self._delay_from(installed, event.replay_hint())
            obs.record_decision(
                event, self.name, mode="reorder", priority=prio,
                source=source,
                generation=obs.current_generation_id(),
                **extra)
            now = self._now()
            with self._pending_lock:
                if self._anchor is None:
                    self._anchor = now
                    self._anchor_set.set()
                self._pending.append((prio, self._pending_seq, now, event))
                self._pending_seq += 1
            if self._stop_reorder.is_set():
                # shutdown flushed between our check and the append —
                # drain again (idempotent) so the event is not stranded
                self._drain_pending(gap=0.0)
            return
        installed, source, extra = self._decision_ctx()
        delay = self._delay_from(installed, event.replay_hint())
        obs.record_decision(event, self.name, mode="delay", delay=delay,
                            source=source,
                            generation=obs.current_generation_id(),
                            **extra)
        self._queue.put_at(event, delay)

    def _queue_events_batch(self, events) -> list:
        """Batch decision point (the orchestrator's event loop hands
        over its drained batch): the fnv64a-bucket -> delay-table lookup
        runs vectorized over the whole batch, then the result feeds the
        release machinery in ONE lock acquisition — ``put_at_many`` on
        the delay queue, or one ``_pending_lock`` append run for the
        reorder window. Decision VALUES are identical to the sequential
        path (same hash, same table, same record_decision detail); only
        the per-event Python overhead is gone. Returns the rejected
        events (poison procsets — the vectorized path itself is
        all-or-nothing)."""
        rejected = []
        plain = []
        for event in events:
            if isinstance(event, ProcSetEvent):
                # answered out-of-band via the proc subpolicy; rides the
                # scalar path (no table lookup to vectorize). Isolated:
                # a poison procset must not lose the rest of the batch
                try:
                    self.queue_event(event)
                except Exception:
                    log.exception("procset event %r rejected (batch "
                                  "continues)", event)
                    rejected.append(event)
            else:
                plain.append(event)
        if not plain:
            return rejected
        if self._stop_reorder.is_set() and self.release_mode == "reorder":
            # raced with shutdown's final flush: scalar path releases
            # each event immediately
            for event in plain:
                try:
                    self.queue_event(event)
                except Exception:
                    log.exception("event %r rejected during reorder "
                                  "shutdown flush", event)
                    rejected.append(event)
            return rejected
        installed, source, extra = self._decision_ctx()
        vals = self._delays_from_many(
            installed, [ev.replay_hint() for ev in plain])
        generation = obs.current_generation_id()
        if self.release_mode == "reorder":
            for event, prio in zip(plain, vals):
                obs.record_decision(
                    event, self.name, mode="reorder",
                    priority=float(prio), source=source,
                    generation=generation, **extra)
            now = self._now()
            with self._pending_lock:
                if self._anchor is None:
                    self._anchor = now
                    self._anchor_set.set()
                for event, prio in zip(plain, vals):
                    self._pending.append(
                        (float(prio), self._pending_seq, now, event))
                    self._pending_seq += 1
            if self._stop_reorder.is_set():
                self._drain_pending(gap=0.0)
            return rejected
        for event, delay in zip(plain, vals):
            obs.record_decision(event, self.name, mode="delay",
                                delay=float(delay), source=source,
                                generation=generation, **extra)
        self._queue.put_at_many(
            (event, float(delay)) for event, delay in zip(plain, vals))
        return rejected

    def _action_for(self, event: Event):
        if self._fault_for(event.replay_hint()):
            fault = event.default_fault_action()
            if fault is not None:
                return fault
        return event.default_action()

    # -- search plane -----------------------------------------------------

    def start(self) -> None:
        super().start()
        # _start_lock makes the spawns idempotent under concurrent
        # queue_event callers (the base class guards only its own thread)
        with self._start_lock:
            if (self.release_mode == "reorder"
                    and self._reorder_thread is None):
                self._reorder_thread = self._spawn(self._reorder_loop,
                                                   "reorder")
            if self.search_on_start and self._search_thread is None:
                self._search_thread = self._spawn(self._search_once,
                                                  "search")

    # -- reorder window ---------------------------------------------------

    def _drain_pending(self, gap: float,
                       boundary: Optional[float] = None) -> None:
        """Release pending events whose window has closed.

        ``boundary`` (monotonic time) limits the drain to events that
        arrived before it — i.e. to *closed* windows only; ``None`` takes
        everything (shutdown flush). The batch is released in
        (window, priority, arrival) order: exactly the permutation the
        scorer's ``order_release_times`` assigns to these arrivals, so the
        realized interleaving IS the scored one."""
        anchor, w = self._anchor, self.reorder_window
        with self._pending_lock:
            if boundary is None:
                batch, self._pending = self._pending, []
            else:
                batch = [p for p in self._pending if p[2] < boundary]
                self._pending = [p for p in self._pending
                                 if p[2] >= boundary]

        def win(t: float) -> int:
            if anchor is None or w <= 0:
                return 0
            return int((t - anchor) // w)

        batch.sort(key=lambda p: (win(p[2]), p[0], p[1]))
        # a closed window, counted when its last event is out: how many
        # events it held, and whether its paced drain ran past the NEXT
        # boundary (anchor + (k + 2) w for window k, which closes at
        # anchor + (k + 1) w) — from there on the realized slots are
        # later than the scorer's ``close + gap * rank``. The shutdown
        # flush (no boundary) is not a window's drain and counts nothing.
        counted = boundary is not None and anchor is not None and w > 0
        first = 0  # index of the current window's first event
        for i, (_prio, _seq, t, event) in enumerate(batch):
            # during shutdown, stop pacing so a large in-flight batch
            # cannot outlive the join window and lose its tail
            if i and gap > 0 and not self._stop_reorder.is_set():
                time.sleep(gap)
            obs.record_released(event, self.name)
            obs.queue_dwell(self.name, event.entity_id,
                            obs.latency(event, "enqueued"))
            self._emit(self._action_for(event))
            k = win(t)
            if counted and (i + 1 == len(batch)
                            or win(batch[i + 1][2]) != k):
                obs.reorder_window_drained(
                    self.name, i + 1 - first,
                    overran=self._now() > anchor + (k + 2) * w)
                first = i + 1

    def _reorder_loop(self) -> None:
        """Tick at absolute window boundaries ``anchor + k*window`` and
        release only the windows that closed — not whatever happens to be
        pending at wake-up, which would batch events across the scorer's
        window boundaries."""
        w = self.reorder_window
        # phase 1: wait for the first event to anchor the window clock
        while not self._stop_reorder.is_set():
            if self._anchor_set.wait(timeout=0.05):
                break
        # phase 2: aligned ticks
        while not self._stop_reorder.is_set():
            anchor = self._anchor
            now = self._now()
            k = int((now - anchor) // w) + 1
            if self._stop_reorder.wait(max(0.0, anchor + k * w - now)):
                break
            self._drain_pending(self.reorder_gap,
                                boundary=anchor + k * w)

    def _build_search(self):
        from namazu_tpu.models.search import build_search_from_params

        return build_search_from_params(self._search_params())

    def _guidance_active(self) -> bool:
        """Guidance runs only when asked for AND the obs plane is on:
        the coverage signature is derived from recorded structure, so
        ``obs_enabled = false`` degrades to the exact pre-guidance
        blind search instead of guiding on phantom data."""
        return self.guidance_enabled and obs.metrics.enabled()

    def _checkpoint(self) -> str:
        """Checkpoint path; a relative path anchors to the experiment's
        storage dir (stable across `run` invocations from any cwd)."""
        p = self.checkpoint_path
        if (p and not os.path.isabs(p)
                and getattr(self._storage, "dir", None)):
            return os.path.join(self._storage.dir, p)
        return p

    def _install_from_checkpoint(self, ckpt: str) -> bool:
        """Install the checkpointed best tables from the raw npz, without
        touching any jax machinery. The testee's decisive window (a
        leader election, a reader's grace period) is typically over
        within the first few hundred ms of the run; building the search
        object first (imports, mesh, jit setup) loses that race and the
        whole run silently executes hash-fallback delays."""
        import numpy as _np

        from namazu_tpu.ops.trace_encoding import (
            HINT_SPACE,
            checkpoint_hint_space,
        )

        try:
            with _np.load(ckpt) as z:
                if "best_delays" not in z or "generations_run" not in z:
                    return False
                if int(z["generations_run"]) <= 0:
                    return False
                space = checkpoint_hint_space(z)
                if space != HINT_SPACE:
                    log.warning(
                        "checkpoint %s is from hint space %r (this build: "
                        "%r); not installing its schedule", ckpt, space,
                        HINT_SPACE)
                    return False
                fit = (float(z["best_fitness"])
                       if "best_fitness" in z else float("nan"))
                if not _np.isfinite(fit):
                    return False
                delays = _np.array(z["best_delays"])
                if delays.shape != (self.H,):
                    log.warning(
                        "checkpoint %s has best_delays of shape %s but "
                        "hint_buckets=%d; not installing", ckpt,
                        delays.shape, self.H)
                    return False
                faults = (_np.array(z["best_faults"])
                          if "best_faults" in z else None)
        except Exception:
            log.exception("unreadable checkpoint %s", ckpt)
            return False
        self._install_tables(delays, faults, "checkpoint")
        log.info("installed checkpointed schedule (fitness %.4f) from %s",
                 fit, ckpt)
        return True

    def _search_once(self) -> None:
        """Background: ingest history, evolve, install the best tables."""
        try:
            ckpt = self._checkpoint()
            installed = False
            if ckpt and os.path.exists(ckpt) and self._delays is None:
                # cheap install FIRST (np.load only), then the heavy build
                installed = self._install_from_checkpoint(ckpt)
            if not installed and self._delays is None and self.knowledge:
                # truly cold run (no checkpoint product): the fleet's
                # best table for this scenario beats the hash fallback —
                # the whole point of the knowledge plane (doc/knowledge.md)
                self._knowledge_warmstart_table()
            if installed and self.search_every > 1:
                storage = self._storage
                try:
                    n = storage.nr_stored_histories() if storage else 0
                except Exception:
                    n = 0
                if n % self.search_every != 0:
                    log.info(
                        "install-only run (search_every=%d, %d stored "
                        "runs); next evolution at %d",
                        self.search_every, n,
                        -(-n // self.search_every) * self.search_every)
                    return
            if self.sidecar:
                # park until the run ends: a warm sidecar evolve is
                # fast enough to land INSIDE the testee's decisive
                # window, and on small hosts the CPU it burns there
                # skews the very timing being fuzzed. The evolve's
                # product ships via the checkpoint to the next run,
                # so end-of-run is the right moment (and the
                # reference's division of labor: exploration work
                # happens between experiments, SURVEY.md 3.1).
                self._run_ending.wait()
                try:
                    self._sidecar_search(ckpt)
                except Exception:
                    # the sidecar owns the chip(s): this process must
                    # never initialise a device backend beside it, so a
                    # failed request keeps whatever table is installed
                    # (checkpoint or hash) instead of searching here
                    log.exception(
                        "sidecar %s unreachable/failed; %s delays remain "
                        "(no in-process search in sidecar mode)",
                        self.sidecar, self._table_source())
                return
            with self._search_lock:
                if self._search is None:
                    self._search = self._build_search()
                    if ckpt and os.path.exists(ckpt):
                        try:
                            self._search.load(ckpt)
                            log.info("loaded search checkpoint %s (gen %d)",
                                     ckpt, self._search.generations_run)
                        except Exception:
                            # incompatible (hint space, backend, shape) or
                            # corrupt: evolve fresh rather than abort the
                            # whole search; the save below replaces it
                            log.exception(
                                "checkpoint %s not loadable; starting a "
                                "fresh search", ckpt)
                    self._wire_remote_surrogate(self._search)
                search = self._search
            if search.generations_run > 0 and self._delays is None:
                # install the checkpointed best NOW: the testee's decisive
                # window (e.g. a leader election) is typically over within
                # the first second of the run, long before this thread's
                # own evolution finishes — so each run replays the
                # schedule found by the end of the *previous* run, and
                # this run's evolution product ships in the checkpoint
                import numpy as _np

                b = search.best()
                if _np.isfinite(b.fitness):
                    self._install_tables(b.delays, b.faults, "checkpoint")
                    log.info(
                        "installed checkpointed schedule (fitness %.4f) "
                        "before this run's search", b.fitness)
            references = self._ingest_history(search)
            if not references:
                log.info("no stored history yet; keeping hash-based delays")
                return
            best = search.run(references, generations=self.generations)
            with obs.search_phase("install"):
                self._install_tables(best.delays, best.faults, "search")
            from namazu_tpu.parallel.mesh import device_summary

            log.info("installed searched schedule (fitness %.4f, gen %d) "
                     "on %s", best.fitness, search.generations_run,
                     _device_str(device_summary()))
            if ckpt:
                search.save(ckpt)
            self._knowledge_push_best(best.delays, best.fitness)
        except Exception:
            log.exception("schedule search failed; hash-based delays remain")

    MAX_REFERENCE_TRACES = 4
    MAX_SEED_GENOMES = 16

    def _failure_seed(self, trace):
        """See models/ingest.py failure_seed (shared with the sidecar)."""
        from namazu_tpu.models.ingest import failure_seed

        return failure_seed(trace, self.H, self.max_interval)

    def _search_params(self) -> dict:
        """Flat JSON-able search knobs, under ``SEARCH_DEFAULTS``' names
        — what ``models.search.build_search_from_params`` builds the
        backend from, here or in the sidecar."""
        return {
            "H": self.H, "L": self.L, "K": self.K,
            "population": self.population,
            "migrate_k": self.migrate_k,
            "fused_chunk": self.fused_chunk,
            "seed": self.seed,
            "max_interval": self.max_interval,
            "max_fault": self.max_fault,
            "surrogate_topk": self.surrogate_topk,
            "min_failure_signatures": self.min_failure_signatures,
            "novelty_floor": self.novelty_floor,
            "guidance": self._guidance_active(),
            "guidance_bonus": self.guidance_bonus,
            "guidance_width": self.guidance_width,
            "guidance_window": self.guidance_window,
            "release_mode": self.release_mode,
            "w_novelty": self.w_novelty, "w_bug": self.w_bug,
            "w_delay_cost": self.w_delay_cost,
            "w_fault_cost": self.w_fault_cost,
            "tau": self.tau,
            "reorder_gap": self.reorder_gap,
            "reorder_window": self.reorder_window,
            "devices": self.n_devices,
        }

    def _sidecar_search(self, ckpt: str) -> None:
        """Delegate the evolve cycle to the persistent sidecar and
        install what it returns. Raises on any failure — the caller
        keeps its current table."""
        import numpy as _np

        from namazu_tpu.obs.context import wire_stamp
        from namazu_tpu.sidecar import request

        storage_dir = getattr(self._storage, "dir", None)
        if not storage_dir:
            raise RuntimeError(
                "sidecar search needs a directory-backed storage")
        req = {
            "op": "search",
            "key": os.path.abspath(storage_dir),
            "storage": os.path.abspath(storage_dir),
            "search_params": self._search_params(),
            "ingest_params": self._ingest_params()._asdict(),
            "generations": self.generations,
            "checkpoint": os.path.abspath(ckpt) if ckpt else "",
        }
        rid = ""
        if obs.metrics.enabled():
            # the stamp is the request's id on the sidecar's spans
            # (obs/spans.py request_begin), so this run's log line and
            # the sidecar's span tree name the same request
            stamp = req["ctx"] = wire_stamp()
            self.sidecar_request_id = f"{stamp['o']}:{stamp['lc']}"
            rid = f", request {self.sidecar_request_id}"
        resp = request(self.sidecar, req,
                       timeout=max(self.search_join_timeout, 30.0))
        if not resp.get("ok"):
            raise RuntimeError(f"sidecar: {resp.get('error', 'failed')}")
        if resp.get("no_history"):
            log.info("sidecar: no stored history yet; keeping current "
                     "delays")
            return
        self._install_tables(_np.asarray(resp["delays"], _np.float32),
                             _np.asarray(resp["faults"], _np.float32),
                             "sidecar")
        log.info("installed sidecar schedule (fitness %.4f, gen %d) on "
                 "%s%s", resp["fitness"], resp["generations_run"],
                 _device_str(resp.get("device")), rid)
        self._knowledge_push_best(self._delays, float(resp["fitness"]))

    # -- global failure-knowledge plane (doc/knowledge.md) ---------------

    def _scenario_fingerprint(self, config) -> str:
        """Warm-start key: campaigns of one experiment — same run/
        validate scripts, hint space, bucket count, release mode — must
        land on one knowledge-service scenario without coordination,
        and experiments with different oracles must never share a delay
        table (their fitness scales aren't comparable)."""
        import hashlib
        import json as _json

        from namazu_tpu.signal.base import HINT_SPACE

        basis = [str(config.get("run", "")),
                 str(config.get("validate", "")),
                 HINT_SPACE, int(self.H), self.release_mode]
        return hashlib.sha256(
            _json.dumps(basis).encode()).hexdigest()[:16]

    def _knowledge_tenant(self) -> str:
        d = getattr(self._storage, "dir", None)
        return os.path.basename(os.path.abspath(d)) if d else "anon"

    def _knowledge_client(self):
        """The process-shared client for this policy's service/tenant/
        scenario triple, or None when the knowledge plane is off."""
        if not self.knowledge:
            return None
        from namazu_tpu.knowledge import shared_client

        return shared_client(self.knowledge,
                             tenant=self._knowledge_tenant(),
                             scenario=self.scenario)

    def _knowledge_warmstart_table(self) -> bool:
        """Cold-run hot-path warm-start: install the scenario's best
        fleet delay table when nothing better exists yet (no checkpoint,
        no own search product). Returns whether a table was installed;
        outages/empty services return False and hash fallback remains —
        a knowledge outage must never fail (or even delay) a run."""
        client = self._knowledge_client()
        if client is None:
            return False
        try:
            table = client.scenario_table(self.H)
        except Exception:
            log.exception("knowledge warm-start failed; keeping "
                          "hash-based delays")
            return False
        if table is None:
            return False
        self._install_tables(table["delays"], self._faults, "knowledge")
        obs.knowledge_warmstart("table")
        log.info("installed knowledge warm-start schedule (fitness "
                 "%.4f, scenario %s)", table["fitness"], self.scenario)
        return True

    def _knowledge_push_best(self, delays, fitness: float) -> None:
        """Publish this run's evolved best so the NEXT cold campaign of
        this scenario warm-starts from it (service keeps the highest
        fitness per scenario). Best-effort."""
        client = self._knowledge_client()
        if client is None:
            return
        import numpy as _np

        if delays is None or not _np.isfinite(fitness):
            return
        try:
            client.push(best={
                "delays": [float(x) for x in _np.asarray(delays)],
                "fitness": float(fitness), "H": self.H,
            })
        except Exception:
            log.exception("could not push best schedule to the "
                          "knowledge service")

    def _wire_remote_surrogate(self, search) -> None:
        """Give the search the shared-surrogate hook: candidate features
        go to the knowledge service scoped by this search's own pair
        fingerprint (features never cross feature spaces). Consulted
        only while the local surrogate is too thin (models/search.py
        _surrogate_pick)."""
        client = self._knowledge_client()
        if client is None:
            return

        from namazu_tpu.knowledge.client import pairs_fingerprint

        def hook(feats, _client=client, _search=search):
            return _client.predict(
                feats, pairs_fp=pairs_fingerprint(_search.pairs))

        search.remote_surrogate = hook

    def _failure_pool_path(self) -> str:
        """Pool dir; a relative path anchors to the storage dir's PARENT
        so sibling experiment storages (e.g. A/B batches under one root)
        share one pool."""
        p = self.failure_pool
        if (p and not os.path.isabs(p)
                and getattr(self._storage, "dir", None)):
            parent = os.path.dirname(
                os.path.abspath(self._storage.dir))
            return os.path.join(parent, p)
        return p

    def _ingest_params(self):
        from namazu_tpu.models.ingest import IngestParams

        return IngestParams(
            H=self.H, L=self.L,
            release_mode=self.release_mode,
            reference_mode=self.reference_mode,
            max_interval=self.max_interval,
            max_reference_traces=self.MAX_REFERENCE_TRACES,
            max_seed_genomes=self.MAX_SEED_GENOMES,
            order_mode_max_l=self.ORDER_MODE_MAX_L,
            failure_pool=self._failure_pool_path(),
            knowledge=self.knowledge,
            knowledge_tenant=self._knowledge_tenant(),
            knowledge_scenario=self.scenario,
            guidance=self._guidance_active(),
            guidance_width=self.guidance_width,
            guidance_window=self.guidance_window,
        )
    # order mode materializes [population, L] release times per
    # reference trace and generation (ops/schedule.py): a stored run
    # over this many events is cut in reorder mode unless the user set a
    # length explicitly; a shorter run keeps its own length quantum
    ORDER_MODE_MAX_L = 4096

    def _ingest_history(self, search):
        """Feed stored traces into the archives; return the reference
        traces to evolve against — shared implementation with the
        persistent search sidecar (models/ingest.py, which carries the
        full design rationale)."""
        from namazu_tpu.models.ingest import ingest_history

        return ingest_history(search, self._storage, self._ingest_params())

    def shutdown(self) -> None:
        """With a checkpoint configured, let an in-flight search finish
        (bounded) before the run ends — the searched schedule + checkpoint
        are the run's product for the next `run` invocation's policy to
        pick up. Without one the result could not outlive the process, so
        don't hold the shutdown."""
        if self._reorder_thread is not None:
            self._stop_reorder.set()
            self._reorder_thread.join(timeout=10)
            self._drain_pending(gap=0.0)  # flush, loss-free shutdown
        self._run_ending.set()  # release a parked sidecar evolve
        t = self._search_thread
        if t is not None and self.checkpoint_path:
            t.join(timeout=self.search_join_timeout)
        super().shutdown()

    def wait_for_search(self, timeout: float = 120.0) -> bool:
        """Block until the background search installed a schedule (tests)."""
        self._run_ending.set()
        t = self._search_thread
        if t is None:
            return self._delays is not None
        t.join(timeout=timeout)
        return self._delays is not None


register_policy(TPUSearchPolicy.NAME, TPUSearchPolicy)
