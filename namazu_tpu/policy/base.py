"""Policy interface and registry."""

from __future__ import annotations

import queue
import threading
from typing import Callable, Dict, Iterable, Optional, TYPE_CHECKING

from namazu_tpu import obs
from namazu_tpu.signal.action import Action
from namazu_tpu.signal.event import Event
from namazu_tpu.utils.log import get_logger
from namazu_tpu.utils.sched_queue import QueueClosed, ScheduledQueue

log = get_logger("policy")

if TYPE_CHECKING:  # pragma: no cover
    from namazu_tpu.storage.base import HistoryStorage
    from namazu_tpu.utils.config import Config


class PolicyError(Exception):
    pass


class _PolicyDone:
    """Sentinel a policy emits on ``action_out`` after shutdown has flushed
    every remaining action — lets the orchestrator drain without racing."""

    def __repr__(self) -> str:  # pragma: no cover
        return "<POLICY_DONE>"


POLICY_DONE = _PolicyDone()


class ExplorePolicy:
    """Base class for exploration policies.

    Contract (parity with the reference's ExplorePolicy interface,
    /root/reference/nmz/explorepolicy/interface.go:24-40, and the
    non-blocking warning in its README.md:304):

    * ``queue_event`` MUST return quickly — never block on I/O or sleep.
    * actions appear on ``action_out`` (a thread-safe queue) in the order
      the policy decides to release them; that order IS the fuzz. An
      ``action_out`` item is one :class:`Action` OR a list of them (a
      burst released together — the consumer flattens in order); the
      batch form exists so a burst costs one queue hand-off, not one
      thread wakeup per action.
    * ``load_config`` may be called again at runtime for dynamic reload.
    """

    NAME = "abstract"

    #: id of the last search request this policy sent to a sidecar: what
    #: the run's `search` phase row names (orchestrator/core.py
    #: shutdown). "" for a policy that sends none
    sidecar_request_id = ""

    def __init__(self) -> None:
        self.action_out: "queue.Queue[Action]" = queue.Queue()
        self._storage: Optional["HistoryStorage"] = None

    @property
    def name(self) -> str:
        return self.NAME

    def load_config(self, config: "Config") -> None:
        """Read ``explore_policy_param.*`` keys. Unknown keys are ignored
        (parity: the reference tolerates unknown params,
        randompolicy_test.go:49-91)."""

    def set_history_storage(self, storage: "HistoryStorage") -> None:
        self._storage = storage

    def queue_event(self, event: Event) -> None:
        raise NotImplementedError

    def queue_events(self, events: Iterable[Event]) -> "list[Event]":
        """Batch entry point: decide a whole batch in one call; returns
        the events the policy REJECTED (empty when all queued — the
        orchestrator skips lifecycle marks for rejected events, keeping
        batched and per-event telemetry identical). The default just
        loops; policies with a vectorizable decision (the TPU policy's
        bucket -> table lookup) override the batch hook so the
        orchestrator's event loop can hand them a drained batch without
        a per-event Python round trip.

        Failures are isolated per event, matching the per-event path's
        semantics: one poison event must not take down the rest of the
        drained batch."""
        rejected = []
        for event in events:
            try:
                self.queue_event(event)
            except Exception:
                log.exception(
                    "policy %s rejected event %r (rest of the batch "
                    "continues)", self.name, event)
                rejected.append(event)
        return rejected

    def force_release_entity(self, entity_id: str) -> int:
        """Release any events parked for ``entity_id`` immediately;
        returns how many were released. Called by the orchestrator's
        liveness watchdog when the entity is declared dead — the default
        is a no-op for policies without a delay queue."""
        return 0

    def start(self) -> None:
        """Start worker threads (idempotent)."""

    def shutdown(self) -> None:
        """Stop worker threads, flush pending actions, then emit
        :data:`POLICY_DONE` on ``action_out``."""
        self.action_out.put(POLICY_DONE)  # type: ignore[arg-type]

    # -- helpers for subclasses -----------------------------------------

    def _emit(self, action: Action) -> None:
        self.action_out.put(action)

    def _spawn(self, target: Callable[[], None], name: str) -> threading.Thread:
        t = threading.Thread(target=target, name=f"{self.name}-{name}", daemon=True)
        t.start()
        return t


class QueueBackedPolicy(ExplorePolicy):
    """Shared machinery for policies built around one ScheduledQueue: an
    idempotent start, a dequeue worker mapping each released event to an
    action via :meth:`_action_for`, and a flushing shutdown."""

    def __init__(self, seed: Optional[int] = None,
                 time_source=None) -> None:
        super().__init__()
        # the delay queue reads the process TimeSource by default: a
        # `run --virtual-clock` installs a VirtualTimeSource before the
        # policy is constructed, and the queue's parked deadlines
        # become the fast-forward coordinator's jump targets
        # (utils/timesource.py)
        self._queue = ScheduledQueue(seed=seed, obs_name=self.name,
                                     time_source=time_source)
        self._started = False
        self._start_lock = threading.Lock()
        self._dequeue_thread: Optional[threading.Thread] = None

    def start(self) -> None:
        with self._start_lock:
            if self._started:
                return
            self._started = True
            self._dequeue_thread = self._spawn(self._dequeue_loop, "dequeue")

    def queue_events(self, events: Iterable[Event]) -> "list[Event]":
        """Shared batch preamble (one home for the list/size/start
        boilerplate): single events ride the isolated scalar loop,
        larger batches go through :meth:`_queue_events_batch`."""
        events = list(events)
        if len(events) <= 1:
            return super().queue_events(events)
        self.start()
        return self._queue_events_batch(events)

    def _queue_events_batch(self, events: "list[Event]") -> "list[Event]":
        """Batch hook for >= 2 events (``start()`` already called):
        queue the whole batch, ideally under one queue-lock
        acquisition; returns the rejected events. Default: the
        isolated scalar loop."""
        return super().queue_events(events)

    #: how many simultaneously-ripe releases one dequeue pass may drain
    #: (and the largest burst list emitted on action_out)
    DEQUEUE_BATCH_MAX = 256

    def _dequeue_loop(self) -> None:
        while True:
            try:
                events = self._queue.get_batch(self.DEQUEUE_BATCH_MAX)
            except QueueClosed:
                return
            actions = []
            for event in events:
                # the released span feeds the causality plane's
                # parking/dispatch segment split (obs/causality.py);
                # the shared span dict makes it visible on the action
                obs.mark(event, "released")
                obs.record_released(event, self.name)
                obs.queue_dwell(self.name, event.entity_id,
                                obs.latency(event, "enqueued"))
                actions.append(self._action_for(event))
            if len(actions) == 1:
                self._emit(actions[0])
            else:
                # one queue hand-off for the whole burst (list form of
                # the action_out contract)
                self.action_out.put(actions)

    def _action_for(self, event: Event) -> Action:
        return event.default_action()

    def force_release_entity(self, entity_id: str) -> int:
        events = self._queue.expedite(
            lambda ev: getattr(ev, "entity_id", None) == entity_id,
            collect=True)
        # attribute the non-policy release: the chaos invariant checker
        # and `tools trace diff` must be able to tell "the watchdog
        # freed this" from "the policy chose this" (doc/robustness.md)
        for event in events:
            obs.record_decision(event, self.name, source="watchdog")
        return len(events)

    def shutdown(self) -> None:
        """Release all still-delayed events immediately, wait for the
        dequeue worker to flush their actions, then signal POLICY_DONE."""
        self._queue.close(immediate=True)
        t = self._dequeue_thread
        if t is not None:
            t.join(timeout=10)
        # dwell is normally observed at dequeue; events still resident
        # here (worker never started, died, or outlived the join window)
        # would otherwise vanish from the histogram — exactly the
        # long-stuck tail an operator most needs to see
        for event in self._queue.drain_remaining():
            entity = getattr(event, "entity_id", "")
            if entity:
                obs.queue_dwell(self.name, entity,
                                obs.latency(event, "enqueued"))
        super().shutdown()


PolicyFactory = Callable[[], ExplorePolicy]

_POLICIES: Dict[str, PolicyFactory] = {}


def register_policy(name: str, factory: PolicyFactory) -> None:
    """Register a policy factory (parity: RegisterPolicy,
    /root/reference/nmz/explorepolicy/explorepolicy.go:25-31)."""
    if name in _POLICIES:
        raise PolicyError(f"policy {name!r} already registered")
    _POLICIES[name] = factory


def create_policy(name: str) -> ExplorePolicy:
    """Instantiate a registered policy (parity: CreatePolicy,
    explorepolicy.go:33-37). The TPU search policy is registered lazily so
    that control-plane-only deployments never import jax."""
    if name == "tpu_search" and name not in _POLICIES:
        try:
            from namazu_tpu.policy import tpu as _tpu  # noqa: F401  (self-registers)
        except ImportError as e:
            raise PolicyError(f"tpu_search policy unavailable: {e}") from e
    try:
        factory = _POLICIES[name]
    except KeyError:
        raise PolicyError(
            f"unknown policy {name!r}; known: {sorted(_POLICIES)}"
        ) from None
    return factory()


def known_policies() -> Iterable[str]:
    return sorted(_POLICIES)
