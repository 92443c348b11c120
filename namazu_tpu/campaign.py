"""Supervised experiment campaigns: ``nmz-tpu campaign <storage> -n N``.

The tool's whole value proposition is the N-run reproduction loop
(BASELINE.md: ``for i in $(seq 1 100); do nmz-tpu run d; done``), but a
bare shell loop has no answer for the exact failure class the tool
exists to hunt: a hung testee parks the loop forever, a crashed
inspector burns the remaining N-i runs on a broken environment, and a
SIGKILL mid-write corrupts the storage every later run trains on. The
campaign runner is that loop with supervision (doc/robustness.md):

* each run is a child ``nmz-tpu run`` in its OWN session (process
  group); a per-run wall-clock deadline kills the entire group on
  expiry, so orphaned testee children cannot outlive their run;
* the NEXT attempt's child is started while the current run is going
  and waits, imported, at a gate before it has read anything (the
  standby, doc/performance.md "Standby run child"): an attempt tells
  it to go instead of paying an interpreter's start, and every way out
  of the campaign ends it;
* per-phase (run/validate/clean) deadlines are forwarded to the child,
  which enforces them the same way (cli/run_cmd.py, utils/cmd.py);
* every completed run is classified — ``experiment`` (an outcome,
  pass or repro), ``timeout`` (a deadline fired), ``infra`` (the
  harness itself failed). N bounds the SLOTS supervised: a slot that
  exhausts its retries keeps its failure class and still consumes one
  of the N (the budget is bounded wall-clock, not bounded outcomes);
  the final summary reports how many slots actually recorded an
  experiment outcome;
* infra-class failures are retried with capped exponential backoff +
  full jitter (utils/retry.py); K consecutive infra-class run slots
  abort the campaign (the environment is broken; burning the budget
  will not unbreak it);
* after every attempt the resumable ``campaign.json`` checkpoint is
  atomically rewritten, so a crashed supervisor resumes where it died;
* between one run's reap and the next run's go the supervisor touches
  only the run dirs the attempt it has just reaped created: one storage
  handle a campaign, the progress document folded run by run, the pgid
  sweep over the attempt's own dirs (doc/performance.md "Between
  runs"), so that stretch is as short a thousand runs into a hunt as
  ten runs into it;
* SIGINT/SIGTERM request a graceful stop (finish the in-flight run,
  checkpoint, exit); a second signal kills the in-flight group and
  aborts immediately.
"""

from __future__ import annotations

import json
import os
import queue
import random
import signal
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from namazu_tpu.cli.run_cmd import EXIT_TIMEOUT, RUN_STANDBY_ENV
from namazu_tpu.obs import metrics as obs_metrics
from namazu_tpu.obs import spans as obs_spans
from namazu_tpu.utils.atomic import atomic_write_json
from namazu_tpu.utils.cmd import (
    CmdFactory,
    kill_process_group,
    sweep_stale_pgid_files,
)
from namazu_tpu.utils import timesource
from namazu_tpu.utils.log import get_logger
from namazu_tpu.utils.retry import backoff_delays

log = get_logger("campaign")

CHECKPOINT_NAME = "campaign.json"
CHECKPOINT_VERSION = 1

#: outcome classes (doc/robustness.md)
CLASS_EXPERIMENT = "experiment"  # the run recorded an outcome (pass/repro)
CLASS_TIMEOUT = "timeout"        # a deadline killed the run's process group
CLASS_INFRA = "infra"            # the harness failed (nonzero exit, signal)
CLASS_INTERRUPTED = "interrupted"  # operator abort mid-run

#: campaign exit statuses (distinct from run_cmd's, which the child uses)
EXIT_OK = 0
EXIT_USAGE = 2
EXIT_INFRA_STOP = 3     # K consecutive infra-class run slots
EXIT_INTERRUPTED = 130  # stopped on SIGINT/SIGTERM (128 + SIGINT)


def _reap(child: subprocess.Popen, deadline: Optional[float]) -> None:
    """Wait for ``child`` for at most ``deadline`` seconds (None: for
    good) and return when it EXITS: the blocking ``waitpid`` on a
    thread of its own, joined for at most the deadline. ``Popen.wait``
    with a timeout is a sleep loop backing off to 50 ms, which put
    every run's wall on that grid (doc/performance.md "Between runs").
    Raises ``TimeoutExpired`` as ``Popen.wait`` does; the reaper then
    ends with the kill that follows."""
    reaper = threading.Thread(target=child.wait, name="campaign-reap",
                              daemon=True)
    reaper.start()
    reaper.join(deadline)
    if reaper.is_alive():
        raise subprocess.TimeoutExpired(child.args, deadline)


@dataclass
class CampaignSpec:
    """Everything that parameterizes one supervised campaign."""

    storage_dir: str
    runs: int
    # supervisor-side wall-clock deadline for one whole `nmz-tpu run`
    # child (covers hangs the per-phase deadlines cannot see: a wedged
    # orchestrator shutdown, a stuck storage flush); 0 = none
    run_wall_deadline_s: float = 0.0
    # per-phase deadlines forwarded to the child (0 = none)
    run_deadline_s: float = 0.0
    validate_deadline_s: float = 0.0
    clean_deadline_s: float = 0.0
    retries: int = 2              # extra attempts per slot on infra/timeout
    backoff_base_s: float = 1.0
    backoff_cap_s: float = 30.0
    max_consecutive_infra: int = 3
    python: str = sys.executable
    seed: Optional[int] = None    # jitter RNG seed (tests)
    extra_run_args: List[str] = field(default_factory=list)
    # forward --virtual-clock to every run child (doc/performance.md
    # "Virtual clock"): each child fast-forwards its scheduled delays,
    # so campaign throughput decouples from the scenario's idle time.
    # The supervisor's own deadlines stay wall — they bound CHILD
    # processes whose hangs are real
    virtual_clock: bool = False
    # fleet telemetry collector (doc/observability.md "Fleet
    # telemetry"): "auto" = <storage>/telemetry.sock (with a /tmp
    # fallback past the AF_UNIX path limit), "" = off, else an explicit
    # socket path. The supervisor hosts the fleet aggregator on it and
    # exports NMZ_TELEMETRY_URL so every run child (and, through the
    # children's federation hop, their inspectors) pushes here —
    # ``tools top --url uds://<path>`` shows the whole campaign.
    telemetry_collector: str = "auto"
    # tenancy serve mode (doc/tenancy.md): when set, run slots LEASE
    # namespaced runs on this shared orchestrator (http://... or
    # uds://...) instead of forking `nmz-tpu run` children — the
    # supervisor drives each slot's loopback workload through the wire
    # under its leased namespace, renews the lease at TTL/3, and
    # records the released trace into the local storage. A slot that
    # stops renewing (crash) is reclaimed server-side on TTL expiry.
    serve_url: str = ""
    serve_ttl_s: float = 15.0
    serve_events: int = 200
    serve_entities: int = 2
    serve_policy: str = "random"
    serve_policy_param: Dict[str, Any] = field(default_factory=dict)
    # extra environment exported to every run child — the calibration
    # plane's knob transport (NMZ_CALIB_<NAME>, namazu_tpu/calibrate):
    # a probe's candidate knob values ride the environment into the
    # experiment scripts
    extra_env: Dict[str, str] = field(default_factory=dict)
    # called after every finished slot with (slot, progress-or-None);
    # returning True stops the campaign gracefully (stopped_reason
    # "callback", exit 0) — how the calibration harness early-stops a
    # probe the moment its band SPRT concludes
    on_slot: Optional[Callable[[Dict[str, Any],
                                Optional[Dict[str, Any]]], bool]] = None


class Campaign:
    """One supervised campaign over one storage dir."""

    def __init__(self, spec: CampaignSpec):
        self.spec = spec
        self.state: Dict[str, Any] = {}
        self._rng = random.Random(spec.seed)
        self._stop_requested = threading.Event()
        self._abort = threading.Event()
        self._child: Optional[subprocess.Popen] = None
        self._child_lock = threading.Lock()
        # the next attempt's run child, waiting at its gate, and the
        # `_child_env()` it was started with (fork mode; _start_standby)
        self._standby: Optional[subprocess.Popen] = None
        self._standby_env: Dict[str, str] = {}
        self._telemetry_server = None
        self._telemetry_path = ""
        # run phases (doc/observability.md "Run phases"): when the last
        # attempt's child was reaped (monotonic; the start of the next
        # attempt's `respawn`)
        self._last_reap: Optional[float] = None
        # the campaign's one storage handle (doc/performance.md "Between
        # runs"), how many runs it had allocated when it was last caught
        # up (a run from there on is the next attempt's; None = not
        # known), and the progress rows kept from slot to slot
        self._storage = None
        self._allocated: Optional[int] = None
        self._fold = None

    # -- checkpoint ------------------------------------------------------

    @property
    def checkpoint_path(self) -> str:
        return os.path.join(self.spec.storage_dir, CHECKPOINT_NAME)

    def _fresh_state(self) -> Dict[str, Any]:
        return {
            "version": CHECKPOINT_VERSION,
            "requested_runs": self.spec.runs,
            "slots": [],            # one entry per finished run slot
            "consecutive_infra": 0,
            "stopped_reason": None,  # None while running; "done"/"infra"/
                                     # "interrupted" when finished
            "started_at": time.time(),
            "updated_at": time.time(),
        }

    def _load_or_init_state(self, resume: bool) -> None:
        path = self.checkpoint_path
        if resume and os.path.exists(path):
            try:
                with open(path) as f:
                    state = json.load(f)
            except (OSError, ValueError) as e:
                raise CampaignError(
                    f"unreadable checkpoint {path}: {e}; remove it or "
                    "rerun with --no-resume") from None
            if int(state.get("version", -1)) != CHECKPOINT_VERSION:
                raise CampaignError(
                    f"checkpoint {path} has version "
                    f"{state.get('version')!r}, this build writes "
                    f"{CHECKPOINT_VERSION}; rerun with --no-resume")
            # a resumed campaign may raise or lower the target; the
            # completed prefix stands either way
            state["requested_runs"] = self.spec.runs
            state["stopped_reason"] = None
            # the operator re-running IS the claim the environment is
            # fixed: carrying the counter over would re-stop on infra
            # before attempting a single run
            state["consecutive_infra"] = 0
            self.state = state
            log.info("resuming campaign from %s: %d slot(s) already done",
                     path, len(state["slots"]))
        else:
            self.state = self._fresh_state()
        self._checkpoint()

    def _checkpoint(self) -> None:
        self.state["updated_at"] = time.time()
        atomic_write_json(self.checkpoint_path, self.state, indent=2,
                          sort_keys=True)

    # -- signals ---------------------------------------------------------

    def _install_signal_handlers(self):
        if threading.current_thread() is not threading.main_thread():
            return None
        previous = {}

        def handler(signum, frame):
            if self._stop_requested.is_set():
                # second signal: the operator means it — kill the
                # in-flight group and abort
                log.warning("second signal; aborting the in-flight run")
                self._abort.set()
                with self._child_lock:
                    child = self._child
                if child is not None:
                    kill_process_group(child)
            else:
                log.warning("stop requested; finishing the in-flight run "
                            "then checkpointing (signal again to abort)")
                self._stop_requested.set()

        for signum in (signal.SIGINT, signal.SIGTERM):
            previous[signum] = signal.signal(signum, handler)
        return previous

    @staticmethod
    def _restore_signal_handlers(previous) -> None:
        if previous:
            for signum, old in previous.items():
                signal.signal(signum, old)

    # -- one attempt -----------------------------------------------------

    def _run_argv(self) -> List[str]:
        spec = self.spec
        argv = [spec.python, "-m", "namazu_tpu.cli", "run",
                spec.storage_dir]
        for flag, value in (("--run-deadline", spec.run_deadline_s),
                            ("--validate-deadline", spec.validate_deadline_s),
                            ("--clean-deadline", spec.clean_deadline_s)):
            if value and value > 0:
                argv += [flag, str(value)]
        if spec.virtual_clock:
            argv.append("--virtual-clock")
        argv += spec.extra_run_args
        return argv

    def _child_env(self, spawned: Optional[float] = None,
                   respawn: Optional[float] = None) -> Dict[str, str]:
        # the child must be able to import the framework even when it is
        # not installed site-wide; CmdFactory.env() owns that logic
        env = CmdFactory(extra_env=self.spec.extra_env).env()
        if spawned is not None:
            # the origin of the child's run phases: its `boot` is from
            # this stamp to its own first (obs/spans.py run_begin), its
            # `respawn` what the supervisor took to get here
            env[obs_spans.RUN_SPAWNED_ENV] = repr(spawned)
            if respawn is not None:
                env[obs_spans.RUN_RESPAWN_ENV] = repr(respawn)
        if self._telemetry_path:
            # run children push their metrics (and forward their
            # inspectors') to the supervisor's collector — the one
            # campaign-wide fleet view (doc/observability.md)
            env["NMZ_TELEMETRY_URL"] = f"uds://{self._telemetry_path}"
        return env

    # -- fleet telemetry --------------------------------------------------

    def _collector_path(self) -> str:
        raw = self.spec.telemetry_collector
        if not raw:
            return ""
        if raw != "auto":
            return os.path.abspath(raw)
        path = os.path.abspath(os.path.join(self.spec.storage_dir,
                                            "telemetry.sock"))
        if len(path) >= 100:
            # sun_path caps AF_UNIX socket paths (~108 bytes); a deep
            # storage dir falls back to a pid-scoped /tmp name
            path = os.path.join("/tmp", f"nmz-telemetry-{os.getpid()}.sock")
        return path

    def _start_telemetry(self) -> None:
        from namazu_tpu import obs
        from namazu_tpu.obs import federation
        from namazu_tpu.utils.config import Config

        # honor the storage config's kill switch and SLO declarations
        # BEFORE deciding to host a collector: `telemetry_enabled =
        # false` must disable the whole plane for the supervisor too,
        # and declared [[slo]] objectives must reach the aggregator
        # this process is about to host (same config.toml-over-
        # config.json precedence as `run`)
        cfg_path = os.path.join(self.spec.storage_dir, "config.toml")
        if not os.path.exists(cfg_path):
            cfg_path = os.path.join(self.spec.storage_dir, "config.json")
        if os.path.exists(cfg_path):
            try:
                obs.configure_from_config(Config.from_file(cfg_path))
            except Exception:
                log.warning("could not apply the storage config's "
                            "telemetry keys; using process defaults",
                            exc_info=True)
        path = self._collector_path()
        if not path or not federation.enabled():
            return
        server = federation.TelemetryServer(path)
        try:
            server.start()
        except (OSError, RuntimeError) as e:
            # a dead collector must never gate the campaign itself —
            # the children simply stay local-only (the relay's own
            # degradation contract)
            log.warning("fleet telemetry collector on %s unavailable "
                        "(%s); campaign runs without the fleet view",
                        path, e)
            return
        self._telemetry_server = server
        self._telemetry_path = path
        # the supervisor is a producer too (campaign slot counters,
        # collector occupancy): its registry merges straight into the
        # local aggregator it hosts
        federation.ensure_self_relay("campaign")
        # continuous profiling: the supervisor samples itself too, so
        # `tools top` shows where campaign overhead goes between slots
        from namazu_tpu.obs import profiling

        profiling.ensure_profiler("campaign")
        log.info("fleet view: nmz-tpu tools top --url uds://%s", path)

    def _stop_telemetry(self) -> None:
        server, self._telemetry_server = self._telemetry_server, None
        self._telemetry_path = ""
        if server is not None:
            server.shutdown()

    # -- the standby run child -------------------------------------------

    def _start_standby(self) -> None:
        """Start the NEXT attempt's run child now, while this attempt's
        run is going: the same ``nmz-tpu run`` in a session of its own,
        told (``RUN_STANDBY_ENV``) to wait at its gate — a blocking
        read of the pipe held here — before it reads anything, so its
        interpreter start and imports overlap the testee. It cannot
        outlive this process: however the supervisor dies, the pipe's
        other end sees EOF and the child exits, nothing touched."""
        env = self._child_env()
        try:
            self._standby = subprocess.Popen(
                self._run_argv(), env={**env, RUN_STANDBY_ENV: "1"},
                stdin=subprocess.PIPE, start_new_session=True)
        except OSError as e:
            log.warning("could not start a standby run child (%s); the "
                        "next attempt starts cold", e)
            return
        self._standby_env = env

    def _take_standby(self, spawned: Optional[float],
                      respawn: Optional[float] = None
                      ) -> Optional[subprocess.Popen]:
        """Tell the standby to go and hand it over as this attempt's
        child; None when there is none or it died while waiting (the
        attempt then starts cold). The go line carries what ``Popen``
        would have put into this attempt's environment: the spawn
        stamp, the ``respawn`` that ended at it, and whatever of
        ``_child_env()`` moved since the standby was started (null = no
        longer set)."""
        child, self._standby = self._standby, None
        if child is None:
            return None
        was, env = self._standby_env, self._child_env()
        moved: Dict[str, Optional[str]] = {
            k: v for k, v in env.items() if was.get(k) != v}
        moved.update({k: None for k in was if k not in env})
        go = json.dumps({"spawned": spawned, "respawn": respawn,
                         "env": moved}) + "\n"
        if child.poll() is None:
            try:
                child.stdin.write(go.encode())
                # closed at once: the run's own children read EOF there
                child.stdin.close()
                return child
            except OSError:
                pass
        log.warning("the standby run child died while waiting (exit %s); "
                    "starting this attempt cold", child.poll())
        self._end_standby(child)
        return None

    def _end_standby(self, child: Optional[subprocess.Popen] = None) -> None:
        """End a standby no attempt will take (every way out of the
        campaign comes through here): EOF at its gate is "never
        wanted" and it exits by itself; its whole session is killed if
        it has not within a second. Reaped either way."""
        if child is None:
            child, self._standby = self._standby, None
        if child is None:
            return
        try:
            child.stdin.close()
        except OSError:
            pass
        try:
            child.wait(timeout=1.0)
        except subprocess.TimeoutExpired:
            kill_process_group(child)

    def _one_attempt(self, slot_index: int = 0) -> Dict[str, Any]:
        """One attempt: fork mode runs an ``nmz-tpu run`` child in its
        own session under the wall deadline — the standby told to go,
        else a cold spawn — and starts the next attempt's standby;
        serve mode leases a run slot on the shared orchestrator instead
        (doc/tenancy.md)."""
        if self.spec.serve_url:
            return self._one_serve_attempt(slot_index)
        spec = self.spec
        observed = obs_metrics.enabled()
        # the moment this run is wanted: the origin of its phases, of
        # `wall_s` and of the wall deadline, standby or not; and the
        # end of its `respawn`, which the run is told with the stamp
        t0 = time.monotonic()
        respawn = (t0 - self._last_reap
                   if observed and self._last_reap is not None else None)
        child = self._take_standby(t0 if observed else None, respawn)
        warm = child is not None
        if child is None:
            child = subprocess.Popen(
                self._run_argv(),
                env=self._child_env(t0 if observed else None, respawn),
                start_new_session=True)
        with self._child_lock:
            self._child = child
        timed_out = False
        try:
            deadline = (spec.run_wall_deadline_s
                        if spec.run_wall_deadline_s > 0 else None)
            try:
                if not self._stop_requested.is_set():
                    self._start_standby()
                _reap(child, deadline)
            except subprocess.TimeoutExpired:
                timed_out = True
                log.warning("run exceeded the %.1fs wall deadline; "
                            "killing its process group", deadline)
                kill_process_group(child)
            except BaseException:
                kill_process_group(child)
                raise
        finally:
            with self._child_lock:
                self._child = None
            # a hard-killed child (SIGKILL skips its cleanup) can leave
            # its run script's process group orphaned in its own
            # session, outside the group we just killed — the pgid
            # breadcrumb run_cmd wrote points the sweep at it
            # (doc/robustness.md "Chaos plane"); it lies in a dir this
            # attempt created, if anywhere
            new_runs = self._catch_up()
            self._sweep_pgid_files(new_runs)
        wall_s = time.monotonic() - t0
        rc = child.returncode
        if timed_out:
            cls = CLASS_TIMEOUT
        elif self._abort.is_set():
            cls = CLASS_INTERRUPTED
        elif rc == 0:
            cls = CLASS_EXPERIMENT
        elif rc == EXIT_TIMEOUT:
            cls = CLASS_TIMEOUT  # a child-enforced phase deadline fired
        else:
            cls = CLASS_INFRA  # nonzero exit or signal death (rc < 0)
        attempt = {"class": cls, "exit_status": rc,
                   "wall_s": round(wall_s, 3),
                   "wall_deadline_hit": timed_out,
                   "start": "standby" if warm else "cold"}
        if observed:
            phases = self._attempt_phases(t0, wall_s, respawn, new_runs)
            if phases:
                attempt["phases"] = phases
        return attempt

    # -- the campaign's storage handle (doc/performance.md "Between runs")

    def _open_storage(self):
        """The campaign's one storage handle; opened — ``init()``'s
        walk from the storage's persisted watermark — where there is
        none: at the campaign's start or resume, and after something
        failed on it."""
        if self._storage is None:
            from namazu_tpu.storage import load_storage

            self._storage = load_storage(self.spec.storage_dir)
        return self._storage

    def _close_storage(self) -> None:
        storage, self._storage = self._storage, None
        self._allocated = None
        if storage is not None:
            try:
                storage.close()
            except Exception:
                log.warning("storage close failed", exc_info=True)

    def _catch_up(self) -> Optional[range]:
        """Catch the handle up with what an attempt just allocated
        (``refresh()``: ``storage.json`` re-read, ``init()``'s
        quarantine applied to the dirs not yet seen settled: the new
        ones) and return the new runs' indices: the dirs the attempt
        created. None where that is
        not known (no handle until now, or the storage cannot be read:
        anything may be new). Best-effort like the progress
        publication: a storage that cannot be read costs the attempt
        its child rows and the sweep its aim, never the campaign its
        loop."""
        try:
            storage = self._open_storage()
            before = self._allocated
            self._allocated = storage.refresh()
        except Exception:
            log.warning("could not catch the storage handle up; "
                        "continuing", exc_info=True)
            self._close_storage()
            return None
        if before is None:
            return None
        return range(min(before, self._allocated), self._allocated)

    def _sweep_pgid_files(self, runs: Optional[range]) -> None:
        """Sweep the breadcrumbs of ``runs``' dirs; of every entry of
        the storage where ``runs`` is None (the campaign's start; an
        attempt whose dirs are not known)."""
        storage_dir = self.spec.storage_dir
        if runs is not None and self._storage is not None:
            dirs = [self._storage.run_dir(i) for i in runs]
        else:
            try:
                dirs = [os.path.join(storage_dir, name)
                        for name in sorted(os.listdir(storage_dir))]
            except OSError:
                return
        sweep_stale_pgid_files(dirs)

    def _stored_phases(self, runs: Optional[range]) -> List[list]:
        """``metadata["phases"]`` of the run an attempt stored (the
        rows the run child stored of itself, cli/run_cmd.py), looked
        for in the dirs that attempt created; ``[]`` where it stored
        none."""
        for i in reversed(runs or ()):
            try:
                rows = self._storage.get_metadata(i).get("phases")
            except Exception:
                continue  # allocated, no result: killed or aborted
            return [list(r) for r in rows or []]
        return []

    def _attempt_phases(self, t0: float, wall_s: float,
                        respawn: Optional[float],
                        new_runs: Optional[range]) -> List[list]:
        """One attempt's cycle as ``[name, parent, start_s, seconds]``
        rows counted from the spawn stamp ``t0``: the rows the child
        stored with its run, and around them what only the supervisor
        sees — ``respawn``, from the previous attempt's reap to this
        spawn (so it starts before 0; the run was told it and stored a
        row of its own, which is left out here: this one stands for
        both), and ``teardown``, from the end of the child's last row
        to the reap (the ``result.json`` write, the storage's close,
        the exit hooks, the interpreter's exit). An attempt whose child
        stored no run has neither child rows nor ``teardown``. The two
        are observed here, into the supervisor's own
        ``nmz_run_phase_seconds``; the child observed its own."""
        before, after = [], []
        if respawn is not None:
            before.append([obs_spans.RESPAWN_PHASE, None,
                           round(-respawn, 6), round(respawn, 6)])
        self._last_reap = t0 + wall_s
        rows = [r for r in self._stored_phases(new_runs)
                if not r or r[0] != obs_spans.RESPAWN_PHASE]
        try:
            end = max(r[2] + r[3] for r in rows if r[1] is None)
        except (TypeError, ValueError, IndexError):
            end = None  # no child rows, or rows no run child wrote
        if end is not None:
            after.append(["teardown", None, round(end, 6),
                          round(wall_s - end, 6)])
        obs_spans.run_phases_observed(before + after)
        return before + rows + after

    # -- tenancy serve mode (doc/tenancy.md) ------------------------------

    def _one_serve_attempt(self, slot_index: int) -> Dict[str, Any]:
        from namazu_tpu.tenancy.client import TenancyWireError

        t0 = time.monotonic()
        crashed = False
        try:
            crashed = self._drive_serve_slot(slot_index)
        except (TenancyWireError, OSError, RuntimeError, ValueError) as e:
            log.warning("serve slot %d failed: %s", slot_index, e)
            return {"class": CLASS_INFRA, "exit_status": None,
                    "wall_s": round(time.monotonic() - t0, 3),
                    "wall_deadline_hit": False, "error": str(e)}
        wall_s = time.monotonic() - t0
        # the slot's run is stored in this storage and finished: the
        # progress fold reads it like a run child's
        self._catch_up()
        if self._abort.is_set():
            cls = CLASS_INTERRUPTED
        elif crashed:
            # the tenancy.slot.crash chaos seam fired: this tenant died
            # mid-run without releasing; the orchestrator reclaims its
            # namespace on TTL expiry — classified infra so the slot
            # retries like any crashed run child
            cls = CLASS_INFRA
        else:
            cls = CLASS_EXPERIMENT
        return {"class": cls, "exit_status": 0 if cls == CLASS_EXPERIMENT
                else None,
                "wall_s": round(wall_s, 3), "wall_deadline_hit": False}

    def _drive_serve_slot(self, slot_index: int) -> bool:
        """Lease a namespace, drive the slot's loopback workload through
        the shared orchestrator, release, record the returned trace.
        Returns True when the ``tenancy.slot.crash`` seam killed the
        tenant mid-run (lease left to expire server-side)."""
        import uuid as _uuid

        from namazu_tpu.storage import load_storage
        from namazu_tpu.tenancy.client import TenancyClient
        from namazu_tpu.utils.trace import SingleTrace

        spec = self.spec
        run_name = (f"{os.path.basename(os.path.abspath(spec.storage_dir))}"
                    f"-s{slot_index}-{_uuid.uuid4().hex[:6]}")
        client = TenancyClient(spec.serve_url)
        # serve slots run in-process: their durations and drive
        # deadlines read the process TimeSource, so a virtual-clock
        # supervisor fast-forwarding its own waits cannot time out a
        # healthy (parked) workload (doc/performance.md "Virtual clock")
        t0 = timesource.get().now()
        lease = self._serve_lease(client, run_name)
        lease_id = lease["lease_id"]
        # a placement service's lease says WHERE the workload runs
        # (host_url); a plain orchestrator's lease doesn't, and the
        # serve url is the workload url as before
        workload = {"url": lease.get("host_url") or spec.serve_url}
        moved = threading.Event()
        renew_stop = threading.Event()

        def renew_loop() -> None:
            interval = max(spec.serve_ttl_s / 3.0, 0.05)
            while not renew_stop.wait(interval):
                try:
                    doc = client.renew(lease_id)
                except Exception:
                    return  # lease gone (released, expired, or crash)
                new_url = str(doc.get("host_url") or "")
                if new_url and new_url != workload["url"]:
                    # the pool migrated this run (host drain/death);
                    # re-target the workload at its new home
                    log.warning("run %s migrated to %s; re-targeting "
                                "workload", run_name, new_url)
                    workload["url"] = new_url
                    moved.set()

        renewer = threading.Thread(target=renew_loop,
                                   name=f"lease-renew-s{slot_index}",
                                   daemon=True)
        renewer.start()
        try:
            crashed = self._drive_serve_workload(run_name, workload,
                                                 moved)
            if crashed:
                # die like a SIGKILLed tenant: no release — stop
                # renewing and walk away; TTL expiry reclaims the
                # namespace server-side (chaos: tenancy.slot.crash)
                return True
            released = client.release(lease_id)
        finally:
            renew_stop.set()
            renewer.join(timeout=2)
            client.close()
        storage = load_storage(spec.storage_dir)
        try:
            storage.create_new_working_dir()
            storage.record_new_trace(
                SingleTrace.from_jsonable(released.get("trace") or []))
            # serve slots run the wire workload, not a validate script:
            # the outcome is "completed" (successful = no repro claim)
            storage.record_result(True, timesource.get().now() - t0)
        finally:
            storage.close()
        log.info("serve slot %d: run %s released (%s event(s), %s "
                 "action(s) traced)", slot_index, run_name,
                 released.get("events"), released.get("dispatched"))
        return False

    def _serve_lease(self, client, run_name: str) -> Dict[str, Any]:
        """Lease the slot's namespace, honoring admission pushback: a
        refusal carrying Retry-After (the pool's 429 while its SLO
        burn is hot, or a single host's ingress gate) is a deferral,
        not a failure — wait as told and re-knock, bounded. Refusals
        without a Retry-After propagate to the slot's normal
        infra-retry path."""
        from namazu_tpu.tenancy.client import TenancyWireError

        spec = self.spec
        deferrals = 8
        while True:
            try:
                return client.lease(
                    run_name, ttl_s=spec.serve_ttl_s,
                    policy=spec.serve_policy or "random",
                    policy_param=dict(spec.serve_policy_param) or None)
            except TenancyWireError as e:
                hint = getattr(e, "retry_after", None)
                if hint is None or deferrals <= 0 \
                        or self._abort.is_set():
                    raise
                deferrals -= 1
                delay = min(max(float(hint), 0.0), 5.0)
                log.info("lease for %s deferred by admission control; "
                         "retrying in %.2fs (%s)", run_name, delay, e)
                if self._abort.wait(delay):
                    raise

    def _drive_serve_workload(self, run_name: str,
                              workload: Optional[Dict[str, str]] = None,
                              moved: Optional[threading.Event] = None,
                              ) -> bool:
        """The slot's loopback workload: post deferred events under the
        leased namespace, wait for every answering action. Returns True
        when the ``tenancy.slot.crash`` seam fired mid-drive.

        ``workload["url"]`` is the CURRENT workload target — the renew
        thread rewrites it and sets ``moved`` when the placement plane
        migrates the run to another host. On a move the transceivers
        are rebuilt against the new home; in-flight events whose
        actions died with the old host are NOT re-awaited — they were
        parked in the run's journal, recovered on the new host, and
        flush into the release trace (the exactly-once contract), so
        the slot only waits for answers that can still arrive."""
        from namazu_tpu import chaos
        from namazu_tpu.signal import PacketEvent

        spec = self.spec
        if workload is None:
            workload = {"url": spec.serve_url}
        entities = [f"n{i}" for i in range(max(1, spec.serve_entities))]

        def build(url):
            if url.startswith("uds://"):
                from namazu_tpu.inspector.uds_transceiver import (
                    UdsTransceiver,
                )

                built = {e: UdsTransceiver(e, url[len("uds://"):],
                                           run_ns=run_name)
                         for e in entities}
            else:
                from namazu_tpu.inspector.rest_transceiver import (
                    RestTransceiver,
                )

                built = {e: RestTransceiver(e, url, use_batch=True,
                                            flush_window=0.01,
                                            run_ns=run_name)
                         for e in entities}
            for tx in built.values():
                tx.start()
            return built

        def teardown(built):
            for tx in built.values():
                try:
                    tx.shutdown()
                except Exception:  # pragma: no cover - defensive
                    pass

        txs = build(workload["url"])
        crashed = False
        chans = []

        def retarget():
            nonlocal txs, chans
            teardown(txs)
            txs = build(workload["url"])
            # answers already delivered stay awaitable; the rest are
            # journal-recovered server-side and traced at release
            chans = [ch for ch in chans if not ch.empty()]

        def ride_out_migration(exc):
            """The wire died mid-send. Against a placement pool that is
            usually a host DYING under us — the monitor needs one
            detection window (dead_after + a renew tick) before the
            renew thread re-targets the workload, so wait that out
            rather than failing a slot the pool is about to save. A
            plain orchestrator (no mover) or a genuine outage (the
            renewer dies with the lease, ``moved`` never fires) still
            raises into the slot's infra-retry path."""
            if moved is None:
                raise exc
            deadline = timesource.get().now() + max(
                2.0 * spec.serve_ttl_s, 10.0)
            while not moved.wait(0.25):
                if self._abort.is_set() \
                        or timesource.get().now() >= deadline:
                    raise exc
            moved.clear()
            retarget()

        try:
            for i in range(max(1, spec.serve_events)):
                if i % 64 == 0 \
                        and chaos.decide("tenancy.slot.crash") is not None:
                    log.warning("chaos: tenancy.slot.crash fired; "
                                "abandoning run %s mid-drive", run_name)
                    crashed = True
                    break
                if self._abort.is_set():
                    break
                if moved is not None and moved.is_set():
                    moved.clear()
                    retarget()
                e = entities[i % len(entities)]
                ev = PacketEvent.create(e, e, "peer", hint=f"h{i % 16}")
                try:
                    chans.append(txs[e].send_event(ev))
                except (OSError, RuntimeError) as exc:
                    ride_out_migration(exc)
                    chans.append(txs[e].send_event(ev))
            if not crashed:
                deadline = timesource.get().now() + 60.0
                while chans:
                    if moved is not None and moved.is_set():
                        moved.clear()
                        retarget()
                        continue
                    try:
                        chans[0].get(timeout=0.5)
                        chans.pop(0)
                    except queue.Empty:
                        if timesource.get().now() >= deadline:
                            raise RuntimeError(
                                f"run {run_name}: workload actions "
                                "still outstanding after 60s")
        finally:
            teardown(txs)
        return crashed

    # -- the supervised loop ---------------------------------------------

    def run(self, resume: bool = True) -> int:
        spec = self.spec
        if spec.runs < 1:
            raise CampaignError(f"runs must be >= 1, got {spec.runs}")
        if not os.path.exists(os.path.join(spec.storage_dir,
                                           "config.json")):
            raise CampaignError(
                f"{spec.storage_dir} is not an initialized storage "
                "(no config.json; run `init` first)")
        self._load_or_init_state(resume)
        previous_handlers = self._install_signal_handlers()
        self._start_telemetry()
        try:
            # the one place a campaign, fresh or resumed, visits every
            # stored run, before its first run is wanted: the handle's
            # `init()`, the sweep for a breadcrumb that a supervisor
            # killed earlier may have left in any dir, and the rows the
            # first progress document will rest on
            self._catch_up()
            self._sweep_pgid_files(None)
            try:
                self._fold_new_runs()
            except Exception:
                log.warning("could not read the stored history; the "
                            "first slot will", exc_info=True)
                self._fold = None
            return self._loop()
        finally:
            self._end_standby()
            self._stop_telemetry()
            self._restore_signal_handlers(previous_handlers)
            self._close_storage()
            self._checkpoint()

    def _finish(self, reason: str, status: int) -> int:
        self.state["stopped_reason"] = reason
        # the document a campaign leaves behind rests on no fold
        self._publish_progress(from_scratch=True)
        self._checkpoint()
        counts: Dict[str, int] = {}
        for slot in self.state["slots"]:
            counts[slot["class"]] = counts.get(slot["class"], 0) + 1
        log.info("campaign finished (%s): %d/%d slot(s) done, classes %s",
                 reason, len(self.state["slots"]),
                 self.state["requested_runs"], counts or "{}")
        return status

    def _loop(self) -> int:
        spec = self.spec
        state = self.state
        while len(state["slots"]) < state["requested_runs"]:
            if self._abort.is_set():
                return self._finish("interrupted", EXIT_INTERRUPTED)
            if self._stop_requested.is_set():
                return self._finish("interrupted", EXIT_INTERRUPTED)
            if (spec.max_consecutive_infra > 0
                    and state["consecutive_infra"]
                    >= spec.max_consecutive_infra):
                log.error(
                    "%d consecutive infra-class run slot(s); the "
                    "environment is broken — stopping the campaign",
                    state["consecutive_infra"])
                return self._finish("infra", EXIT_INFRA_STOP)
            slot_index = len(state["slots"])
            slot = self._run_slot(slot_index)
            state["slots"].append(slot)
            obs_spans.campaign_slot(slot["class"])
            if slot["class"] == CLASS_EXPERIMENT:
                state["consecutive_infra"] = 0
            elif slot["class"] == CLASS_INTERRUPTED:
                self._checkpoint()
                return self._finish("interrupted", EXIT_INTERRUPTED)
            else:
                state["consecutive_infra"] += 1
            self._checkpoint()
            progress = self._publish_progress()
            if spec.on_slot is not None and spec.on_slot(slot, progress):
                # the caller has seen enough (calibration probe SPRT
                # concluded, A/B budget reached): graceful stop, the
                # completed prefix stands
                return self._finish("callback", EXIT_OK)
        if (spec.max_consecutive_infra > 0
                and state["consecutive_infra"]
                >= spec.max_consecutive_infra):
            return self._finish("infra", EXIT_INFRA_STOP)
        return self._finish("done", EXIT_OK)

    def _run_slot(self, slot_index: int) -> Dict[str, Any]:
        """One run slot: attempt + bounded infra/timeout retries."""
        spec = self.spec
        attempts: List[Dict[str, Any]] = []
        delays = backoff_delays(max(0, spec.retries),
                                base=spec.backoff_base_s,
                                cap=spec.backoff_cap_s, rng=self._rng)
        while True:
            log.info("slot %d attempt %d", slot_index, len(attempts) + 1)
            attempt = self._one_attempt(slot_index)
            attempts.append(attempt)
            slot = {"slot": slot_index, "class": attempt["class"],
                    "attempts": attempts}
            if attempt["class"] == CLASS_EXPERIMENT:
                return slot
            if (attempt["class"] == CLASS_INTERRUPTED
                    or self._abort.is_set()):
                slot["class"] = CLASS_INTERRUPTED
                return slot
            if self._stop_requested.is_set():
                return slot
            # infra/timeout: retry with backoff while the budget lasts
            try:
                delay = next(delays)
            except StopIteration:
                return slot
            # persist the failed attempt before sleeping: a supervisor
            # crash during the backoff must not forget it
            self._checkpoint_partial(slot)
            log.warning("slot %d attempt %d was %s (exit %s); retrying "
                        "in %.2fs", slot_index, len(attempts),
                        attempt["class"], attempt["exit_status"], delay)
            if self._stop_requested.wait(delay):
                return slot

    def _fold_new_runs(self, from_scratch: bool = False) -> None:
        """Bring the progress rows kept (analytics.ProgressFold) up to
        date with the storage: read the runs allocated since the last
        time, from the campaign's own handle. The whole history is read
        — a fresh handle, every run — where nothing was read yet (the
        campaign's start or resume), where the storage holds fewer runs
        than were folded, and ``from_scratch``."""
        from namazu_tpu.obs import analytics

        if from_scratch:
            self._close_storage()
            self._fold = None
        if self._allocated is None:
            self._catch_up()
        if self._fold is not None \
                and self._fold.contradicted_by(self._allocated):
            self._close_storage()
            self._fold = None
            self._catch_up()
        if self._fold is None:
            self._fold = analytics.ProgressFold()
        self._fold.fold(self._open_storage(), self._allocated)

    def _publish_progress(self, from_scratch: bool = False
                          ) -> Optional[Dict[str, Any]]:
        """The live progress surface's supervisor face: after every
        slot, bring the storage's sequential statistics
        (obs/analytics.progress_stats) up to date, publish the
        nmz_campaign_* gauges the fleet federates, and stash the
        document in the in-memory state for the on_slot callback.

        Up to date, not recomputed: the rows of the runs below the
        watermark are kept and only the runs the slot's attempts
        created are read (``_fold_new_runs``) — the same arithmetic
        over the same rows, so the document is byte-for-byte what
        ``progress_stats`` over a fresh ``load_storage`` gives. A
        document for which the whole history was read counts as
        ``path="walk"`` of ``nmz_campaign_progress_folds_total``: a
        campaign's first (read where the campaign started), one after
        the storage contradicted the watermark, and ``from_scratch``
        (the document ``_finish`` leaves in ``campaign.json``); every
        other as ``path="fold"``. The checkpoint half of the inputs is
        ``self.state``, which ``_checkpoint()`` has just written.
        Best-effort — a mid-write storage or a stats bug degrades to
        None, never kills the campaign loop."""
        try:
            from namazu_tpu.obs import analytics

            self._fold_new_runs(from_scratch)
            progress = self._fold.document(
                calibration=analytics._load_doc(self.spec.storage_dir,
                                                "calibration.json"),
                checkpoint=self.state)
        except Exception:
            log.warning("progress publication failed; continuing",
                        exc_info=True)
            self._fold = None
            return None
        path = self._fold.take_path()
        obs_spans.campaign_progress_fold(path)
        if not from_scratch and self.state["slots"]:
            # beside the attempts' `start`: how this slot's document
            # was made (on disk with the next checkpoint)
            self.state["slots"][-1]["progress_path"] = path
        obs_spans.campaign_progress(
            rate=progress["repro_rate"],
            ci=progress["rate_ci95"],
            repros_per_hour=progress["repros_per_hour"],
            eta_next_repro_s=progress["eta_next_repro_s"],
            runs_to_ci=(progress["runs_to_ci_width"] or {}).get(
                "more_runs"),
            in_band=(1 if progress["band_verdict"] == "in_band"
                     else 0 if progress["band_verdict"] in
                     ("below", "above") else None),
            repros_per_hour_virtual=progress.get(
                "repros_per_hour_virtual"),
        )
        self.state["progress"] = progress
        return progress

    def _checkpoint_partial(self, slot: Dict[str, Any]) -> None:
        """Checkpoint with the in-progress slot appended provisionally
        (it is rewritten when the slot finishes for real)."""
        snapshot = dict(self.state)
        snapshot["slots"] = self.state["slots"] + [
            dict(slot, in_progress=True)]
        snapshot["updated_at"] = time.time()
        atomic_write_json(self.checkpoint_path, snapshot, indent=2,
                          sort_keys=True)


class CampaignError(Exception):
    pass


def load_checkpoint(storage_dir: str) -> Optional[Dict[str, Any]]:
    """Read a storage's campaign checkpoint (None when absent)."""
    path = os.path.join(storage_dir, CHECKPOINT_NAME)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def summarize(state: Dict[str, Any]) -> Dict[str, Any]:
    """Roll a checkpoint up into the counts dashboards/CI gate on."""
    slots = [s for s in state.get("slots", [])
             if not s.get("in_progress")]
    by_class: Dict[str, int] = {}
    unclassified = 0
    for s in slots:
        cls = s.get("class")
        if cls not in (CLASS_EXPERIMENT, CLASS_TIMEOUT, CLASS_INFRA,
                       CLASS_INTERRUPTED):
            unclassified += 1
        else:
            by_class[cls] = by_class.get(cls, 0) + 1
    return {
        "requested_runs": state.get("requested_runs", 0),
        "completed_slots": len(slots),
        "experiment": by_class.get(CLASS_EXPERIMENT, 0),
        "timeout": by_class.get(CLASS_TIMEOUT, 0),
        "infra": by_class.get(CLASS_INFRA, 0),
        "interrupted": by_class.get(CLASS_INTERRUPTED, 0),
        "unclassified": unclassified,
        "stopped_reason": state.get("stopped_reason"),
    }
