"""``nmz-tpu run <storage_dir>`` — run one experiment.

Parity: /root/reference/nmz/cli/run.go:171-248 (call stack SURVEY.md 3.1):
allocate a run dir, start the orchestrator, run the experiment's ``run``
script (which boots the testee + inspectors), shut down, judge with the
``validate`` script (exit status = oracle), record trace + result, clean.

Driven N times by the user (``for i in $(seq 1 100); do nmz-tpu run d; done``)
— this loop is the repro-rate metric loop of BASELINE.md.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Optional

from namazu_tpu.orchestrator import Orchestrator
from namazu_tpu.policy import create_policy
from namazu_tpu.storage import load_storage
from namazu_tpu.utils.cmd import CmdFactory
from namazu_tpu.utils.config import Config
from namazu_tpu.utils.log import init_log

#: exit statuses the campaign supervisor classifies on (doc/robustness.md)
EXIT_OK = 0
EXIT_INFRA = 1
EXIT_TIMEOUT = 124  # a phase deadline expired (same convention as timeout(1))

#: a campaign supervisor's word to the run child it starts one run early
#: (campaign.py, doc/performance.md "Standby run child"): wait at the
#: gate for the go. Like ``NMZ_RUN_SPAWNED`` it is the supervisor's
#: alone and is taken out of the environment by the run that reads it;
#: a bare ``nmz-tpu run`` never sees it and never reads its stdin
RUN_STANDBY_ENV = "NMZ_RUN_STANDBY"


def register(sub) -> None:
    p = sub.add_parser("run", help="run one experiment from a storage dir")
    p.add_argument("storage", help="storage directory created by init")
    for phase in ("run", "validate", "clean"):
        p.add_argument(
            f"--{phase}-deadline", type=float, default=None, metavar="S",
            help=f"deadline for the {phase} script (seconds; its whole "
                 f"process group is killed on expiry); default: the "
                 f"config's {phase}_deadline_s, 0 = none")
    p.add_argument(
        "--journal", action="store_true",
        help="write the crash-recovery event journal into the run's "
             "working dir (doc/robustness.md): a killed orchestrator's "
             "parked events survive and a restart over the same dir "
             "resumes them. Also enabled by event_journal = true in "
             "the config")
    p.add_argument(
        "--knowledge", default="", metavar="HOST:PORT",
        help="global failure-knowledge service address (a sidecar "
             "started with --pool-dir, doc/knowledge.md): cold runs "
             "warm-start from the fleet's pooled failures, failures "
             "stream back; an outage degrades to local-only search. "
             "Overrides the config's explore_policy_param.knowledge")
    p.add_argument(
        "--virtual-clock", action="store_true",
        help="run under a discrete-event virtual clock "
             "(doc/performance.md \"Virtual clock\"): scheduled delays "
             "fast-forward instead of real-sleeping whenever every "
             "waiter is parked, and experiment children get the "
             "LD_PRELOAD clock interposer so their sleeps/poll "
             "timeouts park too. Repro results are unchanged at "
             "delay-scale 1; wall time shrinks by the scenario's idle "
             "fraction. Also enabled by virtual_clock = true in the "
             "config")
    p.add_argument(
        "--telemetry-url", default="", metavar="URL",
        help="push this process's metrics to a fleet aggregator "
             "(doc/observability.md \"Fleet telemetry\"): an "
             "orchestrator's REST endpoint (http://...) or a campaign "
             "supervisor's collector (uds:///path). Defaults to "
             "$NMZ_TELEMETRY_URL (a campaign supervisor exports it to "
             "its run children); overrides the config's telemetry_url")
    p.set_defaults(func=run)


def _deadline(cli_value: Optional[float], cfg: Config, key: str
              ) -> Optional[float]:
    v = cli_value if cli_value is not None else float(cfg.get(key, 0) or 0)
    return v if v and v > 0 else None


def _standby_gate() -> Optional[float]:
    """A standby run child's wait for its go: one blocking read of one
    line from stdin, before this process has read, opened or bound
    anything of the storage. The line is the supervisor's JSON object
    ``{"spawned": <its monotonic stamp at the go, or null while the
    campaign is not observed>, "respawn": <the seconds it took from the
    previous reap to that stamp, or null>, "env": {<name>: <value, or
    null = unset>}}`` — what it would have put into this attempt's
    environment at ``Popen`` time. Returns the stamp of the arrival at the gate, or
    None when the run was never wanted: EOF (the supervisor closed the
    pipe, or died) or anything that is not that object."""
    # what a run imports inline later, whatever its config says: loaded
    # while nobody waits for it. No config, storage, plugin or policy
    # object — everything a user can change between two runs is read
    # after the go
    import namazu_tpu.calibrate.artifact  # noqa: F401
    import namazu_tpu.endpoint.agent  # noqa: F401
    import namazu_tpu.endpoint.rest  # noqa: F401
    import namazu_tpu.endpoint.uds  # noqa: F401
    import namazu_tpu.policy.plugins  # noqa: F401
    from namazu_tpu import obs

    arrived = time.monotonic()
    try:
        go = json.loads(sys.stdin.buffer.readline())
        spawned, env = go["spawned"], go["env"]
        if spawned is not None:
            os.environ[obs.spans.RUN_SPAWNED_ENV] = repr(float(spawned))
            if go.get("respawn") is not None:
                os.environ[obs.spans.RUN_RESPAWN_ENV] = repr(
                    float(go["respawn"]))
        for name, value in env.items():
            if value is None:
                os.environ.pop(name, None)
            else:
                os.environ[name] = value
    except (AttributeError, KeyError, OSError, TypeError, ValueError):
        return None
    return arrived


def run(args) -> int:
    standby_since = None
    if os.environ.pop(RUN_STANDBY_ENV, None):
        standby_since = _standby_gate()
        if standby_since is None:
            return EXIT_OK  # never wanted: nothing was touched
    storage_dir = args.storage
    # a user-editable config.toml wins over the init-time config.json
    # snapshot, so swapping the policy between runs of one storage works
    # (reference parity: run.go:55 reads storageDir/config.toml directly —
    # e.g. record history under `random`, then re-run under `tpu_search`)
    cfg_path = os.path.join(storage_dir, "config.toml")
    if not os.path.exists(cfg_path):
        cfg_path = os.path.join(storage_dir, "config.json")
    # config.json is only ever written by init, so its absence (even with
    # a config.toml present, e.g. `run` pointed at an example source dir)
    # means this is not an initialized storage
    if not os.path.exists(os.path.join(storage_dir, "config.json")):
        print(f"error: {storage_dir} is not initialized (no config.json; "
              "run `init` first — an edited config.toml wins over it "
              "afterwards)", file=sys.stderr)
        return 1
    cfg = Config.from_file(cfg_path)
    # the run's own phases (doc/observability.md "Run phases") are timed
    # only where the config leaves observability on, so the switches
    # (obs_enabled, and telemetry_enabled = false for the relay below)
    # are read before the first stamp; `boot` ends and `prepare` starts
    # here
    from namazu_tpu import obs

    obs.configure_from_config(cfg)
    entered = obs.run_entered()
    # chaos plane (doc/robustness.md): fault plans reach child `run`
    # processes (campaign slots, kill-tests) via NMZ_CHAOS; no-op unless
    # set, and an explicitly installed plan wins
    from namazu_tpu import chaos

    chaos.install_from_env()
    if args.knowledge:
        # CLI wins over the config snapshot (same precedence as the
        # deadline flags): `campaign --knowledge` forwards this to every
        # child without editing the storage's config
        cfg.set("explore_policy_param.knowledge", args.knowledge)

    storage = load_storage(storage_dir)
    working_dir = storage.create_new_working_dir()
    materials_dir = os.path.join(storage_dir, "materials")
    # correlate this run's log lines, metrics, and flight-recorder trace
    # (GET /traces/<run_id>) with the on-disk run dir via one key
    if not cfg.is_set("run_id"):
        cfg.set("run_id", os.path.basename(os.path.normpath(working_dir)))
    obs.run_begin(str(cfg.get("run_id")), entered, standby_since)
    init_log(os.path.join(working_dir, "nmz.log"))
    if args.journal or bool(cfg.get("event_journal")):
        # the journal lives in the run's own dir: recovery is per-run,
        # and fsck/quarantine semantics over the storage stay untouched
        cfg.set("event_journal_dir", working_dir)
    factory = CmdFactory(working_dir=working_dir, materials_dir=materials_dir)
    # calibration plane (namazu_tpu/calibrate): a committed
    # calibration.json in the storage (copied by init from the example
    # dir) exports its knob values as NMZ_CALIB_<NAME> to every
    # experiment script — calibrated timing is provenance the scripts
    # read from the environment, never an edited source constant.
    # Explicit environment (a calibration probe's candidate values,
    # exported by the campaign supervisor) wins over the artifact.
    from namazu_tpu.calibrate import artifact as _calib_artifact

    calib = _calib_artifact.load_calibration(storage_dir)
    if calib is not None:
        env_knobs = _calib_artifact.knob_env(calib)
        factory.extra_env.update(
            {k: v for k, v in env_knobs.items() if k not in os.environ})
    # record the run script's process group while a phase is in flight:
    # if THIS process is SIGKILLed mid-run (the orchestrator crash the
    # chaos plane injects), the campaign supervisor sweeps the group so
    # testee processes cannot orphan into the next slot
    factory.pgid_file = os.path.join(working_dir, "phase.pgid")

    # virtual clock (doc/performance.md "Virtual clock"): installed
    # BEFORE the policy/orchestrator exist so every ScheduledQueue,
    # liveness stamp, and lease TTL constructed below reads the virtual
    # source; children inherit the epoch page + interposer via the env
    vclock_handle = None
    vclock_summary = None
    if getattr(args, "virtual_clock", False) or bool(
            cfg.get("virtual_clock")):
        from namazu_tpu import vclock

        vclock_handle = vclock.activate(working_dir, cfg)
        factory.extra_env.update(vclock_handle.child_env())

    from namazu_tpu.policy.plugins import load_policy_plugins

    load_policy_plugins(cfg, materials_dir)
    policy = create_policy(cfg.get("explore_policy"))
    policy.load_config(cfg)
    policy.set_history_storage(storage)

    # the live GET /analytics route aggregates over this storage (the
    # same dir `tools report` reads offline — one payload, two surfaces)
    obs.set_analytics_storage(os.path.abspath(storage_dir))
    if args.knowledge:
        # fold the fleet's pool/tenant stats into GET /analytics
        obs.set_knowledge_address(args.knowledge)
    # fleet telemetry: claim this process's producer identity as a
    # campaign `run` child BEFORE the orchestrator's own idempotent
    # ensure_self_relay can name it "orchestrator"; precedence CLI >
    # $NMZ_TELEMETRY_URL (the campaign supervisor's export) > config
    if args.telemetry_url:
        cfg.set("telemetry_url", args.telemetry_url)
    obs.federation.ensure_self_relay(
        "run",
        push_url=(args.telemetry_url
                  or os.environ.get("NMZ_TELEMETRY_URL", "")
                  or str(cfg.get("telemetry_url", "") or "")),
        interval_s=float(cfg.get("telemetry_interval_s", 2.0) or 2.0))
    # continuous profiling (doc/observability.md "Profiling"): same
    # claim-before-the-orchestrator rule as the relay above, so the
    # profile rides this child's telemetry as job "run"
    obs.profiling.ensure_profiler("run", cfg=cfg)

    run_deadline = _deadline(args.run_deadline, cfg, "run_deadline_s")
    validate_deadline = _deadline(args.validate_deadline, cfg,
                                  "validate_deadline_s")
    clean_deadline = _deadline(args.clean_deadline, cfg, "clean_deadline_s")

    orchestrator = Orchestrator(cfg, policy, collect_trace=True)
    orchestrator.start()
    obs.run_phase_since("prepare", entered)

    successful = False
    recorded = False
    start = time.monotonic()
    # the clean script runs in the OUTER finally no matter how the run
    # ends — a failed validate, a deadline kill, or a Ctrl-C after the
    # run script must not leak testee state (ports, scratch files,
    # half-dead processes) into the next run of the campaign loop
    try:
        try:
            run_script = cfg.get("run")
            if not run_script:
                print("error: config has no 'run' script", file=sys.stderr)
                return EXIT_INFRA
            try:
                with obs.run_phase("testee"):
                    res = factory.run(run_script, deadline=run_deadline)
            except subprocess.TimeoutExpired:
                print(f"error: run script exceeded its {run_deadline:.1f}s "
                      "deadline; killed its process group; not recording "
                      "this run", file=sys.stderr)
                return EXIT_TIMEOUT
            if res.returncode != 0:
                # infra failure, not an experiment outcome: abort without
                # recording so it cannot pollute repro-rate stats or the
                # search plane's failure archive (parity: cli/run.go aborts
                # when the run command errors)
                print(f"error: run script exited {res.returncode}; "
                      "not recording this run", file=sys.stderr)
                return EXIT_INFRA
        finally:
            with obs.run_phase("drain"):
                trace = orchestrator.shutdown()
            # stop fast-forwarding before validate/clean: the oracle
            # runs at wall rate, and the restored default TimeSource
            # must not leak a jumped clock into the next in-process run
            if vclock_handle is not None:
                vclock_summary = vclock_handle.finish()

        validate_script = cfg.get("validate")
        if validate_script:
            try:
                with obs.run_phase("validate"):
                    successful = factory.run(
                        validate_script,
                        deadline=validate_deadline).returncode == 0
            except subprocess.TimeoutExpired:
                print("error: validate script exceeded its "
                      f"{validate_deadline:.1f}s deadline; killed its "
                      "process group; not recording this run",
                      file=sys.stderr)
                return EXIT_TIMEOUT
        required_time = time.monotonic() - start

        from namazu_tpu.signal.base import HINT_SPACE

        with obs.run_phase("record"):
            storage.record_new_trace(trace)
        # stamp the replay-hint format version: a future format bump must
        # be able to tell (and skip) histories whose recorded event_hint
        # strings hash into a different bucket space (policy/tpu.py
        # _ingest_history)
        metadata = {"hint_space": HINT_SPACE}
        if vclock_summary is not None:
            # required_time (and every rate derived from it) stays
            # wall-denominated — SPRT budgets and calibration artifacts
            # must keep comparing like with like; the virtual elapsed
            # rides as separate metadata for the virtual-rate surfaces
            metadata["virtual_time_s"] = vclock_summary[
                "virtual_elapsed_s"]
            metadata["wall_time_s"] = vclock_summary["wall_elapsed_s"]
            metadata["vclock_speedup"] = vclock_summary["speedup_ratio"]
            metadata["vclock_pinned_s"] = vclock_summary["pinned_s"]
        # the run carries its own spans: whoever reads this record (the
        # campaign supervisor, the search home's ingest) has the run's
        # cycle by phase without a wire to this process
        phases = obs.run_end()
        if phases:
            metadata["phases"] = phases
        storage.record_result(successful, required_time,
                              metadata=metadata)
        recorded = True

        extra = ""
        if vclock_summary is not None:
            extra = (f" virtual={vclock_summary['virtual_elapsed_s']:.2f}s"
                     f" speedup={vclock_summary['speedup_ratio']}x")
        print(f"run finished: successful={successful} "
              f"time={required_time:.2f}s{extra} trace={len(trace)} "
              f"actions workdir={working_dir}")
        return EXIT_OK
    finally:
        obs.run_end()  # an aborted run's scope; closed already otherwise
        # abort paths (deadline kill, infra failure, Ctrl-C) must also
        # restore the wall TimeSource; finish() is idempotent
        if vclock_handle is not None:
            vclock_handle.finish()
        if not recorded:
            # deliberate abort (infra failure / deadline / interrupt):
            # mark the allocated run dir so fsck can tell it from a
            # crash and analytics never mistakes it for data
            try:
                storage.quarantine_current_run(
                    "run aborted before a result was recorded")
            except Exception as e:
                print(f"warning: could not mark aborted run: {e}",
                      file=sys.stderr)
        # crash-safe close: a storage backend flushing remote state
        # (mongodb) must not turn a recorded run into a failed exit
        try:
            storage.close()
        except Exception as e:
            print(f"warning: storage close failed: {e}", file=sys.stderr)
        clean_script = cfg.get("clean")
        if clean_script:
            try:
                factory.run(clean_script, deadline=clean_deadline)
            except subprocess.TimeoutExpired:
                print("warning: clean script exceeded its "
                      f"{clean_deadline:.1f}s deadline; killed its "
                      "process group", file=sys.stderr)
            except Exception as e:
                print(f"warning: clean script failed: {e}", file=sys.stderr)
