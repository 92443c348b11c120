"""Experiment script execution.

Parity: /root/reference/nmz/util/cmd/cmdutil.go:27-77 — run the config's
init/run/validate/clean commands via ``sh -c`` with the working dir and
materials dir exported (reference env names NMZ_WORKING_DIR /
NMZ_MATERIALS_DIR; both the reference names and NMZ_TPU_* are exported for
drop-in compatibility with existing experiment scripts).
"""

from __future__ import annotations

import os
import signal
import subprocess
import time
from typing import Iterable, Optional

from namazu_tpu.utils.log import get_logger

log = get_logger("utils.cmd")

#: SIGTERM -> SIGKILL escalation grace when a deadline kills a script's
#: process group
KILL_GRACE_S = 3.0


def kill_process_group(proc: subprocess.Popen,
                       grace: float = KILL_GRACE_S) -> None:
    """Terminate ``proc``'s whole process group (it must have been
    started with ``start_new_session=True``): SIGTERM first, SIGKILL
    after ``grace`` seconds. Killing the *group* is the point — an
    experiment ``run`` script forks testee processes and inspectors,
    and killing only ``sh`` would orphan them into the next run."""
    try:
        pgid = os.getpgid(proc.pid)
    except (OSError, ProcessLookupError):
        return
    try:
        os.killpg(pgid, signal.SIGTERM)
    except (OSError, ProcessLookupError):
        return
    try:
        proc.wait(timeout=grace)
    except subprocess.TimeoutExpired:
        pass
    # ALWAYS escalate the group: the direct child exiting on SIGTERM
    # says nothing about a SIGTERM-ignoring grandchild
    try:
        os.killpg(pgid, signal.SIGKILL)
    except (OSError, ProcessLookupError):
        pass
    try:
        proc.wait(timeout=grace)
    except subprocess.TimeoutExpired:
        pass
    # give group stragglers a moment to be reaped (SIGKILL cannot be
    # ignored; this just bounds the observable window)
    deadline = time.monotonic() + grace
    while time.monotonic() < deadline:
        try:
            os.killpg(pgid, 0)
        except (OSError, ProcessLookupError):
            return  # group gone
        time.sleep(0.05)


class CmdFactory:
    def __init__(self, working_dir: str = "", materials_dir: str = "",
                 extra_env: Optional[dict] = None):
        self.working_dir = working_dir
        self.materials_dir = materials_dir
        # when set, deadline-mode phases write their process-group id
        # here while in flight (removed on completion): the breadcrumb
        # a supervisor needs to kill testee groups orphaned by a HARD
        # kill of this process — SIGKILL skips every finally, so the
        # group's pgid must already be on disk (doc/robustness.md)
        self.pgid_file: str = ""
        # extra variables exported to every script — the calibration
        # plane's knob transport (NMZ_CALIB_<NAME>, namazu_tpu/calibrate):
        # a calibrated timing value reaches the experiment scripts as
        # environment, never as an edited source constant
        self.extra_env: dict = dict(extra_env or {})

    def env(self) -> dict:
        env = dict(os.environ)
        env.update({str(k): str(v) for k, v in self.extra_env.items()})
        if self.working_dir:
            env["NMZ_WORKING_DIR"] = self.working_dir
            env["NMZ_TPU_WORKING_DIR"] = self.working_dir
        if self.materials_dir:
            env["NMZ_MATERIALS_DIR"] = self.materials_dir
            env["NMZ_TPU_MATERIALS_DIR"] = self.materials_dir
        # experiment scripts spawn fresh interpreters that must be able to
        # import the framework (e.g. `python -m namazu_tpu.cli inspectors`)
        # even when it is not installed site-wide
        import namazu_tpu

        pkg_parent = os.path.dirname(os.path.dirname(
            os.path.abspath(namazu_tpu.__file__)))
        parts = env.get("PYTHONPATH", "").split(os.pathsep)
        if pkg_parent not in parts:
            env["PYTHONPATH"] = os.pathsep.join(
                [pkg_parent] + [p for p in parts if p])
        return env

    def run(
        self,
        script: str,
        timeout: Optional[float] = None,
        cwd: Optional[str] = None,
        deadline: Optional[float] = None,
    ) -> subprocess.CompletedProcess:
        """Run ``script`` with sh -c; stdout/stderr inherit the caller's
        (experiment scripts print progress).

        With ``deadline`` the script runs in its own session (process
        group); on expiry the WHOLE group is killed (SIGTERM, then
        SIGKILL) so forked testee children cannot outlive the phase, and
        :class:`subprocess.TimeoutExpired` is raised. The plain
        ``timeout`` keeps subprocess.run semantics (kills only ``sh``)
        for callers that manage their own children."""
        argv = ["sh", "-c", script]
        run_cwd = cwd or self.working_dir or None
        if deadline is None:
            return subprocess.run(
                argv, env=self.env(), cwd=run_cwd, timeout=timeout)
        proc = subprocess.Popen(
            argv, env=self.env(), cwd=run_cwd, start_new_session=True)
        self._write_pgid(proc)
        try:
            proc.wait(timeout=deadline)
        except subprocess.TimeoutExpired:
            log.warning("script exceeded its %.1fs deadline; killing its "
                        "process group: %s", deadline, script)
            kill_process_group(proc)
            raise subprocess.TimeoutExpired(argv, deadline) from None
        except BaseException:
            # interrupted mid-phase (e.g. KeyboardInterrupt): same
            # no-orphans guarantee as the deadline path
            kill_process_group(proc)
            raise
        finally:
            self._clear_pgid()
        return subprocess.CompletedProcess(argv, proc.returncode)

    def _write_pgid(self, proc: subprocess.Popen) -> None:
        if not self.pgid_file:
            return
        try:
            with open(self.pgid_file, "w") as f:
                f.write(str(os.getpgid(proc.pid)))
        except OSError:
            pass  # best effort: supervision degrades, the run continues

    def _clear_pgid(self) -> None:
        if self.pgid_file:
            try:
                os.unlink(self.pgid_file)
            except OSError:
                pass


def sweep_stale_pgid_files(run_dirs: Iterable[str]) -> int:
    """Kill process groups whose ``phase.pgid`` breadcrumb outlived its
    writer (the `run` process was hard-killed mid-phase, so its finally
    never removed the file and never killed the group), looking in
    ``run_dirs`` and nowhere else. Called by the campaign supervisor
    with every entry of the storage where a campaign starts (a
    supervisor killed earlier may have left one anywhere) and, after an
    attempt, with the dirs that attempt created — only they can hold
    its breadcrumb; returns how many groups were swept. The
    pgid-recycling race is accepted: the supervisor runs this
    immediately after the slot ends, and a recycled pgid would have to
    land inside that window on a group id we just created."""
    swept = 0
    for run_dir in run_dirs:
        path = os.path.join(run_dir, "phase.pgid")
        try:
            with open(path) as f:
                pgid = int(f.read().strip())
        except (OSError, ValueError):
            continue
        try:
            os.killpg(pgid, 0)
        except (OSError, ProcessLookupError):
            pass  # group already gone: just the breadcrumb to sweep
        else:
            log.warning("sweeping orphaned process group %d left by a "
                        "hard-killed run (%s)", pgid, path)
            try:
                os.killpg(pgid, signal.SIGKILL)
                swept += 1
            except (OSError, ProcessLookupError):
                pass
        try:
            os.unlink(path)
        except OSError:
            pass
    return swept
