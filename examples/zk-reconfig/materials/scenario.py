"""The ZOOKEEPER-2080 scenario in miniature: ReconfigRecoveryTest's
restarts and reconfiguration played against five ``reconfig_server.py``
processes, one step after another, each step waiting until the live
servers have formed an ensemble again (their ``state<N>`` files).

Steps: the first election (four voters, server 5 an observer); server 3
is lost; server 3 comes back with the configuration it had, and while
it is in its election the leader is asked for the reconfiguration that
makes the observer a participant (``RECONFIG_DELAY_MS`` after the
restart: a client thread the restart is not synchronised with, so the
delay is drawn per run unless the environment fixes it); the leader is
lost; it comes back; servers 1, 2 and 3 are restarted in turn; the new
leader is lost and comes back. A step that does not form within
``STEP_DEADLINE_S`` ends the run: the oracle then finds ``timed_out``.

Usage: scenario.py MATERIALS_DIR OUT_DIR
"""

import os
import random
import signal
import subprocess
import sys
import time

SERVERS = (1, 2, 3, 4, 5)
LAST_ZXID = "0x100000002"
STEP_DEADLINE_S = float(os.environ.get("NMZ_ZK2080_STEP_DEADLINE_S", "20"))
#: the reconfiguration is asked for this long after server 3's restart
RECONFIG_DELAY_MAX_MS = 1500


def log(msg):
    sys.stderr.write(f"[scenario {time.monotonic():.3f}] {msg}\n")
    sys.stderr.flush()


class Ensemble:
    def __init__(self, materials, out):
        self.materials, self.out = materials, out
        self.procs = {}

    def start(self, sid):
        peers = ",".join(f"{d}:127.1.{sid}.{d}:127.1.0.{d}"
                         for d in SERVERS if d != sid)
        with open(os.path.join(self.out, f"server{sid}.log"), "a") as f:
            self.procs[sid] = subprocess.Popen(
                [sys.executable,
                 os.path.join(self.materials, "reconfig_server.py"),
                 str(sid), LAST_ZXID, f"127.1.0.{sid}", self.out, peers],
                stdout=f, stderr=f)
        log(f"server {sid} started")

    def kill(self, sid):
        self.procs.pop(sid).kill()
        try:
            os.unlink(os.path.join(self.out, f"state{sid}"))
        except OSError:
            pass
        log(f"server {sid} killed")

    def state(self, sid):
        try:
            with open(os.path.join(self.out, f"state{sid}")) as f:
                words = f.read().split()
        except OSError:
            return None
        return dict(w.split("=") for w in words[1:])

    def formed(self, leader=None, config=None):
        """Every live server is part of one ensemble: the same leader
        (``leader`` if given), the same configuration."""
        states = [self.state(s) for s in self.procs]
        if any(s is None for s in states):
            return False
        leaders = {s["leader"] for s in states}
        configs = {s["config"] for s in states}
        if len(leaders) != 1 or len(configs) != 1:
            return False
        if leader is not None and leaders != {str(leader)}:
            return False
        if str(leaders.pop()) not in {str(s) for s in self.procs}:
            return False  # they still name a server that is gone
        return config is None or configs == {config}

    def wait_formed(self, what, **want):
        deadline = time.monotonic() + STEP_DEADLINE_S
        while time.monotonic() < deadline:
            for sid, p in self.procs.items():
                if p.poll() is not None:
                    raise SystemExit(f"server {sid} exited: infra error")
            if self.formed(**want):
                log(f"formed after: {what}")
                return True
            time.sleep(0.02)
        log(f"NOT formed after: {what}")
        return False

    def stop(self):
        for p in self.procs.values():
            p.send_signal(signal.SIGTERM)
        for p in self.procs.values():
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()


def play(e):
    for sid in SERVERS:
        e.start(sid)
    if not e.wait_formed("the first election", leader=4):
        return False
    e.kill(3)
    time.sleep(0.3)
    # the race: server 3 back in its election while the reconfiguration
    # that makes the observer a participant is proposed and committed
    delay_ms = os.environ.get("NMZ_ZK2080_RECONFIG_DELAY_MS")
    delay_ms = (float(delay_ms) if delay_ms
                else random.SystemRandom().uniform(0, RECONFIG_DELAY_MAX_MS))
    log(f"reconfiguration {delay_ms:.0f} ms after the restart")
    e.start(3)
    time.sleep(delay_ms / 1000.0)
    e.procs[4].send_signal(signal.SIGUSR1)
    if not e.wait_formed("server 3 back, observer made participant",
                         leader=4, config="200000001"):
        return False
    for step, lost, leader in (("the leader lost", 4, 5),
                               ("server 1 restarted", 1, 5),
                               ("server 2 restarted", 2, 5),
                               ("server 3 restarted", 3, 5),
                               ("the second leader lost", 5, 4),
                               ("server 1 restarted again", 1, 4),
                               ("server 2 restarted again", 2, 4)):
        e.kill(lost)
        if not e.wait_formed(step, leader=leader):
            return False
        e.start(lost)
        if not e.wait_formed(f"{step}, and back", leader=leader):
            return False
    return True


def main():
    materials, out = sys.argv[1], sys.argv[2]
    e = Ensemble(materials, out)
    try:
        ok = play(e)
    finally:
        e.stop()
    with open(os.path.join(out, "formed" if ok else "timed_out"), "w") as f:
        f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
