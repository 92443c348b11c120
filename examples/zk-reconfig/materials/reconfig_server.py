"""A reconfiguring ZooKeeper server in miniature: zk-zab's server
(``examples/zk-zab/materials/server.py``: the election, quorum and
client wire formats, imported, not copied) with what the
ZOOKEEPER-2080 hunt adds on top of it:

* **elections in sequence.** zk-zab's server elects once; this one goes
  back to LOOKING when its leader's connection ends, when it is told a
  newer configuration, or when a committed reconfiguration changes its
  role, and starts a new round (the round is the notification's
  ``electionEpoch``). What is left out of FLE: a LOOKING server does
  not send its notifications again when it hears nothing (every peer is
  told on connect and on every change of vote, over connections that
  do not lose messages, so nothing depends on it — and under a policy
  that holds messages for seconds the repeats would feed on
  themselves).
* **a dynamic configuration.** The 3.5 notification: zk-zab's 36 bytes,
  then ``version`` (0x2), then the configuration as text
  (``server.N=...:participant|observer`` lines and ``version=<hex>``).
  Votes of servers that are not voters of the receiver's configuration
  are not counted; an observer proposes nobody.
* **the reconfiguration** itself: one ZAB transaction
  (``reconfig:<version>:<voters>``), proposed by the leader on SIGUSR1,
  acknowledged and committed like any other, installed on commit. A
  server whose role it changes restarts its election and announces
  the new configuration in its notifications.
* **what is on disk**: the committed log and the configuration
  (``disk<N>.json``), so that a server killed before the
  reconfiguration comes back with the OLD configuration.

THE BUG (ZOOKEEPER-2080's class: the election's receiver against the
connection manager). Two locks: ``qv_lock`` guards the configuration
(ZooKeeper's ``QV_LOCK``), ``cnx_lock`` the connection manager. A server
that has just decided tears its election down: it holds ``cnx_lock``
while it halts the listener and joins the workers (``TEARDOWN_MS``, the
scenario's one timing knob), then takes ``qv_lock`` to read the view it
will dial by. The receiver, handed a notification with a NEWER
configuration, takes ``qv_lock``, installs it, and restarts the
election, for which it needs ``cnx_lock``. A newer configuration that
reaches a server inside its teardown finds each thread holding the lock
the other wants: the server never follows, never answers, and the
ensemble does not re-form around it. Before the teardown the newer
configuration just restarts the election; after it, it is installed
quietly. Only the order of election-port messages decides.

Usage: reconfig_server.py SID LAST_ZXID HOST OUT_DIR PEER[,PEER...]
       PEER = sid:electionHost:quorumHost (the election port is reached
       through the proxy, the quorum port directly: only election
       messages are inspected, as upstream's zk_inspector.py does)
"""

import json
import os
import signal
import socket
import struct
import sys
import threading
import time

import namazu_tpu

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(namazu_tpu.__file__))),
    "examples", "zk-zab", "materials"))

import server as zab  # noqa: E402  (zk-zab's server: the wire formats)
from server import (  # noqa: E402
    ACK, ACKEPOCH, COMMIT, FOLLOWERINFO, FOLLOWING, LEADERINFO, LEADING,
    LOOKING, NEWLEADER, PING, PROPOSAL, UPTODATE, Sender, note,
    quorum_packet, read_exact, read_quorum_packet)

NOTIFICATION_VERSION = 0x2  # FastLeaderElection.Notification.CURRENTVERSION
FOLLOW_DIAL_S = 2.0
LEAD_SYNC_S = 3.0
#: the first configuration: four voters and one observer
FIRST_VOTERS, FIRST_VERSION = (1, 2, 3, 4), 0x100000000
TEARDOWN_S = float(os.environ.get("NMZ_CALIB_TEARDOWN_MS", "100")) / 1000.0


def config_text(voters, version):
    lines = [f"server.{n}=127.1.0.{n}:2888:3888:"
             + ("participant" if n in voters else "observer")
             for n in range(1, zab.ENSEMBLE + 1)]
    return ("\n".join(lines) + f"\nversion={version:x}").encode()


def parse_config(text):
    voters, version = [], 0
    for line in text.decode().splitlines():
        key, _, value = line.partition("=")
        if key == "version":
            version = int(value, 16)
        elif value.endswith(":participant"):
            voters.append(int(key.split(".")[1]))
    return tuple(voters), version


class ReconfigServer(zab.Server):
    def __init__(self, sid, last_zxid, host, out_dir, peers, quorum_hosts):
        super().__init__(sid, last_zxid, host, out_dir, peers)
        self.quorum_hosts = quorum_hosts  # sid -> host of its quorum port
        self.qv_lock = threading.Lock()   # the configuration
        self.cnx_lock = threading.Lock()  # the connection manager
        self.voters, self.config_version = FIRST_VOTERS, FIRST_VERSION
        self.round = 0
        self.leader_sock = None
        self.disk = os.path.join(out_dir, f"disk{sid}.json")
        if os.path.exists(self.disk):
            with open(self.disk) as f:
                d = json.load(f)
            self.voters = tuple(d["voters"])
            self.config_version = d["config_version"]
            for zxid, txn in d["txns"]:
                zab.Server._apply(self, zxid, txn.encode())
            self.epoch = self.last_zxid >> 32
        self.vote = self._first_vote()
        self.votes = {sid: self.vote}

    def _first_vote(self):
        """A voter proposes itself; an observer nobody
        (``getInitId()`` is Long.MIN_VALUE for a non-participant)."""
        if self.sid in self.voters:
            return (self.last_zxid, self.sid)
        return (-1, -1)

    # -- what is on disk, and what the driver reads -------------------------

    def _persist(self):
        first = sum(z >> 32 <= 1 for z, _ in self.log)
        tmp = self.disk + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"voters": list(self.voters),
                       "config_version": self.config_version,
                       "txns": [[z, t.decode()]
                                for z, t in self.log[first:]]}, f)
        os.replace(tmp, self.disk)

    def _formed(self, role):
        """This server is part of a formed ensemble: what the driver
        waits for after every step, and the oracle reads at the end."""
        tmp = os.path.join(self.out_dir, f".state{self.sid}")
        # two learners' threads can get here at once (followers whose
        # teardowns end together acknowledge NEWLEADER together): one
        # temporary file, so one writer at a time
        with self.lock:
            with open(tmp, "w") as f:
                f.write(f"{role} leader={self.leader} "
                        f"config={self.config_version:x} "
                        f"round={self.round}\n")
            os.replace(tmp, os.path.join(self.out_dir, f"state{self.sid}"))
        note(self.sid, f"formed: {role} of {self.leader}, config "
             f"{self.config_version:x}")

    # -- election: the 3.5 notification and the configuration in it ---------

    def _notification(self):
        zxid, leader = self.vote
        config = config_text(self.voters, self.config_version)
        body = struct.pack(">iqqqq", self.state, leader, zxid, self.round,
                           self.epoch)
        body += struct.pack(">ii", NOTIFICATION_VERSION, len(config))
        body += config
        return struct.pack(">i", len(body)) + body

    def _fle_recv(self, conn):
        try:
            (psid,) = struct.unpack(">q", read_exact(conn, 8))
            while True:
                (flen,) = struct.unpack(">i", read_exact(conn, 4))
                body = read_exact(conn, flen)
                state, leader, zxid, _e, _pe = struct.unpack(
                    ">iqqqq", body[:36])
                _v, clen = struct.unpack(">ii", body[36:44])
                self._on_config(psid, *parse_config(body[44:44 + clen]))
                self._on_notification(psid, state, (zxid, leader))
        except (OSError, struct.error):
            conn.close()

    def _on_config(self, psid, voters, version):
        """WorkerReceiver: the configuration is looked at before the
        vote, and a newer one restarts the election."""
        with self.qv_lock:
            if version <= self.config_version:
                return
            note(self.sid, f"newer configuration {version:x} from {psid}")
            self._install(voters, version)
            # halt the connection manager to restart the election: the
            # other half of THE BUG holds it through its teardown
            with self.cnx_lock:
                pass
        self._look(f"newer configuration {version:x}", restart=True)

    def _install(self, voters, version):
        with self.lock:
            self.voters, self.config_version = tuple(voters), version
            self._persist()

    def _on_notification(self, psid, state, vote):
        with self.lock:
            if state == LOOKING:
                self.decided_by.pop(psid, None)
                if psid not in self.voters and self.state == LOOKING:
                    return  # an observer's vote is not counted
            elif psid not in self.voters:
                return  # nor is what an observer says it follows
            super()._on_notification(psid, state, vote)

    def _look(self, why, restart=False):
        """(Re)start leader election; ``restart``: even if one is under
        way (its first vote depends on the configuration)."""
        with self.lock:
            if self.state == LOOKING and self.round and not restart:
                return
            note(self.sid, f"looking: {why}")
            self.round += 1
            self.state, self.leader = LOOKING, None
            self.vote = self._first_vote()
            self.votes, self.decided_by = {self.sid: self.vote}, {}
            self.changed = time.monotonic()
            self.elected.clear()
            self.active.clear()
            for out in self.forwarding:  # a leader stands down
                out.sock.close()
            self.forwarding = []
            if self.leader_sock is not None:
                self.leader_sock.close()
            self._broadcast()
        threading.Thread(target=self._elect, daemon=True).start()

    def _decide(self, leader):
        if self.state != LOOKING:
            return
        if leader not in self.voters:
            return  # a stale notification naming a server that cannot lead
        super()._decide(leader)
        if self.state == LEADING:
            self.new_epoch = self.epoch + 1
            self.newleader_acks = {self.sid: None}
            self.outstanding, self.counter = {}, 0
        threading.Thread(target=self._leave_election,
                         args=(self.round,), daemon=True).start()

    def _leave_election(self, rnd):
        """Tear the election down, then lead or follow. THE BUG's first
        half: the connection manager is held while the listener halts
        and the workers are joined, and only then is the view read."""
        with self.cnx_lock:
            time.sleep(TEARDOWN_S)
            with self.qv_lock:
                pass
        with self.lock:
            if self.round != rnd or self.state == LOOKING:
                return
            leading = self.state == LEADING
        if not leading:
            return self._follow(rnd)
        # a leader that no quorum follows gives up and looks again
        # (ZooKeeper's initLimit): under a policy that reorders votes a
        # server can be elected by a quorum that then moves on
        if not self.active.wait(LEAD_SYNC_S):
            with self.lock:
                if self.round != rnd:
                    return
            self._look("no quorum of followers synchronised")

    # -- following: zk-zab's, over a connection that may end ----------------

    def _follow(self, rnd):
        host = self.quorum_hosts[self.leader]
        deadline = time.monotonic() + FOLLOW_DIAL_S
        s = None
        while s is None:
            try:
                s = socket.create_connection((host, zab.QUORUM_PORT),
                                             timeout=1.0)
            except OSError:
                if time.monotonic() > deadline:
                    return self._look(f"leader {self.leader} unreachable")
                time.sleep(0.02)
        s.settimeout(None)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        with self.lock:
            if self.round != rnd:
                s.close()
                return
            self.leader_sock = s
        out = Sender(s)
        pending, in_diff = {}, True
        try:
            out.send(quorum_packet(
                FOLLOWERINFO, self.epoch << 32,
                struct.pack(">qi", self.sid, 0x10000)))
            ptype, zxid, _ = read_quorum_packet(s)
            assert ptype == LEADERINFO, ptype
            out.send(quorum_packet(ACKEPOCH, self.last_zxid,
                                   struct.pack(">i", self.epoch)))
            while True:
                ptype, zxid, data = read_quorum_packet(s)
                if ptype == PROPOSAL:
                    pending[zxid] = data
                    if not in_diff:
                        out.send(quorum_packet(ACK, zxid))
                elif ptype == COMMIT:
                    if zxid in pending:
                        with self.lock:
                            self._apply(zxid, pending.pop(zxid))
                elif ptype == NEWLEADER:
                    in_diff, self.epoch = False, zxid >> 32
                    out.send(quorum_packet(ACK, zxid))
                elif ptype == UPTODATE:
                    self._formed("following")
                elif ptype == PING:
                    self.pings += 1
                    out.send(quorum_packet(PING, self.last_zxid))
        except (OSError, AssertionError, struct.error) as e:
            s.close()
            with self.lock:
                if self.round != rnd:
                    return
            self._look(f"leader connection ended: {e}")

    # -- the reconfiguration -------------------------------------------------

    def _newleader_ack(self, fsid, out):
        was = self.active.is_set()
        super()._newleader_ack(fsid, out)
        if self.active.is_set() and not was:
            self._formed("leading")

    def reconfigure(self):
        """SIGUSR1: the leader proposes the next configuration — the
        observer becomes a participant."""
        if self.state != LEADING or not self.active.is_set():
            note(self.sid, "reconfig asked of a server that is not leading")
            return
        voters = tuple(range(1, zab.ENSEMBLE + 1))
        with self.lock:
            version = (self.epoch << 32) | (self.counter + 1)
        txn = (f"reconfig:{version:x}:"
               + ",".join(map(str, voters))).encode()
        self.propose(txn, lambda zxid: note(
            self.sid, f"reconfiguration {zxid:x} committed"))

    def _apply(self, zxid, txn):
        super()._apply(zxid, txn)
        if not txn.startswith(b"reconfig:"):
            self._persist()
            return
        _, version, voters = txn.decode().split(":")
        voters = tuple(int(v) for v in voters.split(","))
        version = int(version, 16)
        was_voter = self.sid in self.voters
        if version > self.config_version:
            self._install(voters, version)
            note(self.sid, f"configuration {version:x} installed")
            if (self.sid in voters) != was_voter:
                # a role change restarts this server's election, off the
                # thread that applies transactions
                threading.Thread(
                    target=self._look, daemon=True,
                    args=(f"role changed by configuration {version:x}",),
                ).start()
            elif self.state != LOOKING:
                self._formed("leading" if self.state == LEADING
                             else "following")
        else:
            self._persist()

    # -- the process ----------------------------------------------------------

    def run(self):
        stop = threading.Event()
        signal.signal(signal.SIGTERM, lambda *_: stop.set())
        signal.signal(signal.SIGUSR1, lambda *_: threading.Thread(
            target=self.reconfigure, daemon=True).start())
        self._listen(zab.ELECTION_PORT, self._fle_recv)
        self._listen(zab.QUORUM_PORT, self._learner)
        self._look("started")  # round 1 before the first dial announces it
        for psid, host in self.peers.items():
            threading.Thread(target=self._fle_dial, args=(psid, host),
                             daemon=True).start()
        threading.Thread(target=self._ping_followers, daemon=True).start()
        stop.wait()
        self.dump()


def main():
    sid, last_zxid = int(sys.argv[1]), int(sys.argv[2], 0)
    peers, quorum_hosts = {}, {}
    for p in sys.argv[5].split(","):
        psid, election, quorum = p.split(":")
        peers[int(psid)], quorum_hosts[int(psid)] = election, quorum
    ReconfigServer(sid, last_zxid, sys.argv[3], sys.argv[4], peers,
                   quorum_hosts).run()


if __name__ == "__main__":
    main()
