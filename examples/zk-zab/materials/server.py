"""Miniature ZooKeeper server: fast leader election, ZAB and the client
port, each on ZooKeeper's real wire format, so that the proxy
inspector's ``ZkStreamParser`` yields the hints the upstream
zktraffic-based inspector yields (``fle:*``, ``zab:<type>:zxid=..``,
``cm:*`` / ``sm:*``; pings are parsed and passed, not deferred).

What it speaks:

* **FLE** (election port): QuorumCnxManager's 3.4 handshake (bare sid)
  and length-framed notifications ``state, leader, zxid, electionEpoch,
  peerEpoch``. One outbound connection per peer, as zk-election's node.
  A LOOKING server adopts the better (zxid, sid) vote and re-broadcasts,
  decides when a quorum holds its vote and ``FINALIZE_WAIT_S`` passes
  without a better one; a decided server answers a LOOKING peer with
  its vote and state, and a LOOKING server follows a leader that a
  quorum of decided peers names.
* **ZAB** (quorum port): unframed jute ``QuorumPacket`` records.
  Discovery and synchronisation — FOLLOWERINFO, LEADERINFO, ACKEPOCH,
  DIFF (the committed transactions the learner lacks, as PROPOSAL +
  COMMIT pairs), NEWLEADER, ACK, UPTODATE — then broadcast: PROPOSAL to
  every forwarding follower, ACK, COMMIT in zxid order once a quorum
  (the leader counts) has acknowledged. The leader PINGs each follower
  every ``TICK_S`` and the follower answers.
* **client** (client port, served by the active leader only):
  ConnectRequest / ConnectResponse, ``create`` requests and replies,
  session pings.

What it leaves out of ZAB: the epoch is not negotiated (the new epoch is
the old one + 1, no wait for a quorum of FOLLOWERINFO), no SNAP / TRUNC
(a learner is never ahead), no observers, no REQUEST forwarding (the
client talks to the leader), no leader failure, nothing on disk.

The planted bug is of the ZOOKEEPER-2212 class — a decision taken on a
view that is not looked at again, on the rejoin: when a learner's
ACKEPOCH arrives, the leader takes the committed log as the DIFF and
starts forwarding NEW proposals to the learner, but does not queue the
proposals that are in flight at that moment (ZooKeeper's
``Leader.startForwarding`` queues ``outstandingProposals`` under the
same lock). Such a proposal reaches the learner as a bare COMMIT, which
it cannot apply: a committed znode is missing there. With no
interception every learner synchronises long before the client's first
write; under delays the rejoining server's synchronisation slides into
the writes.

Usage: server.py SID LAST_ZXID HOST OUT_DIR PEER[,PEER...]
       HOST = this server's address (election 3888, quorum 2888,
              client 2181 on it, the ZooKeeper ports)
       PEER = sid:host  (the proxy-side address of that peer's ports)
"""

import os
import signal
import socket
import struct
import sys
import threading
import time

ELECTION_PORT, QUORUM_PORT, CLIENT_PORT = 3888, 2888, 2181
ENSEMBLE = 5
QUORUM = ENSEMBLE // 2 + 1
FINALIZE_WAIT_S = 0.2  # FastLeaderElection.finalizeWait
TICK_S = 0.25

LOOKING, FOLLOWING, LEADING = 0, 1, 2
(PROPOSAL, ACK, COMMIT, PING, NEWLEADER, FOLLOWERINFO, UPTODATE, DIFF,
 LEADERINFO, ACKEPOCH) = 2, 3, 4, 5, 10, 11, 12, 13, 17, 18
OP_CREATE, XID_PING = 1, -2


def note(sid, msg):
    sys.stderr.write(f"[zk{sid} {time.monotonic():.3f}] {msg}\n")
    sys.stderr.flush()


def read_exact(conn, n):
    buf = b""
    while len(buf) < n:
        chunk = conn.recv(n - len(buf))
        if not chunk:
            raise OSError("eof")
        buf += chunk
    return buf


def quorum_packet(ptype, zxid, data=None):
    """jute QuorumPacket: type, zxid, data (buffer), authinfo (null)."""
    body = struct.pack(">iq", ptype, zxid)
    body += (struct.pack(">i", -1) if data is None
             else struct.pack(">i", len(data)) + data)
    return body + struct.pack(">i", -1)


def read_quorum_packet(conn):
    ptype, zxid, dlen = struct.unpack(">iqi", read_exact(conn, 16))
    data = read_exact(conn, dlen) if dlen > 0 else b""
    (nauth,) = struct.unpack(">i", read_exact(conn, 4))
    assert nauth <= 0, "authinfo is never sent here"
    return ptype, zxid, data


class Sender:
    """One socket, many writers: whole packets, in order."""

    def __init__(self, sock):
        self.sock, self.lock = sock, threading.Lock()

    def send(self, data):
        with self.lock:
            try:
                self.sock.sendall(data)
            except OSError:
                pass


class Server:
    def __init__(self, sid, last_zxid, host, out_dir, peers):
        self.sid, self.host, self.out_dir = sid, host, out_dir
        self.peers = peers  # sid -> proxy-side host of that peer
        self.lock = threading.RLock()
        # the data: committed transactions in order, and the tree
        self.epoch = last_zxid >> 32
        self.log = [((self.epoch << 32) | i, f"/nmz/pre{i}".encode())
                    for i in range(1, (last_zxid & 0xFFFFFFFF) + 1)]
        self.tree = [path.decode() for _, path in self.log]
        self.last_zxid = last_zxid
        # election
        self.state = LOOKING
        self.vote = (last_zxid, sid)
        self.votes = {sid: self.vote}  # LOOKING peers' votes, and mine
        self.decided_by = {}           # decided peers: sid -> leader
        self.fle_out = {}              # sid -> Sender
        self.changed = time.monotonic()
        self.leader = None
        self.elected = threading.Event()
        # leading
        self.new_epoch = self.epoch + 1
        self.forwarding = []           # Senders of synchronised learners
        self.newleader_acks = {sid: None}  # sid -> Sender, acked
        self.active = threading.Event()
        self.outstanding = {}          # zxid -> [data, acks, reply]
        self.counter = 0
        self.pings = 0                 # answered: the leader's, a session's

    # -- election ---------------------------------------------------------

    def _notification(self):
        zxid, leader = self.vote
        body = struct.pack(">iqqqq", self.state, leader, zxid, 1,
                           self.epoch)
        return struct.pack(">i", len(body)) + body

    def _broadcast(self):
        for out in list(self.fle_out.values()):
            out.send(self._notification())

    def _fle_dial(self, psid, host):
        """Keep one outbound election connection to a peer; a peer that
        is not up yet shows as a socket the proxy closes at once."""
        while True:
            try:
                s = socket.create_connection((host, ELECTION_PORT),
                                             timeout=1.0)
                s.settimeout(None)
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                s.sendall(struct.pack(">q", self.sid))
                out = Sender(s)
                with self.lock:
                    out.send(self._notification())
                    self.fle_out[psid] = out
                while s.recv(4096):  # nothing comes back on this side
                    pass
            except OSError:
                pass
            with self.lock:
                self.fle_out.pop(psid, None)
            time.sleep(0.02)

    def _fle_recv(self, conn):
        try:
            (psid,) = struct.unpack(">q", read_exact(conn, 8))
            while True:
                (flen,) = struct.unpack(">i", read_exact(conn, 4))
                state, leader, zxid, _e, _pe = struct.unpack(
                    ">iqqqq", read_exact(conn, flen)[:36])
                self._on_notification(psid, state, (zxid, leader))
        except (OSError, struct.error):
            conn.close()

    def _on_notification(self, psid, state, vote):
        with self.lock:
            if state == LOOKING:
                if self.state != LOOKING:
                    # a decided server tells a LOOKING peer its leader
                    out = self.fle_out.get(psid)
                    if out is not None:
                        out.send(self._notification())
                    return
                self.votes[psid] = vote
                if vote > self.vote:  # (zxid, sid), lexicographic
                    self.vote = vote
                    self.votes[self.sid] = vote
                    self.changed = time.monotonic()
                    self._broadcast()
            elif self.state == LOOKING:
                self.decided_by[psid] = vote[1]
                named = [ld for ld in self.decided_by.values()
                         if ld == vote[1]]
                if len(named) + 1 >= QUORUM and (
                        vote[1] in self.decided_by or vote[1] == psid):
                    self._decide(vote[1])

    def _decide(self, leader):
        self.leader = leader
        self.state = LEADING if leader == self.sid else FOLLOWING
        self.vote = (self.vote[0], leader)
        note(self.sid, f"elected leader={leader}")
        with open(os.path.join(self.out_dir, f"leader{self.sid}"),
                  "w") as f:
            f.write(str(leader))
        self.elected.set()

    def _elect(self):
        """Decide once a quorum holds my vote and it has stood for
        FINALIZE_WAIT_S."""
        while not self.elected.is_set():
            time.sleep(0.01)
            with self.lock:
                if self.state != LOOKING:
                    return
                agree = sum(1 for v in self.votes.values()
                            if v == self.vote)
                if agree >= QUORUM and (time.monotonic() - self.changed
                                        >= FINALIZE_WAIT_S):
                    self._decide(self.vote[1])

    # -- leading ----------------------------------------------------------

    def _learner(self, conn):
        """LearnerHandler: one follower's connection."""
        self.elected.wait()
        if self.state != LEADING:
            conn.close()
            return
        out = Sender(conn)
        try:
            ptype, _zxid, data = read_quorum_packet(conn)
            assert ptype == FOLLOWERINFO, ptype
            (fsid,) = struct.unpack(">q", data[:8])
            out.send(quorum_packet(LEADERINFO, self.new_epoch << 32,
                                   struct.pack(">i", 0x10000)))
            ptype, peer_last, _ = read_quorum_packet(conn)
            assert ptype == ACKEPOCH, ptype
            with self.lock:
                diff = [t for t in self.log if t[0] > peer_last]
                out.send(quorum_packet(DIFF, self.last_zxid))
                for zxid, txn in diff:
                    out.send(quorum_packet(PROPOSAL, zxid, txn))
                    out.send(quorum_packet(COMMIT, zxid))
                out.send(quorum_packet(NEWLEADER, self.new_epoch << 32))
                # THE BUG: proposals in flight right now are in neither
                # the DIFF nor this learner's queue (module docstring)
                self.forwarding.append(out)
                if self.outstanding:
                    note(self.sid, f"learner {fsid} synchronised with "
                         f"{len(self.outstanding)} proposal(s) in flight")
            while True:
                ptype, zxid, _ = read_quorum_packet(conn)
                if ptype == ACK and zxid == self.new_epoch << 32:
                    self._newleader_ack(fsid, out)
                elif ptype == ACK:
                    self._ack(fsid, zxid)
        except (OSError, AssertionError, struct.error) as e:
            note(self.sid, f"learner connection ended: {e}")
            with self.lock:
                if out in self.forwarding:
                    self.forwarding.remove(out)

    def _newleader_ack(self, fsid, out):
        with self.lock:
            self.newleader_acks[fsid] = out
            if self.active.is_set():
                out.send(quorum_packet(UPTODATE, self.new_epoch << 32))
            elif len(self.newleader_acks) >= QUORUM:
                self.epoch = self.new_epoch
                for o in self.newleader_acks.values():
                    if o is not None:
                        o.send(quorum_packet(UPTODATE,
                                             self.new_epoch << 32))
                note(self.sid, "leading: quorum synchronised")
                self.active.set()

    def propose(self, txn, reply):
        with self.lock:
            self.counter += 1
            zxid = (self.epoch << 32) | self.counter
            self.outstanding[zxid] = [txn, {self.sid}, reply]
            for out in self.forwarding:
                out.send(quorum_packet(PROPOSAL, zxid, txn))

    def _ack(self, fsid, zxid):
        with self.lock:
            if zxid in self.outstanding:
                self.outstanding[zxid][1].add(fsid)
            # commit in zxid order, as the leader's CommitProcessor does
            while self.outstanding:
                first = min(self.outstanding)
                txn, acks, reply = self.outstanding[first]
                if len(acks) < QUORUM:
                    break
                del self.outstanding[first]
                self._apply(first, txn)
                for out in self.forwarding:
                    out.send(quorum_packet(COMMIT, first))
                reply(first)

    def _apply(self, zxid, txn):
        self.log.append((zxid, txn))
        self.tree.append(txn.decode())
        self.last_zxid = zxid

    def _ping_followers(self):
        while True:
            time.sleep(TICK_S)
            with self.lock:
                for out in self.forwarding:
                    out.send(quorum_packet(PING, self.last_zxid))

    # -- following --------------------------------------------------------

    def _follow(self):
        host = self.peers[self.leader]
        while True:
            try:
                s = socket.create_connection((host, QUORUM_PORT),
                                             timeout=1.0)
                break
            except OSError:
                time.sleep(0.02)
        s.settimeout(None)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
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
                    if not in_diff:  # a DIFF's proposals are not acked
                        out.send(quorum_packet(ACK, zxid))
                elif ptype == COMMIT:
                    if zxid in pending:
                        with self.lock:
                            self._apply(zxid, pending.pop(zxid))
                    else:
                        note(self.sid, f"COMMIT {zxid:#x} for a proposal "
                             "never seen: dropped")
                elif ptype == NEWLEADER:
                    in_diff, self.epoch = False, zxid >> 32
                    out.send(quorum_packet(ACK, zxid))
                elif ptype == UPTODATE:
                    note(self.sid, "following: up to date")
                elif ptype == PING:
                    self.pings += 1
                    out.send(quorum_packet(PING, self.last_zxid))
        except (OSError, AssertionError, struct.error) as e:
            note(self.sid, f"leader connection ended: {e}")

    # -- the client port --------------------------------------------------

    def _client(self, conn):
        self.elected.wait()
        if self.state != LEADING:
            conn.close()  # the client of this scenario talks to the leader
            return
        self.active.wait()
        out = Sender(conn)

        def frame(body):
            return struct.pack(">i", len(body)) + body

        try:
            (flen,) = struct.unpack(">i", read_exact(conn, 4))
            read_exact(conn, flen)  # ConnectRequest
            out.send(frame(struct.pack(">iiqi", 0, 4000, 0x1000 + self.sid,
                                       16) + bytes(16)))
            while True:
                (flen,) = struct.unpack(">i", read_exact(conn, 4))
                body = read_exact(conn, flen)
                xid, op = struct.unpack(">ii", body[:8])
                if xid == XID_PING:
                    self.pings += 1
                    out.send(frame(struct.pack(">iqi", XID_PING,
                                               self.last_zxid, 0)))
                    continue
                assert op == OP_CREATE, op
                (plen,) = struct.unpack(">i", body[8:12])
                path = body[12:12 + plen]

                def reply(zxid, xid=xid, path=path):
                    out.send(frame(struct.pack(">iqi", xid, zxid, 0)
                                   + struct.pack(">i", len(path)) + path))

                self.propose(path, reply)
        except (OSError, AssertionError, struct.error):
            conn.close()

    # -- the process ------------------------------------------------------

    def _listen(self, port, handler):
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind((self.host, port))
        srv.listen(16)

        def accept():
            while True:
                try:
                    conn, _ = srv.accept()
                except OSError:
                    return
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                threading.Thread(target=handler, args=(conn,),
                                 daemon=True).start()

        threading.Thread(target=accept, daemon=True).start()

    def dump(self):
        note(self.sid, f"pings answered: {self.pings}")
        with self.lock:
            with open(os.path.join(self.out_dir, f"data{self.sid}"),
                      "w") as f:
                f.write("".join(p + "\n" for p in self.tree))

    def run(self):
        stop = threading.Event()
        signal.signal(signal.SIGTERM, lambda *_: stop.set())
        self._listen(ELECTION_PORT, self._fle_recv)
        self._listen(QUORUM_PORT, self._learner)
        self._listen(CLIENT_PORT, self._client)
        for psid, host in self.peers.items():
            threading.Thread(target=self._fle_dial, args=(psid, host),
                             daemon=True).start()
        threading.Thread(target=self._elect, daemon=True).start()
        while not stop.is_set() and not self.elected.wait(0.05):
            pass
        if self.state == LEADING:
            threading.Thread(target=self._ping_followers,
                             daemon=True).start()
        elif self.state == FOLLOWING:
            threading.Thread(target=self._follow, daemon=True).start()
        stop.wait()
        self.dump()


def main():
    sid, last_zxid = int(sys.argv[1]), int(sys.argv[2], 0)
    peers = dict((int(p.split(":")[0]), p.split(":")[1])
                 for p in sys.argv[5].split(","))
    Server(sid, last_zxid, sys.argv[3], sys.argv[4], peers).run()


if __name__ == "__main__":
    main()
