"""One ZooKeeper client session on the real client wire format:
ConnectRequest, then ``create`` requests for N znodes with at most
WINDOW outstanding (the asynchronous API's pipelining), session pings
every ``PING_S``. Writes the paths the server acknowledged, one per
line, to OUT_FILE: what the oracle holds every server's tree against.

run.sh starts the session a second after the ensemble; the leader
serves it as soon as a quorum has synchronised.

Usage: client.py HOST:PORT N_WRITES WINDOW OUT_FILE
"""

import socket
import struct
import sys
import threading
import time

PING_S = 0.25
OP_CREATE, OP_PING, XID_PING = 1, 11, -2


def frame(body):
    return struct.pack(">i", len(body)) + body


def read_frame(sock):
    def exact(n):
        buf = b""
        while len(buf) < n:
            chunk = sock.recv(n - len(buf))
            if not chunk:
                raise OSError("eof")
            buf += chunk
        return buf

    (flen,) = struct.unpack(">i", exact(4))
    return exact(flen)


def ustring(s):
    return struct.pack(">i", len(s)) + s


def create_request(xid, path):
    """CreateRequest: path, data, acl (world:anyone, all), flags."""
    acl = struct.pack(">ii", 1, 31) + ustring(b"world") + ustring(b"anyone")
    return frame(struct.pack(">ii", xid, OP_CREATE) + ustring(path)
                 + ustring(b"x" * 16) + acl + struct.pack(">i", 0))


def main():
    host, port = sys.argv[1].rsplit(":", 1)
    n_writes, window, out_file = (int(sys.argv[2]), int(sys.argv[3]),
                                  sys.argv[4])
    # a server that is not up yet shows as a socket the proxy closes at
    # once: dial again until a ConnectResponse comes back
    while True:
        try:
            s = socket.create_connection((host, int(port)), timeout=1.0)
            s.settimeout(60.0)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            # ConnectRequest: protocolVersion, lastZxidSeen, timeOut,
            # sessionId, passwd
            s.sendall(frame(struct.pack(">iqiqi", 0, 0, 4000, 0, 16)
                            + bytes(16)))
            read_frame(s)  # ConnectResponse
            break
        except OSError:
            time.sleep(0.02)
    lock = threading.Lock()
    done = threading.Event()

    def pinger():
        while not done.wait(PING_S):
            with lock:
                s.sendall(frame(struct.pack(">ii", XID_PING, OP_PING)))

    threading.Thread(target=pinger, daemon=True).start()
    acked, sent = [], 0
    paths = [f"/nmz/n{i:03d}".encode() for i in range(n_writes)]
    while len(acked) < n_writes:
        while sent < n_writes and sent - len(acked) < window:
            with lock:
                s.sendall(create_request(sent + 1, paths[sent]))
            sent += 1
        body = read_frame(s)
        xid, _zxid, err = struct.unpack(">iqi", body[:16])
        if xid == XID_PING:
            continue
        assert err == 0 and xid == len(acked) + 1, (xid, err)
        acked.append(paths[xid - 1].decode())
    done.set()
    with open(out_file, "w") as f:
        f.write("".join(p + "\n" for p in acked))
    s.close()


if __name__ == "__main__":
    main()
