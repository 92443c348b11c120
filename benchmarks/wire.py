"""The sidecar's framed wire, client side: a 4-byte little-endian length
and a JSON body per frame (``namazu_tpu/endpoint/agent.py``). Kept here
so the harness's parent talks to the sidecar without importing the
program."""

from __future__ import annotations

import json
import socket
import struct
from typing import Optional

MAX_FRAME = 64 << 20


def write_frame(sock: socket.socket, doc: dict) -> None:
    data = json.dumps(doc).encode()
    sock.sendall(struct.pack("<I", len(data)) + data)


def _read_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return bytes(buf)


def read_frame(sock: socket.socket) -> Optional[dict]:
    header = _read_exact(sock, 4)
    if header is None:
        return None
    (length,) = struct.unpack("<I", header)
    if length > MAX_FRAME:
        raise ConnectionError(f"frame of {length} bytes")
    body = _read_exact(sock, length)
    return None if body is None else json.loads(body)


def connect(addr: str, timeout: float) -> socket.socket:
    host, _, port = addr.rpartition(":")
    return socket.create_connection((host or "127.0.0.1", int(port)),
                                    timeout=timeout)


def request(addr: str, doc: dict, timeout: float = 600.0) -> dict:
    """One request/response on a connection of its own, as the policy's
    end-of-run request is sent."""
    with connect(addr, timeout) as s:
        write_frame(s, doc)
        resp = read_frame(s)
    if resp is None:
        raise ConnectionError(f"sidecar {addr}: connection closed")
    return resp


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
