"""A testee for the benchmark's CPU rehearsals: two entities exchange a
dozen messages through the orchestrator's REST endpoint, each message an
interceptable packet event with a flow-qualified hint. It passes when
every message came back; nothing here is timing-sensitive."""

import sys

from namazu_tpu.inspector.transceiver import new_transceiver
from namazu_tpu.signal import PacketEvent


def main() -> int:
    url, out = sys.argv[1], sys.argv[2]
    trans = new_transceiver(url, "mini")
    trans.start()
    got = 0
    try:
        for i in range(12):
            src, dst = ("a", "b") if i % 2 == 0 else ("b", "a")
            ch = trans.send_event(PacketEvent.create(
                "mini", src, dst, bytes([i]), hint=f"msg:{i % 6}"))
            ch.get(timeout=30)
            got += 1
    finally:
        trans.shutdown()
    with open(out, "w") as f:
        f.write(str(got))
    return 0


if __name__ == "__main__":
    sys.exit(main())
