"""One proxy-inspector process for the whole ensemble: every directed
server pair's election and quorum link and the client's link, one REST
transceiver to the experiment's orchestrator, and per protocol one
stream parser picked by the upstream's port (``zk_parser_for_port``:
3888 FLE, 2888 ZAB, else client), as the upstream inspector keys its
zktraffic sniffers by port.

Usage: proxy.py ORCHESTRATOR_URL LINK[,LINK...]
       LINK = listenHost:upstreamHost:port:srcEntity:dstEntity
"""

import signal as _signal
import sys
import threading

from namazu_tpu.inspector.ethernet import EthernetProxyInspector
from namazu_tpu.inspector.transceiver import new_transceiver
from namazu_tpu.inspector.zookeeper import zk_parser_for_port


def main():
    url = sys.argv[1]
    entity = "_nmz_zk_zab_proxy"
    trans = new_transceiver(url, entity)
    # a parser belongs to an inspector, so one inspector per protocol;
    # all share the transceiver and its entity (the REST action queue is
    # keyed by the event's entity and the transceiver polls its own)
    inspectors = {}
    for spec in sys.argv[2].split(","):
        lhost, uhost, port, src, dst = spec.split(":")
        parser = zk_parser_for_port(int(port))
        inspector = inspectors.get(parser.protocol)
        if inspector is None:
            inspector = inspectors[parser.protocol] = \
                EthernetProxyInspector(trans, entity_id=entity,
                                       parser=parser, action_timeout=30.0)
        inspector.add_link(f"{lhost}:{port}", f"{uhost}:{port}",
                           src_entity=src, dst_entity=dst)
    for inspector in inspectors.values():
        inspector.start()
    print("proxy ready", flush=True)
    stop = threading.Event()
    _signal.signal(_signal.SIGTERM, lambda *_: stop.set())
    try:
        stop.wait()
    except KeyboardInterrupt:
        pass
    finally:
        for inspector in inspectors.values():
            inspector.stop()


if __name__ == "__main__":
    main()
