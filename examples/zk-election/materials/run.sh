#!/bin/sh
# 3-node miniature FLE election through the proxy inspector.
# Node listen ports 21281-21283; each ordered pair (src,dst) gets a
# dedicated proxied link on 22000+10*src+dst -> dst's listener, so every
# notification crosses the orchestrator exactly once.
PORT="${NMZ_REST_PORT:-10982}"
URL="http://127.0.0.1:${PORT}"
OUT="$NMZ_WORKING_DIR"

python "$NMZ_MATERIALS_DIR/proxy.py" "$URL" \
  "22012:21282:zk1:zk2,22013:21283:zk1:zk3,22021:21281:zk2:zk1,22023:21283:zk2:zk3,22031:21281:zk3:zk1,22032:21282:zk3:zk2" \
  > "$OUT/proxy.log" 2>&1 &
proxy_pid=$!

# wait for the six listeners; a dead proxy is an infra error, not a bug
# repro — exit non-zero so the runner aborts without recording
ready=0
i=0
while [ $i -lt 100 ]; do
  if grep -q "proxy ready" "$OUT/proxy.log" 2>/dev/null; then ready=1; break; fi
  if ! kill -0 "$proxy_pid" 2>/dev/null; then break; fi
  i=$((i + 1)); sleep 0.1
done
if [ "$ready" != "1" ]; then
  echo "proxy failed to start:" >&2
  cat "$OUT/proxy.log" >&2
  kill "$proxy_pid" 2>/dev/null
  exit 1
fi

# peers are addressed through the proxy ports; node 3 carries the newest
# zxid and starts 120ms late (a restarting node)
python "$NMZ_MATERIALS_DIR/node.py" 1 0x100 21281 \
  "$OUT/leader1" "2:127.0.0.1:22012,3:127.0.0.1:22013" \
  > "$OUT/node1.log" 2>&1 &
n1=$!
python "$NMZ_MATERIALS_DIR/node.py" 2 0x100 21282 \
  "$OUT/leader2" "1:127.0.0.1:22021,3:127.0.0.1:22023" \
  > "$OUT/node2.log" 2>&1 &
n2=$!
( sleep 0.12
  python "$NMZ_MATERIALS_DIR/node.py" 3 0x300 21283 \
    "$OUT/leader3" "1:127.0.0.1:22031,2:127.0.0.1:22032" \
    > "$OUT/node3.log" 2>&1 ) &
n3=$!

# a crashed node is an infra error, not a bug repro: propagate it so the
# runner aborts without recording (same guard as the proxy above)
rc=0
wait "$n1" || rc=1
wait "$n2" || rc=1
wait "$n3" || rc=1
kill "$proxy_pid" 2>/dev/null
wait "$proxy_pid" 2>/dev/null
if [ "$rc" != "0" ]; then
  echo "a node process failed:" >&2
  tail -5 "$OUT"/node*.log >&2
fi
exit "$rc"
