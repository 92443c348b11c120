#!/bin/sh
# Two clients race an unguarded read-modify-write on a KV server; every
# HTTP message crosses the orchestrator once (one proxied link per
# client).
PORT="${NMZ_REST_PORT:-10983}"
URL="http://127.0.0.1:${PORT}"
OUT="$NMZ_WORKING_DIR"

python "$NMZ_MATERIALS_DIR/server.py" 23300 \
  > "$OUT/server.log" 2>&1 &
srv_pid=$!

python "$NMZ_MATERIALS_DIR/proxy.py" "$URL" \
  "23311:23300:c1:kv,23312:23300:c2:kv" > "$OUT/proxy.log" 2>&1 &
proxy_pid=$!

ready() { grep -q "$2" "$1" 2>/dev/null; }
i=0
while [ $i -lt 100 ]; do
  if ready "$OUT/server.log" "kv ready" && ready "$OUT/proxy.log" "proxy ready"; then
    break
  fi
  # a dead server/proxy is an infra error: stop waiting immediately
  if ! kill -0 "$srv_pid" 2>/dev/null || ! kill -0 "$proxy_pid" 2>/dev/null; then
    i=100; break
  fi
  i=$((i + 1)); sleep 0.1
done
if [ $i -ge 100 ]; then
  echo "server/proxy failed to start" >&2
  cat "$OUT/server.log" "$OUT/proxy.log" >&2
  kill "$srv_pid" "$proxy_pid" 2>/dev/null
  exit 1
fi

# one interpreter drives both clients from threads (client.py): the
# 180 ms stagger sits on one clock, so uninspected runs are always
# serialized and the only reordering force is the policy's deferrals
rc=0
python "$NMZ_MATERIALS_DIR/client.py" \
  23311 23312 0.18 || rc=1

# read the final value DIRECTLY from the server (uninspected path); a
# failed read is an infra error, not a repro — abort without recording
if ! python - "$OUT/final" <<'EOF'
import http.client, sys
c = http.client.HTTPConnection("127.0.0.1", 23300, timeout=10)
c.request("GET", "/kv")
open(sys.argv[1], "w").write(c.getresponse().read().decode())
EOF
then
  echo "could not read the final value from the server" >&2
  kill "$srv_pid" "$proxy_pid" 2>/dev/null
  exit 1
fi

kill "$srv_pid" "$proxy_pid" 2>/dev/null
wait "$srv_pid" 2>/dev/null
wait "$proxy_pid" 2>/dev/null
if [ "$rc" != "0" ]; then
  echo "a client failed:" >&2
  exit 1
fi
exit 0
