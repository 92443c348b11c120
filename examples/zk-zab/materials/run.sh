#!/bin/sh
# 5-server miniature ZooKeeper ensemble and one client through the proxy
# inspector. Server N listens on 127.0.0.N at ZooKeeper's own ports
# (3888 election, 2888 quorum, 2181 client); each ordered pair (src,dst)
# reaches dst through a proxied address of its own, 127.0.<src>.<dst>,
# and the client reaches the leader through 127.0.9.4, so every FLE, ZAB
# and client message crosses the orchestrator exactly once and the
# parser is picked by the port, as upstream's inspector does.
PORT="${NMZ_REST_PORT:-10985}"
URL="http://127.0.0.1:${PORT}"
OUT="$NMZ_WORKING_DIR"
M="$NMZ_MATERIALS_DIR"
# the session's creates: 100 unless the config's run line says otherwise
# (config_w21.toml: the benchmark's live cut, ../../README.md "zk-zab")
WRITES="${NMZ_ZAB_WRITES:-100}"
WINDOW=8

links="127.0.9.4:127.0.0.4:2181:client:zk4"
for s in 1 2 3 4 5; do
  for d in 1 2 3 4 5; do
    [ "$s" = "$d" ] && continue
    links="$links,127.0.$s.$d:127.0.0.$d:3888:zk$s:zk$d"
    links="$links,127.0.$s.$d:127.0.0.$d:2888:zk$s:zk$d"
  done
done
python "$M/proxy.py" "$URL" "$links" > "$OUT/proxy.log" 2>&1 &
proxy_pid=$!

# wait for the listeners; a dead proxy is an infra error, not a bug
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

peers_of() {
  p=""
  for d in 1 2 3 4 5; do
    [ "$1" = "$d" ] && continue
    p="$p${p:+,}$d:127.0.$1.$d"
  done
  echo "$p"
}

# servers 1-4 hold the newest transactions; server 5 missed the last
# two and restarts late (the rejoining server): by the scenario's one
# timing knob, $NMZ_CALIB_REJOIN_DELAY_MS ([calibration] in
# ../config.toml) — the later it rejoins, the more often a write is in
# flight while it synchronises
pids=""
for s in 1 2 3 4; do
  python "$M/server.py" "$s" 0x100000002 "127.0.0.$s" "$OUT" \
    "$(peers_of "$s")" > "$OUT/server$s.log" 2>&1 &
  pids="$pids $!"
done
ms="${NMZ_CALIB_REJOIN_DELAY_MS:-500}"
( sleep "$((ms / 1000)).$(printf %03d $((ms % 1000)))"
  exec python "$M/server.py" 5 0x100000000 127.0.0.5 "$OUT" "$(peers_of 5)" \
    > "$OUT/server5.log" 2>&1 ) &
pids="$pids $!"
# the client's session starts a second after the ensemble: an
# undisturbed ensemble, the rejoined server included, is long up by then
sleep 1
python "$M/client.py" 127.0.9.4:2181 "$WRITES" "$WINDOW" "$OUT/acked" \
  > "$OUT/client.log" 2>&1 &
client_pid=$!

# a crashed client is an infra error, not a bug repro: propagate it so
# the runner aborts without recording (same guard as the proxy above)
rc=0
wait "$client_pid" || rc=1
# the last COMMITs are still in the proxy (at most one delay deep)
sleep 0.6
kill $pids 2>/dev/null
for p in $pids; do
  wait "$p" || rc=1
done
kill "$proxy_pid" 2>/dev/null
wait "$proxy_pid" 2>/dev/null
if [ "$rc" != "0" ]; then
  echo "a testee process failed:" >&2
  for f in "$OUT"/client.log "$OUT"/server*.log; do
    echo "== $f" >&2
    sed -n '$p' "$f" >&2
  done
fi
exit "$rc"
