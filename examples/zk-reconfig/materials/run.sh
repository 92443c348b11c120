#!/bin/sh
# Five reconfiguring miniature ZooKeeper servers behind the proxy
# inspector, which sees the ELECTION port only (upstream's
# zk_inspector.py hooks the FLE ports and nothing else): server N
# listens on 127.1.0.N (3888 election, 2888 quorum), each ordered pair
# (src,dst) reaches dst's election port through a proxied address of its
# own, 127.1.<src>.<dst>, and its quorum port directly. scenario.py
# plays the restarts and the reconfiguration.
PORT="${NMZ_REST_PORT:-10986}"
URL="http://127.0.0.1:${PORT}"
OUT="$NMZ_WORKING_DIR"
M="$NMZ_MATERIALS_DIR"

links=""
for s in 1 2 3 4 5; do
  for d in 1 2 3 4 5; do
    [ "$s" = "$d" ] && continue
    links="$links${links:+,}127.1.$s.$d:127.1.0.$d:3888:zk$s:zk$d"
  done
done
# zk-zab's proxy, not a copy: one inspector process, the parser picked
# by the port
ZAB_MATERIALS="$(python -c 'import namazu_tpu, os; print(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(namazu_tpu.__file__))), "examples", "zk-zab", "materials"))')"
python "$ZAB_MATERIALS/proxy.py" "$URL" "$links" > "$OUT/proxy.log" 2>&1 &
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

rc=0
python "$M/scenario.py" "$M" "$OUT" > "$OUT/scenario.log" 2>&1 || rc=1
kill "$proxy_pid" 2>/dev/null
wait "$proxy_pid" 2>/dev/null
if [ "$rc" != "0" ]; then
  echo "the scenario failed to play:" >&2
  sed -n '$p' "$OUT/scenario.log" >&2
fi
exit "$rc"
