#!/bin/sh
# Run the flaky test under the proc inspector, which reports the process
# tree to the orchestrator (REST) and applies the policy's scheduler
# attributes.
PORT="${NMZ_REST_PORT:-10980}"
python -m namazu_tpu.cli inspectors proc \
    --orchestrator-url "http://127.0.0.1:${PORT}" \
    --entity-id racy \
    --watch-interval 0.01 \
    --cmd "python \"$NMZ_MATERIALS_DIR/racy.py\" \"$NMZ_WORKING_DIR\""
rc=$?
echo "$rc" > "$NMZ_WORKING_DIR/rc.txt"
exit 0
