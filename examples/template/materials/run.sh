#!/bin/sh
# Send two messages through the orchestrator and record the order the
# policy released them in.
PORT="${NMZ_REST_PORT:-10983}"
python "$NMZ_MATERIALS_DIR/pingpong.py" \
    "http://127.0.0.1:${PORT}" "$NMZ_WORKING_DIR/order.txt"
