#!/bin/sh
PORT="${NMZ_REST_PORT:-10967}"
exec python "$NMZ_MATERIALS_DIR/testee.py" "http://127.0.0.1:${PORT}" "$NMZ_WORKING_DIR/got"
