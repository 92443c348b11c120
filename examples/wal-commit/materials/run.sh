#!/bin/sh
# WAL-commit race under the LD_PRELOAD fs interposer.
# The writer runs with the interposer preloaded: its mkdir/create calls
# become deferred FilesystemEvents through the guest-agent endpoint; the
# reader runs clean.
PORT="${NMZ_AGENT_PORT:-10981}"
LIB=$(python -c 'import namazu_tpu, os; print(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(namazu_tpu.__file__))), "native", "build", "libnmz_fs_interpose.so"))')
WAL="$NMZ_WORKING_DIR/wal"
mkdir -p "$WAL"

env LD_PRELOAD="$LIB" \
    NMZ_TPU_AGENT_ADDR="127.0.0.1:${PORT}" \
    NMZ_TPU_ENTITY_ID=waldb-writer \
    NMZ_TPU_FS_ROOT="$WAL" \
    python "$NMZ_MATERIALS_DIR/writer.py" "$WAL" &
writer_pid=$!

python "$NMZ_MATERIALS_DIR/reader.py" "$WAL"
rc=$?
echo "$rc" > "$NMZ_WORKING_DIR/rc.txt"
kill "$writer_pid" 2>/dev/null
wait "$writer_pid" 2>/dev/null
exit 0
