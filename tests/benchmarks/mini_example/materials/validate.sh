#!/bin/sh
test "$(cat "$NMZ_WORKING_DIR/got")" = "12"
