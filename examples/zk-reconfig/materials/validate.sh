#!/bin/sh
# Oracle: healthy iff the ensemble formed again after every step of the
# scenario (scenario.py writes `formed`), every server ends in the new
# configuration and all name the same leader. A step that never formed
# (`timed_out`: a server wedged in THE BUG) is the reproduction.
W="$NMZ_WORKING_DIR"
[ -f "$W/formed" ] || exit 1
[ -f "$W/timed_out" ] && exit 1
leader=""
for n in 1 2 3 4 5; do
  [ -f "$W/state$n" ] || exit 1
  grep -q "config=200000001" "$W/state$n" || exit 1
  l="$(sed -n 's/.*leader=\([0-9]*\).*/\1/p' "$W/state$n")"
  [ -n "$leader" ] || leader="$l"
  [ "$l" = "$leader" ] || exit 1
done
exit 0
