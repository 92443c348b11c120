#!/bin/sh
# Oracle: healthy iff every server elected server 4 (the highest sid
# among those with the newest zxid) and every znode the client was
# acknowledged is in every server's tree, the rejoined server's
# included. Leader files that disagree, or a committed znode missing on
# a server, is the bug.
W="$NMZ_WORKING_DIR"
[ -s "$W/acked" ] || exit 1
for n in 1 2 3 4 5; do
  [ -f "$W/leader$n" ] || exit 1
  [ "$(cat "$W/leader$n")" = "4" ] || exit 1
  [ -f "$W/data$n" ] || exit 1
  while read -r path; do
    grep -qx "$path" "$W/data$n" || exit 1
  done < "$W/acked"
done
exit 0
