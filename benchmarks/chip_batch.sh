#!/bin/sh
# Runs of run.py in one chip call: each line of stdin is
#   <label> <workload> <seed> <seconds> <trace>
# The result line goes to stdout with its wall time; stderr's end to chiprun_out/.
mkdir -p chiprun_out
while read label wl seed secs tr; do
  [ -z "$label" ] && continue
  t0=$(date +%s)
  python3 benchmarks/run.py --workload "$wl" --seed "$seed" --seconds "$secs" --trace "$tr" \
      > "chiprun_out/$label.out" 2> "chiprun_out/$label.err"
  rc=$?
  t1=$(date +%s)
  echo "RUN $label rc=$rc wall=$((t1 - t0)) $(tail -n 1 chiprun_out/$label.out | cut -c1-3500)"
  tail -c 1500 "chiprun_out/$label.err" | grep -E "^(memory|compared)" | sed "s/^/  $label /"
  tail -c 3000 "chiprun_out/$label.err" > "chiprun_out/$label.err.tail"; rm -f "chiprun_out/$label.err"
done
