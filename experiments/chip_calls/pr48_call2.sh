#!/bin/bash
# PR 48, second call: the one run of call 1 that read 7% under its eleven siblings (change, seed 4800000059:
# 3,783.0 tokens/s) again with its tick series brought back, and the pair call 1 had no time for (seed 4800000061).
out=chiprun_out/p48b; mkdir -p $out
C=_archive/change; P=_archive/parent; L=ling3_serve_reason
for run in "$C $L 4800000059 0" "$P $L 4800000061 0" "$C $L 4800000061 0"; do
  echo "$run" > $out/l_one.txt
  bash experiments/chip_calls/pr47_run.sh p48b $out/l_one.txt
  set -- $run
  cp $1/perf_out/$2/seed$3_trace0/series.json $out/$(basename $1)_$3_series.json
done
