#!/bin/bash
# usage: pr47_run.sh <out dir> <list file> with lines "<tree> <cell> <seed> <trace>" (a FILE: the chip tool's stdin stays open and silent)
out=chiprun_out/$1; mkdir -p $out
root=$(pwd)
while read tree cell seed trace; do
  [ -z "$tree" ] && continue
  tag=$(echo $tree | tr '/.' '__')_${cell}_${seed}_t${trace}
  t0=$(date +%s)
  (cd $tree && timeout 900 python3 perf/run.py --workload $cell --seed $seed --seconds 45 --trace $trace > $root/$out/$tag.full 2> $root/$out/$tag.err; echo "rc=$?" >> $root/$out/$tag.full)
  tail -n 2 $out/$tag.full | head -n 1 > $out/$tag.json
  grep -v '^{' $out/$tag.full | tail -n 40 > $out/$tag.log
  grep -v "^W0\|^I0" $out/$tag.err | tail -n 15 > $out/$tag.errtail; rm -f $out/$tag.err $out/$tag.full
  echo "$tag $(($(date +%s)-t0))s $(grep '^check' $out/$tag.log | cut -c1-110 | tr '\n' '|')"
  python3 - $out/$tag.json <<'P'
import json,sys
try:
    r=json.load(open(sys.argv[1])); m=r["metrics"]
    print("   correct",r["correct"],"failed",r.get("failed"),{k:round(v["value"],4) for k,v in m.items()}, "peak",r["device"].get("memory_peak_bytes"),"busy_s",r["device"].get("busy_s"),"window_s",r["device"].get("window_s"))
    b=r.get("breakdown",{})
    if b: print("   ops",[(k,round(v,4)) for k,v in b.get("device_ops",[])]); print("   idle",[(k,round(v,4)) for k,v in b.get("idle_gaps",[])][:8])
except Exception as e: print("   no result:",e)
P
done < $2
