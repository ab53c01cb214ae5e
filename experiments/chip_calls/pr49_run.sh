#!/bin/bash
# usage: pr49_run.sh <out dir> <list file> with lines "<tree> <cell> <seed> <trace> <entry>" (a FILE: the chip
# tool's stdin stays open and silent); <entry> is `run` (perf/run.py) or `recorder` (pr49_recorder.py: a
# TraceRecorder attached, no profiler). After a traced run: perf/tools/annots_report.py on its xplane (CPU).
# A run is skipped where fewer than 300 s of the call's CALL_S (default 3300) are left.
out=chiprun_out/$1; mkdir -p $out
root=$(pwd)
T_CALL=${T_CALL:-$(date +%s)}
while read tree cell seed trace entry; do
  [ -z "$tree" ] && continue
  [ $(( ${CALL_S:-3300} - ($(date +%s) - T_CALL) )) -lt 300 ] && { echo "no time for $tree $cell $seed $trace $entry"; continue; }
  tag=$(echo $tree | tr '/.' '__')_${cell}_${seed}_t${trace}_${entry}
  cmd="perf/run.py"; [ "$entry" = recorder ] && cmd="$root/experiments/chip_calls/pr49_recorder.py"
  t0=$(date +%s)
  (cd $tree && timeout 900 python3 $cmd --workload $cell --seed $seed --seconds 45 --trace $trace > $root/$out/$tag.full 2> $root/$out/$tag.err; echo "rc=$?" >> $root/$out/$tag.full)
  tail -n 2 $out/$tag.full | head -n 1 > $out/$tag.json
  grep -v '^{' $out/$tag.full | tail -n 40 > $out/$tag.log
  grep -v "^W0\|^I0" $out/$tag.err | tail -n 15 > $out/$tag.errtail; rm -f $out/$tag.err $out/$tag.full
  echo "$tag $(($(date +%s)-t0))s $(tail -n 1 $out/$tag.log) $(grep '^check' $out/$tag.log | cut -c1-110 | tr '\n' '|')"
  grep "^recorder:" $out/$tag.errtail
  python3 - $out/$tag.json <<'P'
import json,sys
try:
    r=json.load(open(sys.argv[1])); m=r["metrics"]
    print("   correct",r["correct"],"failed",r.get("failed"),{k:round(v["value"],4) for k,v in m.items()}, "peak",r["device"].get("memory_peak_bytes"),"busy_s",r["device"].get("busy_s"),"window_s",r["device"].get("window_s"))
except Exception as e: print("   no result:",e)
P
  python3 - $tree/perf_out/$cell/seed${seed}_trace${trace}/series.json <<'P'
import json,sys
try:
    s=json.load(open(sys.argv[1])); k=s.get("ticks",[])
    print("   series", s["metrics"], "ticks", len(k), "longest tick s", round(max((t["dt"] for t in k), default=0), 3),
          "end of tick 50 / 100 / 140 s", [round(k[i]["t"] + k[i]["dt"], 4) for i in (49, 99, 139) if i < len(k)],
          "slots of ticks 50 / 100 / 140", [k[i]["slots"] for i in (49, 99, 139) if i < len(k)])
except Exception as e: print("   no series:",e)
P
  if [ "$trace" = 1 ] && [ -f perf/tools/annots_report.py ]; then
    JAX_PLATFORMS=cpu timeout 600 python3 perf/tools/annots_report.py $tree/perf_out/$cell/seed${seed}_trace1 > $out/$tag.annots.json 2> $out/$tag.annots.err
    python3 - $out/$tag.annots.json <<'P'
import json,sys
try:
    r=json.load(open(sys.argv[1]))
    print("   annots", {k:r[k] for k in ("reader_s","window_s","decode_burst","prefill","admissions","ticks","readers")}, "slow_ticks", r["slow_ticks"][:3])
    print("   names", {k:v["events"] for k,v in r["names"].items()})
except Exception as e: print("   no annots report:",e)
P
  fi
done < $2
