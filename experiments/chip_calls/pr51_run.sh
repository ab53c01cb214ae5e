#!/bin/bash
# usage: pr51_run.sh <out dir> <list file> with lines "<tree> <cell> <seed> <trace> <entry>" (a FILE: the chip tool's
# stdin stays open and silent); <entry> is `run` (perf/run.py) or `kib<N>` (pr51_step_bytes.py N: `ssm_step`'s cell
# set to N KiB). pr49_run.sh without the recorder and the series: a line a run with its itemised set-up's
# `warm_engine` lines, `serve_tok_s`, `setup_s`, and for a traced run the metrics this PR predicts.
# A run is skipped where fewer than 240 s of the call's CALL_S (default 3300) are left.
out=chiprun_out/$1; mkdir -p $out
root=$(pwd)
T_CALL=${T_CALL:-$(date +%s)}
while read tree cell seed trace entry; do
  [ -z "$tree" ] && continue
  [ $(( ${CALL_S:-3300} - ($(date +%s) - T_CALL) )) -lt 240 ] && { echo "no time for $tree $cell $seed $trace $entry"; continue; }
  tag=$(echo $tree | tr '/.' '__')_${cell}_${seed}_t${trace}_${entry}
  cmd="perf/run.py"; [ "${entry#kib}" != "$entry" ] && cmd="$root/experiments/chip_calls/pr51_step_bytes.py ${entry#kib}"
  t0=$(date +%s)
  (cd $tree && timeout 900 python3 $cmd --workload $cell --seed $seed --seconds 45 --trace $trace > $root/$out/$tag.full 2> $root/$out/$tag.err; echo "rc=$?" >> $root/$out/$tag.full)
  tail -n 2 $out/$tag.full | head -n 1 > $out/$tag.json
  grep -v '^{' $out/$tag.full | tail -n 40 > $out/$tag.log
  grep -v "^W0\|^I0" $out/$tag.err | tail -n 15 > $out/$tag.errtail; rm -f $out/$tag.err $out/$tag.full
  echo "$tag $(($(date +%s)-t0))s $(tail -n 1 $out/$tag.log) | $(grep 'decode burst\|TOTAL' $out/$tag.log | cut -c7-16 | tr '\n' ' ')"
  python3 - $out/$tag.json <<'P'
import json,sys
try:
    r=json.load(open(sys.argv[1])); m={k:v["value"] for k,v in r["metrics"].items()}
    keep=("serve_tok_s","setup_s","flood_ssm_step_roofline","flood_decode_step_dev_ms","flood_ssm_dev_pct","flood_mixer_dev_pct",
          "flood_moe_dev_pct","flood_mlp_dev_pct","flood_moe_gmm_roofline","flood_paged_decode_roofline","flood_prefill_dev_ms_p50",
          "flood_tick_readback_ms","flood_tick_max_ms","flood_slots_decoding_pct","flood_compiles_in_window")
    print("   correct",r["correct"],"failed",r.get("failed"),{k:round(m[k],4) for k in keep if k in m},"peak",r["device"].get("memory_peak_bytes"),
          "busy_s",r["device"].get("busy_s"),"window_s",r["device"].get("window_s"))
    ops=r.get("breakdown",{}).get("device_ops")
    if ops: print("   device_ops", [(k, round(v, 3)) for k, v in ops[:6]])
except Exception as e: print("   no result:",e)
P
done < $2
