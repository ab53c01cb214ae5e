#!/bin/bash
# call E: the sweep that brackets the knee (60 s a rate, a drain of 120 s, the dealt order); the rate set to
# twice the knee IN THIS COPY; three control seeds; six seeds; one traced run; then, while the call has the
# time, one parent / change pair of qwen3next_serve_mixed
out=chiprun_out/cE; mkdir -p $out
T_CALL=$(date +%s)
left() { echo $(( ${CALL_S:-3300} - ($(date +%s) - T_CALL) )); }
t0=$(date +%s)
timeout 1500 python3 experiments/chip_calls/pr47_sweep.py --rates ${RATES:-2.5,3,3.5,4,4.5,5} --seconds 60 --drain 120 > $out/sweep.out 2> $out/sweep.err; echo "sweep rc=$? after $(($(date +%s)-t0)) s"
grep "^{" $out/sweep.out | cut -c1-700
grep -v "^W0\|^I0" $out/sweep.err | tail -n 8
python3 - $out/sweep.out <<'P' || exit 1
import json,sys
rows=[json.loads(l) for l in open(sys.argv[1]) if l.startswith("{")]
ok=lambda r: r["met_share"]>=0.9 and r["queue_end"]<=r["queue_mid"]
fails=[r["rate_rps"] for r in rows if not ok(r)]
first_fail=min(fails) if fails else None
holds=[r["rate_rps"] for r in rows if ok(r) and (first_fail is None or r["rate_rps"]<first_fail)]
if not holds: print("NO RATE HELD: stopping"); sys.exit(1)
knee=max(holds)
print("KNEE",knee,"first failing rate",first_fail,"BRACKETED" if first_fail else "NOT BRACKETED")
p="perf/traffic/reason_docs_s128.json"; t=json.load(open(p)); t["tenants"][0]["rate_rps"]=2.0*knee
json.dump(t,open(p,"w"),indent=1); print("rate set to",2.0*knee)
P
for s in 5000000231 5000000243 5000000257; do
  [ $(left) -lt 330 ] && { echo "no time for control $s"; continue; }
  t0=$(date +%s)
  OUT=cE timeout 900 python3 experiments/chip_calls/pr47_control.py --workload ling3_serve_reason --seed $s --seconds 45 --trace 0 > $out/control_$s.out 2> $out/control_$s.err; echo "control $s rc=$? after $(($(date +%s)-t0)) s"
  grep "^CONTROL\|^check" $out/control_$s.out | cut -c1-700; tail -n 1 $out/control_$s.out | cut -c1-600
  grep -v "^W0\|^I0" $out/control_$s.err | tail -n 5
done
n=0
for s in 5000000269 5000000281 5000000293 5000000307 5000000319 5000000331; do
  [ $(left) -lt 240 ] && { echo "no time for seed $s"; continue; }
  echo ". ling3_serve_reason $s 0" > $out/l_$s.txt
  bash experiments/chip_calls/pr47_run.sh cE/set1 $out/l_$s.txt
done
if [ $(left) -gt 260 ]; then
  echo ". ling3_serve_reason 5000000343 1" > $out/l_trace.txt
  bash experiments/chip_calls/pr47_run.sh cE/trace $out/l_trace.txt
  JAX_PLATFORMS=cpu python3 perf/tools/scopes_report.py perf_out/ling3_serve_reason/seed5000000343_trace1 > $out/scopes_report.json 2>/dev/null
  cp perf_out/ling3_serve_reason/seed5000000343_trace1/trace_head.json $out/ 2>/dev/null
fi
for tree in _archive/parent .; do
  [ $(left) -lt 240 ] && { echo "no time for $tree qwen3next"; continue; }
  echo "$tree qwen3next_serve_mixed 5000000357 0" > $out/l_pair.txt
  bash experiments/chip_calls/pr47_run.sh cE/pairs $out/l_pair.txt
done
echo "call E took $(($(date +%s)-T_CALL)) s"
