#!/bin/bash
# call A: parent on the new cell (must fail at once); one run of the change; the sweep; the rate set to
# twice the knee IN THIS COPY; the controls on one seed; one traced run; six seeds
out=chiprun_out/cA; mkdir -p $out
T_CALL=$(date +%s)
root=$(pwd)
t0=$(date +%s)
(cd _archive/parent && timeout 600 python3 perf/run.py --workload ling3_serve_reason --seed 11 --seconds 45 --trace 0 > $root/$out/parent.out 2> $root/$out/parent.err; echo "parent rc=$? after $(($(date +%s)-t0)) s")
tail -n 3 $out/parent.out; grep -v "^W0\|^I0" $out/parent.err | tail -n 4
echo ". ling3_serve_reason 5000000017 0" > $out/l1.txt
bash experiments/chip_calls/pr47_run.sh cA/first $out/l1.txt
grep -q '"correct": true' chiprun_out/cA/first/*.json || { echo "FIRST RUN NOT CORRECT: stopping"; cat chiprun_out/cA/first/*.errtail | tail -n 30; cat chiprun_out/cA/first/*.log | tail -n 30; exit 1; }
cp perf/traffic/reason_docs_s128.json $out/traffic_before.json
sed -i 's/"serve_state_latent_by_leaf"/"serve_by_leaf"/' perf/traffic/reason_docs_s128.json
t0=$(date +%s)
timeout 1500 python3 perf/tools/by_leaf.py sweep --workload ling3_serve_reason --rates ${RATES:-3,4,5,6} --seconds 20 > $out/sweep.out 2> $out/sweep.err; echo "sweep rc=$? after $(($(date +%s)-t0)) s"
grep "^{" $out/sweep.out | cut -c1-600
grep -v "^W0\|^I0" $out/sweep.err | tail -n 5
cp $out/traffic_before.json perf/traffic/reason_docs_s128.json
python3 - $out/sweep.out <<'P'
import json,sys
rows=[json.loads(l) for l in open(sys.argv[1]) if l.startswith("{")]
knee=None
for r in rows:
    if r["met_share"]>=0.9 and r["queue_end"]<=r["queue_mid"]: knee=r["rate_rps"]
knee=knee or 4.0
p="perf/traffic/reason_docs_s128.json"; t=json.load(open(p)); t["tenants"][0]["rate_rps"]=2.0*knee
json.dump(t,open(p,"w"),indent=1); print("KNEE",knee,"rate set to",2.0*knee)
P
t0=$(date +%s)
OUT=cA timeout 900 python3 experiments/chip_calls/pr47_control.py --workload ling3_serve_reason --seed 5000000029 --seconds 45 --trace 0 > $out/control.out 2> $out/control.err; echo "control rc=$? after $(($(date +%s)-t0)) s"
grep "^CONTROL\|^check\|^reference" $out/control.out | cut -c1-1500; tail -n 1 $out/control.out | cut -c1-400
grep -v "^W0\|^I0" $out/control.err | tail -n 5
echo ". ling3_serve_reason 5000000039 1" > $out/l2.txt
bash experiments/chip_calls/pr47_run.sh cA/trace $out/l2.txt
JAX_PLATFORMS=cpu python3 perf/tools/scopes_report.py perf_out/ling3_serve_reason/seed5000000039_trace1 > $out/scopes_report.json 2>/dev/null
cp perf_out/ling3_serve_reason/seed5000000039_trace1/trace_head.json $out/ 2>/dev/null
# six seeds where the call has the time for them (a run is ~4 min), else three
if [ $(($(date +%s)-T_CALL)) -lt 1500 ]; then seeds="5000000051 5000000063 5000000077 5000000089 5000000101 5000000113"; else seeds="5000000051 5000000063 5000000077"; fi
for s in $seeds; do echo ". ling3_serve_reason $s 0"; done > $out/l3.txt
bash experiments/chip_calls/pr47_run.sh cA/set1 $out/l3.txt
