#!/bin/bash
# PR 48's chip calls, one script: `kda_step` alone in every form tried (pr48_kda_step_time.py; KERNEL=0 skips it),
# then parent (_archive/parent = git archive of 137b279) against change (_archive/change = git archive $(git
# write-tree): the committed files alone): `ling3_serve_reason` (parent, change, change, parent, a seed a pair),
# one traced run a side on one seed, the guard `qwen3next_serve_mixed` (it shares ops/gdn.py), six more seeds of
# the change, two more pairs. LISTS: which lists, in order; a line "<tree> <cell> <seed> <trace>" goes to
# pr47_run.sh. Chips were scarce (call 1 alone was refused for want of a free chip), hence everything in one call.
out=chiprun_out/${OUT:-p48}; mkdir -p $out
T_CALL=$(date +%s)
left() { echo $(( ${CALL_S:-3300} - ($(date +%s) - T_CALL) )); }
if [ "${KERNEL:-1}" = 1 ]; then   # the archived tree's script and package (it puts its own root first on sys.path)
  python3 _archive/change/experiments/chip_calls/pr48_kda_step_time.py 2> $out/kernel.err | tee $out/kernel.jsonl
  grep -v "^W0\|^I0" $out/kernel.err | tail -n 5; echo "kernel timing done at $(($(date +%s)-T_CALL)) s"
fi
P=_archive/parent; C=_archive/change; L=ling3_serve_reason; Q=qwen3next_serve_mixed
pairs="$P $L 4800000011 0|$C $L 4800000011 0|$C $L 4800000023 0|$P $L 4800000023 0|$P $L 4800000037 0|$C $L 4800000037 0"
traced="$C $L 4800000041 1|$P $L 4800000041 1"
guard="$P $Q 4800000053 0|$C $Q 4800000053 0"
more="$C $L 4800000059 0|$P $L 4800000059 0|$P $L 4800000061 0|$C $L 4800000061 0"
six="$C $L 4800000067 0|$C $L 4800000071 0|$C $L 4800000073 0|$C $L 4800000079 0|$C $L 4800000083 0|$C $L 4800000089 0"
for list in ${LISTS:-pairs traced guard six more}; do
  IFS='|' read -ra runs <<< "${!list}"
  for run in "${runs[@]}"; do
    [ $(left) -lt 300 ] && { echo "no time for: $run"; continue; }
    echo "$run" > $out/l_one.txt
    bash experiments/chip_calls/pr47_run.sh ${OUT:-p48}/$list $out/l_one.txt
  done
done
echo "call took $(($(date +%s)-T_CALL)) s"
