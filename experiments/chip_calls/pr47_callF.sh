#!/bin/bash
# call F: from _archive/change (= git archive $(git write-tree): the committed files alone) a second set of six
# seeds of the cell at the committed rate and limits; then parent / change pairs of the three cells that share
# most code with it (parent, change, change, parent), while the call has the time (CELLS: which, in order)
out=chiprun_out/cF; mkdir -p $out
T_CALL=$(date +%s)
left() { echo $(( ${CALL_S:-3300} - ($(date +%s) - T_CALL) )); }
for s in ${SEEDS:-5000000369 5000000381 5000000393 5000000407 5000000419 5000000431}; do
  [ $(left) -lt 240 ] && { echo "no time for seed $s"; continue; }
  echo "_archive/change ling3_serve_reason $s 0" > $out/l_$s.txt
  bash experiments/chip_calls/pr47_run.sh cF/set2 $out/l_$s.txt
done
seed=5000000443
for cell in ${CELLS:-kanana2_serve_docs smallthinker_serve_shortlong qwen3next_serve_mixed}; do
  # parent, change, change, parent: a seed a pair
  for pair in "_archive/parent _archive/change $seed" "_archive/change _archive/parent $((seed+6))"; do
    set -- $pair
    for tree in $1 $2; do
      [ $(left) -lt 300 ] && { echo "no time for $tree $cell"; continue; }
      echo "$tree $cell $3 0" > $out/l_pair.txt
      bash experiments/chip_calls/pr47_run.sh cF/pairs $out/l_pair.txt
    done
  done
  seed=$((seed+12))
done
echo "call F took $(($(date +%s)-T_CALL)) s"
