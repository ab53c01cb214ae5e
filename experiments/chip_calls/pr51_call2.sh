#!/bin/bash
# PR 51, second call: `nemo3s_serve_flood`, six seeds a side, parent (_archive/parent = git archive of 0f407a3) and change
# (_archive/change = git archive $(git write-tree)) alternated (parent, change, change, parent), a seed a pair, `--trace 0`;
# both programs were compiled into the machine's cache by call 1's traced pair, so every `setup_s` here is warm.
# LISTS: which lists, in order; `more` = four further seeds of the change alone if the call has time left.
out=chiprun_out/p51b; mkdir -p $out
export T_CALL=$(date +%s) CALL_S=${CALL_S:-2750}
P=_archive/parent; C=_archive/change; N=nemo3s_serve_flood
cat > $out/l_pairs.txt <<L
$P $N 2151000101 0 run
$C $N 2151000101 0 run
$C $N 2151000113 0 run
$P $N 2151000113 0 run
$P $N 2151000127 0 run
$C $N 2151000127 0 run
$C $N 2151000131 0 run
$P $N 2151000131 0 run
$P $N 2151000149 0 run
$C $N 2151000149 0 run
$C $N 2151000151 0 run
$P $N 2151000151 0 run
L
cat > $out/l_more.txt <<L
$C $N 2151000163 0 run
$C $N 2151000167 0 run
$C $N 2151000179 0 run
$C $N 2151000181 0 run
L
for list in ${LISTS:-pairs more}; do bash experiments/chip_calls/pr51_run.sh p51b/$list $out/l_$list.txt; done
echo "call took $(($(date +%s)-T_CALL)) s"
