#!/bin/bash
# PR 51, first call: `ssm_step` alone by groups a grid cell at both callers' shapes (pr51_ssm_step_time.py), then the 2 MiB
# gate in `minicpm_sala_serve_long`: parent (_archive/parent = git archive of 0f407a3), change (_archive/change = git
# archive $(git write-tree), the 1 MiB rule: lightning's program is the parent's) and the change with `_STEP_BYTES` set to
# 2 MiB by pr51_step_bytes.py (lightning: grid (32, 1), 32 heads unrolled), one run each first to fill the compile cache,
# then three a side alternated, a seed a round; one traced run of the change there; then the traced pair of
# `nemo3s_serve_flood`, which also fills the cache for the second call's six pairs.
out=chiprun_out/p51; mkdir -p $out
export T_CALL=$(date +%s) CALL_S=${CALL_S:-2750}
python3 experiments/chip_calls/pr51_ssm_step_time.py 2> $out/kernel.err | tee $out/kernel.jsonl | cut -c1-400
grep -v "^W0\|^I0" $out/kernel.err | tail -n 5; rm -f $out/kernel.err
echo "kernel timing done at $(($(date +%s)-T_CALL)) s"
P=_archive/parent; C=_archive/change; N=nemo3s_serve_flood; M=minicpm_sala_serve_long
cat > $out/l_sala.txt <<L
$P $M 2151000011 0 run
$C $M 2151000011 0 kib2048
$P $M 2151000023 0 run
$C $M 2151000023 0 run
$C $M 2151000023 0 kib2048
$C $M 2151000037 0 kib2048
$P $M 2151000037 0 run
$C $M 2151000037 0 run
$C $M 2151000041 0 run
$C $M 2151000041 0 kib2048
$P $M 2151000041 0 run
$C $M 2151000053 1 run
L
cat > $out/l_traced.txt <<L
$C $N 2151000059 1 run
$P $N 2151000059 1 run
L
for list in ${LISTS:-sala traced}; do bash experiments/chip_calls/pr51_run.sh p51/$list $out/l_$list.txt; done
echo "call took $(($(date +%s)-T_CALL)) s"
