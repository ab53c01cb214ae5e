#!/bin/bash
# PR 51, last call, on the final tree (_archive/change = git archive $(git write-tree): the committed files alone): the
# tree's kernel beside the parent's form at both shapes, a pair in `nemo3s_serve_flood`, and `minicpm_sala_serve_long`
# change, parent, change (call 1 had the change's first run there cold: two warm runs a side were left).
out=chiprun_out/p51c; mkdir -p $out
export T_CALL=$(date +%s) CALL_S=${CALL_S:-1700}
P=_archive/parent; C=_archive/change; N=nemo3s_serve_flood; M=minicpm_sala_serve_long
python3 $C/experiments/chip_calls/pr51_ssm_step_time.py --shape mamba s1g1u tree 2> /dev/null | tee $out/kernel_mamba.jsonl | cut -c1-400
python3 $C/experiments/chip_calls/pr51_ssm_step_time.py --shape lightning s1g16u tree 2> /dev/null | tee $out/kernel_lightning.jsonl | cut -c1-400
cat > $out/l_final.txt <<L
$C $N 2151000191 0 run
$P $N 2151000191 0 run
$C $M 2151000193 0 run
$P $M 2151000193 0 run
$C $M 2151000197 0 run
L
bash experiments/chip_calls/pr51_run.sh p51c/final $out/l_final.txt
echo "call took $(($(date +%s)-T_CALL)) s"
