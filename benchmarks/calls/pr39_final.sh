# git add -A && rm -rf _checkout && mkdir _checkout && git archive $(git write-tree) | tar -x -C _checkout
# (_parent/: git archive 2fb8c47)
# chiprun --timeout 3400 -- bash benchmarks/calls/pr39_final.sh
# PR 39, the final tree: the committed files alone (_checkout/). The passes and the whole mixer by the builder's tool, the four
# kernels alone as the library has them; then pr39_cell.sh on seeds not used before (cold first run, traced run, three pairs
# against the parent, the control) and a Mistral pair, a cell that runs none of the changed code.
mkdir -p chiprun_out
ROOT=$PWD
(cd _checkout && python benchmarks/gdn_kernels.py --only mixer 2>&1 | grep "^{\|Error\|Traceback" | tee $ROOT/chiprun_out/pr39f_mixer.jsonl | cut -c1-1300)
(cd _checkout && python benchmarks/calls/pr39_sweep.py library 2>&1 | grep "^{" | tee $ROOT/chiprun_out/pr39f_kernels.jsonl)
TAG=pr39f SEED=3000001501 OTHER=mistral-7b-d16.sft-2k-full bash benchmarks/calls/pr39_cell.sh
