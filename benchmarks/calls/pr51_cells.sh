# git add -A && rm -rf _checkout _parent && mkdir _checkout _parent && git archive $(git write-tree) | tar -x -C _checkout
# && git archive 606ab3253249474701422c459072dbe3e9aa94da | tar -x -C _parent
# chiprun --timeout 3500 -- env PART=both bash benchmarks/calls/pr51_cells.sh   (then PART=others1, others2; or mistral, granite apart)
# PR 51 (tracing; no claim), from the committed files alone (_checkout/) and the parent's (_parent/), through run.py itself.
# BOTH TREES RUN AT ONE PATH (each is moved to _run/ for its run): a Pallas kernel's serialized body carries its call
# stack's files and lines, so two trees at two paths share no step program in the cache; at one path they share every
# program whose call path kept its line numbers, which is what PR 51 says of observe/xla.py: the parent's first traced
# run after the change's reads setup_cache_misses 0 only if that holds. A cache of this call's own without a size limit
# (the machine's 192 MiB holds no cell's step; it came with 25 MiB), so the first run of a cell is cold and is ALSO the
# look ROADMAP.md item 17a asks of every tracing PR: scoped_time_pct.train on a fresh cache directory.
# PART=mistral | granite, the two cells whose parent is measured: change traced (cold, fresh cache), parent traced,
# change traced (warm: the acceptance run), then two untraced warm pairs parent, change, change, parent (what the finder
# costs setup_s), and the parent traced once more if its first traced run was no warm one.
# PART=others1 | others2, the seven other cells: change untraced for 3 s (cold: fills the cache), change traced (warm).
# Every traced run leaves its setup_spans.json, setup_table and (the change's) import_table in chiprun_out/pr51_*.
# EXTRA="--rehearse 1" (with JAX_PLATFORMS=cpu) rehearses the control flow on a CPU.
mkdir -p chiprun_out
ROOT=$PWD
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache/pr51 JAX_COMPILATION_CACHE_MAX_SIZE=-1
MISTRAL=mistral-7b-d16.sft-2k-full
GRANITE=granite-4.0-h-micro.sft-8k-ssd-tied-last2
SUMMARY='
import json, sys
line = [ln for ln in open(sys.argv[1]) if ln.startswith("{")]
if not line:
    print("  NO RESULT LINE"); sys.exit(0)
line = json.loads(line[-1]); m = {k: v["value"] for k, v in line["metrics"].items()}
keep = [k for k in m if k.startswith(("setup_", "train_step_", "train_tokens", "scoped_time", "device_idle", "recompiles", "train_mfu"))]
print("  correct", line["correct"], "failed", line["failed"], line["device"].get("kind"), "peak GiB", round(line["device"]["memory_peak_bytes"] / 2**30, 3))
print("  " + ", ".join(f"{k} {m[k]:.4f}" for k in keep))
'
run() {  # tree cell seed trace seconds tag
  mv $1 _run
  (cd _run && python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds $5 --trace $4 $EXTRA > $ROOT/chiprun_out/pr51_$6.log 2>&1; echo "$6 ($1, seed $3, trace $4, $5 s) exit $? at $SECONDS s")
  grep -E '^set-up|^window|^reference|^chipbench|Error|Traceback|RESOURCE' chiprun_out/pr51_$6.log | cut -c1-300
  python -c "$SUMMARY" chiprun_out/pr51_$6.log
  if [ $4 = 1 ]; then
    (cd _run && cp .chipbench_trace/$2/setup_spans.json $ROOT/chiprun_out/pr51_$6_setup_spans.json
     timeout 120 python benchmarks/chipbench/tools/setup_table.py .chipbench_trace/$2 12 > $ROOT/chiprun_out/pr51_$6_setup_table.txt 2>&1
     [ -f benchmarks/chipbench/tools/import_table.py ] && timeout 120 python benchmarks/chipbench/tools/import_table.py .chipbench_trace/$2 12 5 > $ROOT/chiprun_out/pr51_$6_import_table.txt 2>&1)
  fi
  mv _run $1
}
misses() {  # tag -> setup_cache_misses of that traced run (-1: no line)
  python -c "
import json, sys
line = [ln for ln in open(sys.argv[1]) if ln.startswith('{')]
print(int(json.loads(line[-1])['metrics'].get('setup_cache_misses', {'value': -1})['value']) if line else -1)" chiprun_out/pr51_$1.log
}
pairs() {  # cell short seeds...
  CELL=$1; S=$2
  run _checkout $CELL $3 1 12 ${S}_change_cold_traced
  run _parent $CELL $4 1 12 ${S}_parent_traced
  run _checkout $CELL $4 1 12 ${S}_change_traced
  head -30 chiprun_out/pr51_${S}_change_traced_import_table.txt | cut -c1-160
  run _parent $CELL $5 0 30 ${S}_pair1_parent
  run _checkout $CELL $5 0 30 ${S}_pair1_change
  run _checkout $CELL $6 0 30 ${S}_pair2_change
  run _parent $CELL $6 0 30 ${S}_pair2_parent
  if [ "$(misses ${S}_parent_traced)" != 0 ]; then
    run _parent $CELL $7 1 12 ${S}_parent_traced_again
    run _checkout $CELL $7 1 12 ${S}_change_traced_again
  fi
}
others() {  # seed0 cells...
  SEED=$1; shift
  for CELL in "$@"; do
    S=${CELL%%-*}
    run _checkout $CELL $SEED 0 3 ${S}_change_cold
    run _checkout $CELL $((SEED + 1000003)) 1 12 ${S}_change_traced
    head -12 chiprun_out/pr51_${S}_change_traced_import_table.txt | cut -c1-160
    SEED=$((SEED + 17))
  done
}
case "${PART:-mistral}" in
mistral) pairs $MISTRAL mistral 3000005101 2147487751 3000005119 2147487769 3000005147 ;;
granite) pairs $GRANITE granite 3000005153 2147487773 3000005167 2147487791 3000005171 ;;
both)  # chips were scarce: the two cells with a parent's side in ONE call
  pairs $MISTRAL mistral 3000005101 2147487751 3000005119 2147487769 3000005147
  pairs $GRANITE granite 3000005153 2147487773 3000005167 2147487791 3000005171 ;;
why)  # (ran on the FIRST version of PR 51, whose observe/startup.py held a sys.meta_path finder; the seds below find
  # nothing in the tree as handed in) the pairs read the change 10 to 16 s slower before the first program: which part of it, and which form of the
  # finder does not cost that? _x1 is the change without the finder (its install a `pass`: the same lines), _f1 the
  # change with ONLY imports under no other import timed (below one the finder answers None: no stand-in, no clock),
  # _f2 the change without the process's CPU clock. A cold run fills the cache, then P, C, F1, F2, X1 twice over.
  rm -rf _x1 _f1 _f2; cp -r _checkout _x1; cp -r _checkout _f1; cp -r _checkout _f2
  sed -i 's/^        startup.install_import_spans(lambda: _RECORDER)$/        pass/' _x1/llm_fine_tune_distributed_tpu/observe/xla.py
  sed -i 's/^        if self._recorder().frozen:$/        if self._recorder().frozen or getattr(self._local, "depth", 0):/' _f1/llm_fine_tune_distributed_tpu/observe/startup.py
  sed -i 's/time.process_time()/0.0/' _f2/llm_fine_tune_distributed_tpu/observe/startup.py
  for T in _x1 _f1 _f2; do diff -r _checkout $T | grep -c '^[<>]'; done
  run _checkout $MISTRAL 3000005401 0 3 why_change_cold
  SEED=3000005411
  for ROUND in 1 2; do
    ORDER="_parent _checkout _f1 _f2 _x1"; [ $ROUND = 2 ] && ORDER="_x1 _f2 _f1 _checkout _parent"  # the order turned round
    for TREE in $ORDER; do
      run $TREE $MISTRAL $SEED 0 3 why${ROUND}${TREE}
      SEED=$((SEED + 12))
    done
  done ;;
final)  # the tree as handed in (no finder: imports are spans where the package wraps them), Mistral then Granite:
  # a cold run fills the cache, then parent, change, change, parent untraced for 3 s (Granite: parent, change), then the
  # change traced (the table; setup_spanned_pct)
  run _checkout $MISTRAL 3000005601 0 3 final_mistral_change_cold
  run _parent $MISTRAL 3000005611 0 3 final_mistral_pair1_parent
  run _checkout $MISTRAL 3000005611 0 3 final_mistral_pair1_change
  run _checkout $MISTRAL 3000005623 0 3 final_mistral_pair2_change
  run _parent $MISTRAL 3000005623 0 3 final_mistral_pair2_parent
  run _checkout $MISTRAL 3000005641 1 12 final_mistral_change_traced
  head -16 chiprun_out/pr51_final_mistral_change_traced_import_table.txt | cut -c1-160
  run _checkout $GRANITE 3000005651 0 3 final_granite_change_cold
  run _parent $GRANITE 3000005659 0 3 final_granite_pair1_parent
  run _checkout $GRANITE 3000005659 0 3 final_granite_pair1_change
  run _checkout $GRANITE 3000005677 1 12 final_granite_change_traced
  head -12 chiprun_out/pr51_final_granite_change_traced_import_table.txt | cut -c1-160 ;;
others1) others 3000005200 smollm3-3b.sft-1k-full moonlight-16b-a3b-ep8-d6.sft-4k-allparams \
           mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams evabyte-6.5b-d10.sft-32k-eva-last2 ;;
others2) others 3000005300 qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams \
           trinity-mini-26b-a3b-ep8-d5.sft-8k-gated-swa-allparams kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams ;;
esac
