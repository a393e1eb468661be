# chiprun --timeout 3000 -- bash benchmarks/calls/pr37_load_split.sh
# PR 37, step 0: where PR 36's +10.9 s of warm load are. Each side compiles the cell's step in one process and loads
# it in a second, against a cache directory of its own: lower() s, .compile() s, the entry's bytes (pr37_load.py).
# Sides: the parent (_parent/, git archive of 7d120b3), PR 36's tree (_step1/: three programs, 8 chunks unrolled), this
# tree with 8, 4 and 2 chunks side by side in the text (two programs), and the forward kernel with autodiff of the XLA
# form behind it (one program). Before them the new kernels at a tiny size under faulthandler, then alone: their speed,
# and the same split for the rule by itself (one call site, three, forward + backward).
mkdir -p chiprun_out
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr37_split.log
: > $OUT
for g in 4 2 8; do python benchmarks/calls/pr37_rule.py tiny --group $g 2>&1 | grep "^tiny\|Error\|error\|Timeout" | tee -a $OUT; done
for g in 8 4 2; do python benchmarks/calls/pr37_rule.py speed --group $g 2>&1 | grep "^{\|Error\|error" | tee -a $OUT; done
(cd _step1 && python ../benchmarks/calls/pr37_rule.py speed 2>&1 | grep "^{\|Error\|error" | sed 's/^/pr36 /' | tee -a $OUT)
side() {  # tag, directory, what, options
  tag=$1; dir=$2; what=$3; shift 3
  export JAX_COMPILATION_CACHE_DIR=/tmp/pr37_cache_${what}_$tag
  rm -rf $JAX_COMPILATION_CACHE_DIR
  for run in cold warm; do
    (cd $dir && python $ROOT/benchmarks/calls/pr37_load.py $what --tag "$tag $run" "$@" 2>&1 | grep "^{\|^gated\|Error\|error" | cut -c1-600 | tee -a $OUT)
  done
}
side pr36 _step1 kernels
side group8 . kernels --group 8
side group4 . kernels --group 4
side group2 . kernels --group 2
side parent _parent step
side pr36 _step1 step
side group4 . step --group 4
side group2 . step --group 2
side group8 . step --group 8
side fwd_only . step --group 4 --bwd xla
