# chiprun --timeout 1500 -- bash benchmarks/calls/pr51_probe.sh
# PR 51: the pairs of benchmarks/calls/pr51_cells.sh read the change's setup_s 10 to 16 s over the parent's, all of it
# inside `import google.api_core` (its packages_distributions() scan: 26.5 to 34.0 s under the finder, about 24 s at the
# parent by subtraction; half a second on the sandbox, where the finder costs nothing measurable). One process a mode
# (benchmarks/calls/pr51_import_probe.py), the modes alternating, to see what of the finder the scan feels.
mkdir -p chiprun_out
for MODE in plain hooked untimed inert noproc plain hooked nogc; do
  python benchmarks/calls/pr51_import_probe.py $MODE backend 2>&1 | grep '^{' | tee -a chiprun_out/pr51_probe.jsonl | cut -c1-330
done
