#!/bin/bash
# PR 48: the backward sweep's forward half, chunk by chunk (a chunk's three state-free products, then its two of the
# walk: landed) against a group's state-free products first and the walk after (_step1/: the tree with that one edit),
# the sweeps alone at the cell's shape. The edit, in _kda_bwd_kernel's forward(): make the group's chunks first,
#   alone = [_kda_alone_again(q[j], k[j], v[j], g[j], b_ref, c, tk_ref[0, 0, _rows(c), :][:, :CHUNK]) for j, c in enumerate(chunks)]
# and only then loop over zip(chunks, alone) for s_at, _kda_through, d_at and w_at. (pr48_final.sh PART=sweeps runs the same.)
#   chiprun --timeout 900 -- bash benchmarks/calls/pr48_order.sh
mkdir -p chiprun_out
python benchmarks/gdn_kernels.py --only sweeps --parent _step1 --iters 20 > chiprun_out/pr48a_order.log 2>&1; echo "exit $?"
grep -E '^\{|Error|Traceback' chiprun_out/pr48a_order.log | cut -c1-900
