"""Compiled-HLO collective accounting: the communication half of the scaling
story, measured from the artifact that actually runs.

The reference's communication cost is whatever NCCL does for DDP's bucketed
all-reduce (reference ``training.py:285``, ``deploy/pytorchjob.yaml:51-64``) —
opaque, measurable only on the cluster. On TPU the collectives are *compiled
into the program*: XLA emits them from sharding annotations, so the exact
per-step communication volume of any mesh is readable from the optimized HLO
without running a single step. This module does that read:

  compiled = jax.jit(step).lower(abstract_args).compile()
  report   = account_compiled(compiled, mesh)

and returns every collective instruction with

- its **execution count** per step (collectives inside ``lax.scan``/``while``
  bodies run once per iteration; XLA records ``known_trip_count`` in the
  loop's backend config, and nested loops multiply),
- its **mesh-axis attribution** (replica groups are decoded to concrete
  device groups and matched against the partitions induced by each mesh-axis
  subset — so "this all-reduce rides the ``data`` axis" is a fact, not a
  guess), and
- its **wire bytes** under the standard bidirectional-ring cost model
  (`scaling-book <https://jax-ml.github.io/scaling-book>`_ conventions):

    =================  =============================================
    all-gather          out_bytes × (g-1)/g
    reduce-scatter      out_bytes × (g-1)        (= full × (g-1)/g)
    all-reduce          2 × bytes × (g-1)/g      (RS + AG)
    all-to-all          bytes × (g-1)/g
    collective-permute  bytes                    (each device sends its shard)
    =================  =============================================

``tests/test_comm_accounting.py`` pins these volumes against analytic
expectations per target mesh; ``benchmarks/project_scaling.py`` feeds them
into its v5e-16 throughput projection.

Works on any backend whose compiled text is HLO (CPU, TPU). The parser
understands sync collectives and the ``-start``/``-done`` async pairs.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from itertools import combinations
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
    "f8e5m2": 1, "f8e4m3fn": 1, "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "c64": 8, "c128": 16,
}

# sync name -> canonical kind; -start variants are normalized to these
KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of every array shape mentioned in an HLO type string
    (tuples sum their elements)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue  # token[], opaque[] etc. carry no payload
        numel = 1
        for d in dims.split(","):
            if d:
                numel *= int(d)
        total += numel * _DTYPE_BYTES[dtype]
    return total


def _parse_replica_groups(attrs: str) -> Optional[List[List[int]]]:
    """Decode ``replica_groups=...`` — explicit ``{{0,1},{2,3}}`` or iota
    ``[ng,gs]<=[dims]`` with an optional ``T(perm)`` transpose."""
    m = re.search(r"replica_groups=\{\{([\d,{} ]*)\}\}", attrs)
    if m:
        return [
            [int(x) for x in grp.split(",") if x.strip()]
            for grp in m.group(1).split("},{")
        ]
    m = re.search(
        r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", attrs
    )
    if m:
        ng, gs = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(x) for x in m.group(4).split(",")])
        return ids.reshape(ng, gs).tolist()
    return None


def _parse_pairs(attrs: str) -> Optional[List[Tuple[int, int]]]:
    m = re.search(r"source_target_pairs=\{(.*?)\}\}", attrs)
    if not m:
        return None
    return [
        tuple(int(x) for x in p.split(","))
        for p in m.group(1).strip("{}").split("},{")
    ]


@dataclass
class Collective:
    kind: str                 # canonical (sync) opcode
    computation: str          # enclosing HLO computation
    result_bytes: int         # bytes of the (per-device) result shape(s)
    group_size: int
    axes: Tuple[str, ...]     # mesh axes the groups ride ("?" if unmatched)
    count: int                # executions per step (loop trip products)
    op_name: str = ""         # jax op_name metadata (for attribution reading)

    @property
    def wire_bytes_once(self) -> float:
        """Per-device bytes on the wire for ONE execution (ring model)."""
        g = self.group_size
        if g <= 1:
            return 0.0
        b = self.result_bytes
        if self.kind == "all-gather":
            return b * (g - 1) / g
        if self.kind == "reduce-scatter":
            return b * (g - 1)          # result is the 1/g shard
        if self.kind == "all-reduce":
            return 2 * b * (g - 1) / g
        if self.kind == "all-to-all":
            return b * (g - 1) / g
        if self.kind == "collective-permute":
            return b
        return 0.0

    @property
    def wire_bytes(self) -> float:
        return self.wire_bytes_once * self.count


@dataclass
class CommReport:
    collectives: List[Collective] = field(default_factory=list)

    def total_wire_bytes(self) -> float:
        return sum(c.wire_bytes for c in self.collectives)

    def wire_bytes_by_axis(self) -> Dict[Tuple[str, ...], float]:
        out: Dict[Tuple[str, ...], float] = {}
        for c in self.collectives:
            out[c.axes] = out.get(c.axes, 0.0) + c.wire_bytes
        return out

    def wire_bytes_by_kind(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for c in self.collectives:
            out[c.kind] = out.get(c.kind, 0.0) + c.wire_bytes
        return out

    def filter(self, kind: Optional[str] = None, axes: Optional[Sequence[str]] = None) -> "CommReport":
        sel = self.collectives
        if kind is not None:
            sel = [c for c in sel if c.kind == kind]
        if axes is not None:
            sel = [c for c in sel if c.axes == tuple(axes)]
        return CommReport(list(sel))

    def table(self) -> str:
        rows = ["kind               axes              count  result_MB  wire_MB  where"]
        for c in sorted(self.collectives, key=lambda c: -c.wire_bytes):
            rows.append(
                f"{c.kind:<18} {'x'.join(c.axes) or '-':<17} {c.count:>5}  "
                f"{c.result_bytes/1e6:>9.3f}  {c.wire_bytes/1e6:>7.3f}  {c.op_name[:60]}"
            )
        rows.append(f"TOTAL wire: {self.total_wire_bytes()/1e6:.3f} MB/step/device")
        return "\n".join(rows)


# --------------------------------------------------------------------- parse


def _split_computations(text: str) -> Dict[str, List[str]]:
    """Map computation name -> its instruction lines. Computation headers sit
    at column 0 (``ENTRY`` marks the entry); bodies are indented."""
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    for line in text.splitlines():
        if line and not line[0].isspace():
            m = re.match(r"(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*->", line)
            if m and line.rstrip().endswith("{"):
                cur = m.group(1)
                if line.startswith("ENTRY"):
                    cur = "__ENTRY__:" + cur
                comps[cur] = []
                continue
            cur = None
        elif cur is not None:
            comps[cur].append(line.strip())
    return comps


_REF_ATTRS = (
    ("body=", None),          # trip count resolved from backend_config
    ("condition=", 1),
    ("calls=", 1),
    ("to_apply=", 1),
    ("true_computation=", 1),
    ("false_computation=", 1),
)


def _comp_multipliers(comps: Dict[str, List[str]]) -> Dict[str, int]:
    """Executions per step of each computation: product of enclosing loop trip
    counts, propagated from ENTRY through the call graph (a DAG)."""
    entry = next(k for k in comps if k.startswith("__ENTRY__:"))
    edges: Dict[str, List[Tuple[str, int]]] = {k: [] for k in comps}
    for name, lines in comps.items():
        for line in lines:
            trip = 1
            mt = re.search(r'known_trip_count\":\{\"n\":\"(\d+)\"', line)
            if mt:
                trip = int(mt.group(1))
            for attr, mult in _REF_ATTRS:
                for m in re.finditer(re.escape(attr) + r"\(?%?([\w\.\-]+)", line):
                    callee = m.group(1)
                    n = trip if attr == "body=" else (mult or 1)
                    if callee in comps:
                        edges[name].append((callee, n))
            m = re.search(r"branch_computations=\{([^}]*)\}", line)
            if m:
                for callee in re.findall(r"%?([\w\.\-]+)", m.group(1)):
                    if callee in comps:
                        edges[name].append((callee, 1))
    mults = {k: 0 for k in comps}
    mults[entry] = 1
    # Topological accumulation over the call DAG: a callee executes the SUM
    # over call sites of caller_multiplier x per-site count. (A max-relaxation
    # would count a computation invoked once from each of two call sites as
    # one execution — ADVICE r3.) Deliberate upper-bound semantics for
    # conditionals: sibling branches are mutually exclusive per invocation,
    # so a helper reachable from BOTH arms is credited twice — accounting
    # reports bound bytes from above, and undercounting is the unsafe
    # direction (branch probabilities are unknowable statically).
    from collections import deque

    indeg = {k: 0 for k in comps}
    for out in edges.values():
        for callee, _ in out:
            indeg[callee] += 1
    ready = deque(k for k, d in indeg.items() if d == 0)
    while ready:
        name = ready.popleft()
        for callee, n in edges[name]:
            mults[callee] += mults[name] * n
            indeg[callee] -= 1
            if indeg[callee] == 0:
                ready.append(callee)
    return mults


def _device_id_grid(mesh) -> np.ndarray:
    return np.vectorize(lambda d: d.id)(mesh.devices)


def _axis_partition(grid: np.ndarray, axis_names, subset) -> frozenset:
    """The partition of device ids induced by grouping along ``subset`` axes."""
    order = [i for i, a in enumerate(axis_names) if a not in subset] + [
        i for i, a in enumerate(axis_names) if a in subset
    ]
    gsz = int(np.prod([grid.shape[i] for i, a in enumerate(axis_names) if a in subset]))
    rows = grid.transpose(order).reshape(-1, gsz)
    return frozenset(frozenset(int(x) for x in row) for row in rows)


def _attribute_axes(groups: List[List[int]], mesh) -> Tuple[str, ...]:
    """Find the smallest mesh-axis subset whose induced grouping matches."""
    grid = _device_id_grid(mesh)
    names = list(mesh.axis_names)
    observed = frozenset(frozenset(g) for g in groups)
    live = [a for a in names if mesh.shape[a] > 1]
    for r in range(1, len(live) + 1):
        for subset in combinations(live, r):
            if _axis_partition(grid, names, set(subset)) == observed:
                return subset
    return ("?",)


def _attribute_pairs(pairs: List[Tuple[int, int]], mesh) -> Tuple[str, ...]:
    """A permute rides axis A if every (src, dst) differs only in A's coord."""
    grid = _device_id_grid(mesh)
    names = list(mesh.axis_names)
    coord = {int(grid[idx]): idx for idx in np.ndindex(grid.shape)}
    for i, a in enumerate(names):
        if mesh.shape[a] <= 1:
            continue
        if all(
            s in coord and t in coord
            and all(cs == ct for j, (cs, ct) in enumerate(zip(coord[s], coord[t])) if j != i)
            and coord[s][i] != coord[t][i]
            for s, t in pairs
        ):
            return (a,)
    return ("?",)


def account_text(text: str, mesh) -> CommReport:
    """Parse optimized-HLO text into a per-step communication report."""
    comps = _split_computations(text)
    mults = _comp_multipliers(comps)
    report = CommReport()
    for name, lines in comps.items():
        count = mults.get(name, 0)
        if count == 0:
            continue
        for line in lines:
            kind = None
            for k in KINDS:
                if re.search(rf"(?<![\w-]){k}(-start)?\(", line):
                    kind = k
                    break
            if kind is None:
                continue
            is_start = f"{k}-start(" in line
            head = line.split(f" {k}{'-start' if is_start else ''}(", 1)[0]
            type_str = head.split("=", 1)[1] if "=" in head else head
            result_bytes = _shape_bytes(type_str)
            if is_start and kind == "all-gather":
                # start op's result tuple is (operand, output): keep the output
                shapes = [
                    _shape_bytes(f"{d}[{dims}]")
                    for d, dims in _SHAPE_RE.findall(type_str)
                ]
                result_bytes = max(shapes) if shapes else 0
            elif is_start:
                # (operand, result) alias tuple doubles the payload
                result_bytes //= 2
            mo = re.search(r'op_name="([^"]*)"', line)
            if kind == "collective-permute":
                pairs = _parse_pairs(line) or []
                axes = _attribute_pairs(pairs, mesh) if pairs else ("?",)
                gsz = 2 if pairs else 1  # pairwise sends; wire model uses bytes directly
            else:
                groups = _parse_replica_groups(line)
                if not groups or len(groups[0]) <= 1:
                    continue
                gsz = len(groups[0])
                axes = _attribute_axes(groups, mesh)
            report.collectives.append(
                Collective(
                    kind=kind,
                    computation=name.replace("__ENTRY__:", ""),
                    result_bytes=result_bytes,
                    group_size=gsz,
                    axes=axes,
                    count=count,
                    op_name=mo.group(1) if mo else "",
                )
            )
    return report


def account_compiled(compiled, mesh) -> CommReport:
    """Account a ``jax.stages.Compiled`` (from ``jit(f).lower(...).compile()``)."""
    return account_text(compiled.as_text(), mesh)
