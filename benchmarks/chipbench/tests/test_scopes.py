"""``readers/scopes.py`` and ``xplane_meta.py`` on the scoped trace recorded on
the chip (``tools/record_scoped_trace.py`` -> ``testdata/scoped.xplane.pb``):
two ``layer<i>`` scopes under ``jax.checkpoint``, a ``loss_head`` with a matmul
of its own, a ``grad`` that reaches the input through frozen layer 0 and trains
layer 1 and the head, an ``optimizer`` clipped by the global norm. The shares
are pinned at what the readers read when the trace was recorded, and against
each other.

Run: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/chipbench/tests -q``
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, ROOT)

from benchmarks.chipbench import run, trace, xplane_meta  # noqa: E402
from benchmarks.chipbench.readers import scopes  # noqa: E402

PB = os.path.join(BENCH, "testdata", "scoped.xplane.pb")
EXPECTED = run.load_json(BENCH, "testdata", "scoped.expected.json")
METRICS = [m["name"] for m in run.load_json(ROOT, "BENCHMARK.json")["per_layer"]
           if m["name"].endswith("_time_pct.train") and m["name"] != "flash_time_pct.train"]


def sources():
    red = trace.reduce_planes(trace.read_planes(PB), chips=1)
    return {"trace": red, "config": EXPECTED["config"], "traffic": {"recipe": EXPECTED["recipe"]}}


def share(name, src):
    spec = run.load_json(BENCH, "metrics", name + ".json")
    assert spec["reader"] == "readers.scopes.scope_time_pct"
    return scopes.scope_time_pct(src, spec, xplane_path=PB)


def test_the_seven_scope_metrics_are_the_ones_benchmark_json_lists():
    assert sorted(METRICS) == sorted(EXPECTED["shares"])
    assert len(METRICS) == 7


@pytest.mark.parametrize("name", sorted(EXPECTED["shares"]))
def test_share_is_what_it_read_when_the_trace_was_recorded(name):
    assert share(name, sources()) == pytest.approx(EXPECTED["shares"][name], rel=1e-9)


def test_shares_stand_in_the_relations_the_metrics_promise():
    src = sources()
    got = {name: share(name, src) for name in METRICS}
    five = sum(got[k + "_time_pct.train"] for k in ("frozen_fwd", "frozen_bwd", "tail", "loss_head", "optimizer"))
    assert five == pytest.approx(got["scoped_time_pct.train"])  # no embed scope in this function
    assert 90.0 < got["scoped_time_pct.train"] <= 100.0
    # layer 0 is frozen and differentiated: a forward, an activation gradient, a recompute
    assert got["frozen_fwd_time_pct.train"] > 5.0 and got["frozen_bwd_time_pct.train"] > got["frozen_fwd_time_pct.train"]
    # layer 1 also pays its weight gradient: more than either half of layer 0
    assert got["tail_time_pct.train"] > got["frozen_bwd_time_pct.train"]
    assert 0.0 < got["remat_time_pct.train"] < got["frozen_bwd_time_pct.train"] + got["tail_time_pct.train"]
    assert got["loss_head_time_pct.train"] > 0.0 and got["optimizer_time_pct.train"] > 0.0


def test_every_matmul_of_the_trace_carries_its_scope_and_direction():
    meta = xplane_meta.read(PB)
    red = sources()["trace"]
    assert set(red["op_seconds"]) <= set(meta)
    found = set()
    for name in red["op_seconds"]:
        m = meta[name]
        if m.get("hlo_category") == "convolution fusion":
            cls, backward, _ = scopes.classify(m["tf_op"], 1)
            assert cls in ("frozen", "tail", "loss_head"), m["tf_op"]
            found.add((cls, backward))
    assert found == {(cls, backward) for cls in ("frozen", "tail", "loss_head") for backward in (False, True)}


def test_the_same_trace_read_as_all_trainable_has_no_frozen_share():
    src = sources()
    src["traffic"] = {"recipe": {"freeze_strategy": "full"}}
    layers = sum(EXPECTED["shares"][k + "_time_pct.train"] for k in ("frozen_fwd", "frozen_bwd", "tail"))
    assert share("frozen_fwd_time_pct.train", src) == 0.0
    assert share("frozen_bwd_time_pct.train", src) == 0.0
    assert share("tail_time_pct.train", src) == pytest.approx(layers)
