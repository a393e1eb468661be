"""The benchmark's reading of a device trace by scope, on the CPU:
``benchmarks/chipbench/xplane_meta.py`` reads what the chip's compiler wrote
about each operation from a trace recorded on the v5e (``testdata/``), and
``readers/scopes.py`` classifies ``tf_op`` strings as the chip writes them and
adds them up. The trace with scopes in it is pinned by
``benchmarks/chipbench/tests/test_scopes.py``.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chipbench import trace, xplane_meta  # noqa: E402
from benchmarks.chipbench.readers import scopes  # noqa: E402

SMALL = os.path.join(REPO, "benchmarks", "chipbench", "testdata", "small.xplane.pb")
BODY = "jit(train_step)/while/body/closed_call/"


def test_metadata_of_the_small_trace_recorded_on_the_chip():
    meta = xplane_meta.read(SMALL)
    fusion = [v for k, v in meta.items() if k.startswith("%fusion = ")]
    assert len(fusion) == 1
    assert fusion[0]["tf_op"] == "jit(<lambda>)/dot_general:"
    assert fusion[0]["hlo_category"] == "convolution fusion"
    assert fusion[0]["flops"] == 2 * 1024**3 + 3 * 1024**2  # the matmul, and tanh + reduce at 3 a cell
    assert fusion[0]["bytes_accessed"] == 2 * 1024 * 1024 * 2 + 2
    copies = {v["hlo_category"] for k, v in meta.items() if k.startswith("%copy-")}
    assert copies == {"copy-start", "copy-done"}
    assert all("tf_op" not in v for k, v in meta.items() if k.startswith("%copy-"))


def test_metadata_names_are_the_names_the_reduction_keys_self_time_by():
    red = trace.reduce_planes(trace.read_planes(SMALL), chips=1)
    meta = xplane_meta.read(SMALL)
    assert set(red["op_seconds"]) <= set(meta)


def test_a_string_stat_may_be_a_reference_into_the_stat_names():
    """``hlo_category`` is stored as ``ref_value``: the id of a stat metadata
    whose NAME is the string. Hand-made plane: one event, stat 7 by
    reference to stat-metadata 9, stat 8 a plain string."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    def field(number, payload):
        if isinstance(payload, int):
            return varint(number << 3) + varint(payload)
        return varint((number << 3) | 2) + varint(len(payload)) + payload

    def stat_meta(ident, name):
        return field(5, field(1, ident) + field(2, field(1, ident) + field(2, name.encode())))

    event = field(1, 3) + field(2, b"%fusion.1 = ...") + field(5, field(1, 7) + field(7, 9)) \
        + field(5, field(1, 8) + field(5, b"jit(f)/layer0/mul:"))
    plane = field(2, b"/device:TPU:0") + field(4, field(1, 3) + field(2, event)) \
        + stat_meta(7, "hlo_category") + stat_meta(8, "tf_op") + stat_meta(9, "loop fusion")
    name, events = xplane_meta.plane_metadata(plane)
    assert name == "/device:TPU:0"
    assert events == {"%fusion.1 = ...": {"hlo_category": "loop fusion", "tf_op": "jit(f)/layer0/mul:"}}


@pytest.mark.parametrize("tf_op,want", [
    # frozen trunk (first trainable layer 34): forward, activation gradient, its recompute
    (BODY + "jvp(layer0)/attn/dot_general:", ("frozen", False, False)),
    (BODY + "jvp(layer33)/mlp/dot_general:", ("frozen", False, False)),
    (BODY + "transpose(jvp(layer3))/jvp(layer3)/checkpoint/mlp/dot_general:", ("frozen", True, False)),
    (BODY + "transpose(jvp(layer3))/jvp(layer3)/checkpoint/rematted_computation/mlp/mul:", ("frozen", True, True)),
    ("jit(train_step)/layer12/attn/add:", ("frozen", False, False)),  # no transform around the scope
    # the whole index counts: layer3 is frozen, layer34 and layer35 are the tail
    (BODY + "jvp(layer34)/attn/dot_general:", ("tail", False, False)),
    (BODY + "transpose(jvp(layer35))/jvp(layer35)/checkpoint/attn/pallas_call:", ("tail", True, False)),
    (BODY + "transpose(jvp(layer35))/jvp(layer35)/checkpoint/rematted_computation/attn/mul:", ("tail", True, True)),
    # final norm and loss head are one class
    (BODY + "jvp(final_norm)/mul:", ("loss_head", False, False)),
    (BODY + "jvp(loss_head)/while/body/closed_call/dot_general:", ("loss_head", False, False)),
    (BODY + "transpose(jvp(loss_head))/while/body/closed_call/checkpoint/rematted_computation/dot_general:",
     ("loss_head", True, True)),
    # accumulate and optimizer are one class
    (BODY + "grad_accum/add:", ("optimizer", False, False)),
    ("jit(train_step)/optimizer/sqrt:", ("optimizer", False, False)),
    # embed: the lookup, and on a tied model the scatter of its gradient
    (BODY + "jvp(embed)/gather:", ("embed", False, False)),
    (BODY + "transpose(jvp(embed))/scatter-add:", ("embed", True, False)),
    # no scope of the vocabulary: bookkeeping, rope's tables, a jitted function of the same name
    (BODY + "jvp()/cos:", (None, False, False)),
    ("jit(train_step)/while/body/dynamic_slice:", (None, False, False)),
    ("jit(train_step)/jit(optimizer)/mul:", (None, False, False)),
    ("jit(train_step)/player1/mul:", (None, False, False)),
    ("", (None, False, False)),
    # XLA joins the paths of merged operations with ';': the first counts
    (BODY + "jvp(layer35)/attn/reshape;" + BODY + "jvp(loss_head)/reshape:", ("tail", False, False)),
])
def test_classify(tf_op, want):
    assert scopes.classify(tf_op, 34) == want


def test_first_trainable_layer_from_config_and_recipe():
    assert scopes.first_trainable_layer({"num_hidden_layers": 36},
                                        {"freeze_strategy": "last_n_and_head", "unfreeze_last_n_layers": 2}) == 34
    assert scopes.first_trainable_layer({"num_hidden_layers": 16}, {"freeze_strategy": "lora"}) == 0
    assert scopes.first_trainable_layer({"num_hidden_layers": 2},
                                        {"freeze_strategy": "last_n_and_head", "unfreeze_last_n_layers": 4}) == 0


SPECS = {
    "frozen_fwd": {"classes": ["frozen"], "pass": "forward"},
    "frozen_bwd": {"classes": ["frozen"], "pass": "backward"},
    "tail": {"classes": ["tail"]},
    "loss_head": {"classes": ["loss_head"]},
    "optimizer": {"classes": ["optimizer"]},
    "remat": {"recomputed": True},
    "scoped": {"classes": list(scopes.CLASSES)},
}


def _sources(op_seconds, layers=4, unfreeze=2):
    return {"trace": {"busy_s": sum(op_seconds.values()), "op_seconds": op_seconds},
            "config": {"num_hidden_layers": layers},
            "traffic": {"recipe": {"freeze_strategy": "last_n_and_head", "unfreeze_last_n_layers": unfreeze}}}


def _shares(monkeypatch, tf_ops, seconds):
    monkeypatch.setattr(scopes, "_metadata", lambda path, mtime: {k: {"tf_op": v} for k, v in tf_ops.items()})
    return {k: scopes.scope_time_pct(_sources(seconds), spec, xplane_path=SMALL) for k, spec in SPECS.items()}


def test_shares_add_up_on_a_hand_made_step(monkeypatch):
    tf_ops = {
        "a": BODY + "jvp(layer0)/mlp/dot_general:",                                                 # frozen fwd
        "b": BODY + "transpose(jvp(layer1))/jvp(layer1)/checkpoint/mlp/dot_general:",                # frozen bwd
        "c": BODY + "transpose(jvp(layer1))/jvp(layer1)/checkpoint/rematted_computation/mlp/mul:",   # frozen bwd, remat
        "d": BODY + "transpose(jvp(layer2))/jvp(layer2)/checkpoint/rematted_computation/mlp/mul:",   # tail, remat
        "e": BODY + "jvp(loss_head)/dot_general:",
        "f": "jit(train_step)/optimizer/mul:",
        "g": BODY + "jvp(embed)/gather:",
        "h": BODY + "jvp()/checkpoint/rematted_computation/cos:",                                    # unscoped, remat
        "i": "",
    }
    seconds = {"a": 30.0, "b": 20.0, "c": 10.0, "d": 8.0, "e": 12.0, "f": 5.0, "g": 2.0, "h": 1.0, "i": 12.0}
    got = _shares(monkeypatch, tf_ops, seconds)
    assert got == pytest.approx({"frozen_fwd": 30.0, "frozen_bwd": 30.0, "tail": 8.0, "loss_head": 12.0,
                                 "optimizer": 5.0, "remat": 19.0, "scoped": 87.0})
    disjoint = sum(got[k] for k in ("frozen_fwd", "frozen_bwd", "tail", "loss_head", "optimizer"))
    assert got["scoped"] - disjoint == pytest.approx(2.0)  # the embed scope


def test_a_class_without_operations_reads_zero_and_a_trace_without_scopes_reads_nothing(monkeypatch):
    # Mistral's cell: no frozen layer is differentiated
    got = _shares(monkeypatch, {"a": BODY + "jvp(layer0)/mlp/dot_general:"}, {"a": 1.0})
    assert got["frozen_bwd"] == 0.0 and got["frozen_fwd"] == 100.0
    # the parent's program, or its executable out of a cache keyed without debug information
    got = _shares(monkeypatch, {"a": "jit(train_step)/while/body/closed_call/dot_general:"}, {"a": 1.0})
    assert set(got.values()) == {None}


def test_readers_return_nothing_without_a_trace(tmp_path):
    spec = SPECS["scoped"]
    assert scopes.scope_time_pct({"trace": None}, spec) is None
    assert scopes.newest_xplane(str(tmp_path)) is None
    older = tmp_path / ".chipbench_trace" / "cell-a" / "plugins" / "profile" / "t1"
    newer = tmp_path / ".chipbench_trace" / "cell-b" / "plugins" / "profile" / "t2"
    for d, stamp in ((older, 1_000), (newer, 2_000)):
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
        os.utime(d / "host.xplane.pb", (stamp, stamp))
    assert scopes.newest_xplane(str(tmp_path)) == str(newer / "host.xplane.pb")


def test_train_step_load_s_reads_the_programs_compile_ledger():
    spec = {"program": "train_step"}
    ledger = {"programs": {"train_step": {"compiles": 1, "compile_s": 41.5}}, "recompiles_after_warmup": 0}
    assert scopes.train_step_load_s({"compile_ledger": ledger}, spec) == 41.5
    assert scopes.train_step_load_s({"compile_ledger": {"programs": {}}}, spec) is None
    assert scopes.train_step_load_s({}, spec) is None
