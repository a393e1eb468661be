"""True multi-process distributed training test.

Launches TWO separate python processes running the real ``training.py`` CLI,
rendezvousing through ``jax.distributed.initialize`` (coordinator = process 0,
the reference's MASTER_ADDR/MASTER_PORT contract) with one CPU device each —
so the fsdp=2 mesh spans PROCESS boundaries and every collective crosses a
real process gap, unlike the 8-virtual-device single-process tests.

This is the test the reference could never write (its multi-node behavior was
only validated on a live cluster — SURVEY.md §4): rendezvous, cross-process
batch assembly, sharded compute, host-0-only artifact writes, and the shared
summary contract, all on one machine.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_training(tmp_path):
    from llm_fine_tune_distributed_tpu.data.convert import convert_jsonl_to_parquet

    jsonl = tmp_path / "qa.jsonl"
    with open(jsonl, "w") as f:
        for i in range(48):
            f.write(json.dumps({
                "topic": "Knots",
                "question": f"question {i}?",
                "answer": f"answer {i}: " + "word " * (3 + i % 4),
            }) + "\n")
    convert_jsonl_to_parquet(str(jsonl), str(tmp_path / "qa_dataset.parquet"), verbose=False)

    out = tmp_path / "outputs"
    cfg = {
        "model_name": "tiny-random",
        "model_preset": "tiny",
        "tokenizer_path": "byte-chatml",
        "system_prompt": "You are an expert.",
        "data_dir": str(tmp_path),
        "dataset_file": "qa_dataset.parquet",
        "output_dir": str(out),
        "epochs": 1,
        "per_device_batch_size": 2,
        "gradient_accumulation_steps": 2,
        "learning_rate": 2e-3,
        "max_seq_length": 128,
        "eval_steps": 4,
        "logging_steps": 2,
        "save_steps": 100,
        "mesh": {"data": 1, "fsdp": 2, "tensor": 1, "seq": 1},
        "use_native_loader": False,
        "heartbeat": False,
        # exercise the cross-host checksum exchange (runtime/desync.py) in a
        # REAL multi-process world every few steps
        "desync_check_steps": 4,
    }
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(cfg))

    port = _free_port()
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update(
            WORLD_SIZE="2",
            RANK=str(rank),
            MASTER_ADDR="127.0.0.1",
            MASTER_PORT=str(port),
            XLA_FLAGS="--xla_force_host_platform_device_count=1",
            JAX_PLATFORMS="cpu",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, os.path.join(REPO, "training.py"),
                 "--config", str(cfg_path), "--platform", "cpu"],
                env=env,
                cwd=REPO,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )

    outputs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process training timed out (rendezvous hang?)")
        outputs.append(stdout)

    for rank, (p, text) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{text[-4000:]}"

    # host-0 artifact contract; host 1 must NOT have written duplicates
    assert (out / "best_model" / "model.safetensors").exists()
    with open(out / "training_summary.json") as f:
        summary = json.load(f)
    assert summary["world_size"] == 2
    assert summary["distributed_training"] is True
    history = json.loads((out / "training_history.json").read_text())
    losses = [h["loss"] for h in history if "loss" in h]
    assert losses and all(np.isfinite(l) for l in losses)
    # the completion banner is host-0-gated (reference rank-0 prints)
    assert "completed successfully" in outputs[0]
    assert "completed successfully" not in outputs[1]


@pytest.mark.slow
def test_two_process_cross_host_sequence_parallel(tmp_path):
    """The seq axis SPANS process boundaries: 2 processes x 1
    device, mesh seq=2, ring attention — each host loads the same batch rows
    and its device holds a sequence slice; the ring's ppermute crosses the
    process gap every step."""
    from llm_fine_tune_distributed_tpu.data.convert import convert_jsonl_to_parquet

    jsonl = tmp_path / "qa.jsonl"
    with open(jsonl, "w") as f:
        for i in range(32):
            f.write(json.dumps({
                "topic": "Knots",
                "question": f"question {i}?",
                "answer": f"answer {i}: " + "word " * (3 + i % 4),
            }) + "\n")
    convert_jsonl_to_parquet(str(jsonl), str(tmp_path / "qa_dataset.parquet"), verbose=False)

    out = tmp_path / "outputs"
    cfg = {
        "model_name": "tiny-random",
        "model_preset": "tiny",
        "tokenizer_path": "byte-chatml",
        "system_prompt": "You are an expert.",
        "data_dir": str(tmp_path),
        "dataset_file": "qa_dataset.parquet",
        "output_dir": str(out),
        "epochs": 1,
        "per_device_batch_size": 2,
        "gradient_accumulation_steps": 2,
        "learning_rate": 2e-3,
        "max_seq_length": 128,
        "eval_steps": 4,
        "logging_steps": 2,
        "save_steps": 100,
        "attention_impl": "ring",
        "mesh": {"data": 1, "fsdp": 1, "tensor": 1, "seq": 2},
        "use_native_loader": False,
        "heartbeat": False,
    }
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(cfg))

    port = _free_port()
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update(
            WORLD_SIZE="2",
            RANK=str(rank),
            MASTER_ADDR="127.0.0.1",
            MASTER_PORT=str(port),
            XLA_FLAGS="--xla_force_host_platform_device_count=1",
            JAX_PLATFORMS="cpu",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, os.path.join(REPO, "training.py"),
                 "--config", str(cfg_path), "--platform", "cpu"],
                env=env,
                cwd=REPO,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )

    outputs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("cross-host seq-parallel training timed out")
        outputs.append(stdout)

    for rank, (p, text) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{text[-4000:]}"

    assert (out / "best_model" / "model.safetensors").exists()
    history = json.loads((out / "training_history.json").read_text())
    losses = [h["loss"] for h in history if "loss" in h]
    assert len(losses) >= 2 and all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], f"no learning: {losses[0]} -> {losses[-1]}"
    assert "completed successfully" in outputs[0]


_DECODE_PROBE = r"""
import json, os, sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=os.environ["MASTER_ADDR"] + ":" + os.environ["MASTER_PORT"],
    num_processes=int(os.environ["WORLD_SIZE"]),
    process_id=int(os.environ["RANK"]),
)
import jax.numpy as jnp
from llm_fine_tune_distributed_tpu.data.tokenizer import ByteChatMLTokenizer
from llm_fine_tune_distributed_tpu.infer import GenerationConfig, Generator
from llm_fine_tune_distributed_tpu.infer.generate import make_tp_mesh
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import init_params

mc = get_preset("tiny")
params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
mesh = make_tp_mesh(2)  # spans BOTH single-device processes
assert len({d.process_index for d in mesh.devices.flat}) == 2
gen = Generator(params, mc, ByteChatMLTokenizer(), compute_dtype=jnp.float32,
                eos_token_ids=[], mesh=mesh)
tok = ByteChatMLTokenizer()
cfg = GenerationConfig(max_new_tokens=8, do_sample=False, repetition_penalty=1.1)
out = gen.generate_batch(
    [tok.encode("the quick brown fox"), tok.encode("water water water")], cfg
)
if jax.process_index() == 0:
    with open(sys.argv[1], "w") as f:
        json.dump(out, f)
print("DECODE PROBE OK", jax.process_index())
"""


@pytest.mark.slow
def test_two_process_tensor_parallel_decode_parity(tmp_path):
    """Multi-host inference: a tensor=2 mesh spanning TWO
    single-device processes decodes with greedy BIT-PARITY (f32) against the
    single-process meshless Generator — weights placed via global arrays,
    TP psums crossing a real process boundary every layer."""
    port = _free_port()
    out_file = tmp_path / "decode.json"
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update(
            WORLD_SIZE="2",
            RANK=str(rank),
            MASTER_ADDR="127.0.0.1",
            MASTER_PORT=str(port),
            XLA_FLAGS="--xla_force_host_platform_device_count=1",
            JAX_PLATFORMS="cpu",
            PYTHONPATH=REPO,
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _DECODE_PROBE, str(out_file)],
                env=env, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
        )
    outputs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("2-process TP decode timed out (rendezvous hang?)")
        outputs.append(stdout)
    for rank, (p, text) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{text[-4000:]}"

    # single-process reference: same seeded init, no mesh
    import jax
    import jax.numpy as jnp

    from llm_fine_tune_distributed_tpu.data.tokenizer import ByteChatMLTokenizer
    from llm_fine_tune_distributed_tpu.infer import GenerationConfig, Generator
    from llm_fine_tune_distributed_tpu.models.configs import get_preset
    from llm_fine_tune_distributed_tpu.models.transformer import init_params

    mc = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    tok = ByteChatMLTokenizer()
    ref = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    cfg = GenerationConfig(max_new_tokens=8, do_sample=False, repetition_penalty=1.1)
    expected = ref.generate_batch(
        [tok.encode("the quick brown fox"), tok.encode("water water water")], cfg
    )
    got = json.loads(out_file.read_text())
    assert got == expected, f"multi-host TP decode diverged: {got} != {expected}"


_COORD_PROBE = r"""
import json, os, sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=os.environ["MASTER_ADDR"] + ":" + os.environ["MASTER_PORT"],
    num_processes=int(os.environ["WORLD_SIZE"]),
    process_id=int(os.environ["RANK"]),
)
import jax.numpy as jnp
from llm_fine_tune_distributed_tpu.data.tokenizer import ByteChatMLTokenizer
from llm_fine_tune_distributed_tpu.infer import GenerationConfig, Generator
from llm_fine_tune_distributed_tpu.infer.generate import make_tp_mesh
from llm_fine_tune_distributed_tpu.infer.multihost import MultihostCoordinator, follow
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import init_params

mc = get_preset("tiny")
params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
tok = ByteChatMLTokenizer()
gen = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[],
                mesh=make_tp_mesh(2))
if jax.process_index() == 0:
    coord = MultihostCoordinator(gen)
    outs = []
    # two batches with DIFFERENT configs: followers must mirror both
    outs.append(coord.generate_batch(
        [tok.encode("the quick brown fox")],
        GenerationConfig(max_new_tokens=6, do_sample=False, repetition_penalty=1.1)))
    outs.append(coord.generate_batch(
        [tok.encode("water water"), tok.encode("abc abc")],
        GenerationConfig(max_new_tokens=4, do_sample=True, temperature=0.8), seed=7))
    coord.stop()
    with open(sys.argv[1], "w") as f:
        json.dump(outs, f)
else:
    follow(gen)
print("COORD PROBE OK", jax.process_index())
"""


@pytest.mark.slow
def test_two_process_serving_coordinator(tmp_path):
    """The multi-host serving bridge: host 0 broadcasts (prompts, config,
    seed) per batch, the follower mirrors the exact generate_batch calls
    (greedy AND sampled, different shapes), and stop() releases it."""
    port = _free_port()
    out_file = tmp_path / "coord.json"
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update(
            WORLD_SIZE="2", RANK=str(rank),
            MASTER_ADDR="127.0.0.1", MASTER_PORT=str(port),
            XLA_FLAGS="--xla_force_host_platform_device_count=1",
            JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _COORD_PROBE, str(out_file)],
                env=env, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
        )
    outputs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("serving-coordinator probe timed out")
        outputs.append(stdout)
    for rank, (p, text) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{text[-4000:]}"
        assert f"COORD PROBE OK {rank}" in text
    outs = json.loads(out_file.read_text())
    assert len(outs) == 2 and len(outs[1]) == 2


_ELASTIC_PROBE = r"""
import json, os, sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=os.environ["MASTER_ADDR"] + ":" + os.environ["MASTER_PORT"],
    num_processes=int(os.environ["WORLD_SIZE"]),
    process_id=int(os.environ["RANK"]),
)
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from llm_fine_tune_distributed_tpu.config import MeshConfig, TrainConfig
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import init_params
from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
from llm_fine_tune_distributed_tpu.parallel.optimizer import build_optimizer
from llm_fine_tune_distributed_tpu.parallel.sharding import _validate_spec, param_spec
from llm_fine_tune_distributed_tpu.runtime.mesh import data_parallel_size, make_mesh
from llm_fine_tune_distributed_tpu.train.checkpoints import CheckpointManager
from llm_fine_tune_distributed_tpu.train.state import TrainState
from llm_fine_tune_distributed_tpu.train.step import build_train_step, jit_train_step
from llm_fine_tune_distributed_tpu.utils.tree import split_by_mask

mode, ckpt_dir, dump = sys.argv[1], sys.argv[2], sys.argv[3]
world = jax.process_count()
mesh = make_mesh(MeshConfig(data=1, fsdp=world, tensor=1, seq=1))
mc = get_preset("tiny")
tc = TrainConfig(model_preset="tiny", per_device_batch_size=1,
                 gradient_accumulation_steps=2, max_seq_length=64)

params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
trainable, frozen = split_by_mask(params, trainable_mask(params, mc, tc))
frozen = {k: v.astype(jnp.bfloat16) for k, v in frozen.items()}
put = lambda flat: {
    k: jax.device_put(
        v, NamedSharding(mesh, _validate_spec(param_spec(k, v.ndim), v.shape, mesh))
    )
    for k, v in flat.items()
}
trainable, frozen = put(trainable), put(frozen)
opt = build_optimizer(tc, None, total_steps=8, data_parallel_size=data_parallel_size(mesh))
rep = NamedSharding(mesh, P())
full_devices = set(np.asarray(mesh.devices).flat)
from jax.experimental import multihost_utils


def on_full_mesh(x):
    # same normalization the trainer applies: scalar opt leaves can come out
    # single-device; route them host-side (eager cross-host device_put is
    # unsupported on the CPU backend) and re-place replicated
    if getattr(x, "sharding", None) and set(x.sharding.device_set) == full_devices:
        return x
    local = np.zeros(x.shape, x.dtype)
    if getattr(x, "is_fully_addressable", True):
        local = np.asarray(jax.device_get(x))
    val = multihost_utils.broadcast_one_to_all(local)
    return jax.device_put(val, rep)


state = TrainState(
    step=jax.device_put(jnp.zeros((), jnp.int32), rep),
    trainable=trainable,
    frozen=frozen,
    opt_state=jax.tree.map(on_full_mesh, jax.jit(opt.init)(trainable)),
)
mgr = CheckpointManager(ckpt_dir)
if mode == "save":
    act = NamedSharding(mesh, P(("data", "fsdp"), None, None))
    step_fn = jit_train_step(build_train_step(mc, tc, opt, activation_sharding=act))
    rng = np.random.RandomState(0)
    bsz = data_parallel_size(mesh)
    sh = NamedSharding(mesh, P(None, ("data", "fsdp")))
    for i in range(2):
        batch = {
            "input_ids": jax.device_put(
                rng.randint(0, mc.vocab_size, (2, bsz, 64)).astype(np.int32), sh),
            "loss_mask": jax.device_put(np.ones((2, bsz, 64), np.float32), sh),
            "attention_mask": jax.device_put(np.ones((2, bsz, 64), np.int32), sh),
        }
        state, _ = step_fn(state, batch)
    mgr.save(int(jax.device_get(state.step)), state)
    mgr.wait()
else:
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), state
    )
    state = mgr.restore(mgr.latest_step, abstract)
mgr.close()

# dump every leaf (trainable + frozen + opt moments + step) from host 0,
# resharded replicated so the bytes are host-fetchable on any world size
leaves, _ = jax.tree_util.tree_flatten_with_path(
    {"step": state.step, "trainable": state.trainable,
     "frozen": state.frozen, "opt": state.opt_state}
)
out = {}
for path, leaf in leaves:
    key = jax.tree_util.keystr(path)
    # eager cross-host device_put is unsupported on the CPU backend; a
    # compiled identity reshard (all-gather collective) is
    v = jax.jit(lambda x: x, out_shardings=rep)(leaf)
    if jax.process_index() == 0:
        out[key] = np.asarray(v)
if jax.process_index() == 0:
    np.savez(dump, **out)
print("ELASTIC PROBE OK", mode, world, jax.process_index())
"""


def _run_elastic_phase(mode, world, ckpt_dir, dump):
    port = _free_port()
    procs = []
    for rank in range(world):
        env = dict(os.environ)
        env.update(
            WORLD_SIZE=str(world), RANK=str(rank),
            MASTER_ADDR="127.0.0.1", MASTER_PORT=str(port),
            XLA_FLAGS="--xla_force_host_platform_device_count=1",
            JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _ELASTIC_PROBE, mode, str(ckpt_dir), str(dump)],
                env=env, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
        )
    outputs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"elastic {mode} (world={world}) timed out")
        outputs.append(stdout)
    for rank, (p, text) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"{mode} world={world} rank {rank} failed:\n{text[-4000:]}"


def _assert_dumps_identical(a_path, b_path):
    a, b = np.load(a_path), np.load(b_path)
    assert sorted(a.files) == sorted(b.files)
    for k in a.files:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


@pytest.mark.slow
def test_elastic_resume_four_to_two_processes(tmp_path):
    """The JobSet restart reality: a sharded Orbax save from
    FOUR processes restores into TWO — every leaf (params, frozen, Adam
    moments, step) bit-identical. Orbax stores global arrays; the fsdp axis
    resize is pure resharding."""
    _run_elastic_phase("save", 4, tmp_path / "ckpt", tmp_path / "saved.npz")
    _run_elastic_phase("restore", 2, tmp_path / "ckpt", tmp_path / "restored.npz")
    _assert_dumps_identical(tmp_path / "saved.npz", tmp_path / "restored.npz")


@pytest.mark.slow
def test_elastic_resume_two_to_four_processes(tmp_path):
    """The inverse resize: save from TWO processes, restore into FOUR."""
    _run_elastic_phase("save", 2, tmp_path / "ckpt", tmp_path / "saved.npz")
    _run_elastic_phase("restore", 4, tmp_path / "ckpt", tmp_path / "restored.npz")
    _assert_dumps_identical(tmp_path / "saved.npz", tmp_path / "restored.npz")
