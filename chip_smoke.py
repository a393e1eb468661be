#!/usr/bin/env python
"""Standing proof that the main path starts on the attached TPU.

    python chip_smoke.py               # one chip: kernels, trainer, server
    python chip_smoke.py --four-chips  # four chips: fsdp=4 trainer vs solo

One chip (what the driver runs), through the entry points a user calls:

1. kernels   the compiled Pallas kernels against their XLA references, on
             the device, at SmolLM3 head shapes: paged int8 decode (block
             length 256) and flash forward/backward with a padding mask.
2. trainer   ``python training.py`` on the ``smollm3_3b`` preset at full
             width and depth (36 layers, hidden 2048, vocab 128256, seq
             1024), random-initialised from the seed, a few optimizer steps
             on a slice of the committed parquet, one eval, ``best_model/``.
3. server    ``python -m llm_fine_tune_distributed_tpu.infer.server
             --engine paged`` on that ``best_model/``, twice: bf16 pool, then
             ``--quantize-kv int8`` (the paged-decode kernel compiled inside
             the engine). Requests over HTTP, then SIGTERM and a clean drain.

``--four-chips`` runs the trainer twice and nothing else: on the mesh
``training.py`` builds by default on a four-chip host (fsdp=4), and on a
one-device mesh in the same run, same seed, data and global batch.

One process owns a chip at a time, so this parent never initialises a JAX
backend: every phase is a child, run strictly one after the other, and each
child states the device it ran on. A phase that is not on a TPU, an assertion
that fails or a child that exits non-zero fails the run; there is no option
that accepts a CPU. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
everything printed before it (losses, seconds, bytes) is an observation of
one run, not a metric.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
# ~9 GB of weights and checkpoint: inside the checkout (the driver gives the
# checkout a disk of its own), never under chiprun_out/, removed at the end
WORK = os.path.join(REPO, ".chip_smoke_work")

SMOLLM3_HEADS = dict(hq=16, hkv=4, d=128)
# bf16 tolerance of sharded-vs-solo parity (.claude/skills/verify/SKILL.md)
SHARDED_VS_SOLO_RTOL = 1e-2


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# --------------------------------------------------------------- children


class Child:
    """A child process whose output is echoed (prefixed) and kept."""

    def __init__(self, tag, cmd, env=None):
        self.tag, self.lines = tag, []
        say(f"{tag}: {' '.join(cmd)}")
        self.proc = subprocess.Popen(
            cmd, cwd=REPO, env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        self._reader = threading.Thread(target=self._pump, daemon=True)
        self._reader.start()

    def _pump(self):
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            self.lines.append(line)
            print(f"  {self.tag}| {line}", flush=True)

    def wait(self, timeout=None) -> int:
        rc = self.proc.wait(timeout=timeout)
        self._reader.join(timeout=10)
        return rc

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()

    def grep(self, pattern):
        rx = re.compile(pattern)
        return [m for m in (rx.search(line) for line in self.lines) if m]


def child_env(**extra):
    env = dict(os.environ)
    env.update({k: str(v) for k, v in extra.items()})
    return env


def require_platform(phase, platform, expect_platform):
    say(f"{phase}: ran on platform {platform!r}")
    if platform != expect_platform:
        raise SystemExit(
            f"chip_smoke: phase {phase} ran on {platform!r}, not "
            f"{expect_platform!r}"
        )


# ---------------------------------------------------------------- kernels


def phase_kernels(expect_platform="tpu", interpret=False):
    """In-process (this IS the child): compiled kernels vs XLA references."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_fine_tune_distributed_tpu.ops.attention import xla_attention
    from llm_fine_tune_distributed_tpu.ops.flash_attention import (
        paged_decode_attention,
        paged_decode_mode,
        pallas_flash_attention,
    )
    from llm_fine_tune_distributed_tpu.ops.int8 import dequantize_kv_gather
    from llm_fine_tune_distributed_tpu.ops.int8_matmul import trunk_matmul_mode
    from llm_fine_tune_distributed_tpu.runtime.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    dev = jax.devices()[0]
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    print("SMOKE_DEVICE " + json.dumps(device), flush=True)
    if dev.platform != expect_platform:
        raise SystemExit(f"kernels: platform {dev.platform!r}, not {expect_platform!r}")
    print(
        f"resolved kernel modes: PAGED_DECODE={paged_decode_mode()} "
        f"TRUNK_MATMUL={trunk_matmul_mode()} interpret={interpret}",
        flush=True,
    )

    hq, hkv, d = (SMOLLM3_HEADS[k] for k in ("hq", "hkv", "d"))
    rng = np.random.RandomState(0)

    # --- paged int8 decode, server default block length
    num_blocks, block_len, b, nb = 24, 256, 4, 4
    q = jnp.asarray(rng.randn(b, 1, hq, d), jnp.bfloat16)
    ck = jnp.asarray(rng.randint(-127, 128, (num_blocks, block_len, hkv, d)), jnp.int8)
    cv = jnp.asarray(rng.randint(-127, 128, (num_blocks, block_len, hkv, d)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.5, 4.0, (num_blocks, hkv)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.5, 4.0, (num_blocks, hkv)), jnp.float32)
    # block 0 is the null block: zero codes, zero scale
    ck, cv = ck.at[0].set(0), cv.at[0].set(0)
    ks, vs = ks.at[0].set(0.0), vs.at[0].set(0.0)
    tables = np.zeros((b, nb), np.int32)
    lengths = np.asarray([1, 256, 700, 1024], np.int32)
    free = iter(rng.permutation(np.arange(1, num_blocks)))
    for r in range(b):
        for j in range(-(-int(lengths[r]) // block_len)):
            tables[r, j] = next(free)
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)

    got = jax.jit(
        lambda *a: paged_decode_attention(*a, lengths=lengths, interpret=interpret)
    )(q, ck, cv, ks, vs, tables)

    def reference(q, ck, cv, ks, vs, tables):
        k = dequantize_kv_gather(ck, ks, tables, jnp.float32)
        v = dequantize_kv_gather(cv, vs, tables, jnp.float32)
        mask = jnp.arange(k.shape[1])[None, None, :] < lengths[:, None, None]
        return xla_attention(q.astype(jnp.float32), k, v, mask=mask, causal=False)

    ref = jax.jit(reference)(q, ck, cv, ks, vs, tables)
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(got).all(), "paged decode kernel: non-finite output"
    print(f"paged decode kernel vs XLA gather: max |diff| {np.abs(got - ref).max():.3e}")
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-2)

    # --- flash forward + backward with a right-padding mask
    s = 1024
    q = jnp.asarray(rng.randn(2, s, hq, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(2, s, hkv, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(2, s, hkv, d), jnp.bfloat16)
    pad = jnp.asarray(np.arange(s)[None, :] < np.asarray([[s], [700]]), jnp.int32)
    w = jnp.asarray(rng.randn(2, s, hq, d), jnp.float32) * pad[:, :, None, None]

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v, padding_mask=pad).astype(jnp.float32) * w).sum()

    flash = lambda q, k, v, **kw: pallas_flash_attention(q, k, v, interpret=interpret, **kw)
    o_f, g_f = jax.jit(jax.value_and_grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    o_x, g_x = jax.jit(jax.value_and_grad(loss(xla_attention), argnums=(0, 1, 2)))(q, k, v)
    for name, a, r in (("dq", g_f[0], g_x[0]), ("dk", g_f[1], g_x[1]), ("dv", g_f[2], g_x[2])):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        assert np.isfinite(a).all(), f"flash {name}: non-finite"
        rel = np.linalg.norm(a - r) / np.linalg.norm(r)
        print(f"flash {name} vs XLA attention: relative error {rel:.3e}")
        assert rel < 2e-2, f"flash {name} off by {rel}"
    rel = abs(float(o_f) - float(o_x)) / max(abs(float(o_x)), 1.0)
    print(f"flash weighted output sum vs XLA: relative error {rel:.3e}")
    assert rel < 2e-2
    print("KERNELS_OK", flush=True)
    return device


def run_kernels_child(expect_platform):
    child = Child("kernels", [sys.executable, __file__, "--phase", "kernels"])
    try:
        rc = child.wait(timeout=600)
    finally:
        child.kill()
    if rc != 0 or not child.grep(r"^KERNELS_OK$"):
        raise SystemExit(f"chip_smoke: kernels phase failed (rc={rc})")
    device = json.loads(child.grep(r"^SMOKE_DEVICE (.*)$")[0].group(1))
    require_platform("kernels", device["platform"], expect_platform)
    return device


# ---------------------------------------------------------------- trainer


def write_data_slice(data_dir, n_rows, min_bytes):
    """The first ``n_rows`` rows of the committed parquet whose question +
    answer fill ``min_bytes``: with the system prompt below every row then
    fills the sequence, so each token weighs the same however the global
    batch is cut into microbatches (one device or four)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    table = pq.read_table(os.path.join(REPO, "data", "qa_dataset.parquet"))
    rows = [
        r for r in table.to_pylist()
        if len((r["full-question"] + r["answer"]).encode()) >= min_bytes
    ][:n_rows]
    if len(rows) < n_rows:
        raise SystemExit(f"only {len(rows)} rows of >= {min_bytes} bytes")
    os.makedirs(data_dir, exist_ok=True)
    pq.write_table(
        pa.Table.from_pylist(rows, schema=table.schema),
        os.path.join(data_dir, "qa_dataset.parquet"),
    )


def run_trainer(
    tag, out_dir, *, preset, seq, microbatch, accum, n_rows, expect_platform,
    expect_devices, mesh_env=None, scrape_memory=False,
    dump_hlo=False, timeout=1500,
):
    """``python training.py --config ...`` as a child; returns what it logged."""
    from llm_fine_tune_distributed_tpu.data.prompts import (
        WILDERNESS_EXPERT_SYSTEM_PROMPT,
    )

    # a system prompt of seq - 224 bytes + rows of >= 224 bytes: full rows
    prompt_bytes = max(seq - 224, 16)
    data_dir = os.path.join(WORK, "data")
    write_data_slice(data_dir, n_rows, min_bytes=min(224, seq))
    with open(os.path.join(REPO, "benchmarks", "flagship_tpu.json")) as f:
        cfg = json.load(f)  # the recipe that fits one 16 GB chip
    cfg.update(
        model_name="chip-smoke-random-init",  # not a directory: seeded init
        model_preset=preset,
        tokenizer_path="byte-chatml",
        data_dir=data_dir,
        output_dir=out_dir,
        system_prompt=WILDERNESS_EXPERT_SYSTEM_PROMPT[:prompt_bytes],
        epochs=1,
        per_device_batch_size=microbatch,
        gradient_accumulation_steps=accum,
        max_seq_length=seq,
        attention_impl="flash",
        scale_lr_by_data_parallel=False,
        logging_steps=1,
        eval_steps=10_000,  # one eval, at the end
        save_steps=10_000,  # one checkpoint, at the end
        checkpoint_trainable_only=True,
        checkpoint_async_snapshot=False,
        best_model_tracking="checkpoint",
    )
    os.makedirs(out_dir, exist_ok=True)
    cfg_path = os.path.join(out_dir, "smoke_train.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f, indent=1)

    env = child_env(**(mesh_env or {}))
    port = None
    if scrape_memory:
        port = free_port()
        env["TRAIN_PORT"] = str(port)
    dump_dir = os.path.join(out_dir, "hlo")
    if dump_hlo:
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "") + f" --xla_dump_to={dump_dir} "
            "--xla_dump_hlo_as_text --xla_dump_hlo_module_re=.*train_step.*"
        ).strip()

    t0 = time.time()
    child = Child(tag, [sys.executable, "training.py", "--config", cfg_path], env=env)
    per_device = {}
    try:
        if scrape_memory:
            # the training control plane's per-device HBM gauges, scraped
            # while the run steps: the largest bytes_in_use seen per device
            while child.proc.poll() is None:
                time.sleep(2.0)
                for dev_id, used in scrape_hbm(port).items():
                    per_device[dev_id] = max(per_device.get(dev_id, 0), used)
        rc = child.wait(timeout=timeout)
    finally:
        child.kill()
    wall = time.time() - t0
    if rc != 0:
        raise SystemExit(f"chip_smoke: {tag} exited {rc}")

    runtime = child.grep(
        r"\[runtime\] \{'platform': '(\w+)', 'device_kind': '([^']+)'.*"
        r"'global_devices': (\d+)"
    )
    if not runtime:
        raise SystemExit(f"chip_smoke: {tag} never stated its device")
    platform, kind, count = runtime[0].group(1), runtime[0].group(2), int(runtime[0].group(3))
    require_platform(tag, platform, expect_platform)
    if count != expect_devices:
        raise SystemExit(f"chip_smoke: {tag} saw {count} devices, expected {expect_devices}")

    if child.grep(r"native loader unavailable"):
        raise SystemExit(f"chip_smoke: {tag}: the native runtime failed to build")
    loader = child.grep(r"\[data\] batches from (\w+)")[0].group(1)
    say(f"{tag}: batches fed by {loader} (NativeBatchLoader = built from native/*.cc)")
    if loader != "NativeBatchLoader":
        raise SystemExit(f"chip_smoke: {tag}: Python fallback loader fed the trainer")

    traced = child.grep(r"\[train\] step program traced on (\w+); (.*)$")
    if not traced:
        raise SystemExit(f"chip_smoke: {tag} never said which attention path it traced")
    say(f"{tag}: {traced[0].group(2)}")
    paths = dict(re.findall(r"(\w+)=(\d+)", traced[0].group(2).split("|")[0]))
    if expect_platform == "tpu" and (
        int(paths.get("flash", 0)) == 0 or int(paths.get("xla", 0)) != 0
    ):
        raise SystemExit(f"chip_smoke: {tag}: flash kernel not traced: {paths}")

    with open(os.path.join(out_dir, "training_history.json")) as f:
        history = json.load(f)
    steps = [h for h in history if "loss" in h and "grad_norm" in h]
    if not 3 <= len(steps) <= 6:
        raise SystemExit(f"chip_smoke: {tag}: {len(steps)} logged steps, wanted 3-5")
    for h in steps:
        if not (math.isfinite(h["loss"]) and math.isfinite(h["grad_norm"])
                and h["grad_norm"] > 0):
            raise SystemExit(f"chip_smoke: {tag}: bad step {h}")
    with open(os.path.join(out_dir, "training_summary.json")) as f:
        summary = json.load(f)
    if not math.isfinite(float(summary["final_eval_loss"])):
        raise SystemExit(f"chip_smoke: {tag}: eval loss {summary['final_eval_loss']}")
    best = os.path.join(out_dir, "best_model")
    if not glob.glob(os.path.join(best, "*.safetensors")):
        raise SystemExit(f"chip_smoke: {tag}: no weights in {best}")

    last = steps[-1]
    say(
        f"{tag}: {len(steps)} steps on mesh {summary.get('mesh')}; loss "
        f"{steps[0]['loss']:.4f} -> {last['loss']:.4f}; grad norm "
        f"{steps[0]['grad_norm']:.4f} -> {last['grad_norm']:.4f}; eval loss "
        f"{summary['final_eval_loss']:.4f}"
    )
    say(
        f"{tag}: wall {wall:.0f}s; first calls (compile + first run) "
        f"{last.get('compile_s_total', float('nan')):.1f}s over "
        f"{last.get('compile_total')} programs; step p50 "
        f"{last.get('phase_step_p50_s')}s; peak HBM "
        f"{last.get('hbm_peak_bytes_in_use', 0) / 2**30:.2f} GiB summed over devices"
    )
    return {
        "device": {"platform": platform, "kind": kind, "count": count},
        "steps": steps, "summary": summary, "best_model": best,
        "per_device_bytes": per_device, "hlo_dir": dump_dir, "wall": wall,
    }


def scrape_hbm(port):
    """{device id: bytes_in_use} from the training control plane's /metrics
    ({} until the plane is up)."""
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=5) as r:
            text = r.read().decode()
    except (urllib.error.URLError, OSError):
        return {}
    return {
        m.group(1): int(float(m.group(2)))
        for m in re.finditer(
            r'^device_hbm_bytes_in_use\{device="(\d+)"\} (\S+)$', text, re.M
        )
    }


# ----------------------------------------------------------------- server


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(method, url, body=None, timeout=300):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method, headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        raw = r.read().decode()
    try:
        return json.loads(raw)
    except json.JSONDecodeError:
        return raw


def run_server(tag, model_dir, *, quantize_kv, expect_platform, new_tokens=24,
               ready_timeout=600):
    port = free_port()
    cmd = [
        sys.executable, "-m", "llm_fine_tune_distributed_tpu.infer.server",
        "--model-dir", model_dir, "--engine", "paged", "--host", "127.0.0.1",
        "--port", str(port), "--flight-dir", os.path.join(WORK, "flight"),
    ]
    if quantize_kv:
        cmd += ["--quantize-kv", "int8"]
    base = f"http://127.0.0.1:{port}"
    t0 = time.time()
    child = Child(tag, cmd, env=child_env())
    try:
        while True:
            if child.proc.poll() is not None:
                raise SystemExit(f"chip_smoke: {tag} died before /healthz (rc={child.proc.returncode})")
            if time.time() - t0 > ready_timeout:
                raise SystemExit(f"chip_smoke: {tag} not healthy after {ready_timeout}s")
            try:
                if http_json("GET", base + "/healthz", timeout=5) == "ok":
                    break
            except (urllib.error.URLError, OSError):
                time.sleep(1.0)
        ready_s = time.time() - t0
        stated = child.grep(r"\[serve\] (\d+) devices \((\w+), (.+)\)")
        if not stated:
            raise SystemExit(f"chip_smoke: {tag} never stated its device")
        require_platform(tag, stated[0].group(2), expect_platform)

        ask = {"max_new_tokens": new_tokens, "greedy": True}
        water = "How do I purify water in the wild?"
        # The first request prefills its whole prompt; the second and third
        # find its full blocks in the prefix cache and prefill the rest. The
        # identical greedy pair is the second and third: the same request down
        # the same path. The first against the second is printed, not
        # asserted: the two paths may round differently in bf16, and a
        # near-random model's logits are near-tied.
        questions = [water, water, water,
                     "What is the safest way to start a fire in wet weather?"]
        # The byte tokenizer decodes ids < 256 only, so a near-random model
        # at vocab 128256 answers with mostly empty TEXT: output is judged on
        # the generated token ids every response carries.
        t_req = time.time()
        answers, token_ids = [], []
        for qn in questions:
            before = http_json("GET", base + "/v1/stats")["tokens_served"]
            resp = http_json("POST", base + "/v1/generate", dict(ask, question=qn))
            served = http_json("GET", base + "/v1/stats")["tokens_served"] - before
            answers.append(resp["answer"])
            token_ids.append(resp["token_ids"])
            if not resp["token_ids"] or served < len(resp["token_ids"]):
                raise SystemExit(
                    f"chip_smoke: {tag}: {qn!r} returned {len(resp['token_ids'])} "
                    f"token ids, /v1/stats counted {served}"
                )
        first_req_s = time.time() - t_req
        if not all(isinstance(a, str) for a in answers):
            raise SystemExit(f"chip_smoke: {tag}: answers are not text: {answers!r}")
        # With the bf16 pool that pair must be identical. With the int8 pool
        # it is printed: a block's quantization scale only ever grows and is
        # not reset when the block is freed, so a recycled block quantizes
        # under its last owner's scale and the answer depends on the pool's
        # history (PERF.md section 7) — with near-tied logits, visibly.
        pair_equal = token_ids[1] == token_ids[2]
        if not pair_equal and not quantize_kv:
            raise SystemExit(
                f"chip_smoke: {tag}: identical greedy requests returned different "
                f"token ids: {token_ids[1]} vs {token_ids[2]}"
            )
        # one stream (SSE data: lines; body key "question"): the same greedy
        # request once more, so its ids must be the pair's
        req = urllib.request.Request(
            base + "/v1/stream", method="POST",
            data=json.dumps(dict(ask, question=water)).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            events = [ln for ln in r.read().decode().splitlines() if ln.startswith("data:")]
        done = json.loads(events[-1][len("data:"):]) if events else {}
        if not done.get("done") or done.get("n_tokens", 0) < 1:
            raise SystemExit(f"chip_smoke: {tag}: /v1/stream ended with {events[-1:]}")
        stream_equal = done["token_ids"] == token_ids[1]
        if not stream_equal and not quantize_kv:
            raise SystemExit(
                f"chip_smoke: {tag}: /v1/stream and /v1/generate disagree on the "
                f"same greedy request: {done['token_ids']} vs {token_ids[1]}"
            )

        stats = http_json("GET", base + "/v1/stats")
        n_req = len(questions) + 1
        if stats["requests_completed"] != n_req or stats["tokens_served"] < n_req:
            raise SystemExit(f"chip_smoke: {tag}: /v1/stats does not count the requests: {stats}")
        peak = sum(
            (d.get("peak_bytes_in_use") or 0) for d in stats.get("device_memory", {}).values()
        )
        say(
            f"{tag}: healthy after {ready_s:.0f}s; {n_req} requests, "
            f"{stats['tokens_served']} tokens served in {stats['decode_steps']} decode "
            f"steps; {len(questions)} generates took {first_req_s:.1f}s (compiles "
            f"included); identical pair equal: {pair_equal}, stream equal to "
            f"it: {stream_equal}"
            f"{' (printed, not asserted, with the int8 pool)' if quantize_kv else ''}"
            f"; first (whole prefill) equal to second (prefix reused): "
            f"{token_ids[0] == token_ids[1]}; "
            f"tokens per generate {[len(t) for t in token_ids]}; stream sent "
            f"{done['n_tokens']} tokens in {len(events)} events; peak HBM "
            f"{peak / 2**30:.2f} GiB; ids[0][:8] = {token_ids[0][:8]}; "
            f"answer[0] = {answers[0][:40]!r}"
        )
        child.proc.send_signal(signal.SIGTERM)
        rc = child.wait(timeout=120)
    finally:
        child.kill()
    if rc != 0:
        raise SystemExit(f"chip_smoke: {tag}: drain exited {rc}, wanted 0")
    if not child.grep(r"\[serve\] drained; exiting"):
        raise SystemExit(f"chip_smoke: {tag}: no clean drain in the log")
    say(f"{tag}: SIGTERM -> drained, exit 0")
    return token_ids


# ------------------------------------------------------------- the two runs


def remove_native_binaries():
    """Only what git would commit may feed the run: any built ``.so`` beside
    the native sources goes (a copied tree flattens the mtimes that
    runtime/native.py compares), and the trainer child rebuilds it."""
    native = os.path.join(REPO, "llm_fine_tune_distributed_tpu", "native")
    for so in glob.glob(os.path.join(native, "*.so")):
        os.remove(so)
        say(f"removed prebuilt {os.path.relpath(so, REPO)}")


def rows_for(train_samples):
    """Rows to slice so that ``train_samples`` remain after the trainer's 10%
    validation split."""
    return int(train_samples / 0.9) + 2


def one_chip(expect_platform="tpu", preset="smollm3_3b", seq=1024, microbatch=2,
             accum=16, steps=4):
    device = run_kernels_child(expect_platform)
    remove_native_binaries()
    n_rows = rows_for(microbatch * accum * steps)
    train = run_trainer(
        "trainer", os.path.join(WORK, "train"), preset=preset, seq=seq,
        microbatch=microbatch, accum=accum, n_rows=n_rows,
        expect_platform=expect_platform, expect_devices=1,
    )
    if train["device"] != device:
        raise SystemExit(f"chip_smoke: device changed between phases: {device} vs {train['device']}")
    bf16 = run_server(
        "server-bf16", train["best_model"], quantize_kv=False,
        expect_platform=expect_platform,
    )
    int8 = run_server(
        "server-int8kv", train["best_model"], quantize_kv=True,
        expect_platform=expect_platform,
    )
    # near-tied logits of a near-random model: agreement between the pools is
    # observed, not asserted (the kernels phase holds the int8 kernel to XLA)
    agree = [
        sum(x == y for x, y in zip(a, b)) / max(len(a), len(b))
        for a, b in zip(int8, bf16)
    ]
    say(f"share of token ids equal between the int8-KV and bf16 pools, by request: {agree}")
    return device


def four_chips(expect_platform="tpu", preset="smollm3_3b", seq=1024, microbatch=2,
               accum=16, steps=4, n_devices=4, check_memory=True):
    """The recipe of the one-chip run, unchanged (benchmarks/flagship_tpu.json),
    on the mesh ``training.py`` builds by default and on a one-device mesh."""
    remove_native_binaries()
    n_rows = rows_for(microbatch * accum * steps)
    common = dict(
        preset=preset, seq=seq, microbatch=microbatch, n_rows=n_rows,
        expect_platform=expect_platform, expect_devices=n_devices,
    )
    # the mesh training.py builds by default: fsdp = every device
    sharded = run_trainer(
        "trainer-fsdp4", os.path.join(WORK, "train_fsdp4"),
        accum=accum // n_devices, scrape_memory=True, dump_hlo=True, **common,
    )
    shutil.rmtree(os.path.join(WORK, "train_fsdp4", "best_model"))
    shutil.rmtree(os.path.join(WORK, "train_fsdp4", "checkpoints"), ignore_errors=True)
    # the same global batch on a one-device mesh (a fully specified mesh
    # takes a prefix of the devices, runtime/mesh.py)
    solo = run_trainer(
        "trainer-solo", os.path.join(WORK, "train_solo"), accum=accum,
        mesh_env={f"MESH_{a}": 1 for a in ("DATA", "FSDP", "TENSOR", "SEQ", "EXPERT", "PIPE")},
        **common,
    )
    if sharded["summary"]["mesh"]["fsdp"] != n_devices or solo["summary"]["mesh"]["fsdp"] != 1:
        raise SystemExit("chip_smoke: meshes are not fsdp=4 and solo")

    say("step | loss fsdp4 / solo | grad norm fsdp4 / solo")
    for a, b in zip(sharded["steps"], solo["steps"]):
        say(f"{a['step']:>4} | {a['loss']:.5f} / {b['loss']:.5f} | "
            f"{a['grad_norm']:.5f} / {b['grad_norm']:.5f}")
    a, b = sharded["steps"][0], solo["steps"][0]
    for key in ("loss", "grad_norm"):
        rel = abs(a[key] - b[key]) / abs(b[key])
        say(f"first-step {key}: relative difference {rel:.3e} (bound {SHARDED_VS_SOLO_RTOL})")
        if rel > SHARDED_VS_SOLO_RTOL:
            raise SystemExit(f"chip_smoke: first-step {key} differs: {a[key]} vs {b[key]}")

    per_device = sharded["per_device_bytes"]
    say("largest bytes_in_use seen per device (fsdp4): "
        + ", ".join(f"{k}: {v / 2**30:.2f} GiB" for k, v in sorted(per_device.items())))
    if check_memory:  # a CPU rehearsal has no memory_stats to read
        if len(per_device) != n_devices:
            raise SystemExit(f"chip_smoke: memory gauges for {len(per_device)} devices, wanted {n_devices}")
        if min(per_device.values()) < 0.5 * max(per_device.values()):
            raise SystemExit(f"chip_smoke: devices do not hold comparable shares: {per_device}")

    counts = count_collectives(sharded["hlo_dir"], n_devices)
    say(f"collectives over {n_devices} devices in the compiled fsdp4 step: {counts}")
    if not counts["all-gather"] or not (counts["all-reduce"] + counts["reduce-scatter"]):
        raise SystemExit("chip_smoke: the compiled step holds no fsdp collectives")
    return sharded["device"]


def count_collectives(hlo_dir, group_size):
    """Collective ops whose replica groups span ``group_size`` devices, in
    the optimised HLO the compiler dumped for the train step. On an fsdp-only
    mesh every such group is the fsdp axis."""
    files = sorted(
        glob.glob(os.path.join(hlo_dir, "*train_step*after_optimizations.txt")),
        key=os.path.getsize,
    )
    if not files:
        raise SystemExit(f"chip_smoke: no optimised HLO of the train step in {hlo_dir}")
    with open(files[-1]) as f:
        text = f.read()
    counts = {}
    for op in ("all-gather", "all-reduce", "reduce-scatter"):
        n = 0
        for line in text.splitlines():
            if not re.search(rf"= .*\b{op}(-start)?\(", line):
                continue
            iota = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
            listed = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
            size = int(iota.group(2)) if iota else (
                len(listed.group(1).split(",")) if listed else 0
            )
            n += size == group_size
        counts[op] = n
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the fsdp=4 trainer and its one-device control, nothing else")
    ap.add_argument("--phase", choices=["kernels"], help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.phase == "kernels":  # child: owns the chip for its lifetime
        phase_kernels()
        return 0

    from llm_fine_tune_distributed_tpu.runtime.compile_cache import (
        enable_compile_cache,
    )

    t0 = time.time()
    say(f"compile cache: {enable_compile_cache()}")
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    try:
        device = four_chips() if args.four_chips else one_chip()
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    say(f"all phases passed in {time.time() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
