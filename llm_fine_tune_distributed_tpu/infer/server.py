"""Minimal HTTP serving for the tuned model, with dynamic request batching.

The reference has NO serving server — inference is CLI-only, and
``examples/openshift-deploy.yaml`` (C21) is an unrelated KServe template kept
"for a future endpoint" (SURVEY.md §2.1 C21, "not present" list). This
closes that gap with a dependency-free stdlib server exposing:

  GET  /healthz                      -> 200 "ok" (readiness probe target);
                                        503 while draining, circuit-open,
                                        or multi-host-wedged
  GET  /v1/stats                     -> serving counters/gauges + histogram
                                        percentile summaries + HBM report
                                        (JSON)
  GET  /metrics                      -> the same telemetry as Prometheus
                                        text exposition (scrape target)
  GET  /v1/capacity                  -> capacity observatory: load
                                        forecast, sustainable throughput,
                                        headroom, replica recommendation,
                                        autoscaler decision history
  POST /v1/fleet/scale               -> {"replicas": N} manual fleet
                                        resize within the autoscaler
                                        bounds (fleet servers only)
  POST /v1/generate {"question": .., -> {"answer": .., "token_ids": [..]}
                                        (the generated ids: what a client
                                        compares when the tokenizer cannot
                                        decode them)
        optional: "max_new_tokens", "temperature", "top_p", "top_k",
                  "repetition_penalty", "greedy", "seed", "system_prompt",
                  "adapter" (tenant LoRA adapter name under --adapter-dir;
                  continuous/paged engines — the request's rows gather
                  that adapter's delta inside the shared batch),
                  "trace" (true -> response carries the request's
                  lifecycle span timeline),
                  "priority" ("interactive" | "batch" | "best_effort" —
                  admission tier; continuous/paged engines order by aged
                  tier and shed/preempt the lowest tier first under
                  pressure; default --priority-default),
                  "deadline_ms" (client budget for the whole request —
                  queue + prefill + decode; on expiry the engine cancels
                  it wherever it is and the 504 body carries the tokens
                  generated so far)}

Failures surface through the taxonomy in infer/errors.py: queue overflow
is a 429 with a finite ``Retry-After`` derived from observed service time,
engine restarts / drain / queue-deadline sheds are 503s (retryable),
brownout sheds are tier-labelled 429s, client-deadline expiries are 504s
carrying partial tokens, and fatal engine states are 500s — all with a
structured ``{"error": {kind, message, retryable, ...}}`` body. SIGTERM starts a graceful drain:
admission closes (503 + Retry-After), ``/healthz`` reports ``draining``,
in-flight requests finish up to ``--drain-timeout-s``, then the process
exits 0.

Handlers run on threads; a single worker owns the TPU. Three engines
(``--engine``):

- ``continuous`` (default, single-host): slot-based persistent decode loop
  (infer/engine.py) — mixed greedy/sampled traffic co-batches, freed slots
  refill mid-flight, and /v1/stream rides the shared batch. With
  ``--speculative K`` every tick drafts up to K tokens per slot
  (prompt-lookup, or a small same-vocab model via ``--draft-dir``) and ONE
  fused forward verifies all slots' K+1 positions — speculative requests
  (streaming included) ride the shared batch; without the flag they fall
  back to the window engine's solo program.
- ``paged`` (single-host): the continuous engine over a block-paged KV
  pool (``--kv-block-len``) — decode cost tracks live occupancy, shared
  prompt prefixes prefill once (refcounted block reuse), and long prompts
  prefill in ``--prefill-chunk`` pieces interleaved with decode.
- ``window``: the drain-a-window batcher (infer/batching.py) — the
  multi-host path, and the fallback when per-step host scheduling is
  unwanted. ``--max-batch 1`` restores strict serialization.

``--replicas N`` (continuous/paged, single-host) runs N supervised engine
replicas behind the in-process fleet router (infer/fleet.py): params are
shared read-only, placement follows ``--routing`` (prefix-cache affinity
by default), replica failures fail over to siblings, and ``/v1/stats`` +
``/metrics`` report fleet aggregates plus per-replica series labelled
``replica="i"``. ``--replica-roles prefill,decode,...`` disaggregates the
fleet into prefill/decode pools: new requests land on prefill-capable
replicas, finished prompts hand their KV blocks to a decode replica
through the shared ``--host-tier-mb`` tier (greedy bit-identical; any
handoff failure decodes in place), and ``--autoscale-ratio`` lets the
autoscaler move the pool ratio toward the observed prefill/decode
token-demand split.

Run: ``python -m llm_fine_tune_distributed_tpu.infer.server --model-dir ...``
or ``ask_tuned_model.py --serve``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs


def serve(
    model_dir: str,
    host: str = "0.0.0.0",
    port: int = 8080,
    max_batch: int = 8,
    batch_window_ms: float = 10.0,
    quantize: str = "none",
    quantize_kv: str = "none",
    template_kwargs: Optional[dict] = None,
    request_timeout_s: Optional[float] = 600.0,
    tp: int = 1,
    draft_dir: Optional[str] = None,
    speculative_k: int = 0,
    adapter_dir: Optional[str] = None,
    max_adapters: int = 8,
    adapter_capacity: int = 0,
    engine_kind: str = "continuous",
    replicas: int = 1,
    routing: str = "prefix",
    replica_roles: Optional[str] = None,
    autoscale: str = "dry-run",
    min_replicas: int = 1,
    max_replicas: int = 0,
    scale_cooldown_s: float = 30.0,
    autoscale_ratio: bool = False,
    slots: int = 8,
    kv_buf_len: int = 4096,
    kv_block_len: int = 256,
    prefill_chunk: int = 512,
    host_tier_mb: int = 0,
    migrate_on_retire: bool = False,
    max_queue_depth: int = 256,
    queue_deadline_s: Optional[float] = None,
    priority_default: str = "interactive",
    age_promote_s: float = 5.0,
    brownout_queue_wait_s: float = 2.0,
    brownout_drain_s: float = 10.0,
    brownout_cap_tokens: int = 32,
    drain_timeout_s: float = 30.0,
    restart_backoff_s: float = 0.5,
    restart_backoff_max_s: float = 30.0,
    circuit_threshold: int = 5,
    circuit_window_s: float = 60.0,
    watchdog_timeout_s: float = 0.0,
    flight_dir: Optional[str] = "outputs/flight_recorder",
    trace_log: Optional[str] = None,
    trace_log_max_mb: float = 0.0,
    profile_dir: Optional[str] = None,
    publish_watch_dir: Optional[str] = None,
    publish_poll_s: float = 2.0,
    auto_rollback_window_s: float = 0.0,
    auto_rollback_error_rate: float = 0.5,
    canary_window_s: float = 0.0,
    canary_min_requests: int = 8,
    slo_ttft_p99_s: float = 2.0,
    slo_inter_token_p99_s: float = 0.5,
    slo_error_rate: float = 0.01,
    slo_availability: float = 0.999,
    slo_fast_window_s: float = 60.0,
    slo_slow_window_s: float = 600.0,
    slo_sample_interval_s: float = 1.0,
    control: Optional[dict] = None,
) -> None:
    """``control``, when given, is populated with the drain entry points
    (``begin_drain``, ``httpd``, the engines) so in-process tests can drive
    the SIGTERM path without owning the main thread (signal handlers can
    only be installed there)."""
    from llm_fine_tune_distributed_tpu.data.prompts import WILDERNESS_EXPERT_SYSTEM_PROMPT
    from llm_fine_tune_distributed_tpu.infer import (
        GenerationConfig,
        Generator,
        load_model_dir,
        load_tokenizer_dir,
    )

    from llm_fine_tune_distributed_tpu.infer.batching import (
        PRIORITY_TIERS,
        BatchingEngine,
    )
    from llm_fine_tune_distributed_tpu.infer.errors import (
        DrainingError,
        ServingError,
        error_payload,
    )

    from llm_fine_tune_distributed_tpu.infer.fleet import EngineFleet
    from llm_fine_tune_distributed_tpu.infer.routing import (
        REPLICA_ROLES,
        ROUTING_POLICIES,
    )
    from llm_fine_tune_distributed_tpu.observe.capacity import (
        Autoscaler,
        report_from_capacity_snapshots,
    )
    from llm_fine_tune_distributed_tpu.observe.metrics import (
        PROMETHEUS_CONTENT_TYPE,
        prometheus_exposition,
    )
    from llm_fine_tune_distributed_tpu.observe.profiler import device_memory_report
    from llm_fine_tune_distributed_tpu.observe.xla import (
        CaptureBusyError,
        ProfilerCapture,
    )
    from llm_fine_tune_distributed_tpu.ops.int8 import (
        KV_QUANT_MODES,
        QUANTIZE_MODES,
        maybe_quantize,
    )

    if quantize not in QUANTIZE_MODES:  # fail fast, before the model load
        raise ValueError(
            f"unknown quantize mode {quantize!r} (expected one of {QUANTIZE_MODES})"
        )
    if quantize_kv not in KV_QUANT_MODES:
        raise ValueError(
            f"unknown --quantize-kv mode {quantize_kv!r} (expected one of "
            f"{KV_QUANT_MODES})"
        )
    if quantize_kv != "none" and engine_kind != "paged":
        raise ValueError(
            "--quantize-kv quantizes the PAGED block pool (per-block int8 "
            "scales indexed by block id); the dense/window caches have no "
            "blocks to scale — pick --engine paged or drop --quantize-kv"
        )
    # flag-combination validation mirrors infer/cli.py: a bad speculation
    # setup must fail AT STARTUP with a clear message, not at first request
    speculative_k = max(0, int(speculative_k or 0))
    if draft_dir and not speculative_k:
        raise ValueError(
            "--draft-dir requires --speculative K (the draft model only "
            "runs inside the speculative decode loop)"
        )
    if speculative_k and engine_kind == "window":
        raise ValueError(
            "--speculative K applies to the continuous/paged engines "
            "(engine-level fused draft+verify ticks); the window engine "
            "instead takes per-request speculation via POST /v1/generate "
            "with 'speculative': K — drop --speculative or pick "
            "--engine continuous|paged"
        )
    if adapter_dir and engine_kind == "window":
        raise ValueError(
            "--adapter-dir (multi-tenant LoRA serving) needs a continuous/"
            "paged engine (per-request adapter deltas are gathered inside "
            "the fused slot batch, which the window batcher does not run); "
            "drop --adapter-dir or pick --engine continuous|paged"
        )
    if adapter_dir and not os.path.isdir(adapter_dir):
        raise ValueError(
            f"--adapter-dir not found: {adapter_dir!r} (expected a "
            "directory of PEFT-layout adapter subdirectories)"
        )
    replicas = max(1, int(replicas or 1))
    if routing not in ROUTING_POLICIES:
        raise ValueError(
            f"unknown --routing {routing!r} (expected one of "
            f"{ROUTING_POLICIES})"
        )
    if replicas > 1 and engine_kind == "window":
        raise ValueError(
            "--replicas N needs a continuous/paged engine (the fleet "
            "router places by queue depth and prefix residency, which the "
            "window batcher does not expose); drop --replicas or pick "
            "--engine continuous|paged"
        )
    autoscale = autoscale or "dry-run"
    if autoscale not in Autoscaler.MODES:
        raise ValueError(
            f"unknown --autoscale mode {autoscale!r} (expected one of "
            f"{Autoscaler.MODES})"
        )
    min_replicas = max(1, int(min_replicas or 1))
    max_replicas = max(0, int(max_replicas or 0))
    if max_replicas and max_replicas < replicas:
        raise ValueError(
            "--max-replicas must be >= --replicas (the fleet starts at "
            f"--replicas); got {max_replicas} < {replicas}"
        )
    if min_replicas > replicas:
        raise ValueError(
            "--min-replicas must be <= --replicas (the fleet starts at "
            f"--replicas); got {min_replicas} > {replicas}"
        )
    if max_replicas > replicas and engine_kind == "window":
        raise ValueError(
            "--max-replicas (elastic fleet growth) needs a continuous/"
            "paged engine; drop --max-replicas or pick "
            "--engine continuous|paged"
        )
    host_tier_mb = max(0, int(host_tier_mb or 0))
    if host_tier_mb and engine_kind != "paged":
        raise ValueError(
            "--host-tier-mb spills paged KV BLOCKS to host RAM on eviction/"
            "preemption; the dense/window caches have no blocks to spill — "
            "pick --engine paged or drop --host-tier-mb"
        )
    if migrate_on_retire and not (replicas > 1 or max_replicas > replicas):
        raise ValueError(
            "--migrate-on-retire live-migrates a retiring replica's "
            "requests to SIBLING replicas; it needs a fleet — set "
            "--replicas > 1 (or --max-replicas above --replicas) or drop "
            "--migrate-on-retire"
        )
    # disaggregated prefill/decode pools: per-replica roles, parsed here so
    # a bad role string fails before the model load
    role_list: list = []
    if replica_roles:
        role_list = [
            r.strip() for r in str(replica_roles).split(",") if r.strip()
        ]
        bad = [r for r in role_list if r not in REPLICA_ROLES]
        if bad:
            raise ValueError(
                f"unknown role(s) {bad} in --replica-roles (expected a "
                f"comma list over {REPLICA_ROLES})"
            )
        if len(role_list) != replicas:
            raise ValueError(
                "--replica-roles must name one role per starting replica; "
                f"got {len(role_list)} roles for --replicas {replicas}"
            )
        if not (replicas > 1 or max_replicas > replicas):
            raise ValueError(
                "--replica-roles splits a FLEET into prefill/decode pools; "
                "set --replicas > 1 (or --max-replicas above --replicas) "
                "or drop --replica-roles"
            )
        if any(r != "mixed" for r in role_list) and (
            engine_kind != "paged" or not host_tier_mb
        ):
            raise ValueError(
                "prefill/decode roles hand a request over by shipping its "
                "KV blocks through the shared host tier — they need "
                "--engine paged AND --host-tier-mb > 0; drop "
                "--replica-roles or add both"
            )
    if autoscale_ratio and not any(r != "mixed" for r in role_list):
        raise ValueError(
            "--autoscale-ratio treats the prefill/decode pool ratio as a "
            "scaling dimension; it needs --replica-roles with at least one "
            "prefill or decode replica"
        )
    if publish_watch_dir and engine_kind == "window":
        raise ValueError(
            "--publish-watch-dir (checkpoint hot-swap) needs a continuous/"
            "paged engine — the swap lands at the slot scheduler's tick "
            "boundary, which the window batcher does not have; drop "
            "--publish-watch-dir or pick --engine continuous|paged"
        )
    import jax

    devices = jax.devices()
    print(
        f"[serve] {len(devices)} devices ({devices[0].platform}, "
        f"{devices[0].device_kind})",
        flush=True,
    )
    print(f"Loading model from {model_dir} ...")
    params, model_config = load_model_dir(model_dir)
    params = maybe_quantize(params, quantize)
    tokenizer = load_tokenizer_dir(model_dir)
    mesh = None
    if tp > 1:
        from llm_fine_tune_distributed_tpu.infer.generate import make_tp_mesh

        mesh = make_tp_mesh(tp, model_config)
        print(f"Tensor-parallel decode over {tp} devices")
    draft_kwargs = {}
    if draft_dir:
        # a small same-vocab model turns "speculative": K requests into
        # draft-model speculation (Generator docstring); prompt-lookup
        # remains the draftless fallback behavior when unset
        draft_params, draft_config = load_model_dir(draft_dir)
        draft_kwargs = {"draft_params": draft_params, "draft_config": draft_config}
        print(f"Draft model for speculation: {draft_dir}")
    generator = Generator(params, model_config, tokenizer, mesh=mesh, **draft_kwargs)
    coordinator = None
    slot_bridge = None
    engine_target = generator
    if getattr(generator, "_multihost", False):
        if engine_kind in ("continuous", "paged"):
            # sharded slot engines over the tick protocol: process 0 owns
            # HTTP, batching state, and settlement, and announces every
            # device dispatch over the slot bridge; followers mirror each
            # dispatch against their shards of the global cache/pool
            from llm_fine_tune_distributed_tpu.infer.multihost import (
                SlotBridge,
                follow_slots,
            )

            if replicas > 1 or max_replicas > replicas:
                raise ValueError(
                    "--replicas/--max-replicas scale-out is per-host and "
                    "cannot share one slot bridge; multi-host --tp serving "
                    "runs ONE sharded engine per fleet — run one server per "
                    "slice behind an external balancer instead"
                )
            if jax.process_index() != 0:
                follower_adapters = None
                if adapter_dir:
                    from llm_fine_tune_distributed_tpu.infer.adapters import (
                        AdapterRegistry,
                    )

                    follower_adapters = AdapterRegistry(
                        generator.params, adapter_dir,
                        max_adapters=max_adapters, mesh=mesh,
                    )
                print(
                    f"[serve] process {jax.process_index()}: following "
                    f"host 0's {engine_kind} slot engine"
                )
                follow_slots(generator, adapters=follower_adapters)
                return
            slot_bridge = SlotBridge()
            print(
                f"[serve] coordinating {jax.process_count()} hosts "
                f"({engine_kind} slot engine over the tick bridge)"
            )
        else:
            from llm_fine_tune_distributed_tpu.infer.multihost import (
                MultihostCoordinator,
                follow,
            )

            if jax.process_index() != 0:
                # follower hosts never serve HTTP: they mirror process 0's
                # batches until the coordinator stops them
                print(f"[serve] process {jax.process_index()}: following host 0")
                follow(generator)
                return
            coordinator = MultihostCoordinator(generator)
            engine_target = coordinator
            print(f"[serve] coordinating {jax.process_count()} hosts")
            if speculative_k:
                raise ValueError(
                    "--speculative K needs a continuous/paged engine; those "
                    "now serve multi-host meshes too — start with "
                    "--engine continuous|paged --tp N instead of "
                    "--engine window"
                )
            if adapter_dir:
                raise ValueError(
                    "--adapter-dir needs a continuous/paged engine; those "
                    "now serve multi-host meshes too — start with "
                    "--engine continuous|paged --tp N instead of "
                    "--engine window (or merge ONE adapter into the "
                    "weights via parallel/lora.merge_lora and serve that "
                    "checkpoint)"
                )
    if engine_kind not in ("continuous", "paged", "window"):
        raise ValueError(
            f"unknown engine {engine_kind!r} (expected 'continuous', 'paged' "
            "or 'window')"
        )
    # The window engine always exists: it is the multi-host path AND the
    # carrier for speculative requests when the slot engines were started
    # WITHOUT --speculative (engine-level speculation compiles the fused
    # draft+verify slot step up front; K=0 engines keep the plain step).
    engine = BatchingEngine(engine_target, max_batch=max_batch, window_ms=batch_window_ms)
    cont_engine = None
    cont_kind = "window"
    # supervision + admission knobs shared by both slot engines
    engine_kwargs = {
        "max_queue_depth": max_queue_depth,
        "queue_deadline_s": queue_deadline_s,
        "restart_backoff_s": restart_backoff_s,
        "restart_backoff_max_s": restart_backoff_max_s,
        "circuit_threshold": circuit_threshold,
        "circuit_window_s": circuit_window_s,
        "watchdog_timeout_s": watchdog_timeout_s,
        "speculative_k": speculative_k,
        "flight_dir": flight_dir or None,
        "trace_log": trace_log or None,
        # overload control (infer/engine.py): default priority tier for
        # requests that don't name one, anti-starvation aging rate, and the
        # brownout controller's pressure budgets / best_effort token cap
        "priority_default": priority_default,
        "age_promote_s": age_promote_s,
        "brownout_queue_wait_s": brownout_queue_wait_s,
        "brownout_drain_s": brownout_drain_s,
        "brownout_cap_tokens": brownout_cap_tokens,
        # SLO engine (observe/slo.py): trace-log rotation bound and the
        # metric-ring sample cadence; each replica gets its OWN SloPolicy
        # in _make_replica (the policy carries breach-transition state)
        "trace_log_max_mb": trace_log_max_mb,
        "slo_sample_interval_s": slo_sample_interval_s,
    }
    # ONE host tier shared by every paged replica (infer/paged.HostBlockTier):
    # the sharing is what live slot migration ships blocks through
    host_tier = None
    if host_tier_mb and engine_kind == "paged" and slot_bridge is None:
        from llm_fine_tune_distributed_tpu.infer.paged import HostBlockTier

        host_tier = HostBlockTier(host_tier_mb * 1024 * 1024)
        print(f"[serve] host KV tier: {host_tier_mb} MiB")
    if engine_kind in ("continuous", "paged"):
        from llm_fine_tune_distributed_tpu.infer.engine import (
            ContinuousBatchingEngine,
            PagedContinuousBatchingEngine,
        )

        if adapter_dir:
            from llm_fine_tune_distributed_tpu.infer.adapters import (
                AdapterRegistry,
            )

        def _make_replica(i: int, role: Optional[str] = None):
            # every replica wraps the SAME generator — params resident
            # once, jitted programs shared — but owns its own KV pool,
            # supervisor, and stats. Crash artifacts get per-replica
            # paths so two replicas' dumps cannot clobber each other.
            # ``role`` comes from the autoscaler growing a specific pool;
            # otherwise the --replica-roles list assigns by index and
            # replicas grown past the list default to mixed.
            kw = dict(engine_kwargs)
            kw["role"] = role or (
                role_list[i] if i < len(role_list) else "mixed"
            )
            from llm_fine_tune_distributed_tpu.observe.slo import (
                SloPolicy,
            )

            kw["slo_policy"] = SloPolicy(
                ttft_p99_s=slo_ttft_p99_s,
                inter_token_p99_s=slo_inter_token_p99_s,
                error_rate=slo_error_rate,
                availability=slo_availability,
                fast_window_s=slo_fast_window_s,
                slow_window_s=slo_slow_window_s,
            )
            if slot_bridge is not None:
                # process-spanning mesh: every dispatch announces over
                # the bridge before entering the collective program
                kw["bridge"] = slot_bridge
            if adapter_dir:
                # per-replica registry: pool residency is a replica-
                # local property (the fleet routes tenants to the
                # replica already holding their adapter), and pool
                # leaves are value-updated in place — sharing one
                # across replicas would let replica A's eviction yank
                # a slot replica B is decoding with
                kw["adapters"] = AdapterRegistry(
                    generator.params,
                    adapter_dir,
                    max_adapters=max_adapters,
                    mesh=mesh,
                )
                kw["adapter_quota"] = adapter_capacity
            if replicas > 1 or max_replicas > replicas:
                if kw.get("flight_dir"):
                    kw["flight_dir"] = os.path.join(
                        kw["flight_dir"], f"replica{i}"
                    )
                if kw.get("trace_log"):
                    kw["trace_log"] = f"{kw['trace_log']}.replica{i}"
            if engine_kind == "paged":
                return PagedContinuousBatchingEngine(
                    generator, slots=slots, buf_len=kv_buf_len,
                    block_len=kv_block_len, prefill_chunk=prefill_chunk,
                    kv_quant=quantize_kv, host_tier=host_tier,
                    **kw,
                )
            return ContinuousBatchingEngine(
                generator, slots=slots, buf_len=kv_buf_len, **kw
            )

        if replicas > 1 or max_replicas > replicas:
            # a growable fleet even from --replicas 1: elastic growth
            # needs the router/fleet shape from the start, so
            # --max-replicas above --replicas forces it
            cont_engine = EngineFleet(
                [_make_replica(i) for i in range(replicas)],
                routing=routing,
                replica_factory=_make_replica,
                migrate_on_retire=migrate_on_retire,
            )
        else:
            cont_engine = _make_replica(0)
        cont_kind = engine_kind
    # elastic fleet control loop (observe/capacity.py): dry-run (default)
    # records would-be decisions without acting — read GET /v1/capacity,
    # then restart with --autoscale on once the recommendations look sane
    autoscaler = None
    if isinstance(cont_engine, EngineFleet):
        autoscaler = Autoscaler(
            cont_engine,
            mode=autoscale,
            min_replicas=min_replicas,
            max_replicas=max_replicas or replicas,
            cooldown_s=scale_cooldown_s,
            retire_timeout_s=drain_timeout_s,
            ratio=autoscale_ratio,
        )
        if autoscale != "off":
            autoscaler.start()
            print(
                f"[serve] autoscaler ({autoscale}): replicas in "
                f"[{min_replicas}, {max_replicas or replicas}], "
                f"cooldown {scale_cooldown_s:g}s"
                + (", prefill/decode ratio dimension on"
                   if autoscale_ratio else "")
            )
    if role_list:
        print(f"[serve] replica roles: {','.join(role_list)}")
    # on-demand profiler capture (POST /v1/profile): one per server process
    # (jax.profiler traces are process-wide). Captures go on the engine's
    # flight-recorder timeline so they line up with crashes and restarts.
    profiler_capture = None
    if profile_dir:
        if isinstance(cont_engine, EngineFleet):
            capture_recorder = cont_engine.replicas[0].recorder
        elif cont_engine is not None:
            capture_recorder = cont_engine.recorder
        else:
            capture_recorder = None
        profiler_capture = ProfilerCapture(
            profile_dir,
            on_event=capture_recorder.record if capture_recorder else None,
        )
    # live deployment (infer/deploy.py): watch a trainer's publish dir and
    # hot-swap new checkpoints in at tick boundaries, POST /v1/deploy[/rollback]
    deploy_mgr = None
    if publish_watch_dir:
        if cont_engine is None:
            raise ValueError(
                "--publish-watch-dir needs a continuous/paged engine on "
                "this host (multi-host serving falls back to the window "
                "engine, which cannot hot-swap)"
            )
        from llm_fine_tune_distributed_tpu.infer.deploy import (
            CheckpointWatcher,
            HotSwapManager,
        )

        canary_judge = None
        if canary_window_s > 0:
            from llm_fine_tune_distributed_tpu.observe.slo import CanaryJudge

            canary_judge = CanaryJudge(
                window_s=canary_window_s,
                min_requests=canary_min_requests,
            )
        deploy_mgr = HotSwapManager(
            cont_engine,
            CheckpointWatcher(publish_watch_dir, base_params=generator.params),
            poll_s=publish_poll_s,
            auto_rollback_window_s=auto_rollback_window_s,
            auto_rollback_error_rate=auto_rollback_error_rate,
            canary=canary_judge,
        )
        deploy_mgr.start()
        print(
            f"[serve] watching {publish_watch_dir} for published "
            f"checkpoints (poll every {publish_poll_s:g}s"
            + (
                f", auto-rollback at {auto_rollback_error_rate:.0%} errors "
                f"over {auto_rollback_window_s:g}s"
                if auto_rollback_window_s > 0
                else ""
            )
            + ")"
        )
    drain_state = {"draining": False}

    def parse_overload_fields(req: dict):
        """Shared /v1/generate + /v1/stream parsing for the overload-control
        request fields: ``priority`` (tier name) and ``deadline_ms`` (client
        budget for the WHOLE request — queue wait, prefill, and decode; on
        expiry the engine cancels it wherever it is and the 504 carries the
        tokens generated so far). Both need a slot engine: the window
        engine's batcher has no scheduler tick to enforce either."""
        priority = req.get("priority") or None
        if priority is not None:
            if priority not in PRIORITY_TIERS:
                raise ValueError(
                    f"'priority' must be one of {PRIORITY_TIERS}, "
                    f"got {priority!r}"
                )
            if cont_engine is None:
                raise ValueError(
                    "'priority' needs a continuous/paged engine; this "
                    "server runs the window engine, which admits FIFO"
                )
        deadline_s = None
        if req.get("deadline_ms") is not None:
            deadline_s = float(req["deadline_ms"]) / 1000.0
            if not deadline_s > 0:
                raise ValueError(
                    f"'deadline_ms' must be a positive number of "
                    f"milliseconds, got {req['deadline_ms']!r}"
                )
            if cont_engine is None:
                raise ValueError(
                    "'deadline_ms' needs a continuous/paged engine; this "
                    "server runs the window engine, which cannot cancel "
                    "mid-decode"
                )
        return priority, deadline_s

    print(
        f"Model ready (engine={cont_kind}, "
        + (f"replicas={replicas}, routing={routing}, " if replicas > 1 else "")
        + (
            f"adapter_dir={adapter_dir}, max_adapters={max_adapters}, "
            if adapter_dir and cont_engine is not None
            else ""
        )
        + f"slots={slots}, max_batch={max_batch}, quantize={quantize})."
    )

    class Handler(BaseHTTPRequestHandler):
        # HTTP/1.1 so /v1/stream may use chunked transfer encoding (every
        # non-stream response carries an explicit Content-Length)
        protocol_version = "HTTP/1.1"

        def _send(
            self,
            code: int,
            payload: dict | str,
            headers: Optional[dict] = None,
            content_type: Optional[str] = None,
        ) -> None:
            body = (
                payload if isinstance(payload, str) else json.dumps(payload)
            ).encode()
            self.send_response(code)
            self.send_header(
                "Content-Type",
                content_type
                or ("text/plain" if isinstance(payload, str) else "application/json"),
            )
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, str(v))
            self.end_headers()
            self.wfile.write(body)

        def _send_error(self, exc: BaseException) -> None:
            """Map any serving failure through the taxonomy (infer/errors.py)
            to status + structured JSON body + Retry-After when known."""
            status, payload, retry_after = error_payload(exc)
            headers = {}
            if retry_after is not None:
                # ceil to a whole second: Retry-After must be a positive int
                headers["Retry-After"] = max(1, int(-(-retry_after // 1)))
            self._send(status, payload, headers=headers)

        def do_GET(self):  # noqa: N802 (stdlib casing)
            # /v1/history takes a query string; every other route matches
            # on the bare path
            path, _, query = self.path.partition("?")
            if path == "/healthz":
                # a multi-host fleet whose followers died on a mirrored
                # decode failure cannot serve again — report unhealthy so
                # the orchestrator restarts every host (multihost.py)
                if coordinator is not None and coordinator.wedged:
                    self._send(503, {"error": "follower hosts wedged; restart fleet"})
                elif drain_state["draining"]:
                    # SIGTERM received: the orchestrator should stop routing
                    # here while in-flight requests finish
                    self._send(
                        503,
                        {"status": "draining"},
                        headers={"Retry-After": max(1, int(drain_timeout_s))},
                    )
                elif cont_engine is not None and not cont_engine.healthy:
                    # circuit open or fatal worker death: in-process recovery
                    # is over, ask for a pod recycle
                    self._send(503, {
                        "status": "unhealthy",
                        "circuit_state": cont_engine.circuit_state,
                        "error": cont_engine.terminal_error.to_dict(),
                    })
                else:
                    self._send(200, "ok")
            elif path == "/v1/stats":
                # serving-side observability: queue depth, live slots, slot
                # occupancy, cumulative tokens — the continuous engine's
                # counters (observe/metrics.ServingStats). Window mode
                # reports the little it tracks (its queue).
                if cont_engine is not None:
                    stats = {"engine": cont_kind, **cont_engine.stats_snapshot()}
                else:
                    stats = {
                        "engine": "window",
                        "queue_depth": engine._q.qsize(),
                        "max_batch": max_batch,
                    }
                stats["device_memory"] = device_memory_report()
                if cont_engine is not None and hasattr(
                    cont_engine, "memory_breakdown"
                ):
                    stats["device_memory_report"] = (
                        cont_engine.memory_breakdown()
                    )
                self._send(200, stats)
            elif path == "/metrics":
                # Prometheus text exposition: every ServingStats counter/
                # gauge/histogram plus per-device HBM gauges, scrape-ready.
                # A fleet emits the aggregate series (unlabelled) followed
                # by the same metrics labelled replica="i", all under one
                # TYPE per name.
                replica_series = None
                if isinstance(cont_engine, EngineFleet):
                    snap = {"engine": cont_kind, **cont_engine.stats_snapshot()}
                    per = snap.pop("per_replica")
                    # per_replica labels are STABLE ids, not positions: a
                    # scaled fleet's ids are sparse, and a replica retired
                    # between the snapshot and here simply drops its series
                    by_id = dict(cont_engine.replica_items())
                    replica_series = [
                        (
                            label,
                            per[label],
                            by_id[int(label)].stats.hist,
                        )
                        for label in sorted(per, key=int)
                        if int(label) in by_id
                    ]
                    hists = cont_engine.merged_histograms()
                    tenant_hists = cont_engine.merged_tenant_histograms()
                elif cont_engine is not None:
                    snap = {"engine": cont_kind, **cont_engine.stats_snapshot()}
                    hists = cont_engine.stats.hist
                    tenant_hists = cont_engine.stats.tenant_histograms()
                else:
                    snap = {
                        "engine": "window",
                        "queue_depth": engine._q.qsize(),
                        "max_batch": max_batch,
                    }
                    hists = None
                    tenant_hists = None
                text = prometheus_exposition(
                    snap, hists, memory=device_memory_report(),
                    replicas=replica_series,
                    tenant_histograms=tenant_hists,
                )
                self._send(200, text, content_type=PROMETHEUS_CONTENT_TYPE)
            elif path == "/v1/slo":
                # burn-rate report per objective/window (observe/slo.py):
                # a fleet answers with the merged view + per_replica
                if cont_engine is None:
                    self._send(404, {
                        "error": "SLO evaluation needs a continuous/paged "
                        "engine (the window engine has no metric ring)"
                    })
                    return
                self._send(200, {
                    "engine": cont_kind, **cont_engine.slo_report(),
                })
            elif path == "/v1/history":
                # trailing time series of one sampled counter/gauge from
                # the in-process metric ring: ?metric=<name>[&window=<s>]
                if cont_engine is None:
                    self._send(404, {
                        "error": "metric history needs a continuous/paged "
                        "engine (the window engine has no metric ring)"
                    })
                    return
                qs = parse_qs(query)
                metric = (qs.get("metric") or [None])[0]
                if not metric:
                    self._send(400, {
                        "error": "missing ?metric=<name> "
                        "(GET /v1/history?metric=queue_depth&window=60)"
                    })
                    return
                window_s = None
                try:
                    if qs.get("window"):
                        window_s = float(qs["window"][0])
                        if not window_s > 0:
                            raise ValueError
                except ValueError:
                    self._send(400, {
                        "error": f"'window' must be a positive number of "
                        f"seconds, got {qs['window'][0]!r}"
                    })
                    return
                try:
                    self._send(200, cont_engine.history(metric, window_s))
                except ValueError as e:
                    self._send(400, {"error": str(e)})
            elif path == "/v1/flight":
                # the flight recorder, live: the same bounded event ring
                # the supervisor dumps post-crash, readable before one
                if cont_engine is None:
                    self._send(404, {
                        "error": "flight events need a continuous/paged "
                        "engine (the window engine has no flight recorder)"
                    })
                    return
                qs = parse_qs(query)
                try:
                    limit = int((qs.get("limit") or [256])[0])
                    if limit <= 0:
                        raise ValueError
                except ValueError:
                    self._send(400, {
                        "error": f"'limit' must be a positive integer, "
                        f"got {qs['limit'][0]!r}"
                    })
                    return
                if isinstance(cont_engine, EngineFleet):
                    # "fleet" carries the fleet's own lifecycle events
                    # (scale_up / scale_down / scale_decision); per-replica
                    # rings are keyed by stable id, not position
                    self._send(200, {
                        "fleet": cont_engine.recorder.events()[-limit:],
                        "replicas": {
                            str(rid): rep.recorder.events()[-limit:]
                            for rid, rep in cont_engine.replica_items()
                        },
                    })
                else:
                    self._send(
                        200,
                        {"events": cont_engine.recorder.events()[-limit:]},
                    )
            elif path == "/v1/capacity":
                # capacity observatory (observe/capacity.py): current and
                # forecast load, sustainable per-replica throughput,
                # headroom, the hysteresis-banded replica recommendation,
                # and the autoscaler's bounded decision history
                if cont_engine is None:
                    self._send(404, {
                        "error": "capacity reporting needs a continuous/"
                        "paged engine (the window engine has no load "
                        "forecaster)"
                    })
                    return
                if isinstance(cont_engine, EngineFleet):
                    report = cont_engine.capacity_report(
                        horizon_s=(
                            autoscaler.horizon_s if autoscaler else 60.0
                        ),
                        min_replicas=min_replicas,
                        max_replicas=(
                            autoscaler.max_replicas if autoscaler
                            else replicas
                        ),
                    )
                else:
                    # single engine: same report shape, a fleet of one
                    report = report_from_capacity_snapshots(
                        [cont_engine.capacity_snapshot()], 1
                    )
                report["engine"] = cont_kind
                report["autoscale"] = (
                    autoscaler.mode if autoscaler else "off"
                )
                report["decisions"] = (
                    autoscaler.decisions() if autoscaler else []
                )
                self._send(200, report)
            elif path == "/v1/lineage":
                # train→serve lineage: which training run/step produced
                # each resident weight generation, was its anomaly window
                # clean, and how has each generation served (per-generation
                # SLO slices joined in) — a canary rejection is one record
                if deploy_mgr is None:
                    self._send(404, {
                        "error": "lineage needs live deployment: start the "
                        "server with --publish-watch-dir"
                    })
                    return
                payload = deploy_mgr.lineage()
                slices = None
                if cont_engine is not None:
                    if isinstance(cont_engine, EngineFleet):
                        slices = cont_engine.stats_snapshot().get(
                            "per_generation"
                        )
                    else:
                        slo = getattr(cont_engine, "slo_slices", None)
                        if slo is not None:
                            slices = slo.summaries()
                if slices:
                    payload["serving"] = slices
                    for gen, rec in payload["generations"].items():
                        if gen in slices:
                            rec["slo"] = slices[gen]
                self._send(200, payload)
            else:
                self._send(404, {"error": "not found"})

        def _stream(self, req: dict) -> None:
            """POST /v1/stream: Server-Sent Events, one ``data:`` event per
            decoded text delta. Cuts time-to-first-token from O(max_new)
            decode steps to O(chunk): the reference's own default
            (``max_new_tokens=3768``) otherwise leaves a client staring at
            nothing for the whole generation.

            With the continuous engine, the stream RIDES the shared slot
            batch (engine.stream): tokens surface as the slot decodes them,
            concurrently with every other in-flight request. Window mode
            streams on the handler thread against the Generator directly —
            concurrent dispatches serialize in the device queue. Multi-host
            serving does not stream (the per-chunk host round-trip would
            need a broadcast each chunk); clients get a 501 there."""
            # everything fallible happens BEFORE headers go out, so clients
            # get a 400 instead of a hung keep-alive connection
            try:
                spec = int(req.get("speculative", 0))
                if spec and cont_engine is None:
                    # window engine (explicit or multi-host fallback):
                    # streaming has no speculative path there — name what
                    # IS supported. speculative=0 (the documented off
                    # value) passes through.
                    raise ValueError(
                        "'speculative' on /v1/stream needs a continuous/"
                        "paged engine started with --speculative K; with "
                        "--engine window the supported alternatives are: "
                        "POST /v1/generate with 'speculative': K "
                        "(non-streaming speculative decode), or /v1/stream "
                        "without 'speculative' (plain streaming)"
                    )
                if spec and not speculative_k:
                    # continuous/paged engine compiled WITHOUT the fused
                    # draft+verify step: speculation cannot ride the slot
                    # batch. Restart with the flag, or use the supported
                    # shapes on this server.
                    raise ValueError(
                        "'speculative' on /v1/stream needs the server "
                        "started with --speculative K (engine-level fused "
                        "verify); supported now: POST /v1/generate with "
                        "'speculative': K (non-streaming speculative "
                        "decode), or /v1/stream without 'speculative' "
                        "(plain streaming)"
                    )
                adapter = req.get("adapter") or None
                if adapter is not None and not isinstance(adapter, str):
                    raise ValueError(
                        "'adapter' must be a string adapter name"
                    )
                if adapter and cont_engine is None:
                    # window engine (explicit or multi-host fallback) has
                    # no adapter pool: per-request deltas ride the slot
                    # batch only
                    raise ValueError(
                        "'adapter' needs a continuous/paged engine started "
                        "with --adapter-dir; this server runs the window "
                        "engine — supported: requests without 'adapter' "
                        "(base model), or a server started with "
                        "--engine continuous|paged --adapter-dir DIR"
                    )
                priority, deadline_s = parse_overload_fields(req)
                gen_kwargs = {
                    k: cast(req[k])
                    for k, cast in self._FIELD_CASTS.items()
                    if k in req
                }
                if spec:
                    # the stream rides the speculative slot batch: the
                    # engine drafts min(K, --speculative) per tick and
                    # accepted runs surface as ordinary streamed tokens
                    gen_kwargs["speculative_lookup"] = spec
                if "greedy" in req:
                    gen_kwargs["do_sample"] = not req["greedy"]
                gen = GenerationConfig(**gen_kwargs)
                stream_chunk = int(req.get("stream_chunk", 8))
                if stream_chunk < 1:
                    raise ValueError(f"stream_chunk must be >= 1, got {stream_chunk}")
                seed = int(req.get("seed", 0))
                messages = [
                    {
                        "role": "system",
                        "content": req.get("system_prompt", WILDERNESS_EXPERT_SYSTEM_PROMPT),
                    },
                    {"role": "user", "content": req["question"]},
                ]
                prompt_ids = generator.encode_chat(messages, **(template_kwargs or {}))
            except (ValueError, KeyError, TypeError) as e:
                self._send(400, {"error": f"bad request: {e}"})
                return
            if coordinator is not None:
                self._send(501, {"error": "streaming unavailable in multi-host serving"})
                return
            token_iter = None
            if cont_engine is not None:
                # admission (overflow / drain / circuit) happens at stream()
                # call time, BEFORE headers, so shed requests get a real
                # status code + Retry-After instead of an empty SSE body
                try:
                    token_iter = cont_engine.stream(
                        prompt_ids,
                        gen,
                        seed=seed,
                        timeout=request_timeout_s,
                        adapter=adapter,
                        priority=priority,
                        deadline_s=deadline_s,
                    )
                except (ServingError, TimeoutError) as e:
                    self._send_error(e)
                    return
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()

            def chunk_out(data: bytes) -> None:
                self.wfile.write(f"{len(data):X}\r\n".encode() + data + b"\r\n")

            if token_iter is not None:
                # ride the shared slot batch: one token per piece, emitted
                # as the engine's scheduler loop decodes it
                source = ([t] for t in token_iter)
            else:
                source = generator.generate_stream(
                    prompt_ids, gen, seed=seed, chunk=stream_chunk
                )
            ids_all, prev_text = [], ""
            try:
                for piece in source:
                    ids_all.extend(piece)
                    text = generator.tokenizer.decode(
                        ids_all, skip_special_tokens=True
                    )
                    delta = text[len(prev_text):]
                    prev_text = text
                    if delta:
                        chunk_out(
                            f"data: {json.dumps({'delta': delta})}\n\n".encode()
                        )
                done = {
                    "done": True,
                    "n_tokens": len(ids_all),
                    "token_ids": [int(t) for t in ids_all],
                }
                chunk_out(f"data: {json.dumps(done)}\n\n".encode())
            except Exception as e:
                # the request died mid-stream (decode failure, shed, device
                # error): emit a terminal error event with the structured
                # body instead of silently truncating the stream
                _, payload, _ = error_payload(e)
                chunk_out(
                    f"event: error\ndata: {json.dumps(payload['error'])}\n\n".encode()
                )
            finally:
                self.wfile.write(b"0\r\n\r\n")

        _FIELD_CASTS = {
            "max_new_tokens": int,
            "temperature": float,
            "top_p": float,
            "top_k": int,
            "repetition_penalty": float,
        }

        def do_POST(self):  # noqa: N802
            if drain_state["draining"] and self.path in (
                "/v1/generate", "/v1/stream", "/v1/profile"
            ):
                # admission is closed server-wide during drain; in-flight
                # work keeps running until done or --drain-timeout-s
                self._send_error(DrainingError(
                    "server draining; retry against another replica",
                    retry_after_s=float(drain_timeout_s),
                ))
                return
            if self.path == "/v1/profile":
                # on-demand jax.profiler capture: starts a bounded trace to
                # a fresh subdirectory of --profile-dir and auto-stops.
                # 409 while a capture is already running (one at a time).
                if profiler_capture is None:
                    self._send(404, {
                        "error": "profiling disabled; start the server "
                                 "with --profile-dir",
                    })
                    return
                try:
                    length = int(self.headers.get("Content-Length", "0"))
                    req = json.loads(self.rfile.read(length) or b"{}")
                    if not isinstance(req, dict):
                        raise TypeError("body must be a JSON object")
                    duration_s = float(req.get("duration_s", 3.0))
                    trace_dir = profiler_capture.start(duration_s)
                except CaptureBusyError as e:
                    self._send(409, {"error": str(e)})
                    return
                except (ValueError, TypeError) as e:
                    self._send(400, {"error": f"bad request: {e}"})
                    return
                self._send(200, {
                    "profiling": True,
                    "trace_dir": trace_dir,
                    "duration_s": duration_s,
                })
                return
            if self.path == "/v1/stream":
                try:
                    length = int(self.headers.get("Content-Length", "0"))
                    req = json.loads(self.rfile.read(length) or b"{}")
                    if not isinstance(req, dict) or "question" not in req:
                        raise TypeError("body must be a JSON object with 'question'")
                except (ValueError, KeyError, TypeError) as e:
                    self._send(400, {"error": f"bad request: {e}"})
                    return
                try:
                    self._stream(req)
                except Exception as e:  # headers may already be sent: log only
                    print(f"[serve] stream error: {e}", flush=True)
                return
            if self.path in ("/v1/deploy", "/v1/deploy/rollback"):
                # live deployment (infer/deploy.py). Deliberately NOT behind
                # the drain guard: a draining replica may still be rolled
                # back while its in-flight work finishes.
                if deploy_mgr is None:
                    self._send(404, {
                        "error": "live deployment disabled; start the "
                                 "server with --publish-watch-dir",
                    })
                    return
                try:
                    if self.path.endswith("/rollback"):
                        result = deploy_mgr.rollback()
                    else:
                        result = deploy_mgr.poll_once() or {
                            "kind": "noop",
                            "detail": "no publish newer than the deployed "
                                      "generation",
                            **deploy_mgr.status(),
                        }
                except RuntimeError as e:
                    self._send(409, {"error": str(e)})
                    return
                except Exception as e:  # noqa: BLE001 — swap failures map
                    # through the taxonomy (engine kept the old generation)
                    self._send_error(e)
                    return
                self._send(200, result)
                return
            if self.path == "/v1/fleet/scale":
                # manual override: step the fleet to an absolute replica
                # count (the autoscaler keeps adjusting afterwards unless
                # started with --autoscale dry-run/off). Deliberately NOT
                # behind the drain guard: an operator may shed replicas
                # while in-flight work finishes.
                if not isinstance(cont_engine, EngineFleet):
                    self._send(404, {
                        "error": "fleet scaling needs --replicas > 1 or "
                                 "--max-replicas above --replicas",
                    })
                    return
                try:
                    length = int(self.headers.get("Content-Length", "0"))
                    req = json.loads(self.rfile.read(length) or b"{}")
                    target = int(req["replicas"])
                except (ValueError, KeyError, TypeError) as e:
                    self._send(400, {
                        "error": "bad request: body must be a JSON object "
                                 f"with an integer 'replicas' ({e})",
                    })
                    return
                lo = min_replicas
                hi = (
                    autoscaler.max_replicas if autoscaler
                    else max(replicas, max_replicas)
                )
                if not lo <= target <= hi:
                    self._send(400, {
                        "error": f"'replicas' must be within [{lo}, {hi}]"
                                 f", got {target}",
                    })
                    return
                try:
                    while len(cont_engine.replicas) < target:
                        cont_engine.add_replica()
                    while len(cont_engine.replicas) > target:
                        cont_engine.retire_replica(
                            timeout_s=drain_timeout_s
                        )
                except (RuntimeError, ValueError) as e:
                    self._send(409, {"error": str(e)})
                    return
                self._send(200, {"replicas": len(cont_engine.replicas)})
                return
            if self.path != "/v1/generate":
                self._send(404, {"error": "not found"})
                return
            # Optional fields cast and forwarded only when present, so
            # GenerationConfig stays the single source of sampling defaults.
            field_casts = self._FIELD_CASTS
            # "speculative": K maps to GenerationConfig.speculative_lookup
            # (prompt-lookup decoding, infer/generate.py — greedy exact-match
            # or sampled rejection-sampling verification)
            try:
                length = int(self.headers.get("Content-Length", "0"))
                req = json.loads(self.rfile.read(length) or b"{}")
                if not isinstance(req, dict):
                    raise TypeError("body must be a JSON object")
                question = req["question"]
                gen_kwargs = {
                    k: cast(req[k]) for k, cast in field_casts.items() if k in req
                }
                if "greedy" in req:
                    gen_kwargs["do_sample"] = not req["greedy"]
                if "speculative" in req:
                    gen_kwargs["speculative_lookup"] = int(req["speculative"])
                seed = int(req.get("seed", 0))
                want_trace = bool(req.get("trace", False))
                adapter = req.get("adapter") or None
                if adapter is not None and not isinstance(adapter, str):
                    raise ValueError("'adapter' must be a string adapter name")
                if adapter and cont_engine is None:
                    raise ValueError(
                        "'adapter' needs a continuous/paged engine started "
                        "with --adapter-dir; this server runs the window "
                        "engine — supported: requests without 'adapter' "
                        "(base model), or a server started with "
                        "--engine continuous|paged --adapter-dir DIR"
                    )
                if (
                    adapter
                    and gen_kwargs.get("speculative_lookup", 0) > 0
                    and not speculative_k
                ):
                    # a speculative request on a K=0 slot engine falls back
                    # to the window engine's solo program, which has no
                    # adapter pool — refuse the combination up front
                    raise ValueError(
                        "'adapter' with 'speculative' needs the server "
                        "started with --speculative K (on a K=0 engine "
                        "speculative requests fall back to the window "
                        "engine, which has no adapter pool); drop "
                        "'speculative' or restart with --speculative K"
                    )
                priority, deadline_s = parse_overload_fields(req)
                if (
                    (priority is not None or deadline_s is not None)
                    and gen_kwargs.get("speculative_lookup", 0) > 0
                    and not speculative_k
                ):
                    # same fallback trap as 'adapter': a speculative request
                    # on a K=0 slot engine rides the window engine, which
                    # has no admission scheduler to honor either field
                    raise ValueError(
                        "'priority'/'deadline_ms' with 'speculative' needs "
                        "the server started with --speculative K (on a K=0 "
                        "engine speculative requests fall back to the "
                        "window engine); drop 'speculative' or restart "
                        "with --speculative K"
                    )
            except (ValueError, KeyError, TypeError) as e:
                self._send(400, {"error": f"bad request: {e}"})
                return
            gen = GenerationConfig(**gen_kwargs)
            messages = [
                {
                    "role": "system",
                    "content": req.get("system_prompt", WILDERNESS_EXPERT_SYSTEM_PROMPT),
                },
                {"role": "user", "content": question},
            ]
            if (
                slot_bridge is not None
                and gen.speculative_lookup > 0
                and speculative_k == 0
            ):
                # the window engine's solo fallback program is not part of
                # the slot bridge's tick protocol, so followers would never
                # mirror it (fleet deadlock)
                self._send(400, {"error": (
                    "'speculative' on a multi-host --tp slot engine needs "
                    "the server started with --speculative K (the window "
                    "fallback is single-host only); retry without "
                    "'speculative' or restart with "
                    "--engine continuous|paged --tp N --speculative K"
                )})
                return
            try:
                # tokenize/decode on the handler thread (Generator's shared
                # chat helpers, so CLI and server cannot diverge); only the
                # device work goes through the batching engine's worker
                prompt_ids = generator.encode_chat(messages, **(template_kwargs or {}))
                # speculative requests ride the slot batch when the engine
                # was started with --speculative K (per-slot drafting +
                # fused verify); on a K=0 engine they fall back to the
                # window engine's solo fused draft+verify program
                if cont_engine is not None and (
                    gen.speculative_lookup == 0 or speculative_k > 0
                ):
                    pending = cont_engine.submit_full(
                        prompt_ids,
                        gen,
                        seed=seed,
                        timeout=request_timeout_s,
                        adapter=adapter,
                        priority=priority,
                        deadline_s=deadline_s,
                    )
                else:
                    pending = engine.submit_full(
                        prompt_ids, gen, seed=seed, timeout=request_timeout_s
                    )
                answer = generator.decode_reply(pending.result)
            except ServingError as e:
                # taxonomy failures (overflow 429, restart/drain/deadline
                # 503, circuit/fatal 500): structured body + Retry-After
                self._send_error(e)
                return
            except TimeoutError as e:  # wedged device: shed load, don't pile up
                self._send(503, {"error": str(e)})
                return
            except Exception as e:  # surface generation errors as 500s
                self._send(500, {"error": str(e)})
                return
            resp = {
                "answer": answer,
                "token_ids": [int(t) for t in pending.result],
            }
            if gen.speculative_lookup > 0 and pending.spec_acceptance is not None:
                # draft-acceptance telemetry so clients can see whether the
                # speculation they asked for is actually paying off — THIS
                # request's own counts, not its batch's
                resp["speculative"] = {
                    "acceptance_rate": round(pending.spec_acceptance, 3),
                    "draft_tokens_proposed": pending.draft_tokens_proposed,
                    "draft_tokens_accepted": pending.draft_tokens_accepted,
                }
                if pending.spec_steps is not None:
                    # window engine only: its whole-batch sequential-forward
                    # count (a slot engine has no per-request equivalent)
                    resp["speculative"]["sequential_forwards"] = pending.spec_steps
            if want_trace and pending.trace is not None:
                # per-request lifecycle timeline (continuous/paged engines;
                # the window engine does not trace) — span names and
                # request-relative times, the client-visible view of the
                # engine's RequestTrace
                resp["trace"] = pending.trace.to_dict()
            self._send(200, resp)

        def log_message(self, fmt, *args):
            print(f"[serve] {self.address_string()} {fmt % args}", flush=True)

    httpd = ThreadingHTTPServer((host, port), Handler)

    def begin_drain(signum=None, frame=None):
        """SIGTERM entry point (k8s drain / spot preemption): close
        admission, let in-flight work finish up to ``drain_timeout_s``,
        then stop the server loop so ``serve`` returns and the process
        exits 0 — a clean goodbye instead of killed mid-stream."""
        if drain_state["draining"]:
            return
        drain_state["draining"] = True
        print(
            f"[serve] drain: admission closed, finishing in-flight work "
            f"(timeout {drain_timeout_s}s)",
            flush=True,
        )
        for eng in (cont_engine, engine):
            if eng is not None:
                eng.begin_drain()

        def _finish():
            deadline = time.monotonic() + float(drain_timeout_s)
            clean = True
            for eng in (cont_engine, engine):
                if eng is not None:
                    clean = eng.wait_drained(
                        max(0.0, deadline - time.monotonic())
                    ) and clean
            print(
                "[serve] drain complete; shutting down"
                if clean
                else "[serve] drain timeout: shutting down with "
                     "requests unresolved",
                flush=True,
            )
            httpd.shutdown()

        threading.Thread(target=_finish, name="drain", daemon=True).start()

    try:
        signal.signal(signal.SIGTERM, begin_drain)
    except ValueError:
        pass  # not the main thread: tests drive begin_drain via `control`
    if control is not None:
        control["begin_drain"] = begin_drain
        control["httpd"] = httpd
        control["cont_engine"] = cont_engine
        control["window_engine"] = engine
        control["profiler"] = profiler_capture
        control["deploy"] = deploy_mgr
        control["autoscaler"] = autoscaler

    print(f"Serving on {host}:{port}")
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        httpd.server_close()
        if autoscaler is not None:
            autoscaler.stop()
        if deploy_mgr is not None:
            deploy_mgr.stop()
        if coordinator is not None:
            coordinator.stop()  # release follower hosts
        if slot_bridge is not None:
            slot_bridge.stop()  # release slot-engine follower hosts
        if drain_state["draining"]:
            print("[serve] drained; exiting", flush=True)


def main(argv: Optional[list] = None) -> int:
    from llm_fine_tune_distributed_tpu.runtime.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    parser = argparse.ArgumentParser(description="Serve the tuned model over HTTP")
    parser.add_argument(
        "--model-dir", default=os.environ.get("MODEL_DIR", "outputs/best_model")
    )
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8080)
    parser.add_argument(
        "--engine", choices=["continuous", "paged", "window"],
        default="continuous",
        help="continuous: slot-based persistent decode loop (mixed traffic "
             "co-batches, mid-flight admission); paged: continuous plus "
             "block-paged KV with shared-prefix reuse and chunked prefill; "
             "window: drain-a-window batching (multi-host falls back to "
             "this automatically)",
    )
    parser.add_argument(
        "--replicas", type=int, default=1, metavar="N",
        help="continuous/paged engines: run N supervised engine replicas "
             "behind the in-process fleet router (params shared read-only; "
             "each replica owns its KV pool, supervisor, and stats). "
             "1 = single engine, no router",
    )
    parser.add_argument(
        "--routing", choices=["prefix", "least-loaded", "round-robin"],
        default="prefix",
        help="fleet placement policy (--replicas > 1): prefix = prompt-"
             "prefix cache affinity, ties least-loaded; least-loaded = "
             "smallest backlog per slot; round-robin = strict rotation",
    )
    parser.add_argument(
        "--replica-roles", default=None, metavar="R1,R2,...",
        help="disaggregated serving: comma list assigning each starting "
             "replica a pool role (mixed|prefill|decode), e.g. "
             "'prefill,decode'. New requests route to prefill-capable "
             "replicas; after the prompt is ingested the request hands "
             "over to a decode replica through the shared host KV tier "
             "(greedy output bit-identical; any handoff failure degrades "
             "to decoding in place). Needs --engine paged, "
             "--host-tier-mb > 0, and a fleet",
    )
    parser.add_argument(
        "--autoscale", choices=["dry-run", "on", "off"], default="dry-run",
        help="elastic fleet control loop (observe/capacity.py): dry-run "
             "(default) records every would-be scale decision on "
             "GET /v1/capacity and the flight recorder WITHOUT acting; "
             "on additionally adds/retires replicas within "
             "--min-replicas/--max-replicas; off disables the loop",
    )
    parser.add_argument(
        "--min-replicas", type=int, default=1, metavar="N",
        help="autoscaler floor: never retire below N replicas",
    )
    parser.add_argument(
        "--max-replicas", type=int, default=0, metavar="N",
        help="autoscaler ceiling: never grow past N replicas. 0 = "
             "--replicas (no elastic growth); a value above --replicas "
             "builds a growable fleet even from --replicas 1",
    )
    parser.add_argument(
        "--scale-cooldown-s", type=float, default=30.0,
        help="autoscaler: seconds between APPLIED scale actions, so a "
             "burst cannot ladder the fleet up faster than replicas warm",
    )
    parser.add_argument(
        "--autoscale-ratio", action="store_true",
        help="autoscaler (--replica-roles): treat the prefill/decode pool "
             "ratio as a scaling dimension — count changes grow/retire the "
             "most/least saturated role, and a starved role inside the "
             "count band grows (or trades a surplus dedicated replica) "
             "toward the demand split",
    )
    parser.add_argument(
        "--slots", type=int, default=8,
        help="continuous engine: persistent decode slots (the max live batch)",
    )
    parser.add_argument(
        "--kv-buf-len", type=int, default=4096,
        help="continuous engine: per-slot KV buffer length "
             "(prompt + generated tokens must fit)",
    )
    parser.add_argument(
        "--kv-block-len", type=int, default=256,
        help="paged engine: tokens per KV block (prefix sharing granularity)",
    )
    parser.add_argument(
        "--prefill-chunk", type=int, default=512,
        help="paged engine: max prompt tokens prefilled per scheduler tick "
             "(longer prompts interleave with decode)",
    )
    parser.add_argument(
        "--host-tier-mb", type=int, default=0, metavar="MB",
        help="paged engine: host-RAM KV tier budget in MiB (LRU over "
             "bytes). Evicted prefix-cache blocks and preempted requests' "
             "banked blocks spill here instead of vanishing, and resume/"
             "reuse restores them to the device instead of re-prefilling "
             "(int8 code+scale blocks round-trip as a unit). 0 = off",
    )
    parser.add_argument(
        "--migrate-on-retire", action="store_true",
        help="fleet (--replicas > 1): retire_replica, autoscaler scale-"
             "down, and rolling hot-swaps empty a replica by live-"
             "migrating its in-flight requests to siblings through the "
             "host tier (O(blocks), greedy bit-identical) instead of "
             "waiting for the longest stream to finish",
    )
    parser.add_argument(
        "--speculative", type=int, default=0, metavar="K",
        help="continuous/paged engines: draft up to K tokens per slot per "
             "tick (prompt-lookup by default) and verify them in ONE fused "
             "forward; requests opt in per-call with 'speculative': K. "
             "0 = off (speculative requests fall back to the window engine)",
    )
    parser.add_argument(
        "--draft-dir", default=None,
        help="small same-vocab draft model directory: engine-level "
             "speculation drafts with it instead of prompt-lookup "
             "(requires --speculative K)",
    )
    parser.add_argument(
        "--adapter-dir", default=None,
        help="continuous/paged engines: directory of PEFT-layout LoRA "
             "adapter subdirectories for multi-tenant serving — requests "
             "name one with 'adapter' and co-batch against the shared "
             "base model (infer/adapters.py)",
    )
    parser.add_argument(
        "--max-adapters", type=int, default=8,
        help="adapter pool depth: up to N-1 adapters resident at once "
             "(slot 0 is the reserved identity adapter); idle adapters "
             "evict LRU, pinned ones never",
    )
    parser.add_argument(
        "--adapter-capacity", type=int, default=0, metavar="N",
        help="per-tenant admission quota: max in-flight requests per "
             "adapter name before a tenant-scoped 429 + Retry-After "
             "(0 = unlimited)",
    )
    parser.add_argument(
        "--max-batch", type=int, default=8,
        help="window engine: max concurrent requests grouped into one device "
             "batch (1 = serialize)",
    )
    parser.add_argument(
        "--batch-window-ms", type=float, default=10.0,
        help="how long the batcher waits to fill a group",
    )
    parser.add_argument(
        "--quantize-weights", "--quantize", dest="quantize",
        choices=["none", "int8", "nf4"], default="none",
        help="weight-only inference quantization of the block linears "
             "(ops/int8.py, ops/nf4.py); adapter pools and the draft model "
             "stay full precision",
    )
    parser.add_argument(
        "--quantize-kv", choices=["none", "int8"], default="none",
        help="paged engine only: store the KV block pool as int8 with "
             "per-block absmax scales (halves HBM per resident token); "
             "decode reads fuse the dequant into the paged attention",
    )
    parser.add_argument(
        "--tp", type=int, default=1, metavar="N",
        help="tensor-parallel inference over N local devices",
    )
    parser.add_argument(
        "--request-timeout-s", type=float, default=600.0,
        help="max seconds a request waits for the device before a 503 "
             "(0 = wait forever)",
    )
    parser.add_argument(
        "--max-queue-depth", type=int, default=256,
        help="bounded admission: requests beyond this many waiters are shed "
             "with 429 + Retry-After (0 = unbounded)",
    )
    parser.add_argument(
        "--queue-deadline-s", type=float, default=0.0,
        help="shed requests still queued after this many seconds BEFORE "
             "prefill (503, retryable; 0 = no deadline)",
    )
    parser.add_argument(
        "--priority-default", choices=["interactive", "batch", "best_effort"],
        default="interactive",
        help="continuous/paged engines: priority tier assumed for requests "
             "that send no 'priority' field (admission orders by aged tier; "
             "under pressure the lowest tier sheds and preempts first)",
    )
    parser.add_argument(
        "--age-promote-s", type=float, default=5.0,
        help="anti-starvation: every this-many seconds a queued request "
             "waits, it is ordered as one tier more important (raw tier "
             "still governs shedding/preemption)",
    )
    parser.add_argument(
        "--brownout-queue-wait-s", type=float, default=2.0,
        help="brownout pressure budget: queue-wait EWMA at this many "
             "seconds counts as pressure 1.0",
    )
    parser.add_argument(
        "--brownout-drain-s", type=float, default=10.0,
        help="brownout pressure budget: predicted queue drain time at this "
             "many seconds counts as pressure 1.0",
    )
    parser.add_argument(
        "--brownout-cap-tokens", type=int, default=32,
        help="brownout stage >= 2: max_new_tokens cap applied to "
             "best_effort requests admitted during the brownout",
    )
    parser.add_argument(
        "--drain-timeout-s", type=float, default=30.0,
        help="SIGTERM grace: how long in-flight requests may finish before "
             "the server exits anyway",
    )
    parser.add_argument(
        "--restart-backoff-s", type=float, default=0.5,
        help="supervisor: delay before the first in-process engine restart "
             "(doubles per failure in the circuit window)",
    )
    parser.add_argument(
        "--restart-backoff-max-s", type=float, default=30.0,
        help="supervisor: cap on the exponential restart backoff",
    )
    parser.add_argument(
        "--circuit-threshold", type=int, default=5,
        help="supervisor: retryable failures within --circuit-window-s that "
             "open the circuit (engine stops restarting, /healthz goes 503)",
    )
    parser.add_argument(
        "--circuit-window-s", type=float, default=60.0,
        help="supervisor: sliding window for the circuit-breaker count",
    )
    parser.add_argument(
        "--watchdog-timeout-s", type=float, default=0.0,
        help="hard-exit if the decode worker makes no progress for this many "
             "seconds (wedged device sync; runtime/watchdog.py). Must exceed "
             "the worst-case prefill compile. 0 = off",
    )
    parser.add_argument(
        "--flight-dir", default="outputs/flight_recorder",
        help="directory for flight-recorder JSON dumps (recent engine "
             "events, written on crash/circuit-open). Empty string disables",
    )
    parser.add_argument(
        "--trace-log", default=None,
        help="JSONL file appending every settled request's lifecycle trace "
             "(span + request-relative time + propagated trace id). Off by "
             "default",
    )
    parser.add_argument(
        "--trace-log-max-mb", type=float, default=0.0,
        help="rotate --trace-log when it exceeds this many MB (keeping the "
             "last 5 rotated files); 0 = unbounded append",
    )
    parser.add_argument(
        "--profile-dir", default=None,
        help="enable POST /v1/profile: on-demand jax.profiler captures "
             "written to fresh subdirectories of this path (view with "
             "tensorboard --logdir). Off by default",
    )
    parser.add_argument(
        "--publish-watch-dir", default=os.environ.get("PUBLISH_DIR") or None,
        help="live deployment: watch this trainer publish directory "
             "(train --publish-dir) and hot-swap each new checkpoint in "
             "at a tick boundary with zero dropped requests and zero "
             "recompiles; enables POST /v1/deploy and "
             "POST /v1/deploy/rollback. Off by default",
    )
    parser.add_argument(
        "--publish-poll-s", type=float, default=2.0,
        help="seconds between publish-directory polls "
             "(--publish-watch-dir)",
    )
    parser.add_argument(
        "--auto-rollback-window-s", type=float, default=0.0,
        help="after each hot-swap, watch the error rate for this many "
             "seconds and roll back automatically if it trips "
             "--auto-rollback-error-rate (0 = manual rollback only)",
    )
    parser.add_argument(
        "--auto-rollback-error-rate", type=float, default=0.5,
        help="failed-request fraction within the post-swap window that "
             "triggers the automatic rollback",
    )
    parser.add_argument(
        "--canary-window-s", type=float, default=0.0,
        help="canary-scored deploys (needs --replicas > 1): after swapping "
             "the FIRST replica, compare its per-generation latency/error "
             "deltas against the unswapped siblings for this many seconds; "
             "a regression verdict rolls the canary back and blocks the "
             "publish. 0 = roll all replicas without a canary window",
    )
    parser.add_argument(
        "--canary-min-requests", type=int, default=8,
        help="settled requests the canary (and the sibling baseline) must "
             "see inside --canary-window-s for the verdict to bind; below "
             "it the roll proceeds (the error-rate backstop still guards)",
    )
    parser.add_argument(
        "--slo-ttft-p99-s", type=float, default=2.0,
        help="SLO objective: p99 time-to-first-token target in seconds "
             "(GET /v1/slo burn rates, serving_slo_* gauges)",
    )
    parser.add_argument(
        "--slo-inter-token-p99-s", type=float, default=0.5,
        help="SLO objective: p99 inter-token gap target in seconds",
    )
    parser.add_argument(
        "--slo-error-rate", type=float, default=0.01,
        help="SLO objective: max failed-request fraction (the error "
             "budget burned by requests_failed)",
    )
    parser.add_argument(
        "--slo-availability", type=float, default=0.999,
        help="SLO objective: availability target; sheds (overflow, "
             "deadline, quota) burn the 1 - target budget",
    )
    parser.add_argument(
        "--slo-fast-window-s", type=float, default=60.0,
        help="fast burn-rate window in seconds (a breach needs BOTH "
             "windows hot: fast catches cliffs, slow catches bleeds)",
    )
    parser.add_argument(
        "--slo-slow-window-s", type=float, default=600.0,
        help="slow burn-rate window in seconds",
    )
    parser.add_argument(
        "--slo-sample-interval-s", type=float, default=1.0,
        help="seconds between metric-ring samples (taken on the scheduler "
             "tick clock — zero extra clock reads on the token hot path)",
    )
    args = parser.parse_args(argv)
    if not os.path.isdir(args.model_dir):
        print(f"Error: model directory not found: {args.model_dir!r}")
        return 1
    serve(args.model_dir, args.host, args.port, args.max_batch,
          args.batch_window_ms, args.quantize,
          quantize_kv=args.quantize_kv,
          request_timeout_s=args.request_timeout_s or None, tp=args.tp,
          draft_dir=args.draft_dir, speculative_k=args.speculative,
          adapter_dir=args.adapter_dir, max_adapters=args.max_adapters,
          adapter_capacity=args.adapter_capacity,
          engine_kind=args.engine, replicas=args.replicas,
          routing=args.routing, replica_roles=args.replica_roles,
          autoscale=args.autoscale,
          min_replicas=args.min_replicas, max_replicas=args.max_replicas,
          scale_cooldown_s=args.scale_cooldown_s,
          autoscale_ratio=args.autoscale_ratio, slots=args.slots,
          kv_buf_len=args.kv_buf_len, kv_block_len=args.kv_block_len,
          prefill_chunk=args.prefill_chunk,
          host_tier_mb=args.host_tier_mb,
          migrate_on_retire=args.migrate_on_retire,
          max_queue_depth=args.max_queue_depth,
          queue_deadline_s=args.queue_deadline_s or None,
          priority_default=args.priority_default,
          age_promote_s=args.age_promote_s,
          brownout_queue_wait_s=args.brownout_queue_wait_s,
          brownout_drain_s=args.brownout_drain_s,
          brownout_cap_tokens=args.brownout_cap_tokens,
          drain_timeout_s=args.drain_timeout_s,
          restart_backoff_s=args.restart_backoff_s,
          restart_backoff_max_s=args.restart_backoff_max_s,
          circuit_threshold=args.circuit_threshold,
          circuit_window_s=args.circuit_window_s,
          watchdog_timeout_s=args.watchdog_timeout_s,
          flight_dir=args.flight_dir or None,
          trace_log=args.trace_log,
          trace_log_max_mb=args.trace_log_max_mb,
          profile_dir=args.profile_dir,
          publish_watch_dir=args.publish_watch_dir,
          publish_poll_s=args.publish_poll_s,
          auto_rollback_window_s=args.auto_rollback_window_s,
          auto_rollback_error_rate=args.auto_rollback_error_rate,
          canary_window_s=args.canary_window_s,
          canary_min_requests=args.canary_min_requests,
          slo_ttft_p99_s=args.slo_ttft_p99_s,
          slo_inter_token_p99_s=args.slo_inter_token_p99_s,
          slo_error_rate=args.slo_error_rate,
          slo_availability=args.slo_availability,
          slo_fast_window_s=args.slo_fast_window_s,
          slo_slow_window_s=args.slo_slow_window_s,
          slo_sample_interval_s=args.slo_sample_interval_s)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
