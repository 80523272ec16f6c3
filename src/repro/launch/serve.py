"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Builds one ``core.spec.RuntimeSpec`` from the CLI flags (the single
configuration surface), spins up the serving engine on a reduced config,
submits a demo request mix, and reports tokens/s + the compile-once
accounting.

Multi-topology mode: ``--fleet qwen1.5-0.5b,codeqwen1.5-7b`` serves
several architectures from ONE compiled decode step — shared maxima are
planned with ``maxima_for``, each model is packed into the fabric's
weight table, and requests carry a model id.

Harness mode: ``--trace t.jsonl`` replays an on-disk trace (see
``repro.harness.trace``) through the engine instead of the demo mix and
prints the reduced TTFT/ITL/goodput metrics; ``--tuned`` discards the
hand-picked memory/scheduler flags and lets the analytical autotuner
(``RuntimeSpec.tuned``) choose them — from the trace's own statistics
when ``--trace`` is also given.
"""
from __future__ import annotations

import argparse
import time

from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import ensure_host_devices


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--devices", type=int, default=None,
                    help="force this many host-platform devices (must be "
                         "set before jax initializes — which is why every "
                         "heavy import in this driver is deferred)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel width: the fused step's weights "
                         "and KV pool shard over a (1, tp) GSPMD mesh")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel replicas behind one admission "
                         "queue (serving.cluster.EngineCluster)")
    ap.add_argument("--fleet", default=None,
                    help="comma-separated arch ids served multi-topology "
                         "from one compiled step (overrides --arch)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--sync-every", type=int, default=4,
                    help="fused decode steps dispatched between host syncs")
    ap.add_argument("--kernels", choices=("xla", "pallas"), default="xla",
                    help="matmul routing for prefill/decode")
    ap.add_argument("--quant", choices=("none", "int8"), default="none",
                    help="serving-time weight quantization (C6); works in "
                         "--fleet mode too (int8 fleet weight table)")
    ap.add_argument("--quant-min-size", type=int, default=None,
                    help="param leaves under this many elements stay float")
    ap.add_argument("--kv-dtype", choices=("compute", "int8"),
                    default="compute",
                    help="KV-cache storage codec: bf16 values or "
                         "quantize-on-write int8 (~2x cache capacity)")
    ap.add_argument("--param-dtype", default=None,
                    help="parameter dtype by name, e.g. fp32 / bf16")
    ap.add_argument("--compute-dtype", default=None,
                    help="activation dtype by name, e.g. bf16 / fp32")
    ap.add_argument("--cache-layout", choices=("dense", "paged"),
                    default="dense")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged layout: tokens per KV block")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="paged layout: pool size (default: dense worst case)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share prompt-prefix KV blocks across requests "
                         "(requires --cache-layout paged; rejected at spec "
                         "construction otherwise)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft tokens proposed per "
                         "fused step (0 disables); the target verifies all "
                         "k+1 positions in one chunk-shaped attend")
    ap.add_argument("--draft", default=None,
                    help="draft arch id for --spec-k (default: the target "
                         "itself, i.e. self-draft; must share the target's "
                         "vocab / tokenizer space)")
    ap.add_argument("--trace", default=None,
                    help="replay this on-disk trace (repro.harness.trace "
                         "format) instead of the demo request mix and print "
                         "harness metrics")
    ap.add_argument("--tuned", action="store_true",
                    help="ignore the memory/scheduler flags and let the "
                         "analytical autotuner pick them (uses the trace's "
                         "workload statistics when --trace is given)")
    ap.add_argument("--slo-ttft-steps", type=int, default=None,
                    help="with --trace: count a request toward goodput only "
                         "if its first token lands within this many steps")
    args = ap.parse_args()
    if args.tuned and args.fleet:
        ap.error("--tuned tunes a single architecture; drop --fleet")
    if args.spec_k and (args.fleet or args.tuned or args.dp > 1):
        ap.error("--spec-k drives one hand-specified engine in this "
                 "driver; drop --fleet/--tuned/--dp")
    if args.dp > 1 and args.fleet:
        ap.error("--dp replicates one architecture; drop --fleet")
    need = args.tp * args.dp
    if args.devices is not None:
        ensure_host_devices(max(args.devices, need))
    elif need > 1:
        ensure_host_devices(need)

    use_compile_cache()
    # everything below may initialize jax — after the device bootstrap
    import dataclasses

    import jax

    from repro.configs import REGISTRY, reduced
    from repro.core.spec import (ExecutionSpec, MemorySpec, MeshSpec,
                                 RuntimeSpec, maxima_for)
    from repro.models.model import Model
    from repro.serving.cluster import EngineCluster
    from repro.serving.engine import ServingEngine
    from repro.serving.sampling import SamplingParams

    names = (args.fleet.split(",") if args.fleet else [args.arch])
    cfgs = [reduced(REGISTRY[n]) for n in names]
    maxima = (maxima_for(*cfgs, seq_max=args.max_len)
              if args.fleet else None)
    # string dtype names flow straight into the spec — ExecutionSpec
    # normalizes "bf16"/"fp32"/... at construction
    ex_kw = {}
    if args.param_dtype is not None:
        ex_kw["param_dtype"] = args.param_dtype
    if args.compute_dtype is not None:
        ex_kw["compute_dtype"] = args.compute_dtype
    if args.quant_min_size is not None:
        ex_kw["quant_min_size"] = args.quant_min_size
    trace = None
    if args.trace is not None:
        from repro.harness import load_trace
        trace = load_trace(args.trace)
    execution = ExecutionSpec(matmul_backend=args.kernels,
                              quant=args.quant, **ex_kw)
    if args.tuned:
        from repro.harness import WorkloadProfile
        workload = (WorkloadProfile.from_trace(trace)
                    if trace is not None else None)
        spec = RuntimeSpec.tuned(cfgs[0], workload=workload,
                                 max_len=args.max_len, execution=execution,
                                 allow_int8_kv=args.kv_dtype == "int8")
        m = spec.memory
        print(f"tuned spec: {m.cache_layout} max_batch={m.max_batch} "
              f"policy={spec.scheduler.policy} "
              f"chunk={spec.scheduler.chunk_size} "
              f"kv_dtype={m.kv_dtype} prefix_cache={m.prefix_cache}")
    else:
        speculation = draft_cfg = None
        if args.spec_k:
            from repro.core.spec import SpeculationSpec
            draft_cfg = (reduced(REGISTRY[args.draft]) if args.draft
                         else cfgs[0])
            # a temperature > 0 demo mix needs the rejection-sampling
            # accept path; greedy runs take the exact argmax-match path
            speculation = SpeculationSpec(
                draft_model=draft_cfg, k=args.spec_k,
                greedy_accept=args.temperature <= 0.0)
        spec = RuntimeSpec(
            arch=cfgs[0], maxima=maxima,
            execution=execution,
            memory=MemorySpec(cache_layout=args.cache_layout,
                              max_batch=args.max_batch, max_len=args.max_len,
                              block_size=args.block_size,
                              num_blocks=args.num_blocks,
                              kv_dtype=args.kv_dtype,
                              prefix_cache=args.prefix_cache),
            speculation=speculation)
    if args.tp > 1 or args.dp > 1:
        spec = dataclasses.replace(
            spec, mesh=MeshSpec(tp=args.tp, dp=args.dp))
    sampling = SamplingParams(temperature=args.temperature, top_k=40)
    if args.dp > 1:
        eng = EngineCluster(spec)
    else:
        eng = ServingEngine(spec, max_models=max(len(cfgs), 1),
                            sampling=sampling)
    if args.fleet:
        model_ids = [eng.add_model(Model(c).init(jax.random.PRNGKey(i)), c)
                     for i, c in enumerate(cfgs)]
    else:
        params = Model.from_spec(spec).init(jax.random.PRNGKey(0))
        if args.spec_k:
            draft = (params if draft_cfg == cfgs[0]
                     else Model(draft_cfg).init(jax.random.PRNGKey(1)))
            eng.load(params, draft=draft)
        else:
            eng.load(params)
        model_ids = [0]

    if trace is not None:
        from repro.harness import SLO, replay
        slo = (SLO(ttft_steps=args.slo_ttft_steps)
               if args.slo_ttft_steps is not None else None)
        t0 = time.time()
        res = replay(eng, trace, slo=slo)
        dt = time.time() - t0
        done, m = res.finished, res.metrics
        print(f"trace {trace.name!r} (seed {trace.seed}): "
              f"{m.n_finished}/{m.n_requests} finished over {m.steps} "
              f"fused steps in {dt:.1f}s ({m.tokens_per_s:,.0f} tok/s)")
        print(f"  TTFT p50/p99 {m.ttft_steps_p50}/{m.ttft_steps_p99} steps "
              f"({m.ttft_s_p50 * 1e3:.1f}/{m.ttft_s_p99 * 1e3:.1f} ms)   "
              f"ITL p50/p99 {m.itl_steps_p50}/{m.itl_steps_p99} steps")
        print(f"  peak concurrency {m.peak_concurrency}, "
              f"{m.n_preemptions} preemptions, {m.prefix_hits} prefix hits")
        if slo is not None:
            print(f"  SLO (ttft<={args.slo_ttft_steps} steps): "
                  f"{m.n_slo_met}/{m.n_requests} met, goodput "
                  f"{m.goodput_req_per_1k_steps:.1f} req/1k-steps "
                  f"({m.goodput_req_s:.2f} req/s)")
    else:
        rng = jax.random.PRNGKey(7)
        for i in range(args.requests):
            rng, k = jax.random.split(rng)
            plen = int(jax.random.randint(k, (), 4, args.max_len // 2))
            prompt = list(range(1, plen + 1))
            # the cluster has no engine-level default sampling — pass it
            # per submit (a no-op on the single-engine path)
            eng.submit(prompt, max_new_tokens=args.max_new,
                       sampling=sampling,
                       model=model_ids[i % len(model_ids)])

        t0 = time.time()
        done = (eng.run_to_completion() if args.dp > 1
                else eng.run_to_completion(sync_every=args.sync_every))
        dt = time.time() - t0
        total_new = sum(len(r.generated) for r in done)
        print(f"{len(done)} requests, {total_new} tokens in {dt:.1f}s "
              f"({total_new / dt:,.0f} tok/s)")
    if args.fleet:
        print(f"fleet: {names} served by ONE fused step "
              f"(decode compilations = {eng.compilations['decode']})")
    if args.tp > 1 or args.dp > 1:
        cap = spec.capacity()
        print(f"mesh: tp={args.tp} x dp={args.dp} on {cap.n_devices} "
              f"devices — KV pool {cap.kv_shards}-way sharded, "
              f"{cap.per_device_cache_bytes / 2**20:.2f} MiB cache/device, "
              f"up to {cap.max_concurrent} concurrent")
    if args.dp > 1:
        print("compile accounting per replica:", eng.compilations)
        gets = sum(s["device_gets"] for s in eng.replica_stats())
        print(f"host traffic: {gets} bulk device_gets over "
              f"{eng.stats['decode_steps']} cluster rounds")
        for r in done[:3]:
            print(f"  req {r.uid} (model {r.model}): "
                  f"prompt[:6]={r.prompt[:6]} -> {r.generated[:10]}...")
        return
    print("compile accounting:", eng.compilations)
    if args.spec_k:
        acc, ss = eng.stats["spec_accepted"], eng.stats["spec_steps"]
        mean = acc / ss if ss else 0.0
        print(f"speculation: k={args.spec_k} draft={draft_cfg.name}, "
              f"{acc} draft tokens accepted over {ss} speculative steps "
              f"(mean {mean:.2f}; ~{1 + mean:.2f} tokens/step per "
              "decoding slot)")
    if spec.memory.kv_dtype == "int8":
        hd = cfgs[0].resolved_head_dim
        print(f"int8 KV cache: {2 * hd / (hd + 4):.2f}x fewer cache "
              f"bytes/token than bf16 at head_dim={hd}")
    print(f"host traffic: {eng.stats['device_gets']} bulk device_gets over "
          f"{eng.stats['decode_steps']} fused decode steps")
    if spec.memory.cache_layout == "paged":
        s = eng.memory_stats()
        print(f"paged pool: {s.total_blocks} x "
              f"{spec.memory.block_size}-token blocks, "
              f"{eng.stats['preemptions']} preemptions")
        if spec.memory.prefix_cache:
            print(f"prefix cache: {eng.stats['prefix_hits']} hits / "
                  f"{eng.stats['prefix_hit_tokens']} tokens skipped, "
                  f"{eng.stats['cow_forks']} CoW forks, "
                  f"{eng.stats['prefix_evictions']} evictions; "
                  f"{s.shared_blocks} shared + {s.cached_blocks} parked "
                  "blocks resident")
    for r in done[:3]:
        print(f"  req {r.uid} (model {r.model}): prompt[:6]={r.prompt[:6]} "
              f"-> {r.generated[:10]}...")


if __name__ == "__main__":
    main()
