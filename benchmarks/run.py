"""Benchmark driver: one section per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run`` prints CSV blocks:
  table1  — throughput/efficiency per network (GOPS analogues)
  table2  — analytical model vs compiled HLO (% error)
  fig5    — tile-size sweep (VMEM fit / occupancy / modeled latency)
  fig8    — runtime heads-register sweep on one compiled engine
  fig11   — portability: tile re-planning across memory budgets
  fig12   — the 40-cell roofline table from the dry-run records
  fleet   — multi-topology serving vs per-model engines (equal memory)
  serving — chunked prefill vs bucketed (TTFT / tok/s; BENCH_serving.json)
  qcache  — int8 vs bf16 KV cache at equal HBM (concurrency / drain)
  prefix  — prefix-cached pool vs no sharing (warm TTFT / concurrency)
  harness — tuned spec vs naive default at equal memory (load harness)
  sharded — dp x tp mesh cluster vs 1 device at equal cache/device
  spec    — speculative vs target-only decode (tok/step at equal bytes)

``--devices N`` forces N host-platform (CPU) devices, for the sharded
section on a host without chips; it must be applied before anything
imports jax, so the benchmark modules are imported inside ``main``
after the flag is parsed.  Without it nothing is forced.
"""
from __future__ import annotations

import argparse
import time
import traceback

from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import ensure_host_devices


def _fleet():
    from benchmarks import multi_topology
    r = multi_topology.run(max_batch=4, max_len=64, n_per_model=5,
                           max_new=4, layers=1)
    yield "metric,fleet,two_engines"
    yield f"fused_steps,{r['fleet_steps']},{r['solo_steps']}"
    yield f"wall_s,{r['fleet_wall']:.2f},{r['solo_wall']:.2f}"


def _serving():
    from benchmarks import chunked_prefill
    r = chunked_prefill.run(arch="qwen1.5-0.5b", layers=1, max_batch=4,
                            max_len=64, chunk=16, budget=32, max_new=4,
                            require_speedup=None,
                            out_json="BENCH_serving.json")
    res = r["results"]
    yield "metric,bucketed,chunked"
    for key in ("ttft_short", "ttft_long"):
        yield (f"{key}_warm,{res['phases']['bucketed']['warm'][key]:.4f},"
               f"{res['phases']['chunked']['warm'][key]:.4f}")
    yield ("drain_toks_per_s,"
           f"{res['drain_toks_per_s']['bucketed']:.1f},"
           f"{res['drain_toks_per_s']['chunked']:.1f}")
    yield ("prefill_compilations,"
           f"{res['compilations']['bucketed']['prefill']},"
           f"{res['compilations']['chunked']['prefill']}")


def _qcache():
    from benchmarks import quantized_cache
    r = quantized_cache.run(arch="qwen1.5-0.5b", layers=1, head_dim=64,
                            max_len=64, budget_blocks=24, block_size=8,
                            n_requests=36, max_batch=48, require_gain=1.8,
                            out_json="BENCH_serving.json",
                            require_identical=1.0)
    res = r["results"]
    yield "metric,bf16_cache,int8_cache"
    yield (f"peak_concurrency,{res['peak_concurrency']['compute']},"
           f"{res['peak_concurrency']['int8']}")
    yield (f"steps_to_drain,{res['steps_to_drain']['compute']},"
           f"{res['steps_to_drain']['int8']}")
    yield f"concurrency_gain,1.00,{res['concurrency_gain']:.2f}"


def _prefix():
    from benchmarks import prefix_cache
    r = prefix_cache.run(arch="qwen1.5-0.5b", layers=1, max_len=128,
                         block_size=8, num_blocks=40, n_requests=15,
                         max_batch=24, require_ttft=2.0, require_peak=1.5,
                         out_json="BENCH_serving.json")
    res = r["results"]
    yield "metric,sharing_off,sharing_on"
    yield (f"warm_ttft_s,{res['warm_ttft']['sharing-off']['seconds']:.4f},"
           f"{res['warm_ttft']['sharing-on']['seconds']:.4f}")
    yield (f"peak_concurrency,{res['peak_concurrency']['sharing-off']},"
           f"{res['peak_concurrency']['sharing-on']}")
    yield (f"steps_to_drain,{res['steps_to_drain']['sharing-off']},"
           f"{res['steps_to_drain']['sharing-on']}")
    yield f"identical_streams,{res['identical_streams']},="


def _harness():
    from benchmarks import load_harness
    r = load_harness.run(arch="qwen1.5-0.5b", layers=1, n_requests=24,
                         burst_size=12, gap_steps=16, max_len=64, max_new=4,
                         naive_batch=8, slo_ttft_steps=12,
                         require_goodput_gain=1.2,
                         out_json="BENCH_serving.json")
    res = r["results"]
    m = res["metrics"]
    yield "metric,naive,tuned"
    yield (f"goodput_req_per_1k_steps,"
           f"{m['naive']['goodput_req_per_1k_steps']:.1f},"
           f"{m['tuned']['goodput_req_per_1k_steps']:.1f}")
    yield (f"slo_met,{m['naive']['n_slo_met']}/{m['naive']['n_requests']},"
           f"{m['tuned']['n_slo_met']}/{m['tuned']['n_requests']}")
    yield (f"ttft_steps_p99,{m['naive']['ttft_steps_p99']},"
           f"{m['tuned']['ttft_steps_p99']}")
    yield (f"peak_concurrency,{m['naive']['peak_concurrency']},"
           f"{m['tuned']['peak_concurrency']}")
    yield f"goodput_gain,1.00,{res['goodput_gain']:.2f}"
    yield f"bit_reproducible,=,{res['bit_reproducible']}"


# the sharded section's mesh geometry; main() overwrites from --tp/--dp
MESH = {"tp": 2, "dp": 2}


def _sharded():
    from benchmarks import sharded_serving
    r = sharded_serving.run(arch="qwen1.5-0.5b", layers=1,
                            tp=MESH["tp"], dp=MESH["dp"], num_blocks=12,
                            block_size=8, max_batch=24, n_requests=16,
                            burst_size=16, gap_steps=10, max_len=20,
                            max_new=5, slo_ttft_steps=16,
                            require_peak_gain=2.0,
                            require_goodput_gain=1.3,
                            out_json="BENCH_serving.json")
    res = r["results"]
    yield "metric,single_device,sharded"
    yield (f"peak_concurrency,"
           f"{res['metrics']['single']['peak_concurrency']},"
           f"{res['metrics']['sharded']['peak_concurrency']}")
    yield (f"goodput_req_per_1k_steps,"
           f"{res['metrics']['single']['goodput_req_per_1k_steps']:.1f},"
           f"{res['metrics']['sharded']['goodput_req_per_1k_steps']:.1f}")
    yield (f"pool_tokens,{res['capacity']['pool_tokens']['single']},"
           f"{res['capacity']['pool_tokens']['sharded']}")
    yield (f"per_device_cache_bytes,"
           f"{res['capacity']['per_device_cache_bytes']},=")
    yield f"peak_gain,1.00,{res['peak_gain']:.2f}"
    yield f"goodput_gain,1.00,{res['goodput_gain']:.2f}"
    yield f"identical_streams,=,{res['identical_streams']}"
    yield f"bit_reproducible,=,{res['bit_reproducible']}"


def _spec():
    from benchmarks import speculative
    r = speculative.run(arch="qwen1.5-0.5b", layers=1, spec_k=3,
                        max_len=128, block_size=8, num_blocks=96,
                        n_requests=8, max_new=24, max_batch=6,
                        require_gain=1.5, out_json="BENCH_serving.json")
    res = r["results"]
    yield "metric,target_only,speculative"
    yield (f"tokens_per_step,{res['tokens_per_step']['target_only']:.2f},"
           f"{res['tokens_per_step']['speculative']:.2f}")
    yield (f"steps,{res['steps']['target_only']},"
           f"{res['steps']['speculative']}")
    yield f"mean_accepted_len,=,{res['mean_accepted_len']:.2f}"
    yield f"gain,1.00,{res['gain']:.2f}"
    yield f"identical_streams,=,{res['identical_streams']}"
    yield f"deterministic_replay,=,{res['deterministic_replay']}"


def _figure(module: str):
    def fn():
        import importlib
        return importlib.import_module(f"benchmarks.{module}").run()
    return fn


SECTIONS = [
    ("table1", _figure("table1_throughput")),
    ("table2", _figure("table2_analytical")),
    ("fig5", _figure("fig5_tilesize")),
    ("fig8", _figure("fig8_heads")),
    ("fig11", _figure("fig11_portability")),
    ("fig12", _figure("fig12_roofline")),
    ("fleet", _fleet),
    ("serving", _serving),
    ("qcache", _qcache),
    ("prefix", _prefix),
    ("harness", _harness),
    ("sharded", _sharded),
    ("spec", _spec),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("only", nargs="?", default=None,
                    help="run just this section")
    ap.add_argument("--devices", type=int, default=None,
                    help="host-platform device count to force before jax "
                         "initializes (on CPU the sharded section needs "
                         "tp*dp)")
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--dp", type=int, default=2)
    args = ap.parse_args()
    MESH["tp"], MESH["dp"] = args.tp, args.dp
    if args.devices is not None:
        ensure_host_devices(max(args.devices, args.tp * args.dp))
    use_compile_cache()
    failures = 0
    for name, fn in SECTIONS:
        if args.only and name != args.only:
            continue
        t0 = time.perf_counter()
        print(f"== {name} ==", flush=True)
        try:
            for line in fn():
                print(line)
        except Exception:
            failures += 1
            print(f"{name},ERROR")
            traceback.print_exc()
        print(f"# {name} took {time.perf_counter() - t0:.1f}s", flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
