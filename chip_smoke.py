#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU at qwen1.5-0.5b's published widths.

    python chip_smoke.py               # one chip: phases A and B
    python chip_smoke.py --four-chips  # four chips: tp=2 x dp=2 cluster

One process, no children.  On one chip it serves eight greedy requests
through ``ServingEngine`` twice, with the unreduced config (24 layers,
d_model 1024, 16 heads of width 64, vocab 151,936) and random weights
made from a seed:

* phase A: the default execution path (XLA matmuls, block-table gather
  attention, bf16 KV cache);
* phase B: the Pallas kernels (tiled matmuls, paged flash-decode and
  chunked-prefill attention) over an int8 KV cache.

Each phase then compares the logits its spec gives through the paged
cache (chunked prefill, then decode steps) against a float32
``Model.forward`` over the same tokens.  ``--four-chips`` runs only the
sharded path: an ``EngineCluster`` at tp=2 x dp=2 against a one-chip
engine on the same requests.

Every phase must pass or the script exits non-zero; it also exits
non-zero, printing no result, where JAX finds no TPU.  The last line of
standard output is one JSON object naming the device.  Seconds printed
here are readings of one run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

ARCH = "qwen1.5-0.5b"
SEED = 0
MAX_BATCH, MAX_LEN, BLOCK = 8, 2048, 16
N_REQUESTS, MAX_NEW = 8, 32
PROMPT_MIN, PROMPT_MAX = 64, 1024
CHECK_PROMPT, CHECK_DECODE = 256, 16

# Logits tolerances: the largest relative L2 error over all positions,
# ||got_t - ref_t|| / ||ref_t||, against a float32 forward at "highest"
# matmul precision.  On CPU at full width and 4-12 layers the bf16 path
# sits at 0.020-0.022 and the int8-KV path at 0.026-0.028, flat in depth
# (bf16 keeps 8 mantissa bits; symmetric int8 K/V loses up to 1/254 of a
# row's largest entry per element).  Each tolerance is three times its
# floor.  A wrong cache position gave 0.13-0.15 (a K/V row written one
# position late) up to 1.4 (a RoPE phase or chunk row off by one), and a
# dropped int8 scale 1.4-1.7, so each of those fails.
TOL_A = 0.06     # phase A: bf16 activations and bf16 K/V
TOL_B = 0.09     # phase B: bf16 activations and int8 K/V

# --four-chips: fp32 compute at "highest" matmul precision so that a
# sharded reduction cannot flip a greedy argmax; depth cut to keep
# four-chip time short, widths whole.
FOUR_CHIP_LAYERS = 4
FOUR_CHIP_MAX_LEN = 512


def _fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


def _check(ok: bool, msg: str) -> None:
    if not ok:
        _fail(msg)


class CompileMeter:
    """Seconds JAX spends tracing, lowering and compiling (or reading a
    compiled program back from the persistent cache)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration


def _device():
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"no TPU found: JAX reports platform {d.platform!r} "
              f"({len(devs)} device(s)); this script runs only on a TPU",
              file=sys.stderr)
        raise SystemExit(2)
    return d, len(devs)


def _prompts(rng, vocab: int, lo: int, hi: int) -> list[list[int]]:
    import numpy as np
    lens = np.linspace(lo, hi, N_REQUESTS).astype(int)
    return [rng.integers(1, vocab, size=int(n)).tolist() for n in lens]


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def serve_phase(name: str, spec, params, prompts, meter, device) -> None:
    """Serve ``prompts`` to completion and check the compile-once
    accounting and every stream's token budget."""
    import jax

    from repro.serving.engine import ServingEngine
    from repro.serving.sampling import SamplingParams

    c0 = meter.seconds
    eng = ServingEngine(spec, sampling=SamplingParams(temperature=0.0))
    eng.load(params)
    uids = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    t0 = time.perf_counter()
    done = eng.run_to_completion()
    jax.block_until_ready((eng.cache, eng.state))
    wall = time.perf_counter() - t0
    compile_s = meter.seconds - c0
    comp = eng.compilations()
    lens = {r.uid: len(r.generated) for r in done}
    # the Pallas custom calls live in the compiled step programs
    grants = jax.numpy.zeros((spec.memory.max_batch,), jax.numpy.int32)
    kernels = sum(
        prog.as_text().count("tpu_custom_call") for prog in (
            eng._step.lower(eng.params, eng.cache, eng.state,
                            eng.block_tables, grants).compile(),
            eng._decode.lower(eng.params, eng.cache, eng.state,
                              eng.block_tables).compile()))
    print(f"phase {name}: compile_s={compile_s:.2f} "
          f"decode_compilations={comp['decode']} "
          f"prefill_compilations={comp['prefill']} "
          f"finished={len(done)}/{len(uids)} "
          f"tokens={sum(lens.values())} "
          f"fused_steps={eng.stats['decode_steps']} "
          f"tpu_custom_calls={kernels} "
          f"peak_bytes_in_use={_peak_bytes(device)} "
          f"smoke_wall_s={wall:.2f} (compile included; not a benchmark)",
          flush=True)
    _check(comp["decode"] == 1 and comp["prefill"] == 1,
           f"phase {name}: compilations {dict(comp)}, expected 1 each")
    _check(sorted(lens) == sorted(uids),
           f"phase {name}: {len(lens)}/{len(uids)} requests finished")
    _check(all(n == MAX_NEW for n in lens.values()),
           f"phase {name}: stream lengths {sorted(lens.values())}, "
           f"expected {MAX_NEW} each (no EOS is set)")
    if spec.execution.paged_attn_impl == "pallas":
        _check(kernels > 0, f"phase {name}: no tpu_custom_call compiled")


def logits_check(name: str, spec, params, rng, tol: float) -> None:
    """Chunked prefill of CHECK_PROMPT tokens and CHECK_DECODE decode
    steps through a paged cache whose blocks are scattered over the
    pool, against a float32 forward over the same tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.paging import PagingConfig
    from repro.models.model import Model, ModelOptions

    cfg, bs = spec.arch, spec.memory.block_size
    w = spec.scheduler.chunk_size
    total = CHECK_PROMPT + CHECK_DECODE
    nblk = spec.memory.max_len // bs
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, size=total),
                       jnp.int32)[None]
    table = np.zeros((1, nblk), np.int32)
    table[0, :total // bs] = 1 + rng.permutation(nblk)[:total // bs]
    table = jnp.asarray(table)

    model = Model.from_spec(spec)
    cache = model.init_cache(1, spec.memory.max_len,
                             paging=PagingConfig(block_size=bs,
                                                 num_blocks=nblk))
    mixed, decode = jax.jit(model.mixed_step), jax.jit(model.decode_step)
    got = []
    for c in range(0, CHECK_PROMPT, w):
        lg, cache = mixed(params, cache, toks[:, c:c + w],
                          jnp.asarray([c], jnp.int32),
                          jnp.asarray([w], jnp.int32), table)
        got.append(lg[0])
    for i in range(CHECK_PROMPT, total):
        lg, cache = decode(params, cache, toks[:, i:i + 1],
                           jnp.asarray([i], jnp.int32), table)
        got.append(lg[0])
    got = jnp.concatenate(got)

    ref_model = Model(cfg, ModelOptions(param_dtype=jnp.float32,
                                        compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(ref_model.forward)(params, {"tokens": toks})[0]
    rel = jnp.linalg.norm(got - ref, axis=-1) / jnp.linalg.norm(ref, axis=-1)
    rel = np.asarray(rel)
    worst = int(rel.argmax())
    agree = float(np.mean(np.asarray(got.argmax(-1) == ref.argmax(-1))))
    print(f"phase {name} logits: max_rel_l2={rel.max():.5f} at position "
          f"{worst} (prefill max {rel[:CHECK_PROMPT].max():.5f}, decode "
          f"max {rel[CHECK_PROMPT:].max():.5f}), tolerance {tol}, "
          f"argmax agreement {agree:.4f}", flush=True)
    _check(bool(np.isfinite(np.asarray(got)).all()),
           f"phase {name}: non-finite logits")
    _check(float(rel.max()) <= tol,
           f"phase {name}: logits off the float32 reference by "
           f"{rel.max():.5f} > {tol}")


def one_chip(device) -> None:
    import jax
    import numpy as np

    from repro.configs import REGISTRY
    from repro.core.spec import (ExecutionSpec, MemorySpec, RuntimeSpec,
                                 SchedulerSpec)
    from repro.models.model import Model

    meter = CompileMeter()
    cfg = REGISTRY[ARCH]
    rng = np.random.default_rng(SEED)
    prompts = _prompts(rng, cfg.vocab_size, PROMPT_MIN, PROMPT_MAX)
    memory = MemorySpec(cache_layout="paged", max_batch=MAX_BATCH,
                        max_len=MAX_LEN, block_size=BLOCK)
    spec_a = RuntimeSpec(arch=cfg, memory=memory,
                         scheduler=SchedulerSpec(policy="chunked"))
    spec_b = dataclasses.replace(
        spec_a,
        execution=ExecutionSpec(matmul_backend="pallas",
                                paged_attn_impl="pallas"),
        memory=dataclasses.replace(memory, kv_dtype="int8"))

    params = Model.from_spec(spec_a).init(jax.random.PRNGKey(SEED))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"{ARCH}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.num_heads}/{cfg.num_kv_heads} heads x "
          f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}: {n_params} parameters; prompts "
          f"{[len(p) for p in prompts]}", flush=True)
    for name, spec, tol in (("A", spec_a, TOL_A), ("B", spec_b, TOL_B)):
        serve_phase(name, spec, params, prompts, meter, device)
        gc.collect()   # the engine's jitted bound methods form a cycle
        logits_check(name, spec, params, rng, tol)
    print(f"compile_s_total={meter.seconds:.2f}", flush=True)


def four_chips(n_devices: int) -> None:
    import jax
    import numpy as np

    from repro.configs import REGISTRY
    from repro.core.spec import (ExecutionSpec, MemorySpec, MeshSpec,
                                 RuntimeSpec, SchedulerSpec)
    from repro.models.model import Model
    from repro.serving.cluster import EngineCluster
    from repro.serving.engine import ServingEngine
    from repro.serving.sampling import SamplingParams

    _check(n_devices >= 4, f"--four-chips needs 4 devices, found {n_devices}")
    cfg = dataclasses.replace(REGISTRY[ARCH], num_layers=FOUR_CHIP_LAYERS)
    rng = np.random.default_rng(SEED)
    prompts = _prompts(rng, cfg.vocab_size, PROMPT_MIN,
                       FOUR_CHIP_MAX_LEN - MAX_NEW)
    spec = RuntimeSpec(
        arch=cfg, execution=ExecutionSpec(compute_dtype="fp32"),
        memory=MemorySpec(cache_layout="paged", max_batch=MAX_BATCH,
                          max_len=FOUR_CHIP_MAX_LEN, block_size=BLOCK),
        scheduler=SchedulerSpec(policy="chunked"))
    params = Model.from_spec(spec).init(jax.random.PRNGKey(SEED))
    greedy = SamplingParams(temperature=0.0)

    def streams(eng):
        uids = [eng.submit(p, max_new_tokens=MAX_NEW, sampling=greedy)
                for p in prompts]
        done = {r.uid: r.generated for r in eng.run_to_completion()}
        return [done[u] for u in uids]

    # a TPU runs float32 matmuls as one bf16 pass by default, where the
    # sharded reductions' other summation order flips near-tied argmaxes
    # of random weights; "highest" makes float32 compute float32
    with jax.default_matmul_precision("highest"):
        single = ServingEngine(spec, sampling=greedy)
        single.load(params)
        base = streams(single)
        cluster = EngineCluster(dataclasses.replace(
            spec, mesh=MeshSpec(tp=2, dp=2)))
        cluster.load(params)
        got = streams(cluster)

    def ids(tree) -> list[int]:
        return sorted({d.id for x in jax.tree.leaves(tree)
                       for d in x.devices()})

    devs = jax.devices()
    for i, eng in enumerate(cluster.replicas):
        want = sorted(d.id for d in devs[2 * i:2 * i + 2])
        where = {"params": ids(eng.params), "cache": ids(eng.cache),
                 "block tables": ids(eng.block_tables)}
        comp = dict(eng.compilations)
        print(f"replica {i}: " + ", ".join(
            f"{k} on devices {v}" for k, v in where.items())
            + f", compilations {comp}", flush=True)
        _check(all(v == want for v in where.values()),
               f"replica {i} placement {where}, expected devices {want}")
        _check(comp["decode"] == 1 and comp["prefill"] == 1,
               f"replica {i} compilations {comp}, expected 1 each")
    same = sum(a == b for a, b in zip(got, base))
    first = [next((t for t, (x, y) in enumerate(zip(a, b)) if x != y), None)
             for a, b in zip(got, base)]
    print(f"four chips ({cfg.num_layers} of {REGISTRY[ARCH].num_layers} "
          f"layers, fp32): {same}/{len(base)} greedy streams identical to "
          f"the one-chip engine; first differing token per stream {first}",
          flush=True)
    _check(got == base, "tp2 x dp2 streams differ from the one-chip engine")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tp=2 x dp=2 cluster against a "
                         "one-chip engine (needs four chips)")
    args = ap.parse_args()
    cache_dir = use_compile_cache()
    device, n = _device()
    print(f"device: {device.device_kind} x {n} ({device.platform}); "
          f"compile cache {cache_dir}", flush=True)
    if args.four_chips:
        four_chips(n)
    else:
        one_chip(device)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": n}}))


if __name__ == "__main__":
    main()
