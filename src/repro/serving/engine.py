"""Batched serving engine with device-resident continuous batching.

Compile-once discipline (the paper's Alg. 18 applied to serving):

* **chunked scheduler** (default wherever the family supports it) — ONE
  fused mixed step, compiled exactly once, does everything: prompts are
  split into fixed ``chunk_size`` chunks and up to ``token_budget``
  prompt tokens ride *inside the same jitted step* that decodes active
  slots (a Sarathi-style mixed batch).  Every slot advances by up to W =
  chunk_size query lanes per dispatch — a decoding slot uses one lane, a
  prefilling slot a chunk of its prompt (gathered on device from
  ``SlotState.prompt_buf``), an idle slot none.  Prefill compilations
  drop from O(#buckets x modes) to O(1) and a long prompt never stalls
  the decoding slots sharing its batch.  The cache and ``SlotState`` are
  donated to the step (``donate_argnums``), so XLA updates the KV pool
  in place instead of copying it every token.
* **bucketed scheduler** (legacy; families with sequential prefill
  state) — ``prefill_fn`` compiled per prompt-length *bucket* (powers of
  two up to max_len): a new request is padded up to its bucket,
  prefilled at B=1, and its cache is scattered into the shared batched
  cache; ``decode_fn`` is the one-lane fused step.  Idle slots compute
  masked garbage (idle PEs) that never reaches a live output.

Host↔device discipline (the paper's "no host intervention beyond the
topology registers"): **all** per-slot state lives in device arrays
(``SlotState``).  The host only *dispatches* the fused step and harvests
finished requests with one bulk ``device_get`` of the (done, count)
vectors per sync — O(1) transfers per step regardless of ``max_batch``.
Finished token buffers are pulled with one more bulk get, sliced to the
longest finished stream (never ``max_len`` columns).

Cache layouts (the paper's tiling discipline applied to KV memory):

* ``cache_layout="dense"`` — per-slot ``[max_batch, max_len]`` rows; a
  request of length 40 pays for ``max_len``, so concurrency is bounded
  by the worst case.
* ``cache_layout="paged"`` — a pooled ``[num_blocks, block_size, ...]``
  cache (``core.paging``): a request is **admitted when the blocks for
  its prompt are free**, blocks are appended as decode crosses block
  boundaries (pre-reserved per sync window, so the fused step still
  needs zero host intervention) and returned to the free list at
  harvest.  When the pool runs dry mid-flight the most recently admitted
  slot is preempted (its tokens are banked and the request re-queued for
  recompute-resume), so the oldest request always completes.

Configuration surface: the engine is built from one frozen
``core.spec.RuntimeSpec`` — ``ServingEngine(spec)``.  Every knob the
constructor used to take piecemeal (``matmul_backend``, ``cache_layout``,
``block_size``, ``num_blocks``) now lives in ``spec.execution`` /
``spec.memory``; the old ``ServingEngine(model, kwarg=...)`` spellings
keep working for one release behind ``DeprecationWarning`` shims.

Multi-topology serving (the paper's §3.12 payoff): ``ServingEngine(spec,
maxima=...)`` compiles the register-driven ``serving.fabric`` at the
maxima instead of one fixed architecture.  ``add_model(params, arch)``
packs any dense-family model into the device-resident weight table, each
slot carries its model's topology registers inside ``SlotState``, and the
one fused decode step serves a mixed fleet — continuous batching *across
models*, zero retraces.

Fully-quantized serving: ``spec.execution.quant="int8"`` quantizes the
weights (including the fleet's weight table — int8 values + f32 scales
per member) and ``spec.memory.kv_dtype="int8"`` swaps the KV cache for
the ``core.kv_quant`` codec (quantize-on-write int8 with per-row scales,
~2x concurrent capacity at equal HBM) in every mode — dense, paged,
chunked, fleet.  See README "Fully-quantized serving".
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import warnings
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig
from repro.distributed import sharding as shd
from repro.core.paging import (NULL_BLOCK, BlockAllocator, FragmentationStats,
                               PrefixCache, blocks_for_tokens)
from repro.core.jitutil import strict_jit
from repro.core.kv_quant import fork_block
from repro.core.spec import (CHUNKABLE_FAMILIES, ExecutionSpec, MemorySpec,
                             RuntimeSpec)
from repro.kernels.mla_attention import live_keys
from repro.kernels.runtime import interpret_default
from repro.models import backend
from repro.models.model import Model
from repro.serving.events import EngineEvent, EventBus
from repro.serving.events import now as _now
from repro.serving.fabric import N_REGS, DecodeFabric
from repro.serving.sampling import (SamplingParams, fold_in_keys,
                                    sample_per_slot, speculative_accept,
                                    split_keys)

# The always-on summary counters.  These are *derived* telemetry kept for
# backward compatibility (tests and benchmarks read them); anything
# per-request or per-step now flows through the structured event surface
# (``serving.events`` / ``engine.events``) instead of growing this dict.
_STAT_KEYS = ("decode_steps", "device_gets", "harvest_elems", "preemptions",
              "max_step_prefill_tokens", "prefix_hits",
              "prefix_hit_tokens", "cow_forks", "prefix_evictions",
              "spec_steps", "spec_accepted")


def _span(name: str):
    """Run the decorated method inside the host span ``name``
    (``serving.events.SPAN_NAMES``)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with TraceAnnotation(name):
                return fn(*args, **kwargs)
        return wrapped
    return deco


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int = 32
    eos_id: int | None = None
    sampling: SamplingParams | None = None   # None -> engine default
    generated: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    slot: int | None = None
    # tokens generated before a preemption; on re-admission they extend
    # the prompt (recompute-resume) and still count against the budget
    prefix: list[int] = dataclasses.field(default_factory=list)
    # fleet member serving this request (multi-topology mode; 0 otherwise)
    model: int = 0


class SlotState(NamedTuple):
    """All per-slot decode state, resident on device (one pytree)."""

    last: jax.Array    # [B, 1] i32  token fed to the next decode step
    index: jax.Array   # [B]    i32  cache write position
    active: jax.Array  # [B]    bool slot is live (prefilling or decoding)
    done: jax.Array    # [B]    bool finished, not yet harvested/reused
    budget: jax.Array  # [B]    i32  max_new_tokens (incl. prefill token)
    count: jax.Array   # [B]    i32  tokens generated so far
    eos: jax.Array     # [B]    i32  eos id, -1 = none
    temp: jax.Array    # [B]    f32  sampling temperature (0 = greedy)
    top_k: jax.Array   # [B]    i32  top-k cutoff (0 = disabled)
    top_p: jax.Array   # [B]    f32  nucleus threshold (1 = disabled)
    buf: jax.Array     # [B, max_len] i32 generated tokens
    # [B, 2] u32 per-slot PRNG key lanes, split once per fused step: each
    # slot's sampling stream is a pure function of its own lane, so a
    # harness replay is byte-identical regardless of batch composition
    rng: jax.Array
    topo: jax.Array    # [B, N_REGS] i32 per-slot topology registers
    # chunked-prefill progress (the token-budget scheduler's device side)
    prompt_buf: jax.Array  # [B, max_len] i32 prompt tokens, chunk source
    prompt_len: jax.Array  # [B] i32 total prompt length
    pf_pos: jax.Array      # [B] i32 prompt tokens already written to cache
    # speculative-decoding accounting (zeros when speculation is off)
    acc: jax.Array         # [B] i32 accepted draft tokens, cumulative
    spec_steps: jax.Array  # [B] i32 fused steps this slot spec-decoded in
    # MoE models: [2] i32 (token, expert) rows the held experts computed
    # and held experts hit, summed over layers and steps (None otherwise)
    experts: jax.Array | None = None


class _Compilations(dict):
    """Compile-count mapping that is also callable: both the historical
    ``engine.compilations["decode"]`` property spelling and the newer
    ``engine.compilations()["prefill"]`` read the same accounting."""

    def __call__(self) -> "_Compilations":
        return self


def _buckets(max_len: int, smallest: int = 32) -> list[int]:
    out, b = [], smallest
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return out


def _resolve_spec(spec, maxima, max_batch, max_len, matmul_backend,
                  cache_layout, block_size, num_blocks):
    """Normalize the constructor surface onto one ``RuntimeSpec``.

    Returns ``(spec, model)``; ``model`` is the caller's ``Model``
    instance when the legacy model-first spelling was used (kept so the
    inherit path can reuse it without re-tracing).  The legacy per-knob
    kwargs are deprecation shims: they still work, warn once, and are
    folded into the spec so everything downstream reads one surface.
    """
    legacy = {k: v for k, v in (("matmul_backend", matmul_backend),
                                ("cache_layout", cache_layout),
                                ("block_size", block_size),
                                ("num_blocks", num_blocks)) if v is not None}
    if legacy:
        warnings.warn(
            "ServingEngine(" + ", ".join(f"{k}=..." for k in sorted(legacy))
            + ") is deprecated; configure these through core.spec."
              "RuntimeSpec — execution=ExecutionSpec(matmul_backend=...), "
              "memory=MemorySpec(cache_layout=..., block_size=..., "
              "num_blocks=...) — and pass the spec to ServingEngine",
            DeprecationWarning, stacklevel=3)
    if isinstance(spec, Model):
        model = spec
        opt = model.opt
        ex = ExecutionSpec(
            matmul_backend=legacy.get("matmul_backend", opt.matmul_backend),
            paged_attn_impl=opt.paged_attn_impl,
            param_dtype=opt.param_dtype,
            compute_dtype=opt.compute_dtype,
            grouped_gqa=opt.grouped_gqa)
        mem = MemorySpec(
            cache_layout=legacy.get("cache_layout", "dense"),
            max_batch=8 if max_batch is None else max_batch,
            max_len=512 if max_len is None else max_len,
            block_size=legacy.get("block_size", 16),
            num_blocks=legacy.get("num_blocks"))
        return RuntimeSpec(arch=model.cfg, maxima=maxima, execution=ex,
                           memory=mem), model
    if not isinstance(spec, RuntimeSpec):
        raise TypeError(
            "ServingEngine expects a core.spec.RuntimeSpec (or a legacy "
            f"Model), got {type(spec).__name__}")
    ex, mem = spec.execution, spec.memory
    if "matmul_backend" in legacy:
        ex = dataclasses.replace(ex, matmul_backend=legacy["matmul_backend"])
    mem_kw = {k: v for k, v in legacy.items()
              if k in ("cache_layout", "block_size", "num_blocks")}
    if max_batch is not None:
        mem_kw["max_batch"] = max_batch
    if max_len is not None:
        mem_kw["max_len"] = max_len
    if mem_kw:
        mem = dataclasses.replace(mem, **mem_kw)
    if maxima is None:
        maxima = spec.maxima
    if ex is not spec.execution or mem is not spec.memory \
            or maxima is not spec.maxima:
        spec = dataclasses.replace(spec, execution=ex, memory=mem,
                                   maxima=maxima)
    return spec, None


class ServingEngine:
    def __init__(self, spec: RuntimeSpec | Model, *,
                 maxima=None, max_models: int = 4,
                 sampling: SamplingParams = SamplingParams(),
                 rng: jax.Array | None = None,
                 devices=None,
                 max_batch: int | None = None,
                 max_len: int | None = None,
                 matmul_backend: str | None = None,
                 cache_layout: str | None = None,
                 block_size: int | None = None,
                 num_blocks: int | None = None):
        spec, model = _resolve_spec(spec, maxima, max_batch, max_len,
                                    matmul_backend, cache_layout,
                                    block_size, num_blocks)
        cfg = spec.arch
        if cfg.family == "encoder":
            raise ValueError("encoder-only archs have no decode step")
        self.spec = spec
        self.cfg: ArchConfig = cfg
        self.max_batch = spec.memory.max_batch
        self.max_len = spec.memory.max_len
        self.sampling = sampling
        self.buckets = _buckets(self.max_len)
        self.matmul_backend = spec.execution.matmul_backend
        # Pallas kernels need interpret mode off-TPU; evaluated once here
        # instead of on every fused dispatch
        self._interpret = interpret_default()

        # ---- scheduler: chunked (token-budget) or bucketed ---------------
        sched = spec.scheduler
        chunkable = (spec.maxima is not None
                     or cfg.family in CHUNKABLE_FAMILIES) \
            and not sched.chunk_violations(spec.memory)
        if sched.policy == "auto":
            self.scheduler = "chunked" if chunkable else "bucketed"
        else:
            # an unsatisfiable explicit "chunked" was rejected by
            # RuntimeSpec.validate at construction
            self.scheduler = sched.policy
        self.chunk_size = min(sched.chunk_size, self.max_len)
        self.token_budget = sched.resolved_token_budget

        # ---- speculation: a draft model rides the fused step -------------
        # The draft decodes from its OWN private dense cache inside the
        # same jitted program (propose k tokens, one masked lane each),
        # then the target verifies all k+1 positions as a chunk-shaped
        # attend.  ``spec_horizon`` = k+1 is the positions a decoding slot
        # may consume per fused step — block budgeting scales by it.
        sp = spec.speculation
        self.speculation = sp
        self.spec_horizon = 1 if sp is None else sp.horizon
        self.draft_model: Model | None = None
        self.draft_params: Any = None
        self.draft_cache: Any = None
        if sp is not None:
            if self.scheduler != "chunked":
                raise ValueError(
                    "speculation requires the chunked scheduler, but policy "
                    "'auto' resolved to 'bucketed' for this spec; fix the "
                    "chunk geometry so chunked is satisfiable")
            if sp.horizon > self.chunk_size:
                raise ValueError(
                    f"SpeculationSpec.k={sp.k} needs {sp.horizon} verify "
                    f"lanes but the engine's chunk width is "
                    f"{self.chunk_size}; raise SchedulerSpec.chunk_size")
            from repro.models.model import ModelOptions
            # the draft's cache is always dense + compute-dtype: it is
            # small, rolls back by index rewind alone, and never pages
            self.draft_model = Model(
                sp.draft_model,
                dataclasses.replace(
                    ModelOptions.from_execution(spec.execution),
                    kv_dtype="compute"))

        # ---- tensor-parallel mesh (spec.mesh.tp devices per fused step) --
        # MeshSpec(tp=1) without an explicit device list is the historical
        # single-device engine: no mesh object, identical lowering.  With
        # tp > 1 (or an explicit ``devices=`` placement, how EngineCluster
        # pins each DP replica to its own device slice) the engine builds a
        # (data=1, model=tp) mesh: params shard via the logical-axis rules,
        # the cache's kv-head axis shards via ``kv_cache_shardings``, and
        # SlotState / block tables replicate.  spec.validate() already
        # rejected tp > 1 with fleet mode / Pallas kernels / bucketed.
        tp = spec.mesh.tp
        if spec.mesh.dp > 1 and devices is None:
            raise ValueError(
                f"spec.mesh.dp={spec.mesh.dp}: data parallelism is replica-"
                "level — construct serving.cluster.EngineCluster(spec) (one "
                "ServingEngine is a single replica; EngineCluster passes "
                "each replica its device slice via devices=)")
        self._mesh = self._strategy = self._cache_shardings = None
        self._device = None
        if tp > 1 or devices is not None:
            if tp > 1 and self.scheduler != "chunked":
                raise ValueError(
                    "mesh.tp > 1 requires the chunked scheduler, but policy "
                    "'auto' resolved to 'bucketed' for this spec (the "
                    "bucketed path stages B=1 prefill caches off-mesh); fix "
                    "the chunk geometry so chunked is satisfiable")
            devs = list(devices) if devices is not None \
                else jax.devices()[:tp]
            if len(devs) < tp:
                raise ValueError(
                    f"mesh.tp={tp} needs {tp} devices but only {len(devs)} "
                    "are visible; on CPU force virtual host devices before "
                    "jax initializes (launch.mesh.ensure_host_devices(n) / "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=n)")
            if tp > 1:
                self._mesh = shd.tp_mesh(devs[:tp])
                self._strategy = shd.strategy_for_mesh(self._mesh)
            else:
                # tp=1 replica pinned to one device: no GSPMD at all.  A
                # 1x1 mesh would work but poisons the jit cache — device_put
                # commits NamedShardings while the step's outputs come back
                # SingleDeviceSharding, and the sharding mismatch recompiles
                # the step on its second call (sharding is part of the C++
                # jit cache key).  Committed single-device placement gives
                # one stable key and disjoint replica residency for free.
                self._device = devs[0]

        # ---- compute path: one fixed model, or the register fabric -------
        if spec.maxima is not None:
            # multi-topology mode: one compiled step at the maxima serves a
            # fleet of models selected by per-slot registers (add_model)
            if spec.execution.matmul_backend != "xla":
                raise ValueError(
                    f"matmul_backend={spec.execution.matmul_backend!r} is "
                    "not yet supported in multi-topology mode: the fabric's "
                    "per-slot weight gathers do not route through the "
                    "tiled-kernel backend (use the default 'xla'; for "
                    "quantized fleet serving use "
                    "ExecutionSpec(quant='int8') — the fabric packs an "
                    "int8 weight table itself — see README "
                    "'Fully-quantized serving')")
            self.fabric: DecodeFabric | None = DecodeFabric(
                spec.maxima, max_models, cfg,
                compute_dtype=spec.execution.compute_dtype,
                param_dtype=spec.execution.param_dtype,
                quant=spec.execution.quant,
                quant_min_size=spec.execution.quant_min_size,
                kv_dtype=spec.memory.kv_dtype)
            self.fabric.check_member(cfg)
            self.model: Model | None = None
            self._traced_model: Model | None = None
            self.fleet: list[ArchConfig | None] = [None] * max_models
            self._fleet_rows: list[list[int] | None] = [None] * max_models
        else:
            self.fabric = None
            # single source of truth: the backend every trace uses is
            # spec.execution.matmul_backend.  A caller's Model instance is
            # kept when it already agrees; with a legacy override the
            # traced model is rebuilt around the spec's backend but keeps
            # its other build options (remat/unroll are training-side
            # knobs the spec does not model — the shim must not reset
            # them)
            if model is None:
                self.model = Model.from_spec(spec)
            elif model.opt.matmul_backend == self.matmul_backend \
                    and model.opt.kv_dtype == spec.memory.kv_dtype:
                self.model = model
            else:
                self.model = Model(cfg, dataclasses.replace(
                    model.opt, matmul_backend=self.matmul_backend,
                    kv_dtype=spec.memory.kv_dtype))
            self._traced_model = self.model

        # ---- cache layout -------------------------------------------------
        self.paging = spec.memory.paging()
        max_batch, max_len = self.max_batch, self.max_len
        if self.paging is not None:
            bs = self.paging.block_size
            if self.buckets[0] % bs:
                raise ValueError(
                    f"block_size={bs} must divide the smallest prefill "
                    f"bucket {self.buckets[0]}")
            self.allocator = BlockAllocator(self.paging)
            self.blocks_per_slot = max_len // bs
            self._tables = [[NULL_BLOCK] * self.blocks_per_slot
                            for _ in range(max_batch)]
            self._slot_blocks: list[list[int]] = [[] for _ in range(max_batch)]
            self._tables_dirty = True
            self.block_tables: jax.Array | None = jnp.zeros(
                (max_batch, self.blocks_per_slot), jnp.int32)
        else:
            self.allocator = None
            self.block_tables = None

        # ---- prefix cache (paged + chunked only) -------------------------
        self.prefix_cache: PrefixCache | None = None
        if spec.memory.prefix_cache:
            if self.scheduler != "chunked":
                raise ValueError(
                    "prefix_cache=True requires the chunked scheduler, but "
                    "policy 'auto' resolved to 'bucketed' for this spec "
                    "(a cache-hit request resumes prefill mid-prompt, which "
                    "only the fused chunked step supports); fix the chunk "
                    "geometry so the chunked scheduler is satisfiable")
            self.prefix_cache = PrefixCache(self.allocator)
        # one-shot per occupancy: a slot's prompt blocks are registered in
        # the trie once its prefill completes
        self._reg_done = [False] * max_batch
        # host mirrors for block budgeting (exact at sync points; between
        # syncs ``_idx_ub`` is a per-step upper bound on the device index)
        self._plen = [0] * max_batch
        self._budget = [0] * max_batch
        self._idx_ub = [0] * max_batch
        self._admit_seq = [0] * max_batch
        self._seq = 0
        # chunked-prefill progress mirror: exact, because the host grants
        # every chunk itself — no device read needed
        self._pf = [0] * max_batch

        self.params: Any = None
        self.cache: Any = None
        if self.fabric is not None:
            # the fabric's synthesis-time buffers exist before any model is
            # loaded — add_model only writes device data into them
            self.params = self.fabric.init_table()
            self.cache = self.fabric.init_cache(max_batch, max_len,
                                                paging=self.paging)
            if self._placement is not None:
                # DP replica placement: the fabric's table and cache live
                # whole on this replica's device (slice); add_model's
                # scatters and the fused step keep that placement because
                # every other operand follows the committed arrays
                self.params = jax.device_put(self.params, self._placement)
                self.cache = jax.device_put(self.cache, self._placement)
        # MoE models count their held experts' work in the slot state
        # (``SlotState.experts``); each harvest publishes what it added
        self._experts = self._traced_model is not None \
            and self._traced_model.cfg.moe is not None
        self._experts_seen = [0, 0]
        self._unsynced = 0        # dispatches since the last harvest
        self.state: SlotState = self._init_state(
            rng if rng is not None else jax.random.PRNGKey(0))
        if self._placement is not None:
            self.state = jax.device_put(self.state, self._placement)
        self.slot_req: list[Request | None] = [None] * max_batch
        self.queue: list[Request] = []
        self._uid = 0
        # host↔device traffic accounting (asserted O(1)/step by the tests);
        # harvest_elems counts i32 elements pulled for finished buffers —
        # bounded by the finished streams' lengths, not max_len
        self.stats = dict.fromkeys(_STAT_KEYS, 0)
        # structured lifecycle events (serving.events): subscribers see
        # submit/admit/first_token/progress/finish/preempt per request.
        # Publishing is skipped entirely while nobody subscribes.
        self.events = EventBus()
        # uids whose first token was already announced — a re-admission
        # after preemption must not emit first_token twice
        self._ft_emitted: set[int] = set()

        # the cache and SlotState are donated: XLA aliases the KV pool and
        # the slot buffers in place of copying them on every fused step.
        # strict_jit raises (REPRO_STRICT=1) if XLA ever demotes that
        # aliasing to a copy instead of warning into the void.
        self._decode = strict_jit(self._decode_impl, donate_argnums=(1, 2))
        self._step = strict_jit(self._mixed_impl, donate_argnums=(1, 2))
        self._prefill = {}        # bucket -> jitted fn (bucketed path)
        self._insert = jax.jit(self._insert_impl, static_argnums=(3,))
        self._insert_paged = jax.jit(self._insert_paged_impl,
                                     static_argnums=(3,))
        self._admit_slot = jax.jit(self._admit_slot_impl)
        self._admit_chunk = jax.jit(self._admit_chunk_impl)
        self._evict_slot = jax.jit(self._evict_slot_impl)
        # copy-on-write fork: duplicate one pool block (values + scales)
        # before a cache-hit request writes past the divergence point.
        # src/dst are traced scalars — one compilation, cache donated.
        self._cow = strict_jit(self._cow_impl, donate_argnums=(0,))

    # ------------------------------------------------------------------
    def _init_state(self, rng: jax.Array) -> SlotState:
        B = self.max_batch
        return SlotState(
            last=jnp.zeros((B, 1), jnp.int32),
            index=jnp.zeros((B,), jnp.int32),
            active=jnp.zeros((B,), bool),
            done=jnp.zeros((B,), bool),
            budget=jnp.zeros((B,), jnp.int32),
            count=jnp.zeros((B,), jnp.int32),
            eos=jnp.full((B,), -1, jnp.int32),
            temp=jnp.zeros((B,), jnp.float32),
            top_k=jnp.zeros((B,), jnp.int32),
            top_p=jnp.ones((B,), jnp.float32),
            buf=jnp.zeros((B, self.max_len), jnp.int32),
            rng=jax.random.split(rng, B),
            topo=jnp.zeros((B, N_REGS), jnp.int32),
            prompt_buf=jnp.zeros((B, self.max_len), jnp.int32),
            prompt_len=jnp.zeros((B,), jnp.int32),
            pf_pos=jnp.zeros((B,), jnp.int32),
            acc=jnp.zeros((B,), jnp.int32),
            spec_steps=jnp.zeros((B,), jnp.int32),
            experts=jnp.zeros((2,), jnp.int32) if self._experts else None)

    def _emit(self, kind: str, uid: int, **data) -> None:
        """Publish one lifecycle event (no-op without subscribers).  The
        event's logical clock is the fused-dispatch count, so event
        arithmetic is bit-reproducible; the wall stamp is not."""
        if self.events.active:
            self.events.publish(EngineEvent(
                kind, uid, self.stats["decode_steps"], _now(), data))

    def _emit_first_token(self, uid: int) -> None:
        """``first_token`` exactly once per uid — a request re-admitted
        after preemption already announced its first token."""
        if self.events.active and uid not in self._ft_emitted:
            self._ft_emitted.add(uid)
            self.events.publish(EngineEvent(
                "first_token", uid, self.stats["decode_steps"], _now(), {}))

    def load(self, params, draft=None) -> None:
        """Install weights (quantized here when ``spec.execution.quant``
        asks for it).  Multi-topology mode: equivalent to
        ``add_model(params)`` for the engine's own architecture.
        ``draft`` installs the speculation draft's weights in the same
        call (sugar for :meth:`load_draft`)."""
        if draft is not None and self.speculation is None:
            raise ValueError(
                "load(draft=...) requires spec.speculation — construct the "
                "RuntimeSpec with speculation=SpeculationSpec(...)")
        if self.fabric is not None:
            self.add_model(params)
        else:
            if self.spec.execution.quant == "int8":
                from repro.core.serve_quant import quantize_params
                params = quantize_params(
                    params, min_size=self.spec.execution.quant_min_size)
            self.params = params
            self.cache = self.model.init_cache(self.max_batch, self.max_len,
                                               paging=self.paging)
            if self._mesh is not None or self._device is not None:
                self._shard_arrays()
        if draft is not None:
            self.load_draft(draft)

    def load_draft(self, params) -> None:
        """Install the speculation draft's weights and its private dense
        KV cache.  The draft never pages and never quantizes its cache —
        it is small by design, and rejected-suffix rollback on a dense
        cache is a pure index rewind (stale rows are masked by the causal
        window and overwritten on the next propose pass).  On a TP mesh
        the draft replicates whole — its work is k one-lane decodes."""
        if self.speculation is None:
            raise ValueError(
                "load_draft requires spec.speculation — construct the "
                "RuntimeSpec with speculation=SpeculationSpec(...)")
        if self.spec.execution.quant == "int8":
            from repro.core.serve_quant import quantize_params
            params = quantize_params(
                params, min_size=self.spec.execution.quant_min_size)
        self.draft_params = params
        self.draft_cache = self.draft_model.init_cache(self.max_batch,
                                                       self.max_len)
        if self._placement is not None:
            self.draft_params = jax.device_put(self.draft_params,
                                               self._placement)
            self.draft_cache = jax.device_put(self.draft_cache,
                                              self._placement)

    @property
    def _placement(self):
        """device_put target for whole (replicated) arrays: the mesh's
        replicated sharding, the pinned replica device, or None for the
        historical uncommitted single-device engine."""
        if self._mesh is not None:
            return shd.replicated(self._mesh)
        return self._device

    def _shard_arrays(self) -> None:
        """Lower ``spec.mesh`` through ``distributed.sharding``: params
        via the logical-axis rules the models already annotate
        (``model.axes()``), the cache via its kv-head axis, block tables
        replicated.  Committed placements matter beyond locality — the
        donated fused step must see inputs already laid out like its
        outputs, or strict_jit's donation contract trips."""
        if self._mesh is None:
            self.params = jax.device_put(self.params, self._device)
            self.cache = jax.device_put(self.cache, self._device)
            if self.block_tables is not None:
                self.block_tables = jax.device_put(self.block_tables,
                                                   self._device)
            return
        mesh, strategy = self._mesh, self._strategy
        axes, abstract = self.model.axes(), self.model.abstract()
        if self.spec.execution.quant == "int8":
            from repro.core.serve_quant import (quantize_abstract,
                                                quantize_axes)
            ms = self.spec.execution.quant_min_size
            axes = quantize_axes(axes, abstract, min_size=ms)
            abstract = quantize_abstract(abstract, min_size=ms)
        self.params = jax.device_put(
            self.params,
            shd.tree_param_shardings(mesh, axes, abstract, strategy))
        arch = self.spec.arch
        self._cache_shardings = shd.kv_cache_shardings(
            mesh, self.cache, strategy, kv_heads=arch.num_kv_heads,
            head_dim=arch.resolved_head_dim)
        self.cache = jax.device_put(self.cache, self._cache_shardings)
        if self.block_tables is not None:
            self.block_tables = jax.device_put(self.block_tables,
                                               shd.replicated(mesh))

    def _pin_outputs(self, cache, state: SlotState):
        """In-graph output shardings for the donated (cache, state) pair:
        identical to the input shardings, so XLA's buffer donation holds
        under GSPMD.  No-op off-mesh (the jaxpr of the single-device
        engine is unchanged — the census fingerprints pin that)."""
        if self._mesh is None:
            return cache, state
        wsc = jax.lax.with_sharding_constraint
        if self._cache_shardings is not None:
            cache = jax.tree.map(wsc, cache, self._cache_shardings)
        rep = shd.replicated(self._mesh)
        state = jax.tree.map(lambda x: wsc(x, rep), state)
        return cache, state

    def _mesh_scope(self):
        """Activation-constraint scope for traced bodies: inside it the
        models' ``constrain(...)`` hints resolve against this engine's
        mesh (no-ops off-mesh)."""
        if self._mesh is None:
            return contextlib.nullcontext()
        return shd.active(self._mesh, self._strategy)

    def add_model(self, params, arch: ArchConfig | None = None) -> int:
        """Pack one fleet member's weights into the fabric's model table
        and return its model id (pass to ``submit(..., model=id)``).

        A device scatter, never a retrace: the table rows are synthesis-
        time buffers, loading a model is the paper's weight-write step.
        """
        if self.fabric is None:
            raise ValueError(
                "add_model requires multi-topology mode — construct the "
                "engine with ServingEngine(spec, maxima=...)")
        if isinstance(arch, RuntimeSpec):
            arch = arch.arch
        arch = arch or self.cfg
        mid = next((i for i, a in enumerate(self.fleet) if a is None), None)
        if mid is None:
            raise ValueError(
                f"model table full ({self.fabric.max_models} rows); "
                "construct the engine with a larger max_models")
        row = self.fabric.pack_member(arch, params)
        self.params = self.fabric.insert_model(self.params, row, mid)
        self.fleet[mid] = arch
        self._fleet_rows[mid] = self.fabric.topo_row(arch, mid)
        return mid

    @_span("engine.submit")
    def submit(self, prompt: list[int], max_new_tokens: int = 32,
               eos_id: int | None = None,
               sampling: SamplingParams | None = None,
               model: int = 0) -> int:
        # reject at the door: raising later, mid-drain, would abort
        # run_to_completion with live requests still in flight.  The guard
        # mirrors the decode finish condition (index >= max_len): every
        # admitted request can use the full cache, so a max_len prompt is
        # fine when its one token comes from the prefill sample.
        if not prompt:
            raise ValueError("empty prompt: the engine needs at least one "
                             "token to condition on")
        if len(prompt) > self.max_len:
            raise ValueError(f"prompt length {len(prompt)} exceeds "
                             f"max_len={self.max_len}")
        if len(prompt) == self.max_len and max_new_tokens > 1:
            raise ValueError(
                f"prompt length {len(prompt)} leaves no cache position for "
                f"decode (max_len={self.max_len}); max_new_tokens must be 1")
        if self.paging is not None:
            need = blocks_for_tokens(len(prompt), self.paging.block_size)
            if need > self.paging.num_blocks:
                # an unadmittable request would sit in the queue forever
                raise ValueError(
                    f"prompt needs {need} blocks but the pool has only "
                    f"{self.paging.num_blocks}; increase num_blocks")
        if self.fabric is not None:
            if not 0 <= model < len(self.fleet) or self.fleet[model] is None:
                loaded = [i for i, a in enumerate(self.fleet) if a is not None]
                raise ValueError(f"model id {model} is not loaded "
                                 f"(loaded ids: {loaded}); call add_model")
            vocab = self.fleet[model].vocab_size
            if prompt and not all(0 <= t < vocab for t in prompt):
                raise ValueError(
                    f"prompt contains token ids outside model {model}'s "
                    f"vocab [0, {vocab})")
        elif model != 0:
            raise ValueError("submit(model=...) requires multi-topology "
                             "mode (ServingEngine(spec, maxima=...))")
        else:
            vocab = self.cfg.vocab_size
            if not all(0 <= t < vocab for t in prompt):
                # out-of-range ids are not just garbage-in: XLA clamps the
                # OOB embedding gather, and a *sharded* table clamps to a
                # different row than an unsharded one — the same submit
                # would stream different tokens on different meshes
                raise ValueError(
                    f"prompt contains token ids outside vocab [0, {vocab})")
        self._uid += 1
        self.queue.append(Request(self._uid, list(prompt), max_new_tokens,
                                  eos_id, sampling, model=model))
        self._emit("submit", self._uid, prompt_len=len(prompt),
                   max_new_tokens=max_new_tokens, model=model)
        return self._uid

    # ------------------------------------------------------------------
    # jitted impls (traced under the configured matmul backend)
    # ------------------------------------------------------------------
    def _prefill_impl(self, bucket: int, params, tokens, extras):
        with backend.use(self.matmul_backend):
            batch = {"tokens": tokens, **extras}
            # paged: the B=1 cache is only a staging buffer for the block
            # scatter, so bucket-sized is enough (and cheaper than max_len)
            cache_len = bucket if self.paging is not None else self.max_len
            logits, cache = self._traced_model.prefill(params, batch,
                                                       max_len=cache_len)
            return logits, cache

    def _prefill_fabric_impl(self, bucket: int, params, tokens, topo):
        """Fabric prefill: the member's topology registers are device data,
        so every fleet model shares this bucket's one compilation."""
        with backend.use(self.matmul_backend):
            cache_len = bucket if self.paging is not None else self.max_len
            return self.fabric.prefill(params, topo, tokens, cache_len)

    def _insert_impl(self, global_cache, one_cache, slot, _bucket):
        def put(g, o):
            if g.ndim == o.ndim and g.shape[0] == o.shape[0] and g.ndim >= 2 \
                    and g.shape[1] == self.max_batch:
                return g.at[:, slot].set(o[:, 0])      # [L, B, ...] stacked
            return g.at[slot].set(o[0])                # [B, ...] per-layer
        return jax.tree.map(put, global_cache, one_cache)

    def _insert_paged_impl(self, pool, one_cache, table_row, bucket: int):
        """Scatter a B=1 prefill cache into the block pool.

        Chunks past the prompt's allocated blocks carry padding garbage;
        their table entries are the null block, which absorbs them."""
        bs = self.paging.block_size
        nchunks = bucket // bs
        ids = table_row[:nchunks]

        def put(g, o):
            chunks = o.reshape(o.shape[0], nchunks, bs, *g.shape[3:])
            return g.at[:, ids].set(chunks)
        return jax.tree.map(put, pool, one_cache)

    def _admit_slot_impl(self, state: SlotState, last_logits, slot, plen,
                         budget, eos, temp, top_k, top_p,
                         topo) -> SlotState:
        """Seat one prefilled request: sample its first token and reset
        every per-slot field — all on device, no host round trip.
        ``topo`` writes the slot's topology registers (zeros when the
        engine serves a single fixed architecture)."""
        ks = jax.random.split(state.rng[slot])
        first = sample_per_slot(last_logits, ks[1:], temp[None], top_k[None],
                                top_p[None])[0]
        # spent: a 1-token budget is consumed by the prefill sample, an
        # eos prefill sample ends the request, and a max_len prompt has
        # no cache position left to decode into
        fin = (budget <= 1) | ((eos >= 0) & (first == eos)) \
            | (plen >= self.max_len)
        return SlotState(
            last=state.last.at[slot, 0].set(first),
            index=state.index.at[slot].set(plen),
            active=state.active.at[slot].set(~fin),
            done=state.done.at[slot].set(fin),
            budget=state.budget.at[slot].set(budget),
            count=state.count.at[slot].set(1),
            eos=state.eos.at[slot].set(eos),
            temp=state.temp.at[slot].set(temp),
            top_k=state.top_k.at[slot].set(top_k),
            top_p=state.top_p.at[slot].set(top_p),
            buf=state.buf.at[slot].set(0).at[slot, 0].set(first),
            rng=state.rng.at[slot].set(ks[0]),
            topo=state.topo.at[slot].set(topo),
            prompt_buf=state.prompt_buf,
            prompt_len=state.prompt_len.at[slot].set(plen),
            pf_pos=state.pf_pos.at[slot].set(plen),  # bucketed: prefilled
            acc=state.acc.at[slot].set(0),
            spec_steps=state.spec_steps.at[slot].set(0),
            experts=state.experts)

    def _admit_chunk_impl(self, state: SlotState, slot, toks, plen, budget,
                          eos, temp, top_k, top_p, topo,
                          start) -> SlotState:
        """Seat one request for chunked prefill: write its prompt into the
        device-resident chunk source and reset every per-slot field — the
        prompt is *not* run here; the fused mixed step consumes it chunk
        by chunk under the token budget.  ``start`` (a traced scalar, so
        no retrace) is the prefix-cache hit length: positions below it
        are already resident in the slot's mapped blocks, so prefill
        resumes mid-prompt exactly as it does after a chunk boundary —
        0 without a hit."""
        return SlotState(
            last=state.last.at[slot, 0].set(0),
            index=state.index.at[slot].set(start),
            active=state.active.at[slot].set(True),
            done=state.done.at[slot].set(False),
            budget=state.budget.at[slot].set(budget),
            count=state.count.at[slot].set(0),
            eos=state.eos.at[slot].set(eos),
            temp=state.temp.at[slot].set(temp),
            top_k=state.top_k.at[slot].set(top_k),
            top_p=state.top_p.at[slot].set(top_p),
            buf=state.buf.at[slot].set(0),
            rng=state.rng,
            topo=state.topo.at[slot].set(topo),
            prompt_buf=state.prompt_buf.at[slot].set(toks),
            prompt_len=state.prompt_len.at[slot].set(plen),
            pf_pos=state.pf_pos.at[slot].set(start),
            acc=state.acc.at[slot].set(0),
            spec_steps=state.spec_steps.at[slot].set(0),
            experts=state.experts)

    def _cow_impl(self, cache, src, dst):
        """Fork pool block ``src`` into ``dst`` across every cache leaf
        (values and int8 scale rows alike — ``kv_quant.fork_block``).
        Donated, so the pool's mesh sharding is re-pinned on the way out."""
        cache = fork_block(cache, src, dst)
        if self._mesh is not None and self._cache_shardings is not None:
            cache = jax.tree.map(jax.lax.with_sharding_constraint, cache,
                                 self._cache_shardings)
        return cache

    def _evict_slot_impl(self, state: SlotState, slot) -> SlotState:
        """Preemption: park a slot as idle (its tokens were banked on the
        host; the request re-enters through the normal admission path)."""
        return state._replace(
            active=state.active.at[slot].set(False),
            done=state.done.at[slot].set(False),
            count=state.count.at[slot].set(0),
            index=state.index.at[slot].set(0),
            prompt_len=state.prompt_len.at[slot].set(0),
            pf_pos=state.pf_pos.at[slot].set(0),
            acc=state.acc.at[slot].set(0),
            spec_steps=state.spec_steps.at[slot].set(0))

    def _decode_impl(self, params, cache, state: SlotState, block_tables):
        """The fused device step: decode -> sample -> scatter token ->
        advance indices/budgets -> raise done flags.  One dispatch, zero
        host syncs.  With speculation on, the steady-state decode program
        is the draft-propose / target-verify step specialized to zero
        prompt lanes (``decode_only``) — still exactly one compilation."""
        if self.speculation is not None:
            return self._spec_impl(params, cache, state, block_tables,
                                   None, decode_only=True)
        with backend.use(self.matmul_backend), self._mesh_scope():
            rng, keys = split_keys(state.rng)
            if self.fabric is not None:
                logits, cache = self.fabric.decode_step(
                    params, cache, state.last, state.index, state.topo,
                    block_tables=block_tables,
                    paged_attn_impl=self.spec.execution.paged_attn_impl,
                    interpret=self._interpret)
            else:
                out = self._traced_model.decode_step(
                    params, cache, state.last, state.index,
                    block_tables=block_tables, live=state.active,
                    expert_counts=self._experts)
                logits, cache = out[:2]
                state = self._add_expert_counts(state, out)
            with jax.named_scope("sample"):
                toks = sample_per_slot(logits[:, 0], keys, state.temp,
                                       state.top_k, state.top_p)

            with jax.named_scope("slot_update"):
                act = state.active
                act_i = act.astype(jnp.int32)
                rows = jnp.arange(self.max_batch)
                pos = jnp.minimum(state.count, self.max_len - 1)
                buf = state.buf.at[rows, pos].set(
                    jnp.where(act, toks, state.buf[rows, pos]))
                count = state.count + act_i
                index = state.index + act_i
                hit_eos = act & (state.eos >= 0) & (toks == state.eos)
                # cache-full is index >= max_len: position max_len-1 is a
                # real, usable slot (the historical `max_len - 1` check
                # wasted it)
                finish = act & (hit_eos | (count >= state.budget)
                                | (index >= self.max_len))
                state = state._replace(
                    last=jnp.where(act[:, None], toks[:, None], state.last),
                    index=index,
                    active=act & ~finish,
                    done=state.done | finish,
                    count=count,
                    buf=buf,
                    rng=rng)
            return self._pin_outputs(cache, state)

    def _mixed_impl(self, params, cache, state: SlotState, block_tables,
                    chunk_len):
        """THE fused step of the chunked scheduler: one dispatch advances
        every slot by up to W = chunk_size lanes — prompt chunks for
        prefilling slots (``chunk_len[b]`` > 0, tokens gathered on device
        from ``prompt_buf``), the next decode token for decoding slots,
        nothing for idle ones — then samples, scatters tokens and
        advances indices/budgets/eos flags.  Zero host syncs; chunk
        grants are data, so this traces exactly once."""
        if self.speculation is not None:
            return self._spec_impl(params, cache, state, block_tables,
                                   chunk_len, decode_only=False)
        with backend.use(self.matmul_backend), self._mesh_scope():
            B, W = self.max_batch, self.chunk_size
            rng, keys = split_keys(state.rng)
            prefilling = chunk_len > 0
            decoding = state.active & (state.pf_pos >= state.prompt_len)
            n_live = jnp.where(prefilling, chunk_len,
                               jnp.where(decoding, 1, 0))
            start = jnp.where(prefilling, state.pf_pos, state.index)
            # lane tokens: the slot's next prompt window, or its last
            # sampled token in lane 0 (dead lanes carry garbage that the
            # lane masks drop)
            gidx = jnp.minimum(
                start[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :],
                self.max_len - 1)
            ptoks = jnp.take_along_axis(state.prompt_buf, gidx, axis=1)
            dtoks = jnp.pad(state.last, ((0, 0), (0, W - 1)))
            toks = jnp.where(prefilling[:, None], ptoks, dtoks)
            if self.fabric is not None:
                logits, cache = self.fabric.mixed_step(
                    params, cache, toks, start, n_live, state.topo,
                    block_tables=block_tables,
                    paged_attn_impl=self.spec.execution.paged_attn_impl,
                    interpret=self._interpret)
            else:
                out = self._traced_model.mixed_step(
                    params, cache, toks, start, n_live,
                    block_tables=block_tables, prefill_lanes=prefilling,
                    expert_counts=self._experts)
                logits, cache = out[:2]
                state = self._add_expert_counts(state, out)

            # sampling lane: a completing prompt's last live lane, else 0
            completes = prefilling & \
                (state.pf_pos + chunk_len >= state.prompt_len)
            with jax.named_scope("sample"):
                sel = jnp.where(completes, chunk_len - 1, 0)
                lsel = jnp.take_along_axis(logits, sel[:, None, None],
                                           axis=1)[:, 0]
                toks_s = sample_per_slot(lsel, keys, state.temp,
                                         state.top_k, state.top_p)

            with jax.named_scope("slot_update"):
                emit = decoding | completes   # slots producing a token now
                rows = jnp.arange(B)
                pos = jnp.minimum(state.count, self.max_len - 1)
                buf = state.buf.at[rows, pos].set(
                    jnp.where(emit, toks_s, state.buf[rows, pos]))
                count = state.count + emit.astype(jnp.int32)
                index = state.index + n_live
                pf_pos = state.pf_pos + jnp.where(prefilling, chunk_len, 0)
                hit_eos = emit & (state.eos >= 0) & (toks_s == state.eos)
                finish = emit & (hit_eos | (count >= state.budget)
                                 | (index >= self.max_len))
                state = state._replace(
                    last=jnp.where(emit[:, None], toks_s[:, None],
                                   state.last),
                    index=index,
                    active=state.active & ~finish,
                    done=state.done | finish,
                    count=count,
                    buf=buf,
                    rng=rng,
                    pf_pos=pf_pos)
            return self._pin_outputs(cache, state)

    def _add_expert_counts(self, state: SlotState, out) -> SlotState:
        """Add a model step's held-expert counts (its third output, MoE
        models only) to the running totals in ``state``."""
        if not self._experts:
            return state
        return state._replace(experts=state.experts + out[2])

    def _spec_impl(self, params, cache, state: SlotState, block_tables,
                   chunk_len, decode_only: bool):
        """The speculative fused step: draft-propose -> target-verify ->
        accept/rollback, ONE dispatch, zero host syncs.

        ``params``/``cache`` are ``(target, draft)`` pairs — the draft
        decodes from its own private dense cache inside this same jitted
        program.  Per decoding slot: the draft proposes ``k`` tokens
        (one masked ``mixed_step`` lane each, positions ``index + j``),
        then the target scores all ``k + 1`` positions in a single
        chunk-shaped attend — exactly the chunked-prefill machinery
        (``gqa_mixed``/``gqa_mixed_paged`` walking the block tables), so
        a verify pass costs one mixed dispatch, not k+1 decode steps.
        Acceptance is cumulative (``serving.sampling.speculative_accept``)
        and the *rollback is an index rewind*: ``index`` advances only by
        the accepted length m <= k+1, so the rejected suffix's stale KV
        sits beyond every causal mask and is overwritten by the next
        step's writes at the same positions.  Block-table tails freed by
        the rewind are reclaimed host-side (``_truncate_slot_blocks``).

        ``decode_only=True`` is the steady-state specialization (the
        ``_decode`` program): no prompt lanes anywhere, so the draft's
        chunk-prefill pass is dropped and the verify attend shrinks from
        ``chunk_size`` to ``k + 1`` lanes.
        """
        with backend.use(self.matmul_backend), self._mesh_scope():
            B = self.max_batch
            k = self.speculation.k
            greedy_mode = self.speculation.greedy_accept
            W = self.spec_horizon if decode_only else self.chunk_size
            rng, keys = split_keys(state.rng)
            if decode_only:
                prefilling = jnp.zeros((B,), bool)
            else:
                prefilling = chunk_len > 0
            decoding = state.active & (state.pf_pos >= state.prompt_len)
            p_t, p_d = params
            c_t, c_d = cache
            start = jnp.where(prefilling, state.pf_pos, state.index)

            if not decode_only:
                # draft rides the same prompt chunks: its private cache
                # must hold the prompt KV before it can propose (logits
                # discarded; a prefix-cache hit skips these positions for
                # the target but not the draft — see README, acceptance
                # simply degrades on the reused span)
                gidx = jnp.minimum(
                    start[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :],
                    self.max_len - 1)
                ptoks = jnp.take_along_axis(state.prompt_buf, gidx, axis=1)
                n_pf = jnp.where(prefilling, chunk_len, 0)
                _, c_d = self.draft_model.mixed_step(
                    p_d, c_d, ptoks, start, n_pf, prefill_lanes=prefilling)

            # draft proposes k tokens, one masked lane per inner pass
            # (mixed_step, NOT decode_step: dead lanes must write nothing
            # — idle and prefilling slots would corrupt their own cache)
            dec1 = jnp.where(decoding, 1, 0)
            cur = state.last
            proposals, dlogits = [], []
            for j in range(k):
                lg, c_d = self.draft_model.mixed_step(
                    p_d, c_d, cur, state.index + j, dec1)
                dl = lg[:, 0]
                g = jnp.argmax(dl, axis=-1).astype(jnp.int32)
                if greedy_mode:
                    d = g
                else:
                    # temperature-only proposal, matching the densities
                    # speculative_accept uses in its accept ratio
                    x = dl.astype(jnp.float32) \
                        / jnp.maximum(state.temp, 1e-6)[:, None]
                    dj = jax.vmap(jax.random.categorical)(
                        fold_in_keys(keys, j + 2), x).astype(jnp.int32)
                    d = jnp.where(state.temp <= 0.0, g, dj)
                proposals.append(d)
                dlogits.append(dl)
                cur = d[:, None]
            # write-only pass: park d_k's KV at index+k so a fully
            # accepted step leaves no hole in the draft cache (the next
            # propose pass attends across index..index+k)
            _, c_d = self.draft_model.mixed_step(
                p_d, c_d, cur, state.index + k, dec1)
            d_toks = jnp.stack(proposals, axis=1)          # [B, k]
            d_logits = jnp.stack(dlogits, axis=1)          # [B, k, V]

            # target verify: [last, d_1..d_k] occupy positions
            # index..index+k; lane j's logits condition on the prefix
            # plus proposals 1..j.  Lanes past the cache end are masked
            # (n_spec), their writes land in the null block.
            ver = jnp.concatenate([state.last, d_toks], axis=1)  # [B, k+1]
            ver_w = jnp.pad(ver, ((0, 0), (0, W - (k + 1))))
            n_spec = jnp.clip(self.max_len - state.index, 0, k + 1)
            n_live = jnp.where(prefilling, 0, jnp.where(decoding, n_spec, 0))
            toks = ver_w
            if not decode_only:
                n_live = jnp.where(prefilling, chunk_len, n_live)
                toks = jnp.where(prefilling[:, None], ptoks, ver_w)
            if self.fabric is not None:
                logits, c_t = self.fabric.mixed_step(
                    p_t, c_t, toks, start, n_live, state.topo,
                    block_tables=block_tables,
                    paged_attn_impl=self.spec.execution.paged_attn_impl,
                    interpret=self._interpret)
            else:
                logits, c_t = self._traced_model.mixed_step(
                    p_t, c_t, toks, start, n_live,
                    block_tables=block_tables, prefill_lanes=prefilling)

            # accept / rollback over the k+1 verify lanes
            n_acc, out = speculative_accept(
                logits[:, :k + 1], d_toks, d_logits, fold_in_keys(keys, 1),
                state.temp, greedy=greedy_mode)
            n_acc = jnp.minimum(n_acc, jnp.maximum(n_spec - 1, 0))
            jar = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
            cand = (jar <= n_acc[:, None]) & decoding[:, None]
            room = state.count[:, None] + jar < state.budget[:, None]
            is_eos = (state.eos[:, None] >= 0) & (out == state.eos[:, None])
            stop = cand & room & is_eos
            eos_before = jnp.cumsum(stop.astype(jnp.int32), axis=1) \
                - stop.astype(jnp.int32)
            # valid lanes form a prefix run: room and eos cuts are
            # monotone in j, so m = sum(valid) and out[:, :m] is emitted
            valid = cand & room & (eos_before == 0)
            m = valid.sum(axis=1).astype(jnp.int32)

            rows = jnp.arange(B)
            # invalid lanes are routed out of bounds and dropped — a
            # where-write at a clamped position would race a valid lane's
            # scatter at max_len - 1
            wpos = jnp.where(valid, state.count[:, None] + jar, self.max_len)
            buf = state.buf.at[rows[:, None], wpos].set(out, mode="drop")
            count = state.count + m
            index = state.index + jnp.where(decoding, m, 0)
            pf_pos = state.pf_pos
            last_dec = jnp.take_along_axis(
                out, jnp.maximum(m - 1, 0)[:, None], axis=1)[:, 0]
            lastv = jnp.where(decoding, last_dec, state.last[:, 0])
            emit = decoding
            hit_eos = stop.any(axis=1)

            if not decode_only:
                # completing prompt chunks sample their first token from
                # the verify pass's own logits — identical to the base
                # mixed step
                completes = prefilling & \
                    (state.pf_pos + chunk_len >= state.prompt_len)
                sel = jnp.where(completes, chunk_len - 1, 0)
                lsel = jnp.take_along_axis(logits, sel[:, None, None],
                                           axis=1)[:, 0]
                toks_s = sample_per_slot(lsel, fold_in_keys(keys, 0),
                                         state.temp, state.top_k,
                                         state.top_p)
                buf = buf.at[rows, jnp.where(completes, count,
                                             self.max_len)].set(
                    toks_s, mode="drop")
                count = count + completes.astype(jnp.int32)
                index = index + jnp.where(prefilling, chunk_len, 0)
                pf_pos = pf_pos + jnp.where(prefilling, chunk_len, 0)
                lastv = jnp.where(completes, toks_s, lastv)
                emit = emit | completes
                hit_eos = hit_eos | (completes & (state.eos >= 0)
                                     & (toks_s == state.eos))

            finish = emit & (hit_eos | (count >= state.budget)
                             | (index >= self.max_len))
            state = state._replace(
                last=lastv[:, None],
                index=index,
                active=state.active & ~finish,
                done=state.done | finish,
                count=count,
                buf=buf,
                rng=rng,
                pf_pos=pf_pos,
                acc=state.acc + jnp.where(decoding,
                                          jnp.maximum(m - 1, 0), 0),
                spec_steps=state.spec_steps + decoding.astype(jnp.int32))
            c_t, state = self._pin_outputs(c_t, state)
            if self._mesh is not None:
                rep = shd.replicated(self._mesh)
                c_d = jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(x, rep), c_d)
            return (c_t, c_d), state

    # ------------------------------------------------------------------
    # host-side control (dispatch-only between syncs)
    # ------------------------------------------------------------------
    @_span("engine.admit")
    def _admit(self) -> None:
        if self.scheduler == "chunked":
            self._admit_chunked()
            return
        for slot in range(self.max_batch):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            req = self.queue[0]
            prompt = req.prompt + req.prefix
            plen = len(prompt)
            budget = req.max_new_tokens - len(req.prefix)
            bucket = next((b for b in self.buckets if b >= plen), None)
            if bucket is None:
                raise ValueError(
                    f"prompt length {plen} exceeds max_len={self.max_len}")
            blocks: list[int] | None = None
            if self.paging is not None:
                # block-budget admission: seat the request iff its prompt's
                # blocks are free right now (FCFS — the queue head waits
                # rather than being overtaken by shorter prompts)
                blocks = self.allocator.alloc(blocks_for_tokens(
                    plen, self.paging.block_size))
                if blocks is None:
                    break
            self.queue.pop(0)
            if bucket not in self._prefill:
                if self.fabric is not None:
                    self._prefill[bucket] = jax.jit(
                        lambda p, t, tp, _b=bucket:
                        self._prefill_fabric_impl(_b, p, t, tp))
                else:
                    self._prefill[bucket] = jax.jit(
                        lambda p, t, e, _b=bucket:
                        self._prefill_impl(_b, p, t, e))
            toks = jnp.asarray(prompt + [0] * (bucket - plen), jnp.int32)[None]
            topo_row = jnp.zeros((N_REGS,), jnp.int32)
            if self.fabric is not None:
                topo_row = jnp.asarray(self._fleet_rows[req.model], jnp.int32)
                logits, one_cache = self._prefill[bucket](self.params, toks,
                                                          topo_row)
            else:
                extras = {}
                if self.cfg.frontend is not None:
                    extras["frontend"] = jnp.zeros(
                        (1, self.cfg.frontend.num_tokens, self.cfg.d_model),
                        jnp.bfloat16)
                logits, one_cache = self._prefill[bucket](self.params, toks,
                                                          extras)
            if self.paging is not None:
                self._slot_blocks[slot] = blocks
                row = blocks + [NULL_BLOCK] * (self.blocks_per_slot
                                               - len(blocks))
                self._tables[slot] = row
                self._tables_dirty = True
                self.cache = self._insert_paged(
                    self.cache, one_cache, jnp.asarray(row, jnp.int32), bucket)
            else:
                self.cache = self._insert(self.cache, one_cache, slot, bucket)
            sp = req.sampling or self.sampling
            temp, top_k, top_p = sp.as_arrays()
            self.state = self._admit_slot(
                self.state, logits[:, plen - 1], jnp.int32(slot),
                jnp.int32(plen), jnp.int32(budget),
                jnp.int32(-1 if req.eos_id is None else req.eos_id),
                temp, top_k, top_p, topo_row)
            req.slot = slot
            self.slot_req[slot] = req
            self._plen[slot] = plen
            self._budget[slot] = budget
            self._idx_ub[slot] = plen
            self._pf[slot] = plen
            self._seq += 1
            self._admit_seq[slot] = self._seq
            self._emit("admit", req.uid, slot=slot, cached_tokens=0)
            # the bucketed prefill dispatch samples the first token itself
            self._emit_first_token(req.uid)

    def _admit_chunked(self) -> None:
        """Token-budget admission: seat a request by *writing its prompt*
        into the device-resident chunk source — no prefill dispatch, no
        bucket compile.  The fused mixed step earns its first token once
        the scheduler has granted all its chunks."""
        for slot in range(self.max_batch):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            req = self.queue[0]
            prompt = req.prompt + req.prefix
            plen = len(prompt)
            budget = req.max_new_tokens - len(req.prefix)
            start = 0
            if self.paging is not None:
                total = blocks_for_tokens(plen, self.paging.block_size)
                if self.prefix_cache is not None:
                    # consult the trie BEFORE allocating: the hit's blocks
                    # are pinned (incref + unpark) so the eviction the
                    # allocation below may trigger cannot reclaim them.
                    # The cached span is capped at plen - 1 — the last
                    # prompt token always runs through the model, because
                    # the first sample needs its logits.
                    hit = self.prefix_cache.lookup(
                        self._namespace(req.model), prompt, plen - 1)
                    self.prefix_cache.acquire(hit)
                    fresh = self._alloc_blocks(total - len(hit.blocks))
                    if fresh is None:
                        self.prefix_cache.release(hit)
                        break   # FCFS: the queue head waits for blocks
                    blocks = hit.blocks + fresh
                    start = hit.tokens
                    if hit.fork_block is not None:
                        # mid-block divergence: fork the partial source
                        # into the request's first private block, then
                        # unpin the source — concurrent writers never
                        # alias a shared block
                        self.cache = self._cow(self.cache,
                                               jnp.int32(hit.fork_block),
                                               jnp.int32(fresh[0]))
                        self.prefix_cache.drop_fork_source(hit)
                        start += hit.fork_tokens
                        self.stats["cow_forks"] += 1
                    if start:
                        self.stats["prefix_hits"] += 1
                        self.stats["prefix_hit_tokens"] += start
                else:
                    blocks = self.allocator.alloc(total)
                    if blocks is None:
                        break   # FCFS: the queue head waits for blocks
                self._slot_blocks[slot] = blocks
                row = blocks + [NULL_BLOCK] * (self.blocks_per_slot
                                               - len(blocks))
                self._tables[slot] = row
                self._tables_dirty = True
            self.queue.pop(0)
            toks = jnp.asarray(prompt + [0] * (self.max_len - plen),
                               jnp.int32)
            topo_row = jnp.zeros((N_REGS,), jnp.int32)
            if self.fabric is not None:
                topo_row = jnp.asarray(self._fleet_rows[req.model], jnp.int32)
            sp = req.sampling or self.sampling
            temp, top_k, top_p = sp.as_arrays()
            self.state = self._admit_chunk(
                self.state, jnp.int32(slot), toks, jnp.int32(plen),
                jnp.int32(budget),
                jnp.int32(-1 if req.eos_id is None else req.eos_id),
                temp, top_k, top_p, topo_row, jnp.int32(start))
            req.slot = slot
            self.slot_req[slot] = req
            self._plen[slot] = plen
            self._budget[slot] = budget
            # the scheduler's mirrors start at the cached span: the token
            # budget is charged only for the uncached suffix
            self._idx_ub[slot] = start
            self._pf[slot] = start
            self._reg_done[slot] = False
            self._seq += 1
            self._admit_seq[slot] = self._seq
            self._emit("admit", req.uid, slot=slot, cached_tokens=start)

    def _grant_chunks(self) -> list[int]:
        """The token-budget scheduler: up to ``token_budget`` prompt
        tokens per fused step, at most ``chunk_size`` per slot, split
        fairly across the prefilling slots (decode lanes ride along for
        free).  The fair share is what kills head-of-line blocking: a
        long prompt cannot monopolize the budget, so a short prompt
        admitted beside it still completes its prefill in one or two
        steps.  Leftover budget goes FCFS by admission order.  Pure host
        arithmetic over exact mirrors — no device read."""
        grants = [0] * self.max_batch
        order = [s for s in sorted(self._occupied(),
                                   key=lambda t: self._admit_seq[t])
                 if self._pf[s] < self._plen[s]]
        if not order:
            return grants
        share = max(min(self.token_budget // len(order), self.chunk_size), 1)
        left = self.token_budget
        for cap in (share, self.chunk_size):   # fair pass, then leftovers
            for slot in order:
                rem = self._plen[slot] - self._pf[slot] - grants[slot]
                g = min(cap - grants[slot], rem, left)
                if g <= 0:
                    continue
                grants[slot] += g
                left -= g
        return grants

    def _occupied(self) -> list[int]:
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    def _namespace(self, model: int):
        """Prefix-trie namespace of one request's KV blocks.  Fleet
        members share the physical pool but never a trie chain — a
        prompt's KV is a function of the model that prefilled it."""
        if self.fabric is not None:
            return self.fabric.cache_namespace(self.fleet[model], model)
        return 0

    # -- paged block budgeting ----------------------------------------
    def _alloc_blocks(self, n: int) -> list[int] | None:
        """``allocator.alloc`` with the LRU eviction tier behind it: when
        the free list cannot cover ``n``, parked (unreferenced but
        trie-cached) blocks are evicted oldest-first to make room before
        the caller falls back to preempting live requests."""
        got = self.allocator.alloc(n)
        if got is None and self.prefix_cache is not None:
            freed = self.prefix_cache.evict(n - self.allocator.num_free)
            if freed:
                self.stats["prefix_evictions"] += freed
                got = self.allocator.alloc(n)
        return got

    def _slot_token_cap(self, slot: int) -> int:
        """Most cache positions this slot can ever need (then it finishes)."""
        return min(self._plen[slot] + self._budget[slot] - 1, self.max_len)

    @_span("engine.capacity")
    def _ensure_capacity(self, horizon: int) -> None:
        """Pre-reserve blocks so the next ``horizon`` fused steps cannot
        write outside a slot's blocks (the fused step itself never talks
        to the allocator).  Oldest slots are served first; when the pool
        runs dry the most recently admitted slot is preempted."""
        if self.paging is None:
            return
        bs = self.paging.block_size
        # a speculative step writes up to spec_horizon (= k+1) verify
        # positions per fused step instead of 1, so the reservation
        # window scales with it (over-reserved tails are reclaimed at
        # the next sync by _truncate_slot_blocks)
        h = horizon * self.spec_horizon
        for slot in sorted(self._occupied(),
                           key=lambda s: self._admit_seq[s]):
            if self.slot_req[slot] is None:   # preempted by an earlier turn
                continue
            if self._pf[slot] < self._plen[slot]:
                # a mid-prefill slot owns its prompt's blocks already; it
                # needs >= 1 step to finish the prompt, so it can write at
                # most horizon - 1 decode tokens on top within the window
                need_tokens = min(self._plen[slot] + h - 1,
                                  self._slot_token_cap(slot))
            else:
                need_tokens = min(self._idx_ub[slot] + h,
                                  self._slot_token_cap(slot))
            missing = blocks_for_tokens(need_tokens, bs) \
                - len(self._slot_blocks[slot])
            while missing > 0:
                got = self._alloc_blocks(missing)
                if got is not None:
                    n_have = len(self._slot_blocks[slot])
                    self._slot_blocks[slot] += got
                    row = self._tables[slot]
                    row[n_have:n_have + len(got)] = got
                    self._tables_dirty = True
                    break
                victims = [s for s in self._occupied() if s != slot]
                if not victims:
                    raise RuntimeError(
                        f"paged pool exhausted: {missing} more blocks needed "
                        f"for slot {slot} with no other slot to preempt — "
                        f"num_blocks={self.paging.num_blocks} cannot hold one "
                        "full request; increase num_blocks")
                self._preempt(max(victims, key=lambda s: self._admit_seq[s]))

    def _release_slot_blocks(self, slot: int) -> None:
        """Release a slot's blocks and null out its table row.

        With prefix caching this is a *decref*, not a free: blocks other
        requests still map just lose one reference, blocks the trie owns
        are parked in the LRU tier at refcount zero, and only unshared,
        uncached blocks return to the free list."""
        if self.prefix_cache is not None:
            zeros = self.allocator.decref(self._slot_blocks[slot])
            self.allocator.free(self.prefix_cache.park(zeros))
        else:
            self.allocator.free(self._slot_blocks[slot])
        self._slot_blocks[slot] = []
        self._tables[slot] = [NULL_BLOCK] * self.blocks_per_slot
        self._tables_dirty = True
        self._reg_done[slot] = False

    def _truncate_slot_blocks(self, slot: int, keep_tokens: int) -> None:
        """Roll back a slot's block tail after rejected speculation: the
        dispatch loop reserved ``spec_horizon`` positions per step but
        the accepted length is only known at the sync, so blocks past
        the last resident token are handed back through the decref-aware
        ``BlockAllocator.truncate`` — still-shared blocks just lose one
        reference, trie-owned blocks park in the LRU tier (never free:
        another request's prefix may gather from them), and only
        private, uncached blocks return to the free list.  The table
        tail is nulled so the next verify pass's masked overrun writes
        land in the null block, never a reassigned one."""
        keep = blocks_for_tokens(keep_tokens, self.paging.block_size)
        blocks = self._slot_blocks[slot]
        if keep >= len(blocks):
            return
        kept, zeros = self.allocator.truncate(blocks, keep)
        if self.prefix_cache is not None:
            zeros = self.prefix_cache.park(zeros)
        self.allocator.free(zeros)
        self._slot_blocks[slot] = kept
        row = self._tables[slot]
        row[keep:] = [NULL_BLOCK] * (self.blocks_per_slot - keep)
        self._tables_dirty = True

    def _preempt(self, slot: int) -> None:
        """Recompute-preemption: bank the slot's generated tokens, free its
        blocks, and push the request back to the queue head — it resumes
        by re-entering the scheduler with prompt+banked tokens (greedy
        streams are unchanged; the request keeps its uid and budget).  A
        slot preempted *mid-prefill* has banked nothing and simply
        restarts its chunk sequence from the prompt head."""
        req = self.slot_req[slot]
        # ONE bulk device_get for the whole bank (count + tokens), sliced
        # host-side: the per-slot count-then-buffer pair used to cost two
        # blocking syncs per preemption (RA005).  The transfer is bounded
        # by the host-known budget mirror, never max_len columns.
        cap = min(self._budget[slot], self.max_len)
        cnt_d, row = jax.device_get(
            (self.state.count[slot], self.state.buf[slot, :cap]))
        self.stats["device_gets"] += 1
        cnt = int(cnt_d)
        if cnt > 0:
            self.stats["harvest_elems"] += cnt
            req.prefix = req.prefix + [int(t) for t in row[:cnt]]
        self.state = self._evict_slot(self.state, jnp.int32(slot))
        if self.paging is not None:
            self._release_slot_blocks(slot)
        self.slot_req[slot] = None
        self._pf[slot] = 0
        req.slot = None
        self.queue.insert(0, req)
        self.stats["preemptions"] += 1
        self._emit("preempt", req.uid, banked=len(req.prefix))

    def _dispatch(self) -> None:
        """Launch one step program: the mixed step when some prompt has a
        chunk granted, else the one-lane decode step (the bucketed
        scheduler prefills at admission, so all its steps decode)."""
        with TraceAnnotation("engine.dispatch") as span:
            if self.paging is not None and self._tables_dirty:
                # host-built step inputs go straight to this replica's
                # placement, like every other operand of the step
                self.block_tables = jax.device_put(
                    np.asarray(self._tables, np.int32), self._placement)
                self._tables_dirty = False
            grants = (self._grant_chunks() if self.scheduler == "chunked"
                      else [0] * self.max_batch)
            granted = sum(grants)
            program = "mixed" if granted else "decode"
            span.set_metadata(program=program)
            # under speculation the draft rides inside the same dispatch:
            # the jitted step takes (target, draft) pairs for params and
            # cache, and the donated tuple comes back the same shape
            params: object = self.params
            cache: object = self.cache
            if self.speculation is not None:
                params = (self.params, self.draft_params)
                cache = (self.cache, self.draft_cache)
            if granted:
                cache, self.state = self._step(
                    params, cache, self.state, self.block_tables,
                    jax.device_put(np.asarray(grants, np.int32),
                                   self._placement))
            else:
                # steady state (no prompt work anywhere): the one-lane
                # fused decode is the W == 1 special case of the mixed
                # step — same math, same rng schedule, ~chunk_size x less
                # query compute.  Still exactly one dispatch per step.
                cache, self.state = self._decode(
                    params, cache, self.state, self.block_tables)
            if self.speculation is not None:
                self.cache, self.draft_cache = cache
            else:
                self.cache = cache
            self.stats["decode_steps"] += 1
            self._unsynced += 1
            self.stats["max_step_prefill_tokens"] = max(
                self.stats["max_step_prefill_tokens"], granted)
            occ = self._occupied()
            if self.events.active:
                # a decoding slot computes spec_horizon lanes (1 without
                # speculation); a prefilling slot its grant
                n_dec = sum(not grants[s] and self._pf[s] >= self._plen[s]
                            for s in occ)
                width = self.chunk_size if granted else self.spec_horizon
                t_max = self.max_len if self.paging is None \
                    else self.blocks_per_slot * self.paging.block_size
                self._emit("dispatch", -1, program=program, slots=len(occ),
                           prefill_tokens=granted,
                           lanes=self.max_batch * width,
                           live_lanes=granted + n_dec * self.spec_horizon,
                           keys=len(occ) * t_max,
                           live_keys=sum(live_keys(self._idx_ub[s], width,
                                                   t_max) for s in occ))
            for slot in occ:
                if grants[slot]:
                    self._pf[slot] += grants[slot]
                    self._idx_ub[slot] = self._pf[slot]
                    if self._pf[slot] >= self._plen[slot]:
                        # this dispatch's completing chunk sampled the
                        # slot's first token (``completes`` in the step)
                        self._emit_first_token(self.slot_req[slot].uid)
                elif self._pf[slot] >= self._plen[slot]:
                    # a speculative step can land up to k+1 tokens; the
                    # mirror is an upper bound until the next sync
                    self._idx_ub[slot] = min(
                        self._idx_ub[slot] + self.spec_horizon,
                        self._slot_token_cap(slot))
            if self.prefix_cache is not None:
                self._register_prefixes()

    def _register_prefixes(self) -> None:
        """Register every slot whose prefill just completed: its whole
        prompt blocks enter the trie (existing chains win — the slot's
        duplicate block simply stays private and is freed at release).
        One-shot per occupancy; registration happens right after the
        completing dispatch, so any later reader's gather is ordered
        behind the writes by the device queue itself."""
        bs = self.paging.block_size
        for slot in self._occupied():
            if self._reg_done[slot] or self._pf[slot] < self._plen[slot]:
                continue
            req = self.slot_req[slot]
            tokens = req.prompt + req.prefix
            n_full = len(tokens) // bs
            if n_full:
                self.prefix_cache.insert(self._namespace(req.model), tokens,
                                         self._slot_blocks[slot][:n_full])
            self._reg_done[slot] = True

    @_span("engine.harvest")
    def _harvest(self) -> list[Request]:
        """One bulk device_get of the done/count vectors; token buffers are
        pulled (one more bulk get) only for slots that actually finished,
        sliced to the longest finished stream — the transfer scales with
        the tokens produced, not with max_len."""
        st = self.state
        with TraceAnnotation("engine.harvest.wait"):
            done_h, count_h, acc_h, ss_h, ex_h = jax.device_get(
                (st.done, st.count,
                 *((st.acc, st.spec_steps) if self.speculation is not None
                   else (None, None)), st.experts))
        self.stats["device_gets"] += 1
        if ex_h is not None:
            # totals since the last harvest (int32 on the device: the
            # difference is taken modulo 2**32)
            rows, hit = ((int(t) - s) % 2**32
                         for t, s in zip(ex_h, self._experts_seen))
            self._experts_seen = [int(t) for t in ex_h]
            self._emit("experts", -1, expert_rows=rows, experts_hit=hit,
                       dispatches=self._unsynced)
        self._unsynced = 0
        occ = self._occupied()
        slots = [i for i in occ if done_h[i]]
        for i in occ:   # sync point: tighten the index upper bounds
            if self._pf[i] < self._plen[i]:
                self._idx_ub[i] = self._pf[i]   # mid-prefill: mirror exact
            else:
                self._idx_ub[i] = self._plen[i] + max(int(count_h[i]) - 1, 0)
                if (self.speculation is not None and self.paging is not None
                        and not done_h[i]):
                    # speculative rollback, host half: the dispatch loop
                    # reserved spec_horizon positions/step; now that the
                    # exact resident length is known, hand the rejected
                    # tail's blocks back (shared ones park, never free)
                    self._truncate_slot_blocks(i, self._idx_ub[i])
            # completion-honest telemetry: the device_get above ordered
            # this sync behind the dispatched steps, so these counts (and
            # their wall stamps) reflect tokens that actually exist
            if acc_h is not None:
                self._emit("progress", self.slot_req[i].uid,
                           count=int(count_h[i]), accepted=int(acc_h[i]),
                           spec_steps=int(ss_h[i]))
            else:
                self._emit("progress", self.slot_req[i].uid,
                           count=int(count_h[i]))
        if not slots:
            return []
        maxc = max(int(count_h[i]) for i in slots)
        with TraceAnnotation("engine.harvest.fetch"):
            bufs = jax.device_get(
                self.state.buf[jnp.asarray(slots, jnp.int32), :maxc])
        self.stats["device_gets"] += 1
        self.stats["harvest_elems"] += len(slots) * maxc
        finished = []
        for row, i in zip(bufs, slots):
            req = self.slot_req[i]
            req.generated = req.prefix + [int(t) for t in row[:count_h[i]]]
            req.done = True
            if acc_h is not None:
                self.stats["spec_accepted"] += int(acc_h[i])
                self.stats["spec_steps"] += int(ss_h[i])
            self.slot_req[i] = None
            if self.paging is not None:
                self._release_slot_blocks(i)
            finished.append(req)
            self._emit("finish", req.uid, n_generated=len(req.generated))
        return finished

    def step(self) -> list[Request]:
        """Admit waiting requests, advance every active slot one token.
        Returns requests completed this step."""
        with TraceAnnotation("engine.step", step=self.stats["decode_steps"]):
            self._admit()
            if not self._occupied():
                return []
            self._ensure_capacity(1)
            self._dispatch()
            return self._harvest()

    def run_to_completion(self, max_steps: int = 10_000,
                          sync_every: int = 1) -> list[Request]:
        """Drain queue + slots.  ``sync_every=k`` dispatches k fused steps
        back-to-back before each harvest sync (admission and block
        reservation also happen at sync points, so large k trades
        slot-refill latency for zero host reads in steady state)."""
        done: list[Request] = []
        steps = 0
        while steps < max_steps:
            self._admit()
            if not self._occupied():
                break
            window = min(max(1, sync_every), max_steps - steps)
            self._ensure_capacity(window)
            for _ in range(window):
                self._dispatch()
                steps += 1
            done += self._harvest()
        return done

    @property
    def compilations(self) -> _Compilations:
        """Compile-count accounting (the Alg. 18 amortization claim).

        ``"prefill"``/``"decode"`` count the compilations serving each
        role.  Under the chunked scheduler both name the ONE fused mixed
        step — prefill stopped being a separate program.
        ``"prefill_buckets"`` is the legacy bucketed count and stays 0
        under the chunked scheduler; readers of it should migrate to
        ``compilations()["prefill"]``.
        """
        buckets = len(self._prefill)
        if self.scheduler == "chunked":
            n = self._step._cache_size()
            # the one-lane steady-state decode program may never compile
            # (workloads that always carry prompt work); the mixed step
            # is then the only program decoding
            return _Compilations(decode=self._decode._cache_size() or n,
                                 prefill=n, prefill_buckets=buckets)
        return _Compilations(decode=self._decode._cache_size(),
                             prefill=buckets, prefill_buckets=buckets)

    def memory_stats(self) -> FragmentationStats:
        """Pool occupancy + fragmentation (paged layout only).  Exact at
        sync points; between syncs resident tokens are an upper bound."""
        if self.paging is None:
            raise ValueError("memory_stats requires cache_layout='paged'")
        self.allocator.set_used_tokens(
            sum(self._idx_ub[i] for i in self._occupied()))
        return self.allocator.stats()
