"""The model zoo facade: one ``Model`` class interpreting any ArchConfig.

Families: dense / vlm (dense + stub patch embeddings) / moe (+MLA, MTP) /
ssm (mamba-1) / hybrid (RG-LRU + local attention) / audio (whisper enc-dec,
stub frame embeddings) / encoder (the paper's own BERT-style networks).

Layout discipline:
* Homogeneous layer stacks are *stacked* (leading layer dim) and driven by
  ``lax.scan`` — compact HLO at 80 layers, remat-friendly.
* Heterogeneous stacks (hybrid pattern, MoE dense prefix) unroll in Python.
* Every parameter is created through ``ParamBuilder`` so the same code
  yields real arrays, ShapeDtypeStructs (dry-run) or logical
  PartitionSpecs (sharding) — ADAPTOR's synthesis/runtime split.

Decode: ``init_cache`` + ``decode_step`` implement one-new-token serving
with per-family state (KV / MLA latent / SSM / rolling window).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import masking
from repro.core.kv_quant import CacheCodec
from repro.core.paging import PagingConfig, pool_row
from repro.core.spec import CHUNKABLE_FAMILIES, KV_QUANTIZABLE_FAMILIES
from repro.distributed.sharding import constrain
from repro.models import attention as attn
from repro.models import backend
from repro.models import layers, moe, rglru, ssm
from repro.models.attention import KVCache, MLACache
from repro.models.params import ParamBuilder


def _is_causal(cfg: ArchConfig) -> bool:
    return cfg.family != "encoder"


def _with_backend(fn):
    """Run a model entry point under its configured matmul backend (the
    routing is read at trace time, so jitted callers bake it in)."""
    @functools.wraps(fn)
    def wrapped(self, *args, **kwargs):
        with self._mm_ctx():
            return fn(self, *args, **kwargs)
    return wrapped


@dataclasses.dataclass(frozen=True)
class ModelOptions:
    """Build-time execution options (the 'synthesis parameters')."""

    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat: str = "none"  # none | full  (per-layer rematerialization)
    mtp_loss_weight: float = 0.3
    moe_aux_weight: float = 0.01
    # Unroll layer stacks into straight-line HLO instead of lax.scan.
    # Needed by the dry-run: XLA's cost_analysis counts a while-loop body
    # once, not x trip-count, so scanned-layer FLOPs/bytes/collectives
    # would be undercounted by ~num_layers.
    unroll_layers: bool = False
    # Decode attention: GQA-grouped contraction (no repeat_kv copy of the
    # KV cache to the full head count) — §Perf optimization.
    grouped_gqa: bool = False
    # Matmul routing for every dense in this model: "xla" (default) or
    # "pallas" (the ADAPTOR tiled kernels; int8 weights take the C6
    # int8_matmul path).  Applied at trace time, so jitted callers bake
    # the choice into their compiled executable.
    matmul_backend: str = "xla"
    # Paged attention: "gather" (XLA block-table gather + the dense
    # contraction, bit-identical to the dense layout) or "pallas" (fused
    # kernels with the gather folded into the online-softmax loop: the
    # paged-decode kernel at one lane a slot, the chunked-prefill kernel
    # above it).  Only consulted when a step receives block tables.
    paged_attn_impl: str = "gather"
    # KV-cache storage codec: "compute" (bf16 values, historical) or
    # "int8" (quantize-on-write with per-row f32 scales; see
    # core.kv_quant).  Lowered from MemorySpec.kv_dtype by from_spec.
    kv_dtype: str = "compute"

    @classmethod
    def from_execution(cls, ex, memory=None) -> "ModelOptions":
        """Lower a ``core.spec.ExecutionSpec`` (and optionally the
        ``MemorySpec`` holding the cache codec) onto the zoo's build-time
        options — the one place the vocabularies meet."""
        return cls(param_dtype=ex.param_dtype,
                   compute_dtype=ex.compute_dtype,
                   grouped_gqa=ex.grouped_gqa,
                   matmul_backend=ex.matmul_backend,
                   paged_attn_impl=ex.paged_attn_impl,
                   kv_dtype="compute" if memory is None else memory.kv_dtype)


class Model:
    def __init__(self, cfg: ArchConfig, options: ModelOptions | None = None):
        self.cfg = cfg
        self.opt = options or ModelOptions()

    @classmethod
    def from_spec(cls, spec) -> "Model":
        """Build the zoo model a ``core.spec.RuntimeSpec`` describes; every
        execution knob is read from ``spec.execution`` (single source),
        the cache codec from ``spec.memory.kv_dtype``."""
        return cls(spec.arch, ModelOptions.from_execution(spec.execution,
                                                          spec.memory))

    @property
    def codec(self) -> CacheCodec:
        """The cache codec this model's decode state uses."""
        return CacheCodec(self.opt.kv_dtype)

    def _mm_ctx(self):
        if self.opt.matmul_backend != "xla":
            return backend.use(self.opt.matmul_backend)
        return contextlib.nullcontext()

    # ------------------------------------------------------------------
    # Parameter construction (init / abstract / axes via ParamBuilder)
    # ------------------------------------------------------------------
    def build(self, b: ParamBuilder) -> dict:
        cfg = self.cfg
        p: dict[str, Any] = {"embed": layers.build_embedding(b, cfg.vocab_size,
                                                             cfg.d_model)}
        if cfg.positional == "learned":
            p["pos_embed"] = {"table": b.param(
                (cfg.max_position_embeddings, cfg.d_model), ("pos", "embed"))}
        if not cfg.tie_embeddings:
            p["lm_head"] = {"table": b.param(
                (cfg.vocab_size, cfg.d_model), ("vocab", "embed"))}
        p["final_norm"] = layers.build_norm(b, cfg.d_model, cfg.norm)

        if cfg.family == "ssm":
            with b.stacked(cfg.num_layers):
                p["layers"] = self._build_ssm_layer(b)
        elif cfg.family == "hybrid":
            p["layers"] = [self._build_hybrid_layer(b, kind)
                           for kind in self._hybrid_kinds()]
        elif cfg.family == "moe":
            k = cfg.moe.first_k_dense
            if k:
                dense_cfg = dataclasses.replace(cfg, d_ff=cfg.moe.dense_d_ff)
                p["dense_prefix"] = [self._build_dense_layer(b, dense_cfg)
                                     for _ in range(k)]
            with b.stacked(cfg.num_layers - k):
                p["layers"] = self._build_moe_layer(b)
            if cfg.num_mtp_modules:
                p["mtp"] = self._build_mtp(b)
        elif cfg.encdec is not None:
            with b.stacked(cfg.encdec.num_encoder_layers):
                p["enc_layers"] = self._build_dense_layer(b, cfg, causal=False)
            with b.stacked(cfg.num_layers):
                p["layers"] = self._build_cross_layer(b)
            p["enc_final_norm"] = layers.build_norm(b, cfg.d_model, cfg.norm)
            p["enc_pos_embed"] = {"table": b.param(
                (cfg.encdec.encoder_seq_len, cfg.d_model), ("pos", "embed"))}
        else:  # dense / vlm / encoder
            with b.stacked(cfg.num_layers):
                p["layers"] = self._build_dense_layer(b, cfg)
        return p

    def _build_attn(self, b, cfg: ArchConfig) -> dict:
        if cfg.mla is not None:
            return attn.build_mla(b, cfg)
        return attn.build_gqa(b, cfg)

    def _build_dense_layer(self, b, cfg: ArchConfig, causal: bool = True) -> dict:
        use_bias = cfg.norm == "layernorm"  # paper-style FFN carries biases
        return {
            "ln1": layers.build_norm(b, cfg.d_model, cfg.norm),
            "attn": self._build_attn(b, cfg),
            "ln2": layers.build_norm(b, cfg.d_model, cfg.norm),
            "ffn": moe.build_ffn(b, cfg, cfg.d_ff, use_bias=use_bias),
        }

    def _build_moe_layer(self, b) -> dict:
        cfg = self.cfg
        return {
            "ln1": layers.build_norm(b, cfg.d_model, cfg.norm),
            "attn": self._build_attn(b, cfg),
            "ln2": layers.build_norm(b, cfg.d_model, cfg.norm),
            "moe": moe.build_moe(b, cfg),
        }

    def _build_ssm_layer(self, b) -> dict:
        cfg = self.cfg
        return {"ln": layers.build_norm(b, cfg.d_model, cfg.norm),
                "ssm": ssm.build_ssm(b, cfg)}

    def _hybrid_kinds(self) -> list[str]:
        pat = self.cfg.hybrid.pattern
        return [pat[i % len(pat)] for i in range(self.cfg.num_layers)]

    def _build_hybrid_layer(self, b, kind: str) -> dict:
        cfg = self.cfg
        p = {"ln1": layers.build_norm(b, cfg.d_model, cfg.norm),
             "ln2": layers.build_norm(b, cfg.d_model, cfg.norm),
             "ffn": moe.build_ffn(b, cfg, cfg.d_ff)}
        if kind == "r":
            p["rglru"] = rglru.build_rglru(b, cfg)
        else:
            p["attn"] = attn.build_gqa(b, cfg)
        return p

    def _build_cross_layer(self, b) -> dict:
        cfg = self.cfg
        return {
            "ln1": layers.build_norm(b, cfg.d_model, cfg.norm),
            "attn": self._build_attn(b, cfg),
            "ln_cross": layers.build_norm(b, cfg.d_model, cfg.norm),
            "cross": attn.build_gqa(b, cfg),
            "ln2": layers.build_norm(b, cfg.d_model, cfg.norm),
            "ffn": moe.build_ffn(b, cfg, cfg.d_ff,
                                 use_bias=cfg.norm == "layernorm"),
        }

    def _build_mtp(self, b) -> dict:
        cfg = self.cfg
        return {"proj": layers.build_dense(b, 2 * cfg.d_model, cfg.d_model,
                                           ("embed", "embed")),
                "norm_h": layers.build_norm(b, cfg.d_model, cfg.norm),
                "norm_e": layers.build_norm(b, cfg.d_model, cfg.norm),
                "layer": self._build_moe_layer(b)}

    def init(self, rng: jax.Array) -> dict:
        return self.build(ParamBuilder("init", rng, self.opt.param_dtype))

    def abstract(self) -> dict:
        return self.build(ParamBuilder("abstract", dtype=self.opt.param_dtype))

    def axes(self) -> dict:
        return self.build(ParamBuilder("axes", dtype=self.opt.param_dtype))

    # ------------------------------------------------------------------
    # Layer bodies
    # ------------------------------------------------------------------
    def _maybe_remat(self, f):
        if self.opt.remat == "full":
            return jax.checkpoint(f)
        if self.opt.remat == "dots":
            # save matmul outputs: no recompute of attention/FFN/dispatch
            return jax.checkpoint(
                f, policy=jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable)
        return f

    def _run_stack(self, body, x, stacked):
        """Scan over stacked layer params, or unroll (dry-run mode).
        ``body(x, layer_params) -> (x, None)``."""
        if not self.opt.unroll_layers:
            return jax.lax.scan(body, x, stacked)[0]
        n = jax.tree.leaves(stacked)[0].shape[0]
        for i in range(n):
            x, _ = body(x, jax.tree.map(lambda l, i=i: l[i], stacked))
        return x

    def _run_stack_cache(self, body, x, stacked, cache):
        """Layer loop threading a per-layer cache; scan or unrolled."""
        if not self.opt.unroll_layers:
            return jax.lax.scan(body, x, (stacked, cache))
        n = jax.tree.leaves(stacked)[0].shape[0]
        outs = []
        for i in range(n):
            x, c = body(x, (jax.tree.map(lambda l, i=i: l[i], stacked),
                            jax.tree.map(lambda l, i=i: l[i], cache)))
            outs.append(c)
        return x, jax.tree.map(lambda *ls: jnp.stack(ls), *outs)

    def _run_stack_collect(self, body, x, stacked):
        """Layer loop collecting a per-layer output (prefill caches)."""
        if not self.opt.unroll_layers:
            return jax.lax.scan(body, x, stacked)
        n = jax.tree.leaves(stacked)[0].shape[0]
        outs = []
        for i in range(n):
            x, c = body(x, jax.tree.map(lambda l, i=i: l[i], stacked))
            outs.append(c)
        return x, jax.tree.map(lambda *ls: jnp.stack(ls), *outs)

    def _run_cache_layers(self, attend, x, params, cache, paged: bool,
                          live: jax.Array | None = None):
        """Layer loop of the attention-cache decode and mixed steps;
        ``attend(hn, attn_params, cache, layer) -> (o, cache)``.  Returns
        ``(x, cache, counts)``: ``counts`` is int32 [expert rows, experts
        hit] summed over the MoE layers (``moe.apply_moe``, which routes
        only the lanes ``live`` [B, W] marks), None without experts.

        ``paged``: the whole layer-stacked pool rides the loop carry and
        each layer writes and reads its own rows through its layer
        index, so no per-layer pool array is sliced out, copied or
        re-stacked and the donated pool aliases the output.  The MoE
        dense prefix holds layers ``0..k-1`` of the same pool and the
        stacked main layers follow; ``unroll_layers`` runs the same body
        with static indices.

        Dense ``[L, B, S]`` rows instead go through the scan as per-layer
        ``xs``/``ys`` slices (``layer`` None): carried, a ``[.., kv, 64]``
        row is copied whole in and out of the loop at 2x padding, which
        does not fit qwen1.5-0.5b's 32 x 2048 serving cache on one v5e."""
        cfg = self.cfg

        def body(h, lp, c, layer, n, moe_layer=None):
            hn = layers.apply_norm(h, lp["ln1"], cfg.norm)
            o, c = attend(hn, lp["attn"], c, layer)
            h = h + o
            hn = layers.apply_norm(h, lp["ln2"], cfg.norm)
            if "moe" in lp:
                y, got = moe.apply_moe(hn, lp["moe"], cfg, live, moe_layer)
                return h + y, c, n + got
            return h + moe.apply_ffn(hn, lp["ffn"], cfg.activation), c, n

        prefix = params.get("dense_prefix", [])
        stacked = params["layers"]
        n_ex = None if cfg.moe is None else jnp.zeros((2,), jnp.int32)
        if not paged:
            return self._run_prefix_then_stack(body, x, prefix, stacked,
                                               cache, n_ex)
        for i, lp in enumerate(prefix):
            x, cache, n_ex = body(x, lp, cache, i, n_ex)
        n = jax.tree.leaves(stacked)[0].shape[0]
        if self.opt.unroll_layers:
            for i in range(n):
                x, cache, n_ex = body(
                    x, jax.tree.map(lambda l, i=i: l[i], stacked), cache,
                    len(prefix) + i, n_ex)
            return x, cache, n_ex

        # the experts' weights stay whole outside the scan's slices: the
        # grouped matmul kernel reads one layer's in place
        whole = {k: v for k, v in stacked.get("moe", {}).items()
                 if k in moe.EXPERT_WEIGHTS}
        if whole:
            stacked = dict(stacked, moe={k: v for k, v in
                                         stacked["moe"].items()
                                         if k not in whole})

        def step(carry, inp):
            (h, c, got), (lp, layer) = carry, inp
            if whole:
                lp = dict(lp, moe=dict(lp["moe"], **whole))
                return body(h, lp, c, layer, got, layer - len(prefix)), None
            return body(h, lp, c, layer, got), None

        ids = jnp.arange(n, dtype=jnp.int32) + len(prefix)
        (x, cache, n_ex), _ = jax.lax.scan(step, (x, cache, n_ex),
                                           (stacked, ids))
        return x, cache, n_ex

    def _run_prefix_then_stack(self, body, x, prefix, stacked, cache, n_ex):
        """Dense-cache layer loop: the unrolled MoE dense prefix layers
        hold their own cache slices at the front of the stacked cache,
        the scanned main stack follows, and the prefix caches are
        re-stacked on the way out."""
        def step(carry, inp):
            h, c, got = body(carry[0], *inp, None, carry[1])
            return (h, got), c

        if not prefix:
            (x, n_ex), new = self._run_stack_cache(step, (x, n_ex), stacked,
                                                   cache)
            return x, new, n_ex
        new_pref = []
        for i, lp in enumerate(prefix):
            x, c, n_ex = body(x, lp, jax.tree.map(lambda l, i=i: l[i], cache),
                              None, n_ex)
            new_pref.append(c)
        (x, n_ex), new_main = self._run_stack_cache(
            step, (x, n_ex), stacked,
            jax.tree.map(lambda l: l[len(prefix):], cache))
        stacked_pref = jax.tree.map(lambda *ls: jnp.stack(ls), *new_pref)
        return x, jax.tree.map(lambda a, b_: jnp.concatenate([a, b_]),
                               stacked_pref, new_main), n_ex

    def _dense_body(self, x, lp, positions, causal, window=None):
        cfg = self.cfg
        # re-pin the scan carry: GSPMD propagation through while loops
        # otherwise drops the batch sharding (see DESIGN.md §7).  Under a
        # sequence-parallel strategy "seq" resolves to the TP axis and the
        # residual stream stays token-sharded between blocks (Megatron-SP:
        # the TP all-reduce splits into reduce-scatter + bf16 all-gather).
        x = constrain(x, ("batch", "seq", None))
        h = layers.apply_norm(x, lp["ln1"], cfg.norm)
        if cfg.mla is not None:
            h = attn.mla_attention(h, lp["attn"], cfg, positions=positions)
        else:
            h = attn.gqa_attention(h, lp["attn"], cfg, positions=positions,
                                   causal=causal, window=window)
        x = x + h
        h = layers.apply_norm(x, lp["ln2"], cfg.norm)
        if "moe" in lp:
            h = moe.apply_moe(h, lp["moe"], cfg)[0]
        else:
            h = moe.apply_ffn(h, lp["ffn"], cfg.activation)
        return x + h

    def _ssm_body(self, x, lp):
        x = constrain(x, ("batch", None, None))
        h = layers.apply_norm(x, lp["ln"], self.cfg.norm)
        return x + ssm.ssm_forward(h, lp["ssm"], self.cfg)

    def _hybrid_body(self, x, lp, kind, positions):
        cfg = self.cfg
        x = constrain(x, ("batch", None, None))
        h = layers.apply_norm(x, lp["ln1"], cfg.norm)
        if kind == "r":
            h = rglru.rglru_forward(h, lp["rglru"], cfg)
        else:
            h = attn.gqa_attention(h, lp["attn"], cfg, positions=positions,
                                   causal=True,
                                   window=cfg.hybrid.attention_window)
        x = x + h
        h = layers.apply_norm(x, lp["ln2"], cfg.norm)
        return x + moe.apply_ffn(h, lp["ffn"], cfg.activation)

    def _cross_body(self, x, lp, positions, enc_kv):
        cfg = self.cfg
        x = constrain(x, ("batch", None, None))
        h = layers.apply_norm(x, lp["ln1"], cfg.norm)
        h = attn.gqa_attention(h, lp["attn"], cfg, positions=positions,
                               causal=True)
        x = x + h
        h = layers.apply_norm(x, lp["ln_cross"], cfg.norm)
        x = x + self._cross_attend(h, lp["cross"], enc_kv)
        h = layers.apply_norm(x, lp["ln2"], cfg.norm)
        return x + moe.apply_ffn(h, lp["ffn"], cfg.activation)

    def _cross_attend(self, h, cp, enc_kv):
        """Cross-attention: queries from decoder, K/V precomputed from encoder."""
        cfg = self.cfg
        b_, s, _ = h.shape
        hd = cfg.resolved_head_dim
        q = layers.apply_dense(h, cp["wq"]).reshape(b_, s, cfg.num_heads, hd)
        k, v = enc_kv
        n_rep = cfg.num_heads // max(cfg.num_kv_heads, 1)
        k, v = attn.repeat_kv(k, n_rep), attn.repeat_kv(v, n_rep)
        o = attn.full_attention(q, k, v, causal=False)
        return layers.apply_dense(o.reshape(b_, s, cfg.num_heads * hd), cp["wo"])

    def _cross_kv(self, cp, enc_out):
        cfg = self.cfg
        b_, se, _ = enc_out.shape
        hd = cfg.resolved_head_dim
        k = layers.apply_dense(enc_out, cp["wk"]).reshape(b_, se, cfg.num_kv_heads, hd)
        v = layers.apply_dense(enc_out, cp["wv"]).reshape(b_, se, cfg.num_kv_heads, hd)
        return k, v

    # ------------------------------------------------------------------
    # Embedding / positions
    # ------------------------------------------------------------------
    def _embed_inputs(self, params, batch, q_offset: int = 0):
        cfg = self.cfg
        tokens = batch["tokens"]
        x = layers.embed(tokens, params["embed"], self.opt.compute_dtype)
        b_, s = tokens.shape
        positions = jnp.arange(s, dtype=jnp.int32)[None, :] + q_offset
        if cfg.positional == "learned":
            idx = jnp.minimum(positions, cfg.max_position_embeddings - 1)
            x = x + params["pos_embed"]["table"].astype(x.dtype)[idx[0]][None]
        if cfg.frontend is not None and cfg.encdec is None and "frontend" in batch:
            # stub vision frontend: first num_tokens positions carry the
            # precomputed patch embeddings (audio frontends feed the encoder)
            fe = batch["frontend"].astype(x.dtype)
            n = fe.shape[1]
            mask = (jnp.arange(s) < n)[None, :, None]
            fe_pad = jnp.pad(fe, ((0, 0), (0, max(s - n, 0)), (0, 0)))[:, :s]
            x = jnp.where(mask, fe_pad, x)
        return constrain(x, ("batch", None, None)), positions

    def _unembed(self, params, x):
        with jax.named_scope("head"):
            x = layers.apply_norm(x, params["final_norm"], self.cfg.norm)
            table = params["embed"]["table"] if self.cfg.tie_embeddings \
                else params["lm_head"]["table"]
            logits = layers.unembed(x, {"table": table})
            return constrain(logits, ("batch", None, "vocab"))

    # ------------------------------------------------------------------
    # Forward (train / prefill)
    # ------------------------------------------------------------------
    @_with_backend
    def forward(self, params: dict, batch: dict) -> jax.Array:
        """Full-sequence forward -> logits [B, S, vocab] (f32)."""
        return self._unembed(params, self._backbone(params, batch))

    def _backbone(self, params: dict, batch: dict) -> jax.Array:
        """Embed + all layers -> pre-final-norm hidden states [B, S, d]."""
        cfg = self.cfg
        x, positions = self._embed_inputs(params, batch)
        causal = _is_causal(cfg)

        if cfg.family == "ssm":
            body = self._maybe_remat(lambda h, lp: (self._ssm_body(h, lp), None))
            x = self._run_stack(body, x, params["layers"])
        elif cfg.family == "hybrid":
            for lp, kind in zip(params["layers"], self._hybrid_kinds()):
                f = self._maybe_remat(functools.partial(
                    self._hybrid_body, kind=kind, positions=positions))
                x = f(x, lp)
        elif cfg.family == "moe":
            for lp in params.get("dense_prefix", []):
                f = self._maybe_remat(functools.partial(
                    self._dense_body, positions=positions, causal=True))
                x = f(x, lp)
            body = self._maybe_remat(lambda h, lp: (
                self._dense_body(h, lp, positions, True), None))
            x = self._run_stack(body, x, params["layers"])
        elif cfg.encdec is not None:
            enc = self._encode(params, batch)
            def cross_body(h, lp):
                kv = self._cross_kv(lp["cross"], enc)
                return self._cross_body(h, lp, positions, kv), None
            x = self._run_stack(self._maybe_remat(cross_body), x,
                                params["layers"])
        else:
            window = cfg.hybrid.attention_window if cfg.hybrid else None
            body = self._maybe_remat(lambda h, lp: (
                self._dense_body(h, lp, positions, causal, window), None))
            x = self._run_stack(body, x, params["layers"])
        return x

    def _encode(self, params: dict, batch: dict) -> jax.Array:
        """Whisper encoder over stub frame embeddings [B, T_enc, d]."""
        cfg = self.cfg
        fe = batch["frontend"].astype(self.opt.compute_dtype)
        pos = jnp.arange(fe.shape[1], dtype=jnp.int32)[None, :]
        x = fe + params["enc_pos_embed"]["table"].astype(fe.dtype)[None]
        body = self._maybe_remat(lambda h, lp: (
            self._dense_body(h, lp, pos, causal=False), None))
        x = self._run_stack(body, x, params["enc_layers"])
        return layers.apply_norm(x, params["enc_final_norm"], cfg.norm)

    # ------------------------------------------------------------------
    # Loss (train step body)
    # ------------------------------------------------------------------
    @_with_backend
    def loss(self, params: dict, batch: dict) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        x = self._backbone(params, batch)
        logits = self._unembed(params, x)
        targets = batch["targets"]
        xent = _xent(logits, targets)
        aux: dict[str, jax.Array] = {"xent": xent}
        total = xent
        if cfg.family == "moe" and self.opt.moe_aux_weight:
            # router balance loss on the embedding stream (cheap proxy input)
            e, _ = self._embed_inputs(params, batch)
            lb = moe.load_balance_loss(
                e, _first_layer(params["layers"], "moe")["router"], cfg.moe)
            aux["load_balance"] = lb
            total = total + self.opt.moe_aux_weight * lb
        if cfg.num_mtp_modules and "mtp" in params:
            mtp_loss = self._mtp_loss(params, batch, x)
            aux["mtp"] = mtp_loss
            total = total + self.opt.mtp_loss_weight * mtp_loss
        aux["total"] = total
        return total, aux

    def _mtp_loss(self, params: dict, batch: dict, x: jax.Array) -> jax.Array:
        """DeepSeek-V3 multi-token prediction (depth 1), reusing the main
        backbone hidden states ``x``: combine h_t with emb(t+1), run one
        extra layer, predict token t+2."""
        cfg = self.cfg
        targets = batch["targets"]
        positions = jnp.arange(targets.shape[1], dtype=jnp.int32)[None, :]
        mp = params["mtp"]
        e = layers.embed(targets, params["embed"], self.opt.compute_dtype)
        h = jnp.concatenate([
            layers.apply_norm(x, mp["norm_h"], cfg.norm),
            layers.apply_norm(e, mp["norm_e"], cfg.norm)], axis=-1)
        h = layers.apply_dense(h, mp["proj"])
        h = self._dense_body(h, mp["layer"], positions, True)
        logits = self._unembed(params, h)
        return _xent(logits, jnp.roll(targets, -1, axis=1))

    # ------------------------------------------------------------------
    # Decode (one new token with per-family cache)
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, abstract: bool = False,
                   paging: "PagingConfig | None" = None):
        """Decode cache in either layout and either storage codec.

        ``paging=None`` (dense): per-slot ``[batch, max_len, ...]`` rows —
        the training/test layout.  With a ``core.paging.PagingConfig``,
        returns the pooled block layout ``[num_blocks+1, block_size, ...]``
        shared by all slots (row 0 is the null block); ``batch``/``max_len``
        then only bound the serving engine's block tables, not the pool.

        With ``ModelOptions(kv_dtype="int8")`` the KV/latent values are
        int8 and per-row f32 scale arrays ride in the same pytree
        (``core.kv_quant``); supported for the attention-cache families
        only.
        """
        cfg = self.cfg
        codec = self.codec
        kd = codec.storage_dtype(jnp.bfloat16)
        if codec.quantized and cfg.family not in KV_QUANTIZABLE_FAMILIES:
            raise ValueError(
                f"kv_dtype='int8' is unsupported for family {cfg.family!r} "
                "(only KV/latent attention caches are quantized); use "
                "kv_dtype='compute'")
        if paging is not None:
            return self._init_paged_cache(paging, abstract)

        def kv(n_layers, s, n_kv, hd):
            shape = (n_layers, batch, s, n_kv, hd)
            kvals, ksc = codec.cache_arrays(shape, abstract=abstract)
            vvals, vsc = codec.cache_arrays(shape, abstract=abstract)
            return KVCache(kvals, vvals, ksc, vsc)

        if cfg.family == "ssm":
            st = ssm.ssm_init_state(cfg, batch, abstract)
            return jax.tree.map(
                lambda l: _stack_abstract(l, cfg.num_layers) if abstract
                else jnp.broadcast_to(l, (cfg.num_layers,) + l.shape).copy(), st)
        if cfg.mla is not None:
            m = cfg.mla
            cv, cs = codec.cache_arrays(
                (cfg.num_layers, batch, max_len, m.kv_lora_rank),
                abstract=abstract)
            rv, rs = codec.cache_arrays(
                (cfg.num_layers, batch, max_len, m.qk_rope_head_dim),
                abstract=abstract)
            return MLACache(cv, rv, cs, rs)
        if cfg.family == "hybrid":
            caches = []
            for kind in self._hybrid_kinds():
                if kind == "r":
                    caches.append(rglru.rglru_init_state(cfg, batch, abstract))
                else:
                    w = min(cfg.hybrid.attention_window, max_len)
                    shape = (batch, w, cfg.num_kv_heads, cfg.resolved_head_dim)
                    if abstract:
                        caches.append(KVCache(jax.ShapeDtypeStruct(shape, kd),
                                              jax.ShapeDtypeStruct(shape, kd)))
                    else:
                        caches.append(KVCache(jnp.zeros(shape, kd),
                                              jnp.zeros(shape, kd)))
            return caches
        if cfg.encdec is not None:
            se = cfg.encdec.encoder_seq_len
            return {"self": kv(cfg.num_layers, max_len, cfg.num_kv_heads,
                               cfg.resolved_head_dim),
                    "cross": kv(cfg.num_layers, se, cfg.num_kv_heads,
                                cfg.resolved_head_dim)}
        return kv(cfg.num_layers, max_len, cfg.num_kv_heads,
                  cfg.resolved_head_dim)

    def _init_paged_cache(self, paging, abstract: bool):
        cfg = self.cfg
        codec = self.codec
        if cfg.family not in ("dense", "vlm", "moe"):
            raise ValueError(
                f"paged KV cache unsupported for family {cfg.family!r} "
                "(SSM / rolling-window / enc-dec state is not paged)")

        pb, bs = paging.pool_blocks, paging.block_size
        if cfg.mla is not None:
            m = cfg.mla
            cv, cs = codec.cache_arrays(
                (cfg.num_layers, pb, bs, m.kv_lora_rank), abstract=abstract)
            rv, rs = codec.cache_arrays(
                (cfg.num_layers, pb, bs, m.qk_rope_head_dim),
                abstract=abstract)
            return MLACache(cv, rv, cs, rs)
        # int8 scales stay one per (position, kv head)
        kv = cfg.num_kv_heads
        shape = (cfg.num_layers, pb, bs,
                 *pool_row(kv, cfg.resolved_head_dim))
        scales = shape[:3] + (kv,)
        kvals, ksc = codec.cache_arrays(shape, scale_shape=scales,
                                        abstract=abstract)
        vvals, vsc = codec.cache_arrays(shape, scale_shape=scales,
                                        abstract=abstract)
        return KVCache(kvals, vvals, ksc, vsc)

    @_with_backend
    # jit-region
    def prefill(self, params: dict, batch: dict, max_len: int):
        """Prompt -> (logits [B,S,V], decode cache ready at index S).

        The serving counterpart of ``forward``: identical math, but every
        layer also emits its decode-time state.
        """
        cfg = self.cfg
        x, positions = self._embed_inputs(params, batch)

        def ffn_half(h, lp):
            # SP residual pinning only — prefill never had the scan-carry
            # sharding bug, and pinning batch here regressed propagation
            # (see EXPERIMENTS.md §Perf prefill iteration 1)
            h = constrain(h, (None, "seq", None))
            hn = layers.apply_norm(h, lp["ln2"], cfg.norm)
            if "moe" in lp:
                return h + moe.apply_moe(hn, lp["moe"], cfg)[0]
            return h + moe.apply_ffn(hn, lp["ffn"], cfg.activation)

        if cfg.family == "ssm":
            def body(h, lp):
                hn = layers.apply_norm(h, lp["ln"], cfg.norm)
                o, st = ssm.ssm_prefill(hn, lp["ssm"], cfg)
                return h + o, st
            x, cache = self._run_stack_collect(body, x, params["layers"])
        elif cfg.family == "hybrid":
            cache = []
            for lp, kind in zip(params["layers"], self._hybrid_kinds()):
                hn = layers.apply_norm(x, lp["ln1"], cfg.norm)
                if kind == "r":
                    o, st = rglru.rglru_prefill(hn, lp["rglru"], cfg)
                else:
                    o, st = attn.gqa_prefill(
                        hn, lp["attn"], cfg, positions=positions,
                        max_len=max_len, window=cfg.hybrid.attention_window)
                x = ffn_half(x + o, lp)
                cache.append(st)
        elif cfg.encdec is not None:
            enc = self._encode(params, batch)
            def body(h, lp):
                hn = layers.apply_norm(h, lp["ln1"], cfg.norm)
                o, st = attn.gqa_prefill(hn, lp["attn"], cfg,
                                         positions=positions, max_len=max_len)
                h = h + o
                kc, vc = self._cross_kv(lp["cross"], enc)
                hn = layers.apply_norm(h, lp["ln_cross"], cfg.norm)
                h = h + self._cross_attend(hn, lp["cross"], (kc, vc))
                hn = layers.apply_norm(h, lp["ln2"], cfg.norm)
                h = h + moe.apply_ffn(hn, lp["ffn"], cfg.activation)
                cross = KVCache(kc.astype(jnp.bfloat16), vc.astype(jnp.bfloat16))
                return h, (st, cross)
            x, (self_c, cross_c) = self._run_stack_collect(
                body, x, params["layers"])
            cache = {"self": self_c, "cross": cross_c}
        else:
            def body(h, lp):
                h = constrain(h, (None, "seq", None))
                hn = layers.apply_norm(h, lp["ln1"], cfg.norm)
                if cfg.mla is not None:
                    o, st = attn.mla_prefill(hn, lp["attn"], cfg,
                                             positions=positions,
                                             max_len=max_len,
                                             codec=self.codec)
                else:
                    o, st = attn.gqa_prefill(hn, lp["attn"], cfg,
                                             positions=positions,
                                             max_len=max_len,
                                             codec=self.codec)
                return ffn_half(h + o, lp), st

            pref = []
            for lp in params.get("dense_prefix", []):
                x, st = body(x, lp)
                pref.append(st)
            x, main_cache = self._run_stack_collect(body, x, params["layers"])
            if pref:
                stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *pref)
                cache = jax.tree.map(lambda a, b_: jnp.concatenate([a, b_]),
                                     stacked, main_cache)
            else:
                cache = main_cache
        return self._unembed(params, x), cache

    @_with_backend
    # jit-region
    def decode_step(self, params: dict, cache, tokens: jax.Array,
                    cache_index: jax.Array,
                    block_tables: jax.Array | None = None,
                    live: jax.Array | None = None,
                    expert_counts: bool = False):
        """tokens: [B, 1] -> (logits [B, 1, vocab], new cache).

        ``cache_index``: scalar, or [B] per-slot indices (serving).
        ``block_tables``: [B, blocks_per_slot] int32 selects the paged
        cache layout (``cache`` must then be the pooled block layout from
        ``init_cache(..., paging=...)``); None keeps the dense layout.
        ``live`` ([B] bool, default all): the slots that write their K/V
        row and that MoE layers route; a slot that is not live writes
        nothing.  ``expert_counts``: also return the held experts' int32
        [rows, experts hit] (``_run_cache_layers``) as a third output.

        The attention-cache families decode through ``mixed_step`` at
        one lane; the others keep their sequential state steps here."""
        cfg = self.cfg
        if cfg.family in CHUNKABLE_FAMILIES:
            n_live = jnp.ones(tokens.shape[:1], jnp.int32) if live is None \
                else live.astype(jnp.int32)
            return self.mixed_step(params, cache, tokens, start=cache_index,
                                   n_live=n_live, block_tables=block_tables,
                                   expert_counts=expert_counts)
        if block_tables is not None:
            raise ValueError(
                f"paged decode unsupported for family {cfg.family!r}")
        idx_vec = attn.as_index_vector(cache_index, tokens.shape[0])
        x = layers.embed(tokens, params["embed"], self.opt.compute_dtype)
        if cfg.positional == "learned":
            idx = jnp.minimum(idx_vec, cfg.max_position_embeddings - 1)
            x = x + params["pos_embed"]["table"].astype(x.dtype)[idx][:, None]

        if cfg.family == "ssm":
            def body(h, inp):
                lp, st = inp
                hn = layers.apply_norm(h, lp["ln"], cfg.norm)
                out, st2 = ssm.ssm_decode(hn, lp["ssm"], cfg, st)
                return h + out, st2
            x, new_cache = self._run_stack_cache(body, x, params["layers"], cache)
        elif cfg.family == "hybrid":
            new_cache = []
            for lp, kind, st in zip(params["layers"], self._hybrid_kinds(), cache):
                hn = layers.apply_norm(x, lp["ln1"], cfg.norm)
                if kind == "r":
                    o, st2 = rglru.rglru_decode(hn, lp["rglru"], cfg, st)
                else:
                    o, st2 = attn.gqa_decode(hn, lp["attn"], cfg, st, cache_index,
                                             window=cfg.hybrid.attention_window,
                                             grouped=self.opt.grouped_gqa)
                x = x + o
                hn = layers.apply_norm(x, lp["ln2"], cfg.norm)
                x = x + moe.apply_ffn(hn, lp["ffn"], cfg.activation)
                new_cache.append(st2)
        else:   # enc-dec: self-attention cache beside the cross K/V
            def body(h, inp):
                lp, (c_self, c_cross) = inp
                hn = layers.apply_norm(h, lp["ln1"], cfg.norm)
                o, c2 = attn.gqa_decode(hn, lp["attn"], cfg, c_self, cache_index,
                                        grouped=self.opt.grouped_gqa)
                h = h + o
                hn = layers.apply_norm(h, lp["ln_cross"], cfg.norm)
                h = h + self._cross_attend(hn, lp["cross"], (c_cross.k, c_cross.v))
                hn = layers.apply_norm(h, lp["ln2"], cfg.norm)
                h = h + moe.apply_ffn(hn, lp["ffn"], cfg.activation)
                return h, (c2, c_cross)
            x, new_cache = self._run_stack_cache(
                body, x, params["layers"], (cache["self"], cache["cross"]))
            new_cache = {"self": new_cache[0], "cross": new_cache[1]}
        out = self._unembed(params, x), new_cache
        return out + (None,) if expert_counts else out

    @_with_backend
    # jit-region
    def mixed_step(self, params: dict, cache, tokens: jax.Array,
                   start: jax.Array, n_live: jax.Array,
                   block_tables: jax.Array | None = None,
                   prefill_lanes: jax.Array | None = None,
                   expert_counts: bool = False):
        """Chunked-prefill/decode mixed step: tokens [B, W] -> (logits
        [B, W, vocab], new cache; with ``expert_counts`` the held
        experts' int32 [rows, experts hit] third, as in ``decode_step``).

        Lane ``l`` of slot ``b`` sits at cache position ``start[b] + l``;
        only the first ``n_live[b]`` lanes are real.  A decoding slot uses
        one lane (its next token), a prefilling slot up to a chunk of
        prompt tokens, an idle slot none — one compiled step serves any
        mixture, so prefill stops being a separate per-bucket dispatch.
        ``prefill_lanes`` ([B] bool) marks slots whose lanes are prompt
        tokens (only consulted by the vlm frontend stub).  Restricted to
        attention-cache families: recurrent / rolling-window / enc-dec
        prefill state is sequential and stays on the bucketed path.
        """
        cfg = self.cfg
        if cfg.family not in CHUNKABLE_FAMILIES:
            raise ValueError(
                f"mixed_step unsupported for family {cfg.family!r} "
                "(sequential prefill state); use the bucketed scheduler")
        b_, w = tokens.shape
        start = attn.as_index_vector(start, b_)
        positions = start[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
        x = layers.embed(tokens, params["embed"], self.opt.compute_dtype)
        if cfg.positional == "learned":
            idx = jnp.minimum(positions, cfg.max_position_embeddings - 1)
            x = x + params["pos_embed"]["table"].astype(x.dtype)[idx]
        if cfg.frontend is not None and prefill_lanes is not None:
            # parity with the stub vision frontend of prefill: prompt
            # positions < num_tokens carry the (zero-stub) patch
            # embeddings instead of token embeddings
            fm = prefill_lanes[:, None, None] \
                & (positions < cfg.frontend.num_tokens)[..., None]
            x = jnp.where(fm, jnp.zeros_like(x), x)

        def attend(hn, ap, c, layer):
            if cfg.mla is not None and block_tables is not None:
                return attn.mla_mixed_paged(hn, ap, cfg, c, start, n_live,
                                            block_tables, layer,
                                            codec=self.codec)
            if cfg.mla is not None:
                return attn.mla_mixed(hn, ap, cfg, c, start, n_live,
                                      codec=self.codec)
            if block_tables is not None:
                return attn.gqa_mixed_paged(
                    hn, ap, cfg, c, start, n_live, block_tables, layer,
                    grouped=self.opt.grouped_gqa,
                    impl=self.opt.paged_attn_impl, codec=self.codec)
            return attn.gqa_mixed(hn, ap, cfg, c, start, n_live,
                                  grouped=self.opt.grouped_gqa,
                                  codec=self.codec)

        x, new_cache, n_ex = self._run_cache_layers(
            attend, x, params, cache, block_tables is not None,
            masking.lane_mask(w, attn.as_index_vector(n_live, b_)))
        out = self._unembed(params, x), new_cache
        return out + (n_ex,) if expert_counts else out


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def _xent(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Cross-entropy that stays sharded over a vocab-partitioned logits
    tensor: the gold logit is picked with a fused iota-compare-reduce, not
    a gather (a gather across the sharded vocab axis would force GSPMD to
    all-gather the full [B, S, V] logits on every device)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1) \
        == targets[..., None]
    gold = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    return jnp.mean(lse - gold)


def _first_layer(stacked: dict, key: str) -> dict:
    return jax.tree.map(lambda l: l[0], stacked[key])


def _stack_abstract(leaf, n: int):
    return jax.ShapeDtypeStruct((n,) + leaf.shape, leaf.dtype)

