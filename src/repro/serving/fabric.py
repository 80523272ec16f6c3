"""Register-driven multi-topology decode fabric.

``core.adaptive.AdaptiveEngine`` proves the paper's C1 claim for
full-sequence encoders: one compiled step, any topology within maxima,
selected by register *data*.  This module is the serving-side
counterpart: a **padded maximal GQA causal LM** whose prefill/decode
steps are compiled once at ``Maxima`` shapes and then serve a mixed
fleet of models — every batch slot may run a *different* topology
(heads / layers / d_model / d_ff / vocab) and a *different* weight set,
with zero retraces.  NPE's overlay argument (one fabric, many NLP
models) meets continuous batching: requests from different models share
one fused decode dispatch.

Mechanics:

* **model table** — every fleet member's weights are packed (KV heads
  replicated to the full head count, exactly ``core.adaptive.pack``'s
  GQA trick, then zero-padded to maxima) into row ``m`` of a
  ``[max_models, ...]`` device table.  Loading a model is a device
  scatter — the paper's weight-loading units, no recompile.
* **topology registers** — a ``[B, N_REGS]`` int32 array rides in the
  engine's ``SlotState``; column ``REG_MODEL`` picks the table row, the
  rest are the live extents.  ``core.masking``'s per-slot variants keep
  idle lanes (dead heads, dead layers, dead d_model/d_ff/vocab lanes)
  from contaminating live compute — clock gating as masking.
* **structural template** — like the FPGA fabric, some choices are
  frozen at synthesis: norm kind, activation, RoPE theta and the PE
  lane width (head_dim).  ``check_member`` rejects models that would
  need a different fabric with an actionable message.

Both cache layouts work: dense ``[L, B, S, H, hd]`` rows or the pooled
paged layout (``core.paging``), including the Pallas flash-decode kernel
with padded-head-lane masking.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import masking
from repro.core.kv_quant import CacheCodec, cache_put, gather_view
from repro.core.paging import PagingConfig
from repro.core.quant import DEFAULT_QUANT_MIN_SIZE, QTensor
from repro.core.registers import Maxima
from repro.models.attention import KVCache, paged_write_slot
from repro.models.layers import activate, apply_rope, is_gated

# Topology register columns (the per-slot AXI-Lite register file).
REG_MODEL, REG_HEADS, REG_LAYERS, REG_DMODEL, REG_DFF, REG_VOCAB = range(6)
N_REGS = 6


@dataclasses.dataclass(frozen=True)
class FabricTemplate:
    """Structural choices frozen at 'synthesis' — every fleet member must
    match them (they change the compiled step, not just register data)."""

    norm: str            # "rmsnorm" | "layernorm"
    activation: str      # swiglu | geglu | gelu | relu
    rope_theta: float
    head_dim: int        # the PE lane width; fixed across the fleet

    @classmethod
    def of(cls, arch: ArchConfig) -> "FabricTemplate":
        return cls(norm=arch.norm, activation=arch.activation,
                   rope_theta=arch.rope_theta,
                   head_dim=arch.resolved_head_dim)


class DecodeFabric:
    """One compiled prefill/decode pair serving any dense-family topology
    within ``maxima`` from a ``max_models``-row weight table."""

    def __init__(self, maxima: Maxima, max_models: int,
                 template: FabricTemplate | ArchConfig,
                 compute_dtype: Any = jnp.bfloat16,
                 param_dtype: Any = jnp.float32,
                 quant: str = "none",
                 quant_min_size: int = DEFAULT_QUANT_MIN_SIZE,
                 kv_dtype: str = "compute"):
        if isinstance(template, ArchConfig):
            template = FabricTemplate.of(template)
        if template.head_dim != maxima.head_dim_max:
            raise ValueError(
                f"fabric head_dim {template.head_dim} != maxima.head_dim_max "
                f"{maxima.head_dim_max}: the lane width is fixed at "
                "synthesis (RoPE pairs by head_dim, so it cannot be a "
                "runtime register); synthesize at the fleet's common "
                "head_dim")
        if quant not in ("none", "int8"):
            raise ValueError(f"DecodeFabric quant={quant!r} is not one of "
                             "('none', 'int8')")
        self.mx = maxima
        self.max_models = max_models
        self.template = template
        self.compute_dtype = compute_dtype
        self.param_dtype = param_dtype
        self.quant = quant
        self.quant_min_size = quant_min_size
        # the cache codec: int8 kv quantize-on-write with per-row scales
        self.codec = CacheCodec(kv_dtype)
        self.hd = template.head_dim

    # ------------------------------------------------------------------
    # Fleet membership
    # ------------------------------------------------------------------
    def check_member(self, arch: ArchConfig) -> None:
        """Reject models this fabric cannot serve, with the reason."""
        t = self.template
        if arch.family != "dense":
            raise ValueError(
                f"{arch.name}: multi-topology serving covers the dense GQA "
                f"family; family {arch.family!r} needs its own engine")
        for knob, want, got in (("norm", t.norm, arch.norm),
                                ("activation", t.activation, arch.activation),
                                ("positional", "rope", arch.positional)):
            if want != got:
                raise ValueError(
                    f"{arch.name}: {knob}={got!r} differs from the fabric's "
                    f"synthesized {knob}={want!r}; structural knobs are "
                    "frozen at compile time (re-synthesize a fabric with "
                    "the fleet's shared structure)")
        if arch.rope_theta != t.rope_theta:
            raise ValueError(
                f"{arch.name}: rope_theta={arch.rope_theta} differs from "
                f"the fabric's {t.rope_theta}")
        if arch.resolved_head_dim != self.hd:
            raise ValueError(
                f"{arch.name}: head_dim={arch.resolved_head_dim} != fabric "
                f"lane width {self.hd}; head_dim is not a runtime register")
        mx = self.mx
        over = [f"{n}={v} > {m}" for n, v, m in (
            ("heads", arch.num_heads, mx.heads_max),
            ("layers", arch.num_layers, mx.layers_enc_max),
            ("d_model", arch.d_model, mx.d_model_max),
            ("d_ff", arch.d_ff, mx.d_ff_max),
            ("vocab", arch.vocab_size, mx.vocab)) if v > m]
        if over:
            raise ValueError(
                f"{arch.name} exceeds the synthesized maxima "
                f"({'; '.join(over)}); re-synthesis (recompile) required")

    def topo_row(self, arch: ArchConfig, model_id: int) -> list[int]:
        """The slot register values for one fleet member."""
        return [model_id, arch.num_heads, arch.num_layers, arch.d_model,
                arch.d_ff, arch.vocab_size]

    def cache_namespace(self, arch: ArchConfig, model_id: int) -> tuple:
        """Prefix-trie namespace for one fleet member's KV blocks.

        Fleet members share ONE physical pool, but a prompt's KV is a
        function of the *model* that prefilled it — identical token
        prefixes under different members must never alias.  Keyed on the
        model id *and* the architecture name so a table row reloaded
        with a different member (same id, new weights via
        ``insert_model``) still separates if the caller re-registers the
        engine's namespace map.
        """
        return ("fleet", model_id, arch.name)

    def _quant_names(self) -> frozenset:
        """Table leaves stored as int8 ``QTensor``s under quant='int8'.
        Decided on the table (maxima-padded) per-member sizes — the
        table's structure is shared by every member, so eligibility
        cannot vary per member: a small fleet member may get int8
        weights that its single-topology ``quantize_params`` (which sees
        the unpadded leaf sizes) would leave float.  Stream parity with
        solo engines therefore holds at any ``quant_min_size`` that
        selects the same leaves on both sides (0 selects everything).
        Leaves under the floor stay float (biases and norms always
        do)."""
        if self.quant != "int8":
            return frozenset()
        mx, L = self.mx, self.mx.layers_enc_max
        D, F, V, HO = (mx.d_model_max, mx.d_ff_max, mx.vocab,
                       mx.heads_max * self.hd)
        sizes = {"embed": V * D, "lm_head": V * D,
                 "wq": L * D * HO, "wk": L * D * HO, "wv": L * D * HO,
                 "wo": L * HO * D, "w1": L * D * F, "wg": L * D * F,
                 "w2": L * F * D}
        return frozenset(n for n, sz in sizes.items()
                         if sz >= self.quant_min_size)

    # ------------------------------------------------------------------
    # Model table (synthesis-time buffers + weight loading units)
    # ------------------------------------------------------------------
    def _norm_shape(self, *lead: int) -> dict:
        z = lambda *s: jnp.zeros(s, self.param_dtype)
        p = {"scale": z(*lead, self.mx.d_model_max)}
        if self.template.norm == "layernorm":
            p["bias"] = z(*lead, self.mx.d_model_max)
        return p

    def init_table(self) -> dict:
        mx, M, L = self.mx, self.max_models, self.mx.layers_enc_max
        D, F, V, HO = (mx.d_model_max, mx.d_ff_max, mx.vocab,
                       mx.heads_max * self.hd)
        z = lambda *s: jnp.zeros(s, self.param_dtype)
        qn = self._quant_names()

        def kern(name, *shape):
            # int8 values + per-(stack, output-column) f32 scales
            if name in qn:
                return QTensor(jnp.zeros(shape, jnp.int8),
                               jnp.zeros(shape[:-2] + (1, shape[-1]),
                                         jnp.float32))
            return z(*shape)

        def vocab_table(name, *shape):
            # int8 values + per-row f32 scales (embed / lm_head)
            if name in qn:
                return QTensor(jnp.zeros(shape, jnp.int8),
                               jnp.zeros(shape[:-1] + (1,), jnp.float32))
            return z(*shape)

        layers = {
            "ln1": self._norm_shape(M, L),
            "wq": kern("wq", M, L, D, HO), "bq": z(M, L, HO),
            "wk": kern("wk", M, L, D, HO), "bk": z(M, L, HO),
            "wv": kern("wv", M, L, D, HO), "bv": z(M, L, HO),
            "wo": kern("wo", M, L, HO, D),
            "ln2": self._norm_shape(M, L),
            "w1": kern("w1", M, L, D, F), "b1": z(M, L, F),
            "w2": kern("w2", M, L, F, D), "b2": z(M, L, D),
        }
        if is_gated(self.template.activation):
            layers["wg"] = kern("wg", M, L, D, F)
            layers["bg"] = z(M, L, F)
        return {"embed": vocab_table("embed", M, V, D),
                "lm_head": vocab_table("lm_head", M, V, D),
                "final_norm": self._norm_shape(M), "layers": layers}

    def pack_member(self, arch: ArchConfig, params: dict) -> dict:
        """Zoo-model params -> one zero-padded table row (KV weights
        replicated across the head group, ``core.adaptive.pack``'s GQA
        trick, so runtime compute is uniform MHA over ``heads`` lanes)."""
        self.check_member(arch)
        mx, L = self.mx, self.mx.layers_enc_max
        h, kv, hd = arch.num_heads, arch.num_kv_heads, self.hd
        rep = h // kv

        def pad(a, *shape):
            a = jnp.asarray(a, self.param_dtype)
            return jnp.pad(a, [(0, t - s) for s, t in zip(a.shape, shape)])

        def rep_kv(w):  # [l, d, kv*hd] -> [l, d, h*hd] (head-grouped order)
            l_, d_ = w.shape[:2]
            return jnp.repeat(w.reshape(l_, d_, kv, hd), rep, axis=2) \
                .reshape(l_, d_, h * hd)

        def rep_kv_b(b_):  # [l, kv*hd] -> [l, h*hd]
            l_ = b_.shape[0]
            return jnp.repeat(b_.reshape(l_, kv, hd), rep, axis=1) \
                .reshape(l_, h * hd)

        lp = params["layers"]
        nl, D, F, HO = arch.num_layers, mx.d_model_max, mx.d_ff_max, \
            mx.heads_max * hd

        def bias_or_zeros(p, width):
            # biases are always provisioned in the table; members without
            # them (no qkv_bias, rmsnorm FFN) contribute exact zeros
            return p.get("bias", jnp.zeros((nl, width), self.param_dtype))

        def norm_row(p, *shape):
            out = {"scale": pad(p["scale"], *shape)}
            if self.template.norm == "layernorm":
                out["bias"] = pad(p["bias"], *shape)
            return out

        attn = lp["attn"]
        row_layers = {
            "ln1": norm_row(lp["ln1"], L, D),
            "wq": pad(attn["wq"]["kernel"], L, D, HO),
            "bq": pad(bias_or_zeros(attn["wq"], h * hd), L, HO),
            "wk": pad(rep_kv(attn["wk"]["kernel"]), L, D, HO),
            "bk": pad(rep_kv_b(bias_or_zeros(attn["wk"], kv * hd)),
                      L, HO),
            "wv": pad(rep_kv(attn["wv"]["kernel"]), L, D, HO),
            "bv": pad(rep_kv_b(bias_or_zeros(attn["wv"], kv * hd)),
                      L, HO),
            "wo": pad(attn["wo"]["kernel"], L, HO, D),
            "ln2": norm_row(lp["ln2"], L, D),
            "w1": pad(lp["ffn"]["w1"]["kernel"], L, D, F),
            "b1": pad(bias_or_zeros(lp["ffn"]["w1"], arch.d_ff), L, F),
            "w2": pad(lp["ffn"]["w2"]["kernel"], L, F, D),
            "b2": pad(bias_or_zeros(lp["ffn"]["w2"], arch.d_model),
                      L, D),
        }
        if is_gated(self.template.activation):
            row_layers["wg"] = pad(lp["ffn"]["wg"]["kernel"], L, D, F)
            row_layers["bg"] = pad(
                bias_or_zeros(lp["ffn"]["wg"], arch.d_ff), L, F)
        lm = params["embed"]["table"] if arch.tie_embeddings \
            else params["lm_head"]["table"]
        row = {"embed": pad(params["embed"]["table"], mx.vocab, D),
               "lm_head": pad(lm, mx.vocab, D),
               "final_norm": norm_row(params["final_norm"], D),
               "layers": row_layers}
        return self._quantize_row(row)

    def _quantize_row(self, row: dict) -> dict:
        """Symmetric-int8-quantize the planned leaves of one packed row
        via the ONE quantizer (``core.serve_quant.quantize_leaf``:
        per-output-column scales for kernels, per-row for the vocab
        tables).  Zero padding never moves a scale, so on leaves
        quantized on both sides a member's values equal its
        single-topology ``quantize_params`` values on the live lanes
        (see ``_quant_names`` for the eligibility caveat)."""
        qn = self._quant_names()
        if not qn:
            return row
        from repro.core.serve_quant import quantize_leaf
        for name in ("embed", "lm_head"):
            if name in qn:
                row[name] = quantize_leaf(row[name], "table")
        for name in ("wq", "wk", "wv", "wo", "w1", "w2", "wg"):
            if name in qn and name in row["layers"]:
                row["layers"][name] = quantize_leaf(row["layers"][name],
                                                    "kernel")
        return row

    @staticmethod
    def insert_model(table: dict, row: dict, model_id: int) -> dict:
        """Scatter one packed row into the table (the AXI weight write)."""
        return jax.tree.map(lambda t, r: t.at[model_id].set(r), table, row)

    # ------------------------------------------------------------------
    # Capacity accounting (the harness autotuner's fleet yardstick)
    # ------------------------------------------------------------------
    def kv_bytes_per_token(self) -> int:
        """HBM bytes one cached token costs in this fabric's shared pool.

        The fleet analogue of ``core.analytical.kv_bytes_per_token``:
        the pool is provisioned at the synthesized maxima
        (``layers_enc_max`` layers x ``heads_max`` heads x the fixed
        lane width), whatever member actually fills it — a small model
        in a big fabric still pays maxima-shaped cache rows.
        """
        per_row = self.codec.bytes_per_feature_row(self.hd,
                                                   self.compute_dtype)
        return 2 * self.mx.layers_enc_max * self.mx.heads_max * per_row

    def table_bytes(self, table: dict) -> int:
        """Resident HBM bytes of a packed weight table (all rows,
        quantized leaves included) — what the device budget must cover
        before any cache is provisioned."""
        return sum(leaf.nbytes for leaf in jax.tree.leaves(table))

    # ------------------------------------------------------------------
    # Decode cache (maxima-shaped; both layouts)
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int,
                   paging: PagingConfig | None = None) -> KVCache:
        L, H, hd = self.mx.layers_enc_max, self.mx.heads_max, self.hd
        if paging is not None:
            shape = (L, paging.pool_blocks, paging.block_size, H, hd)
        else:
            shape = (L, batch, max_len, H, hd)
        kv, ks = self.codec.cache_arrays(shape)
        vv, vs = self.codec.cache_arrays(shape)
        return KVCache(kv, vv, ks, vs)

    # ------------------------------------------------------------------
    # Masked compute
    # ------------------------------------------------------------------
    def _norm(self, x: jax.Array, p: dict, d_live: jax.Array) -> jax.Array:
        if self.template.norm == "rmsnorm":
            return masking.masked_rmsnorm_slots(x, p["scale"], d_live)
        return masking.masked_layernorm_slots(x, p["scale"], p["bias"],
                                              d_live)

    @staticmethod
    def _mm(x: jax.Array, w, b: jax.Array | None = None) -> jax.Array:
        """Per-slot dense: x [B,S,Din] @ w [B,Din,Dout] (+ b [B,Dout]),
        bf16 weights / f32 accumulate — the ``backend.matmul`` contract.
        ``w`` may be an int8 ``QTensor`` (quant='int8' fleet table):
        dequantized at the compute dtype exactly like ``layers.dense``'s
        serving path, so fleet streams track the zoo model's."""
        if isinstance(w, QTensor):
            wb = w.values.astype(x.dtype) * w.scale.astype(x.dtype)
        else:
            wb = w.astype(x.dtype)
        y = jnp.einsum("bsd,bdo->bso", x.astype(jnp.float32),
                       wb.astype(jnp.float32)).astype(x.dtype)
        if b is not None:
            y = y + b.astype(y.dtype)[:, None]
        return y

    def _embed_rows(self, table: dict, mid, tokens) -> jax.Array:
        """Token embeddings gathered by (model row, token id); an int8
        table dequants with its gathered per-row scales (mirrors
        ``layers.embed``)."""
        emb = table["embed"]
        if isinstance(emb, QTensor):
            return emb.values[mid, tokens].astype(self.compute_dtype) \
                * emb.scale[mid, tokens].astype(self.compute_dtype)
        return emb[mid, tokens].astype(self.compute_dtype)

    def _qkv(self, xn: jax.Array, lp: dict, positions: jax.Array,
             he: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Masked QKV projections at maxima head lanes; ``he`` is the
        per-slot [B, 1, H, 1] live-head mask."""
        B, S = xn.shape[:2]
        H, hd = self.mx.heads_max, self.hd
        shape = (B, S, H, hd)
        q = self._mm(xn, lp["wq"], lp["bq"]).reshape(shape) * he
        k = self._mm(xn, lp["wk"], lp["bk"]).reshape(shape) * he
        v = self._mm(xn, lp["wv"], lp["bv"]).reshape(shape) * he
        q = apply_rope(q, positions, self.template.rope_theta)
        k = apply_rope(k, positions, self.template.rope_theta)
        return q, k, v

    def _attend(self, q: jax.Array, k: jax.Array, v: jax.Array,
                live: jax.Array) -> jax.Array:
        """Scores over live cache positions only: ``live`` is [B, S_kv],
        or [B, W, S_kv] per-lane masks (the chunked mixed step)."""
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
            / jnp.sqrt(jnp.float32(self.hd))
        m = live[:, None, None, :] if live.ndim == 2 else live[:, None]
        s = jnp.where(m, s, masking.NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)

    def _ffn(self, xn: jax.Array, lp: dict, f_live: jax.Array) -> jax.Array:
        fm = masking.slot_mask(self.mx.d_ff_max, f_live, xn.dtype)[:, None]
        h1 = self._mm(xn, lp["w1"], lp["b1"])
        if is_gated(self.template.activation):
            h = activate(self._mm(xn, lp["wg"], lp["bg"]),
                         self.template.activation) * h1
        else:
            h = activate(h1, self.template.activation)
        return self._mm(h * fm, lp["w2"], lp["b2"])

    def _unembed(self, x: jax.Array, table: dict, mid: jax.Array,
                 d_live: jax.Array, v_live: jax.Array) -> jax.Array:
        fn = jax.tree.map(lambda l: l[mid], table["final_norm"])
        xn = self._norm(x, fn, d_live)
        lm = table["lm_head"]
        if isinstance(lm, QTensor):                      # [B, V, D] int8
            lmf = lm.values[mid].astype(jnp.float32) \
                * lm.scale[mid].astype(jnp.float32)
        else:
            lmf = lm[mid].astype(jnp.float32)            # [B, V, D]
        logits = jnp.einsum("bsd,bvd->bsv", xn.astype(jnp.float32), lmf)
        vm = jnp.arange(self.mx.vocab)[None, None, :] < v_live[:, None, None]
        # dead vocab lanes to NEG_INF so per-slot sampling (argmax /
        # categorical) can never pick a token outside the live vocab
        return jnp.where(vm, logits, masking.NEG_INF)

    def _gather_layer(self, table: dict, mid: jax.Array,
                      i: jax.Array) -> dict:
        """Per-slot weights of layer ``i``: [B, ...] gathered by model id."""
        return jax.tree.map(lambda l: l[mid, i], table["layers"])

    # ------------------------------------------------------------------
    # Prefill (B=1, one request) — same masked math at S > 1
    # ------------------------------------------------------------------
    # jit-region
    def prefill(self, table: dict, topo: jax.Array, tokens: jax.Array,
                max_len: int) -> tuple[jax.Array, KVCache]:
        """tokens [1, S] + topo [N_REGS] -> (masked logits [1, S, V_max],
        per-layer cache [L_max, 1, max_len, H_max, hd])."""
        mx = self.mx
        mid = topo[REG_MODEL][None]
        d_live, h_live = topo[REG_DMODEL][None], topo[REG_HEADS][None]
        f_live, v_live = topo[REG_DFF][None], topo[REG_VOCAB][None]
        l_live = topo[REG_LAYERS][None]
        S = tokens.shape[1]
        emb = self._embed_rows(table, mid[0], tokens)
        x = emb * masking.slot_mask(mx.d_model_max, d_live, emb.dtype)[:, None]
        positions = jnp.arange(S, dtype=jnp.int32)[None]
        he = masking.slot_mask(mx.heads_max, h_live)[:, None, :, None] \
            .astype(self.compute_dtype)
        dm = masking.slot_mask(mx.d_model_max, d_live)[:, None] \
            .astype(self.compute_dtype)
        causal = (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])

        def body(h, i):
            lp = self._gather_layer(table, mid, i)
            xn = self._norm(h, lp["ln1"], d_live)
            q, k, v = self._qkv(xn, lp, positions, he)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
                / jnp.sqrt(jnp.float32(self.hd))
            s = jnp.where(causal[None, None], s, masking.NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v) * he
            a = self._mm(o.reshape(1, S, -1), lp["wo"]) * dm
            h1 = h + a
            f = self._ffn(self._norm(h1, lp["ln2"], d_live), lp,
                          f_live) * dm
            h2 = h1 + f
            out = jnp.where((i < l_live)[:, None, None], h2, h)
            pad = ((0, 0), (0, max_len - S), (0, 0), (0, 0))
            kq, ksc = self.codec.store(k, jnp.bfloat16)
            vq, vsc = self.codec.store(v, jnp.bfloat16)
            if ksc is None:
                return out, (jnp.pad(kq, pad), jnp.pad(vq, pad))
            return out, (jnp.pad(kq, pad), jnp.pad(vq, pad),
                         jnp.pad(ksc, pad[:-1]), jnp.pad(vsc, pad[:-1]))

        x, st = jax.lax.scan(body, x, jnp.arange(mx.layers_enc_max))
        return self._unembed(x, table, mid, d_live, v_live), KVCache(*st)

    # ------------------------------------------------------------------
    # Fused decode step (the multi-topology payoff)
    # ------------------------------------------------------------------
    # jit-region
    def decode_step(self, table: dict, cache: KVCache, tokens: jax.Array,
                    index: jax.Array, topo: jax.Array,
                    block_tables: jax.Array | None = None,
                    paged_attn_impl: str = "gather", *,
                    interpret: bool) -> tuple[jax.Array, KVCache]:
        """tokens [B, 1] + per-slot registers topo [B, N_REGS] -> (masked
        logits [B, 1, V_max], new cache).  One topology per slot; register
        values are data, so this traces exactly once."""
        mx = self.mx
        B = tokens.shape[0]
        mid, h_live = topo[:, REG_MODEL], topo[:, REG_HEADS]
        l_live, d_live = topo[:, REG_LAYERS], topo[:, REG_DMODEL]
        f_live, v_live = topo[:, REG_DFF], topo[:, REG_VOCAB]
        idx = jnp.asarray(index, jnp.int32)
        emb = self._embed_rows(table, mid, tokens[:, 0])
        x = (emb * masking.slot_mask(mx.d_model_max, d_live, emb.dtype)
             )[:, None]
        positions = idx[:, None]
        he = masking.slot_mask(mx.heads_max, h_live)[:, None, :, None] \
            .astype(self.compute_dtype)
        dm = masking.slot_mask(mx.d_model_max, d_live)[:, None] \
            .astype(self.compute_dtype)
        if block_tables is not None:
            bs = cache.k.shape[2]
            t_max = block_tables.shape[1] * bs
            blk, off = paged_write_slot(idx, block_tables, bs)
            live = jnp.arange(t_max)[None, :] <= idx[:, None]
        else:
            rows = jnp.arange(B)
            live = jnp.arange(cache.k.shape[2])[None, :] <= idx[:, None]

        def body(h, inp):
            i, c = inp
            lp = self._gather_layer(table, mid, i)
            xn = self._norm(h, lp["ln1"], d_live)
            q, k_new, v_new = self._qkv(xn, lp, positions, he)
            kq, ksc = self.codec.store(k_new[:, 0], c.k.dtype)
            vq, vsc = self.codec.store(v_new[:, 0], c.v.dtype)
            if block_tables is not None:
                k, k_sc = cache_put(c.k, c.k_scale, (blk, off), kq, ksc)
                v, v_sc = cache_put(c.v, c.v_scale, (blk, off), vq, vsc)
                if paged_attn_impl == "pallas":
                    from repro.kernels.paged_attention import \
                        paged_decode_attention
                    lengths = jnp.minimum(idx + 1, t_max)
                    o = paged_decode_attention(
                        q[:, 0], k, v, block_tables, lengths,
                        live_kv=h_live, k_scale=k_sc, v_scale=v_sc,
                        interpret=interpret)[:, None]
                else:
                    shp = (B, t_max, mx.heads_max, self.hd)
                    kg = gather_view(self.codec, k, k_sc, block_tables,
                                     shp, q.dtype)
                    vg = gather_view(self.codec, v, v_sc, block_tables,
                                     shp, q.dtype)
                    o = self._attend(q, kg, vg, live)
            else:
                k, k_sc = cache_put(c.k, c.k_scale, (rows, idx), kq, ksc)
                v, v_sc = cache_put(c.v, c.v_scale, (rows, idx), vq, vsc)
                o = self._attend(q, self.codec.load(k, k_sc, q.dtype),
                                 self.codec.load(v, v_sc, q.dtype), live)
            a = self._mm((o * he).reshape(B, 1, -1), lp["wo"]) * dm
            h1 = h + a
            f = self._ffn(self._norm(h1, lp["ln2"], d_live), lp,
                          f_live) * dm
            h2 = h1 + f
            out = jnp.where((i < l_live)[:, None, None], h2, h)
            return out, KVCache(k, v, k_sc, v_sc)

        x, new_cache = jax.lax.scan(
            body, x, (jnp.arange(mx.layers_enc_max), cache))
        return self._unembed(x, table, mid, d_live, v_live), new_cache

    # ------------------------------------------------------------------
    # Fused mixed chunk/decode step (chunked prefill on the fabric)
    # ------------------------------------------------------------------
    # jit-region
    def mixed_step(self, table: dict, cache: KVCache, tokens: jax.Array,
                   start: jax.Array, n_live: jax.Array, topo: jax.Array,
                   block_tables: jax.Array | None = None,
                   paged_attn_impl: str = "gather", *,
                   interpret: bool) -> tuple[jax.Array, KVCache]:
        """tokens [B, W] + per-slot registers topo [B, N_REGS] -> (masked
        logits [B, W, V_max], new cache).

        The W-lane generalization of ``decode_step``: lane ``l`` of slot
        ``b`` sits at cache position ``start[b] + l`` and only the first
        ``n_live[b]`` lanes are real — a decoding slot uses one lane, a
        prefilling slot a chunk of its prompt, an idle slot none.  Chunk
        K/V are written before the attend, so one causal-vs-cache mask
        covers intra-chunk causality and the prior cache.  Register
        values, lane counts and chunk contents are all data: prefill and
        decode for the whole fleet share this one compilation.

        This same program doubles as the **speculative verify pass**
        (``serving/engine.py``): a decoding slot presents its last
        emitted token plus the draft's ``k`` proposals as ``k + 1``
        live lanes starting at its decode index, and the returned
        per-lane logits score every proposal in one attend.  Nothing
        here is speculation-specific — lane counts are already data —
        which is why fleet members get speculative decoding for free.
        """
        mx = self.mx
        B, W = tokens.shape
        mid, h_live = topo[:, REG_MODEL], topo[:, REG_HEADS]
        l_live, d_live = topo[:, REG_LAYERS], topo[:, REG_DMODEL]
        f_live, v_live = topo[:, REG_DFF], topo[:, REG_VOCAB]
        start = jnp.asarray(start, jnp.int32)
        positions = start[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        emb = self._embed_rows(table, mid[:, None], tokens)
        x = emb * masking.slot_mask(mx.d_model_max, d_live,
                                    emb.dtype)[:, None, :]
        he = masking.slot_mask(mx.heads_max, h_live)[:, None, :, None] \
            .astype(self.compute_dtype)
        dm = masking.slot_mask(mx.d_model_max, d_live)[:, None] \
            .astype(self.compute_dtype)
        lane_live = masking.lane_mask(W, n_live)
        if block_tables is not None:
            bs = cache.k.shape[2]
            t_max = block_tables.shape[1] * bs
            # dead lanes -> index t_max -> the null block absorbs them
            idx_w = jnp.where(lane_live, positions, t_max)
            blk, off = paged_write_slot(idx_w, block_tables, bs)
            live = masking.chunk_causal_mask(t_max, start, W)
        else:
            rows = jnp.arange(B)[:, None]
            s_max = cache.k.shape[2]
            # dead lanes scatter out of bounds and are dropped
            pos = jnp.where(lane_live, positions, s_max)
            live = masking.chunk_causal_mask(s_max, start, W)

        def body(h, inp):
            i, c = inp
            lp = self._gather_layer(table, mid, i)
            xn = self._norm(h, lp["ln1"], d_live)
            q, k_new, v_new = self._qkv(xn, lp, positions, he)
            kq, ksc = self.codec.store(k_new, c.k.dtype)
            vq, vsc = self.codec.store(v_new, c.v.dtype)
            if block_tables is not None:
                k, k_sc = cache_put(c.k, c.k_scale, (blk, off), kq, ksc)
                v, v_sc = cache_put(c.v, c.v_scale, (blk, off), vq, vsc)
                if paged_attn_impl == "pallas":
                    from repro.kernels.chunked_prefill import \
                        chunked_prefill_attention
                    o = chunked_prefill_attention(
                        q, k, v, block_tables, start,
                        live_kv=h_live, k_scale=k_sc, v_scale=v_sc,
                        interpret=interpret)
                else:
                    shp = (B, t_max, mx.heads_max, self.hd)
                    kg = gather_view(self.codec, k, k_sc, block_tables,
                                     shp, q.dtype)
                    vg = gather_view(self.codec, v, v_sc, block_tables,
                                     shp, q.dtype)
                    o = self._attend(q, kg, vg, live)
            else:
                k, k_sc = cache_put(c.k, c.k_scale, (rows, pos), kq, ksc)
                v, v_sc = cache_put(c.v, c.v_scale, (rows, pos), vq, vsc)
                o = self._attend(q, self.codec.load(k, k_sc, q.dtype),
                                 self.codec.load(v, v_sc, q.dtype), live)
            a = self._mm((o * he).reshape(B, W, -1), lp["wo"]) * dm
            h1 = h + a
            f = self._ffn(self._norm(h1, lp["ln2"], d_live), lp,
                          f_live) * dm
            h2 = h1 + f
            out = jnp.where((i < l_live)[:, None, None], h2, h)
            return out, KVCache(k, v, k_sc, v_sc)

        x, new_cache = jax.lax.scan(
            body, x, (jnp.arange(mx.layers_enc_max), cache))
        return self._unembed(x, table, mid, d_live, v_live), new_cache
