"""DeepSeek-V3 on the CPU at a tiny size against its plain reference.

A DeepSeek-V3-shaped configuration (1 dense + 2 MoE layers, 16 experts
in 4 groups, top 2 groups and top 4 experts, 4 held from expert 4, YaRN
over 16 original positions, a 512-token vocabulary) is drawn from a
seed by the family module and served through ``ServingEngine``: paged,
prompts prefilled in chunks by the mixed step, then decoded.  The
logits each served token was sampled from are compared with
``bench/reference/deepseek_v3.py`` run over the same sequence.
"""
import sys
from pathlib import Path

# the benchmark lives beside src/, outside the package path
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.manifest import family_module, load_cell, reference_module
from repro.core import kv_quant
from repro.models import moe
from repro.models.model import Model
from repro.serving import engine as engine_mod
from repro.serving.engine import ServingEngine
from repro.serving.sampling import SamplingParams

CELL = "deepseek-v3-l7.longgen.batch"
SEED = 2**31 + 77
TINY = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            num_hidden_layers=3, first_k_dense_replace=1,
            n_routed_experts=4, expert_offset=4, n_group=4, topk_group=2,
            num_experts_per_tok=4, vocab_size=512)
PROMPTS = [list(range(3, 40)), [5, 9, 2], list(range(100, 121)),
           [7] * 18 + [11, 12]]
NEW = 12
# Float32 served against the float32 reference.  The program keeps its
# latent cache in bf16 whatever the compute dtype (the "compute" codec of
# core.kv_quant); the float32 run keeps it in float32 here, so that what
# is compared is the program's math: its absorbed latent attention,
# chunked prefill and grouped expert matmuls sum in other orders than
# the reference's naive forward, which moves logits of magnitude ~2 by at
# most 3.5e-6 (seeds 2**31 + 77..80).  1e-4 leaves thirty times that;
# bf16 compute with its bf16 cache reads 0.035-1.2.
LOGIT_TOL = 1e-4

FAM = family_module("deepseek_v3")
REF = reference_module("deepseek_v3")


def tiny_config(dtype="fp32", **over):
    cfg = dict(load_cell(CELL).config, **TINY)
    cfg["published"] = dict(cfg["published"], n_routed_experts=16)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=16)
    cfg["serving"] = dict(cfg["serving"], param_dtype=dtype,
                          compute_dtype=dtype, kv_dtype=dtype, max_batch=4,
                          max_len=128)
    cfg.update(over)
    return cfg


def test_the_cell_names_a_chips_share():
    cfg = load_cell(CELL).config
    assert cfg["published"]["n_routed_experts"] == 256
    spec = FAM.spec(cfg, None)
    m = spec.arch.moe
    assert (m.num_experts, m.n_held, m.expert_offset) == (256, 8, 0)
    assert spec.arch.rope_scaling.factor == 40
    assert FAM.bytes(cfg) == cfg["bytes"]
    # 4.32 B parameters (PERF.md, section 4)
    assert cfg["bytes"]["params"] == 2 * 4_323_401_728


def test_the_weights_fit_the_program_tree():
    cfg = tiny_config()
    w = jax.eval_shape(lambda k: FAM.make(cfg, k, jnp.float32),
                       weights.seed_key(SEED))
    weights.check_tree(Model.from_spec(FAM.spec(cfg, None)).abstract(),
                       jax.eval_shape(lambda w: FAM.to_program(cfg, w), w))


def _serve(cfg, monkeypatch):
    """Serve PROMPTS greedily; returns uid -> (prompt, generated, the
    logits row each generated token was sampled from)."""
    rows = []
    sample = engine_mod.sample_per_slot

    def recording(logits, *args):
        jax.debug.callback(lambda lg: rows.append(np.asarray(lg)), logits)
        return sample(logits, *args)

    monkeypatch.setattr(engine_mod, "sample_per_slot", recording)
    spec = FAM.spec(cfg, None)
    eng = ServingEngine(spec, sampling=SamplingParams(temperature=0.0))
    w = weights.from_seed(FAM, cfg, SEED)
    eng.load(FAM.to_program(cfg, w))
    uids = [eng.submit(p, max_new_tokens=NEW) for p in PROMPTS]
    got = {u: [] for u in uids}
    seen: dict[int, int] = {}
    done = {}
    while eng.queue or any(r is not None for r in eng.slot_req):
        n0 = len(rows)
        finished = eng.step()
        assert len(rows) == n0 + 1            # one sampled row a step
        done.update((r.uid, r.generated) for r in finished)
        # a slot emits at most one token a step; a finished request's
        # slot keeps its count until the slot is seated again
        count = np.asarray(jax.device_get(eng.state.count))
        for b, req in enumerate(eng.slot_req):
            req = req or next((r for r in finished if r.slot == b), None)
            if req is not None and count[b] > seen.get(req.uid, 0):
                got[req.uid].append(rows[-1][b])
                seen[req.uid] = int(count[b])
    return w, {u: (p, done[u], np.stack(got[u]))
               for u, p in zip(uids, PROMPTS)}


def _reference_logits(cfg, w, prompt, generated):
    seq = jnp.asarray(prompt + generated[:-1], jnp.int32)
    lg = REF.logits(cfg, jax.tree.map(lambda a: a.astype(jnp.float32), w),
                    seq, jnp.arange(seq.shape[0]),
                    jnp.zeros(seq.shape[0], jnp.int32))
    return np.asarray(lg)[len(prompt) - 1:]


@pytest.mark.parametrize("dtype", ["fp32", "bf16"])
def test_served_logits_match_the_reference(dtype, monkeypatch):
    """Prefill in chunks, then decode: the logits of every served token
    lie within LOGIT_TOL of the reference's in float32; in bf16 they do
    not."""
    if dtype == "fp32":
        monkeypatch.setattr(kv_quant.CacheCodec, "storage_dtype",
                            lambda self, compute_dtype=None: jnp.float32)
    cfg = tiny_config(dtype)
    w, served = _serve(cfg, monkeypatch)
    worst = 0.0
    for prompt, generated, rows in served.values():
        assert len(generated) == NEW and rows.shape == (NEW, 512)
        ref = _reference_logits(cfg, w, prompt, generated)
        worst = max(worst, float(np.abs(rows - ref).max()))
        if dtype == "fp32":
            assert generated == list(ref.argmax(-1))
    assert (worst < LOGIT_TOL) == (dtype == "fp32"), worst


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of an EP4 deployment each hold 4 of the 16 experts:
    the program's layer on each, with the shared expert (every chip's
    alike) counted once, adds up to the reference's layer holding all
    16."""
    cfg = tiny_config(n_routed_experts=16, expert_offset=0)
    w = weights.from_seed(FAM, cfg, SEED)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 40, TINY["hidden_size"]))
    whole = np.asarray(REF._moe(cfg, REF.layer_weights(cfg, w, 1), x[0]))
    arch = FAM.spec(cfg, None).arch
    p = FAM.to_program(cfg, w)["layers"]["moe"]
    p0 = jax.tree.map(lambda a: a[0], p)
    total = 0.0
    for share in range(4):
        sl = slice(4 * share, 4 * share + 4)
        m = dataclasses.replace(arch.moe, expert_offset=4 * share,
                                held_experts=4)
        held = dict(p0, w1=p0["w1"][sl], wg=p0["wg"][sl], w2=p0["w2"][sl])
        out, _ = moe.apply_moe(x, held, dataclasses.replace(arch, moe=m))
        total = total + np.asarray(out)[0]
    m = dataclasses.replace(arch.moe, held_experts=16)
    shared, counts = moe.apply_moe(x, p0, dataclasses.replace(arch, moe=m),
                                   jnp.zeros((1, 40), bool))
    assert counts.tolist() == [0, 0]
    total = total - 3 * np.asarray(shared)[0]
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)


def test_program_router_matches_the_reference_router():
    cfg = tiny_config(n_routed_experts=16, expert_offset=0)
    w = weights.from_seed(FAM, cfg, SEED)
    x = jax.random.normal(jax.random.PRNGKey(4), (64, TINY["hidden_size"]))
    rw, ri = REF.route(cfg, x, w["moe.router"][1], w["moe.router_bias"][1])
    arch = FAM.spec(cfg, None).arch
    pw, pi = moe.route(x, {"router": w["moe.router"][1],
                           "router_bias": w["moe.router_bias"][1]}, arch.moe)
    o, po = np.argsort(np.asarray(ri), 1), np.argsort(np.asarray(pi), 1)
    np.testing.assert_array_equal(np.take_along_axis(np.asarray(ri), o, 1),
                                  np.take_along_axis(np.asarray(pi), po, 1))
    np.testing.assert_allclose(np.take_along_axis(np.asarray(rw), o, 1),
                               np.take_along_axis(np.asarray(pw), po, 1),
                               rtol=1e-5)


def test_the_harness_serves_a_tiny_cell_correctly():
    """``run.run_cell`` past the chip check on the tiny configuration:
    the same engine, backlog loop, sampling and reference comparison as
    a chip run.  Sound bf16 runs read mean gaps of 0.0007-0.0051 over
    seeds 2**31 + 77..82 and repeats (which requests finish inside a 3 s
    CPU window varies with the host's speed); the limit is four times
    the largest.  At this size the int8 control does not separate (most
    weights fall under the quantization floor), so it is not asserted:
    the chip cell's limits come from chip readings (PERF.md)."""
    from bench import run
    from bench.manifest import Cell

    base = load_cell(CELL)
    cfg = tiny_config("bf16")
    cfg["check"] = {"min_served_tokens": 256, "max_requests": 16}
    mix = {"loop": "batch", "open": "prefilled", "requests": 400,
           "output_points": 6,
           "prompt": {"median": 24, "sigma": 0.5, "min": 4, "max": 64},
           "output": {"median": 12, "sigma": 0.5, "min": 2, "max": 40}}
    cell = Cell(base.name, 1, cfg, mix, base.end_to_end, base.per_layer,
                {"mean_logit_gap": 0.02})
    res, checks, _ = run.run_cell(cell, SEED, 3.0, False, "cpu",
                                  peaks={"bf16_flops": 1e12})
    assert res["correct"], checks
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
    assert checks["window_compiles"]["value"] == 0
