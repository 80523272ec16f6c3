"""The serving gold invariant: prefill(prompt) + token-by-token decode
must reproduce the full-sequence forward, for every cached family."""
import jax
import jax.numpy as jnp
import pytest

from conftest import reduced_cfg
from repro.models.model import Model

FAMS = ["qwen1.5-0.5b", "granite-moe-1b-a400m", "deepseek-v3-671b",
        "falcon-mamba-7b", "recurrentgemma-2b", "whisper-medium",
        "phi-3-vision-4.2b"]

# absorbed-MLA decode is a different (more accurate) contraction order;
# bf16 rounding differs from the naive prefill path by ~1%
TOL = {"deepseek-v3-671b": 5e-2}


def _inputs(cfg, B=2, S=10):
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.encdec is not None:
        batch["frontend"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (B, cfg.encdec.encoder_seq_len, cfg.d_model)).astype(jnp.bfloat16)
    elif cfg.frontend is not None:
        batch["frontend"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (B, cfg.frontend.num_tokens, cfg.d_model)).astype(jnp.bfloat16)
    return batch


# default tier-1 runs a reduced sweep (fast cache families, 2 decode
# steps); the full 7-family x 5-step sweep runs under ``-m slow``
FAST_FAMS = ["qwen1.5-0.5b", "falcon-mamba-7b", "granite-moe-1b-a400m",
             "recurrentgemma-2b"]


def _check_prefill_then_decode(name: str, steps: int) -> None:
    cfg = reduced_cfg(name)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, P = 2, 5
    if cfg.frontend is not None and cfg.encdec is None:
        # vlm: the prompt must cover the patch-embedding positions
        P = max(P, cfg.frontend.num_tokens)
    S = P + steps
    batch = _inputs(cfg, B, S)
    full = model.forward(params, batch)
    scale = float(jnp.abs(full).max()) + 1e-6

    pb = dict(batch)
    pb["tokens"] = batch["tokens"][:, :P]
    logits, cache = model.prefill(params, pb, max_len=S)
    tol = TOL.get(name, 2e-2) * scale
    assert float(jnp.max(jnp.abs(logits - full[:, :P]))) < tol
    for t in range(P, S):
        lg, cache = model.decode_step(params, cache,
                                      batch["tokens"][:, t:t + 1],
                                      jnp.int32(t))
        err = float(jnp.max(jnp.abs(lg[:, 0] - full[:, t])))
        assert err < tol, f"{name} step {t}: {err} vs {tol}"


@pytest.mark.parametrize("name", FAST_FAMS)
def test_prefill_then_decode_matches_forward(name):
    _check_prefill_then_decode(name, steps=2)


@pytest.mark.slow
@pytest.mark.parametrize("name", FAMS)
def test_prefill_then_decode_matches_forward_full(name):
    _check_prefill_then_decode(name, steps=5)


def test_per_slot_vector_indices():
    """Decode with a [B] index vector at different depths must equal two
    independent single-sequence decodes."""
    cfg = reduced_cfg("qwen1.5-0.5b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    S = 12
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, S), 0,
                              cfg.vocab_size)
    # sequence 0 prefilled to 6, sequence 1 prefilled to 3
    _, c0 = model.prefill(params, {"tokens": toks[:1, :6]}, max_len=S)
    _, c1 = model.prefill(params, {"tokens": toks[1:, :3]}, max_len=S)
    cache = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=1), c0, c1)
    step_toks = jnp.stack([toks[0, 6:7], toks[1, 3:4]])
    lg, _ = model.decode_step(params, cache, step_toks,
                              jnp.array([6, 3], jnp.int32))
    full = model.forward(params, {"tokens": toks})
    scale = float(jnp.abs(full).max())
    assert float(jnp.abs(lg[0, 0] - full[0, 6]).max()) < 2e-2 * scale
    assert float(jnp.abs(lg[1, 0] - full[1, 3]).max()) < 2e-2 * scale


def test_rolling_window_longer_than_buffer():
    """Hybrid local attention: decode past the window must match forward
    (the rolling buffer drops exactly the out-of-window tokens)."""
    cfg = reduced_cfg("recurrentgemma-2b")  # window 32 in reduced
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    S = 12
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, S), 0,
                              cfg.vocab_size)
    full = model.forward(params, {"tokens": toks})
    cache = model.init_cache(1, S)
    errs = []
    for t in range(S):
        lg, cache = model.decode_step(params, cache, toks[:, t:t + 1],
                                      jnp.int32(t))
        errs.append(float(jnp.max(jnp.abs(lg[0, 0] - full[0, t]))))
    scale = float(jnp.abs(full).max())
    assert max(errs) < 2e-2 * scale


def _pallas_kernels(jaxpr) -> set[str]:
    """Names of the Pallas kernels a traced program calls, nested jaxprs
    included."""
    names = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.add(eqn.params["name"])
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names |= _pallas_kernels(sub)
    return names


@pytest.mark.parametrize("fleet", [False, True], ids=["model", "fabric"])
@pytest.mark.parametrize("program,kernel", [
    ("decode", "paged_decode_attention"),
    ("mixed", "chunked_prefill_attention")])
def test_pallas_kernel_follows_the_lane_width(fleet, program, kernel):
    """Under ``paged_attn_impl="pallas"`` the decode step (one lane a
    slot) runs the paged-decode kernel and the mixed step (``chunk_size``
    lanes) the chunked-prefill kernel, in the model and in the fabric."""
    from repro.core.spec import (ExecutionSpec, MemorySpec, RuntimeSpec,
                                 SchedulerSpec, maxima_for)
    from repro.serving.engine import ServingEngine
    from repro.serving.sampling import SamplingParams

    cfg = reduced_cfg("qwen1.5-0.5b")
    b = 2
    eng = ServingEngine(RuntimeSpec(
        arch=cfg, maxima=maxima_for(cfg, seq_max=32) if fleet else None,
        execution=ExecutionSpec(paged_attn_impl="pallas"),
        memory=MemorySpec(cache_layout="paged", max_batch=b, max_len=32,
                          block_size=8),
        scheduler=SchedulerSpec(policy="chunked", chunk_size=8)),
        sampling=SamplingParams(), **({"max_models": 1} if fleet else {}))
    eng.load(Model(cfg).init(jax.random.PRNGKey(0)))
    args = (eng.params, eng.cache, eng.state, eng.block_tables)
    if program == "mixed":
        jaxpr = jax.make_jaxpr(eng._mixed_impl)(*args,
                                                jnp.ones((b,), jnp.int32))
    else:
        jaxpr = jax.make_jaxpr(eng._decode_impl)(*args)
    assert _pallas_kernels(jaxpr.jaxpr) == {kernel}


def _decode_writes(name, layout, kv_dtype):
    """One decode step in which slot 0 is live and slot 1 is not: (cache
    before, cache after, block tables or None, expert counts, and the
    expert counts of slot 0 decoding alone, None without experts)."""
    from repro.core.paging import PagingConfig
    from repro.models.model import ModelOptions

    cfg = reduced_cfg(name)
    model = Model(cfg, ModelOptions(kv_dtype=kv_dtype))
    params = model.init(jax.random.PRNGKey(0))
    b, s, bs = 2, 16, 4
    paging = PagingConfig(block_size=bs, num_blocks=b * s // bs) \
        if layout == "paged" else None
    tables = jnp.arange(1, 1 + b * s // bs, dtype=jnp.int32).reshape(b, -1) \
        if paging else None
    cache = model.init_cache(b, s, paging=paging)
    toks = jnp.array([[3], [5]], jnp.int32)
    out = model.decode_step(params, cache, toks, jnp.array([6, 9], jnp.int32),
                            block_tables=tables,
                            live=jnp.array([True, False]), expert_counts=True)
    alone = None
    if cfg.moe is not None:
        one = model.init_cache(1, s, paging=paging)
        alone = model.decode_step(params, one, toks[:1],
                                  jnp.array([6], jnp.int32),
                                  block_tables=None if paging is None
                                  else tables[:1], expert_counts=True)[2]
    return cache, out[1], tables, out[2], alone


@pytest.mark.parametrize("kv_dtype", ["compute", "int8"])
@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.parametrize("name", ["qwen1.5-0.5b", "deepseek-v3-671b"])
def test_slot_that_is_not_live_writes_nothing(name, layout, kv_dtype):
    """A slot that is not live in a decode step leaves its cache rows
    as they were (its lane is dead: dropped on the dense layout, sent to
    the null block on the paged one), the live slot writes its row, and
    the MoE layers count the live slot's experts alone."""
    before, after, tables, counts, alone = _decode_writes(name, layout,
                                                          kv_dtype)
    def rows(leaf, b):
        """Slot b's rows of a dense [L, B, S, ...] or pooled [L, NB, bs,
        ...] cache leaf."""
        return leaf[:, b] if tables is None else leaf[:, tables[b]]

    for old, new in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        assert bool(jnp.array_equal(rows(new, 1), rows(old, 1)))
        assert not bool(jnp.array_equal(rows(new, 0), rows(old, 0)))
    if alone is not None:
        assert counts.tolist() == alone.tolist()
