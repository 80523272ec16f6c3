"""The analyzer analyzes: each RA rule trips on a seeded violation, the
real tree lints clean, the pallas contracts catch broken geometry, the
jaxpr audit sees callbacks/budgets, the census round-trips, and
strict_jit escalates donation failures under REPRO_STRICT=1."""
import json
import os
import pathlib
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import census as census_mod
from repro.analysis.jaxpr_audit import audit_jaxpr, count_primitives
from repro.analysis.lint import lint_paths, lint_source
from repro.analysis.pallas_contracts import (KernelGeometry,
                                             check_contracts,
                                             check_geometry, trace_kernels)
from repro.core.jitutil import DonationError, strict_jit

REPO = pathlib.Path(__file__).resolve().parents[1]


def _codes(src):
    return [f.code for f in lint_source(textwrap.dedent(src), "t.py")]


# ---------------------------------------------------------------------------
# lint: every rule trips on a seeded violation
# ---------------------------------------------------------------------------
def test_ra001_host_sync_in_jit_region():
    src = """
    import jax
    import numpy as np
    @jax.jit
    def step(x):
        v = float(x)
        a = np.asarray(x)
        jax.device_get(x)
        return x.item()
    """
    assert _codes(src) == ["RA001"] * 4


def test_ra002_traced_python_if():
    src = """
    import jax
    @jax.jit
    def step(x):
        if x > 0:
            x = x + 1
        while x < 5:
            x = x * 2
        return x
    """
    assert _codes(src) == ["RA002", "RA002"]


def test_ra002_structural_tests_are_static():
    src = """
    import jax
    @jax.jit
    def step(params, x, kind):
        if x is None:                 # pytree structure
            return params
        if "dec" in params:           # pytree structure
            x = x + 1
        if kind == "r":               # string config dispatch
            x = x * 2
        if x.shape[0] > 4:            # trace-static metadata
            x = x[:4]
        return x
    """
    assert _codes(src) == []


def test_ra003_use_after_donate():
    src = """
    import jax
    def f(p, c, s):
        return c, s
    step = jax.jit(f, donate_argnums=(1, 2))
    def drive(p, c, s):
        out = step(p, c, s)           # c, s dead but not rebound
        return out, c
    """
    assert _codes(src) == ["RA003"]


def test_ra003_rebinding_is_clean():
    src = """
    import jax
    def f(p, c, s):
        return c, s
    step = jax.jit(f, donate_argnums=(1, 2))
    def drive(p, c, s):
        c, s = step(p, c, s)
        return c, s
    """
    assert _codes(src) == []


def test_ra004_mutable_dataclass_default():
    src = """
    import dataclasses
    import numpy as np
    @dataclasses.dataclass
    class Spec:
        tables: list = []
        scales: dict = {}
        buf = None
        weights: np.ndarray = np.zeros(4)
    """
    assert _codes(src) == ["RA004"] * 3


def test_ra005_per_slot_device_gets():
    src = """
    import jax
    def harvest(state, slot):
        n = jax.device_get(state.count[slot])
        row = jax.device_get(state.buf[slot])
        return n, row
    """
    assert _codes(src) == ["RA005"] * 2


def test_ra005_single_bulk_get_is_clean():
    src = """
    import jax
    def harvest(state, slot):
        n, row = jax.device_get((state.count[slot], state.buf[slot]))
        return n, row
    """
    assert _codes(src) == []


def test_suppression_comment():
    src = """
    import jax
    @jax.jit
    def step(x):
        return float(x)  # ra: ignore[RA001]
    """
    assert _codes(src) == []


def test_static_argnames_are_not_traced():
    src = """
    import functools
    import jax
    @functools.partial(jax.jit, static_argnames=("causal",))
    def step(x, causal):
        if causal:
            x = x + 1
        return x
    """
    assert _codes(src) == []


def test_jit_region_marker():
    src = """
    # jit-region
    def inner_step(x):
        return float(x)
    """
    assert _codes(src) == ["RA001"]


def test_shard_map_body_is_a_jit_region():
    # a shard_map body runs inside jit on every mesh device — host
    # round-trips and python-controlled branches there are real traps
    src = """
    import functools
    from jax import shard_map
    def _body(mesh, x):
        n = float(x.sum())
        return x / n
    def run(mesh, specs, x):
        return shard_map(functools.partial(_body, mesh), mesh=mesh,
                         in_specs=specs, out_specs=specs)(x)
    """
    assert _codes(src) == ["RA001"]


def test_shard_map_decorator_form_is_a_jit_region():
    src = """
    import functools
    from jax import shard_map
    import numpy as np
    @functools.partial(shard_map, mesh=None, in_specs=(), out_specs=())
    def body(x):
        return np.asarray(x)
    """
    assert _codes(src) == ["RA001"]


def test_pallas_partial_bound_args_are_static():
    src = """
    import functools
    from jax.experimental import pallas as pl
    def _kernel(scale, quantized, x_ref, o_ref):
        if quantized:
            o_ref[...] = x_ref[...] * scale
        else:
            o_ref[...] = x_ref[...]
    def run(x):
        return pl.pallas_call(functools.partial(_kernel, 2.0, True),
                              out_shape=x)(x)
    """
    assert _codes(src) == []


def test_tree_is_clean():
    findings = lint_paths(REPO / "src" / "repro")
    assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# pallas contracts
# ---------------------------------------------------------------------------
GEO = KernelGeometry(num_heads=4, num_kv_heads=2, head_dim=16,
                     max_batch=2, max_len=32, block_size=8, num_blocks=8)


def test_contracts_hold_on_serving_geometry():
    assert check_geometry(GEO) == []
    assert trace_kernels(GEO) == []


def test_contracts_catch_bad_head_grouping():
    import dataclasses
    bad = dataclasses.replace(GEO, num_heads=5)
    assert any("not a multiple" in v for v in check_geometry(bad))


def test_contracts_catch_starved_pool():
    import dataclasses
    bad = dataclasses.replace(GEO, num_blocks=2)   # max_len needs 4
    assert any("could never be admitted" in v for v in check_geometry(bad))


def test_contracts_catch_vmem_blowup():
    import dataclasses
    bad = dataclasses.replace(GEO, head_dim=8192, block_size=512)
    assert any("VMEM" in v for v in check_geometry(bad))


def test_check_contracts_aggregates():
    import dataclasses
    bad = dataclasses.replace(GEO, num_heads=5)
    out = check_contracts({"ok": GEO, "bad": bad}, trace=False)
    assert list(out) == ["bad"]


# ---------------------------------------------------------------------------
# jaxpr audit
# ---------------------------------------------------------------------------
def test_audit_flags_callbacks():
    def step(x):
        jax.debug.callback(lambda v: None, x)
        return x + 1

    jaxpr = jax.make_jaxpr(step)(jnp.ones(4))
    assert any("callback" in v for v in audit_jaxpr(jaxpr))


def test_audit_budget():
    jaxpr = jax.make_jaxpr(lambda x: x * 2 + 1)(jnp.ones(4))
    n = count_primitives(jaxpr)
    assert audit_jaxpr(jaxpr, budget=n) == []
    assert any("budget" in v for v in audit_jaxpr(jaxpr, budget=n - 1))


def test_audit_clean_step_passes():
    jaxpr = jax.make_jaxpr(
        lambda x: jax.lax.scan(lambda c, t: (c + t, c), 0.0, x)[0]
    )(jnp.ones(8))
    assert audit_jaxpr(jaxpr, budget=50) == []


# ---------------------------------------------------------------------------
# census round trip (the two cheapest matrix points)
# ---------------------------------------------------------------------------
SMALL = ["gqa-dense-xla-bucketed", "gqa-dense-xla-chunked"]


def test_census_round_trip():
    report = census_mod.run_census(SMALL)
    for name, rec in report["points"].items():
        assert "violation" not in rec, (name, rec)
        assert rec["compilations"]["decode"] == 1, (name, rec)
    # self-compare: no diffs
    assert census_mod.compare(report, report, subset=True) == []
    # a grown compile count is a diff
    tampered = json.loads(json.dumps(report))
    tampered["points"][SMALL[0]]["compilations"]["decode"] = 2
    diffs = census_mod.compare(tampered, report, subset=True)
    assert any("compile counts" in d for d in diffs)
    # a lowering swap on the same jax version is a diff
    tampered = json.loads(json.dumps(report))
    tampered["points"][SMALL[1]]["fingerprint"] = "0" * 16
    diffs = census_mod.compare(tampered, report, subset=True)
    assert any("fingerprint" in d for d in diffs)
    # ... but not across jax versions (lowering drift is not ours)
    tampered["jax_version"] = "0.0.0"
    assert census_mod.compare(tampered, report, subset=True) == []


def test_committed_baseline_covers_matrix():
    baseline = census_mod.load_baseline()
    assert baseline is not None, \
        "ANALYSIS.json missing — python -m repro.analysis --update-baseline"
    names = {p.name for p in census_mod.support_matrix()}
    assert set(baseline["points"]) == names
    for name, rec in baseline["points"].items():
        assert rec["compilations"]["decode"] == 1, name


# ---------------------------------------------------------------------------
# strict donation escalation (satellite of the same invariant)
# ---------------------------------------------------------------------------
def test_strict_jit_raises_on_unusable_donation():
    assert os.environ.get("REPRO_STRICT") == "1"
    # a 2-byte output cannot reuse a 4-byte donated buffer; XLA drops the
    # alias without a warning, so only the alias table shows it
    f = strict_jit(lambda x: x.astype(jnp.bfloat16), donate_argnums=(0,))
    with pytest.raises(DonationError, match="float32"):
        f(jnp.ones((8,), jnp.float32))


def test_strict_jit_passes_clean_donation():
    f = strict_jit(lambda x: x + 1, donate_argnums=(0,))
    out = f(jnp.ones((8,), jnp.float32))
    assert out[0] == 2.0
    assert f._cache_size() == 1


def test_strict_jit_off_by_default(monkeypatch):
    monkeypatch.setenv("REPRO_STRICT", "0")
    f = strict_jit(lambda x: x.astype(jnp.bfloat16), donate_argnums=(0,))
    out = f(jnp.ones((8,), jnp.float32))    # unaliased, but must not raise
    assert out.dtype == jnp.bfloat16
