"""Jaxpr audit: what the fused steps are allowed to lower to.

The lint pass reads *source*; this pass reads the *trace*.  For audited
matrix points (see :mod:`repro.analysis.census`) the fused decode step
is traced with ``jax.make_jaxpr`` on the engine's real buffers — no
execution — and the closed jaxpr is walked recursively:

* **no callback primitives** — ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` and friends each punch a host round trip into the
  device step, exactly the class of bug rules RA001/RA005 catch in
  source form.  A callback that reaches the jaxpr got past the linter.
* **no f64 promotion** — serving math is bf16/f32 (and int8 codecs); a
  float64 aval anywhere means a Python float leaked into an op without
  ``jnp.asarray(..., dtype)`` and doubled that tensor's bandwidth.
* **primitive-count budget** — the flattened equation count of each
  audited step must stay under a per-point budget (generous ~2x
  headroom over the measured count).  The budget catches quadratic
  trace blowups (an unrolled Python loop over layers or slots) long
  before they show up as compile-time regressions.
* **donation applied** — the step is ``.lower().compile()``d and every
  donated input must appear in the executable's input/output alias
  table (the KV cache and SlotState must alias, not copy — the same
  check ``core.jitutil.strict_jit`` enforces at runtime under
  ``REPRO_STRICT=1``).
"""
from __future__ import annotations

from typing import Any, Iterable

from repro.analysis.census import MatrixPoint, _point_by_name, build_engine
from repro.core.jitutil import unaliased_donations

# Primitives that re-enter Python from inside a traced computation.
CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "python_callback",
    "callback", "host_callback_call", "outside_call",
})

# Flattened equation budgets per audited point (measured count ~half).
DEFAULT_BUDGETS: dict[str, int] = {
    "gqa-dense-xla-bucketed": 700,     # measured 332
    "gqa-paged-xla-chunked": 800,      # measured 383
    "gqa-paged-int8kv-chunked": 950,   # measured 453
    "mla-dense-xla-chunked": 1400,     # measured 688
}

# The cheap subset the audit drives by default (each exercises a
# different lowering family: dense, paged, int8 codec, MLA).
AUDITED_POINTS = tuple(DEFAULT_BUDGETS)


def _sub_jaxprs(params: dict[str, Any]) -> Iterable[Any]:
    for v in params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for item in vals:
            if hasattr(item, "jaxpr"):        # ClosedJaxpr
                yield item.jaxpr
            elif hasattr(item, "eqns"):       # raw Jaxpr
                yield item


def walk_eqns(jaxpr) -> Iterable[Any]:
    """Every equation in the jaxpr and all nested sub-jaxprs."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)    # unwrap ClosedJaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from walk_eqns(sub)


def count_primitives(jaxpr) -> int:
    return sum(1 for _ in walk_eqns(jaxpr))


def audit_jaxpr(jaxpr, *, budget: int | None = None,
                label: str = "step") -> list[str]:
    """Callback / f64 / budget violations of one closed jaxpr."""
    violations: list[str] = []
    callbacks: set[str] = set()
    f64_ops: set[str] = set()
    n = 0
    for eqn in walk_eqns(jaxpr):
        n += 1
        name = eqn.primitive.name
        if name in CALLBACK_PRIMITIVES or "callback" in name:
            callbacks.add(name)
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None and str(getattr(aval, "dtype", "")) \
                    == "float64":
                f64_ops.add(name)
    if callbacks:
        violations.append(
            f"{label}: callback primitives in the traced step: "
            f"{sorted(callbacks)} — host round trips inside the fused "
            "program")
    if f64_ops:
        violations.append(
            f"{label}: float64 avals produced by {sorted(f64_ops)} — a "
            "Python float promoted the compute dtype")
    if budget is not None and n > budget:
        violations.append(
            f"{label}: {n} primitives exceeds the {budget} budget — "
            "trace blowup (unrolled loop?)")
    return violations


def audit_donation(eng) -> list[str]:
    """Compile the fused decode step and check its alias table."""
    compiled = eng._decode.lower(eng.params, eng.cache, eng.state,
                                 eng.block_tables).compile()
    return [f"decode: donation not applied: {a}"
            for a in unaliased_donations(compiled)]


def audit_point(name: str, *, budget: int | None = None) -> list[str]:
    """Full audit of one census matrix point (trace + compile)."""
    import jax

    budget = budget if budget is not None else DEFAULT_BUDGETS.get(name)
    eng = build_engine(_point_by_name(name))
    jaxpr = jax.make_jaxpr(eng._decode_impl)(
        eng.params, eng.cache, eng.state, eng.block_tables)
    violations = audit_jaxpr(jaxpr, budget=budget, label=f"{name}/decode")
    violations += [f"{name}/{v}" for v in audit_donation(eng)]
    return violations


def run_audit(names: Iterable[str] | None = None,
              progress=None) -> dict[str, list[str]]:
    """Audit the default (or given) points; {name: violations} for
    the points that failed."""
    bad: dict[str, list[str]] = {}
    for name in (names or AUDITED_POINTS):
        if progress:
            progress(name)
        v = audit_point(name)
        if v:
            bad[name] = v
    return bad
