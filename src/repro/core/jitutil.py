"""``strict_jit``: ``jax.jit`` whose donation failures are loud.

Every fused serving/training step donates its big buffers
(``donate_argnums``) so XLA aliases them in place instead of copying a
KV pool per token.  When a refactor silently breaks the aliasing — an
output stops matching a donated input's shape/dtype, or a donated value
gets captured as a constant — the compiled program simply has no alias
for that input and the step quietly doubles its memory traffic.  XLA
warns about some of these cases and not others (a dtype change of equal
byte width is aliased; one of a different width is dropped without a
word), so the check reads the compiled program itself.

``strict_jit`` is a drop-in ``jax.jit`` wrapper.  When ``REPRO_STRICT=1``
is set in the environment (the test suite sets it, see
``tests/conftest.py``), every call that compiles a new executable reads
that executable's input/output alias table and raises ``DonationError``
if a donated input the program keeps is not aliased to any output.
Outside strict mode the wrapper is a transparent passthrough.

The wrapper forwards every attribute of the underlying jitted callable
(``lower``, ``_cache_size``, ...), so compile-count accounting and the
jaxpr audit (``repro.analysis``) see it as a plain jit.
"""
from __future__ import annotations

import os
import re
from typing import Any, Callable

import jax

# One entry of the HLO module's ``input_output_alias={ ... }`` table:
# ``{output index}: (parameter number, {parameter index}, kind)``.
_ALIAS_ENTRY = re.compile(r"\((\d+), \{[^}]*\}, (?:may|must)-alias\)")


def strict_enabled() -> bool:
    """True when REPRO_STRICT=1 asks for donation failures to raise.

    Read per call (not cached) so a test can flip the env var.
    """
    return os.environ.get("REPRO_STRICT", "0") == "1"


class DonationError(RuntimeError):
    """A buffer listed in ``donate_argnums`` was not actually donated."""


def unaliased_donations(compiled: Any) -> list[str]:
    """Donated inputs of a ``jax.stages.Compiled`` that its program does
    not alias to an output, as ``dtype[shape]`` strings (empty when every
    donation was applied).  Inputs the program prunes as unused are not counted:
    the program reads nothing from them, so there is no copy to save."""
    is_none = lambda x: x is None
    infos = jax.tree.leaves(compiled.args_info)
    shardings = jax.tree.leaves(compiled.input_shardings, is_leaf=is_none)
    kept = [info for info, sh in zip(infos, shardings) if sh is not None]
    header = compiled.as_text().split("\n", 1)[0]
    table = header.partition("input_output_alias=")[2]
    aliased = {int(m) for m in _ALIAS_ENTRY.findall(table)}
    return [f"{info.dtype}{list(info.shape)}" for i, info in enumerate(kept)
            if info.donated and i not in aliased]


class _StrictJit:
    """Callable wrapper checking donation on every compiling call.

    Only a call that grows the jit cache pays for the check: it lowers
    the same signature again, which returns the executable the call just
    compiled, and reads its alias table.  Cached-executable calls cost
    one ``_cache_size()`` read in strict mode and nothing outside it.
    """

    def __init__(self, jitted: Any, label: str):
        self._jitted = jitted
        self._label = label

    def __call__(self, *args, **kwargs):
        if not strict_enabled():
            return self._jitted(*args, **kwargs)
        before = self._jitted._cache_size()
        out = self._jitted(*args, **kwargs)
        if self._jitted._cache_size() > before:
            # lowering reads only avals, so donated (deleted) args are fine
            bad = unaliased_donations(
                self._jitted.lower(*args, **kwargs).compile())
            if bad:
                raise DonationError(
                    f"{self._label}: buffer donation was requested but not "
                    f"applied to {', '.join(bad)} (no entry in the compiled "
                    "program's input/output alias table) — a fused step "
                    "that stops aliasing its donated buffers silently "
                    "copies them every dispatch; make the output shapes/"
                    "dtypes match the donated inputs or drop the argnum "
                    "from donate_argnums")
        return out

    def __getattr__(self, name: str):
        return getattr(self._jitted, name)


def strict_jit(fun: Callable, *, donate_argnums=(), **jit_kwargs):
    """``jax.jit`` with donation failures raised under REPRO_STRICT=1.

    Drop-in at every ``donate_argnums`` site; the returned object
    forwards ``lower``/``_cache_size``/... to the underlying jit.
    """
    jitted = jax.jit(fun, donate_argnums=donate_argnums, **jit_kwargs)
    label = getattr(fun, "__qualname__", getattr(fun, "__name__", repr(fun)))
    return _StrictJit(jitted, label)
