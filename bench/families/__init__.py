"""One module per architecture family: what the harness knows of a block.

A configuration names its family under ``reference``.  The harness loads
``bench/families/<name>.py`` for the program's side and
``bench/reference/<name>.py``, which imports nothing of the program, for
the plain reference.  A family module gives:

* ``spec(cfg, control) -> RuntimeSpec``: the serving program's spec,
  the architecture's part of it with ``runtime_spec`` below around it;
* ``shapes(cfg)``: name -> (shape, kind) of every weight, the names the
  reference reads; ``make(cfg, key, dtype)``: the weights, drawn from
  ``key`` (``bench.weights.draw``); ``to_program(cfg, w)``: the same
  arrays in the program's parameter tree;
* ``bytes(cfg)``: the configuration's ``bytes`` section (``params``,
  ``kv_per_token``, ``kv_pool``) worked out from its sizes;
* ``matmul_per_token(cfg)``, ``attention_per_key(cfg)``, ``head(cfg)``:
  the model FLOPs that ``bench.flops`` counts per served token.

A new architecture is a family module, a reference, a configuration
file that names them, limits and manifest entries: no file here changes.
This file holds what every family shares.
"""
from __future__ import annotations

FLOAT_KV = ("bf16", "fp32")


def kv_codec(serving: dict) -> str:
    """``MemorySpec.kv_dtype`` of the configuration's ``serving.kv_dtype``:
    a float cache is kept in the compute dtype, ``int8`` is the int8
    codec; anything else is an error."""
    kv = serving["kv_dtype"]
    if kv == "int8":
        return "int8"
    if kv not in FLOAT_KV:
        raise ValueError(f"serving.kv_dtype {kv!r} is not one of "
                         f"{', '.join(FLOAT_KV + ('int8',))}")
    if kv != serving["compute_dtype"]:
        raise ValueError(f"serving.kv_dtype {kv!r}: a float cache is kept in "
                         f"the compute dtype, {serving['compute_dtype']!r}")
    return "compute"


def runtime_spec(cfg: dict, arch, control: str | None):
    """``arch`` served as the configuration's ``serving`` section says:
    paged KV cache, dtypes, ``paged_attn_impl`` (default ``gather``) and
    ``matmul_backend`` (default ``xla``); ``control`` names the program's
    weight-quantization path (the precision control), else none."""
    from repro.core.spec import ExecutionSpec, MemorySpec, RuntimeSpec

    sv = cfg["serving"]
    return RuntimeSpec(
        arch=arch,
        execution=ExecutionSpec(
            param_dtype=sv["param_dtype"], compute_dtype=sv["compute_dtype"],
            quant=control or "none",
            matmul_backend=sv.get("matmul_backend", "xla"),
            paged_attn_impl=sv.get("paged_attn_impl", "gather")),
        memory=MemorySpec(cache_layout="paged", max_batch=sv["max_batch"],
                          max_len=sv["max_len"], block_size=sv["block_size"],
                          kv_dtype=kv_codec(sv)))
