"""The Qwen2 family module gives the weights and the spec the harness
gave before families existed, and the serving section selects the
program's options."""
import sys
from pathlib import Path

# the benchmark lives beside src/, outside the package path
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import hashlib

import numpy as np
import pytest

from bench import weights
from bench.families import kv_codec
from bench.manifest import family_module, load_cell
from repro.configs.base import ArchConfig
from repro.core.spec import ExecutionSpec, MemorySpec, RuntimeSpec

SEED = 2**31 + 99
# test_bench_correct.py's tiny widths
TINY = dict(hidden_size=256, intermediate_size=512, num_attention_heads=4,
            num_key_value_heads=2, head_dim=64, num_hidden_layers=2,
            vocab_size=4096)
CELLS = ("qwen1.5-0.5b.chat.rate", "codeqwen1.5-7b.longgen.batch")
# sha256 (first 16 hex digits) of every leaf's bytes, bf16, at TINY from
# SEED, as the harness drew them before the draws moved to the family
DRAWS = {
    "qwen1.5-0.5b.chat.rate": {
        "bk": "885aac2843a0f1a7", "bq": "d7f90f894ed9d4fa",
        "bv": "f5effde9b4aba663", "embed": "50852a0c51c53312",
        "final_norm": "4178c17a670cefad", "ln1": "aaf7adca2ac0b530",
        "ln2": "f5320c04f728abe9", "w_down": "fab465eb21d7b9cd",
        "w_gate": "ed812238453778b4", "w_up": "7457e9eb06fb25e7",
        "wk": "fc28ee8a2598bbdf", "wo": "ae090effba0f7f59",
        "wq": "0ff73329ddb3acb2", "wv": "c7df676cc24026d5"},
    "codeqwen1.5-7b.longgen.batch": {
        "bk": "885aac2843a0f1a7", "bq": "d7f90f894ed9d4fa",
        "bv": "f5effde9b4aba663", "embed": "50852a0c51c53312",
        "final_norm": "4178c17a670cefad", "lm_head": "21cab9c77b5e9904",
        "ln1": "f5320c04f728abe9", "ln2": "3d9776f6675a0cc8",
        "w_down": "3be891a7649aa8e4", "w_gate": "7457e9eb06fb25e7",
        "w_up": "2c36a005ae545bbf", "wk": "e271a15bd282a65c",
        "wo": "0ff73329ddb3acb2", "wq": "ea5933aa3fdb319e",
        "wv": "306a7fa728abbc14"},
}


def _parent_spec(cfg, control):
    """The spec the harness built for a Qwen2 configuration before
    families existed."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    arch = ArchConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg.get("head_dim", d // h), activation="swiglu",
        norm="rmsnorm", qkv_bias=True, rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        max_position_embeddings=cfg["max_position_embeddings"],
        source=cfg["source"])
    sv = cfg["serving"]
    return RuntimeSpec(
        arch=arch,
        execution=ExecutionSpec(param_dtype=sv["param_dtype"],
                                compute_dtype=sv["compute_dtype"],
                                quant=control or "none"),
        memory=MemorySpec(cache_layout="paged", max_batch=sv["max_batch"],
                          max_len=sv["max_len"], block_size=sv["block_size"],
                          kv_dtype="compute"))


@pytest.mark.parametrize("cell", CELLS)
def test_qwen2_draws_are_the_parents_bit_for_bit(cell):
    cfg = dict(load_cell(cell).config, **TINY)
    fam = family_module(cfg["reference"])
    w = weights.from_seed(fam, cfg, SEED)
    got = {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
           for k, v in w.items()}
    assert got == DRAWS[cell]


@pytest.mark.parametrize("control", [None, "int8"])
@pytest.mark.parametrize("cell", CELLS)
def test_both_configs_build_the_parents_spec(cell, control):
    cfg = load_cell(cell).config
    spec = family_module(cfg["reference"]).spec(cfg, control)
    assert spec == _parent_spec(cfg, control)


@pytest.mark.parametrize("kv, compute, codec", [
    ("bf16", "bf16", "compute"), ("fp32", "fp32", "compute"),
    ("int8", "bf16", "int8"),
    ("fp8", "bf16", ValueError), ("fp32", "bf16", ValueError)])
def test_kv_dtype_names_the_cache_codec(kv, compute, codec):
    sv = {"kv_dtype": kv, "compute_dtype": compute}
    if codec is ValueError:
        with pytest.raises(ValueError):
            kv_codec(sv)
    else:
        assert kv_codec(sv) == codec


def test_serving_options_come_from_the_config():
    cfg = load_cell(CELLS[0]).config
    cfg["serving"] = dict(cfg["serving"], kv_dtype="int8",
                          paged_attn_impl="pallas", matmul_backend="pallas")
    spec = family_module(cfg["reference"]).spec(cfg, None)
    assert spec.memory.kv_dtype == "int8"
    assert spec.execution.paged_attn_impl == "pallas"
    assert spec.execution.matmul_backend == "pallas"
