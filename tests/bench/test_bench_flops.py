"""The model-FLOP counter and the stated bytes against hand counts,
through the configuration's family module."""
import sys
from pathlib import Path

# the benchmark lives beside src/, outside the package path
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import json

import pytest

from bench import flops
from bench.manifest import family_module
from bench.stats import Record

CONFIGS = Path(__file__).resolve().parents[2] / "bench" / "configs"
QWEN2 = family_module("qwen2_dense")


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name, matmul, per_key, head", [
    # 24 x 2 x (q 1024^2 + k,v 2 x 1024^2 + o 1024^2 + ffn 3 x 1024 x 2816)
    ("qwen1.5-0.5b", 616_562_688, 4 * 24 * 16 * 64, 2 * 1024 * 151_936),
    # 11 x 2 x (q 4096^2 + k,v 2 x 4096 x 512 + o 4096^2 + 3 x 4096 x 13440)
    ("codeqwen1.5-7b-l11", 4_463_788_032, 4 * 11 * 32 * 128,
     2 * 4096 * 92_416),
])
def test_counts_match_hand_counts(name, matmul, per_key, head):
    cfg = _cfg(name)
    fam = family_module(cfg["reference"])
    assert fam.matmul_per_token(cfg) == matmul
    assert fam.attention_per_key(cfg) == per_key
    assert fam.head(cfg) == head
    assert flops.decode_flops(fam, cfg, 100) == matmul + 100 * per_key + head


@pytest.mark.parametrize("name", ["qwen1.5-0.5b", "codeqwen1.5-7b-l11"])
def test_stated_bytes_match_the_shapes(name):
    cfg = _cfg(name)
    sv, b = cfg["serving"], cfg["bytes"]
    assert family_module(cfg["reference"]).bytes(cfg) == b
    per_token = (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
                 * cfg["head_dim"] * 2)
    assert b["kv_per_token"] == per_token
    assert b["kv_pool"] == per_token * sv["max_batch"] * sv["max_len"]


def test_an_int8_cache_states_its_scales_in_kv_bytes():
    cfg = _cfg("qwen1.5-0.5b")
    cfg["serving"] = dict(cfg["serving"], kv_dtype="int8")
    # 24 layers x K and V x 16 heads x (64 int8 values + one f32 scale)
    assert QWEN2.bytes(cfg)["kv_per_token"] == 24 * 2 * 16 * (64 + 4)


def test_step_flops_split_prompts_over_their_prefill_dispatches():
    cfg = {"hidden_size": 4, "num_attention_heads": 2,
           "num_key_value_heads": 1, "head_dim": 2, "intermediate_size": 8,
           "num_hidden_layers": 1, "vocab_size": 10}
    assert QWEN2.matmul_per_token(cfg) == 288
    assert QWEN2.attention_per_key(cfg) == 16
    assert QWEN2.head(cfg) == 80
    p = lambda s, t, c: ("progress", 1, s, t, {"count": c})  # noqa: E731
    events = [("admit", 1, 0, 1.0, {}), p(1, 1.1, 0),
              ("first_token", 1, 2, 1.15, {}), p(2, 1.2, 1),
              p(3, 1.3, 2), p(4, 1.4, 3)]
    rec = Record(events=events, due={1: 1.0}, prompt_len={1: 8}, t0=1.0,
                 t1=2.0, t_drained=2.0, loop="rate", max_batch=1, config=cfg,
                 family=QWEN2)
    prompt = 288 * 8 + 16 * 8 * 9 // 2 + 80
    assert flops.step_flops(rec) == {
        0: ("mixed", prompt / 2), 1: ("mixed", prompt / 2),
        2: ("decode", 288 + 16 * 9 + 80), 3: ("decode", 288 + 16 * 10 + 80)}
