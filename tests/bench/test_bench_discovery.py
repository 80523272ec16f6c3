"""Files are found by name; the runner refuses to run without a chip."""
import sys
from pathlib import Path

# the benchmark lives beside src/, outside the package path
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import json
import os
import shutil
import subprocess

import pytest

from bench import manifest

ROOT = Path(__file__).resolve().parents[2]


def _copy(tmp_path: Path) -> Path:
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def test_every_manifest_entry_has_its_file():
    man = manifest.load_manifest()
    for wl in man["workloads"]:
        cell = manifest.load_cell(wl["name"])
        assert cell.config["name"] == wl["config"]
        assert manifest.reference_module(cell.config["reference"]).gaps
        fam = manifest.family_module(cell.config["reference"])
        assert all(callable(getattr(fam, f)) for f in (
            "spec", "shapes", "make", "to_program", "bytes",
            "matmul_per_token", "attention_per_key", "head"))
        for m in cell.end_to_end + cell.per_layer:
            assert callable(manifest.metric_reader(m["name"]))


def test_new_config_mix_and_metric_are_found_without_edits(tmp_path):
    root = _copy(tmp_path)
    cfg = json.loads((root / "bench/configs/qwen1.5-0.5b.json").read_text())
    cfg["name"] = "tiny-dense"
    (root / "bench/configs/tiny-dense.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "bench/traffic/chat.rate.json").read_text())
    (root / "bench/traffic/burst.rate.json").write_text(
        json.dumps(dict(mix, rate_per_s=9.0)))
    (root / "bench/limits/tiny-dense.burst.rate.json").write_text(
        json.dumps({"max_logit_gap": 0.5}))
    (root / "bench/metrics/answer.py").write_text(
        "def read(rec):\n    return 42.0\n")
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "tiny-dense", "source": "x",
                           "file": "bench/configs/tiny-dense.json",
                           "reduced": [], "why": "x"})
    man["workloads"].append({"name": "tiny-dense.burst.rate",
                             "config": "tiny-dense", "traffic": "burst.rate",
                             "chips": 1, "why": "x"})
    man["per_layer"].append({"name": "answer", "unit": "%",
                             "better": "higher", "source": "program_counter",
                             "layer": "scheduler", "moves": "ttft_p90_s",
                             "workloads": ["tiny-dense.burst.rate"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    cell = manifest.load_cell("tiny-dense.burst.rate", root)
    assert cell.config["name"] == "tiny-dense"
    assert cell.traffic["rate_per_s"] == 9.0
    assert cell.limits == {"max_logit_gap": 0.5}
    assert [m["name"] for m in cell.per_layer if m["name"] == "answer"]
    assert manifest.metric_reader("answer", root)(None) == 42.0
    # the cells already there are unchanged
    old = manifest.load_cell("qwen1.5-0.5b.chat.rate", root)
    assert "answer" not in [m["name"] for m in old.per_layer]


FAMILY = '''"""Qwen2's block without q/k/v biases and with an untied head."""
from pathlib import Path

from bench.families import runtime_spec
from bench.manifest import family_module
from bench.weights import draw

BIASES = ("bq", "bk", "bv")
QWEN2 = family_module("qwen2_dense", Path(__file__).resolve().parents[2])
matmul_per_token = QWEN2.matmul_per_token
attention_per_key = QWEN2.attention_per_key
head = QWEN2.head


def spec(cfg, control):
    from repro.configs.base import ArchConfig
    m = QWEN2.dims(cfg)
    arch = ArchConfig(
        name=cfg["name"], family="dense", num_layers=m["layers"],
        d_model=m["d"], num_heads=m["h"], num_kv_heads=m["kv"],
        d_ff=m["ff"], vocab_size=m["vocab"], head_dim=m["hd"],
        activation="swiglu", norm="rmsnorm", qkv_bias=False,
        rope_theta=cfg["rope_theta"], tie_embeddings=False,
        max_position_embeddings=cfg["max_position_embeddings"],
        source=cfg["source"])
    return runtime_spec(cfg, arch, control)


def shapes(cfg):
    return {k: v for k, v in QWEN2.shapes(cfg).items() if k not in BIASES}


def make(cfg, key, dtype):
    return draw(shapes(cfg), key, dtype)


def to_program(cfg, w):
    tree = QWEN2.to_program(cfg, dict(w, bq=None, bk=None, bv=None))
    for name in ("wq", "wk", "wv"):
        del tree["layers"]["attn"][name]["bias"]
    return tree


def bytes(cfg):
    return QWEN2.bytes(cfg)
'''

REFERENCE = '''"""Qwen2's block without q/k/v biases: the Qwen2
reference with its biases at zero."""
from pathlib import Path

import jax.numpy as jnp

from bench.manifest import reference_module

QWEN2 = reference_module("qwen2_dense", Path(__file__).resolve().parents[2])


def gaps(cfg, w, *rows):
    n, kv = cfg["num_hidden_layers"], cfg["num_key_value_heads"]
    hd, h = cfg["head_dim"], cfg["num_attention_heads"]
    zero = {"bq": jnp.zeros((n, h * hd)), "bk": jnp.zeros((n, kv * hd)),
            "bv": jnp.zeros((n, kv * hd))}
    return QWEN2.gaps(cfg, dict(w, **zero), *rows)
'''


# A sound run of the bias-free family reads a widest gap of 0.004-0.022
# (6 seeds near 2**31, three processes at once on the CPU); one whose
# reference keeps q/k/v biases of 0.2 the program lacks reads 0.81.
NOBIAS_LIMITS = {"max_logit_gap": 0.1}


def _files(root: Path) -> dict:
    return {p.relative_to(root): p.read_bytes() for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_family_is_served_from_new_files_alone(tmp_path):
    from bench import run
    from test_bench_correct import SEED, TINY, _cell

    root = _copy(tmp_path)
    before = _files(root)
    tiny = _cell()
    cfg = dict(tiny.config, name="tiny-nobias", reference="qwen2_nobias",
               tie_word_embeddings=False)
    new = {"bench/families/qwen2_nobias.py": FAMILY,
           "bench/reference/qwen2_nobias.py": REFERENCE,
           "bench/configs/tiny-nobias.json": json.dumps(cfg),
           "bench/traffic/tiny.rate.json": json.dumps(tiny.traffic),
           "bench/limits/tiny-nobias.tiny.rate.json": json.dumps(
               NOBIAS_LIMITS)}
    for rel, text in new.items():
        (root / rel).write_text(text)
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "tiny-nobias", "source": "x",
                           "file": "bench/configs/tiny-nobias.json",
                           "reduced": [], "why": "x"})
    man["workloads"].append({"name": "tiny-nobias.tiny.rate",
                             "config": "tiny-nobias", "traffic": "tiny.rate",
                             "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    after = _files(root)
    # every file already there is as it was, but the manifest
    assert {k for k in before if before[k] != after[k]} == {
        Path("BENCHMARK.json")}
    assert set(after) - set(before) == {Path(k) for k in new}

    cell = manifest.load_cell("tiny-nobias.tiny.rate", root)
    assert cell.config["vocab_size"] == TINY["vocab_size"]
    res, checks, _ = run.run_cell(cell, SEED, 3.0, False, "cpu",
                                  peaks={"bf16_flops": 1e12})
    assert res["correct"], checks
    assert set(res["metrics"]) == {"setup_s"}


@pytest.mark.parametrize("name,reader", [
    ("mixed_step_ms.burst", "mixed_step_ms.py"),
    ("device_idle_share.rate", "device_idle_share.py"),
    ("answer.batch", "answer.batch.py"),
])
def test_a_split_metric_reads_its_quantity_unless_it_has_a_file(
        tmp_path, name, reader):
    root = _copy(tmp_path)
    (root / "bench/metrics/answer.batch.py").write_text(
        "def read(rec):\n    return 7.0\n")
    fn = manifest.metric_reader(name, root)
    assert Path(fn.__code__.co_filename).name == reader


def _run(cwd: Path, env_extra: dict) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env.update(env_extra, HOME=str(cwd), TMPDIR=str(cwd))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen1.5-0.5b.chat.rate", "--seed", str(2**31 + 7), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


@pytest.mark.parametrize("where", ["checkout", "bench_files_only"])
def test_run_exits_nonzero_without_a_chip(tmp_path, where):
    if where == "checkout":
        root = _copy(tmp_path)
        (root / "src").symlink_to(ROOT / "src")
    else:
        root = _copy(tmp_path)   # BENCHMARK.json and bench/ alone
    out = _run(root, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    if where == "checkout":
        assert "no chip" in out.stderr
