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
