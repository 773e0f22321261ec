"""The manifest, the lookup by name, the peaks table, the counts, and
the refusal to run anywhere but on a TPU."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import counts
import harness
from conftest import BENCH, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_manifest_keys_and_names(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in manifest[g]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    assert all(m["moves"] in e2e for m in manifest["per_layer"])
    layers = {m["layer"] for m in manifest["per_layer"]}
    assert all("\n" not in v for v in layers)
    for w in manifest["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200
        reported = [m for m in manifest["per_layer"]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert reported, w["name"]


def test_every_named_file_loads_through_the_harness_lookup(manifest):
    """Each configuration, mix, limit and per-layer metric named in the
    manifest is a file of its own, found by name."""
    for w in manifest["workloads"]:
        cell = harness.load_cell(manifest, w["name"], seed=1, seconds=1.0,
                                 trace=False)
        assert cell.cfg["name"] == w["config"]
        assert hasattr(cell.model, "init")
        driver = harness.load_module(BENCH / "drivers" / f"{cell.driver}.py")
        assert callable(driver.run)
        assert cell.limits
    for m in manifest["per_layer"]:
        reader = harness.load_module(BENCH / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)
    for c in manifest["configs"]:
        assert (REPO / c["file"]).is_file()
        assert c["file"].startswith("bench/")


def test_unknown_device_kind_raises():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="no published peaks"):
        harness.peaks_for("TPU v9 imaginary")
    with pytest.raises(harness.BenchError):
        harness.peaks_for("cpu")


def test_lm_counts_match_hand_count():
    cfg = {"hidden_size": 8, "intermediate_size": 16,
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "num_hidden_layers": 3, "vocab_size": 10}
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, gate/up/down 3 x 8x16
    per_layer = 64 + 32 + 32 + 64 + 3 * 128
    assert counts.lm_matmul_params(cfg) == 3 * per_layer + 80
    assert counts.lm_flops_per_token(cfg) == 2 * (3 * per_layer + 80)


def test_published_deepseek_flops_per_token():
    cfg = json.loads((BENCH / "configs" / "deepseek-7b.json").read_text())
    assert counts.lm_flops_per_token(cfg) == pytest.approx(4.08e9, rel=1e-2)


def test_vgg_counts_match_hand_count():
    cfg = {"image_size": 4, "in_channels": 1, "conv_widths": [[2], [3]],
           "classifier_widths": [5], "num_classes": 2}
    conv = 2 * 16 * 9 * 1 * 2 + 2 * 4 * 9 * 2 * 3
    fc = 2 * (1 * 1 * 3) * 5 + 2 * 5 * 2
    assert counts.vgg_flops_per_image(cfg) == conv + fc
    assert counts.vgg_pool_stacks(cfg) == [(4, 4, 2), (2, 2, 3)]


def test_published_vgg16_flops_per_image():
    cfg = json.loads((BENCH / "configs" / "vgg16-bn.json").read_text())
    # 15.5 G multiply-adds (Simonyan & Zisserman, configuration D)
    assert counts.vgg_flops_per_image(cfg) == pytest.approx(31.0e9, rel=1e-2)


def test_kernel_byte_counts_match_hand_count():
    w = counts.paged_decode_call([3, 5], heads=4, kv_heads=2, head_dim=8)
    assert w.bytes == 2 * 2 * 4 * 8 * 2 + 2 * 8 * 2 * 8 * 2
    assert w.flops == 4 * 8 * 4 * 8
    f = counts.nhwc_pool_stack_fwd(2, 4, 4, 3, n_params=3)
    assert f.bytes == (2 * 16 * 3 + 2 * 4 * 3 + 9) * 4
    b = counts.nhwc_pool_stack_bwd(2, 4, 4, 3, n_params=3)
    assert b.bytes == (2 * 2 * 16 * 3 + 2 * 4 * 3 + 18) * 4
    assert (f + b).bytes == f.bytes + b.bytes
    assert f.least_seconds(1e12, 1e9) == f.bytes / 1e9


def test_deepseek_file_matches_the_registered_widths():
    """Every size but the depth is the program's registered deepseek-7b."""
    from repro.configs import get_config

    serve = harness.load_module(BENCH / "drivers" / "serve.py")
    cfg = json.loads((BENCH / "configs" / "deepseek-7b.json").read_text())
    reg = get_config("deepseek-7b")
    for field, key in serve.SIZES.items():
        if field != "n_layers":
            assert getattr(reg, field) == cfg[key], field
    assert cfg["published"]["num_hidden_layers"] == reg.n_layers


def _run_cli(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vgg16-train",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cpu_run_exits_nonzero_and_names_the_missing_tpu(tmp_path):
    out = _run_cli(REPO, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_benchmark_files_alone_print_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    out = _run_cli(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
