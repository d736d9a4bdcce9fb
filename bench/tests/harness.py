"""A checkout of the benchmark at a tiny size, for driving whole runs on the
CPU: BENCHMARK.json and bench/ copied to a temporary directory, the
program linked in, and tiny configurations, traffic and cells written as
data beside the real ones."""
from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]
FAKE_CHIP = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

TINY_TRAIN = {
    "system": "train", "arch": "granite-3-2b", "arch_reduced": True,
    "model": {"family": "dense", "n_layers": 2, "d_model": 256,
              "n_heads": 4, "kv_heads": 4, "head_dim": 64, "d_ff": 341,
              "vocab": 512, "rope_theta": 10000.0, "tie_embeddings": True,
              "mlp_type": "swiglu"},
    "algorithm": {"name": "lead", "bits": 2, "block": 512,
                  "topology": "ring", "optimizer": "sgd", "eta": 0.03,
                  "gamma": 1.0, "alpha": 0.5}}
TINY_ENGINE = {
    "system": "engine", "agents": 8, "d": 512 * 64,
    "algorithm": {"name": "lead", "bits": 2, "block": 512,
                  "topology": "ring", "gossip": "neighbor",
                  "dither": "match", "gamma": 1.0, "alpha": 0.5},
    "oracle": {"kind": "separable_quadratic", "a_min": 1.0, "a_max": 2.0}}
TINY_CELLS = {
    "tiny.train": ("tiny-train", TINY_TRAIN, "tiny.lm",
                   {"generator": "lm_stream", "agents": 1, "seq_len": 32,
                    "batch_per_agent": 2, "block_size": 64},
                   {"loss_gap": 1e-4, "grad_norm_gap": 1e-4,
                    "first_grad_gap": 1e-4, "change_gap": 1e-4,
                    "bits_gap": 0.0, "window_stall": 10.0}),
    "tiny.train.ring4": ("tiny-train-ring4", TINY_TRAIN, "tiny.lm.a4",
                         {"generator": "lm_stream", "agents": 4,
                          "seq_len": 32, "batch_per_agent": 2,
                          "block_size": 64},
                         # a 2-bit level that flips on rounding reaches
                         # the neighbours through the mix: about 1.4e-4
                         {"loss_gap": 1e-4, "grad_norm_gap": 1e-4,
                          "first_grad_gap": 1e-4, "change_gap": 1e-3,
                          "bits_gap": 0.0, "window_stall": 10.0}),
    "tiny.engine": ("tiny-engine", TINY_ENGINE, "tiny.quadratic",
                    {"generator": "quadratic", "steps_per_call": 16},
                    {"deviating_share": 1e-2, "dist_ratio": 1e-2,
                     "bits_gap": 0.0}),
}


def add_cell(root, name, config_name, config, traffic_name, traffic,
             limits, spec=None):
    """Add a cell as data only: its configuration, traffic and limits as
    new files, and its entries in BENCHMARK.json; one chip per agent of
    its traffic."""
    b = root / "bench"
    (b / "configs" / f"{config_name}.json").write_text(json.dumps(config))
    (b / "traffic" / f"{traffic_name}.json").write_text(json.dumps(traffic))
    (b / "cells" / f"{name}.json").write_text(json.dumps({"limits": limits}))
    spec = spec or json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": config_name, "source": "test",
                            "file": f"bench/configs/{config_name}.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": name, "config": config_name,
                              "traffic": traffic_name,
                              "chips": traffic.get("agents", 1),
                              "why": "test"})
    system = config["system"]
    e2e = {"train": "train_tokens_per_s", "engine": "engine_steps_per_s"}
    for m in spec["end_to_end"]:
        if m["name"] == e2e[system]:
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return spec


def checkout(tmp_path, cells=("tiny.train", "tiny.engine"), src=True):
    """A copy of BENCHMARK.json and bench/ (and a link to the program's
    src/ when ``src``) with the tiny cells added as data."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "recorded"))
    if src:
        os.symlink(ROOT / "src", root / "src")
    for name in cells:
        config_name, config, traffic_name, traffic, limits = TINY_CELLS[name]
        add_cell(root, name, config_name, config, traffic_name, traffic,
                 limits)
    return root


def load_run(root):
    """bench/run.py of the checkout as a module (its ROOT is the copy)."""
    spec = importlib.util.spec_from_file_location(
        f"bench_run_{abs(hash(str(root)))}", root / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def drive(run_mod, monkeypatch, workload, seed=123, seconds=0.3, trace=0):
    """A whole run past the look for a chip (the CPU stands in)."""
    monkeypatch.setattr(run_mod, "require_chip", lambda chips: dict(
        FAKE_CHIP, count=chips))
    return run_mod.run(["--workload", workload, "--seed", str(seed),
                        "--seconds", str(seconds), "--trace", str(trace)])
