"""A checkout in a temporary directory with tiny configurations, for the
benchmark's CPU tests: the benchmark's files copied, the program linked,
and new cells, configurations, mixes, limits and metrics added as files."""

from __future__ import annotations

import json
import os
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_GQA = {
    "name": "tiny-gqa", "source": "test", "reference": "moe_transformer",
    "repo_arch": "qwen3-moe-30b-a3b",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 8, "num_experts_per_tok": 2, "num_hidden_layers": 2,
    "norm_topk_prob": True, "vocab_size": 256, "rope_theta": 1000000.0,
    "rms_norm_eps": 1e-06, "reduced": [],
    "program": {
        "dtype": "bfloat16",
        "overrides": {"n_layers": 2, "d_model": 64, "d_ff": 128, "vocab_size": 256},
        "attn_overrides": {"n_heads": 4, "n_kv_heads": 2, "d_head": 16},
        "moe_overrides": {"n_experts": 8, "top_k": 2, "d_expert": 32,
                          "capacity_factor": 4.0, "expert_exec": "dual_path_cost"},
        "n_slots": 4, "max_seq": 128,
    },
}

TINY_MLA = {
    "name": "tiny-mla", "source": "test", "reference": "moe_transformer",
    "repo_arch": "deepseek-v2-236b",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 8, "n_shared_experts": 1, "first_k_dense_replace": 1,
    "num_experts_per_tok": 2, "num_hidden_layers": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "vocab_size": 256, "rope_theta": 10000,
    "rms_norm_eps": 1e-06, "reduced": [],
    "program": {
        "dtype": "bfloat16",
        "overrides": {"n_layers": 2, "d_model": 64, "d_ff": 128, "vocab_size": 256},
        "attn_overrides": {"n_heads": 4, "n_kv_heads": 4, "d_head": 16},
        "mla_overrides": {"q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_dim": 16,
                          "qk_rope_dim": 8, "v_head_dim": 16},
        "moe_overrides": {"n_experts": 8, "top_k": 2, "d_expert": 32, "n_shared": 1,
                          "first_k_dense": 1, "capacity_factor": 4.0,
                          "expert_exec": "dual_path_cost"},
        "n_slots": 4, "max_seq": 128,
    },
}

ROUTING = {"hot_fraction": 0.25, "hot_mass": 0.9, "tail_alpha": 0.109, "hot_alpha": 6.0}

TINY_CLOSED = {
    "loop": "closed", "clients": "slots",
    "prompt_buckets": {"16": 0.5, "32": 0.5},
    "output": {"law": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 40},
    "population": 32, "preroll_s": 0.0, "check_requests": 3, "routing": ROUTING,
}


def make_root(tmp: Path, configs, mixes, metrics=None, limit: float = 1.0) -> Path:
    """A checkout under ``tmp`` whose BENCHMARK.json holds one cell per
    (config, mix) pair, and the benchmark's own per-layer metrics plus
    ``metrics`` ({name: source}) added as new files."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(REPO / "src", root / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"], bench["workloads"] = [], []
    for cfg in configs:
        path = f"benchmark/configs/{cfg['name']}.json"
        (root / path).write_text(json.dumps(cfg))
        bench["configs"].append({"name": cfg["name"], "source": "test", "file": path,
                                 "reduced": [], "why": "test"})
    for name, mix in mixes.items():
        (root / f"benchmark/traffic/{name}.json").write_text(json.dumps(mix))
    for cfg in configs:
        for name in mixes:
            cell = f"{cfg['name']}.{name}"
            bench["workloads"].append({"name": cell, "config": cfg["name"],
                                       "traffic": name, "chips": 1, "why": "test"})
            (root / f"benchmark/limits/{cell}.json").write_text(
                json.dumps({"mean_logit_gap": limit}))
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    for name, src in (metrics or {}).items():
        (root / f"benchmark/metrics/{name}.py").write_text(src)
        bench["per_layer"].append({"name": name, "unit": "%", "better": "higher",
                                   "source": "host_clock", "layer": "test",
                                   "moves": "tpot_p95_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
