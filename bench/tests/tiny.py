"""A cell small enough for the test suite: the program's dense decoder at
toy widths on the CPU, with its own configuration, mix and readers in a
throwaway benchmark root."""

from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

PUBLISHED = {"hidden_size": 64, "intermediate_size": 128,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "num_hidden_layers": 2, "vocab_size": 512,
             "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
             "tie_word_embeddings": True}

CONFIG = {
    "name": "tiny", "model": "smollm-135m", "arch": "dense",
    "model_overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                        "n_kv_heads": 2, "d_ff": 128, "vocab": 512},
    "published": PUBLISHED,
    "architecture": {"qkv_bias": False, "compute_dtype": "bfloat16"},
    "engine": {"max_batch": 4, "max_seq": 512, "decode_chunk": 2,
               "prefill_chunk_tokens": 64},
    "replicas": 1, "router": None, "admission": None,
    "check": {"sample": 3, "logit_gap_limit": 0.5, "min_tokens_checked": 10},
}

MIX = {
    "name": "tiny-mix", "source": "test", "shape_seed": 5,
    "arrivals": {"process": "poisson", "rate_per_s": 3.0},
    "prompt": {"dist": "lognormal", "median": 60, "sigma": 0.5,
               "min": 16, "max": 200},
    "output": {"dist": "lognormal", "median": 10, "sigma": 0.5,
               "min": 4, "max": 24},
    "steady_s": 0.5, "drain_s": 60,
    "limits": {"ttft_ms": {"base": 5000, "per_1k_prompt_tokens": 0},
               "tpot_ms": 5000},
}


def make_root(tmp: pathlib.Path, config: dict = CONFIG,
              mix: dict = MIX) -> pathlib.Path:
    """A benchmark root holding one cell, ``tiny.tiny-mix``, with the
    repository's own readers and architecture files."""
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir()
    shutil.copytree(BENCH / "metrics", tmp / "bench" / "metrics")
    shutil.copytree(BENCH / "archs", tmp / "bench" / "archs",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    (tmp / "bench" / "traffic" / "tiny-mix.json").write_text(json.dumps(mix))
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec["workloads"] = [{"name": "tiny.tiny-mix", "config": "tiny",
                          "traffic": "tiny-mix", "chips": 1, "why": "test"}]
    for m in spec["per_layer"] + spec["end_to_end"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
