"""CPU tests of the benchmark: ``pytest bench/tests``."""

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


def tiny_config(dtype: str = "bfloat16", tied: bool = True,
                **sizes) -> dict:
    """qwen3-0.6b's file at a size a CPU test can hold."""
    with open(ROOT / "bench" / "configs" / "qwen3-0.6b.json") as f:
        cfg = json.load(f)
    cfg.update(name="qwen3-tiny", hidden_size=64, intermediate_size=128,
               num_hidden_layers=2, num_attention_heads=8,
               num_key_value_heads=4, head_dim=16, vocab_size=512,
               torch_dtype=dtype, tie_word_embeddings=tied)
    cfg.update(sizes)
    cfg["serving"] = dict(cfg["serving"], num_slots=4, max_len=176)
    return cfg


TINY_MIX = {"arrival": "poisson", "rate_per_s": 2.0,
            "distinct_prompt_lengths": 12,
            "prompt_tokens": {"median": 40, "sigma": 0.5, "min": 16,
                              "max": 128},
            "output_tokens": {"median": 6, "sigma": 0.5, "min": 2,
                              "max": 16}}


@pytest.fixture
def tiny():
    return tiny_config
