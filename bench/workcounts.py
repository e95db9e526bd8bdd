"""Operations and bytes a dense GQA decoder (Qwen3) needs, counted from
its published sizes.

These count the work the algorithm needs, never what an implementation
happens to touch: a decode step's attention reads each active slot's live
context once, whatever blocks a kernel walks; a prefill computes the real
prompt, not its padded bucket.  ``cfg`` is a configuration file's dict
(Hugging Face key names).
"""

from __future__ import annotations

import dataclasses

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int          # hidden size
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int         # intermediate size
    vocab: int
    dtype_bytes: int

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        return cls(d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
                   heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg["head_dim"], ff=cfg["intermediate_size"],
                   vocab=cfg["vocab_size"],
                   dtype_bytes=DTYPE_BYTES[cfg["torch_dtype"]])

    @property
    def layer_matmul_params(self) -> int:
        """Weights one token multiplies through in one layer: q, k, v, o
        projections and the gated MLP."""
        attn = self.d * self.head_dim * (2 * self.heads + 2 * self.kv_heads)
        return attn + 3 * self.d * self.ff

    @property
    def kv_line_bytes(self) -> int:
        """K and V of one token in all layers, at the stated dtype."""
        return (2 * self.layers * self.kv_heads * self.head_dim
                * self.dtype_bytes)


def attention_flops(dims: Dims, keys: int) -> float:
    """Score and value products of one query over ``keys`` keys, all
    layers and heads."""
    return 4.0 * dims.layers * dims.heads * dims.head_dim * keys


def token_flops(dims: Dims, keys: int, logits: bool = True) -> float:
    """One token through the model: its matmuls, its causal attention over
    ``keys`` keys, and (when ``logits``) the output head."""
    f = 2.0 * dims.layers * dims.layer_matmul_params
    f += attention_flops(dims, keys)
    if logits:
        f += 2.0 * dims.d * dims.vocab
    return f


def prefill_flops(dims: Dims, prompt_len: int, skip: int = 0) -> float:
    """Useful FLOPs of prefilling positions ``skip..prompt_len-1`` (a prefix
    cache hit skips the first ``skip``): every computed token's matmuls and
    causal attention, and the output head at the last position only."""
    n = prompt_len - skip
    if n <= 0:
        return 0.0
    f = 2.0 * dims.layers * dims.layer_matmul_params * n
    # position p attends to p + 1 keys
    keys = (prompt_len * (prompt_len + 1) - skip * (skip + 1)) // 2
    f += attention_flops(dims, keys)
    return f + 2.0 * dims.d * dims.vocab


def decode_token_flops(dims: Dims, context: int) -> float:
    """A decode token whose query attends to ``context`` keys (itself
    included), with its output head."""
    return token_flops(dims, context)


def paged_attention_work(dims: Dims, contexts, tp: int = 1):
    """(flops, bytes) one chip's paged decode attention needs over all
    layers for a decode step whose active slots hold ``contexts`` live
    keys each: every live K/V line read once at the stated dtype, the
    chip's share (1/tp) of the KV heads, plus the queries read and the
    outputs written."""
    kv_heads = dims.kv_heads // tp
    heads = dims.heads // tp
    keys = float(sum(contexts))
    line = 2 * dims.layers * kv_heads * dims.head_dim * dims.dtype_bytes
    qo = 2 * dims.layers * heads * dims.head_dim * dims.dtype_bytes
    flops = 4.0 * dims.layers * heads * dims.head_dim * keys
    return flops, keys * line + qo * len(contexts)


def least_time_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The chip's least time for the work: the larger of its compute time
    at the bf16 peak and its memory time at the HBM bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
