"""Plain float32 Qwen3 (dense GQA decoder) and the weights of a seed.

The reference the benchmark holds the program to.  It imports nothing of
the program: it draws its own weights from the seed, layer by layer, and
computes the published forward pass in float32 with every matmul at
``Precision.HIGHEST``:

    x = embed[tokens]
    per layer:  h = rms(x) * norm1
                q, k, v = h Wq, h Wk, h Wv          (GQA: kv_heads <= heads)
                q, k = rms_head(q) * q_norm, rms_head(k) * k_norm   (qk-norm)
                q, k = rope(q), rope(k)             (half-split, theta)
                x += causal_softmax(q k^T / sqrt(head_dim)) v  Wo
                h = rms(x) * norm2
                x += (silu(h Wgate) * (h Wup)) Wdown
    logits = (rms(x) * final_norm) head             (head = embed^T if tied)

``lowp=True`` computes the same pass with every matmul operand rounded to
float8 e4m3 under a per-slice absmax scale: the control, one precision
step below the configuration's bfloat16, that a sound comparison must
fail.

Weights: every matrix is a standard normal over the square root of its
fan-in, every norm scale 1 + 0.1 * normal, the embedding (and an untied
head) normal over the square root of the hidden size, so logits have a
standard deviation near 1 at any width.  Each value is drawn in float32
and rounded to the configuration's dtype: the program is served those
rounded values, and the reference computes on the same values in float32.
Each layer's leaves come from ``fold_in(fold_in(key(seed), layer), leaf)``
so one layer can be drawn without the others.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "norm1",
                "norm2", "w_gate", "w_up", "w_down")
GLOBAL_LAYER = 1 << 20          # fold-in index of the non-layer leaves
GLOBAL_LEAVES = ("embed", "head", "final_norm")
SEQ_BUCKET = 1024               # sequences pad to a multiple, all alike
Q_CHUNK = 512                   # query rows per attention block
FP8_MAX = 448.0                 # largest finite float8 e4m3fn


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole seed below 2**64."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _dims(cfg: Dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"])


def layer_leaf_shapes(cfg: Dict) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """Leaf -> (shape, fan-in); fan-in 0 marks a norm scale."""
    D, H, KV, hd, F, _ = _dims(cfg)
    return {"wq": ((D, H, hd), D), "wk": ((D, KV, hd), D),
            "wv": ((D, KV, hd), D), "wo": ((H, hd, D), H * hd),
            "q_norm": ((hd,), 0), "k_norm": ((hd,), 0),
            "norm1": ((D,), 0), "norm2": ((D,), 0),
            "w_gate": ((D, F), D), "w_up": ((D, F), D),
            "w_down": ((F, D), F)}


def global_leaf_shapes(cfg: Dict) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    D, _, _, _, _, V = _dims(cfg)
    out = {"embed": ((V, D), D), "final_norm": ((D,), 0)}
    if not cfg["tie_word_embeddings"]:
        out["head"] = ((D, V), D)
    return out


def _draw(key, index: int, shape, fan_in: int, dtype) -> jax.Array:
    k = jax.random.fold_in(key, index)
    z = jax.random.normal(k, shape, jnp.float32)
    v = 1.0 + 0.1 * z if fan_in == 0 else z / math.sqrt(fan_in)
    return v.astype(dtype)


def served_dtype(cfg: Dict):
    return jnp.dtype(cfg["torch_dtype"])


def draw_layer_leaf(cfg: Dict, key, layer, name: str) -> jax.Array:
    """One layer's leaf ``name`` in the served dtype; ``layer`` may be
    traced (the program's stacked weights map this over layers)."""
    shape, fan_in = layer_leaf_shapes(cfg)[name]
    lk = jax.random.fold_in(key, layer)
    return _draw(lk, LAYER_LEAVES.index(name), shape, fan_in,
                 served_dtype(cfg))


def draw_global_leaf(cfg: Dict, key, name: str) -> jax.Array:
    shape, fan_in = global_leaf_shapes(cfg)[name]
    gk = jax.random.fold_in(key, GLOBAL_LAYER)
    return _draw(gk, GLOBAL_LEAVES.index(name), shape, fan_in,
                 served_dtype(cfg))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fq(x: jax.Array, axes) -> jax.Array:
    """Round to float8 e4m3 under an absmax scale over ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, a, b, a_axes, b_axes, lowp: bool) -> jax.Array:
    """einsum at HIGHEST precision; with ``lowp`` both operands are first
    rounded to float8 over their contracted axes."""
    if lowp:
        a, b = _fq(a, a_axes), _fq(b, b_axes)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale


def _rope(x, positions, theta):
    """x (S, heads, hd): rotate the two halves of head_dim by position."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "lowp"))
def _layer(w, x, *, cfg_items, lowp):
    """One decoder layer over one padded sequence x (S, D), float32."""
    cfg = dict(cfg_items)
    D, H, KV, hd, F, _ = _dims(cfg)
    eps, G = cfg["rms_norm_eps"], H // KV
    S = x.shape[0]
    pos = jnp.arange(S, dtype=jnp.int32)
    h = _rms(x, w["norm1"], eps)
    q = _mm("sd,dhk->shk", h, w["wq"], -1, 0, lowp)
    k = _mm("sd,dhk->shk", h, w["wk"], -1, 0, lowp)
    v = _mm("sd,dhk->shk", h, w["wv"], -1, 0, lowp)
    q = _rope(_rms(q, w["q_norm"], eps), pos, cfg["rope_theta"])
    k = _rope(_rms(k, w["k_norm"], eps), pos, cfg["rope_theta"])
    q = q.reshape(S // Q_CHUNK, Q_CHUNK, KV, G, hd) / math.sqrt(hd)

    def attend(args):
        qc, qpos = args
        s = _mm("qkgh,tkh->kgqt", qc, k, -1, -1, lowp)
        s = jnp.where((qpos[:, None] >= pos[None, :])[None, None], s,
                      -jnp.inf)
        return _mm("kgqt,tkh->qkgh", jax.nn.softmax(s, axis=-1), v, -1, 0,
                   lowp)

    o = jax.lax.map(attend, (q, pos.reshape(-1, Q_CHUNK))).reshape(S, H, hd)
    x = x + _mm("shk,hkd->sd", o, w["wo"], (-2, -1), (0, 1), lowp)
    h = _rms(x, w["norm2"], eps)
    g = _mm("sd,df->sf", h, w["w_gate"], -1, 0, lowp)
    u = _mm("sd,df->sf", h, w["w_up"], -1, 0, lowp)
    return x + _mm("sf,fd->sd", jax.nn.silu(g) * u, w["w_down"], -1, 0,
                   lowp)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _layer_weights(key, layer, *, cfg_items):
    cfg = dict(cfg_items)
    return {n: draw_layer_leaf(cfg, key, layer, n).astype(jnp.float32)
            for n in LAYER_LEAVES}


@functools.partial(jax.jit, static_argnames=("cfg_items", "name"))
def _global(key, *, cfg_items, name):
    return draw_global_leaf(dict(cfg_items), key, name).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "lowp"))
def _logits(h, norm, head, *, eps, lowp):
    """Logits of the rows h (R, D) after the final norm."""
    return _mm("rd,dv->rv", _rms(h, norm, eps), head, -1, 0, lowp)


def _cfg_items(cfg: Dict):
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "vocab_size", "rms_norm_eps",
            "rope_theta", "tie_word_embeddings", "torch_dtype")
    return tuple((k, cfg[k]) for k in keys)


def row_logits(cfg: Dict, seed: int, seqs: Sequence[np.ndarray],
               rows: Sequence[Sequence[int]], lowp: bool = False
               ) -> jax.Array:
    """Logits (sum of len(rows[i]), vocab) at positions ``rows[i]`` of each
    token sequence ``seqs[i]``, layer by layer: one layer's weights are
    drawn, applied to every sequence, and dropped before the next.  Every
    sequence pads to the longest's multiple of ``SEQ_BUCKET``, so a call
    compiles one shape of the layer."""
    items = _cfg_items(cfg)
    key = seed_key(seed)
    table = _global(key, cfg_items=items, name="embed")
    n = -(-max(len(s) for s in seqs) // SEQ_BUCKET) * SEQ_BUCKET
    xs = []
    for s in seqs:
        padded = np.zeros(n, np.int32)
        padded[:len(s)] = s
        xs.append(table[jnp.asarray(padded)])
    del table
    for layer in range(cfg["num_hidden_layers"]):
        w = _layer_weights(key, layer, cfg_items=items)
        xs = [_layer(w, x, cfg_items=items, lowp=lowp) for x in xs]
        del w
    h = jnp.concatenate([x[jnp.asarray(r, jnp.int32)]
                         for x, r in zip(xs, rows)], 0)
    del xs
    head = _global(key, cfg_items=items, name=(
        "embed" if cfg["tie_word_embeddings"] else "head"))
    if cfg["tie_word_embeddings"]:
        head = head.T
    norm = _global(key, cfg_items=items, name="final_norm")
    return _logits(h, norm, head, eps=cfg["rms_norm_eps"], lowp=lowp)


def served_rows(prompt: np.ndarray, served: Sequence[int]
                ) -> Tuple[np.ndarray, List[int]]:
    """The sequence the reference reads for a served request (its prompt
    and every served token but the last) and the positions whose logits
    chose each served token."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    n = len(prompt)
    return seq, list(range(n - 1, n - 1 + len(served)))


def logit_gaps(ref_logits: jax.Array, tokens: np.ndarray) -> np.ndarray:
    """Per row, how far the logit of ``tokens[row]`` lies below the row's
    best, in the reference's logits."""
    ref = np.asarray(ref_logits, np.float32)
    return ref.max(-1) - ref[np.arange(len(tokens)), tokens]
