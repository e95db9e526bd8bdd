"""Public jit'd wrappers for the Pallas kernel library + kernel registry.

``interpret`` defaults to True off-TPU so every kernel validates on this
CPU container; on a TPU backend the same calls compile to Mosaic.

Kernel registry / dispatch
--------------------------
Ops with both a Pallas kernel and a jnp reference register under a name in
``_REGISTRY``; callers dispatch through :func:`resolve` (or the public
per-op wrappers below) with a ``backend`` of:

* ``"pallas"`` — the Pallas kernel (interpret mode off-TPU, Mosaic on TPU)
* ``"jnp"``    — the pure-jnp reference (the byte-checked oracle)
* ``"auto"``   — pallas everywhere (interpret off-TPU); the default

``None`` falls back to the process-wide default set by
:func:`set_default_backend` / :func:`use_backend`.  Backend resolution
happens at *trace time*: code that jits a caller (e.g. the serve engine's
decode step) must rebuild/retrace to pick up a backend change — the serve
engine does this on ``reset()``.

Pipelined page streaming
------------------------
Ops registered with ``pipelined=True`` (the four paged-attention kernels)
additionally accept a ``pipeline`` flag of ``"off"`` (single-buffered
grid walk — the byte-checked reference) or ``"double"`` (two-slab manual
DMA double buffering: page b+1 prefetches while page b computes; bit
identical output).  ``resolve(..., pipeline=...)`` binds it into the
pallas partial; the jnp reference ignores it (there is nothing to
pipeline), and non-pipelined ops reject anything but ``"off"``.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from . import avgpool as _avgpool
from . import conv_direct as _conv_direct
from . import conv_winograd as _conv_winograd
from . import flash_attention as _flash
from . import gelu as _gelu
from . import inner_product as _ip
from . import layernorm as _ln
from . import paged_attention as _paged
from . import ref


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------
# Kernel registry
# --------------------------------------------------------------------------

_REGISTRY: Dict[str, Dict[str, object]] = {}
_BACKENDS = ("auto", "pallas", "jnp")
_PIPELINES = ("off", "double")
_default_backend = "auto"
_default_pipeline = "off"


def register_kernel(name: str, *, pallas: Callable, reference: Callable,
                    pipelined: bool = False) -> None:
    """Register a (pallas, jnp-reference) implementation pair.

    The pallas callable must accept ``interpret: bool``; the reference is
    pure jnp with the same positional/keyword contract minus ``interpret``.
    ``pipelined=True`` declares that the pallas callable also accepts a
    ``pipeline`` kwarg (see module docstring).
    """
    _REGISTRY[name] = {"pallas": pallas, "jnp": reference,
                       "pipelined": pipelined}


def registered_kernels() -> Dict[str, Dict[str, Callable]]:
    return dict(_REGISTRY)


def set_default_backend(backend: str) -> None:
    """Process-wide default for ``backend=None`` dispatches."""
    global _default_backend
    if backend not in _BACKENDS:
        raise ValueError(f"backend {backend!r} not in {_BACKENDS}")
    _default_backend = backend


def default_backend() -> str:
    return _default_backend


@contextlib.contextmanager
def use_backend(backend: str):
    """Scoped default-backend override (trace-time; see module docstring)."""
    prev = _default_backend
    set_default_backend(backend)
    try:
        yield
    finally:
        set_default_backend(prev)


def set_default_pipeline(pipeline: str) -> None:
    """Process-wide default for ``pipeline=None`` dispatches."""
    global _default_pipeline
    if pipeline not in _PIPELINES:
        raise ValueError(f"pipeline {pipeline!r} not in {_PIPELINES}")
    _default_pipeline = pipeline


def default_pipeline() -> str:
    return _default_pipeline


@contextlib.contextmanager
def use_pipeline(pipeline: str):
    """Scoped default-pipeline override (trace-time, like use_backend)."""
    prev = _default_pipeline
    set_default_pipeline(pipeline)
    try:
        yield
    finally:
        set_default_pipeline(prev)


def resolve(name: str, backend: Optional[str] = None, *,
            sharded: bool = False,
            pipeline: Optional[str] = None) -> Callable:
    """Resolve a registered op to a concrete callable for this process.

    ``sharded=True`` marks a call made from inside ``shard_map`` (the
    tensor-parallel serve path, serve/shard.py): the kernel sees per-shard
    operands (local KV heads, local page pools).  On TPU the Pallas kernel
    runs per shard as usual; off-TPU the ``auto`` backend resolves to the
    jnp reference instead of the interpreted kernel — interpret mode
    re-traces the whole grid per shard, and the reference IS the oracle
    the kernels are byte-checked against.  An explicit ``backend="pallas"``
    still forces the kernel.

    ``pipeline`` selects the page-streaming schedule for pipelined ops
    (``"off"``/``"double"``; ``None`` -> the process default).  It only
    binds into the pallas partial — the jnp reference has no pages to
    stream — and requesting ``"double"`` on a non-pipelined op raises.
    """
    backend = backend or _default_backend
    if backend not in _BACKENDS:
        raise ValueError(f"backend {backend!r} not in {_BACKENDS}")
    pipeline = pipeline or _default_pipeline
    if pipeline not in _PIPELINES:
        raise ValueError(f"pipeline {pipeline!r} not in {_PIPELINES}")
    impls = _REGISTRY[name]
    if pipeline != "off" and not impls["pipelined"]:
        raise ValueError(f"op {name!r} does not support pipeline="
                         f"{pipeline!r} (not a paged streaming kernel)")
    if backend == "jnp":
        return impls["jnp"]
    if backend == "auto" and sharded and _interpret_default():
        return impls["jnp"]
    kwargs = {"interpret": _interpret_default()}
    if impls["pipelined"]:
        kwargs["pipeline"] = pipeline
    return functools.partial(impls["pallas"], **kwargs)


register_kernel("paged_attention",
                pallas=_paged.paged_attention,
                reference=_paged.paged_attention_reference,
                pipelined=True)
register_kernel("mla_paged_attention",
                pallas=_paged.mla_paged_attention,
                reference=_paged.mla_paged_attention_reference,
                pipelined=True)
register_kernel("paged_attention_verify",
                pallas=_paged.paged_attention_verify,
                reference=_paged.paged_attention_verify_reference,
                pipelined=True)
register_kernel("mla_paged_attention_verify",
                pallas=_paged.mla_paged_attention_verify,
                reference=_paged.mla_paged_attention_verify_reference,
                pipelined=True)
def _flash_model_layout(q, k, v, *, causal: bool = True,
                        interpret: bool = False):
    """flash kernel in model layout — q (B,Sq,H,hd), k/v (B,Sk,KV,hd)."""
    o = _flash.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, interpret=interpret)
    return o.transpose(0, 2, 1, 3)


register_kernel("flash_attention",
                pallas=_flash_model_layout,
                reference=ref.mha)


def paged_attention(q, k_pool, v_pool, block_tables, pos, *, scale,
                    soft_cap: float = 0.0, k_scale=None, v_scale=None,
                    backend: Optional[str] = None,
                    sharded: bool = False, pipeline: Optional[str] = None):
    """Dispatching GQA paged-decode attention (see kernels/paged_attention).

    q (B, KV, G, hd); pools (P, KV, page, hd); block_tables (B, n_blocks);
    pos (B,).  Returns (B, KV, G, hd).  ``k_scale``/``v_scale``
    (P, KV, 1, page) float32 dequantize quantized pools (kernels/quantize.py)
    — both backends apply the identical dequant, so the oracle contract
    holds on quantized caches.
    """
    impl = resolve("paged_attention", backend, sharded=sharded,
                   pipeline=pipeline)
    return impl(q, k_pool, v_pool, block_tables, pos, scale=scale,
                soft_cap=soft_cap, k_scale=k_scale, v_scale=v_scale)


def mla_paged_attention(q_lat, q_rope, c_pool, r_pool, block_tables, pos, *,
                        scale, c_scale=None, r_scale=None,
                        backend: Optional[str] = None,
                        sharded: bool = False,
                        pipeline: Optional[str] = None):
    """Dispatching MLA paged-decode attention over the compressed cache.

    q_lat (B, H, r); q_rope (B, H, dr); pools (P, page, r) / (P, page, dr);
    block_tables (B, n_blocks); pos (B,).  Returns o_lat (B, H, r).
    ``c_scale``/``r_scale`` (P, 1, page) float32 dequantize quantized pools.
    """
    impl = resolve("mla_paged_attention", backend, sharded=sharded,
                   pipeline=pipeline)
    return impl(q_lat, q_rope, c_pool, r_pool, block_tables, pos,
                scale=scale, c_scale=c_scale, r_scale=r_scale)


def paged_attention_verify(q, k_pool, v_pool, block_tables, pos, *, scale,
                           soft_cap: float = 0.0, k_scale=None,
                           v_scale=None,
                           backend: Optional[str] = None,
                           sharded: bool = False,
                           pipeline: Optional[str] = None):
    """Dispatching GQA multi-token paged verification (spec decoding).

    q (B, T, KV, G, hd) — T draft-chain query tokens at positions
    ``pos + t``; pools (P, KV, page, hd); block_tables (B, n_blocks);
    pos (B,) first-query position.  Returns (B, T, KV, G, hd).
    """
    impl = resolve("paged_attention_verify", backend, sharded=sharded,
                   pipeline=pipeline)
    return impl(q, k_pool, v_pool, block_tables, pos, scale=scale,
                soft_cap=soft_cap, k_scale=k_scale, v_scale=v_scale)


def mla_paged_attention_verify(q_lat, q_rope, c_pool, r_pool, block_tables,
                               pos, *, scale, c_scale=None, r_scale=None,
                               backend: Optional[str] = None,
                               sharded: bool = False,
                               pipeline: Optional[str] = None):
    """Dispatching MLA multi-token paged verification over the latent cache.

    q_lat (B, T, H, r); q_rope (B, T, H, dr); pools (P, page, r) /
    (P, page, dr); pos (B,) first-query position.  Returns (B, T, H, r).
    """
    impl = resolve("mla_paged_attention_verify", backend, sharded=sharded,
                   pipeline=pipeline)
    return impl(q_lat, q_rope, c_pool, r_pool, block_tables, pos,
                scale=scale, c_scale=c_scale, r_scale=r_scale)


@functools.partial(jax.jit, static_argnames=("fuse",))
def inner_product(x, w, fuse: str = "none"):
    return _ip.inner_product(x, w, fuse=fuse, interpret=_interpret_default())


@jax.jit
def gelu(x):
    return _gelu.gelu_blocked(x, interpret=_interpret_default())


@jax.jit
def gelu_naive(x):
    return _gelu.gelu_naive(x, interpret=_interpret_default())


@jax.jit
def layernorm(x, scale, bias):
    return _ln.layernorm(x, scale, bias, interpret=_interpret_default())


@functools.partial(jax.jit, static_argnames=("window",))
def avg_pool(x, window: int = 2):
    return _avgpool.avg_pool_blocked(x, window=window,
                                     interpret=_interpret_default())


@functools.partial(jax.jit, static_argnames=("window",))
def avg_pool_naive(x, window: int = 2):
    return _avgpool.avg_pool_naive(x, window=window,
                                   interpret=_interpret_default())


@jax.jit
def conv2d(x, w):
    return _conv_direct.conv2d_direct(x, w, interpret=_interpret_default())


@jax.jit
def conv2d_winograd(x, w):
    return _conv_winograd.conv2d_winograd(x, w,
                                          interpret=_interpret_default())


@functools.partial(jax.jit, static_argnames=("causal",))
def flash_attention(q, k, v, causal: bool = True):
    """q (B,Sq,H,hd), k/v (B,Sk,KV,hd) — model-layout wrapper."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = _flash.flash_attention(qt, kt, vt, causal=causal,
                               interpret=_interpret_default())
    return o.transpose(0, 2, 1, 3)


# max_pool intentionally routes to the jnp reference: the paper's §3.5
# caveat — its "work" is comparisons, invisible to FLOP counters.
max_pool = jax.jit(ref.max_pool, static_argnames=("window", "stride"))
