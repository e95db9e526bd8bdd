"""Pallas TPU kernel library.

Import style: ``from repro.kernels import ops, ref`` — the jit'd public
wrappers live in ops, the jnp oracles in ref.  (Function names are NOT
re-exported at package level: they would shadow the kernel submodules.)

Registry / dispatch layer (ops.py)
----------------------------------
Ops with both a Pallas kernel and a jnp reference register as named
(pallas, reference) pairs; model code dispatches by name through
``ops.paged_attention`` / ``ops.mla_paged_attention`` (or ``ops.resolve``)
with a backend of ``"pallas"`` | ``"jnp"`` | ``"auto"``.  ``auto`` (the
default) runs the Pallas kernel everywhere — interpret mode off-TPU, so
the whole library validates on CPU CI; Mosaic on a TPU backend.  The jnp
references are the byte-checked oracles the serve engine's correctness
tests pin against.  Backend resolution happens at trace time: jitted
callers (the serve engine's decode step) rebuild on ``Engine.reset()``.

Registered ops: ``paged_attention`` (GQA decode over the paged KV pool),
``mla_paged_attention`` (latent-space decode over the compressed MLA
cache), ``flash_attention`` (full-sequence causal GQA).

VMEM budgets (fp32 accounting; ~16 MiB/core usable)
---------------------------------------------------
* flash_attention: resident K/V stream of one KV head + 3 blocks
  ~ 2*Sk*hd*4 B — Sk <= 8192, hd = 128 fits comfortably; longer sequences
  use the host-level q-chunk wrapper.
* paged_attention (decode): the live-page walk double-buffers a
  (KV, C * page, hd) K slab and V slab, C * page ~ 256 keys, held under
  4 MiB (2 MiB for qwen3-0.6b); the per-block walk of quantized pools
  holds one (page_size, hd) K slab + V slab + the (G, hd)
  query/accumulator and (G, 1) softmax carries per grid step.
* mla_paged_attention: (page, r + rope_hd) slabs + (H, r) accumulator;
  r <= 576 keeps this under ~2 MiB even at 128 heads.
"""

from . import ops, ref

__all__ = ["ops", "ref"]
