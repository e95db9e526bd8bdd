"""Overlap-friendly collectives: ring collective matmuls.

XLA schedules an all-gather *then* the matmul; the ring formulations below
(shard_map + ppermute) compute each shard's partial product while the next
shard's data is in flight — the TPU collective-matmul overlap pattern
(Wang et al., ASPLOS'23).  In the compiled HLO the all-gather disappears,
replaced by n-1 ppermutes the latency-hiding scheduler pipelines with the
local matmuls; wall-clock overlap needs real ICI, numerical equality is
unit-tested here.

These are the next §Perf levers for the ICI-bound cells (qwen3-14b's CP
attention gathers, kimi's EP combine) — wired as library primitives.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map


def row_parallel_psum(partial: jax.Array, axis: str) -> jax.Array:
    """All-reduce epilogue of a row-parallel (contraction-sharded) matmul
    inside ``shard_map``: each shard contracts its slice of the inner dim
    (attention o-proj over local heads, FFN down-proj over local d_ff) and
    the partial products are summed over ``axis``.  This is the Megatron
    ``g-bar`` edge — 2 of these per transformer block is the entire ICI
    cost of tensor-parallel decode, and exactly what the serve ledger's
    communication term prices (scheduler.decode_step_ici_bytes)."""
    return jax.lax.psum(partial, axis)


def ring_matmul_reduce(h: jax.Array, w: jax.Array, axis: str) -> jax.Array:
    """Overlapped row-parallel matmul + all-reduce, for use INSIDE a
    ``shard_map`` body (same call site and semantics as
    ``row_parallel_psum(h @ w, axis)``).

    h (..., K_local) per-shard activations, w (K_local, N) this shard's
    rows of the full weight; returns the fully reduced (..., N) replicated
    over ``axis``.  Instead of one blocking matmul + all-reduce, the N
    columns split into n ring chunks: step s multiplies the local shard's
    activations into ONE chunk of w while the accumulator for the
    previous chunk is in flight on the ring (reduce-scatter by
    ring ppermute), and a tiled all-gather reassembles the full row.  The
    loop is unrolled in Python so the compiled HLO shows n-1 discrete
    collective-permutes the latency-hiding scheduler can pipeline with
    the chunk matmuls.

    Wire bytes: (n-1) ppermutes of one chunk + a tiled all-gather of the
    full row = 2 * payload * (n-1)/n — exactly the analytic all-reduce
    bytes the serve ledger already charges for this edge
    (scheduler.decode_step_ici_bytes), so the ledger-vs-HLO collective
    crosscheck holds on both paths (modulo column padding, below).

    N need not divide by the shard count: w pads with zero columns to the
    next multiple inside the jitted body and the result slices back —
    pad-and-slice, so every mesh shape works, not just powers of two.
    Chunk sums accumulate in the activation dtype, matching what
    ``psum`` puts on the wire; the addition ORDER differs from the
    all-reduce's, so outputs are close but not bitwise equal — greedy
    byte-identity is asserted at the token level.
    """
    n = jax.lax.psum(1, axis)
    if n == 1:
        return h @ w
    idx = jax.lax.axis_index(axis)
    N = w.shape[-1]
    chunk = -(-N // n)                       # ceil: pad-and-slice
    if chunk * n != N:
        w = jnp.pad(w, ((0, 0), (0, chunk * n - N)))
    perm = [(i, (i + 1) % n) for i in range(n)]
    acc = None
    for s in range(n):
        c = (idx - s - 1) % n                # chunk this shard works on
        w_c = jax.lax.dynamic_slice_in_dim(w, c * chunk, chunk, axis=1)
        local = h @ w_c                      # (..., chunk), native dtype
        if acc is None:
            acc = local
        else:
            acc = jax.lax.ppermute(acc, axis, perm) + local
    # after n steps shard idx holds the fully reduced chunk idx
    out = jax.lax.all_gather(acc, axis, axis=acc.ndim - 1, tiled=True)
    if chunk * n != N:
        out = jax.lax.slice_in_dim(out, 0, N, axis=out.ndim - 1)
    return out


def row_parallel_matmul(h: jax.Array, w: jax.Array, axis: Optional[str],
                        overlap: str = "none") -> jax.Array:
    """Row-parallel matmul epilogue dispatcher for shard_map step bodies.

    ``overlap="none"`` is the blocking reference — matmul then
    ``row_parallel_psum`` — and is byte-identical to the historical call
    sites.  ``overlap="ring"`` routes to :func:`ring_matmul_reduce`.
    ``axis=None`` (unsharded) is always the plain matmul.
    """
    if overlap not in ("none", "ring"):
        raise ValueError(f"overlap {overlap!r} not in ('none', 'ring')")
    if axis is None:
        return h @ w
    if overlap == "ring":
        return ring_matmul_reduce(h, w, axis)
    return row_parallel_psum(h @ w, axis)


def all_gather_cols(x: jax.Array, axis: str) -> jax.Array:
    """Gather a column-sharded activation to its full last dim inside
    ``shard_map`` (tiled all-gather) — the vocab-sharded logits edge of
    tensor-parallel decode: every shard computes V/n logit columns, the
    sampler needs the full row."""
    return jax.lax.all_gather(x, axis, axis=x.ndim - 1, tiled=True)


def ring_allgather_matmul(x: jax.Array, w: jax.Array, mesh: Mesh,
                          axis: str = "model") -> jax.Array:
    """``all_gather(x, axis) @ w`` without materializing the gather.

    The megatron sequence-parallel entry edge: x (S, K) sharded P(axis,)
    over its rows, w (K, N) sharded P(None, axis) column-parallel.
    Output (S, N) sharded P(None, axis).  Each ring step multiplies the
    resident row block into its output slot while ppermute forwards it.

    S and N need not divide the shard count: both pad to the next
    multiple (zero rows / zero columns) before the shard_map and the
    result slices back — pad-and-slice, so every mesh shape works.
    """
    n = mesh.shape[axis]
    S, N = x.shape[0], w.shape[1]
    s_pad = -(-S // n) * n
    n_pad = -(-N // n) * n
    if s_pad != S:
        x = jnp.pad(x, ((0, s_pad - S), (0, 0)))
    if n_pad != N:
        w = jnp.pad(w, ((0, 0), (0, n_pad - N)))

    def body(x_blk, w_blk):
        # x_blk (S/n, K); w_blk (K, N/n)
        idx = jax.lax.axis_index(axis)
        rows = x_blk.shape[0]
        perm = [(i, (i + 1) % n) for i in range(n)]
        out = jnp.zeros((rows * n, w_blk.shape[1]), jnp.float32)

        def step(i, carry):
            acc, blk = carry
            src = (idx - i) % n          # original owner of `blk`
            acc = jax.lax.dynamic_update_slice(
                acc, (blk @ w_blk).astype(jnp.float32), (src * rows, 0))
            blk = jax.lax.ppermute(blk, axis, perm)
            return acc, blk

        out, _ = jax.lax.fori_loop(0, n, step, (out, x_blk))
        return out.astype(x_blk.dtype)

    out = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(None, axis)),
        out_specs=P(None, axis),
        check_vma=False,
    )(x, w)
    if s_pad != S or n_pad != N:
        out = out[:S, :N]
    return out


def psum_scatter_matmul(x: jax.Array, w: jax.Array, mesh: Mesh,
                        axis: str = "model") -> jax.Array:
    """Row-parallel matmul with a reduce-scatter epilogue.

    x (M, K) sharded P(None, axis); w (K, N) sharded P(axis, None);
    output (M, N) sharded P(None, axis).  Halves wire bytes vs the
    all-reduce epilogue whenever the consumer is itself sharded over
    ``axis`` (megatron's g/ḡ pairing) — the o-proj/down-proj edge.

    N need not divide the shard count: the partial product pads with
    zero columns to the next multiple INSIDE the jitted body before the
    reduce-scatter and the gathered result slices back.
    """
    n = mesh.shape[axis]
    N = w.shape[1]
    n_pad = -(-N // n) * n

    def body(x_blk, w_blk):
        part = (x_blk @ w_blk).astype(jnp.float32)
        if n_pad != N:
            part = jnp.pad(part, ((0, 0), (0, n_pad - N)))
        return jax.lax.psum_scatter(part, axis, scatter_dimension=1,
                                    tiled=True).astype(x_blk.dtype)

    out = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), P(axis, None)),
        out_specs=P(None, axis),
        check_vma=False,
    )(x, w)
    if n_pad != N:
        out = out[:, :N]
    return out
