"""Continuous-batching scheduler with a per-request decode roofline ledger.

Scheduling
----------
Requests move WAITING -> PREFILL -> RUNNING -> FINISHED, with a
PREEMPTED detour when the block pool runs dry.  Each engine step:

1. *admit*: resume preempted requests first (swap-in or
   recompute-re-prefill), then pop waiting requests into free decode
   slots under *watermark admission*: a request is admitted when the pool
   can back its PROMPT plus a configurable free-page watermark — not the
   full ``prompt + max_new_tokens`` budget.  Slots then grow one page at
   a time as decode crosses page boundaries (kv_cache.ensure_writable);
   the watermark is the slack that keeps growth from immediately starving.
2. *prefill*: every PREFILL request advances one chunk of at most
   ``prefill_chunk`` prompt tokens (0 = the whole prompt in one chunk),
   starting past whatever prefix the block pool's content-hash index
   already holds (prefix sharing skips both the pages and the compute).
   Chunks attend to the request's previously written pages, so chunked and
   whole-prompt prefill are mathematically identical for dense archs.
   (MoE caveat: expert-capacity cutoffs scale with tokens-per-call, so a
   chunked MoE prefill can drop different tokens than a whole-prompt one —
   the same GShard discontinuity batched decode already accepts; prefix
   sharing is gated off for MoE for the same reason.)
3. *decode*: one jitted step over the packed slot batch produces the next
   token for every RUNNING request; finished requests (stop token or token
   budget) are evicted and their pages recycled.  When a slot cannot grow
   (pool dry even after evicting cached pages), the newest-admitted
   running request is *preempted* — its pages either swapped to host
   memory or dropped for recompute-on-resume — and re-queued ahead of all
   waiting work.

Decode roofline ledger (paper eq. 1: ``P = min(pi, I * beta)``)
---------------------------------------------------------------
Generating one token for a request with context length ``L`` does

    W(L) = 2 * N_active  +  4 * H * hd * L * n_attn_blocks        [FLOPs]

(the ``model_flops`` decode convention: weight matmuls + score/value
attention math), and moves

    Q(L) = params_bytes / B_active                               [weights]
         + L * kv_line_bytes  +  kv_line_bytes                   [KV r/w]
         + state_bytes (read+write, recurrent mixers)            [O(1)]

through HBM.  The per-token arithmetic intensity ``I = W/Q`` is tiny —
decode is the most memory-bound workload we serve — and grows with the
number of co-resident requests ``B_active`` because the weight read is
amortized across the batch: exactly the continuous-batching win the
roofline model predicts.  Each request accumulates ``W`` and ``Q`` over
its lifetime; at completion the ledger folds into
:class:`repro.core.roofline.model.RooflineTerms`, giving the request its
arithmetic intensity, its bound class (memory- vs compute-bound), and the
attainable-performance ceiling its tokens/s can be compared against.
"""

from __future__ import annotations

import collections
import dataclasses
import enum
import functools
import math
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.roofline.hardware import ChipSpec, TPU_V5E, tp_scope
from repro.core.roofline.model import PhaseTraffic, RooflineTerms, make_terms
from repro.kernels import quantize as kvq
from repro.kernels.paged_attention import (mla_paged_decode_vmem_bytes,
                                           paged_decode_vmem_bytes)
from repro.models.common import ModelConfig, model_flops, param_counts
from repro.obs.clock import now
from repro.obs.trace import LIFECYCLE_TID, SLOT_TID0, span

from .kv_cache import PagedKVCache


# --------------------------------------------------------------------------
# Analytic per-token decode cost model
# --------------------------------------------------------------------------

def _dtype_bytes(dtype: str) -> int:
    import jax.numpy as jnp
    return jnp.dtype(dtype).itemsize


def _kv_store_isize(cfg: ModelConfig) -> int:
    """Itemsize KV pages are stored at (quantized storage type when
    cfg.kv_dtype != bf16, else the activation dtype)."""
    return kvq.store_itemsize(cfg.kv_dtype, cfg.dtype)


def _kv_scale_isize(cfg: ModelConfig) -> int:
    """Per-line f32 scale bytes a quantized pool adds (0 when bf16)."""
    return 4 if kvq.is_quantized(cfg.kv_dtype) else 0


@functools.lru_cache(maxsize=None)
def kv_line_bytes(cfg: ModelConfig) -> int:
    """Bytes of growing cache per token summed over all layers: the KV line
    read once per context token per decode step.  Quantized pools
    (cfg.kv_dtype int8/fp8_e4m3) shrink the value bytes to the storage
    itemsize and add the per-line float32 scales the page walk streams
    alongside — one per kv head for GQA (k and v each), two per line for
    MLA (latent + rope)."""
    isize = _kv_store_isize(cfg)
    s = _kv_scale_isize(cfg)
    total = 0
    for unit, reps in cfg.segments():
        for b in unit:
            if b.mixer == "attn":
                total += 2 * cfg.n_kv_heads * (cfg.hd * isize + s) * reps
            elif b.mixer == "mla":
                total += ((cfg.kv_lora_rank + cfg.rope_head_dim) * isize
                          + 2 * s) * reps
    return total


@functools.lru_cache(maxsize=None)
def state_bytes(cfg: ModelConfig) -> int:
    """Bytes of O(1) recurrent state summed over all layers (mamba h/conv,
    mLSTM C/n/m, sLSTM c/n/h/m) — read and written once per decode step."""
    isize = _dtype_bytes(cfg.dtype)
    di = cfg.d_inner
    total = 0
    for unit, reps in cfg.segments():
        for b in unit:
            if b.mixer == "mamba":
                total += (di * cfg.mamba_d_state * 4
                          + (cfg.mamba_conv_width - 1) * di * isize) * reps
            elif b.mixer == "mlstm":
                d2 = 2 * cfg.d_model
                hd = d2 // cfg.n_heads
                total += (cfg.n_heads * (hd * hd + hd + 1) * 4
                          + (cfg.mamba_conv_width - 1) * d2 * isize) * reps
            elif b.mixer == "slstm":
                total += 4 * cfg.d_model * 4 * reps
    return total


@functools.lru_cache(maxsize=None)
def params_bytes_active(cfg: ModelConfig) -> float:
    """Weight bytes touched per decode step: active params only (a routed
    MoE step reads top-k expert weights, not the full expert bank)."""
    return param_counts(cfg)["active"] * _dtype_bytes(cfg.dtype)


def decode_token_flops(cfg: ModelConfig, context_len: int) -> float:
    """W for one generated token at context length ``context_len``."""
    return model_flops(cfg, context_len, 1, "decode")


def decode_token_bytes(cfg: ModelConfig, context_len: int,
                       active_batch: int) -> float:
    """Q for one generated token: amortized weight read + this request's
    KV line reads/writes + recurrent state traffic."""
    weights = params_bytes_active(cfg) / max(active_batch, 1)
    kv = (context_len + 1) * kv_line_bytes(cfg)          # read ctx + write 1
    return weights + kv + 2 * state_bytes(cfg)


def attn_kernel_vmem_bytes(cfg: ModelConfig, context_len: int,
                           page_size: int, n_q: int = 1,
                           pipeline: str = "off") -> float:
    """VMEM traffic of one slot's paged-attention walks summed over all
    attention/MLA layers: the HBM page stream crossing VMEM page-padded,
    plus the kernel-resident re-touches (query slab re-reads per block
    step, fp32 softmax carries read+written) the HBM ledger never sees.
    Priced from the kernel grids in kernels/paged_attention.py;
    ``pipeline="double"`` prices the two-slab DMA kernels (query slab
    fetched once per program instead of per block step)."""
    isize = _dtype_bytes(cfg.dtype)
    kv_isize = _kv_store_isize(cfg)
    scale_isize = _kv_scale_isize(cfg)
    total = 0.0
    for unit, reps in cfg.segments():
        for b in unit:
            if b.mixer == "attn":
                total += reps * paged_decode_vmem_bytes(
                    context_len=context_len, page_size=page_size,
                    n_heads=cfg.n_heads, kv_heads=cfg.n_kv_heads,
                    head_dim=cfg.hd, isize=isize, n_q=n_q,
                    pipeline=pipeline, kv_isize=kv_isize,
                    scale_isize=scale_isize)
            elif b.mixer == "mla":
                total += reps * mla_paged_decode_vmem_bytes(
                    context_len=context_len, page_size=page_size,
                    n_heads=cfg.n_heads, lora_rank=cfg.kv_lora_rank,
                    rope_dim=cfg.rope_head_dim, isize=isize, n_q=n_q,
                    pipeline=pipeline, kv_isize=kv_isize,
                    scale_isize=scale_isize)
    return total


def decode_token_vmem_bytes(cfg: ModelConfig, context_len: int,
                            active_batch: int, page_size: int,
                            pipeline: str = "off") -> float:
    """VMEM-level bytes for one generated token: every non-KV HBM byte of
    the step (amortized weight read, recurrent state traffic) crosses
    VMEM exactly once on its way to the compute units, and the paged
    attention kernels add their streamed + resident traffic on top."""
    passthrough = (params_bytes_active(cfg) / max(active_batch, 1)
                   + 2 * state_bytes(cfg))
    return passthrough + attn_kernel_vmem_bytes(cfg, context_len, page_size,
                                                pipeline=pipeline)


def verify_step_vmem_bytes(cfg: ModelConfig, context_len: int, n_fed: int,
                           active_batch: int, page_size: int,
                           pipeline: str = "off") -> float:
    """VMEM-level bytes for one slot's multi-token verification step:
    one weight pass-through scores ``n_fed`` tokens sharing a single
    page walk (the verify kernels flatten the draft window into extra
    query rows, so only the resident re-touches scale with n_fed)."""
    passthrough = (params_bytes_active(cfg) / max(active_batch, 1)
                   + 2 * state_bytes(cfg))
    return passthrough + attn_kernel_vmem_bytes(cfg, context_len, page_size,
                                                n_q=n_fed, pipeline=pipeline)


def slot_swap_bytes(cfg: ModelConfig, n_blocks: int, page_size: int) -> float:
    """Host-link bytes to park (or restore) one slot: its physical pages
    across every paged cache leaf plus its recurrent-state rows — the
    analytic prediction serve/crosscheck.crosscheck_host validates
    against the packed swap DMA's compiled output bytes."""
    return float(n_blocks * page_size * kv_line_bytes(cfg)
                 + state_bytes(cfg))


@functools.lru_cache(maxsize=None)
def kv_shard_fraction(cfg: ModelConfig, tp: int) -> float:
    """Fraction of the per-token KV line resident on EACH chip at TP
    width ``tp``: GQA k/v pools shard over kv_heads (1/tp of the line per
    chip), while MLA latent pools replicate (serve/shard.py pool_pspecs)
    — every chip walks the full compressed cache.  Feeds the per-chip
    HBM term of the sharded ledger (RooflineLedger.terms)."""
    if tp <= 1:
        return 1.0
    total = kv_line_bytes(cfg)
    if total == 0:
        return 1.0
    isize = _kv_store_isize(cfg)
    s = _kv_scale_isize(cfg)
    sharded = 0
    for unit, reps in cfg.segments():
        for b in unit:
            if b.mixer == "attn":
                # per-(line, kv_head) scales shard WITH the kv_heads axis
                sharded += 2 * cfg.n_kv_heads * (cfg.hd * isize + s) * reps
    return (sharded / tp + (total - sharded)) / total


@functools.lru_cache(maxsize=None)
def decode_collective_count(cfg: ModelConfig) -> int:
    """All-reduces per tensor-parallel decode step: one per row-parallel
    matmul epilogue — the attention/MLA o-proj and the dense-FFN
    down-proj (the Megatron pairing; see parallel.collectives
    .row_parallel_psum and the psum hooks in models/)."""
    n = 0
    for unit, reps in cfg.segments():
        for b in unit:
            if b.mixer in ("attn", "mla"):
                n += reps
            if b.ffn == "dense":
                n += reps
    return n


def decode_step_ici_bytes(cfg: ModelConfig, batch: int, tp: int,
                          n_tokens: int = 1) -> float:
    """Per-device ICI wire bytes of ONE tensor-parallel packed decode step
    over ``batch`` slots feeding ``n_tokens`` tokens per slot (1 for
    decode, k+1 for speculative verify).

    Each of the :func:`decode_collective_count` all-reduces moves a
    (batch, n_tokens, d_model) activation with ring wire cost
    ``2 * payload * (tp-1)/tp`` per device; an untied vocab-sharded head
    adds one tiled logits all-gather at ``payload * (tp-1)/tp``.  This is
    the analytic side that serve/crosscheck.crosscheck_collectives
    validates against the all-reduce/all-gather ops in the compiled
    shard_map module's HLO — and the ``I_comm`` numerator's denominator
    in the communication roofline (core.roofline.model.RooflineTerms
    .roofs)."""
    if tp <= 1:
        return 0.0
    isize = _dtype_bytes(cfg.dtype)
    ring = (tp - 1) / tp
    act_payload = batch * n_tokens * cfg.d_model * isize
    wire = decode_collective_count(cfg) * 2.0 * act_payload * ring
    if not cfg.tie_embeddings:
        wire += batch * n_tokens * cfg.vocab_size * isize * ring
    return wire


# --------------------------------------------------------------------------
# Requests + ledger
# --------------------------------------------------------------------------

class RequestState(enum.Enum):
    WAITING = "waiting"
    PREFILL = "prefill"
    RUNNING = "running"
    PREEMPTED = "preempted"
    FINISHED = "finished"


@dataclasses.dataclass
class RooflineLedger:
    """Per-request W/Q accounting, folded into RooflineTerms at completion.

    Speculative decoding splits the decode stream into phases: *verify*
    steps run on the target model (accounted into ``decode_flops`` /
    ``decode_bytes`` so ``arithmetic_intensity`` reflects what the target
    weights actually did: one weight read scores k+1 tokens) and *draft*
    work runs on the proposer (tracked separately in ``draft_flops`` /
    ``draft_bytes`` — it is overhead, not target throughput).
    ``weight_passes`` counts target forward passes, so
    ``tokens_per_pass`` is the measured speculative yield E[tokens/pass];
    ``acceptance_rate`` is accepted drafts / proposed drafts.

    The HBM-capacity axis: ``preemptions`` counts the times this request
    was kicked out of its slot under pool pressure, ``swap_bytes`` the
    host<->device traffic its swap round-trips moved,
    ``prefix_cached_tokens`` the prompt tokens admission found already
    resident in the block pool's content-hash index (pages AND prefill
    compute saved), and ``pages_peak`` the most physical pages the request
    ever held.
    """
    prefill_flops: float = 0.0
    decode_flops: float = 0.0
    decode_bytes: float = 0.0
    decode_kv_bytes: float = 0.0     # KV-walk + state share of decode_bytes
    decode_vmem_bytes: float = 0.0   # on-chip VMEM traffic (stream+resident)
    decode_ici_bytes: float = 0.0    # per-device TP collective wire bytes
    decode_tokens: int = 0
    decode_batch_sum: int = 0        # sum of co-resident batch sizes
    weight_passes: int = 0           # target forward passes (decode+verify)
    draft_flops: float = 0.0         # proposer-side work (draft model)
    draft_bytes: float = 0.0
    proposed: int = 0                # draft tokens offered for verification
    accepted: int = 0                # draft tokens that survived
    preemptions: int = 0             # times evicted under pool pressure
    swap_bytes: float = 0.0          # host<->device swap traffic
    prefix_cached_tokens: int = 0    # prompt tokens served from the index
    pages_peak: int = 0              # most physical pages held at once
    # cross-replica KV-page migration (serve/cluster.py): each migration
    # packs the slot's pages into one SwapSnapshot on the source replica
    # and re-materializes it in the destination's pool; the bytes ride
    # ``migration_link`` ("dcn" across replica groups, "ici" in-pod).
    migrations: int = 0              # replica-to-replica moves
    migration_bytes: float = 0.0     # packed-snapshot bytes moved
    migration_pages: int = 0         # physical pages those snapshots held
    migration_link: str = "dcn"      # wire level that carried them

    def add_decode_token(self, cfg: ModelConfig, context_len: int,
                         active_batch: int, ici_bytes: float = 0.0,
                         vmem_bytes: float = 0.0) -> None:
        """``ici_bytes`` is this request's share of the step's collective
        wire traffic (zero on a single chip — the sharded engine charges
        ``decode_step_ici_bytes / active_batch``); ``vmem_bytes`` the
        on-chip traffic of :func:`decode_token_vmem_bytes` (zero keeps
        pre-hierarchy callers byte-identical)."""
        self.decode_flops += decode_token_flops(cfg, context_len)
        self.decode_bytes += decode_token_bytes(cfg, context_len,
                                                active_batch)
        self.decode_kv_bytes += ((context_len + 1) * kv_line_bytes(cfg)
                                 + 2 * state_bytes(cfg))
        self.decode_vmem_bytes += vmem_bytes
        self.decode_ici_bytes += ici_bytes
        self.decode_tokens += 1
        self.decode_batch_sum += active_batch
        self.weight_passes += 1

    def add_verify_step(self, cfg: ModelConfig, context_len: int,
                        n_fed: int, n_committed: int, n_accepted: int,
                        n_proposed: int, active_batch: int,
                        ici_bytes: float = 0.0,
                        vmem_bytes: float = 0.0) -> None:
        """One multi-token verification step: ``n_fed`` = k+1 tokens scored
        in one weight pass at context ``context_len``; ``n_committed``
        tokens entered the request (``n_accepted`` of them surviving
        drafts — the rest is the corrected/bonus token, unless the commit
        was cut short by a stop token or the token budget).

        W: each fed token t attends ``context_len + t`` keys.  Q: ONE
        amortized weight read, one page walk over the context plus the
        just-written draft lines — read ``context_len + n_fed - 1`` lines,
        write ``n_fed`` — so Q barely moves while W scales by n_fed: the
        measured intensity gain speculative decoding buys.
        """
        line = kv_line_bytes(cfg)
        self.decode_flops += sum(
            decode_token_flops(cfg, context_len + t) for t in range(n_fed))
        self.decode_bytes += (
            params_bytes_active(cfg) / max(active_batch, 1)
            + (context_len + 2 * n_fed - 1) * line
            + 2 * state_bytes(cfg))
        self.decode_kv_bytes += ((context_len + 2 * n_fed - 1) * line
                                 + 2 * state_bytes(cfg))
        self.decode_vmem_bytes += vmem_bytes
        self.decode_ici_bytes += ici_bytes
        self.decode_tokens += n_committed
        self.decode_batch_sum += n_committed * active_batch
        self.weight_passes += 1
        self.proposed += n_proposed
        self.accepted += n_accepted

    def add_draft_cost(self, draft_cfg: ModelConfig, context_len: int,
                       n_fed: int, n_decodes: int, active_batch: int
                       ) -> None:
        """Proposer-side work for one round on a draft model: a catch-up
        pass over ``n_fed`` tokens (the previous round's commits, one
        weight pass) plus ``n_decodes`` single-token draft steps."""
        line = kv_line_bytes(draft_cfg)
        w = params_bytes_active(draft_cfg) / max(active_batch, 1)
        self.draft_flops += sum(
            decode_token_flops(draft_cfg, context_len + t)
            for t in range(n_fed + n_decodes))
        self.draft_bytes += (
            w + (context_len + 2 * n_fed - 1) * line
            + n_decodes * (w + (context_len + n_fed + n_decodes) * line))

    @property
    def mean_batch(self) -> float:
        return self.decode_batch_sum / max(self.decode_tokens, 1)

    @property
    def tokens_per_pass(self) -> float:
        """Measured tokens committed per target weight pass (1.0 for
        non-speculative decode; the speculative yield otherwise)."""
        return self.decode_tokens / max(self.weight_passes, 1)

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / max(self.proposed, 1)

    @property
    def arithmetic_intensity(self) -> float:
        return self.decode_flops / max(self.decode_bytes, 1.0)

    def terms(self, cfg: ModelConfig, chip: ChipSpec = TPU_V5E,
              n_chips: int = 1) -> RooflineTerms:
        """RooflineTerms for this request's decode stream.

        ``n_chips`` > 1 is the tensor-parallel scope: the weight read and
        the FLOPs split evenly across the shards (heads and d_ff divide),
        the KV-walk share splits by :func:`kv_shard_fraction` — GQA pools
        shard over kv_heads but MLA latent pools REPLICATE, so every chip
        walks the full compressed cache — and ``decode_ici_bytes`` is
        already the per-device wire traffic the sharded engine charged.
        The terms therefore expose the honest per-chip HBM roof next to
        the ICI roof at this TP width (RooflineTerms.binding_roof).

        Migration bytes land on their carrying wire level
        (``migration_link``) AND in ``migration_bytes_dev``, so the terms
        grow a separate "migration" roof (RooflineTerms.roofs) that can
        out-bind decode bandwidth on a migration-heavy workload."""
        n = max(n_chips, 1)
        hbm_dev = ((self.decode_bytes - self.decode_kv_bytes) / n
                   + self.decode_kv_bytes * kv_shard_fraction(cfg, n))
        # VMEM shards like HBM (the stream follows the KV pools, the
        # resident re-touches follow the heads) — scale by the same
        # per-device fraction; swap DMAs move each chip's pool shard, so
        # the host level follows the KV shard fraction — and so do the
        # packed migration snapshots (each chip ships its pool shard).
        vmem_dev = (self.decode_vmem_bytes * hbm_dev
                    / max(self.decode_bytes, 1.0))
        mig_dev = self.migration_bytes * kv_shard_fraction(cfg, n)
        return make_terms(
            scope=tp_scope(chip, n_chips),
            dtype=cfg.dtype,
            flops_dev=self.decode_flops / n,
            hbm_bytes_dev=hbm_dev,
            ici_wire_bytes_dev=(self.decode_ici_bytes
                                + (mig_dev if self.migration_link == "ici"
                                   else 0.0)),
            dcn_wire_bytes_dev=(mig_dev if self.migration_link == "dcn"
                                else 0.0),
            vmem_bytes_dev=vmem_dev,
            host_bytes_dev=self.swap_bytes * kv_shard_fraction(cfg, n),
            migration_bytes_dev=mig_dev,
            migration_link=self.migration_link,
            model_flops_total=self.decode_flops,
        )


@dataclasses.dataclass
class Request:
    prompt: np.ndarray                       # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0                       # nucleus mass (0 / >=1 = off)
    stop_token: Optional[int] = None
    rng: Optional[jax.Array] = None
    request_id: int = 0

    state: RequestState = RequestState.WAITING
    slot: int = -1
    prefill_pos: int = 0                     # fill tokens already prefilled
    generated: List[int] = dataclasses.field(default_factory=list)
    finish_reason: str = ""
    ledger: RooflineLedger = dataclasses.field(default_factory=RooflineLedger)
    admit_seq: int = -1                      # admission order (victim pick)
    prefill_skip: int = 0                    # fill tokens prefix-cache hit
    # preemption state: recompute-on-resume re-prefills prefill_src (the
    # context snapshotted at preemption); swap-on-resume restores the
    # parked SwapSnapshot instead
    prefill_src: Optional[np.ndarray] = None
    swap_snapshot: Optional[Any] = None
    # latency trace: wall-clock stamps from the serving host.  submit_time
    # is set by Engine.submit (or the Router front door); one entry lands
    # in token_times per committed token (speculative commits share one
    # stamp — their inter-token gap really is ~0, that is the point).
    # dispatch_time marks the router -> replica handoff (0.0 = the request
    # never crossed a router), prefill_start_time the FIRST placement into
    # a decode slot, prefill_end_time the fence after the last prefill
    # chunk — so TTFT telescopes into queue wait + prefill + first decode
    # (ttft_breakdown).
    submit_time: float = 0.0
    dispatch_time: float = 0.0
    prefill_start_time: float = 0.0
    prefill_end_time: float = 0.0
    token_times: List[float] = dataclasses.field(default_factory=list)
    # cross-replica migration state (serve/cluster.py): True between
    # Scheduler.detach on the source and the swap-in on the destination —
    # flips the restore's phase/ledger charge from "swap" to "migrate".
    migrating: bool = False

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def fill_tokens(self) -> np.ndarray:
        """Tokens the prefill phase must feed: the prompt, or — after a
        recompute-on-resume preemption — the full context at preemption
        (prompt + everything generated by then)."""
        return self.prompt if self.prefill_src is None else self.prefill_src

    @property
    def ttft(self) -> float:
        """Time to first token (s); NaN before the first commit."""
        if not self.token_times:
            return float("nan")
        return self.token_times[0] - self.submit_time

    def ttft_breakdown(self) -> Dict[str, float]:
        """TTFT split into its three telescoping segments:

            queue_wait_s   = prefill_start_time - submit_time
            prefill_s      = prefill_end_time - prefill_start_time
            first_decode_s = token_times[0] - prefill_end_time

        The stamps bracket each other (submit -> first slot placement ->
        post-prefill fence -> first commit), so the segments sum to
        :attr:`ttft` exactly — no residual bucket.  Queue wait covers both
        the router queue (submit -> dispatch) and the replica's admission
        queue (dispatch -> placement); ``dispatch_time`` splits them when
        a Router was in the path.  NaNs before the first commit."""
        if not self.token_times:
            nan = float("nan")
            return {"queue_wait_s": nan, "prefill_s": nan,
                    "first_decode_s": nan}
        return {
            "queue_wait_s": self.prefill_start_time - self.submit_time,
            "prefill_s": self.prefill_end_time - self.prefill_start_time,
            "first_decode_s": self.token_times[0] - self.prefill_end_time,
        }

    def latency_stats(self) -> Dict[str, float]:
        """TTFT + inter-token latency percentiles for this request."""
        gaps = np.diff(np.asarray(self.token_times))
        return {
            "ttft_s": self.ttft,
            "itl_p50_s": float(np.percentile(gaps, 50)) if gaps.size else
            float("nan"),
            "itl_p95_s": float(np.percentile(gaps, 95)) if gaps.size else
            float("nan"),
            "n_tokens": float(len(self.token_times)),
        }

    @property
    def context_len(self) -> int:
        return self.prompt_len + len(self.generated)

    @property
    def budget(self) -> int:
        return self.prompt_len + self.max_new_tokens

    @property
    def tokens(self) -> np.ndarray:
        return np.concatenate(
            [self.prompt, np.asarray(self.generated, np.int32)])


class Scheduler:
    """Admission + queue bookkeeping over a :class:`PagedKVCache`.

    ``watermark`` is the fraction of the pool's pages admission must leave
    obtainable AFTER backing a new request's prompt — the slack that lets
    already-running slots grow on demand without instantly preempting.
    ``preempt_mode`` picks what :meth:`preempt` does with a victim's
    pages: ``"swap"`` parks them in host memory, ``"recompute"`` drops
    them and re-prefills the snapshotted context on resume."""

    def __init__(self, cfg: ModelConfig, kv: PagedKVCache,
                 prefill_chunk: int = 0, watermark: float = 0.0,
                 preempt_mode: str = "swap"):
        if preempt_mode not in ("swap", "recompute"):
            raise ValueError(f"unknown preempt_mode {preempt_mode!r}")
        self.cfg = cfg
        self.kv = kv
        self.prefill_chunk = prefill_chunk
        self.watermark = watermark
        self.preempt_mode = preempt_mode
        self.waiting: Deque[Request] = collections.deque()
        self.preempted: List[Request] = []            # resume-priority queue
        self.active: Dict[int, Request] = {}          # slot -> request
        self.finished: List[Request] = []
        self.preempt_count = 0
        self._next_id = 0
        self._admit_seq = 0
        # Per-phase traffic + fenced wall time for the time-based roofline
        # (keys: prefill / decode / verify / draft / swap).  The engine
        # charges compute phases; preempt/_resume charge the swap phase.
        self.phases: Dict[str, PhaseTraffic] = collections.defaultdict(
            PhaseTraffic)
        # telemetry bundle + trace process id, threaded in by the owning
        # engine (repro.obs.Telemetry, or None = telemetry off)
        self.obs = None
        self.obs_pid = 0

    def reset_phases(self) -> None:
        """Drop accumulated phase traffic (after warm-up, before a timed
        window — compile time must not pollute the budget)."""
        self.phases.clear()

    @property
    def watermark_pages(self) -> int:
        return int(math.ceil(self.watermark * (self.kv.num_pages - 1)))

    def submit(self, req: Request, keep_id: bool = False) -> Request:
        """Queue a request.  ``keep_id`` preserves a caller-assigned id
        (the Router stamps cluster-unique ids before dispatch — replica
        schedulers must not re-number them) and keeps the local counter
        clear of it so direct submits never collide."""
        if keep_id:
            self._next_id = max(self._next_id, req.request_id + 1)
        else:
            req.request_id = self._next_id
            self._next_id += 1
        req.state = RequestState.WAITING
        self.waiting.append(req)
        return req

    def has_work(self) -> bool:
        return bool(self.waiting or self.preempted or self.active)

    # -- phases ------------------------------------------------------------

    def _place(self, req: Request, slot: int, prefilling: bool) -> None:
        req.slot = slot
        req.admit_seq = self._admit_seq
        self._admit_seq += 1
        self.active[slot] = req
        if prefilling:
            req.state = RequestState.PREFILL
            req.prefill_pos = self.kv.prefix_cached_tokens(slot)
            req.prefill_skip = req.prefill_pos
            req.ledger.prefix_cached_tokens = max(
                req.ledger.prefix_cached_tokens, req.prefill_pos)
        else:
            req.state = RequestState.RUNNING
        if req.prefill_start_time == 0.0:
            # first placement into a slot: the TTFT queue-wait segment
            # ends here (kept across preemption round-trips — only the
            # first placement bounds the queue)
            req.prefill_start_time = now()
        req.ledger.pages_peak = max(req.ledger.pages_peak,
                                    self.kv.slot_pages(slot))
        if self.obs is not None:
            self.obs.tracer.instant(
                "place", self.obs_pid, LIFECYCLE_TID, now(),
                request=req.request_id, slot=slot, prefilling=prefilling)

    def _resume(self, req: Request) -> bool:
        """Bring one preempted request back; False if it does not fit."""
        if req.swap_snapshot is not None:
            snap = req.swap_snapshot
            if (not self.kv.free_slot_count
                    or self.kv.swap_in_pages_needed(snap)
                    > self.kv.available_page_count):
                return False
            # restore leg of a cross-replica migration: the wire bytes
            # were charged at detach; the restore DMA is host traffic on
            # THIS replica, phase "migrate" not "swap"
            phase = "migrate" if req.migrating else "swap"
            with span(self.obs, phase + "_in", self.obs_pid,
                      request=req.request_id,
                      bytes=int(snap.nbytes)) as sp:
                slot = self.kv.swap_in(snap)
                if slot is None:
                    sp.drop()               # no restore happened
                    return False
                sp.tid = SLOT_TID0 + slot
                jax.block_until_ready(self.kv.pools)
            self.phases[phase].add(host=float(snap.nbytes),
                                   wall_s=sp.t1 - sp.t0)
            if req.migrating:
                req.migrating = False
                if self.obs is not None:
                    self.obs.tracer.flow_finish(
                        "migrate", self.obs_pid, SLOT_TID0 + slot,
                        req.request_id, sp.t1)
            else:
                req.ledger.swap_bytes += snap.nbytes
            req.swap_snapshot = None
            self._place(req, slot, prefilling=False)
            return True
        fill = req.fill_tokens
        if not self.kv.can_admit_tokens(fill, self.watermark_pages):
            return False
        slot = self.kv.alloc(len(fill), budget=req.budget, tokens=fill)
        if slot is None:
            return False
        self._place(req, slot, prefilling=True)
        return True

    def admit(self) -> List[Request]:
        """Resume preempted requests first (they hold admission priority —
        FIFO by arrival), then FIFO-admit waiting requests while a slot
        plus prompt pages plus the watermark are obtainable."""
        admitted = []
        self.preempted.sort(key=lambda r: r.request_id)
        while self.preempted and self._resume(self.preempted[0]):
            admitted.append(self.preempted.pop(0))
        if self.preempted:
            return admitted                 # do not admit past the queue
        while self.waiting:
            req = self.waiting[0]
            fill = req.fill_tokens
            if not self.kv.can_admit_tokens(fill, self.watermark_pages):
                break
            slot = self.kv.alloc(len(fill), budget=req.budget, tokens=fill)
            if slot is None:
                break
            self.waiting.popleft()
            self._place(req, slot, prefilling=True)
            admitted.append(req)
        return admitted

    def preempt(self, req: Request) -> None:
        """Evict a running request under pool pressure: swap its pages to
        host memory or (recompute mode) drop them after snapshotting its
        committed context for re-prefill.  The request re-enters via
        :meth:`admit` ahead of all waiting work."""
        assert req.state in (RequestState.PREFILL, RequestState.RUNNING)
        del self.active[req.slot]
        if self.preempt_mode == "swap" and req.state is RequestState.RUNNING:
            with span(self.obs, "swap_out", self.obs_pid,
                      SLOT_TID0 + req.slot, request=req.request_id) as sp:
                snap = self.kv.swap_out(req.slot)
                sp.set(bytes=int(snap.nbytes))
            self.phases["swap"].add(host=float(snap.nbytes),
                                    wall_s=sp.t1 - sp.t0)
            req.swap_snapshot = snap
            req.ledger.swap_bytes += snap.nbytes
        else:
            # recompute (or mid-prefill eviction): snapshot the committed
            # context; resume re-prefills it from scratch
            req.prefill_src = req.tokens
            self.kv.free(req.slot)
        req.slot = -1
        req.state = RequestState.PREEMPTED
        req.ledger.preemptions += 1
        self.preempt_count += 1
        self.preempted.append(req)
        if self.obs is not None:
            self.obs.tracer.instant(
                "preempt", self.obs_pid, LIFECYCLE_TID, now(),
                request=req.request_id, mode=self.preempt_mode)

    def detach(self, req: Request, link: str = "dcn") -> Request:
        """Remove a request from this replica for migration to another
        (serve/cluster.py): pack its pages into one :class:`SwapSnapshot`
        (the single-DMA swap path) if it still holds a slot, or adopt the
        snapshot a preemption already parked (mid-decode migration), and
        charge the packed bytes to the migration ledger as wire traffic
        on ``link`` ("dcn" across replica groups, "ici" in-pod).  The
        caller hands the request to the destination's :meth:`attach`.

        A recompute-mode preemptee carries tokens, not pages — it
        migrates for free (the destination re-prefills) and charges no
        migration bytes."""
        assert req.state in (RequestState.RUNNING, RequestState.PREEMPTED), (
            req.state)
        if req.state is RequestState.RUNNING:
            del self.active[req.slot]
            with span(self.obs, "migrate_out", self.obs_pid,
                      SLOT_TID0 + req.slot, request=req.request_id) as sp:
                snap = self.kv.swap_out(req.slot)
                sp.set(bytes=int(snap.nbytes))
            wall = sp.t1 - sp.t0
            req.swap_snapshot = snap
            req.slot = -1
            req.state = RequestState.PREEMPTED
        else:
            if req in self.preempted:
                self.preempted.remove(req)
            snap = req.swap_snapshot          # pack DMA already charged
            wall = 0.0
            if snap is None:                  # recompute-mode preemptee
                return req
        req.migrating = True
        req.ledger.migrations += 1
        req.ledger.migration_bytes += float(snap.nbytes)
        req.ledger.migration_pages += int(snap.n_blocks)
        req.ledger.migration_link = link
        self.phases["migrate"].add(host=float(snap.nbytes), wall_s=wall,
                                   **{link: float(snap.nbytes)})
        if self.obs is not None:
            self.obs.tracer.flow_start(
                "migrate", self.obs_pid, LIFECYCLE_TID, req.request_id,
                now(), link=link, bytes=int(snap.nbytes))
        return req

    def attach(self, req: Request) -> Request:
        """Adopt a detached request from another replica: keep its
        cluster-unique id clear of the local counter and queue it with
        resume priority.  The next :meth:`admit` re-materializes its
        snapshot into THIS pool — re-deduplicating against the local
        prefix index (kv_cache.swap_in) — or re-prefills its snapshotted
        context (recompute-mode preemptee)."""
        self._next_id = max(self._next_id, req.request_id + 1)
        req.state = RequestState.PREEMPTED
        self.preempted.append(req)
        return req

    def preempt_victim(self) -> Optional[Request]:
        """Newest-admitted running request — the standard last-in victim
        (it has the least sunk decode work and frees pages fastest)."""
        cands = [r for r in self.active.values()
                 if r.state is RequestState.RUNNING]
        if not cands:
            return None
        return max(cands, key=lambda r: r.admit_seq)

    def prefill_work(self) -> List[Tuple[Request, int, int]]:
        """(request, start, end) chunks to prefill this step — one chunk
        per prefilling request."""
        out = []
        for req in self.active.values():
            if req.state is not RequestState.PREFILL:
                continue
            fill_len = len(req.fill_tokens)
            start = req.prefill_pos
            end = fill_len if self.prefill_chunk <= 0 else min(
                fill_len, start + self.prefill_chunk)
            out.append((req, start, end))
        return out

    def decode_requests(self) -> List[Request]:
        return [r for r in self.active.values()
                if r.state is RequestState.RUNNING]

    def finish(self, req: Request, reason: str) -> None:
        req.state = RequestState.FINISHED
        req.finish_reason = reason
        req.ledger.pages_peak = max(req.ledger.pages_peak,
                                    self.kv.slot_pages(req.slot))
        self.kv.free(req.slot)
        del self.active[req.slot]
        req.slot = -1
        self.finished.append(req)
        if self.obs is not None:
            # the whole request lifetime as one async slice (emitted as a
            # balanced pair at completion, so no orphan ids from requests
            # still in flight at export time)
            t_end = now()
            t_begin = req.submit_time if req.submit_time > 0.0 else t_end
            self.obs.tracer.async_begin(
                "request", self.obs_pid, LIFECYCLE_TID, req.request_id,
                t_begin)
            self.obs.tracer.async_end(
                "request", self.obs_pid, LIFECYCLE_TID, req.request_id,
                t_end, tokens=len(req.generated), reason=reason)
