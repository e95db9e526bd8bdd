"""Speculative decoding: roofline-guided draft/verify serving.

Why this subsystem exists, in the paper's terms (eq. 1, ``P = min(pi,
I * beta)``): paged decode is the most memory-bound workload in the repo —
every generated token re-reads the active weights plus the KV line, so its
arithmetic intensity ``I = W/Q`` sits far left of the ridge and throughput
is pinned at ``beta * I``.  Speculative decoding attacks ``I`` directly: a
cheap proposer drafts ``k`` tokens, one multi-token *verification* pass
(models.decode_step_verify_paged) scores all ``k+1`` positions in a single
weight read and a single KV page walk, and a rejection-sampling acceptance
rule keeps every committed token distributed exactly as the target model —
greedy output is byte-identical to sequential decode.  W scales by
``k+1`` while Q barely moves, so measured intensity approaches
``(k+1) * I`` under the same memory ceiling; the realized tokens/s gain is
the *yield* ``E[tokens/pass] = (1 - a^(k+1)) / (1 - a)`` for per-draft
acceptance rate ``a`` (:func:`spec_expected_tokens_per_pass`), discounted
by the verify/draft pass-cost ratio (:func:`spec_speedup_model`).

:class:`SpecEngine` subclasses the continuous-batching :class:`Engine`:
admission, chunked prefill, the paged cache, and the per-request roofline
ledger are inherited; only the decode phase is replaced by
propose -> verify -> accept -> variable-length commit.  Rollback of
rejected drafts is pure position bookkeeping: their K/V page writes sit
beyond the committed context, are causally masked, and are overwritten
when a real token is later fed at that position (see
attention.decode_verify_paged).  The ledger gains draft/verify phase
splits (scheduler.RooflineLedger.add_verify_step / add_draft_cost), so a
request reports its measured acceptance rate, tokens-per-weight-pass, and
arithmetic intensity against the non-speculative baseline.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import decode_step_verify_paged
from repro.models.common import ModelConfig
from repro.obs.trace import span

from . import sampling
from .engine import Engine, EngineConfig
from .kv_cache import supports_paging
from .proposer import DraftModelProposer, NgramProposer
from .scheduler import (Request, RequestState, decode_token_bytes,
                        decode_token_flops, kv_line_bytes,
                        params_bytes_active, state_bytes,
                        verify_step_vmem_bytes)


def supports_spec(cfg: ModelConfig) -> bool:
    """Speculative decoding needs a rollback-free cache: rejected drafts
    must be erasable by position bookkeeping alone.  Attention/MLA caches
    qualify (stale lines are masked + overwritten); recurrent state
    (mamba/xlstm) advances destructively and would need checkpointing."""
    return supports_paging(cfg) and all(
        b.mixer in ("attn", "mla") for b in cfg.block_pattern)


@dataclasses.dataclass
class SpecConfig:
    k: int = 4                         # drafted tokens per verify round
    proposer: str = "ngram"            # "ngram" | "draft"
    draft_cfg: Optional[ModelConfig] = None
    draft_params: Any = None
    ngram_max: int = 3                 # longest suffix n-gram to match
    ngram_min: int = 1
    # adaptive drafted length: a host-side EWMA of each request's per-draft
    # acceptance rate picks k_eff <= k every round (the verify step keeps
    # its fixed (num_slots, k+1) shape — shorter drafts are padding).
    # EXPERIMENTS.md §Speculative roofline: the marginal draft survives
    # with prob ~a^j, so drafting past a^j < adapt_floor wastes draft work
    # and verify FLOPs on tokens that almost never commit.
    adaptive: bool = False
    ewma_beta: float = 0.4             # weight of the newest observation
    adapt_floor: float = 0.25          # keep drafting while a^j >= floor
    k_min: int = 1                     # never shrink below this


def adaptive_k(alpha: float, k_max: int, floor: float = 0.25,
               k_min: int = 1) -> int:
    """Drafted length maximizing useful work at acceptance rate ``alpha``:
    the j-th draft commits with probability ~``alpha^j``, so draft while
    that survival probability clears ``floor``."""
    if alpha >= 1.0:
        return k_max
    if alpha <= 0.0:
        return k_min
    j = int(np.floor(np.log(floor) / np.log(alpha)))
    return int(np.clip(j, k_min, k_max))


def spec_expected_tokens_per_pass(alpha: float, k: int) -> float:
    """E[committed tokens per verify pass] when each draft survives i.i.d.
    with probability ``alpha``: 1 + a + ... + a^k = (1 - a^(k+1))/(1 - a).
    The +1 is the always-committed corrected/bonus token."""
    if alpha >= 1.0:
        return float(k + 1)
    return (1.0 - alpha ** (k + 1)) / (1.0 - alpha)


def spec_speedup_model(cfg: ModelConfig, k: int, alpha: float,
                       context_len: int, active_batch: int,
                       draft_cfg: Optional[ModelConfig] = None
                       ) -> Dict[str, float]:
    """Predicted speculative speedup against the memory-bound ceiling.

    Both the baseline decode step and the verify step are memory-bound, so
    their wall-time ratio is their Q ratio: Q_verify/Q_decode = (w/B +
    (L + 2T - 1) * line) / (w/B + (L + 1) * line) — close to 1 when the
    amortized weight read dominates, which is exactly the regime decode
    lives in.  A draft model adds its own memory time per round.  Then

        speedup = E[tokens/pass] / ((Q_verify + Q_draft) / Q_decode)

    See EXPERIMENTS.md §Speculative roofline for the derivation and
    crosscheck_verify for the HLO-measured counterpart of Q_verify.
    """
    T = k + 1
    etok = spec_expected_tokens_per_pass(alpha, k)
    q_dec = decode_token_bytes(cfg, context_len, active_batch)
    q_ver = q_dec + (2 * T - 2) * kv_line_bytes(cfg)
    q_draft = 0.0
    if draft_cfg is not None:
        line_d = kv_line_bytes(draft_cfg)
        w_d = params_bytes_active(draft_cfg) / max(active_batch, 1)
        # one catch-up pass (~etok tokens) + (k-1) single-token steps
        q_draft = (w_d + (context_len + 2 * T - 1) * line_d
                   + (k - 1) * (w_d + (context_len + k) * line_d))
    cost_ratio = (q_ver + q_draft) / q_dec
    return {"tokens_per_pass": etok, "pass_cost_ratio": cost_ratio,
            "speedup": etok / cost_ratio}


def speculative_summary(cfg: ModelConfig, requests: List[Request], k: int,
                        context_len: int,
                        draft_cfg: Optional[ModelConfig] = None
                        ) -> Dict[str, float]:
    """Pool finished requests' ledgers into the speculative report both
    the launcher and the benchmark print: measured acceptance rate and
    tokens-per-weight-pass, plus the memory-bound model's predictions at
    the pooled acceptance rate."""
    acc = (sum(r.ledger.accepted for r in requests)
           / max(sum(r.ledger.proposed for r in requests), 1))
    tpp = (sum(r.ledger.decode_tokens for r in requests)
           / max(sum(r.ledger.weight_passes for r in requests), 1))
    batch = max(int(round(float(np.mean(
        [r.ledger.mean_batch for r in requests])))), 1)
    model = spec_speedup_model(cfg, k, acc, context_len, batch,
                               draft_cfg=draft_cfg)
    return {"acceptance_rate": acc, "tokens_per_pass": tpp,
            "predicted_tokens_per_pass": model["tokens_per_pass"],
            "predicted_speedup": model["speedup"]}


class SpecEngine(Engine):
    """Continuous-batching engine with speculative draft/verify decode.

    Streaming API is the parent's::

        eng = SpecEngine(cfg, params, EngineConfig(num_slots=8),
                         SpecConfig(k=4, proposer="ngram"))
        eng.submit(prompt_ids, GenerateConfig(max_new_tokens=64))
        done = eng.run()

    Every decode round runs ONE jitted verify+accept step over the packed
    slot batch (fixed shape (num_slots, k+1) — compiles once whatever the
    admission state or per-slot draft counts), then commits a variable
    number of tokens per request on the host.  Requests with no drafts
    this round still commit exactly one token — a silent proposer degrades
    to ordinary decode, never below it.
    """

    def __init__(self, cfg: ModelConfig, params,
                 ecfg: Optional[EngineConfig] = None,
                 scfg: Optional[SpecConfig] = None):
        if not supports_spec(cfg):
            raise NotImplementedError(
                f"{cfg.name}: speculative decoding needs attention/MLA "
                "mixers throughout (rollback-free paged cache)")
        super().__init__(cfg, params, ecfg)
        self.scfg = scfg or SpecConfig()
        if self.scfg.k < 1:
            raise ValueError("SpecConfig.k must be >= 1")
        if self.scfg.proposer == "draft":
            dcfg = self.scfg.draft_cfg
            if dcfg is None or self.scfg.draft_params is None:
                raise ValueError("proposer='draft' needs draft_cfg and "
                                 "draft_params")
            if not supports_spec(dcfg):
                raise NotImplementedError(
                    f"draft arch {dcfg.name}: needs attention/MLA mixers")
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError("draft and target must share a vocab")
        elif self.scfg.proposer != "ngram":
            raise ValueError(f"unknown proposer {self.scfg.proposer!r}")
        self.proposer = None
        self.verify_steps = 0
        # request_id -> EWMA of per-draft acceptance (adaptive k); starts
        # optimistic so fresh requests draft at full k
        self._accept_ewma: Dict[int, float] = {}

    # -- wiring ------------------------------------------------------------

    def _kv_margin(self) -> int:
        # verify feeds up to k tokens past the committed context; near the
        # budget edge those writes must resolve to (trash) table entries
        return self.scfg.k + 1

    def _verify_callable(self, cfg: ModelConfig):
        """The fused verify+accept step body over a given config —
        factored like Engine._decode_callable so the tensor-parallel
        engine can shard_map the SAME body with the per-shard config."""
        ps, be = self.ecfg.page_size, self.ecfg.kernel_backend
        pl = self.ecfg.pipeline

        if self.scfg.proposer == "draft":
            def _verify(p, pools, bt, feed, pos, act, draft, qp, nd, kd,
                        steps, temps, top_ks, top_ps):
                logits, pools = decode_step_verify_paged(
                    p, cfg, pools, bt, feed, pos, act, page_size=ps,
                    backend=be, pipeline=pl)
                toks, n_out = sampling.spec_accept(
                    logits, draft, qp, nd, kd, steps, temps, top_ks,
                    top_ps)
                return toks, n_out, pools
        else:
            def _verify(p, pools, bt, feed, pos, act, draft, nd, kd,
                        steps, temps, top_ks, top_ps):
                logits, pools = decode_step_verify_paged(
                    p, cfg, pools, bt, feed, pos, act, page_size=ps,
                    backend=be, pipeline=pl)
                toks, n_out = sampling.spec_accept(
                    logits, draft, None, nd, kd, steps, temps, top_ks,
                    top_ps)
                return toks, n_out, pools
        return _verify

    def reset(self, num_slots: Optional[int] = None,
              max_len: Optional[int] = None) -> None:
        super().reset(num_slots=num_slots, max_len=max_len)
        e, s = self.ecfg, self.scfg
        ps, be = e.page_size, e.kernel_backend
        if s.proposer == "draft":
            self.proposer = DraftModelProposer(
                s.draft_cfg, s.draft_params, num_slots=e.num_slots,
                page_size=ps, max_len=self._kv.max_len, k=s.k, backend=be,
                pipeline=e.pipeline,
                prefill_bucket=max(e.prefill_bucket, 1))
        else:
            self.proposer = NgramProposer(e.num_slots, s.k,
                                          max_n=s.ngram_max,
                                          min_n=s.ngram_min)
        self._verify_fn = jax.jit(self._verify_callable(self.cfg))
        self.verify_steps = 0

    # -- decode = propose -> verify -> accept -> commit --------------------

    def _run_decode(self, running: List[Request]) -> Dict[str, int]:
        kv, s, pid = self._kv, self.scfg, self._obs_pid
        k, T = s.k, s.k + 1
        # the verify step writes T KV lines from context_len - 1 on:
        # back the whole span (growth + copy-on-write) so speculative
        # scribbles can never land on a shared page; past-budget overflow
        # is clipped onto the trash-margin entries as before
        running, bt = self._grow_decode(running, T)
        if not running:
            return {"batch": 0}
        active = np.zeros((self.ecfg.num_slots,), bool)
        active[[r.slot for r in running]] = True
        k_eff = None
        if s.adaptive:
            k_eff = np.full((self.ecfg.num_slots,), k, np.int32)
            for req in running:
                a = self._accept_ewma.get(req.request_id, 1.0)
                k_eff[req.slot] = adaptive_k(a, k, s.adapt_floor, s.k_min)
        with span(self.obs, "engine.propose", pid,
                  batch=len(running)) as draft:
            prop = self.proposer.propose(running, k_eff=k_eff)
        self._sched.phases["draft"].add(wall_s=draft.t1 - draft.t0, steps=1)

        # args are converted here, outside the fenced window (the phase
        # wall measures the device step, not host-side staging)
        with span(self.obs, "engine.decode.stage", pid):
            feed = np.zeros((self.ecfg.num_slots, T), np.int32)
            feed[:, 0] = np.where(active, self._next_token, 0)
            feed[:, 1:] = prop.draft
            pos = np.where(active, self._pos, 0).astype(np.int32)
            args = [self.params, kv.pools, bt, jnp.asarray(feed),
                    jnp.asarray(pos), jnp.asarray(active),
                    jnp.asarray(prop.draft)]
            if prop.q_probs is not None:
                args.append(prop.q_probs)
            args += [jnp.asarray(prop.n_draft), jnp.asarray(self._key_data),
                     jnp.asarray(self._steps), jnp.asarray(self._temps),
                     jnp.asarray(self._top_ks), jnp.asarray(self._top_ps)]
        with span(self.obs, "engine.verify", pid, batch=len(running),
                  k=k) as verify:
            out_tok, n_out, kv.pools = self._verify_fn(*args)
            # fence before stamping (async dispatch; see Engine._run_decode)
            jax.block_until_ready(out_tok)
        t0, t1 = verify.t0, verify.t1
        self.decode_steps += 1
        self.verify_steps += 1
        with span(self.obs, "engine.decode.commit", pid):
            out_np = np.asarray(out_tok)
            n_np = np.asarray(n_out)
            n_active = len(running)
            ici_share = self._step_collective_bytes(T) / n_active
            vph = self._sched.phases["verify"]
            ps = self.ecfg.page_size
            line = kv_line_bytes(self.cfg)
            for req in running:
                slot, L = req.slot, req.context_len
                nd = int(prop.n_draft[slot])
                n = max(1, min(int(n_np[slot]), nd + 1))
                committed = 0
                for j in range(n):
                    self._commit_token(req, int(out_np[slot, j]), t=t1)
                    committed += 1
                    if req.state is RequestState.FINISHED:
                        break
                # the last committed token is the corrected/bonus draw only
                # if the commit chain ran to completion; a stop-token or
                # budget cut means everything committed was an accepted
                # draft
                accepted = committed - 1 if committed == n else committed
                vmem = verify_step_vmem_bytes(self.cfg, L, T, n_active, ps,
                                              pipeline=self.ecfg.pipeline)
                req.ledger.add_verify_step(self.cfg, L, T, committed,
                                           accepted, nd, n_active,
                                           ici_bytes=ici_share,
                                           vmem_bytes=vmem)
                vph.add(flops=sum(decode_token_flops(self.cfg, L + t)
                                  for t in range(T)),
                        vmem=vmem,
                        hbm=(params_bytes_active(self.cfg) / n_active
                             + (L + 2 * T - 1) * line
                             + 2 * state_bytes(self.cfg)),
                        ici=ici_share, steps=0, tokens=committed)
                if s.adaptive and nd > 0:
                    prev = self._accept_ewma.get(req.request_id, 1.0)
                    obs = accepted / nd
                    self._accept_ewma[req.request_id] = (
                        (1.0 - s.ewma_beta) * prev + s.ewma_beta * obs)
                if s.proposer == "draft":
                    n_fed = int(prop.n_catchup[slot])
                    n_decodes = max(int(prop.n_draft[slot]) - 1, 0)
                    req.ledger.add_draft_cost(s.draft_cfg, L, n_fed,
                                              n_decodes, n_active)
            vph.add(wall_s=t1 - t0, steps=1, tokens=0)
        return {"batch": len(running)}

    def _preempt(self, req: Request) -> None:
        # the draft proposer's mirrored slot must go with the target's —
        # it re-admits (re-prefilling the committed context) on resume
        self.proposer.release(req)
        super()._preempt(req)

    def export_request(self, req: Request, link: str = "dcn") -> Request:
        # migrating a running target must free the proposer's mirrored
        # slot here (a preempted one already released at preempt time);
        # the acceptance EWMA leaves with the request — the destination's
        # proposer re-admits from the committed context
        if req.state is RequestState.RUNNING:
            self.proposer.release(req)
        self._accept_ewma.pop(req.request_id, None)
        return super().export_request(req, link=link)

    def step(self) -> List[Request]:
        done = super().step()
        for req in done:
            self.proposer.release(req)
            self._accept_ewma.pop(req.request_id, None)
        return done
