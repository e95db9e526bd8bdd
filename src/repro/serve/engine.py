"""Serving engines.

:class:`Engine` is a continuous-batching engine over a paged KV cache: a
fixed bank of decode slots, one jitted decode step whose shapes are
independent of which slots are live (it compiles once and serves every
admission state), chunked prefill that interleaves with running decodes,
and a per-request roofline ledger (see scheduler.py).  Decoder-only archs
only; enc-dec / VLM requests transparently fall back to the static path.

The decode hot path is fully on-device: paged attention dispatches
through the kernel registry (kernels/ops.py — the Pallas decode kernel or
its jnp gather reference, picked by ``EngineConfig.kernel_backend``), and
batched temperature/top-k sampling with per-slot RNG folds is fused into
the same jitted step (serve/sampling.py), so the host loop only ever sees
chosen token ids.  Whole-prompt prefill is length-bucketed to the next
power of two so the jitted prefill compiles O(log max_len) shapes instead
of one per distinct prompt length.

:class:`StaticEngine` is the original whole-batch prefill -> lockstep
decode loop, kept as the reference implementation the continuous engine is
tested against token-for-token, and as the serving path for archs with
cross-attention caches.  Both engines sample through the one shared
helper in serve/sampling.py, with per-row key streams derived the same
way — their greedy/temperature semantics cannot drift apart.  (Token
-for-token caveat: paged MLA decode always runs the absorbed/latent form,
so for MLA archs the static engine matches byte-for-byte when
``cfg.mla_absorb`` is set and up to fp reordering otherwise; MoE expert
-capacity cutoffs carry their usual batch-composition discontinuity.)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.roofline.hardware import ChipSpec, TPU_V5E
from repro.kernels import ops as kernel_ops
from repro.kernels import paged_attention as paged_kernels
from repro.kernels import quantize
from repro.models import (decode_step, decode_step_paged, init_cache,
                          prefill, prefill_chunk_paged, prefill_padded)
from repro.models.common import ModelConfig, model_flops
from repro.obs import Telemetry
from repro.obs.clock import now
from repro.obs.trace import ENGINE_TID, LIFECYCLE_TID, SLOT_TID0, span

from . import sampling
from .kv_cache import PagedKVCache, supports_paging
from .scheduler import (Request, RequestState, RooflineLedger, Scheduler,
                        decode_token_bytes, decode_token_flops,
                        decode_token_vmem_bytes, kv_line_bytes,
                        params_bytes_active)


@dataclasses.dataclass
class GenerateConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0          # 0 = greedy
    top_k: int = 0                    # 0 = no top-k filter
    top_p: float = 0.0                # nucleus mass (0 or >= 1 = off)
    stop_token: Optional[int] = None


@dataclasses.dataclass
class EngineConfig:
    num_slots: int = 4                # packed decode batch width
    page_size: int = 16               # tokens per physical KV page
    max_len: int = 256                # per-request context ceiling
    prefill_chunk: int = 0            # 0 = whole prompt in one chunk
    num_pages: Optional[int] = None   # None = fully backed pool
    chip: ChipSpec = TPU_V5E          # roofline ledger target hardware
    prefill_bucket: int = 8           # min whole-prompt bucket (0 = off)
    kernel_backend: Optional[str] = None  # "pallas"|"jnp"|"auto"|None
    prefix_cache: bool = False        # content-hash prefix sharing + CoW
    watermark: float = 0.0            # admission slack, fraction of pool
    preempt_mode: str = "swap"        # "swap" | "recompute" on pool-dry
    pipeline: str = "off"             # kernel page streaming: "off"|"double"
    overlap: str = "none"             # TP epilogue schedule: "none"|"ring"
    # paged-KV storage dtype override: None keeps the model config's
    # ``kv_dtype``; "bf16"|"int8"|"fp8_e4m3" rewrite it at engine build
    # (kernels/quantize.py — quantized pools store int8/fp8 values with
    # per-line f32 scales and dequantize inside the page walk)
    kv_dtype: Optional[str] = None
    # observability (repro.obs): span tracing + metrics + live roofline
    # attainment.  Observation-only — every hook is a host-side append
    # behind ``if obs is not None``; token streams are byte-identical
    # with telemetry on or off.
    telemetry: bool = False
    telemetry_window: int = 4         # engine steps per attainment window


def _bucket_len(n: int, floor: int) -> int:
    """Next power of two >= n (but >= floor): bounds distinct prefill
    shapes — and therefore recompiles — to O(log max_len)."""
    return max(floor, 1 << max(n - 1, 0).bit_length())


def _place_prefill_states(cfg: ModelConfig, caches, states, prompt_len: int):
    """Copy collected per-layer states into dense cache buffers.

    Attention k/v (reps, B, S, KV, hd) go into (reps, B, max_len, KV, hd)
    at offset 0; recurrent states replace the zeros outright.
    """
    out = []
    for seg_cache, seg_state in zip(caches, states):
        def merge(c, s):
            if c.shape == s.shape:
                return s.astype(c.dtype)
            # sequence-extended buffers: write the prefix
            return jax.lax.dynamic_update_slice(
                c, s.astype(c.dtype), (0,) * c.ndim)
        out.append(jax.tree.map(merge, seg_cache, seg_state))
    return out


class StaticEngine:
    """Whole-batch prefill -> lockstep decode (the original engine)."""

    def __init__(self, cfg: ModelConfig, params):
        self.cfg = cfg
        self.params = params
        self._decode = jax.jit(
            lambda p, c, t, pos: decode_step(p, cfg, c, t, pos))

    def generate(self, prompts: jax.Array, gen: GenerateConfig,
                 enc_embeds=None, img_embeds=None,
                 rng: Optional[jax.Array] = None) -> Dict[str, Any]:
        """prompts (B, S) int32 -> dict with tokens (B, S+new)."""
        cfg = self.cfg
        B, S = prompts.shape
        max_len = S + gen.max_new_tokens
        caches = init_cache(cfg, B, max_len)
        last_logits, states = prefill(self.params, cfg, prompts,
                                      enc_embeds=enc_embeds,
                                      img_embeds=img_embeds)
        caches = _place_prefill_states(cfg, caches, states, S)

        tokens = [prompts]
        kd = sampling.batch_key_data(rng, B)
        cur = self._sample(last_logits, kd, 0, gen, rng)
        finished = jnp.zeros((B,), bool)
        for i in range(gen.max_new_tokens):
            tokens.append(cur[:, None])
            if gen.stop_token is not None:
                finished = finished | (cur == gen.stop_token)
                if bool(finished.all()):
                    break
            if i == gen.max_new_tokens - 1:
                break
            logits, caches = self._decode(self.params, caches, cur[:, None],
                                          jnp.int32(S + i))
            cur = self._sample(logits, kd, i + 1, gen, rng)
        return {"tokens": jnp.concatenate(tokens, axis=1),
                "finished": finished}

    def _sample(self, logits: jax.Array, kd: np.ndarray, i: int,
                gen: GenerateConfig, rng) -> jax.Array:
        """Shared-helper sampling (serve/sampling.py): per-row key streams
        ``fold_in(rng, b)`` folded with the step index — the same derivation
        the continuous engine fuses into its decode step, so a static batch
        with base key K samples byte-identically to continuous requests
        submitted with ``rng=fold_in(K, b)``."""
        B = logits.shape[0]
        temp = gen.temperature if rng is not None else 0.0
        toks = sampling.sample_host(
            logits, kd,                       # logits stay on device
            np.full((B,), i, np.int32),
            np.full((B,), temp, np.float32),
            np.full((B,), gen.top_k, np.int32),
            np.full((B,), gen.top_p, np.float32))
        return jnp.asarray(toks)


class Engine:
    """Continuous-batching serve engine with paged KV cache.

    Streaming API::

        eng = Engine(cfg, params, EngineConfig(num_slots=8, max_len=512))
        eng.submit(prompt_ids, GenerateConfig(max_new_tokens=64))
        done = eng.run()          # -> List[Request] with roofline ledgers

    ``generate()`` keeps the original whole-batch signature for drop-in
    compatibility (and silently uses :class:`StaticEngine` for archs whose
    caches cannot page: enc-dec, VLM cross-attention).
    """

    def __init__(self, cfg: ModelConfig, params,
                 ecfg: Optional[EngineConfig] = None):
        self.ecfg = ecfg or EngineConfig()
        if (self.ecfg.kv_dtype is not None
                and self.ecfg.kv_dtype != cfg.kv_dtype):
            quantize.validate_kv_dtype(self.ecfg.kv_dtype)
            cfg = dataclasses.replace(cfg, kv_dtype=self.ecfg.kv_dtype)
        self.cfg = cfg
        self.params = params
        self.paged_ok = supports_paging(cfg)
        self._static: Optional[StaticEngine] = None
        self._kv: Optional[PagedKVCache] = None
        self._sched: Optional[Scheduler] = None
        self._decode_fn = None
        self._live_walk = False
        self._prefill_fn = None
        self._next_token: Optional[np.ndarray] = None
        self._pos: Optional[np.ndarray] = None
        self.prefill_shapes: set = set()      # padded lengths compiled
        self.step_count = 0
        self.decode_steps = 0
        self._dispatch_s: Optional[float] = None
        self.obs: Optional[Telemetry] = None
        self._obs_pid = 0
        if self.ecfg.telemetry:
            self.attach_telemetry(
                Telemetry(window_steps=self.ecfg.telemetry_window))

    # -- wiring ------------------------------------------------------------

    def attach_telemetry(self, obs: Telemetry, pid: Optional[int] = None,
                         name: Optional[str] = None) -> None:
        """Adopt a telemetry bundle (a private one from
        ``EngineConfig.telemetry``, or a Cluster's shared bundle — then
        ``pid`` is the replica index so all replicas land on one
        timeline) and announce this engine's trace tracks."""
        self.obs = obs
        if pid is not None:
            self._obs_pid = pid
        obs.tracer.process(self._obs_pid,
                           name or self._obs_process_name())
        obs.tracer.thread(self._obs_pid, ENGINE_TID, "engine steps")
        obs.tracer.thread(self._obs_pid, LIFECYCLE_TID, "request lifecycle")
        if self._sched is not None:
            self._sched.obs = obs
            self._sched.obs_pid = self._obs_pid
            self._announce_slots()

    def _obs_process_name(self) -> str:
        return f"{self.cfg.name} engine"

    def _announce_slots(self) -> None:
        for s in range(self.ecfg.num_slots):
            self.obs.tracer.thread(self._obs_pid, SLOT_TID0 + s,
                                   f"slot {s}")

    def static_engine(self) -> StaticEngine:
        if self._static is None:
            self._static = StaticEngine(self.cfg, self.params)
        return self._static

    def reset(self, num_slots: Optional[int] = None,
              max_len: Optional[int] = None) -> None:
        """(Re)build the paged cache and scheduler.  Drops any in-flight
        requests; call only when idle."""
        if not self.paged_ok:
            raise NotImplementedError(
                f"{self.cfg.name}: continuous batching needs a paged cache; "
                "use generate() (static fallback) for this arch")
        e = self.ecfg
        if num_slots is not None or max_len is not None:
            e = dataclasses.replace(
                self.ecfg,
                num_slots=num_slots or self.ecfg.num_slots,
                max_len=max_len or self.ecfg.max_len)
            self.ecfg = e
        self._kv = PagedKVCache(self.cfg, e.num_slots, e.page_size,
                                e.max_len, num_pages=e.num_pages,
                                margin_tokens=self._kv_margin(),
                                prefix_cache=e.prefix_cache,
                                eager_freeze=e.prefill_chunk <= 0)
        self._sched = Scheduler(self.cfg, self._kv,
                                prefill_chunk=e.prefill_chunk,
                                watermark=e.watermark,
                                preempt_mode=e.preempt_mode)
        if self.obs is not None:
            self._sched.obs = self.obs
            self._sched.obs_pid = self._obs_pid
            self._announce_slots()
        self._next_token = np.zeros((e.num_slots,), np.int32)
        self._pos = np.zeros((e.num_slots,), np.int32)
        # per-slot sampling state, consumed by the fused decode+sample step
        ksize = sampling.key_data(None).shape[0]
        self._key_data = np.zeros((e.num_slots, ksize), np.uint32)
        self._steps = np.zeros((e.num_slots,), np.int32)
        self._temps = np.zeros((e.num_slots,), np.float32)
        self._top_ks = np.zeros((e.num_slots,), np.int32)
        self._top_ps = np.zeros((e.num_slots,), np.float32)
        cfg, ps = self.cfg, e.page_size

        self._decode_fn = jax.jit(self._decode_callable(cfg))

        # named functions, so each program keeps a stable name
        # (jit__prefill_chunk, jit__prefill_bucket) in a profiler trace
        def _prefill_chunk(p, pools, btr, slot, toks, off):
            return prefill_chunk_paged(p, cfg, pools, btr, slot, toks, off,
                                       page_size=ps)

        def _prefill_bucket(p, toks, n):
            return prefill_padded(p, cfg, toks, n)

        # jit handles per-chunk-length retracing under one cache
        self._prefill_fn = jax.jit(_prefill_chunk)
        # bucketed whole-prompt prefill: only archs whose collected states
        # are all per-token (attention/MLA) survive padding — a recurrent
        # final state or an MoE capacity cutoff would see the pad tokens
        self._bucketable = (
            all(b.mixer in ("attn", "mla") for b in cfg.block_pattern)
            and all(b.ffn != "moe" for b in cfg.block_pattern))
        self._prefill_full_fn = jax.jit(_prefill_bucket)
        self.prefill_shapes: set = set()
        self.step_count = 0
        self.decode_steps = 0
        self._dispatch_s = None

    def _kv_margin(self) -> int:
        """Block-table margin (tokens) past ``max_len``; the speculative
        subclass widens this so verify writes near the budget edge stay on
        legal (trash) table entries."""
        return 0

    def _decode_callable(self, cfg: ModelConfig):
        """The fused decode+sample step body over a given config.  Factored
        so the tensor-parallel engine (serve/shard.py) can wrap the SAME
        body in ``shard_map`` with the per-shard local config — the seam
        that keeps the 1x1 mesh byte-identical to this engine."""
        ps, be = self.ecfg.page_size, self.ecfg.kernel_backend
        pl = self.ecfg.pipeline
        impl = kernel_ops.resolve("paged_attention", be,
                                  sharded=cfg.tp_axis is not None,
                                  pipeline=pl)
        # whether the step's GQA layers run the live-page walk (telemetry)
        self._live_walk = (
            any(b.mixer == "attn" for b in cfg.block_pattern)
            and getattr(impl, "func", None) is paged_kernels.paged_attention
            and paged_kernels.walks_live_pages(
                quantized=quantize.is_quantized(cfg.kv_dtype),
                pipeline=impl.keywords["pipeline"]))

        def _decode_sample(p, pools, bt, tok, pos, act, kd, steps, temps,
                           top_ks, top_ps):
            logits, pools = decode_step_paged(
                p, cfg, pools, bt, tok, pos, act, page_size=ps, backend=be,
                pipeline=pl)
            return sampling.sample_tokens(logits, kd, steps, temps,
                                          top_ks, top_ps), pools

        return _decode_sample

    def _step_collective_bytes(self, n_tokens: int) -> float:
        """Per-device collective wire bytes one packed device step moves
        (0 on a single chip; the sharded engine prices its psum/all-gather
        edges — scheduler.decode_step_ici_bytes)."""
        return 0.0

    def _ledger_chips(self) -> int:
        """Chips the per-request ledger's W/Q are split across (the TP
        width for the sharded engine)."""
        return 1

    def _ensure(self, budget: int) -> None:
        if self._kv is None:
            self.reset(max_len=max(budget, self.ecfg.max_len))
        elif budget > self._kv.max_len:
            if self._sched.has_work():
                raise ValueError(
                    f"request budget {budget} exceeds engine max_len "
                    f"{self._kv.max_len} with requests in flight; drain "
                    "first or raise EngineConfig.max_len")
            self.reset(max_len=max(budget, self.ecfg.max_len))

    # -- request API -------------------------------------------------------

    def submit(self, prompt, gen: GenerateConfig,
               rng: Optional[jax.Array] = None) -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        self._ensure(prompt.shape[0] + gen.max_new_tokens)
        req = Request(prompt=prompt, max_new_tokens=gen.max_new_tokens,
                      temperature=gen.temperature, top_k=gen.top_k,
                      top_p=gen.top_p, stop_token=gen.stop_token, rng=rng,
                      submit_time=now())
        req = self._sched.submit(req)
        if self.obs is not None:
            self.obs.tracer.instant("submit", self._obs_pid, LIFECYCLE_TID,
                                    req.submit_time,
                                    request=req.request_id)
        return req

    def enqueue(self, req: Request) -> Request:
        """Queue a pre-built :class:`Request` WITHOUT re-numbering it —
        the Router front door stamps cluster-unique ids and the submit
        wall-clock before dispatching to a replica, and replica-local
        re-numbering would collide the ids the stream keys on."""
        self._ensure(req.budget)
        if req.submit_time == 0.0:
            req.submit_time = now()
        req.dispatch_time = now()
        req = self._sched.submit(req, keep_id=True)
        if self.obs is not None:
            self.obs.tracer.instant("enqueue", self._obs_pid,
                                    LIFECYCLE_TID, req.dispatch_time,
                                    request=req.request_id)
        return req

    def export_request(self, req: Request, link: str = "dcn") -> Request:
        """Detach a request for migration to another replica
        (scheduler.detach: its pages pack into one SwapSnapshot, the
        bytes charge the migration ledger on ``link``).  Subclasses
        release engine-side companion state (the speculative proposer's
        slot) before the scheduler lets go."""
        return self._sched.detach(req, link=link)

    def import_request(self, req: Request) -> Request:
        """Adopt a migrated request: it queues with resume priority and
        the next :meth:`step` re-materializes its snapshot into this
        pool (re-deduplicating against the local prefix index) and
        re-points the packed decode rows — the standard swap-resume
        path, so the token stream continues byte-identically."""
        self._ensure(req.budget)
        return self._sched.attach(req)

    def step(self) -> List[Request]:
        """One scheduler iteration: admit (resuming preempted requests
        first), prefill one chunk per admitted request, one packed decode
        step.  Returns requests finished here.

        Each part runs inside a :func:`repro.obs.trace.span` (``sched.admit``,
        ``engine.prefill``, ``engine.decode`` and the spans nested in them),
        so a profiler trace names the host work behind every idle stretch
        of the chip."""
        sched, obs, pid = self._sched, self.obs, self._obs_pid
        n_done = len(sched.finished)
        with span(obs, "sched.admit", pid):
            admitted = sched.admit()
            for req in admitted:
                self._init_sampling_row(req)
                if req.state is RequestState.RUNNING:
                    self._restore_decode_row(req)        # swap-resume
        work = sched.prefill_work()
        for req, start, end in work:
            with span(obs, "engine.prefill", pid, SLOT_TID0 + req.slot,
                      request=req.request_id, start=start, end=end):
                self._run_prefill(req, start, end)
        running = sched.decode_requests()
        if running:
            with span(obs, "engine.decode", pid) as sp:
                sp.set(**self._run_decode(running))
        elif (not admitted and not work
                and (sched.waiting or sched.preempted)):
            head = (sched.preempted + list(sched.waiting))[0]
            raise RuntimeError(
                f"request {head.request_id} (budget {head.budget}) cannot "
                f"be admitted: engine max_len {self._kv.max_len}, "
                f"{self._kv.available_page_count} obtainable pages "
                f"(watermark {sched.watermark_pages}), "
                f"{len(sched.preempted)} preempted waiting to resume")
        self.step_count += 1
        if self.obs is not None:
            self.obs.on_step(self)
        return sched.finished[n_done:]

    def roofline_terms(self, req: Request):
        """The request's decode RooflineTerms on this engine's target chip
        (``EngineConfig.chip``) — at the engine's TP scope, so a sharded
        engine's terms carry the ICI ceiling next to the HBM one."""
        return req.ledger.terms(self.cfg, self.ecfg.chip,
                                n_chips=self._ledger_chips())

    def run(self) -> List[Request]:
        """Drain all queued work; returns requests finished by this call."""
        if self._sched is None:
            return []
        n0 = len(self._sched.finished)
        while self._sched.has_work():
            self.step()
        return self._sched.finished[n0:]

    # -- hierarchical / time-based roofline --------------------------------

    @property
    def phases(self):
        """Per-phase traffic + fenced wall time (scheduler.Scheduler
        .phases): prefill / decode / verify / draft / swap."""
        return self._sched.phases if self._sched is not None else {}

    def reset_phases(self) -> None:
        """Drop accumulated phase traffic — call after a warm-up pass so
        compile time never pollutes the timed budget."""
        if self._sched is not None:
            self._sched.reset_phases()

    def _no_kernel_cfg(self) -> ModelConfig:
        """A degenerate twin of this engine's config: identical layer
        count, block pattern and paged-cache structure, every tensor
        dimension floored — the compiled decode step has the same op
        graph with near-zero kernel work, so its fenced wall IS the
        per-step framework/launch floor (the paper's no-kernel run)."""
        cfg = self.cfg
        shrink = {"d_model": 8, "n_heads": 1, "n_kv_heads": 1,
                  "head_dim": 8, "d_ff": 8, "vocab_size": 32,
                  "moe_d_ff": 8, "q_lora_rank": 8, "kv_lora_rank": 8,
                  "rope_head_dim": 4, "nope_head_dim": 8, "v_head_dim": 8}
        updates = {k: v for k, v in shrink.items()
                   if getattr(cfg, k) > v}
        return dataclasses.replace(cfg, name=cfg.name + "-nokernel",
                                   **updates)

    def measure_dispatch_overhead(self, repeats: int = 20) -> float:
        """Per-step framework overhead, seconds: the paper's kernel/
        no-kernel protocol (§2.4) — run the SAME decode-step program with
        every kernel's work degenerated to the floor (``_no_kernel_cfg``),
        so tracing, pytree flattening, launch and per-op framework cost
        are all measured and the time budget carries them as an explicit
        dispatch row instead of smearing them into the residual.  Median
        of ``repeats`` fenced calls; cached until the next reset()."""
        if self._dispatch_s is not None:
            return self._dispatch_s
        from repro.models import init_params
        nk_cfg = self._no_kernel_cfg()
        nk = Engine(nk_cfg, init_params(nk_cfg, jax.random.PRNGKey(0)),
                    dataclasses.replace(self.ecfg, num_pages=None))
        nk.reset()
        e = nk.ecfg
        kv = nk._kv
        bt = kv.block_tables_for(list(range(e.num_slots)))
        args = (nk.params, kv.pools, bt,
                jnp.asarray(np.zeros((e.num_slots, 1), np.int32)),
                jnp.asarray(np.zeros((e.num_slots,), np.int32)),
                jnp.asarray(np.ones((e.num_slots,), bool)),
                jnp.asarray(nk._key_data), jnp.asarray(nk._steps),
                jnp.asarray(nk._temps), jnp.asarray(nk._top_ks),
                jnp.asarray(nk._top_ps))
        jax.block_until_ready(nk._decode_fn(*args)[0])   # compile untimed
        samples = []
        for _ in range(max(repeats, 1)):
            t0 = now()
            jax.block_until_ready(nk._decode_fn(*args)[0])
            samples.append(now() - t0)
        self._dispatch_s = float(np.median(samples))
        return self._dispatch_s

    def aggregate_ledger(self) -> RooflineLedger:
        """One ledger summing every request this scheduler has seen
        (finished + in flight) — the step-level view the hierarchy table
        reports."""
        agg = RooflineLedger()
        if self._sched is None:
            return agg
        reqs = list(self._sched.finished) + list(self._sched.active.values())
        reqs += list(self._sched.preempted) + list(self._sched.waiting)
        for req in reqs:
            for f in dataclasses.fields(RooflineLedger):
                v = getattr(req.ledger, f.name)
                if isinstance(v, str):      # migration_link: carry, not sum
                    if req.ledger.migration_bytes > 0:
                        setattr(agg, f.name, v)
                    continue
                setattr(agg, f.name, getattr(agg, f.name) + v)
        return agg

    def hierarchy_report(self, betas=None, label: str = "decode") -> str:
        """The hierarchical + time-based roofline report: the aggregate
        decode terms' per-level ladder (VMEM/HBM/ICI/DCN/host) plus the
        per-phase time budget decomposed against ``betas`` (measured
        LevelBetas when the microbench has run; this chip's analytic
        constants otherwise)."""
        from repro.core.roofline.model import LevelBetas
        from repro.core.roofline.report import (HIERARCHY_HEADER,
                                                TIME_BUDGET_HEADER,
                                                hierarchy_rows,
                                                text_table,
                                                time_budget_rows)
        if betas is None:
            betas = LevelBetas.from_chip(self.ecfg.chip, dtype=self.cfg.dtype)
        t = self.aggregate_ledger().terms(self.cfg, self.ecfg.chip,
                                          n_chips=self._ledger_chips())
        dispatch = self._dispatch_s or 0.0
        out = [f"== hierarchical roofline: {self.cfg.name} "
               f"(betas: {betas.source}) ==",
               text_table(hierarchy_rows(label, t), HIERARCHY_HEADER)]
        rows = time_budget_rows(dict(self.phases), betas,
                                dispatch_s_per_step=dispatch)
        if rows:
            out.append("-- time budget (dispatch "
                       f"{dispatch * 1e6:.0f}us/step) --")
            out.append(text_table(rows, TIME_BUDGET_HEADER))
        return "\n".join(out)

    # -- internals ---------------------------------------------------------

    def _run_prefill(self, req: Request, start: int, end: int) -> None:
        kv, cfg = self._kv, self.cfg
        obs, pid, tid = self.obs, self._obs_pid, SLOT_TID0 + req.slot
        fill = req.fill_tokens
        fill_len = len(fill)
        # chunk writes can hit a prefix-shared page (copy-on-write needs a
        # fresh page) — back the span first, preempting if the pool is dry
        with span(obs, "kv.grow", pid, tid):
            if not self._grow_spans([req], lambda r: (start, end)):
                return                      # req itself was preempted
        whole = start == 0 and end == fill_len
        # length-bucketed jitted prefill: pad the prompt to the next power
        # of two; causal masking makes the prefix rows (and the logits at
        # true_len-1) byte-identical to the unpadded run, so at most
        # O(log max_len) shapes ever compile
        bucketed = whole and self._bucketable and self.ecfg.prefill_bucket > 0
        bucket = (_bucket_len(fill_len, self.ecfg.prefill_bucket)
                  if bucketed else end - start)
        # the phase wall runs from this launch to the fence below
        with span(obs, "engine.prefill.launch", pid, tid) as launch:
            if bucketed:
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :fill_len] = fill
                self.prefill_shapes.add(bucket)
                last_logits, states = self._prefill_full_fn(
                    self.params, jnp.asarray(toks), jnp.int32(fill_len))
            elif whole:
                # one-chunk path: identical computation to the static engine
                last_logits, states = prefill(self.params, cfg,
                                              jnp.asarray(fill[None, :]))
            else:
                btr = jnp.asarray(kv.block_tables[req.slot])
                toks = jnp.asarray(fill[None, start:end])
                last_logits, kv.pools = self._prefill_fn(
                    self.params, kv.pools, btr, jnp.int32(req.slot), toks,
                    jnp.int32(start))
        if whole:
            # eager page writes: dispatched here, run on the device after
            # the logits, so the first-token sample waits behind them
            with span(obs, "kv.write_prefill", pid, tid):
                kv.write_prefill_states(req.slot, states, fill_len)
        elif kv.prefix_cache:
            # chunked-prefill-safe eager registration: every full page
            # this chunk finalized holds canonical prompt content NOW, so
            # it is index-shareable steps before the request commits its
            # first token (alloc-time registration stays gated to
            # whole-prompt prefill — those pages are only promised, not
            # yet written)
            kv.freeze_committed(req.slot, fill, end)
        # fence before stamping (async dispatch; see _run_decode)
        with span(obs, "engine.prefill.fence", pid, tid) as fence:
            jax.block_until_ready(last_logits)
        t0, t1 = launch.t0, fence.t1
        n_new = end - start
        self._sched.phases["prefill"].add(
            flops=(model_flops(cfg, end, 1, "prefill")
                   - model_flops(cfg, start, 1, "prefill")),
            # pass-through floor: one weight read, the prefix KV lines the
            # chunk's attention walks, the new lines it writes
            hbm=params_bytes_active(cfg) + end * kv_line_bytes(cfg),
            vmem=params_bytes_active(cfg) + end * kv_line_bytes(cfg),
            wall_s=t1 - t0, steps=1, tokens=n_new)
        req.prefill_pos = end
        if end == fill_len:
            # post-fence stamp of the LAST chunk: closes the TTFT prefill
            # segment (ttft_breakdown) — sampling the first token is the
            # "first decode" segment that follows.  Gated on the first
            # token: a recompute-resume re-prefill AFTER it must not move
            # the stamp past token_times[0]
            if not req.token_times:
                req.prefill_end_time = t1
            # charge only the compute actually run: a prefix-cache hit
            # skipped the first ``prefill_skip`` tokens entirely
            req.ledger.prefill_flops += model_flops(cfg, fill_len, 1,
                                                    "prefill")
            if req.prefill_skip:
                req.ledger.prefill_flops -= model_flops(
                    cfg, req.prefill_skip, 1, "prefill")
            if req.max_new_tokens <= 0:
                # prefill-only scoring: same shape contract as StaticEngine
                self._sched.finish(req, "length")
                return
            with span(obs, "engine.sample_first", pid, tid):
                tok = self._sample_first(last_logits, req)
                self._commit_token(req, tok, first=True)

    def _grow_spans(self, reqs: List[Request], extent) -> List[Request]:
        """Back every request's write span ``extent(req) -> (start, end)``
        before a device step runs: on-demand page growth plus copy-on-write
        privatization.  When the pool runs dry (even after evicting cached
        pages) the newest-admitted RUNNING request is preempted and the
        growth retried; requests that got preempted (possibly the one being
        grown) drop out of the returned list."""
        for req in sorted(reqs, key=lambda r: r.admit_seq):
            s, e = extent(req)
            while (req.state is not RequestState.PREEMPTED
                   and not self._kv.ensure_writable(req.slot, s, e)):
                victim = self._sched.preempt_victim()
                if victim is None:
                    raise RuntimeError(
                        f"block pool exhausted: request {req.request_id} "
                        f"cannot grow to token {e} with "
                        f"{self._kv.available_page_count} obtainable pages "
                        "and no running victim to preempt; raise "
                        "EngineConfig.num_pages or lower num_slots")
                self._preempt(victim)
        return [r for r in reqs if r.state is not RequestState.PREEMPTED]

    def _preempt(self, req: Request) -> None:
        """Scheduler preemption plus engine-side hooks (subclasses release
        per-request companion state, e.g. the draft proposer's slot)."""
        self._sched.preempt(req)

    def _restore_decode_row(self, req: Request) -> None:
        """Re-point the packed decode rows at a swap-resumed request: the
        next step feeds its last committed token at its old position, so
        the token stream continues exactly where preemption cut it."""
        self._next_token[req.slot] = req.generated[-1]
        self._pos[req.slot] = req.context_len - 1
        self._steps[req.slot] = len(req.generated)

    def _grow_decode(self, running: List[Request], n_tokens: int):
        """Back the ``n_tokens`` KV lines one packed step writes from each
        request's ``context_len - 1`` (page growth / copy-on-write; a dry
        pool preempts) inside a ``kv.grow`` span.  Returns the requests
        still running and their block tables (None when none is)."""
        with span(self.obs, "kv.grow", self._obs_pid):
            running = self._grow_spans(
                running,
                lambda r: (r.context_len - 1, r.context_len - 1 + n_tokens))
            if not running:
                return running, None
            return running, self._kv.block_tables_for(
                [r.slot for r in running])

    def _run_decode(self, running: List[Request]) -> Dict[str, int]:
        """One packed decode step; returns the ``engine.decode`` span's
        stats: ``batch``, the requests it stepped, and with telemetry on
        and the live-page walk in the step, ``pages``, the table entries
        one layer's walk copies."""
        kv, obs, pid = self._kv, self.obs, self._obs_pid
        running, bt = self._grow_decode(running, 1)
        if not running:
            return {"batch": 0}
        stats = {"batch": len(running)}
        # decode + batched sampling run as ONE jitted step: the host sees
        # only the chosen token ids, never the (B, V) logits.  Argument
        # conversion happens BEFORE the fenced window so the phase wall
        # measures the device step, not host-side staging
        with span(obs, "engine.decode.stage", pid):
            active = np.zeros((self.ecfg.num_slots,), bool)
            active[[r.slot for r in running]] = True
            token = np.where(active, self._next_token, 0).astype(np.int32)
            pos = np.where(active, self._pos, 0).astype(np.int32)
            step_args = (self.params, kv.pools, bt,
                         jnp.asarray(token[:, None]), jnp.asarray(pos),
                         jnp.asarray(active), jnp.asarray(self._key_data),
                         jnp.asarray(self._steps), jnp.asarray(self._temps),
                         jnp.asarray(self._top_ks), jnp.asarray(self._top_ps))
            if obs is not None and self._live_walk:
                stats["pages"] = paged_kernels.live_walk_pages(
                    pos, self.ecfg.page_size, bt.shape[1])
                obs.registry.counter(
                    "serve_live_page_walks_total",
                    "decode steps whose GQA layers ran the live-page walk"
                ).inc()
        with span(obs, "engine.decode.launch", pid) as launch:
            next_tok, kv.pools = self._decode_fn(*step_args)
        # fence BEFORE stamping: dispatch is async, so an unfenced stamp
        # records launch time, not completion — every request committed
        # this step shares one post-fence stamp
        with span(obs, "engine.decode.fence", pid) as fence:
            jax.block_until_ready(next_tok)
        t0, t1 = launch.t0, fence.t1
        self.decode_steps += 1
        with span(obs, "engine.decode.commit", pid):
            tok_np = np.asarray(next_tok)
            n_active = len(running)
            ici_share = self._step_collective_bytes(1) / n_active
            ph = self._sched.phases["decode"]
            ps = self.ecfg.page_size
            for req in running:
                vmem = decode_token_vmem_bytes(self.cfg, req.context_len,
                                               n_active, ps,
                                               pipeline=self.ecfg.pipeline)
                req.ledger.add_decode_token(self.cfg, req.context_len,
                                            n_active, ici_bytes=ici_share,
                                            vmem_bytes=vmem)
                ph.add(flops=decode_token_flops(self.cfg, req.context_len),
                       vmem=vmem,
                       hbm=decode_token_bytes(self.cfg, req.context_len,
                                              n_active),
                       ici=ici_share, steps=0, tokens=1)
                self._commit_token(req, int(tok_np[req.slot]), t=t1)
            ph.add(wall_s=t1 - t0, steps=1, tokens=0)
        return stats

    def _commit_token(self, req: Request, tok: int, first: bool = False,
                      t: Optional[float] = None) -> None:
        req.generated.append(tok)
        req.token_times.append(now() if t is None else t)
        if first:
            req.state = RequestState.RUNNING
            if self.obs is not None:
                self.obs.tracer.instant(
                    "first_token", self._obs_pid, LIFECYCLE_TID,
                    req.token_times[-1], request=req.request_id)
        if self._kv.prefix_cache:
            # pages whose every position is now final become
            # prefix-shareable (content-hash registered); gated here so
            # the O(context) req.tokens concat stays off the hot path
            self._kv.freeze_committed(req.slot, req.tokens,
                                      req.context_len - 1)
        if req.stop_token is not None and tok == req.stop_token:
            self._sched.finish(req, "stop")
        elif len(req.generated) >= req.max_new_tokens:
            self._sched.finish(req, "length")
        else:
            self._next_token[req.slot] = tok
            self._pos[req.slot] = req.context_len - 1
            self._steps[req.slot] = len(req.generated)

    def _init_sampling_row(self, req: Request) -> None:
        """Per-slot sampling state for the fused decode+sample step.  A
        request without an rng samples greedily whatever its temperature
        (the pre-fusion host-sampling contract)."""
        slot = req.slot
        self._key_data[slot] = sampling.key_data(req.rng)
        self._temps[slot] = req.temperature if req.rng is not None else 0.0
        self._top_ks[slot] = req.top_k
        self._top_ps[slot] = req.top_p
        self._steps[slot] = 0

    def _sample_first(self, last_logits: jax.Array, req: Request) -> int:
        """Sample the prefill's first token through the same shared helper
        (B=1 row), keeping its RNG stream identical to the fused path."""
        tok = sampling.sample_host(
            jnp.reshape(last_logits, (1, -1)),
            self._key_data[req.slot][None],
            np.asarray([len(req.generated)], np.int32),
            np.asarray([self._temps[req.slot]], np.float32),
            np.asarray([self._top_ks[req.slot]], np.int32),
            np.asarray([self._top_ps[req.slot]], np.float32))
        return int(tok[0])

    # -- batch compatibility API -------------------------------------------

    def generate(self, prompts: jax.Array, gen: GenerateConfig,
                 enc_embeds=None, img_embeds=None,
                 rng: Optional[jax.Array] = None) -> Dict[str, Any]:
        """prompts (B, S) int32 -> dict with tokens (B, S+new), finished.

        Runs the continuous-batching path with one slot per row; archs
        without a paged decode path (enc-dec / VLM) use the static engine.
        """
        if (enc_embeds is not None or img_embeds is not None
                or not self.paged_ok):
            return self.static_engine().generate(
                prompts, gen, enc_embeds=enc_embeds, img_embeds=img_embeds,
                rng=rng)
        if self._sched is not None and self._sched.has_work():
            raise ValueError(
                "generate() rebuilds the scheduler and would drop requests "
                "already in flight; drain with run() first")
        prompts_np = np.asarray(prompts, np.int32)
        B, S = prompts_np.shape
        prev_ecfg = self.ecfg
        self.reset(num_slots=B, max_len=S + gen.max_new_tokens)
        try:
            for b in range(B):
                self.submit(
                    prompts_np[b], gen,
                    rng=None if rng is None else jax.random.fold_in(rng, b))
            done = sorted(self.run(), key=lambda r: r.request_id)
        finally:
            # restore the caller's config; drop the per-call pool so the
            # next streaming submit rebuilds at the configured sizes
            self.ecfg = prev_ecfg
            self._kv = None
            self._sched = None
        n_gen = max(len(r.generated) for r in done)
        out = np.zeros((B, S + n_gen), np.int32)
        finished = np.zeros((B,), bool)
        for r in done:
            row = np.asarray(r.tokens)
            # rows that stopped early hold their last token (the static
            # engine keeps decoding them; callers only see shape <= static)
            padded = np.concatenate(
                [row, np.full((S + n_gen - row.shape[0],), row[-1],
                              np.int32)])
            out[r.request_id] = padded
            finished[r.request_id] = r.finish_reason == "stop"
        return {"tokens": jnp.asarray(out), "finished": jnp.asarray(finished)}
