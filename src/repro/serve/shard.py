"""Tensor-parallel sharded serving: the multi-chip seam of the engine.

The source paper's central construction is a roofline per NUMA scope —
the ceiling that binds depends on whether traffic stays local (DRAM) or
crosses the socket link (UPI).  The TPU serving analogue: a
tensor-parallel decode step reads its weight and KV shards from per-chip
HBM (the local roof) and all-reduces a (B, 1, d_model) activation per
row-parallel matmul over ICI (the remote roof).  This module runs the
EXISTING continuous-batching engine across a ``(data, model)`` device
mesh and prices both roofs:

* Weights are partitioned by the logical-axis rules
  (parallel.sharding.DECODE_TP_RULES): heads / kv_heads / d_ff / vocab
  split over ``model``; norms, latents and the tied embedding table
  replicate (the token lookup needs every row — an untied head stays
  vocab-sharded and the logits edge all-gathers).
* KV page pools shard their kv_heads dim (GQA); MLA pools replicate the
  compressed latent while the q/o projections partition over heads —
  attention runs per-shard in the latent space exactly as on one chip.
* The jitted decode / verify steps are the parent engines' OWN step
  bodies (Engine._decode_callable / SpecEngine._verify_callable) wrapped
  in ``shard_map``: each shard runs the Pallas/jnp kernels on its local
  heads and pages (kernels/ops.py shard-aware dispatch), with the psum /
  all-gather edges of parallel.collectives marking every byte that
  crosses the interconnect.

The 1x1 mesh does not wrap anything — :class:`ShardedEngine` degenerates
to the parent ``Engine`` byte for byte, which is the refactor-safe seam
every future multi-chip PR builds on.  At TP > 1 the per-request ledger
charges ``scheduler.decode_step_ici_bytes`` per step, RooflineTerms gain
the ICI ceiling next to the HBM one (``binding_roof``), and
serve/crosscheck.crosscheck_collectives validates the charged wire bytes
against the all-reduce / all-gather ops in the compiled shard_map HLO.

Scope notes: ``dp`` (data-parallel serving replicas) runs as N
INDEPENDENT engines, each on its own ``(1, tp)`` sub-mesh
(parallel.mesh.dp_submeshes) behind the ledger-routed front door in
serve/cluster.py + serve/router.py — replicas exchange requests (packed
KV snapshots over DCN/ICI), never activations, so no collective spans
the ``data`` axis.  Constructing a single engine with ``dp > 1`` and no
sub-mesh still raises: one engine cannot BE two replicas — build a
``serve.cluster.Cluster``.  MoE FFNs need expert-parallel dispatch and
are gated off (``tp_sharding_error``) — the same route-by-cost problem
the Router solves over ``data``, replayed over ``model``; recurrent
mixers carry per-slot state rows that have no head dim to shard.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.models import init_params, model_param_defs, paged_cache_defs
from repro.models.common import ModelConfig
from repro.parallel import sharding as shd
from repro.parallel.mesh import (MODEL_AXIS, make_host_mesh,
                                 mesh_axis_sizes)

from . import sampling
from .engine import Engine, EngineConfig
from .kv_cache import supports_paging
from .scheduler import decode_step_ici_bytes
from .spec import SpecConfig, SpecEngine


def parse_mesh(spec: str) -> Tuple[int, int]:
    """``"dp,tp"`` (e.g. ``"1,2"``) -> (dp, tp); a bare int means tp."""
    parts = [p.strip() for p in str(spec).split(",") if p.strip()]
    if len(parts) == 1:
        return 1, int(parts[0])
    if len(parts) != 2:
        raise ValueError(f"mesh spec {spec!r}: want 'dp,tp'")
    return int(parts[0]), int(parts[1])


def tp_sharding_error(cfg: ModelConfig, tp: int) -> Optional[str]:
    """Why this config cannot run tensor-parallel decode at width ``tp``
    (None when it can).  The gates mirror what the sharding actually
    partitions: query/o-proj heads, GQA KV heads + pool pages, dense FFN
    inner dim."""
    if tp <= 1:
        return None
    if not supports_paging(cfg):
        return f"{cfg.name}: sharded serving rides the paged engine"
    bad = [b.mixer for b in cfg.block_pattern if b.mixer not in ("attn",
                                                                "mla")]
    if bad:
        return (f"{cfg.name}: recurrent mixers {sorted(set(bad))} keep "
                "per-slot state rows with no head dim to shard")
    if any(b.ffn == "moe" for b in cfg.block_pattern):
        return (f"{cfg.name}: MoE FFNs need expert-parallel dispatch — "
                "the serve/router.py route-by-cost problem over the "
                "model axis (future PR); tensor-parallel decode shards "
                "dense FFNs")
    if cfg.n_heads % tp:
        return f"{cfg.name}: n_heads {cfg.n_heads} not divisible by tp={tp}"
    if (any(b.mixer == "attn" for b in cfg.block_pattern)
            and cfg.n_kv_heads % tp):
        return (f"{cfg.name}: n_kv_heads {cfg.n_kv_heads} not divisible "
                f"by tp={tp} (KV pools shard over kv_heads)")
    if any(b.ffn == "dense" for b in cfg.block_pattern) and cfg.d_ff % tp:
        return f"{cfg.name}: d_ff {cfg.d_ff} not divisible by tp={tp}"
    return None


def supports_tp(cfg: ModelConfig, tp: int) -> bool:
    return tp_sharding_error(cfg, tp) is None


def tp_local_config(cfg: ModelConfig, tp: int,
                    overlap: str = "none") -> ModelConfig:
    """The per-shard config the shard_map body runs: local head / FFN
    counts, explicit head_dim (it must NOT re-derive from the local head
    count), and ``tp_axis`` naming the mesh axis the model's collective
    edges reduce over.  vocab_size stays global — the logits edge uses it
    to detect a sharded head.  ``overlap`` selects the row-parallel
    epilogue schedule ("none" blocking psum, "ring" the overlapped
    collective matmul — parallel.collectives.ring_matmul_reduce)."""
    err = tp_sharding_error(cfg, tp)
    if err:
        raise NotImplementedError(err)
    return dataclasses.replace(
        cfg,
        n_heads=cfg.n_heads // tp,
        n_kv_heads=(cfg.n_kv_heads // tp if cfg.n_kv_heads % tp == 0
                    else cfg.n_kv_heads),
        head_dim=cfg.hd,
        d_ff=cfg.d_ff // tp if cfg.d_ff % tp == 0 else cfg.d_ff,
        tp_axis=MODEL_AXIS,
        tp_overlap=overlap,
    )


def param_pspecs(cfg: ModelConfig, mesh) -> Any:
    """PartitionSpec tree for the model params under DECODE_TP_RULES, with
    the embedding table force-replicated (the token-id gather needs every
    row on every shard; a tied head therefore computes full-width logits,
    an untied ``head`` stays vocab-sharded and all-gathers)."""
    specs = shd.tree_specs(model_param_defs(cfg), mesh,
                           shd.DECODE_TP_RULES)
    specs["embed"]["tok"] = P()
    return specs


def init_sharded_params(cfg: ModelConfig, key: jax.Array, mesh) -> Any:
    """Random weights created directly in their tensor-parallel
    ``NamedSharding``s on ``mesh``: each device materializes only its own
    shard, so a model larger than one chip never lands whole on device 0.
    :class:`ShardedEngine` then finds them already placed."""
    shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                             param_pspecs(cfg, mesh))
    return jax.jit(lambda k: init_params(cfg, k),
                   out_shardings=shardings)(key)


def pool_pspecs(cfg: ModelConfig, num_slots: int, num_pages: int,
                page_size: int, mesh) -> Any:
    """PartitionSpec tree for the paged cache pools: GQA k/v pools shard
    their kv_heads dim, MLA latent pools replicate (DECODE_TP_RULES pins
    the page dims unsharded — a page is the block-table unit)."""
    defs = paged_cache_defs(cfg, num_slots, num_pages, page_size)
    return shd.tree_specs(defs, mesh, shd.DECODE_TP_RULES)


class _ShardedStepMixin:
    """Shared machinery of :class:`ShardedEngine` / :class:`ShardedSpecEngine`:
    build the mesh, place params/pools, and re-wrap the parents' jitted
    step bodies in shard_map on every ``reset()``."""

    def _init_mesh(self, mesh_shape: Tuple[int, int],
                   submesh: Optional[Any] = None,
                   replica_id: int = 0) -> None:
        dp, tp = int(mesh_shape[0]), int(mesh_shape[1])
        if dp < 1 or tp < 1:
            raise ValueError(f"mesh {mesh_shape}: axes must be >= 1")
        if dp != 1 and submesh is None:
            raise NotImplementedError(
                "dp > 1 serving replicas are independent engines behind "
                "a router — one engine cannot be two replicas.  Build a "
                "serve.cluster.Cluster: it slices the (data, model) mesh "
                "into per-replica sub-meshes (parallel.mesh.dp_submeshes) "
                "and hands each engine its own via submesh=")
        self.dp, self.tp = dp, tp
        self.replica_id = int(replica_id)
        self.mesh = None
        self._replica_device = None
        if submesh is not None:
            sizes = mesh_axis_sizes(submesh)
            if sizes.get("model", 1) != tp or sizes.get("data", 1) != 1:
                raise ValueError(
                    f"replica submesh axes {sizes} do not match "
                    f"(data=1, model={tp})")
            if tp == 1:
                # single-device replica: pin params (and, on reset, the
                # pool) to the submesh's device — no shard_map, so the
                # step stays byte-identical to the parent Engine's
                dev = submesh.devices.reshape(-1)[0]
                self.params = jax.device_put(self.params, dev)
                self._replica_device = dev
                return
            self.mesh = submesh
        elif tp == 1:
            return
        else:
            self.mesh = make_host_mesh(data=dp, model=tp)
        self.cfg_local = tp_local_config(self.cfg, tp,
                                         overlap=self.ecfg.overlap)
        self._param_specs = param_pspecs(self.cfg, self.mesh)
        self.params = jax.device_put(
            self.params,
            jax.tree.map(lambda sp: NamedSharding(self.mesh, sp),
                         self._param_specs))
        if self.obs is not None:
            # the private bundle announced the unsharded name before the
            # mesh existed; re-announce with the tp width (last wins)
            self.obs.tracer.process(self._obs_pid, self._obs_process_name())

    # -- engine overrides --------------------------------------------------

    def _obs_process_name(self) -> str:
        tp = getattr(self, "tp", 1)
        if tp > 1:
            return f"{self.cfg.name} engine tp={tp} (replica " \
                   f"{self.replica_id})"
        return super()._obs_process_name()

    def reset(self, num_slots: Optional[int] = None,
              max_len: Optional[int] = None) -> None:
        super().reset(num_slots=num_slots, max_len=max_len)
        if self.mesh is not None:
            self._apply_mesh()
        elif self._replica_device is not None:
            # tp=1 replica on its own device: the pool follows the params
            self._kv.pools = jax.device_put(self._kv.pools,
                                            self._replica_device)

    def _step_collective_bytes(self, n_tokens: int) -> float:
        if self.mesh is None:
            return 0.0
        return decode_step_ici_bytes(self.cfg, self.ecfg.num_slots,
                                     self.tp, n_tokens)

    def _ledger_chips(self) -> int:
        return max(self.tp, 1)

    # -- sharding ----------------------------------------------------------

    def _apply_mesh(self) -> None:
        """Shard the freshly built pools and wrap the jitted steps.

        The step bodies are the parents' own (Engine._decode_callable /
        SpecEngine._verify_callable) traced with the per-shard local
        config: inside shard_map every array is the local shard, the
        kernels see local KV heads and pages, and the only cross-chip
        traffic is the explicit psum / all-gather edges the ledger
        prices."""
        kv, e = self._kv, self.ecfg
        self._pool_specs = pool_pspecs(self.cfg, e.num_slots, kv.num_pages,
                                       e.page_size, self.mesh)
        kv.pools = jax.device_put(
            kv.pools,
            jax.tree.map(lambda sp: NamedSharding(self.mesh, sp),
                         self._pool_specs))
        rep = P()
        self._decode_fn = jax.jit(shard_map(
            self._decode_callable(self.cfg_local), mesh=self.mesh,
            in_specs=(self._param_specs, self._pool_specs) + (rep,) * 9,
            out_specs=(rep, self._pool_specs), check_vma=False))
        if isinstance(self, SpecEngine):
            n_rep_in = 12 if self.scfg.proposer == "draft" else 11
            self._verify_fn = jax.jit(shard_map(
                self._verify_callable(self.cfg_local), mesh=self.mesh,
                in_specs=(self._param_specs, self._pool_specs)
                + (rep,) * n_rep_in,
                out_specs=(rep, rep, self._pool_specs), check_vma=False))

    # -- crosscheck support ------------------------------------------------

    def decode_step_compiled(self):
        """Lower + compile the live sharded decode step at its current
        shapes — the HLO side of crosscheck_collectives."""
        if self._kv is None:
            raise ValueError("engine has no live pool; submit work or "
                             "reset()")
        if self.mesh is None:
            raise ValueError("1x1 mesh: no sharded step to characterize")
        kv, B = self._kv, self.ecfg.num_slots
        ksize = sampling.key_data(None).shape[0]

        def st(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        abstract = jax.tree.map(lambda a: st(a.shape, a.dtype),
                                (self.params, kv.pools))
        args = abstract + (
            st((B, kv.blocks_per_slot), jnp.int32),   # block tables
            st((B, 1), jnp.int32),                    # token
            st((B,), jnp.int32),                      # pos
            st((B,), jnp.bool_),                      # active
            st((B, ksize), jnp.uint32),               # key data
            st((B,), jnp.int32),                      # steps
            st((B,), jnp.float32),                    # temps
            st((B,), jnp.int32),                      # top_ks
            st((B,), jnp.float32),                    # top_ps
        )
        return self._decode_fn.lower(*args).compile()


class ShardedEngine(_ShardedStepMixin, Engine):
    """Continuous-batching engine running its decode step tensor-parallel.

    Drop-in for :class:`Engine` plus a ``mesh_shape=(dp, tp)``::

        eng = ShardedEngine(cfg, params, ecfg, mesh_shape=(1, 4))
        eng.submit(prompt_ids, GenerateConfig(max_new_tokens=64))
        done = eng.run()     # ledgers now carry per-device ICI wire bytes

    On a 1x1 mesh nothing is wrapped or resharded — behaviour (and
    bytes) are the parent engine's exactly.
    """

    def __init__(self, cfg: ModelConfig, params,
                 ecfg: Optional[EngineConfig] = None,
                 mesh_shape: Tuple[int, int] = (1, 1),
                 submesh: Optional[Any] = None, replica_id: int = 0):
        super().__init__(cfg, params, ecfg)
        self._init_mesh(mesh_shape, submesh=submesh, replica_id=replica_id)


class ShardedSpecEngine(_ShardedStepMixin, SpecEngine):
    """Speculative draft/verify engine with the tensor-parallel step: the
    fixed-shape verify+accept body runs per-shard under shard_map (the
    multi-token page walk over local KV heads), so speculative decoding
    and tensor parallelism compose — intensity scales by ~(k+1) while the
    same per-block psum edges carry T-times-wider activations
    (scheduler.decode_step_ici_bytes ``n_tokens``)."""

    def __init__(self, cfg: ModelConfig, params,
                 ecfg: Optional[EngineConfig] = None,
                 scfg: Optional[SpecConfig] = None,
                 mesh_shape: Tuple[int, int] = (1, 1),
                 submesh: Optional[Any] = None, replica_id: int = 0):
        super().__init__(cfg, params, ecfg, scfg)
        self._init_mesh(mesh_shape, submesh=submesh, replica_id=replica_id)


def make_engine(cfg: ModelConfig, params,
                ecfg: Optional[EngineConfig] = None,
                scfg: Optional[SpecConfig] = None,
                mesh_shape: Tuple[int, int] = (1, 1),
                submesh: Optional[Any] = None, replica_id: int = 0):
    """Engine factory the launcher/bench/cluster share: spec config picks
    the speculative subclass, mesh_shape > (1,1) picks the sharded ones;
    ``submesh`` pins one dp replica to its own (1, tp) device row
    (serve/cluster.py passes parallel.mesh.dp_submeshes slices)."""
    if scfg is not None:
        return ShardedSpecEngine(cfg, params, ecfg, scfg,
                                 mesh_shape=mesh_shape, submesh=submesh,
                                 replica_id=replica_id)
    return ShardedEngine(cfg, params, ecfg, mesh_shape=mesh_shape,
                         submesh=submesh, replica_id=replica_id)
