from . import sampling
from .block_pool import BlockPool, PoolStats, chain_hash, token_chain_hashes
from .cluster import Cluster, RoleConfig
from .engine import Engine, EngineConfig, GenerateConfig, StaticEngine
from .kv_cache import (PagedKVCache, SwapSnapshot, supports_paging,
                       supports_prefix_cache)
from .proposer import DraftModelProposer, NgramProposer, Proposal
from .router import Router
from .scheduler import Request, RequestState, RooflineLedger, Scheduler
from .shard import (ShardedEngine, ShardedSpecEngine, init_sharded_params,
                    make_engine, parse_mesh, supports_tp, tp_local_config,
                    tp_sharding_error)
from .spec import (SpecConfig, SpecEngine, adaptive_k,
                   spec_expected_tokens_per_pass, spec_speedup_model,
                   supports_spec)

__all__ = [
    "Cluster", "RoleConfig", "Router",
    "Engine", "EngineConfig", "GenerateConfig", "StaticEngine",
    "BlockPool", "PoolStats", "chain_hash", "token_chain_hashes",
    "PagedKVCache", "SwapSnapshot", "supports_paging",
    "supports_prefix_cache",
    "Request", "RequestState", "RooflineLedger", "Scheduler",
    "DraftModelProposer", "NgramProposer", "Proposal",
    "ShardedEngine", "ShardedSpecEngine", "init_sharded_params",
    "make_engine", "parse_mesh",
    "supports_tp", "tp_local_config", "tp_sharding_error",
    "SpecConfig", "SpecEngine", "adaptive_k",
    "spec_expected_tokens_per_pass", "spec_speedup_model", "supports_spec",
    "sampling",
]
