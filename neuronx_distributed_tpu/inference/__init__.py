"""Inference stack (reference: ``trace/`` + serving modules).

* :mod:`.model_builder` — AOT multi-key/multi-bucket builder + runtime
  container (reference ``ModelBuilder`` / ``NxDModel``).
* :mod:`.kv_cache` — on-device KV cache state (reference
  ``StateInitializer`` buffers).
* :mod:`.generation` — prefill/decode loop (reference serving examples).
* :mod:`.sampling` — greedy/top-k/top-p (reference ``utils/sampling.py``).
* :mod:`.paging` — paged KV block pool + host-side block allocator.
* :mod:`.engine` — continuous-batching serving engine over the paged pool.
* :mod:`.router` — multi-replica front-end: placement, admission control,
  health-checked failover, graceful drain, obs-driven autoscaling, live
  KV-session migration, two-tier prefill/decode fabric.
* :mod:`.transport` — cross-host KV handoff: chunked int8 wire format,
  simulated DCN link under chaos, NACK + bounded-backoff retransmit,
  atomic commit with re-prefill fallback.
* :mod:`.aot_cache` — serialized-executable cache: replicas *load* their
  compiled step instead of recompiling (warm scale-up/revival).
"""

from . import aot_cache
from . import generation
from . import kv_cache
from . import model_builder
from . import paging
from . import engine
from . import sampling
from . import speculative
from . import router
from . import transport
from .aot_cache import AotExecutableCache, AotWorker
from .engine import (EngineConfig, EngineStats, RequestRejected,
                     RequestResult, ServingEngine, SessionTicket,
                     TICKET_MAGIC, TicketWireError)
from .generation import (DECODE_BUCKETS, decode_step, generate, pick_bucket,
                         prefill)
from .kv_cache import KVCache, init_kv_cache
from .model_builder import (ModelBuilder, NxDModel, bundle_generate,
                            bundle_speculative_generate, generate_buckets,
                            register_serving_workers, serving_state_spec,
                            shard_checkpoint)
from .paging import (BlockAllocator, CacheExhaustedError, LatentPagedCache,
                     PagedKVCache, PrefixCache, QuantizedPagedKVCache,
                     SparseStatePagedCache, StatePoolPagedCache,
                     WindowPoolPagedCache, cow_copy_blocks,
                     init_paged_kv_cache, init_quantized_paged_kv_cache,
                     init_serving_cache)
from .router import (FabricConfig, ReplicaRouter, RouterConfig, RouterResult,
                     RouterStats, ScalePolicy, ServingPreempted,
                     TenantPolicy, elastic_chaos_drill, fabric_chaos_drill)
from .sampling import SamplingConfig, sample
from .transport import (CHUNK_MAGIC, ChunkError, ChunkIntegrityError,
                        DcnLink, KVStreamTransport, StreamConfig)
from .speculative import make_speculation_round_fn

__all__ = [
    "generation", "kv_cache", "model_builder", "sampling",
    "speculative", "paging", "engine", "router", "aot_cache",
    "AotExecutableCache", "AotWorker",
    "DECODE_BUCKETS", "decode_step", "generate", "pick_bucket", "prefill",
    "KVCache", "init_kv_cache",
    "BlockAllocator", "CacheExhaustedError", "PagedKVCache",
    "PrefixCache", "QuantizedPagedKVCache", "SparseStatePagedCache",
    "LatentPagedCache", "StatePoolPagedCache", "WindowPoolPagedCache",
    "init_serving_cache", "cow_copy_blocks",
    "init_paged_kv_cache", "init_quantized_paged_kv_cache",
    "ServingEngine", "EngineConfig", "EngineStats", "RequestRejected",
    "RequestResult", "SessionTicket", "TICKET_MAGIC", "TicketWireError",
    "ReplicaRouter", "RouterConfig", "RouterResult", "RouterStats",
    "ScalePolicy", "ServingPreempted", "TenantPolicy",
    "elastic_chaos_drill", "fabric_chaos_drill", "FabricConfig",
    "transport", "CHUNK_MAGIC", "ChunkError", "ChunkIntegrityError",
    "DcnLink", "KVStreamTransport", "StreamConfig",
    "ModelBuilder", "NxDModel", "generate_buckets", "shard_checkpoint",
    "register_serving_workers", "serving_state_spec",
    "bundle_generate", "bundle_speculative_generate",
    "make_speculation_round_fn",
    "SamplingConfig", "sample",
]
