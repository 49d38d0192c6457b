from repro.serve.adaptive import AdaptiveMPController, NumericalGuardrail
from repro.serve.cache_pool import (CachePool, PagedCachePool,
                                    PoolExhausted, dense_slot_bytes,
                                    paged_block_bytes, paged_slot_bytes)
from repro.serve.engine import (ContinuousBatchingEngine, GenResult,
                                ServeEngine, ServeSummary, prefill_bucket)
from repro.serve.faults import (FAULT_KINDS, FaultInjector, FaultSpec,
                                InjectedFault)
from repro.serve.parallel import (make_serving_layout, shard_cache_tree,
                                  shard_serving_params)
from repro.serve.scheduler import Request, RequestResult, Scheduler

__all__ = ["AdaptiveMPController", "CachePool",
           "ContinuousBatchingEngine", "FAULT_KINDS", "FaultInjector",
           "FaultSpec", "GenResult", "InjectedFault", "NumericalGuardrail",
           "PagedCachePool", "PoolExhausted", "Request", "RequestResult",
           "Scheduler", "ServeEngine", "ServeSummary", "dense_slot_bytes",
           "make_serving_layout", "paged_block_bytes", "paged_slot_bytes",
           "prefill_bucket", "shard_cache_tree", "shard_serving_params"]
