"""Shape-stable serving: bucketed prefill, slot KV cache, continuous
batching (see engine.py for the design).

Quick start::

    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(model, slot_count=4, ladder=(16, 32, 64),
                        max_new_cap=32)
    reqs = [eng.submit(prompt, max_new_tokens=24, eos_token_id=eos)
            for prompt in prompts]
    eng.run()                      # continuous batching until drained
    outs = [r.output_ids() for r in reqs]

core.monitor counters: serving.prefill_compiles (bounded by the bucket
ladder), serving.decode_compiles (one executable), serving.steps,
serving.decode_ahead (decode dispatches enqueued ahead of a fetch),
serving.tokens, serving.requests, serving.prefill_dispatches; the paged
layout (kv_pages.py / prefix_cache.py / router.py) adds
serving.prefix_lookups, serving.prefix_hits, serving.prefill_skips;
legacy generate() adds decode.jit_compiles / decode.cache_evictions
(LRU-bounded executable cache).
"""
from ..core.bucketing import (  # noqa: F401
    DEFAULT_LADDER, bucket_for, clip_ladder, resolve_bucket,
)
from .diffusion import BlockDiffusion  # noqa: F401
from .engine import Request, ServingEngine  # noqa: F401
from .kv_pages import PagePool, PoolExhausted  # noqa: F401
from .loadgen import (  # noqa: F401
    LoadGenerator, Scenario, spike_scenario, zipf_tenants,
)
from .prefix_cache import RadixPrefixCache  # noqa: F401
from .router import ReplicaRouter  # noqa: F401
from .sampling import (  # noqa: F401
    filter_topk_topp, request_key, sample_tokens, sample_tokens_with_prob,
)

__all__ = [
    "ServingEngine", "Request", "ReplicaRouter",
    "Scenario", "LoadGenerator", "spike_scenario", "zipf_tenants",
    "PagePool", "PoolExhausted", "RadixPrefixCache",
    "DEFAULT_LADDER", "bucket_for", "clip_ladder", "resolve_bucket",
    "sample_tokens", "sample_tokens_with_prob", "filter_topk_topp",
    "request_key", "BlockDiffusion",
]
