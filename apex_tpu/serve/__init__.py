"""``apex_tpu.serve`` — paged KV-cache inference with continuous
batching on the tensor-parallel stack.

The first non-training workload in the codebase, composing four
existing subsystems on the decode hot path:

- the **paged KV cache** (:mod:`~apex_tpu.serve.cache`): a
  preallocated page pool + per-sequence block tables, mutated in place
  through the donated decode step; fp8-KV mode stores e4m3 pages with
  per-page scales through the :mod:`apex_tpu.amp.fp8` codec (~2x cache
  capacity = ~2x concurrent sequences per chip);
- the **decode attention kernel**
  (``ops.paged_attention.paged_decode_attention``): single query per
  sequence reading K/V through the block table, GQA-aware, page size
  resolved explicit > tuned cache > heuristic via :mod:`apex_tpu.tune`
  (the ``decode_attention`` sweep);
- the **continuous-batching scheduler**
  (:mod:`~apex_tpu.serve.scheduler`): admit/evict/preempt at step
  granularity with capacity accounted in pages; preemption recomputes
  (prefill + decode-replay) and is bit-exact;
- **TP layouts** (:mod:`~apex_tpu.serve.rules`): ``zero.rules``-style
  regex tables producing real PartitionSpecs for the cache (heads over
  the tensor axis) and the GPT param tree;
- ``monitor.profile`` scopes thread prefill/decode attribution through
  the existing analytic walk;
- the **model interface** (:class:`~apex_tpu.serve.model.GPTServed`):
  what the engine asks of a model. GPT answers it here; the
  latent-attention models answer it in :mod:`apex_tpu.serve.deepseek` and
  :mod:`apex_tpu.serve.longcat` over :mod:`apex_tpu.serve.latent`
  (imported on demand, not by this package).

Quick start (see ``examples/serve_gpt.py`` / ``docs/serve.md``)::

    engine = serve.ServeEngine(cfg, params, num_pages=64,
                               max_seq_len=256, max_prompt_len=64)
    engine.add_request(prompt_ids, max_new_tokens=32)
    outputs = engine.run()
"""

from apex_tpu.serve.cache import (CacheConfig, CacheState, init_cache,
                                  resolve_page_size)
from apex_tpu.serve.engine import ServeEngine, naive_generate
from apex_tpu.serve.model import quantize_gpt_weights, weight_stream_bytes
from apex_tpu.serve.rules import (CACHE_RULES, GPT_PARAM_RULES,
                                  match_serve_rules)
from apex_tpu.serve.scheduler import (PageAllocator, Scheduler, Sequence,
                                      StepPlan)
from apex_tpu.serve.spec import accept_greedy, derive_draft

__all__ = [
    "CacheConfig", "CacheState", "init_cache", "resolve_page_size",
    "ServeEngine", "naive_generate", "CACHE_RULES", "GPT_PARAM_RULES",
    "match_serve_rules", "PageAllocator", "Scheduler", "Sequence",
    "StepPlan", "accept_greedy", "derive_draft", "quantize_gpt_weights",
    "weight_stream_bytes",
]
