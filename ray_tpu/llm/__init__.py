"""ray_tpu.llm — native continuous-batching LLM inference.

Reference layer map: where the reference runtime fronts external
inference engines (vLLM et al.), this package is the TPU-native engine
itself, built from the repo's own layers:

  * llm/kv_cache.py      — paged KV pool (PagedAttention block
                            manager) + PrefixPool (hash-indexed,
                            ref-counted prefix cache with COW)
  * ops/pallas/paged_decode.py — decode-attention kernel gathering K/V
                            through block tables (interpret mode on CPU)
  * models/gpt.py        — forward_step / forward_prefill_chunk: the
                            training layer around a paged or a chunk
                            attention sublayer
  * llm/engine.py        — Orca-style iteration-level scheduler
  * llm/spec.py          — speculative decoding (n-gram / small-draft
                            proposers verified in one paged-attention
                            pass; output bit-identical either way)
  * serve/llm.py         — streaming deployment (TTFT/TPOT SLO phases,
                            tokens/s + KV-utilization telemetry)
"""

from .engine import (  # noqa: F401
    FINISHED,
    PREEMPTED,
    PREFILL,
    RUNNING,
    WAITING,
    LLMEngine,
    Request,
)
from .kv_cache import PagedKVCache, PrefixPool  # noqa: F401
from .sampling import rejection_sample, sample, verify_tokens  # noqa: F401
from .spec import (  # noqa: F401
    DraftProposer,
    NgramProposer,
    Proposer,
    SpecConfig,
    SpecDecoder,
)
