"""ray_tpu.llm — native continuous-batching LLM inference.

Reference layer map: where the reference runtime fronts external
inference engines (vLLM et al.), this package is the TPU-native engine
itself, built from the repo's own layers:

  * llm/kv_cache.py      — paged KV pool (PagedAttention block
                            manager) + PrefixPool (hash-indexed,
                            ref-counted prefix cache with COW) +
                            WindowPool (layers with a window: a lane
                            keeps the blocks that cover its window)
  * ops/pallas/paged_fetch.py — decode-attention kernels that copy
                            their pages out of the pools as stored,
                            by block table (interpret mode on CPU)
  * models/seam.py       — the serving seam: a model's step and chunk
                            functions, cache and cost descriptions
  * models/gpt.py, models/laguna.py — forward_step /
                            forward_prefill_chunk: a model's layer
                            around a paged or a chunk attention
                            sublayer; each is one program that is
                            donated the pools, writes its rows' K/V
                            into them and returns the token ids the
                            host needs
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
