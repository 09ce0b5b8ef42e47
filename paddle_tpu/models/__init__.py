"""In-tree model families.

The reference keeps models in separate repos (PaddleNLP, PaddleMIX); they are
in-tree here because they are the benchmark workloads the framework is
measured on (BASELINE.md) and they double as integration tests of the hybrid
parallel stack.
"""

from .afmoe import AfmoeConfig, AfmoeForCausalLM, tiny_afmoe_config
from .generation import (DecodeStep, accept_draft_tokens, greedy_generate,
                         init_kv_cache, sample_tokens)
from .llama import (LlamaConfig, LlamaForCausalLM, LlamaModel,
                    causal_lm_loss, draft_model_from, llama3_8b_config,
                    llama_pipe_descs, tiny_llama_config)

__all__ = [
    "LlamaConfig", "LlamaModel", "LlamaForCausalLM", "llama3_8b_config",
    "tiny_llama_config", "llama_pipe_descs", "causal_lm_loss",
    "DecodeStep", "greedy_generate", "init_kv_cache", "sample_tokens",
    "accept_draft_tokens", "draft_model_from",
    "AfmoeConfig", "AfmoeForCausalLM", "tiny_afmoe_config",
]
