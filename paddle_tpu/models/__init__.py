"""In-tree model families.

The reference keeps models in separate repos (PaddleNLP, PaddleMIX); they are
in-tree here because they are the benchmark workloads the framework is
measured on (BASELINE.md) and they double as integration tests of the hybrid
parallel stack.

What the serving engine (``serving.ServingEngine``) takes is told once, in
:class:`~paddle_tpu.models.parts.ServingTraits`: ``llama`` in every layout,
and ``afmoe`` (Trinity), ``lfm2`` (LFM2-MoE), ``sdar`` (SDAR-MoE),
``latent_moe`` (latent attention, MLA) and ``olmo_hybrid`` (Gated DeltaNet
layers beside attention) as each one's ``serving_traits`` declare — what a family refuses, and why, is its ``unsupported``.
"""

from .afmoe import AfmoeConfig, AfmoeForCausalLM, tiny_afmoe_config
from .generation import (DecodeStep, accept_draft_tokens, greedy_generate,
                         init_kv_cache, sample_tokens)
from .latent_moe import (LatentMoeConfig, LatentMoeForCausalLM,
                         tiny_latent_moe_config)
from .lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM, tiny_lfm2_config
from .olmo_hybrid import (OlmoHybridConfig, OlmoHybridForCausalLM,
                          tiny_olmo_hybrid_config)
from .sdar import SdarMoeConfig, SdarMoeForCausalLM, tiny_sdar_config
from .llama import (LlamaConfig, LlamaForCausalLM, LlamaModel,
                    causal_lm_loss, draft_model_from, llama3_8b_config,
                    llama_pipe_descs, tiny_llama_config)

__all__ = [
    "LlamaConfig", "LlamaModel", "LlamaForCausalLM", "llama3_8b_config",
    "tiny_llama_config", "llama_pipe_descs", "causal_lm_loss",
    "DecodeStep", "greedy_generate", "init_kv_cache", "sample_tokens",
    "accept_draft_tokens", "draft_model_from",
    "AfmoeConfig", "AfmoeForCausalLM", "tiny_afmoe_config",
    "Lfm2MoeConfig", "Lfm2MoeForCausalLM", "tiny_lfm2_config",
    "SdarMoeConfig", "SdarMoeForCausalLM", "tiny_sdar_config",
    "LatentMoeConfig", "LatentMoeForCausalLM", "tiny_latent_moe_config",
    "OlmoHybridConfig", "OlmoHybridForCausalLM", "tiny_olmo_hybrid_config",
]
