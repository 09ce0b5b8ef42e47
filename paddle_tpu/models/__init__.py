"""In-tree model families.

The reference keeps models in separate repos (PaddleNLP, PaddleMIX); they are
in-tree here because they are the benchmark workloads the framework is
measured on (BASELINE.md) and they double as integration tests of the hybrid
parallel stack.

What the serving engine (``serving.ServingEngine``) takes: ``llama`` in every
layout; ``afmoe`` (Trinity) on the paged pool, wave or chunked, with or
without a prefix cache; ``lfm2`` (LFM2-MoE: short-convolution state a slot
beside the paged pool) as ``paged=True, chunked=True, prefix_cache=False``
and nothing else — its ``check_serving_layout`` names what it refuses (a
prefix cache, preemption and the host tier, export/import, the contiguous
cache, wave prefill, int8 KV, speculation, a mesh, int8 weights); ``sdar``
(SDAR-MoE: generation by diffusion over blocks, declared as
``block_diffusion`` — the engine's rows part is then a block of positions a
row, tokens leave it a block at a time) as ``paged=True, chunked=True,
prefix_cache=False`` too, refusing the same list by name;
``latent_moe`` (latent attention, MLA, over a sigmoid-routed MoE: the paged
pool holds ONE entry a position that is key and value at once, declared as
``kv_pool_entry``) as ``paged=True, chunked=True`` WITH or without a prefix
cache, refusing by name the contiguous cache, wave prefill, int8 KV,
preemption and the host tier, export/import, a mesh, speculation and int8
weights.  A model
that keeps a decode state of its own (``init_decode_state``: ``mamba``,
``rwkv``) and does not declare it as serving state (``slot_state`` +
``init_serving_cache``) is refused at construction.
"""

from .afmoe import AfmoeConfig, AfmoeForCausalLM, tiny_afmoe_config
from .generation import (DecodeStep, accept_draft_tokens, greedy_generate,
                         init_kv_cache, sample_tokens)
from .latent_moe import (LatentMoeConfig, LatentMoeForCausalLM,
                         tiny_latent_moe_config)
from .lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM, tiny_lfm2_config
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
]
