"""Model families: TPU-first Llama-style transformers (configs from the
tiny demo scale up to Llama-2-7B, matching BASELINE.json's acceptance
configs), Mixtral-style experts (:mod:`.moe`), latent attention over
fine-grained experts (:mod:`.mla`), state-space layers beside window
and shared attention (:mod:`.hybrid`), Mamba-2 / attention /
expert layers in an order given as a string (:mod:`.nemotron_h`), and
generation by diffusion over blocks under a block-causal mask
(:mod:`.sdar`)."""

from .generate import (forward_with_cache, generate, init_kv_cache,
                       kv_cache_shardings, make_generate_fn,
                       prefill_chunked)
from .hf import (config_from_hf, config_from_hf_json,
                 hybrid_config_from_hf,
                 latent_moe_config_from_hf, load_hf_pretrained,
                 moe_config_from_hf, moe_params_from_hf,
                 nemotron_h_config_from_hf, params_from_hf,
                 sdar_config_from_hf)
from .hybrid import (HybridConfig, init_hybrid_model, layer_kinds_for,
                     make_hybrid_cache, phi4_mini_flash_config,
                     tiny_hybrid_config)
from .mla import (LatentMoEConfig, init_latent_moe_model,
                  joyai_flash_config, latent_moe_forward,
                  latent_moe_shardings, tiny_latent_moe_config)
from .nemotron_h import (NemotronHConfig, init_nemotron_h_model,
                         nemotron3_nano_config, tiny_nemotron_h_config)
from .sdar import (SDARConfig, init_sdar_model, sdar_30b_a3b_config,
                   tiny_sdar_config)
from .lora import (ALL_TARGETS, ATTN_TARGETS, lora_init, lora_merge,
                   lora_num_params, lora_shardings,
                   make_lora_train_step)
from .pp import (make_pp_1f1b_train_step, make_pp_train_step,
                 pp_apply_shardings, pp_loss_fn,
                 pp_stage_params, pp_unstage_params)
from .serving import DecodeServer
from .speculative import speculative_generate
from .quant import (dequantize_weight, dequantize_weight4,
                    is_quantized, is_quantized4, quantization_error,
                    quantize_moe_params, quantize_params,
                    quantize_params4, quantize_weight4,
                    quantize_weight, quantized_moe_shardings,
                    quantized_shardings4,
                    quantized_shardings)
from .moe import (MoEConfig, init_moe_model, mixtral_8x7b_config,
                  moe_forward_hidden,
                  moe_forward, moe_loss_fn, moe_model_shardings,
                  tiny_moe_config)
from .transformer import (SeqParallel, TransformerConfig,
                          fsdp_param_shardings, forward,
                          forward_hidden,
                          init_params, llama2_7b_config, loss_fn,
                          make_train_step, mistral_7b_config,
                          packed_positions, param_shardings,
                          smol_135m_config, tinyllama_1b_config,
                          tiny_config)

__all__ = ["SeqParallel", "TransformerConfig", "forward",
           "forward_hidden",
           "fsdp_param_shardings", "init_params",
           "llama2_7b_config", "loss_fn", "make_train_step",
           "mistral_7b_config", "packed_positions",
           "param_shardings", "smol_135m_config", "tiny_config",
           "tinyllama_1b_config",
           "MoEConfig", "init_moe_model", "mixtral_8x7b_config",
           "moe_forward", "moe_forward_hidden", "moe_loss_fn", "moe_model_shardings",
           "tiny_moe_config",
           "forward_with_cache", "generate", "init_kv_cache",
           "kv_cache_shardings", "make_generate_fn", "prefill_chunked",
           "config_from_hf", "config_from_hf_json",
           "latent_moe_config_from_hf", "load_hf_pretrained",
           "params_from_hf",
           "HybridConfig", "init_hybrid_model", "layer_kinds_for",
           "make_hybrid_cache", "phi4_mini_flash_config",
           "tiny_hybrid_config", "hybrid_config_from_hf",
           "NemotronHConfig", "init_nemotron_h_model",
           "nemotron3_nano_config", "tiny_nemotron_h_config",
           "nemotron_h_config_from_hf",
           "SDARConfig", "init_sdar_model", "sdar_30b_a3b_config",
           "tiny_sdar_config", "sdar_config_from_hf",
           "LatentMoEConfig", "init_latent_moe_model",
           "joyai_flash_config", "latent_moe_forward",
           "latent_moe_shardings", "tiny_latent_moe_config",
           "moe_config_from_hf", "moe_params_from_hf",
           "ALL_TARGETS", "ATTN_TARGETS", "lora_init", "lora_merge",
           "lora_num_params", "lora_shardings", "make_lora_train_step",
           "dequantize_weight", "is_quantized", "quantization_error",
           "quantize_moe_params", "quantize_params", "quantize_weight",
           "quantized_moe_shardings", "quantized_shardings",
           "speculative_generate", "DecodeServer",
           "make_pp_1f1b_train_step", "make_pp_train_step",
           "pp_apply_shardings", "pp_loss_fn",
           "pp_stage_params", "pp_unstage_params"]
