"""Model zoo: MX-quantized transformer/hybrid/SSM stacks + proxy MLP."""
from .transformer import (LMConfig, block_plan, chunk_supported, init_cache,
                          init_cache_paged, kind_paged, lm_apply,
                          lm_decode_step, lm_init, lm_loss, lm_prefill,
                          lm_prefill_chunk, pack_serving_weights,
                          paged_leaf_mask, prefill_supported)
from .proxy import (ProxyConfig, proxy_apply, proxy_batch, proxy_init,
                    proxy_loss, teacher_init)

__all__ = ["LMConfig", "block_plan", "chunk_supported", "init_cache",
           "init_cache_paged", "kind_paged", "lm_apply",
           "lm_decode_step", "lm_init", "lm_loss", "lm_prefill",
           "lm_prefill_chunk", "pack_serving_weights", "paged_leaf_mask",
           "prefill_supported",
           "ProxyConfig", "proxy_apply", "proxy_batch", "proxy_init",
           "proxy_loss", "teacher_init"]
