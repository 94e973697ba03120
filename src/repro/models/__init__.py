"""Model zoo: one config type, six architecture families, pure JAX."""
from .common import ModelConfig
from .lm import (decode_loop, decode_step, forward_train, init_cache,
                 init_cache_specs, init_cast_params, init_lane,
                 init_paged_cache, init_params,
                 loss_fn, prefill, prefill_chunk, prefill_into_slot,
                 read_cache_slot, reset_slot, write_cache_slot)

__all__ = ["ModelConfig", "init_params", "init_cast_params",
           "forward_train", "loss_fn",
           "prefill", "prefill_chunk", "init_lane", "decode_step",
           "decode_loop", "init_cache", "init_cache_specs",
           "init_paged_cache", "prefill_into_slot", "read_cache_slot",
           "reset_slot", "write_cache_slot"]
