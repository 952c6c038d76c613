from .transformer import (block_forward, cache_specs, decode_step, forward,
                          init_cache, init_stack, loss_fn, prefill,
                          splice_prefill)

__all__ = ["block_forward", "cache_specs", "decode_step", "forward",
           "init_cache", "init_stack", "loss_fn", "prefill",
           "splice_prefill"]
