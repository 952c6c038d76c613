"""What the entry adapters and the planted faults share."""

from __future__ import annotations

import contextlib
import importlib
import types
from typing import Callable, Dict, Iterator, List

# field of the program's ModelConfig -> key of the configuration file
_SIZES = {"num_layers": "num_hidden_layers", "d_model": "hidden_size",
          "d_ff": "intermediate_size", "num_heads": "num_attention_heads",
          "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
          "vocab_size": "vocab_size", "qkv_bias": "qkv_bias",
          "norm_eps": "rms_norm_eps", "rope_theta": "rope_theta",
          "tie_embeddings": "tie_word_embeddings"}


def config_mismatch(cfg, config: Dict) -> List[str]:
    """Sizes of the model config the program ran that differ from the
    configuration file."""
    return [f"{field}={getattr(cfg, field)!r} vs {key}={config[key]!r}"
            for field, key in _SIZES.items()
            if getattr(cfg, field) != config[key]]


@contextlib.contextmanager
def patched(module: str, name: str, make: Callable) -> Iterator[None]:
    """``module.name`` replaced by ``make(original)`` for the block."""
    mod = importlib.import_module(module)
    orig = getattr(mod, name)
    setattr(mod, name, make(orig))
    try:
        yield
    finally:
        setattr(mod, name, orig)


def module_copy(base: types.ModuleType, **names) -> types.ModuleType:
    """A module holding every name of ``base``, with ``names`` put in
    their place. Names are copied, not forwarded, so a lookup in it costs
    what one in ``base`` does."""
    copy = types.ModuleType(base.__name__)
    copy.__dict__.update(vars(base))
    copy.__dict__.update(names)
    return copy
