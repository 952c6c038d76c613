"""Faults planted under an entry's timed path, to show that the check
catches them. Each fault is a context manager that patches the program
module the entry drives and restores it on exit. A cell lists the faults
it can have by the name of its entry; the exchange between chips has no
fault here, since every cell runs on one chip.
"""

from __future__ import annotations

from typing import Callable, Dict

from benchmarks.chip.entries.common import patched


def _decode_state_unchanged(orig):
    # the step's logits are right, but it hands back the cache it got
    def step(params, cache, token, cur, cfg):
        return orig(params, cache, token, cur, cfg)[0], cache
    return step


def _decode_half_batch(orig):
    # only the first half of the batch is computed; the rest repeats it
    def step(params, cache, token, cur, cfg):
        import jax
        import jax.numpy as jnp
        half = token.shape[0] // 2
        logits, new = orig(params, jax.tree.map(lambda c: c[:, :half], cache),
                           token[:half], cur[:half], cfg)
        return (jnp.concatenate([logits, logits], 0),
                jax.tree.map(lambda c, n: c.at[:, :half].set(n), cache, new))
    return step


def _prefill_token_altered(orig):
    # prefill's logits are shifted by one along the vocabulary, so the
    # token it produces is its best one's neighbour
    def fill(params, tokens, cfg, **kw):
        import jax.numpy as jnp
        logits, cache = orig(params, tokens, cfg, **kw)
        return jnp.roll(logits, 1, axis=-1), cache
    return fill


FAULTS: Dict[str, Dict[str, Callable]] = {
    "serve": {
        "state_unchanged": lambda: patched(
            "repro.launch.serve", "decode_step", _decode_state_unchanged),
        "half_batch": lambda: patched(
            "repro.launch.serve", "decode_step", _decode_half_batch),
        "token_altered": lambda: patched(
            "repro.launch.serve", "prefill", _prefill_token_altered),
    },
}
