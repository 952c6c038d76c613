"""Adapter for ``repro.launch.serve.run``: one batch job per call.

A job prefills ``batch`` prompts, decodes ``gen`` greedy tokens for each
and, with ``--spill``, appends a row per token to the remote-memory KV
tier, then spills every sequence to the donors and fetches it back.
"""

from __future__ import annotations

import contextlib
from typing import Dict, Iterator, List, Optional

import numpy as np

from benchmarks.chip.entries.common import (config_mismatch, module_copy,
                                            patched)

MODULE = "repro.launch.serve"
# the fixed seed serve.run draws from without --seed:
# jax.random.key(0) for its weights, numpy's default_rng(0) for its prompts
PROGRAM_SEED = 0


@contextlib.contextmanager
def tap(seed: Optional[int]) -> Iterator[Dict]:
    """Installed in ``serve.run``'s module for a run, until the program
    takes ``--seed`` and returns its served tokens itself.

    With ``seed``, the two names it draws its fixed seed from,
    ``jax.random.key`` and numpy's ``random.default_rng``, hand it
    ``seed`` in place of ``PROGRAM_SEED``. numpy's ``stack`` keeps what it
    stacks: ``serve.run`` stacks the tokens it served, once its decode
    loop has been timed, and prints 16 of them. The module gets copies
    of ``jax`` and ``numpy`` with these names put in, so the timed loop
    looks up every other name as fast as before.

    Yields a record: ``stacked``, every array stacked, and ``swapped``,
    how many draws took ``seed``."""
    import jax

    record: Dict[str, List] = {"stacked": [], "swapped": []}

    def swap(s):
        if seed is not None and isinstance(s, int) and s == PROGRAM_SEED:
            record["swapped"].append(s)
            return seed
        return s

    def stack(arrays, *a, **kw):
        out = np.stack(arrays, *a, **kw)
        record["stacked"].append(out)
        return out

    np_random = module_copy(np.random, default_rng=lambda s=None, *a, **kw:
                            np.random.default_rng(swap(s), *a, **kw))
    jax_random = module_copy(jax.random, key=lambda s, *a, **kw:
                             jax.random.key(swap(s), *a, **kw))
    with patched(MODULE, "np", lambda _: module_copy(
            np, random=np_random, stack=stack)), \
            patched(MODULE, "jax", lambda _: module_copy(
                jax, random=jax_random)):
        yield record


def work(out: Dict, args: Dict) -> Dict:
    """What one job served, from what ``serve.run`` returns."""
    B, P, G = args["batch"], args["prompt-len"], args["gen"]
    return {"requests": B,
            "decode_tokens": B * G, "decode_s": B * G / out["decode_tok_s"],
            "prefill_tokens": B * P, "prefill_s": out["prefill_s"]}


def end_to_end(works: List[Dict]) -> Dict[str, float]:
    """Rates over every job of the window: all tokens over all the time
    the jobs spent in the phase."""
    def rate(kind):
        return (sum(w[f"{kind}_tokens"] for w in works)
                / sum(w[f"{kind}_s"] for w in works))
    return {"decode_tok_s": rate("decode"), "prefill_tok_s": rate("prefill")}


def served(out: Dict, record: Dict, config: Dict, args: Dict) -> Dict:
    """What the job produced, copied to the host for the check after the
    window: every token served (the prefill's, then one per decode step:
    ``(batch, gen + 1)``, or None where the job gave no such tokens), the
    first decode step's logits, the spill round trip's verdict and the
    sizes the program ran.

    The decode tokens are ``out["tokens"]`` where ``serve.run`` returns
    them, else the last ``(batch, gen)`` array the job stacked."""
    B, G = args["batch"], args["gen"]
    decoded = out.get("tokens")
    if decoded is None:
        decoded = next((a for a in reversed(record["stacked"])
                        if a.shape == (B, G)), None)
    first = np.asarray(out["first_token"], np.int64)
    tokens = None
    if decoded is not None and np.shape(decoded) == (B, G):
        tokens = np.concatenate([first[:, None],
                                 np.asarray(decoded, np.int64)], 1)
    return {"tokens": tokens,
            "first_logits": np.asarray(out["first_logits"], np.float32),
            "spill_exact": out.get("spill_exact"),
            "config_mismatch": config_mismatch(out["cfg"], config)}


def check(kept: Dict, config: Dict, args: Dict, seed: int, reference,
          quant: Optional[str] = None) -> Dict[str, float]:
    """The numbers compared against the cell's limits.

    ``served_gap_mean``: over every token served, in every sequence (the
    prefill's at position ``prompt-len - 1``, then each decode step's),
    the mean gap by which the token's reference logit lies below the
    reference's best at that position, the reference reading the prompt
    and the tokens served before it. The mean, not the widest gap, is
    compared: a gap opens only where rounding reorders a near tie, so a
    widest gap follows the per-logit error, which the int8 control only
    quadruples, while the mean follows how often and how far ties are
    reordered, both of which grow with the error (PERF.md §2). An
    altered token adds its whole gap, ~5 logits, to the sum of
    8 x 513 tokens. ``decode_logits_err``: the first
    decode step's logits (read through the cache at position
    ``prompt-len``) against the reference's, max abs difference over max
    abs reference logit. ``spill_mismatch``: 1 unless every spilled KV
    page came back byte-exact. ``config_mismatch``: how many sizes the
    program ran differ from the configuration file. A job that gave no
    served tokens reads an infinite mean gap.

    With ``quant``, the reference's precision-reduced twin stands in for
    the program (the control): at every served position of the same
    prompts and tokens it serves the token it puts first, and its logits
    at position ``prompt-len`` stand for the decode step's. The
    reference runs one sequence at a time, so that its logits fit.
    """
    import jax
    import jax.numpy as jnp

    B, P, G, V = args["batch"], args["prompt-len"], args["gen"], \
        config["vocab_size"]
    spill = 0.0 if kept["spill_exact"] is True else 1.0
    mismatch = float(len(kept["config_mismatch"]))
    tokens = kept["tokens"]
    if tokens is None:
        return {"served_gap_mean": float("inf"),
                "decode_logits_err": float("inf"),
                "spill_mismatch": spill, "config_mismatch": mismatch}
    prompts = np.random.default_rng(seed).integers(0, V, (B, P))
    inputs = np.concatenate([prompts, tokens[:, :G]], 1)        # (B, P + G)
    weights = jax.jit(lambda k: reference.init_weights(config, k))(
        jax.random.key(seed))

    def served_logits(w, t, q):
        """Logits of positions P - 1 .. P + G - 1: (1, G + 1, V)."""
        return reference.logits(
            w, reference.hidden(w, t, config, q)[:, P - 1:], config, q)

    @jax.jit
    def read(w, t, s):
        z = served_logits(w, t, None)
        step = z[:, 1]
        if quant is not None:
            zq = served_logits(w, t, quant)
            s, step = jnp.argmax(zq, -1), zq[:, 1]
        picked = jnp.take_along_axis(z, s[..., None], -1)[..., 0]
        return (z.max(-1) - picked).sum(), z[:, 1], step

    gap_sum, ref_step, step = 0.0, [], []
    for b in range(B):
        g, r, s = read(weights, jnp.asarray(inputs[b: b + 1], jnp.int32),
                       jnp.asarray(tokens[b: b + 1], jnp.int32))
        gap_sum += float(g)
        ref_step.append(np.asarray(r[0]))
        step.append(np.asarray(s[0]))
    del weights
    ref_step = np.stack(ref_step)
    step = np.stack(step) if quant else kept["first_logits"][:, :V]
    err = float(np.abs(step - ref_step).max() / np.abs(ref_step).max())
    return {"served_gap_mean": gap_sum / tokens.size, "decode_logits_err": err,
            "spill_mismatch": spill, "config_mismatch": mismatch}
