"""The serving cell's harness, rehearsed on the CPU at reduced sizes.

The look for a chip is skipped and the rest of a run is driven: the
entry's job, what it returns, the trace reduction and the check. Off the
chip nothing is reported under a device metric's name. With the timed
path broken underneath, or the int8 control in the program's place, the
check has to come out false; the int8 control has to read well above
the program.
"""

import numpy as np
import pytest
from conftest import REDUCED_ARGS, SERVE, reduced_cell

from benchmarks.chip import faults, harness


@pytest.fixture(scope="module")
def cell():
    return reduced_cell(SERVE)


def one_job(cell, seed):
    """One job under the entry's tap; returns what it returned and what
    the check keeps of it."""
    with cell.entry.tap(seed) as record:
        out, _ = harness.run_job(cell.program(), harness.job_argv(cell.args))
        kept = cell.entry.served(out, record, cell.config, cell.args)
    return out, record, kept


@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_is_correct_and_reports_no_device_metric(cell, trace):
    r = harness.measure(cell, 2**31 + 11, 0.0, trace)
    assert r["correct"] is True, r["checks"]
    # one window job, or the traced window's two; each serves the batch
    jobs = 2 if trace else 1
    assert r["attempted"] == jobs * REDUCED_ARGS[SERVE]["batch"]
    assert r["failed"] == 0
    assert r["metrics"] == {} and "breakdown" not in r
    assert r["device"]["platform"] == "cpu"
    assert "busy_s" not in r["device"] and "window_s" not in r["device"]
    assert list(r)[-1] == "checks"
    assert r["checks"]["spill_mismatch"]["value"] == 0


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_tap_hands_the_seed_to_the_program(cell, seed):
    """Weights and prompts are drawn from the run's seed, as the
    reference draws them."""
    import jax

    out, record, _ = one_job(cell, seed)
    a = cell.args
    prompts = np.random.default_rng(seed).integers(
        0, cell.config["vocab_size"], (a["batch"], a["prompt-len"]))
    assert np.array_equal(np.asarray(out["prompts"]), prompts)
    w = cell.reference.init_weights(cell.config, jax.random.key(seed))
    assert np.array_equal(np.asarray(out["params"]["embed"]),
                          np.asarray(w["embed"]))
    assert record["swapped"]


def test_tap_keeps_every_served_token_and_restores_the_module(cell):
    import sys

    mod = sys.modules[cell.entry.MODULE]
    np_before, jax_before = mod.np, mod.jax
    out, _, kept = one_job(cell, 9)
    a = cell.args
    assert kept["tokens"].shape == (a["batch"], a["gen"] + 1)
    assert np.array_equal(kept["tokens"][:, 0],
                          np.asarray(out["first_token"]))
    assert mod.np is np_before and mod.jax is jax_before


@pytest.mark.parametrize("where", ["first", "middle", "last"])
def test_check_sees_a_token_altered_anywhere(cell, where):
    """A token altered after the job, at the prefill's position or late
    in the decode, fails the check: every served token is compared."""
    seed = 10
    _, _, kept = one_job(cell, seed)
    read = cell.entry.check(kept, cell.config, cell.args, seed,
                            cell.reference)
    assert read["served_gap_mean"] <= cell.cell["limits"]["served_gap_mean"]
    b, k = {"first": (0, 0), "middle": (1, cell.args["gen"] // 2),
            "last": (1, cell.args["gen"])}[where]
    kept["tokens"][b, k] = (kept["tokens"][b, k]
                            + cell.config["vocab_size"] // 2) \
        % cell.config["vocab_size"]
    altered = cell.entry.check(kept, cell.config, cell.args, seed,
                               cell.reference)
    assert altered["served_gap_mean"] > cell.cell["limits"]["served_gap_mean"]


def test_job_without_served_tokens_is_not_correct(cell):
    _, _, kept = one_job(cell, 11)
    kept["tokens"] = None
    read = cell.entry.check(kept, cell.config, cell.args, 11, cell.reference)
    assert read["served_gap_mean"] == float("inf")


@pytest.mark.parametrize("fault", sorted(faults.FAULTS["serve"]))
def test_planted_fault_fails_the_check(cell, fault):
    r = harness.measure(cell, 5, 0.0, False, warmup=False, fault=fault)
    assert r["correct"] is False, (fault, r["checks"])


def test_int8_control_reads_well_above_the_program(cell):
    """At this size the int8 control stays inside the full-size limits
    (which it fails on the chip, PERF.md §2); what carries over is how
    far it reads above the program: at least 3x the logits error, and a
    larger mean gap of the served tokens."""
    def read(**kw):
        r = harness.measure(cell, 6, 0.0, False, warmup=False, **kw)
        return {k: v["value"] for k, v in r["checks"].items()}

    program, control = read(), read(quant="int8")
    assert control["decode_logits_err"] >= 3 * program["decode_logits_err"]
    assert control["served_gap_mean"] > program["served_gap_mean"]
