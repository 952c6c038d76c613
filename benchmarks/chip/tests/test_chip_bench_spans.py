"""The per-layer metrics that read the program's own spans, on jobs of the
serving cell run on the CPU at reduced sizes.

Each reader finds the traced job among the jobs the program recorded in
this process by its ``serve.prefill`` span, which is where the job's
``prefill_s`` comes from."""

import sys

import pytest
from conftest import SERVE, reduced_cell

from benchmarks.chip import harness

READERS = ("decode.dispatch_us", "kv_tier.append_us", "setup.compile_s")


def reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py",
                               f"bench_metric_{name.replace('.', '_')}")


def ctx_of(cell, work):
    return {"work": work, "config": cell.config, "args": cell.args,
            "peaks": None, "device0": None}


def expected(name, job):
    """What the metric should read for ``job``, from its spans."""
    from repro import trace

    def total_ns(spans):
        return sum(s.end_ns - s.start_ns for s in spans)

    if name == "decode.dispatch_us":
        steps = job.named("serve.decode.dispatch")
        return total_ns(steps) / len(steps) / 1e3
    if name == "kv_tier.append_us":
        return (total_ns(job.named("kv.append"))
                / len(job.named("serve.decode.step")) / 1e3)
    return trace.seconds(trace.jobs()[0].named("serve.compile")[0])


@pytest.fixture(scope="module")
def jobs(tmp_path_factory):
    """Three jobs of the cell: each one's ctx and recorded job."""
    from repro import trace

    cell = reduced_cell(SERVE)
    out = []
    with pytest.MonkeyPatch.context() as mp:
        # before the per-test scratch cache exists: keep this one's
        # programs out of the checkout too
        mp.setenv("JAX_COMPILATION_CACHE_DIR",
                  str(tmp_path_factory.mktemp("jax_cache")))
        for _ in range(3):
            o, wall = harness.run_job(cell.program(),
                                      harness.job_argv(cell.args))
            work = dict(cell.entry.work(o, cell.args), wall_s=wall)
            out.append((ctx_of(cell, work), trace.jobs()[-1]))
    return out


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_a_positive_number(jobs, name):
    ctx, _ = jobs[-1]
    value = reader(name).read(ctx)
    assert value is not None and value > 0


@pytest.mark.parametrize("name", READERS)
def test_reader_picks_the_job_whose_prefill_matches(jobs, name):
    read = reader(name).read
    for ctx, job in jobs:
        assert read(ctx) == expected(name, job)
    if name != "setup.compile_s":       # the warm-up's, whichever job
        assert len({read(ctx) for ctx, _ in jobs}) == len(jobs)


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_where_no_job_matches(jobs, name):
    ctx, _ = jobs[0]
    ctx = dict(ctx, work=dict(ctx["work"], prefill_s=-1.0))
    assert reader(name).read(ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_without_the_recorder(jobs, monkeypatch, name):
    """As on a program that records no spans."""
    ctx, _ = jobs[0]
    monkeypatch.setitem(sys.modules, "repro.trace", None)
    monkeypatch.delattr(sys.modules["repro"], "trace", raising=False)
    assert reader(name).read(ctx) is None
