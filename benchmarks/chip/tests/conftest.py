"""Shared set-up of the benchmark's CPU tests: import paths, and the cells
at the reduced sizes that a test run can hold."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
for path in (ROOT, ROOT / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

SERVE = "qwen1.5-0.5b.serve-spill-1k"
# the program's --reduced model config, in the configuration file's keys
REDUCED = {
    SERVE: {"num_hidden_layers": 2, "hidden_size": 128, "vocab_size": 512,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "head_dim": 32, "intermediate_size": 256},
}
# small jobs
REDUCED_ARGS = {
    SERVE: {"reduced": True, "batch": 2, "prompt-len": 32, "gen": 20},
}


@pytest.fixture(autouse=True)
def _scratch_compile_cache(tmp_path_factory, monkeypatch):
    # the entries keep their compile cache inside the checkout unless told
    # otherwise; keep the tests' CPU programs out of it
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path_factory.getbasetemp() / "jax_cache"))


def spec():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def reduced_cell(name):
    """A harness Cell of ``name`` at the reduced sizes, with its limits."""
    from benchmarks.chip import harness

    entry = next(w for w in spec()["workloads"] if w["name"] == name)
    config = harness.load_json(harness.HERE / "configs"
                               / f"{entry['config']}.json")
    config.update(REDUCED[name])
    cell = harness.load_json(harness.HERE / "workloads" / f"{name}.json")
    cell["args"].update(REDUCED_ARGS[name])
    return harness.Cell(spec(), name, cell=cell, config=config)
