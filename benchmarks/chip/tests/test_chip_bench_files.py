"""The benchmark's files agree with each other and with BENCHMARK.json."""

import json
import re

import pytest
from conftest import ROOT, spec

from benchmarks.chip import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_config_and_metric_has_its_file():
    s = spec()
    for cfg in s["configs"]:
        data = json.loads((ROOT / cfg["file"]).read_text())
        assert sorted(data["reduced"]) == sorted(cfg["reduced"]), cfg["name"]
        assert data["source"] == cfg["source"], cfg["name"]
        assert (harness.HERE / "reference"
                / f"{data['reference']}.py").is_file()
    for w in s["workloads"]:
        cell = harness.load_json(harness.HERE / "workloads"
                                 / f"{w['name']}.json")
        assert (harness.HERE / "entries" / f"{cell['entry']}.py").is_file()
        assert cell["limits"], w["name"]
    for m in s["per_layer"]:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()


def test_names_and_cross_references():
    s = spec()
    cells = {w["name"] for w in s["workloads"]}
    configs = {c["name"] for c in s["configs"]}
    e2e = {m["name"] for m in s["end_to_end"]}
    assert "setup_s" in e2e
    for w in s["workloads"]:
        assert w["config"] in configs
    for m in s["end_to_end"] + s["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert set(m.get("workloads", cells)) <= cells, m["name"]
    for m in s["per_layer"]:
        assert m["moves"] in e2e
        # the end-to-end metric it moves is reported in each of its cells
        moved = next(x for x in s["end_to_end"] if x["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for cell in cells:
        assert NAME.match(cell)
        assert len(harness.cell_metrics(s, cell, "end_to_end")) >= 2
        assert harness.cell_metrics(s, cell, "per_layer")


def test_configuration_files_match_the_program_configs():
    from repro.configs import get_config

    from benchmarks.chip.entries.common import config_mismatch

    for cfg in spec()["configs"]:
        data = json.loads((ROOT / cfg["file"]).read_text())
        assert config_mismatch(get_config(data["arch"]), data) == []


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_reference_weights_are_the_programs(seed):
    """The reference draws from the seed the very weights the program
    initialises (reduced sizes), without taking them from it."""
    import jax
    import numpy as np
    from conftest import SERVE, reduced_cell
    from repro.configs import get_reduced
    from repro.models import init_stack

    c = reduced_cell(SERVE)
    params, _ = init_stack(jax.random.key(seed),
                           get_reduced(c.config["arch"]))
    w = c.reference.init_weights(c.config, jax.random.key(seed))
    pairs = [(params["embed"], w["embed"]), (params["unembed"], w["unembed"]),
             (params["final_norm"], w["final_norm"])]
    attn, mlp = params["blocks"]["attn"], params["blocks"]["mlp"]
    pairs += [(attn[k], w["layers"][k]) for k in ("wq", "wk", "wv", "wo")]
    pairs += [(mlp["wi"], w["layers"]["wi"]), (mlp["wg"], w["layers"]["wg"]),
              (mlp["wo"], w["layers"]["wf"])]
    for a, b in pairs:
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_run_without_a_tpu_fails_and_prints_no_result():
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.chip.run", "--workload",
         "qwen1.5-0.5b.serve-spill-1k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
