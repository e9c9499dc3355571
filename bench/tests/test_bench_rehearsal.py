"""A run of each cell's path on the CPU at a reduced size (the harness's
look for a chip skipped): it completes, checks correct, and writes no
device metric.  With the timed path broken underneath (``harness/
faults.py``), ``correct`` comes out false; and the float8 control, through
the same verdict, comes out not correct where the program comes out
correct."""

import json
import time

import pytest

import control
from harness import faults, manifest, runner

SMALL = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 512}
TRAFFIC = {
    "prefill": {"prefixes": {"count": 2, "length": 32, "popularity": [0.7, 0.3]},
                "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8, "max": 40},
                "output": {"dist": "uniform", "min": 2, "max": 9}},
    "decode": {"prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 32},
               "output": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 24}},
}
# At these widths, with a check of 96 tokens from 8 requests, on seeds 3,
# 2**31 + 5 and 11-20, after 1 round and after 4, both cells: the bf16
# program's widest gap read 0-0.0435 and the float8 control's 0.115-0.593.
# A check of 32 tokens from 4 requests was too small: the control read
# 0.0 on two seeds after 4 rounds, 0.057 on one after 1.
LIMIT = 0.07


@pytest.fixture(autouse=True)
def small_mixes(tmp_path, monkeypatch):
    """The mixes at the reduced size, found by name as the harness finds
    them."""
    for name, mix in TRAFFIC.items():
        (tmp_path / f"{name}.json").write_text(json.dumps(mix))
    monkeypatch.setattr(manifest, "traffic_path",
                        lambda name: str(tmp_path / f"{name}.json"))


def with_cell(m, name):
    """``m`` with cell ``<config>.<traffic>`` in it, for a cell whose files
    exist whether or not the manifest lists it yet."""
    config, traffic = name.rsplit(".", 1)
    if config not in {c["name"] for c in m["configs"]}:
        m["configs"].append({"name": config, "source": "-", "reduced": [],
                             "file": f"bench/configs/{config}.json", "why": "-"})
    if name not in {w["name"] for w in m["workloads"]}:
        m["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": 1, "why": "-"})
    return m


def small_cell(name):
    cell = manifest.resolve(with_cell(manifest.load(), name), name)
    cell.cfg.update(SMALL)
    if "kan_intermediate_size" in cell.cfg:
        cell.cfg.update(kan_intermediate_size=96, embedding_multiplier=8.0)
    else:
        # at these widths a larger embedding would decide every token alone
        cell.cfg.update(intermediate_size=128,
                        init=dict(cell.cfg["init"], embed_std=0.1, final_norm=1.0))
    cell.spec = {
        "serve": {"round_requests": 8, "slots": 4, "chunk_steps": 8,
                  "block_size": 16, "prompt_pad_multiple": 32},
        "check": {"tokens": 96, "requests": 8},
        "limits": {"logit_gap": LIMIT}}
    return cell


def run(cell, seed=2**31 + 17, seconds=1.0):
    return runner.run(cell, seed, seconds, False, time.perf_counter(), log=lambda s: None)


@pytest.mark.parametrize("name", ["kanformer-100m.prefill", "qwen1.5-0.5b.decode"])
def test_rehearsal(name):
    res = run(small_cell(name))
    assert res["correct"], res
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["metrics"] == {}          # a CPU run writes no device metric
    assert res["programs_compiled_in_window"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["logit_gap"]["value"] <= LIMIT


def test_altered_token_fails():
    """A token altered where the engine produces it."""
    with faults.planted("token"):
        res = run(small_cell("kanformer-100m.decode"))
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > LIMIT


@pytest.mark.parametrize("fault", ["kan_plane", "kan_zero"])
def test_kan_fault_fails(fault):
    """The KAN FFN broken where the timed path runs it."""
    with faults.planted(fault):
        res = run(small_cell("kanformer-100m.prefill"))
    assert not res["correct"]
    assert res["failed"] == 0 and res["checks"]["logit_gap"]["value"] > LIMIT


def test_short_output_fails(monkeypatch):
    """A request served fewer tokens than its budget."""
    from repro.serve.engine import Engine

    serve = Engine.serve_continuous

    def short(self, requests, **kw):
        outs = serve(self, requests, **kw)
        return [outs[0][:-1]] + outs[1:]

    monkeypatch.setattr(Engine, "serve_continuous", short)
    res = run(small_cell("kanformer-100m.prefill"))
    assert not res["correct"] and res["failed"] > 0


@pytest.mark.parametrize("rounds", [1, 4])
@pytest.mark.parametrize("name", ["kanformer-100m.prefill", "qwen1.5-0.5b.decode"])
def test_control_reads_above_the_program(name, rounds):
    """A fixed number of rounds per seed, so that the sample is the same on
    every run whatever the machine's load."""
    rows = list(control.readings(small_cell(name), [3, 2**31 + 5], rounds,
                                 log=lambda s: None))
    for r in rows:
        assert r["failed"] == 0
        assert r["program_gap"] <= LIMIT, r
        assert r["control_gap"] > 3 * max(r["program_gap"], 1e-3), r
        assert r["program_correct"] and not r["control_correct"], r
