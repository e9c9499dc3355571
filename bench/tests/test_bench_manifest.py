"""BENCHMARK.json: names and units in their alphabets, every file found by
name, and every per-layer metric moving an end-to-end metric its cells
report."""

import copy

import pytest

from harness import manifest


def test_manifest_is_sound():
    assert manifest.check(manifest.load()) == []


def test_every_cell_resolves():
    m = manifest.load()
    for w in m["workloads"]:
        cell = manifest.resolve(m, w["name"])
        assert cell.per_layer and cell.end_to_end
        assert set(cell.readers) == {p["name"] for p in cell.per_layer}
        for p in cell.per_layer:
            assert p["moves"] in {e["name"] for e in cell.end_to_end}


@pytest.mark.parametrize("edit, complaint", [
    (lambda m: m["per_layer"][0].update(unit="per second"), "bad unit"),
    (lambda m: m["end_to_end"][0].update(name="tok s"), "bad name"),
    (lambda m: m["per_layer"][0].update(name="no_such_metric"), "no reader"),
    (lambda m: m["workloads"][0].update(traffic="no_such_mix"), "no traffic file"),
    (lambda m: m["configs"][0].update(file="bench/configs/none.json"), "no file"),
    (lambda m: m["workloads"].append(dict(m["workloads"][0], name="x")), "appears twice"),
    (lambda m: m["end_to_end"][0].update(workloads=["kanformer-100m.decode"]),
     "does not report"),
    (lambda m: m["per_layer"][0].update(moves="ttft"), "moves unknown"),
])
def test_manifest_faults_are_found(edit, complaint):
    m = copy.deepcopy(manifest.load())
    edit(m)
    errs = manifest.check(m)
    assert any(complaint in e for e in errs), errs
