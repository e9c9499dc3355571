"""``chip_smoke.py`` off the chip: it refuses the CPU, and its phases run
end to end at the reduced kanformer size with the fused kernel in
interpret mode (the rehearsal of the one-chip run)."""

import numpy as np
import pytest

import chip_smoke
from conftest import run_jax_subprocess


def test_chip_smoke_refuses_cpu():
    proc = run_jax_subprocess(argv=["chip_smoke.py"], timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_chip_smoke_alone_fails(tmp_path):
    """Copied out of the checkout, the script finds no ``repro`` and fails."""
    import shutil
    import subprocess
    import sys

    shutil.copy(chip_smoke.__file__, tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, capture_output=True,
        text=True, timeout=300, env={"PATH": "/usr/bin:/bin"},
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


# The smoke's request pattern at the reduced size: a full first wave, a
# second wave behind one cached 16-token prefix block.
REDUCED = chip_smoke.Workload(
    full=False, prompt_lens=(40, 37, 39, 35, 21, 23, 19, 24), prefix_len=16,
    max_new=8)


@pytest.fixture(scope="module")
def served():
    mp = pytest.MonkeyPatch()
    mp.setenv("KAN_SAS_INFERENCE_METHOD", "fused")
    try:
        args, model, prompts = chip_smoke.setup(REDUCED, None, seed=0)
        eng, outs = chip_smoke.serve_phase(args, model, prompts)
    finally:
        mp.undo()
    return eng, model, prompts, outs


def test_chip_smoke_serve_phase_matches_fp32_reference(served):
    eng, model, prompts, outs = served
    rep = chip_smoke.reference_check(eng.params, model, prompts, outs)
    assert rep["finite"]
    assert rep["positions"] == 8 * 8
    assert rep["decided"] * 4 >= rep["positions"], rep
    assert rep["mismatches_decided"] == 0, rep


def test_ffn_check_holds_the_fused_kernel_to_its_bound(served, monkeypatch):
    """The smoke's FFN check at the reduced size, fused kernel in interpret
    mode: every layer inside the bf16 bound, every control outside it."""
    monkeypatch.setenv("KAN_SAS_INFERENCE_METHOD", "fused")
    eng, model = served[:2]
    rows = chip_smoke.ffn_rows(REDUCED)
    rep = chip_smoke.ffn_check(model, eng.params, rows, seed=0)
    assert rep["up"] <= 1 and rep["down"] <= 1, rep
    assert set(rep["controls"]) == {"wrong plane up", "wrong plane down",
                                    "zeroed FFN"}
    assert min(rep["controls"].values()) > 1, rep


def test_kan_layer_bound_rejects_errors_past_it():
    """An error of 1.5 bounds in one element fails; the fp32 reference
    itself passes."""
    import jax
    import jax.numpy as jnp

    from repro.core.bspline import SplineGrid

    grid = SplineGrid(-1.0, 1.0, 5, 3)
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    coeff = 0.02 * jax.random.normal(k[0], (32, grid.n_basis, 16))
    base_w = 0.02 * jax.random.normal(k[1], (32, 16))
    x = jnp.tanh(jax.random.normal(k[2], (8, 32))).astype(jnp.bfloat16)
    y_ref, bound = chip_smoke.kan_layer_reference(coeff, base_w, x, grid)
    assert chip_smoke.bound_ratio(y_ref, (y_ref, bound)) == 0.0
    bad = y_ref.at[3, 5].add(1.5 * bound[3, 5])
    assert chip_smoke.bound_ratio(bad, (y_ref, bound)) == pytest.approx(1.5, rel=1e-3)
    _, split = chip_smoke.kan_layer_reference(coeff, base_w, x, grid, parts=2)
    assert (split > bound).all()


def test_gather_check_on_the_served_pool(served, capsys):
    eng, model = served[:2]
    chip_smoke.gather_check(eng, model, seed=0)
    assert "equal to jnp.take True" in capsys.readouterr().out


def test_engine_lowered_programs_compile(served):
    """``Engine.lowered`` re-lowers each traced program from its abstract
    arguments; the decode chunk is one program however often it ran."""
    eng = served[0]
    lows = eng.lowered("decode_chunk")
    assert len(lows) == 1
    assert "HloModule" in lows[0].compile().as_text()
    assert len(eng.lowered("prefill_pages")) == eng.compiles.programs(
        "prefill_pages")


def test_four_chip_phase_on_four_cpu_devices():
    """The --four-chips phase (2x2 mesh vs 1x1, kernels under shard_map)
    on four virtual CPU devices, fused kernel in interpret mode."""
    code = (
        "import chip_smoke, test_chip_smoke as t\n"
        "chip_smoke.four_chips(0, t.REDUCED)\n"
        "print('FOUR_OK')\n"
    )
    proc = run_jax_subprocess(
        code, devices=4, timeout=600,
        env_extra={"KAN_SAS_INFERENCE_METHOD": "fused",
                   "PYTHONPATH": "src:.:tests"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "FOUR_OK" in proc.stdout
    assert "decided prefixes equal" in proc.stdout
    assert "dropped psum" in proc.stdout
    assert "paged gather" in proc.stdout


def test_logit_tolerance_scales_with_spread_and_depth():
    rs = np.random.RandomState(0)
    logits = rs.normal(size=(2, 3, 100))
    t1 = chip_smoke.logit_tolerance(logits, 1)
    t2 = chip_smoke.logit_tolerance(2 * logits, 1)
    np.testing.assert_allclose(t2, 2 * t1)
    assert (chip_smoke.logit_tolerance(logits, 8) > t1).all()
