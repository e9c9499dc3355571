"""Sparse N:M kernel tests (PR acceptance criteria):

* ``kan_sparse_gemm`` matches the fused path / dense oracle within dtype
  tolerance on ragged (non-tile-multiple) shapes, fp32 and bf16, with and
  without the base term — one ``pallas_call`` per layer;
* the sparse int8 kernel is bit-identical to the dense-band int8 kernel;
* ``resolve_inference_method`` never picks sparse on TPU;
* the autotuner knows the sparse kernels (per-kernel candidate spaces) and
  the cache survives corruption, mutation, and concurrent writers.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kan_layer as kl
from repro.core import quantization as q
from repro.core.bspline import SplineGrid


def _layer(G, P, K, N, seed=0, base=True, dtype=jnp.float32):
    g = SplineGrid(-1.0, 1.0, G, P)
    cfg = kl.KANLayerConfig(K, N, g, base=base)
    params = kl.init_kan_layer(jax.random.PRNGKey(seed), cfg, dtype)
    return g, params


class TestSparseMatchesFused:
    # ragged shapes on purpose (the kernel pads internally); includes the
    # decode shapes (BS <= 8) the kernel is for
    SHAPES = [(5, 3, 40, 24, 1), (5, 3, 40, 24, 8), (5, 3, 100, 37, 5),
              (3, 2, 33, 5, 7), (10, 3, 17, 20, 3), (3, 3, 1, 22, 9),
              (2, 1, 9, 11, 16)]

    @pytest.mark.parametrize("G,P,K,N,BS", SHAPES)
    def test_sparse_matches_dense_fp32(self, G, P, K, N, BS):
        g, params = _layer(G, P, K, N)
        x = jnp.asarray(
            np.random.RandomState(BS + K).uniform(-1, 1, (BS, K)).astype(np.float32)
        )
        a = kl.kan_layer_apply(params, x, g, "dense")
        b = kl.kan_layer_apply(params, x, g, "sparse")
        c = kl.kan_layer_apply(params, x, g, "fused")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
        # sparse vs fused: same basis values, same fp32 accumulation — the
        # two kernels differ only in skipping the zero MACs
        np.testing.assert_allclose(np.asarray(b), np.asarray(c),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("G,P,K,N,BS", SHAPES[:3])
    def test_sparse_matches_dense_bf16(self, G, P, K, N, BS):
        g, params = _layer(G, P, K, N)
        x32 = jnp.asarray(
            np.random.RandomState(BS).uniform(-1, 1, (BS, K)).astype(np.float32)
        )
        ref = kl.kan_layer_apply(params, x32, g, "dense")
        p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        got = kl.kan_layer_apply(p16, x32.astype(jnp.bfloat16), g, "sparse")
        scale = float(jnp.abs(ref).max()) + 1e-9
        err = float(jnp.abs(got.astype(jnp.float32) - ref).max()) / scale
        assert err < 2e-2, err

    def test_sparse_without_base(self):
        g, params = _layer(5, 3, 24, 16, base=False)
        assert "base_w" not in params
        x = jnp.asarray(
            np.random.RandomState(1).uniform(-1, 1, (6, 24)).astype(np.float32)
        )
        a = kl.kan_layer_apply(params, x, g, "dense")
        b = kl.kan_layer_apply(params, x, g, "sparse")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)

    def test_single_pallas_call(self):
        """Spline + base in ONE kernel for the sparse datapath too."""
        g, params = _layer(5, 3, 24, 16)
        x = jnp.zeros((8, 24), jnp.float32)
        jaxpr = str(jax.make_jaxpr(
            lambda p, x: kl.kan_layer_apply(p, x, g, "sparse")
        )(params, x))
        assert jaxpr.count("pallas_call") == 1, jaxpr.count("pallas_call")

    def test_explicit_tiles_win(self):
        """Pinned bb/bn/bk bypass the autotuner (kernel unit-test contract)."""
        from repro.kernels import ops as kops

        g, params = _layer(5, 3, 16, 12)
        x = jnp.asarray(
            np.random.RandomState(2).uniform(-1, 1, (5, 16)).astype(np.float32)
        )
        a = kops.kan_sparse_gemm(x, params["coeff"], g,
                                 base_w=params["base_w"], bb=8, bn=8, bk=4)
        b = kl.kan_layer_apply(params, x, g, "dense")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


class TestSparseInt8:
    @pytest.mark.parametrize("G,P,BS,K,N", [(5, 3, 8, 24, 16),
                                            (5, 3, 33, 10, 7),
                                            (3, 2, 1, 5, 9)])
    def test_bit_identical_to_dense_band(self, G, P, BS, K, N):
        """Same integer address math, same ROM values, same int32
        accumulator — only the zero multiplies are skipped."""
        from repro.kernels import ops as kops

        g = SplineGrid(-1.0, 1.0, G, P)
        rs = np.random.RandomState(BS)
        x = jnp.asarray(rs.uniform(-1.4, 1.4, (BS, K)).astype(np.float32))
        qg = q.QuantizedGrid.make(g)
        x_q = qg.x_quant.quantize(x)
        lut_u8 = jnp.asarray(q.build_lut_u8(P, 256))
        cq = jnp.asarray(rs.randint(-127, 128, (K, g.n_basis, N)).astype(np.int8))
        a = kops.kan_int8_gemm(x_q, lut_u8, cq, g, bb=8, bn=8, bk=4)
        b = kops.kan_sparse_int8_gemm(x_q, lut_u8, cq, g, bb=8, bn=8, bk=4)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_fused_dequant_epilogue(self):
        """scale given: dequantised out_dtype emitted straight from the
        kernel, matching the dense-band kernel's epilogue."""
        from repro.kernels import ops as kops

        g = SplineGrid(-1.0, 1.0, 5, 3)
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.uniform(-1, 1, (4, 10)).astype(np.float32))
        qg = q.QuantizedGrid.make(g)
        x_q = qg.x_quant.quantize(x)
        lut_u8 = jnp.asarray(q.build_lut_u8(g.P, 256))
        cq = jnp.asarray(rs.randint(-127, 128, (10, g.n_basis, 6)).astype(np.int8))
        scale = jnp.asarray(rs.uniform(0.5, 2.0, (6,)).astype(np.float32))
        a = kops.kan_int8_gemm(x_q, lut_u8, cq, g, scale=scale,
                               bb=8, bn=8, bk=4, out_dtype=jnp.bfloat16)
        b = kops.kan_sparse_int8_gemm(x_q, lut_u8, cq, g, scale=scale,
                                      bb=8, bn=8, bk=4, out_dtype=jnp.bfloat16)
        assert b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestMethodResolution:
    def test_sparse_at_decode_rows_on_tpu(self):
        """The sparse kernel does not lower to Mosaic, so decode rows on a
        TPU take the fused kernel like every other row count."""
        assert kl.resolve_inference_method("tpu") == "fused"
        assert kl.resolve_inference_method("cpu") == "compact"

    def test_threshold_env_override(self, monkeypatch):
        monkeypatch.setenv("KAN_SAS_INFERENCE_METHOD", "compact")
        assert kl.resolve_inference_method("tpu") == "compact"
        monkeypatch.setenv("KAN_SAS_INFERENCE_METHOD", "fused")
        assert kl.resolve_inference_method("cpu") == "fused"
        monkeypatch.setenv("KAN_SAS_INFERENCE_METHOD", "sparse")
        assert kl.resolve_inference_method("cpu") == "sparse"
        with pytest.raises(ValueError, match="not compiled for the TPU"):
            kl.resolve_inference_method("tpu")

    def test_auto_uses_row_count(self, monkeypatch):
        """kan_layer_apply('auto') resolves per backend; the env override
        runs the sparse kernel in interpret mode off-TPU."""
        g, params = _layer(5, 3, 8, 6)
        x = jnp.zeros((4, 8), jnp.float32)
        y = kl.kan_layer_apply(params, x, g, "auto")   # cpu -> compact
        np.testing.assert_allclose(
            np.asarray(y),
            np.asarray(kl.kan_layer_apply(params, x, g, "dense")),
            atol=1e-5,
        )
        monkeypatch.setenv("KAN_SAS_INFERENCE_METHOD", "sparse")
        y2 = kl.kan_layer_apply(params, x, g, "auto")  # forced sparse kernel
        np.testing.assert_allclose(np.asarray(y2), np.asarray(y), atol=1e-5)


class TestAutotuneSparse:
    def test_sparse_candidate_space_wider_bk(self):
        from repro.kernels import autotune as tune

        dense = tune.candidate_tiles("fused", 8, 256, 256, 8, backend="cpu")
        sparse = tune.candidate_tiles("sparse", 8, 256, 256, 8,
                                      backend="cpu", nnz=4)
        assert max(bk for _, _, bk in dense) * 8 <= 1024
        assert max(bk for _, _, bk in sparse) * 4 <= 1024
        assert max(bk for _, _, bk in sparse) > max(bk for _, _, bk in dense)
        # sparse candidates are decode-shaped: batch tile stays small
        assert max(bb for bb, _, _ in sparse) <= 32

    def test_sparse_defaults_and_heuristic(self):
        from repro.kernels import autotune as tune

        bb, bn, bk = tune.get_tiles("sparse", 8, 256, 256, 8,
                                    jnp.float32, "cpu", nnz=4)
        assert bb <= 32 and bk * 4 <= 1024
        # tiny problems stay clamped
        bb, bn, bk = tune.get_tiles("sparse", 3, 5, 7, 8,
                                    jnp.float32, "cpu", nnz=4)
        assert bb <= 8 and bk <= 5
        # the decode-shaped DEFAULTS are clamped to the problem too: small
        # K must not pad to the table's bk, nor bn beyond N
        bb, bn, bk = tune.get_tiles("sparse", 8, 16, 128, 8,
                                    jnp.float32, "cpu", nnz=4)
        assert bk <= 16 and bn <= 128

    def test_corrupt_cache_falls_back(self, tmp_path, monkeypatch):
        from repro.kernels import autotune as tune

        path = tmp_path / "at.json"
        monkeypatch.setenv(tune.CACHE_ENV, str(path))
        path.write_text("{ this is not json")
        tiles = tune.get_tiles("fused", 64, 16, 32, 8, jnp.float32, "cpu")
        assert len(tiles) == 3 and all(t > 0 for t in tiles)
        # malformed entry schema also falls through to defaults
        key = tune.problem_key("fused", 64, 16, 32, 8, jnp.float32, "cpu")
        path.write_text(json.dumps({key: {"tiles": "nope"}}))
        tiles = tune.get_tiles("fused", 64, 16, 32, 8, jnp.float32, "cpu")
        assert len(tiles) == 3 and all(t > 0 for t in tiles)

    def test_load_cache_returns_copies(self, tmp_path, monkeypatch):
        from repro.kernels import autotune as tune

        monkeypatch.setenv(tune.CACHE_ENV, str(tmp_path / "at.json"))
        key = tune.problem_key("fused", 8, 8, 8, 8, jnp.float32, "cpu")
        tune._save_cache({key: {"tiles": [8, 8, 4], "us": 1.0}})
        first = tune._load_cache()
        first[key]["tiles"] = [999, 999, 999]   # mutate the returned dict
        first["junk"] = 1
        # a later reader must see the on-disk truth, not the mutation
        assert tune._load_cache()[key]["tiles"] == [8, 8, 4]
        assert "junk" not in tune._load_cache()
        assert tune.get_tiles("fused", 8, 8, 8, 8, jnp.float32, "cpu") == (8, 8, 4)

    def test_atomic_write_unique_tmp(self, tmp_path, monkeypatch):
        """Two interleaved writers must never tear the file: each write goes
        through its own temp file + os.replace, so the survivor is one
        complete JSON document."""
        from repro.kernels import autotune as tune

        path = tmp_path / "at.json"
        monkeypatch.setenv(tune.CACHE_ENV, str(path))
        a = {"a": {"tiles": [1, 2, 3], "us": 1.0}}
        b = {"b": {"tiles": [4, 5, 6], "us": 2.0}}
        tune._save_cache(a)
        tune._save_cache(b)
        on_disk = json.loads(path.read_text())
        assert on_disk == b
        # no stray temp files left behind
        leftovers = [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]
        assert leftovers == []

    def test_autotune_records_sparse_winner(self, tmp_path, monkeypatch):
        from repro.kernels import autotune as tune
        from repro.kernels import ops as kops

        monkeypatch.setenv(tune.CACHE_ENV, str(tmp_path / "at.json"))
        g = SplineGrid(-1.0, 1.0, 5, 3)
        params = kl.init_kan_layer(
            jax.random.PRNGKey(0), kl.KANLayerConfig(16, 32, g)
        )
        x = jnp.asarray(
            np.random.RandomState(0).uniform(-1, 1, (8, 16)).astype(np.float32)
        )
        rep = tune.autotune(
            "sparse",
            lambda bb, bn, bk: kops.kan_sparse_gemm(
                x, params["coeff"], g, base_w=params["base_w"],
                bb=bb, bn=bn, bk=bk,
            ),
            8, 16, 32, g.n_basis, iters=1,
            candidates=[(8, 32, 8), (8, 32, 16)], nnz=g.n_nonzero,
        )
        assert tuple(rep["tiles"]) in {(8, 32, 8), (8, 32, 16)}
        assert tune.get_tiles(
            "sparse", 8, 16, 32, g.n_basis, x.dtype, jax.default_backend()
        ) == tuple(rep["tiles"])
