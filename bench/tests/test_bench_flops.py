"""The counts of operations and bytes, against numbers worked out by hand
for kanformer-100m and qwen1.5-0.5b."""

import json
import os

import pytest

from harness import flops, manifest, runner, traffic as T

CONFIGS = os.path.join(manifest.BENCH, "configs")


def config(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        cfg = json.load(f)
    ref = manifest.load_module(os.path.join(CONFIGS, f"{name}.ref.py"), f"r_{name}")
    return cfg, ref


def test_request_positions():
    # prompt 100, nothing cached, 10 tokens out: positions 0..108
    assert flops.request_positions(100, 0, 10) == (109, 109 * 110 // 2)
    # 64 of 100 prompt tokens cached, 1 token out: positions 64..99
    assert flops.request_positions(100, 64, 1) == (36, sum(range(65, 101)))


@pytest.mark.parametrize("name, per_layer, head, attn_per_ctx", [
    # kanformer: QKV 2·512·(8+16)·64 + out 2·8·64·512 = 2,097,152;
    # KAN 2 layers · 2·512·(5+3+1)·1024 = 18,874,368; head 2·512·32000
    ("kanformer-100m", 2_097_152 + 18_874_368, 32_768_000, 4 * 8 * 64),
    # qwen: QKV 2·1024·48·64 + out 2·16·64·1024 = 8,388,608;
    # SwiGLU 3·2·1024·2816 = 17,301,504; head 2·1024·151936
    ("qwen1.5-0.5b", 8_388_608 + 17_301_504, 311_164_928, 4 * 16 * 64),
])
def test_model_flops(name, per_layer, head, attn_per_ctx):
    cfg, ref = config(name)
    L = cfg["num_hidden_layers"]
    pos, ctx = flops.request_positions(100, 0, 10)
    want = pos * L * per_layer + L * attn_per_ctx * ctx + 10 * head
    assert flops.model_flops(cfg, ref, pos, ctx, 10) == want


@pytest.mark.parametrize("name, seed, want", [
    # one round of each cell at two seeds, to the operation: what mfu
    # divides by: for these two configurations, one GQA layer and the
    # FFN times the layers, and the head
    ("kanformer-100m.prefill", 2**31 + 7, 33_246_628_167_680),
    ("kanformer-100m.prefill", 5, 33_246_322_819_072),
    ("kanformer-100m.decode", 2**31 + 7, 7_521_871_101_952),
    ("kanformer-100m.decode", 5, 7_522_585_346_048),
    ("qwen1.5-0.5b.decode", 2**31 + 7, 66_719_231_770_624),
    ("qwen1.5-0.5b.decode", 5, 66_718_019_584_000),
])
def test_model_flops_of_a_cells_round(name, seed, want):
    cell = manifest.resolve(manifest.load(), name)
    mix = T.load_mix(manifest.traffic_path(cell.traffic_name), cell.spec)
    rnd = T.Traffic(mix, cell.cfg["vocab_size"], seed).next_round()
    data = runner.RunData(cell, mix, [runner.RoundResult(rnd, [], {}, [])], 1.0, None, None)
    assert data.model_flops() == float(want)


def test_kanformer_fused_kernel_counts():
    """Prefill rows are bound by compute, decode rows by bytes."""
    peaks = flops.peaks("TPU v5 lite")
    reader = manifest.load_module(manifest.metric_path("kan_roofline"), "kr")

    def call(rows):
        return [("bf16", (rows, 1024)), ("bf16", (rows, 512)),
                ("f32", (512, 8, 1024)), ("f32", (512, 1024))]

    # 4096 rows: 2·4096·512·9·1024 ops; 4 + 16 + 2 + 8 MiB
    ops, nbytes = 2 * 4096 * 512 * 9 * 1024, 31_457_280
    assert reader.least_time(call(4096), peaks) == pytest.approx(ops / 197e12)
    assert ops / 197e12 > nbytes / 819e9
    # 64 rows: 2·64·512·9·1024 ops; 64 KiB + 16 MiB + 2 MiB + 128 KiB
    ops, nbytes = 2 * 64 * 512 * 9 * 1024, 19_070_976
    assert reader.least_time(call(64), peaks) == pytest.approx(nbytes / 819e9)
    assert ops / 197e12 < nbytes / 819e9


def test_gather_counts():
    peaks = flops.peaks("TPU v5 lite")
    reader = manifest.load_module(manifest.metric_path("gather_roofline"), "gr")
    # 16 slots x 97 blocks of 16 tokens x 8 heads x 64, bf16, for 8 layers
    out = ("bf16", (8, 16, 97, 16, 8, 64))
    table = ("s32", (16, 97))
    pool = ("bf16", (8, 1553, 16, 8, 64))
    nbytes = 16 * 97 * 4 + 2 * 8 * 16 * 97 * 16 * 8 * 64 * 2
    assert reader.least_time([out, table, pool], peaks) == pytest.approx(nbytes / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v99")
