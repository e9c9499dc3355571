"""Model FLOP/s utilization over the window: the operations the window's
requests need (``harness.flops.model_flops``, counted from the
configuration's shapes and the harness's own traffic, so padding, a
recomputed prefix or a faster implementation does not change the count),
over the window's seconds and the chip's bf16 peak."""


def read(run):
    if not run.peaks or not run.window_s:
        return None
    return 100.0 * run.model_flops() / (run.window_s * run.peaks["bf16_flops_per_s"])
