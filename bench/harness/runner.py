"""One run of one cell: set-up, the measured window, the traced round, the
readings, and the correctness check.  ``bench/run.py`` is its command
line; the tests drive :func:`run` directly on the CPU.

The traffic is offline-batch rounds, a closed loop: each round hands
``round_requests`` requests to ``Engine.serve_continuous`` at once, and the
next round starts when it returns.  The window is the whole rounds that
start before ``seconds`` have passed.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import tempfile
import time

import numpy as np

from harness import check, flops, manifest, traffic as traffic_mod
from harness import trace as trace_mod


@dataclasses.dataclass
class RoundResult:
    round: traffic_mod.Round
    outputs: list
    stats: dict
    latency_s: list            # per request, from the round's submission


@dataclasses.dataclass
class RunData:
    """What the per-layer metric readers see."""
    cell: manifest.Cell
    mix: traffic_mod.Mix
    rounds: list               # the window's RoundResults
    window_s: float
    trace: trace_mod.Trace | None
    peaks: dict | None

    def model_flops(self) -> float:
        """The model's operations over the window's requests."""
        m, total = self.mix, 0.0
        for r in self.rounds:
            cached = r.round.cached_prompt_tokens(m.block_size, m.prefix_len)
            for p, n, c in zip(r.round.prompts, r.round.budgets, cached):
                pos, ctx = flops.request_positions(len(p), c, n)
                total += flops.model_flops(self.cell.cfg, self.cell.ref.ffn_flops,
                                           pos, ctx, n)
        return total

    def stat_sum(self, *path) -> float:
        tot = 0.0
        for r in self.rounds:
            v = r.stats
            for k in path:
                v = v[k]
            tot += v
        return tot


def weight_key(seed: int):
    """The weights' PRNG key: every bit of the seed, which may exceed 32."""
    import jax

    magnitude, negative = traffic_mod.seed_words(seed)
    k = jax.random.PRNGKey(magnitude & 0xFFFFFFFF)
    for word in (magnitude >> 32, negative, 1):
        k = jax.random.fold_in(k, word)
    return k


def _programs(eng) -> int:
    return sum(v["traces"] for v in eng.compiles.snapshot().values())


def make_weights(cell: manifest.Cell, seed: int):
    """The configuration's weights from the seed, made on the device in one
    compiled call, in the program's parameter layout and the dtype it
    serves them in (float32)."""
    import jax
    from repro.models import lm

    cfg = cell.cfg
    params = jax.jit(lambda k: cell.serve.to_program(
        cell.ref.init_weights(k, cfg), cfg))(weight_key(seed))
    want = lm.abstract_params(cell.serve.model_config(cfg))
    if (jax.tree.structure(want) != jax.tree.structure(params) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(params)))):
        raise ValueError(f"{cell.config_name}: the weights do not match the "
                         f"program's parameter layout")
    return jax.block_until_ready(params)


def build_engine(cell: manifest.Cell, mix: traffic_mod.Mix, params):
    """The engine as the serving launcher configures it: paged KV pool with
    prefix caching, bf16 activations and cache, greedy, no early stop."""
    from repro.launch import serve as serve_cli
    from repro.serve.engine import Engine, ServeConfig

    return Engine(params, cell.serve.model_config(cell.cfg), ServeConfig(
        max_seq=mix.max_seq(), max_new_tokens=mix.max_output, temperature=0.0,
        eos_id=-1, compute_dtype=serve_cli.SERVED_DTYPE, paged=True,
        block_size=mix.block_size))


def serve_round(eng, mix: traffic_mod.Mix, rnd: traffic_mod.Round,
                seed: int) -> RoundResult:
    t_sub = time.perf_counter()
    outs = eng.serve_continuous(
        rnd.prompts, slots=mix.slots, chunk_steps=mix.chunk_steps,
        seed=int(seed) & 0x7FFFFFFF, max_new=rnd.budgets,
        prompt_pad_multiple=mix.prompt_pad_multiple)
    lead = time.perf_counter() - t_sub - eng.last_serve_stats["wall_s"]
    st = eng.last_serve_stats
    return RoundResult(rnd, outs, st, [lead + x for x in st["request_latency_s"]])


def warm_up(eng, mix, traffic, seed: int) -> None:
    """Serve the warm-up rounds: every program the window uses is traced,
    and compiled or loaded from the compile cache."""
    import jax

    with jax.profiler.TraceAnnotation("bench.warmup"):
        for rnd in traffic.warmup_calls(mix.max_seq()):
            serve_round(eng, mix, rnd, seed)


def window(eng, mix, traffic, seconds: float, seed: int):
    """The measured rounds: every round that starts before ``seconds``
    have passed.  Returns ``(rounds, seconds taken)``."""
    import jax

    t0 = time.perf_counter()
    rounds = []
    while time.perf_counter() < t0 + seconds:
        with jax.profiler.TraceAnnotation("bench.round"):
            rounds.append(serve_round(eng, mix, traffic.next_round(), seed))
    return rounds, time.perf_counter() - t0


def served(rounds, mix) -> list:
    """``(prompt, served tokens, prompt tokens a cached prefix covers)``
    of every request of ``rounds``."""
    return [t for r in rounds for t in zip(
        r.round.prompts, r.outputs,
        r.round.cached_prompt_tokens(mix.block_size, mix.prefix_len))]


def check_sample(requests, mix, seed: int) -> list:
    rng = np.random.default_rng(traffic_mod.seed_words(seed) + [3])
    return check.sample(requests, rng, mix.check_tokens, mix.check_requests)


def failures(rounds: list, vocab: int) -> int:
    """Requests whose output is missing, of the wrong length, or holds a
    token outside the vocabulary."""
    bad = 0
    for r in rounds:
        for o, n in zip(r.outputs, r.round.budgets):
            bad += int(o is None or len(o) != n or not ((o >= 0) & (o < vocab)).all())
    return bad


def run(cell: manifest.Cell, seed: int, seconds: float, traced: bool,
        t_start: float, log=print) -> dict:
    """One run; returns the result line as a dict (``checks`` last)."""
    import jax

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    peaks = flops.peaks(dev.device_kind) if on_chip else None
    mix = traffic_mod.load_mix(manifest.traffic_path(cell.traffic_name), cell.spec)
    vocab = cell.cfg["vocab_size"]
    traffic = traffic_mod.Traffic(mix, vocab, seed)
    eng = build_engine(cell, mix, make_weights(cell, seed))
    warm_up(eng, mix, traffic, seed)
    compiled0 = _programs(eng)

    t0 = time.perf_counter()
    setup_s = t0 - t_start
    rounds, window_s = window(eng, mix, traffic, seconds, seed)
    in_window = _programs(eng) - compiled0
    log(f"[bench] window: {len(rounds)} rounds in {window_s:.3f} s ("
        + ", ".join(f"{r.stats['wall_s']:.3f}" for r in rounds)
        + f" s each); programs compiled in the window: {in_window}")

    tr = None
    if traced:
        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            with jax.profiler.trace(logdir):
                with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                    serve_round(eng, mix, traffic.next_round(), seed)
            tr = trace_mod.load(trace_mod.find_xplane(logdir))
        finally:
            shutil.rmtree(logdir, ignore_errors=True)

    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    data = RunData(cell, mix, rounds, window_s, tr, peaks)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    metrics = {}
    if on_chip:
        if traced:
            for p in cell.per_layer:
                v = cell.readers[p["name"]].read(data)
                if v is not None:
                    metrics[p["name"]] = {"value": v, "unit": p["unit"]}
            device["busy_s"] = tr.busy_s()
            device["window_s"] = tr.window_s
        else:
            lat = [x for r in rounds for x in r.latency_s]
            e2e = {"out_tok_s": sum(sum(r.round.budgets) for r in rounds) / window_s,
                   "req_p95_s": float(np.percentile(lat, 95)),
                   "setup_s": setup_s}
            for e in cell.end_to_end:
                metrics[e["name"]] = {"value": e2e[e["name"]], "unit": e["unit"]}

    # correctness: after the window, with the program's state freed
    requests = served(rounds, mix)
    n_failed = failures(rounds, vocab)
    del eng, rounds, data
    gc.collect()
    samples = check_sample(requests, mix, seed)
    ref = check.Reference(cell.ref, cell.cfg, mix.max_seq(), mix.max_output)
    ref.load(weight_key(seed))
    t_ref = time.perf_counter()
    gap = check.widest_gap(ref, samples)
    log(f"[bench] reference: {len(samples)} requests, "
        f"{sum(len(o) for _, o in samples)} served tokens, "
        f"{time.perf_counter() - t_ref:.1f} s")
    correct, checks = check.verdict(n_failed, gap, cell.limits)
    result = {
        "correct": correct,
        "attempted": len(requests),
        "failed": n_failed,
        "metrics": metrics,
        "device": device,
    }
    if tr is not None and on_chip:
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    result["programs_compiled_in_window"] = in_window
    result["checks"] = checks
    return result
