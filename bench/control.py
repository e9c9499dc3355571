#!/usr/bin/env python3
"""Readings that the limit of a cell's correctness check is set from.

    python3 bench/control.py --workload <cell> --rounds <r> --seeds <n> [<n> ...]
                             [--fault token|kan_plane|kan_zero]

For each seed, in one process: the cell's weights and traffic from the
seed, ``rounds`` of the cell's own rounds through the engine (a fixed
number, so that the sample does not depend on how fast they run), and the
same sample of finished requests that a run checks.  On that sample it
reads the program's widest gap (what a run compares with its limit) and
the control's: the plain reference computed in float8, put in the
program's place, and the gap of the tokens it ranks first.  Each goes
through the cell's own verdict (``check.verdict``): the program's has to
come out correct, the control's not.  With ``--fault`` the program runs
with that fault planted (``harness/faults.py``), and its verdict has to
come out not correct.  One JSON line per seed on standard output.  The
benchmark's own runs do not run this.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import check, faults, manifest, runner, traffic as traffic_mod  # noqa: E402


def readings(cell, seeds, rounds: int, log=print):
    """Yield ``{"seed", "program_gap", "program_correct", "control_gap",
    "control_correct", ...}`` per seed.  One engine serves every seed; its
    weights are replaced between seeds."""
    mix = traffic_mod.load_mix(manifest.traffic_path(cell.traffic_name), cell.spec)
    vocab = cell.cfg["vocab_size"]
    ref = check.Reference(cell.ref, cell.cfg, mix.max_seq(), mix.max_output)
    eng = None
    for seed in seeds:
        t0 = time.perf_counter()
        ref.w = None
        params = runner.make_weights(cell, seed)
        traffic = traffic_mod.Traffic(mix, vocab, seed)
        if eng is None:
            eng = runner.build_engine(cell, mix, params)
            runner.warm_up(eng, mix, traffic, seed)
        else:
            eng.params = params
        done = [runner.serve_round(eng, mix, traffic.next_round(), seed)
                for _ in range(rounds)]
        failed = runner.failures(done, vocab)
        samples = runner.check_sample(runner.served(done, mix), mix, seed)
        ref.load(runner.weight_key(seed))
        program_gap = check.widest_gap(ref, samples)
        control_gap = check.control_gap(ref, samples)
        row = {"seed": seed, "rounds": rounds, "failed": failed,
               "sample_requests": len(samples), "sample_tokens": sum(len(o) for _, o in samples),
               "program_gap": program_gap,
               "program_correct": check.verdict(failed, program_gap, cell.limits)[0],
               "control_gap": control_gap,
               "control_correct": check.verdict(0, control_gap, cell.limits)[0],
               "limit": cell.limits.get("logit_gap"),
               "seconds": time.perf_counter() - t0}
        log(json.dumps(row))
        yield row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rounds", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    cell = manifest.resolve(manifest.load(), args.workload)

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(manifest.ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("[control] needs a TPU", file=sys.stderr)
        return 3
    with faults.planted(args.fault):
        for _ in readings(cell, args.seeds, args.rounds,
                          log=lambda s: print(s, flush=True)):
            pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
