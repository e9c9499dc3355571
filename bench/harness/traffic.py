"""Offline-batch traffic from a mix file: one generator reads every mix.

A mix (``bench/traffic/<name>.json``) gives the distributions of prompt and
output lengths and the shared prefixes with their popularity, and nothing
else.  How a cell serves it (requests per round, slots, chunk steps, block
size, the prompt padding multiple) and how many served tokens its check
reads are the cell's own, in ``bench/cells/<workload>.json``.

Every round holds the same multiset of sizes: the lengths are the
distribution's quantiles at ``(i + 0.5) / n`` for ``i < n`` (a stratified
draw), and each prefix is used a fixed number of times.  The seed decides
the order of the sizes, which sizes go together, which prefix a request
gets, and every token.  So two seeds do the same amount of work, in a
different order, and a run's numbers do not move with the sizes a seed
happened to draw.
"""

from __future__ import annotations

import dataclasses
import json
from statistics import NormalDist

import numpy as np

MIX_KEYS = ("about", "prefixes", "prompt", "output")
SERVE_KEYS = ("round_requests", "slots", "chunk_steps", "block_size",
              "prompt_pad_multiple")
CHECK_KEYS = ("tokens", "requests")


@dataclasses.dataclass(frozen=True)
class Mix:
    round_requests: int
    slots: int
    chunk_steps: int
    block_size: int
    prompt_pad_multiple: int
    prefixes: dict | None
    prompt: dict
    output: dict
    check_tokens: int
    check_requests: int

    @property
    def prefix_len(self) -> int:
        return int(self.prefixes["length"]) if self.prefixes else 0

    @property
    def max_prompt(self) -> int:
        return self.prefix_len + int(self.prompt["max"])

    @property
    def min_prompt(self) -> int:
        return self.prefix_len + int(self.prompt["min"])

    @property
    def max_output(self) -> int:
        return int(self.output["max"])

    def max_seq(self) -> int:
        """The engine's context, as the serving launcher sizes it: the
        longest prompt plus the largest budget plus 8, in whole blocks."""
        n = self.max_prompt + self.max_output + 8
        return -(-n // self.block_size) * self.block_size


def load_mix(path: str, cell: dict) -> Mix:
    """The mix at ``path`` as the cell file ``cell`` serves and checks it:
    its ``serve`` holds exactly :data:`SERVE_KEYS`, its ``check`` exactly
    :data:`CHECK_KEYS`."""
    with open(path) as f:
        d = json.load(f)
    extra = set(d) - set(MIX_KEYS)
    if extra:
        raise ValueError(f"{path}: not a part of a traffic mix: {sorted(extra)}")
    serve, chk = cell["serve"], cell["check"]
    if set(serve) != set(SERVE_KEYS) or set(chk) != set(CHECK_KEYS):
        raise ValueError(f"a cell's serve keys are {SERVE_KEYS} and its check "
                         f"keys {CHECK_KEYS}; got {sorted(serve)}, {sorted(chk)}")
    return Mix(prefixes=d.get("prefixes"), prompt=d["prompt"], output=d["output"],
               check_tokens=int(chk["tokens"]), check_requests=int(chk["requests"]),
               **{k: int(serve[k]) for k in SERVE_KEYS})


def stratified(spec: dict, n: int) -> np.ndarray:
    """``n`` integer sizes at the quantiles ``(i + 0.5) / n`` of ``spec``,
    clipped to its ``[min, max]``."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        x = np.rint(float(spec["median"]) * np.exp(float(spec["sigma"]) * z))
    elif spec["dist"] == "uniform":
        x = np.floor(lo + q * (hi - lo + 1))
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def prefix_counts(popularity, n: int) -> np.ndarray:
    """How many of ``n`` requests use each prefix: ``n·p`` rounded by
    largest remainder, so the counts sum to ``n``."""
    p = np.asarray(popularity, float)
    p = p / p.sum()
    raw = n * p
    counts = np.floor(raw).astype(np.int64)
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[: n - counts.sum()]] += 1
    return counts


def seed_words(seed: int) -> list[int]:
    seed = int(seed)
    return [abs(seed), 1 if seed < 0 else 0]


@dataclasses.dataclass
class Round:
    prompts: list          # int32 arrays
    budgets: list          # output tokens per request
    prefix_ids: list       # shared prefix per request, -1 for none

    def cached_prompt_tokens(self, block_size: int, prefix_len: int) -> list[int]:
        """Prompt tokens of each request that whole blocks of a prefix
        already held by an earlier request of the round cover (the engine
        keeps a hit below the last prompt token: ``(len - 1) // bs``)."""
        seen: set[int] = set()
        out = []
        for p, pid in zip(self.prompts, self.prefix_ids):
            if pid >= 0 and pid in seen:
                out.append(min(prefix_len // block_size,
                               (len(p) - 1) // block_size) * block_size)
            else:
                out.append(0)
            if pid >= 0:
                seen.add(pid)
        return out


class Traffic:
    """The rounds of one run, and the warm-up that precedes them, from
    ``seed``.  The window's rounds and the warm-up draw from separate
    streams, so warming up changes nothing the window serves."""

    def __init__(self, mix: Mix, vocab: int, seed: int):
        self.mix, self.vocab = mix, vocab
        words = seed_words(seed)
        self._window = np.random.default_rng(words + [1])
        self._warm = np.random.default_rng(words + [2])
        n = mix.round_requests
        self.prompt_sizes = stratified(mix.prompt, n)
        self.output_sizes = stratified(mix.output, n)
        if mix.prefixes:
            pre = np.random.default_rng(words + [0])
            self.prefixes = pre.integers(
                0, vocab, (int(mix.prefixes["count"]), mix.prefix_len),
                dtype=np.int32)
            self.prefix_ids = np.repeat(
                np.arange(len(self.prefixes)),
                prefix_counts(mix.prefixes["popularity"], n))
        else:
            self.prefixes = np.zeros((0, 0), np.int32)
            self.prefix_ids = np.full((n,), -1)

    def _tokens(self, rng, n: int) -> np.ndarray:
        return rng.integers(0, self.vocab, n, dtype=np.int32)

    def next_round(self) -> Round:
        rng = self._window
        sizes = rng.permutation(self.prompt_sizes)
        outs = rng.permutation(self.output_sizes)
        pids = rng.permutation(self.prefix_ids)
        prompts = []
        for n, pid in zip(sizes, pids):
            head = self.prefixes[pid] if pid >= 0 else np.zeros((0,), np.int32)
            prompts.append(np.concatenate([head, self._tokens(rng, int(n))]))
        return Round(prompts, [int(o) for o in outs], [int(p) for p in pids])

    # ------------------------------------------------------------------
    # warm-up: every prefill shape a round can reach
    # ------------------------------------------------------------------

    def prefill_buckets(self, max_seq: int) -> dict[int, dict[int, int]]:
        """``{start: {padded suffix length: a prompt length that pads to
        it}}`` for every prompt the mix can draw.  A request starts at 0,
        or after the whole blocks of its prefix that the prefix cache
        holds."""
        m = self.mix
        bs, pad = m.block_size, m.prompt_pad_multiple
        out: dict[int, dict[int, int]] = {}
        for L in range(m.min_prompt, m.max_prompt + 1):
            starts = {0}
            if m.prefixes:
                starts.add(min(m.prefix_len // bs, (L - 1) // bs) * bs)
            for s in starts:
                t_pad = min(-(-(L - s) // pad) * pad, max_seq - s)
                out.setdefault(s, {})[t_pad] = L
        return out

    def warmup_calls(self, max_seq: int) -> list[Round]:
        """Rounds that together trace every (group size, padded length,
        start) prefill program and the decode programs a window uses.

        A group of ``k`` requests at start 0 is ``k`` prompts of one
        padded length, admitted together.  A group at a prefix start needs
        the prefix cached first: ``slots`` requests that share the prefix
        fill every slot, finish at admission (budget 1), and the ``k`` that
        follow are admitted together onto the cached blocks."""
        m, rng = self.mix, self._warm
        calls = []
        buckets = self.prefill_buckets(max_seq)
        for start, by_pad in sorted(buckets.items()):
            for L in sorted(by_pad.values()):
                for k in range(1, m.slots + 1):
                    if start == 0:
                        prompts = [self._tokens(rng, L) for _ in range(k)]
                        calls.append(Round(prompts, [1] * k, [-1] * k))
                        continue
                    head = self._tokens(rng, m.prefix_len)
                    fill = [np.concatenate([head, self._tokens(rng, m.min_prompt - m.prefix_len)])
                            for _ in range(m.slots)]
                    grp = [np.concatenate([head, self._tokens(rng, L - m.prefix_len)])
                           for _ in range(k)]
                    n = m.slots + k
                    calls.append(Round(fill + grp, [1] * n, [0] * n))
        steps = 2 * m.chunk_steps + 1
        L = m.min_prompt
        calls.append(Round([self._tokens(rng, L) for _ in range(m.slots)],
                           [steps] * m.slots, [-1] * m.slots))
        return calls
