"""The comparison that decides ``correct``: served tokens against the
plain reference.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and holding the longest of them, is run through the
configuration's float32 reference (``<config>.ref.py``), teacher-forced on
each prompt and its served tokens.  At every served position the number
read is how far the served token's reference logit lies below the
reference's best logit there; the run's number is the widest such gap.
The control puts the reference computed in float8 in the program's place
and reads the gap of the token it ranks first at the same positions.
"""

from __future__ import annotations

import numpy as np


def sample(requests: list, rng: np.random.Generator, check_tokens: int,
           check_requests: int, cap: int = 64) -> list:
    """``requests``: ``(prompt, served, cached prompt tokens)``.  The
    longest (prompt plus served tokens), the longest whose prefix came from
    the prefix cache, then others in an order drawn from ``rng``, until the
    served tokens reach ``check_tokens`` and the requests
    ``check_requests``, or ``cap`` requests.  Returns ``(prompt, served)``
    pairs."""
    if not requests:
        return []
    sizes = np.array([len(p) + len(o) for p, o, _ in requests])
    hit = np.array([c > 0 for _, _, c in requests])
    first = [int(np.argmax(sizes))]
    if hit.any():
        first.append(int(np.argmax(np.where(hit, sizes, -1))))
    order = list(dict.fromkeys(first + [int(i) for i in rng.permutation(len(requests))]))
    out, n = [], 0
    for i in order[:cap]:
        out.append(requests[i][:2])
        n += len(requests[i][1])
        if n >= check_tokens and len(out) >= check_requests:
            break
    return out


class Reference:
    """The reference's logits at the served positions of one request, in
    one compiled program per run: tokens padded to ``max_seq`` (causal, so
    the padding changes nothing before it) and rows to ``max_rows``."""

    def __init__(self, ref, cfg: dict, max_seq: int, max_rows: int):
        import jax

        self.max_seq, self.max_rows = max_seq, max_rows
        self.w = None
        self._init = jax.jit(lambda k: ref.init_weights(k, cfg))
        self._fn = {
            lowp: jax.jit(lambda w, t, r, lowp=lowp: ref.logits(w, cfg, t, r, lowp))
            for lowp in (False, True)
        }

    def load(self, key) -> None:
        """The weights of ``key``, made again in the reference's layout."""
        self.w = None
        self.w = self._init(key)

    def logits(self, prompt, served, lowp: bool = False) -> np.ndarray:
        import jax

        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        toks = np.zeros((self.max_seq,), np.int32)
        toks[: len(seq)] = seq
        rows = len(prompt) - 1 + np.arange(len(served))
        rows = np.concatenate([rows, np.full(self.max_rows - len(rows), rows[-1])])
        out = self._fn[lowp](self.w, toks, rows.astype(np.int32))
        return np.asarray(jax.device_get(out))[: len(served)]


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position: the reference's best logit minus its logit of the
    token that was put first there."""
    best = ref_logits.max(-1)
    return best - ref_logits[np.arange(len(tokens)), tokens]


def widest_gap(reference: Reference, samples: list) -> float:
    return float(max(gaps(reference.logits(p, o), o).max() for p, o in samples))


def verdict(failed: int, gap: float, limits: dict) -> tuple[bool, dict]:
    """``correct``, and the numbers compared, each beside its limit: no
    request failed, and the widest gap is within the cell's ``logit_gap``
    (a cell with no limit set is never correct)."""
    limit = limits.get("logit_gap")
    limit = float("nan") if limit is None else float(limit)
    checks = {"failed": {"value": failed, "limit": 0},
              "logit_gap": {"value": gap, "limit": limit}}
    return bool(failed == 0 and gap <= limit), checks


def control_gap(reference: Reference, samples: list) -> float:
    """The widest gap of the tokens the float8 reference ranks first."""
    worst = 0.0
    for p, o in samples:
        ref = reference.logits(p, o)
        low = reference.logits(p, o, lowp=True)
        worst = max(worst, float(gaps(ref, low.argmax(-1)).max()))
    return worst
