"""Traffic: the same seed gives the same rounds; every seed gives the same
sizes in another order; the warm-up reaches every prefill shape."""

import json

import numpy as np
import pytest

from harness import manifest, traffic as T


CELLS = {"prefill": "kanformer-100m.prefill", "decode": "kanformer-100m.decode"}


def mix(name):
    with open(manifest.cell_path(CELLS[name])) as f:
        return T.load_mix(manifest.traffic_path(name), json.load(f))


def rounds(m, seed, n=2, vocab=32000):
    t = T.Traffic(m, vocab, seed)
    return [t.next_round() for _ in range(n)]


@pytest.mark.parametrize("name", ["prefill", "decode"])
def test_same_seed_same_rounds(name):
    m = mix(name)
    a, b = rounds(m, 2**31 + 99), rounds(m, 2**31 + 99)
    for ra, rb in zip(a, b):
        assert ra.budgets == rb.budgets and ra.prefix_ids == rb.prefix_ids
        assert all((x == y).all() for x, y in zip(ra.prompts, rb.prompts))


@pytest.mark.parametrize("name", ["prefill", "decode"])
def test_seeds_share_the_sizes(name):
    m = mix(name)
    (a,), (b,) = rounds(m, 1, 1), rounds(m, 2, 1)
    assert a.budgets != b.budgets
    assert sorted(a.budgets) == sorted(b.budgets)
    assert sorted(map(len, a.prompts)) == sorted(map(len, b.prompts))
    assert len(a.prompts) == m.round_requests
    for p in a.prompts:
        assert m.min_prompt <= len(p) <= m.max_prompt and p.dtype == np.int32


def test_prefixes_by_popularity():
    m = mix("prefill")
    (r,) = rounds(m, 5, 1)
    counts = np.bincount(r.prefix_ids, minlength=4)
    assert counts.tolist() == T.prefix_counts([0.55, 0.25, 0.12, 0.08], 256).tolist()
    assert counts.tolist() == [141, 64, 31, 20]
    t = T.Traffic(m, 32000, 5)
    for p, pid in zip(r.prompts, r.prefix_ids):
        assert (p[: m.prefix_len] == t.prefixes[pid]).all()
    cached = r.cached_prompt_tokens(m.block_size, m.prefix_len)
    first = {pid: i for i, pid in reversed(list(enumerate(r.prefix_ids)))}
    for i, (c, pid) in enumerate(zip(cached, r.prefix_ids)):
        assert c == (0 if first[pid] == i else m.prefix_len)


def test_stratified_quantiles():
    s = T.stratified({"dist": "lognormal", "median": 256, "sigma": 0.8,
                      "min": 32, "max": 1024}, 255)
    assert s[127] == 256 and s.min() >= 32 and s.max() <= 1024
    assert (np.diff(s) >= 0).all()
    u = T.stratified({"dist": "uniform", "min": 8, "max": 32}, 25)
    assert u.tolist() == list(range(8, 33))


def test_max_seq_as_the_launcher_sizes_it():
    assert mix("prefill").max_seq() == 3120
    assert mix("decode").max_seq() == 784


def test_warmup_reaches_every_prefill_shape():
    """Every (start, padded length) a round can draw is warmed, for every
    group size up to the slots."""
    m = mix("prefill")
    t = T.Traffic(m, 32000, 3)
    buckets = t.prefill_buckets(m.max_seq())
    assert {s: sorted(b) for s, b in buckets.items()} == {0: [2048, 3072], 1024: [1024, 2048]}
    calls = t.warmup_calls(m.max_seq())
    assert len(calls) == 4 * m.slots + 1
    (r,) = rounds(m, 3, 1)
    for p, c in zip(r.prompts, r.cached_prompt_tokens(m.block_size, m.prefix_len)):
        pad = -(-(len(p) - c) // m.prompt_pad_multiple) * m.prompt_pad_multiple
        assert min(pad, m.max_seq() - c) in buckets[c]
    d = mix("decode")
    assert {s: sorted(b) for s, b in T.Traffic(d, 32000, 3).prefill_buckets(d.max_seq()).items()} == {0: [512]}


def test_a_mix_holds_only_the_mix(tmp_path):
    """Serve and check settings belong to the cell, not to the mix."""
    with open(manifest.traffic_path("decode")) as f:
        d = json.load(f)
    p = tmp_path / "m.json"
    p.write_text(json.dumps(dict(d, slots=8)))
    with open(manifest.cell_path(CELLS["decode"])) as f:
        cell = json.load(f)
    with pytest.raises(ValueError, match="not a part of a traffic mix"):
        T.load_mix(str(p), cell)
    del cell["serve"]["slots"]
    with pytest.raises(ValueError, match="serve keys"):
        T.load_mix(manifest.traffic_path("decode"), cell)
