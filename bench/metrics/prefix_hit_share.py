"""Share of the window's prompt tokens that the prefix cache served
instead of prefill (the pool's ``prefill_tokens_saved`` over saved plus
computed, summed over rounds).  0 where no prompt shares a prefix."""


def read(run):
    saved = run.stat_sum("paged", "prefill_tokens_saved")
    total = saved + run.stat_sum("paged", "prefill_tokens_computed")
    return None if not total else 100.0 * saved / total
