"""Share of the decode batch's row-steps that produced a token the request
kept, over the window (the scheduler's ``useful_token_steps`` over
``total_token_steps``, summed over rounds)."""


def read(run):
    total = run.stat_sum("total_token_steps")
    return None if not total else 100.0 * run.stat_sum("useful_token_steps") / total
