"""tokens_per_s: tokens of the batch tenants' jobs that completed in the
window, over the window's length."""


def read(run):
    jobs = [j for j in run.done_in_window("batch")
            if j.module in run.tokens_per_chunk]
    if not jobs:
        return None
    tokens = sum(len(j.items) * run.tokens_per_chunk[j.module] for j in jobs)
    return tokens / run.window_s
