"""Output tokens that reached the host inside the window, over its length."""


def compute(run):
    n = sum(1 for r in run.reqs.values() for t in r.token_times
            if t <= run.seconds)
    return n / run.seconds
