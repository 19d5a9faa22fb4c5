"""p95 of every gap between consecutive tokens of a request whose later
token landed in the window (tokens of one step share its end time)."""

from bench.stats import p95_ms


def compute(run):
    return p95_ms(b - a for r in run.reqs.values()
                  for a, b in zip(r.token_times, r.token_times[1:])
                  if b <= run.seconds)
