"""p95 of first-token time minus due time, over every request due in the
window; one with no first token when the window closes counts at the
window's end."""

from bench.stats import p95_ms


def compute(run):
    return p95_ms(min(r.token_times[0] if r.token_times else run.seconds,
                      run.seconds) - r.due
                  for r in run.reqs.values() if r.due < run.seconds)
