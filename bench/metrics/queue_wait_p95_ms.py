"""p95 of due time to the start of the step that admitted the request
into a slot; one still waiting when the window closes counts at its
end."""

from bench.stats import p95_ms


def compute(run):
    return p95_ms(min(r.admit if r.admit is not None else run.seconds,
                      run.seconds) - r.due
                  for r in run.reqs.values() if r.due < run.seconds)
