"""p95 of how late each submit ran after its due time (the load
generator shares the host's one loop with the engine)."""

from bench.stats import p95_ms


def compute(run):
    return p95_ms(r.submit - r.due for r in run.reqs.values()
                  if r.submit is not None)
