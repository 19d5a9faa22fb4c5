"""1 - device busy / window, busy being the union of the device's
operation intervals in the traced window (bench/trace.py)."""


def compute(run):
    return (run.trace or {}).get("idle_share")
