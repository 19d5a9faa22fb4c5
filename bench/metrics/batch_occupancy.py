"""Mean decoded lanes over max_slots, over the window's decode steps."""

from bench.stats import in_window


def compute(run):
    lanes = [len(s.decode_lens) for s in in_window(run) if s.decode_lens]
    return sum(lanes) / len(lanes) / run.max_slots if lanes else None
