"""Is what the timed path served correct?  Compared with the reference.

Once the window has closed and the engine is freed: draw a sample of the
requests the window finished, from the seed, always with the one that
served the most tokens in it, until it holds ``SAMPLE_TOKENS`` served
tokens or ``SAMPLE_MAX`` requests.  The reference runs once over each
prompt with its served tokens (teacher-forced), and at every served
position reads how far the served token's logit lies below the
reference's best logit there.

The number compared is the share of those tokens whose gap exceeds
``gap_tau`` logit units, not the widest gap or the mean.  The sc_int
datapath rounds every projection's input to 9 levels, so a difference in
the last bit of a bfloat16 activation can move a level, and the layers
carry it on: with random weights the sound program's widest gap comes
out close to the float8 control's, and its mean within 2.6x of it,
while the share of large gaps is 14 to 31 times apart (PERF.md,
"The check").
"""

from __future__ import annotations

import numpy as np

__all__ = ["SAMPLE_TOKENS", "SAMPLE_MAX", "sample", "gap_readings",
           "control_readings", "judge"]

SAMPLE_TOKENS = 384
SAMPLE_MAX = 8


def sample(run, seed: int) -> list:
    """The finished requests to check (their ReqLogs)."""
    done = [r for r in run.reqs.values()
            if r.done is not None and r.done <= run.seconds and r.served]
    if not done:
        return []
    rng = np.random.default_rng([int(seed) % 2 ** 64, 0xC4EC])
    longest = max(done, key=lambda r: len(r.served))
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    picked, n = [longest], len(longest.served)
    for r in rest:
        if n >= SAMPLE_TOKENS or len(picked) >= SAMPLE_MAX:
            break
        picked.append(r)
        n += len(r.served)
    return picked


def _sequences(picked):
    """Teacher-forced inputs: prompt + served tokens but the last; the
    target at position t is the token after it."""
    seqs, tgts = [], []
    for r in picked:
        full = list(r.prompt) + list(r.served)
        seqs.append(np.asarray(full[:-1], np.int32))
        tgts.append(np.asarray(full[1:], np.int32))
    return seqs, tgts


def gap_readings(ref, picked, pad_to: int, extra=None) -> np.ndarray:
    """Per served token: reference best logit minus the served token's.
    ``extra`` (one int array per request) adds a second row of targets
    read in the same forward pass; the result is then (2, tokens)."""
    seqs, tgts = _sequences(picked)
    rows = [np.stack([t] if extra is None else [t, x])
            for t, x in zip(tgts, extra or tgts)]
    out = ref.readings(seqs, rows, pad_to)
    gaps = np.concatenate([
        (o["best"][None] - o["target"])[:, len(r.prompt) - 1:]
        for o, r in zip(out, picked)], axis=1)
    return gaps[0] if extra is None else gaps


def control_readings(ref, control, picked, pad_to: int):
    """The control in the program's place, without decoding: at each
    served position, the reference's gap for the token the control puts
    first.  Returns (program gaps, control gaps), one reference pass."""
    seqs, tgts = _sequences(picked)
    ctl = control.readings(seqs, [t[None] for t in tgts], pad_to)
    both = gap_readings(ref, picked, pad_to,
                        extra=[np.asarray(o["argmax"], np.int32)
                               for o in ctl])
    return both[0], both[1]


def judge(gaps, check: dict) -> dict:
    """The compared number beside its limit (a configuration file's
    ``check``); nothing served in the window reads as every token off."""
    tau, limit = check["gap_tau"], check["share_limit"]
    share = float(np.mean(np.asarray(gaps) > tau)) if len(gaps) else 1.0
    return {f"share_gap_over_{tau:g}": {"value": share, "limit": limit}}
