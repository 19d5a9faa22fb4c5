"""A run whose timed path is broken underneath reads ``correct: false``.

Each fault is planted in the engine's module for the engine build, the
warm-up and the window (the programs trace with it), and taken out
before the reference runs: a token altered where it is produced, a
decode step that returns its cache unchanged, half of the lanes served
the other half's logits.  (A one-chip cell has no exchange between
chips to leave out.)
"""

import contextlib

import jax.numpy as jnp
import pytest
from conftest import PEAKS, TINY

from bench import run


@contextlib.contextmanager
def _patched(name, make):
    import repro.serving.engine as eng_mod
    orig = getattr(eng_mod, name)
    setattr(eng_mod, name, make(orig))
    try:
        yield
    finally:
        setattr(eng_mod, name, orig)


def _token_altered(orig):
    return lambda logits, vocab: (orig(logits, vocab) + 1) % vocab


def _state_unchanged(orig):
    def step(params, cache, *a, **k):
        logits, _ = orig(params, cache, *a, **k)
        return logits, cache
    return step


def _half_the_lanes(orig):
    def step(*a, **k):
        logits, cache = orig(*a, **k)
        s = logits.shape[0]
        if s > 1:
            logits = jnp.concatenate([logits[:s // 2],
                                      logits[:s - s // 2]])
        return logits, cache
    return step


FAULTS = {"token_altered": ("greedy_tokens", _token_altered),
          "state_unchanged": ("paged_decode_step", _state_unchanged),
          "half_the_lanes": ("paged_decode_step", _half_the_lanes)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(tiny_root, fault):
    name, make = FAULTS[fault]
    res = run.run_cell(TINY, 17, 1.5, False, PEAKS, root=tiny_root,
                       fault=lambda: _patched(name, make))
    assert res["correct"] is False, res["check"]
    (reading,) = res["check"].values()
    assert reading["value"] > reading["limit"]
