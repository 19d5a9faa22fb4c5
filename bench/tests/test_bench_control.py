"""The check's control at a size a test run holds: the reference computed
in float8 e4m3 (the precision below the configuration's bfloat16), put
in the program's place, fails the limit on every seed, while the program
passes it.  The windows compile nothing: the warm-up's copy of the
engine's buckets (bench/serve.prefill_bucket, decode_bucket) reached
every program the engine ran, for this seed and for the seeds after it."""

import pytest
from conftest import PEAKS, TINY

from bench import run


@pytest.mark.parametrize("seeds", [(3, 2 ** 31 + 5, 2 ** 33 + 9)])
def test_control_fails_the_limit_and_the_program_passes(tiny_root, seeds):
    cell = run.Cell(TINY, tiny_root)
    tau = cell.cf["check"]["gap_tau"]
    limit = cell.cf["check"]["share_limit"]
    eng = None
    for seed in seeds:
        log, eng, _ = run.serve(cell, seed, 1.5, False, PEAKS, engine=eng)
        assert log.window_compiles == 0, seed
        picked, prog, ctl = run.readings(cell, log, seed, "float8_e4m3fn")
        assert len(prog) > 0
        assert (prog > tau).mean() <= limit, seed
        assert (ctl > tau).mean() > limit, seed
