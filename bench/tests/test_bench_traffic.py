"""The traffic generator: seeded, inside each mix's bounds, and the same
work for every seed."""

import json
import os
import shutil
from collections import Counter

import pytest

from bench.traffic.generator import count, generate

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")
MIXES = sorted(f[:-5] for f in os.listdir(TRAFFIC) if f.endswith(".json"))


def _mix(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = generate(_mix(name), 2 ** 31 + 17, 12.0, 1000)
    b = generate(_mix(name), 2 ** 31 + 17, 12.0, 1000)
    assert [(r.due_s, r.prompt, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt, r.max_new_tokens) for r in b]
    c = generate(_mix(name), 5, 12.0, 1000)
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_the_mix_bounds(name):
    mix = _mix(name)
    reqs = generate(mix, 3, 30.0, 50000)
    assert len(reqs) == count(mix, 30.0)
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in reqs)
    assert all(0 <= t < 50000 for r in reqs for t in r.prompt)
    assert all(0 <= r.due_s < 30.0 for r in reqs)
    assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_sends_the_same_work(name):
    """The seed permutes sizes and gaps; it does not change them."""
    mix = _mix(name)
    runs = [generate(mix, s, 20.0, 100) for s in (1, 2, 2 ** 33 + 1)]
    for key in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert len({tuple(sorted(map(key, rs))) for rs in runs}) == 1
    spans = {round(rs[-1].due_s, 9) for rs in runs}
    assert len(spans) == 1


def test_backlog_blocks_hold_the_same_sizes():
    mix = _mix("long_decode")
    reqs = generate(mix, 9, 10.0, 100)
    block = mix["block"]
    blocks = [Counter(len(r.prompt) for r in reqs[i:i + block])
              for i in range(0, len(reqs) - block + 1, block)]
    assert all(b == blocks[0] for b in blocks)
    assert all(r.due_s == 0 for r in reqs)


def test_poisson_rate_and_median_follow_the_mix():
    mix = _mix("chat")
    reqs = generate(mix, 4, 100.0, 100)
    rate = mix["arrivals"]["rate_per_s"]
    assert abs(len(reqs) - rate * 100) <= 1
    lens = sorted(len(r.prompt) for r in reqs)
    med = lens[len(lens) // 2]
    assert abs(med - mix["prompt_tokens"]["median"]) <= 8


def test_a_new_arrival_process_and_length_file_are_found_without_edits(
        tmp_path):
    """A mix naming a process or a distribution that is a new file under
    bench/traffic (or bench/traffic/lengths) runs with no other edit."""
    tdir = tmp_path / "traffic"
    shutil.copytree(TRAFFIC, tdir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tdir / "every_second.py").write_text(
        "import numpy as np\n\n\ndef count(arrivals, seconds):\n"
        "    return int(seconds)\n\n\ndef due(arrivals, n, rng):\n"
        "    return np.arange(n, dtype=float)\n")
    (tdir / "lengths" / "fixed.py").write_text(
        "import numpy as np\n\n\ndef quantiles(dist, u):\n"
        "    return np.full(len(u), float(dist['value']))\n")
    mix = {"arrivals": {"process": "every_second"},
           "prompt_tokens": {"dist": "fixed", "value": 7, "min": 1,
                             "max": 9},
           "output_tokens": {"dist": "uniform", "min": 2, "max": 4}}
    reqs = generate(mix, 2 ** 32 + 3, 5.0, 100, str(tdir))
    assert [r.due_s for r in reqs] == [0.0, 1.0, 2.0, 3.0, 4.0]
    assert {len(r.prompt) for r in reqs} == {7}
    assert count(mix, 5.0, str(tdir)) == 5
    with pytest.raises(ValueError, match="no traffic module"):
        generate(mix, 1, 5.0, 100)          # not in the shipped directory
