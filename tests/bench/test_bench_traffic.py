"""The one traffic generator: deterministic per seed, inside its clips,
at its mean rate, and the same set of sizes and arrivals for every seed."""
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import manifest, traffic

DATA = Path(__file__).resolve().parent / "data"


def _mix(name):
    """A benchmark mix, or one of the tests' own."""
    if name == "decode":
        return manifest.traffic(name)
    return manifest.traffic(name, DATA)


@pytest.mark.parametrize("name", ["decode", "bursty-open"])
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a = traffic.sequence(mix, 2 ** 31 + 17, 50)
    b = traffic.sequence(mix, 2 ** 31 + 17, 50)
    assert a == b
    assert traffic.prompt_tokens(2 ** 31 + 17, 3, 40, 1000) == \
        traffic.prompt_tokens(2 ** 31 + 17, 3, 40, 1000)
    assert traffic.prompt_tokens(5, 3, 40, 1000) != \
        traffic.prompt_tokens(6, 3, 40, 1000)


@pytest.mark.parametrize("name", ["decode", "bursty-open"])
def test_lengths_inside_clips(name):
    mix = _mix(name)
    prompts, outputs, _ = traffic.layout(mix)
    for lens, dist in ((prompts, mix["prompt_tokens"]),
                       (outputs, mix["output_tokens"])):
        assert lens.min() >= dist["min"] and lens.max() <= dist["max"]
    if mix["prompt_tokens"]["dist"] == "lognormal":
        med = np.median(prompts)
        assert 0.7 * mix["prompt_tokens"]["median"] < med < \
            1.4 * mix["prompt_tokens"]["median"]
    tok = traffic.prompt_tokens(1, 0, 300, 50280)
    assert min(tok) >= 1 and max(tok) < 50280


def test_open_loop_mean_rate_and_bursts():
    mix = _mix("bursty-open")
    _, _, gaps = traffic.layout(mix)
    assert gaps.sum() == pytest.approx(mix["period_s"])
    assert len(gaps) / gaps.sum() == pytest.approx(mix["rate_per_s"],
                                                   rel=0.02)
    cv = gaps.std() / gaps.mean()
    assert 1.3 < cv < 3.0           # shape 0.25: CV 2, bursty
    for seed in (1, 2 ** 31 + 5):
        reqs = traffic.due_before(mix, seed, mix["period_s"])
        assert all(r.due_s < mix["period_s"] for r in reqs)
        assert len(reqs) == len(gaps)


def test_every_seed_gets_the_same_set_in_another_order():
    mix = _mix("bursty-open")
    n = traffic.layout_size(mix)
    runs = [traffic.sequence(mix, s, n) for s in (1, 2, 3)]
    sets = [Counter((r.prompt_len, r.output_len) for r in run)
            for run in runs]
    assert sets[0] == sets[1] == sets[2]
    orders = {tuple(r.prompt_len for r in run) for run in runs}
    assert len(orders) > 1


def test_closed_loop_layout():
    mix = manifest.traffic("decode")
    reqs = traffic.sequence(mix, 9, mix["clients"])
    assert len(reqs) == mix["clients"]
    assert all(r.due_s == 0.0 for r in reqs)
    assert all(384 <= r.output_len <= 768 for r in reqs)
