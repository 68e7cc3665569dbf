"""The open-loop generator: the mix fixes the schedule, lengths stay in
their clips, bursty gaps have the mix's coefficient of variation, and the
seed chooses the prompt tokens."""

import numpy as np

from bench import traffic

MIX = {"shape_seed": 3,
       "arrivals": {"process": "gamma", "cv": 2.0, "rate_per_s": 40.0},
       "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.9,
                  "min": 32, "max": 1500},
       "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                  "min": 8, "max": 512}}


def test_the_mix_fixes_the_schedule():
    a = traffic.schedule(MIX, 30.0)
    assert a == traffic.schedule(MIX, 30.0)
    assert a != traffic.schedule(dict(MIX, shape_seed=4), 30.0)


def test_lengths_within_clips_and_dues_within_horizon():
    arr = traffic.schedule(MIX, 30.0)
    assert len(arr) == 1200
    assert all(32 <= a.prompt_len <= 1500 for a in arr)
    assert all(8 <= a.max_new <= 512 for a in arr)
    dues = [a.due for a in arr]
    assert dues == sorted(dues) and dues[0] == 0.0 and dues[-1] < 30.0


def test_gamma_gaps_have_cv_two():
    arr = traffic.schedule(MIX, 2000.0)
    gaps = np.diff([a.due for a in arr])
    cv = gaps.std() / gaps.mean()
    assert 1.8 < cv < 2.2


def test_poisson_gaps_have_cv_one():
    mix = dict(MIX, arrivals={"process": "poisson", "rate_per_s": 5.0})
    gaps = np.diff([a.due for a in traffic.schedule(mix, 4000.0)])
    assert 0.95 < gaps.std() / gaps.mean() < 1.05


def test_rate_override_scales_the_count():
    assert len(traffic.schedule(MIX, 30.0, rate_per_s=4.0)) == 120


def test_prompt_tokens_are_seeded_and_in_vocab():
    p = traffic.prompt_tokens(2**31 + 5, 3, 100, 49152)
    assert p.dtype == np.int32 and len(p) == 100
    assert (p >= 0).all() and (p < 49152).all()
    assert (p == traffic.prompt_tokens(2**31 + 5, 3, 100, 49152)).all()
    assert (p != traffic.prompt_tokens(2**31 + 5, 4, 100, 49152)).any()
