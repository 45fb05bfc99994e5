"""The traffic generator: deterministic per seed, the same sizes for
every seed, the stated distributions, and an LM backlog that opens in
steady state."""
import numpy as np
import pytest

from bench import traffic
from bench.kinds import dense_lm

LM = {"kind": "dense_lm", "max_len": 2048, "vocab_size": 49152}
CNN = {"kind": "cnn", "input_hw": 8, "input_ch": 3}
DECODE = {"kind": "backlog", "slots": 64, "requests": 512,
          "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 0.75,
                         "min": 32, "max": 1024},
          "output_len": {"dist": "lognormal", "median": 512, "sigma": 0.5,
                         "min": 128, "max": 1024}}


def test_same_seed_same_inputs():
    big = 2 ** 33 + 12345           # seeds past 32 bits work
    a = traffic.make(DECODE, LM, big).inputs
    b = traffic.make(DECODE, LM, big).inputs
    assert all(np.array_equal(x, y) for x, y in zip(a["prompts"],
                                                    b["prompts"]))
    assert a["max_new"] == b["max_new"]
    c = traffic.make(DECODE, LM, big + 1).inputs
    assert not all(np.array_equal(x, y)
                   for x, y in zip(a["prompts"], c["prompts"]))


def test_every_seed_gets_the_same_sizes():
    a = traffic.make(DECODE, LM, 1).inputs
    b = traffic.make(DECODE, LM, 2).inputs
    def pairs(x):
        return [(len(p), m) for p, m in zip(x["prompts"], x["max_new"])]
    for part in (slice(0, 64), slice(64, None)):    # in flight, backlog
        assert sorted(pairs(a)[part]) == sorted(pairs(b)[part])
    assert pairs(a) != pairs(b)


def test_lognormal_quantiles_follow_the_spec():
    spec = DECODE["prompt_len"]
    x = traffic.quantiles(spec, 4001)
    assert x.min() == spec["min"] and x.max() == spec["max"]
    assert np.median(x) == spec["median"]
    # Between the clips, log lengths spread with the stated sigma.
    inner = np.log(x[(x > spec["min"]) & (x < spec["max"])])
    q25, q75 = np.percentile(np.log(traffic.quantiles(
        {**spec, "min": 1, "max": 10 ** 9}, 4001)), [25, 75])
    assert abs((q75 - q25) / 1.349 - spec["sigma"]) < 0.01
    assert inner.size > 0


def test_uniform_quantiles_cover_every_length_equally():
    x = traffic.quantiles({"dist": "uniform", "min": 1, "max": 8}, 800)
    assert np.bincount(x)[1:].tolist() == [100] * 8


def test_requests_in_flight_are_length_biased_at_uniform_ages():
    L = traffic.quantiles(DECODE["output_len"], 512)
    age, left = dense_lm.in_flight(L, 4096)
    total = age + left
    assert set(total) <= set(L)
    assert (age >= 0).all() and (left >= 1).all()
    # A request is in flight in proportion to its length ...
    assert total.mean() == pytest.approx((L ** 2).mean() / L.mean(),
                                         rel=0.01)
    # ... at an age uniform over it.
    assert (age / total).mean() == pytest.approx(0.5, abs=0.01)


def test_the_backlog_opens_with_one_request_in_flight_per_slot():
    t = traffic.make(DECODE, LM, 9)
    x, slots = t.inputs, DECODE["slots"]
    assert x["opening"] == slots
    assert len(x["prompts"]) == slots + DECODE["requests"]
    lens = np.array([len(p) for p in x["prompts"]])
    new = np.array(x["max_new"])
    assert (lens + new <= LM["max_len"]).all()
    # Each opening prompt is a prompt of the mix and the tokens served.
    age, left = dense_lm.in_flight(
        traffic.quantiles(DECODE["output_len"], DECODE["requests"]), slots)
    assert sorted(new[:slots]) == sorted(left)
    assert lens[:slots].sum() == (traffic.quantiles(DECODE["prompt_len"],
                                                    slots).sum() + age.sum())


def test_images_from_the_seed():
    spec = {"kind": "backlog", "slots": 2, "image_pool": 5}
    a = traffic.make(spec, CNN, 3).inputs["images"]
    b = traffic.make(spec, CNN, 3).inputs["images"]
    assert a.shape == (5, 8, 8, 3) and a.dtype == np.float32
    np.testing.assert_array_equal(a, b)


def test_lengths_past_max_len_are_refused():
    spec = {**DECODE, "output_len": {"dist": "uniform", "min": 1100,
                                     "max": 1100}}
    with pytest.raises(ValueError, match="max_len"):
        traffic.make(spec, LM, 0)


def test_an_unknown_arrival_process_is_refused():
    with pytest.raises(ModuleNotFoundError):
        traffic.make({**DECODE, "kind": "no_such_loop"}, LM, 0)
