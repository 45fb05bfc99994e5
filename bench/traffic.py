"""One generator for every traffic mix.

A mix is a data file, ``bench/traffic/<mix>.json``; this module reads it
and, through the configuration's kind (``bench/kinds/<kind>.py``,
``make_inputs``), makes the whole run's inputs in set-up, so nothing is
generated on the host inside the measured window.  Keys:

    kind          the arrival process, ``bench/loops/<kind>.py``
                  ("backlog": the queue is kept at least ``slots`` deep)
    slots         the engine's batch: LM cache slots or CNN batch size
    requests      LM: how many distinct backlog requests to make
    prompt_len    LM: a length distribution (below)
    output_len    LM: a length distribution (below)
    image_pool    CNN: distinct images; request i sends image i mod pool
    check         what the correctness comparison samples
    trace         the part of the window that a ``--trace 1`` run traces

A length distribution is {"dist": "lognormal", "median", "sigma", "min",
"max"} or {"dist": "uniform", "min", "max"}.  Lengths are the
distribution's quantiles at (i + 0.5) / n, so every seed gets the same
set of sizes; the seed only shuffles their order and draws the token
ids and pixels.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Traffic:
    kind: str
    slots: int
    inputs: dict              # made by the configuration's kind


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent streams from one ``--seed`` (any size of integer)."""
    return np.random.default_rng([seed, stream])


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` integer lengths: the distribution's quantiles, clipped."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = np.floor(spec["min"] + u * (spec["max"] - spec["min"] + 1))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def make(traffic: dict, config: dict, seed: int) -> Traffic:
    from bench import spec
    spec.loop(traffic)                      # an unknown kind fails here
    inputs = spec.kind(config).make_inputs(traffic, config, rng_for(seed, 1))
    return Traffic(traffic["kind"], traffic["slots"], inputs)
