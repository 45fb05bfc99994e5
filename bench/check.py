"""What decides ``correct``: pieces shared by the kinds' comparisons
(``bench/kinds/<kind>.py::compare``), and the verdict against a cell's
limits.

Each kind compares what the timed path served with the plain float32
reference of ``bench/refs``, run once the window has closed and the
program's state is freed.  The control (``controls=(precision, ...)``)
is the same reference in a lower precision, put in the program's place;
its readings are named ``control_*.<precision>``.  The benchmark's own
runs do not run it; ``bench/control.py`` does.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def sample(recs: list, rng: np.random.Generator, target: int,
           max_n: int) -> list:
    """The request with the most served outputs, then others in an order
    drawn from ``rng``, until ``target`` outputs or ``max_n`` requests
    are in.  A request still in flight when the window closed counts
    with the outputs it was served: each is final once emitted."""
    done = [r for r in recs if r.out]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.out), -r.uid))
    rest = [r for r in done if r is not longest]
    order = rng.permutation(len(rest))
    out, n = [longest], len(longest.out)
    for i in order:
        if n >= target or len(out) >= max_n:
            break
        out.append(rest[i])
        n += len(rest[i].out)
    return out


def gaps(ref, picked):
    """Per row: the reference's best logit minus its logit at the
    picked token."""
    return (jnp.max(ref, -1)
            - jnp.take_along_axis(ref, picked[:, None], -1)[:, 0])


def verdict(readings: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and {name: {"value", "limit"}} for each compared
    number; a number that was not read, or no limit, is not correct."""
    compared = {}
    ok = bool(limits)
    for name, lim in limits.items():
        value = readings.get(name)
        compared[name] = {"value": value, "limit": lim["limit"]}
        if value is None or not np.isfinite(value) or value > lim["limit"]:
            ok = False
    return ok, compared
