"""Open-loop traffic from a mix file and a seed.

A mix file (``bench/traffic/<mix>.json``) holds only parameters: the
arrival process and its rate, the prompt and output length distributions
with their clips, and how long the run plays traffic before the measured
window opens.  One generator reads every mix.

The schedule (when each request is due, its prompt and output lengths)
is drawn from the mix's own ``shape_seed``, so every run of a cell offers
the same work at the same moments; the run's seed chooses the prompt
token ids (and, in ``bench/weights.py``, the weights).  At the rates these
engines sustain a 50 s window holds only some tens of requests, and
shuffling the schedule per seed moved the long-chat cell's TTFT p95 by a
factor of two between seeds (its spread over six seeds was 96%): the seed
changed the work, not just the data.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One request of the schedule: due ``due`` seconds after the schedule
    starts, with ``prompt_len`` prompt tokens and ``max_new`` to decode."""
    idx: int
    due: float
    prompt_len: int
    max_new: int


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(rng, spec: dict, n: int) -> np.ndarray:
    """``n`` gaps between arrivals with mean ``1 / rate_per_s``."""
    mean = 1.0 / spec["rate_per_s"]
    if spec["process"] == "poisson":
        return rng.exponential(mean, n)
    if spec["process"] == "gamma":
        # shape k = 1 / cv^2 gives a coefficient of variation of cv
        k = 1.0 / spec["cv"] ** 2
        return rng.gamma(k, mean / k, n)
    raise ValueError(f"unknown arrival process {spec['process']!r}")


def schedule(mix: dict, horizon_s: float,
             rate_per_s: float | None = None) -> list[Arrival]:
    """The arrivals due in ``[0, horizon_s)``.

    ``rate_per_s`` overrides the mix's rate (the knee sweep uses it); the
    gaps are then the same, scaled.  The gaps are scaled so that their sum
    over the schedule's ``n`` requests is exactly ``horizon_s``."""
    arrivals = dict(mix["arrivals"])
    if rate_per_s is not None:
        arrivals["rate_per_s"] = rate_per_s
    n = max(int(round(arrivals["rate_per_s"] * horizon_s)), 1)
    # one stream each, so a longer schedule starts with a shorter one
    prompts = _lengths(np.random.default_rng([mix["shape_seed"], 0]),
                       mix["prompt"], n)
    outputs = _lengths(np.random.default_rng([mix["shape_seed"], 1]),
                       mix["output"], n)
    gaps = _gaps(np.random.default_rng([mix["shape_seed"], 2]), arrivals, n)
    gaps = gaps * (horizon_s / gaps.sum())
    # the first request is due at 0, the last strictly before the horizon
    dues = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Arrival(idx=i, due=float(dues[i]), prompt_len=int(prompts[i]),
                    max_new=int(outputs[i])) for i in range(n)]


def prompt_tokens(seed: int, idx: int, length: int, vocab: int) -> np.ndarray:
    """Prompt ids of request ``idx``: uniform over the vocabulary, from the
    run's seed, so the reference can be fed exactly what the engine saw."""
    rng = np.random.default_rng([seed % 2**64, 2, idx])
    return rng.integers(0, vocab, length, dtype=np.int64).astype(np.int32)
