"""The one general traffic generator.  A mix is a data file of parameters;
everything drawn here is a pure function of (mix, seed), so the same seed
gives the same inputs in the server process and in the load generator.

Imports numpy only: the load generator must stay off JAX.

Request mixes (``"kind": "requests"``)::

    {"loop": "open", "rate_per_s": 4.0, "arrival_cv": 1.0, "drain_s": 15,
     "classes": [{"weight": 1, "prompt_len": <dist>, "output_len": <dist>}]}
    {"loop": "closed", "clients_per_slot": 2, "classes": [...]}

``arrival_cv`` 1 is a Poisson process; above 1 the gaps are gamma
distributed and arrivals come in bursts.  Lengths and classes are drawn by
inverse CDF along a golden-ratio sequence that starts where the seed says:
every length the distribution allows comes up in its proportion, and the
mean over any hundred consecutive requests is the same to a fraction of a
percent whatever the seed, so two runs are offered the same amount of work.
(Independent draws would move a run's mean prompt by over a percent, which
is more than the changes the benchmark is there to see.)  A ``<dist>`` is
one of::

    {"dist": "fixed", "value": n}
    {"dist": "uniform", "min": a, "max": b}
    {"dist": "loguniform", "min": a, "max": b}
    {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}

Batch mixes (``"kind": "lm_batches"``) are ``examples/train_lm.py``'s
permutation task, ``y = perm[x]`` with ``x`` uniform over the vocabulary
(copied: the benchmark's inputs may not change with the examples).
"""

from __future__ import annotations

import functools
import math
import statistics

import numpy as np

_ARRIVALS, _PERM, _BATCHES, _LENGTHS = 0xA771, 0x9E37, 0xBA7C, 0x1E46
# steps of the three quasi-random sequences (class, prompt, output): the
# reciprocals of the golden ratio and of the plastic number and its square
_STEPS = np.array([0.6180339887498949, 0.7548776662466927, 0.5698402909980532])
_NORMAL = statistics.NormalDist()


def quantile(dist: dict, u: float) -> int:
    """The length at quantile ``u`` in (0, 1) of a ``<dist>``."""
    kind = dist["dist"]
    if kind == "fixed":
        return int(dist["value"])
    lo, hi = dist["min"], dist["max"]
    if kind == "uniform":
        return lo + int(u * (hi - lo + 1))
    if kind == "loguniform":
        x = math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(max(round(x), lo), hi))


@functools.lru_cache(maxsize=8)
def _start(seed: int) -> np.ndarray:
    return np.random.default_rng([seed, _LENGTHS]).random(3)


def request(mix: dict, seed: int, index: int, vocab: int) -> tuple:
    """Request ``index`` of the stream: (prompt tokens, max_new_tokens)."""
    u = np.clip((_start(seed) + index * _STEPS) % 1.0, 1e-9, 1 - 1e-9)
    classes = mix["classes"]
    edges = np.cumsum([c.get("weight", 1.0) for c in classes])
    cls = classes[int(np.searchsorted(edges, u[0] * edges[-1], side="right"))]
    n_prompt = quantile(cls["prompt_len"], u[1])
    n_out = quantile(cls["output_len"], u[2])
    tokens = np.random.default_rng([seed, index]).integers(0, vocab, n_prompt)
    return tokens.astype(np.int32), n_out


def prompt_range(mix: dict) -> tuple:
    """Shortest and longest prompt the mix can draw."""
    ends = [(d["value"], d["value"]) if d["dist"] == "fixed"
            else (d["min"], d["max"])
            for d in (c["prompt_len"] for c in mix["classes"])]
    return min(lo for lo, _ in ends), max(hi for _, hi in ends)


def arrivals(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Instants, in seconds from the window's start, at which the open
    loop's requests are due; all of them fall inside the window."""
    rate, cv = float(mix["rate_per_s"]), float(mix.get("arrival_cv", 1.0))
    rng = np.random.default_rng([seed, _ARRIVALS])
    shape = 1.0 / (cv * cv)
    out, t = [], 0.0
    while True:
        t += rng.gamma(shape, 1.0 / (rate * shape))
        if t >= seconds:
            return np.asarray(out)
        out.append(t)


def lm_batches(mix: dict, seed: int, vocab: int, batch: int):
    """Endless (x, y) host batches of the permutation task."""
    perm = np.random.default_rng([seed, _PERM]).permutation(vocab)
    rng = np.random.default_rng([seed, _BATCHES])
    while True:
        x = rng.integers(0, vocab, (batch, mix["seq_len"]))
        yield x.astype(np.int32), perm[x].astype(np.int32)
