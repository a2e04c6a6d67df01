"""One generator for every traffic mix: reads a mix's parameters (a file under
``bench/traffic/``) and makes its requests from the seed.

Every seed gets the same set of sizes, in another order: a length is the
quantile ``(i + 0.5) / n`` of its distribution for i = 0 .. n-1, and the
seed shuffles them.  So two seeds do
the same work, and differ only in its order and in the tokens.

A length is one of

* ``{"choice": [a, b, ...]}``: the values in equal shares;
* ``{"lognormal": {"median": m, "sigma": s}, "min": lo, "max": hi}``:
  log-normal, clipped (the clip is optional).

A mix is a closed loop: ``clients`` callers, each sending its next request
(from a ``pool`` of them) when its previous one ends.  ``warm_start`` lists
the request of each client that the window opens on, as the ``context``
length already in its slot and the ``remaining`` tokens it will still
produce: the same set every seed.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import List

import numpy as np

_NORMAL = statistics.NormalDist()


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator for any whole-number seed (negative or past 64 bits
    included), one independent stream per ``stream``."""
    return np.random.default_rng(np.random.SeedSequence([abs(seed),
                                                         seed < 0, stream]))


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths of the distribution ``spec``, shuffled by ``rng``."""
    if "choice" in spec:
        vals = np.asarray(spec["choice"], np.int64)
        out = vals[np.arange(n) % len(vals)]
    else:
        ln = spec["lognormal"]
        z = np.array([_NORMAL.inv_cdf(q) for q in quantiles(n)])
        out = ln["median"] * np.exp(ln["sigma"] * z)
        out = np.clip(out, spec.get("min", 1), spec.get("max", np.inf))
        out = np.ceil(out).astype(np.int64)
    return rng.permutation(out)


@dataclasses.dataclass
class Request:
    prompt: np.ndarray           # int32 token ids
    max_new_tokens: int


@dataclasses.dataclass
class Mix:
    clients: int
    warm: List[Request]          # in the slots when the window opens
    requests: List[Request]      # in order of submission


def make(traffic: dict, seed: int, vocab: int, max_seq: int) -> Mix:
    """The requests of one run of ``traffic``.  Every request fits
    ``max_seq``: its output budget is cut to what the cache holds."""
    rng = rng_for(seed)
    n = traffic["pool"]
    p_len = lengths(traffic["prompt_len"], n, rng)
    o_len = lengths(traffic["output_len"], n, rng)

    def req(p, o):
        o = int(min(o, max_seq - p + 1))
        if o < 1:
            raise ValueError(f"a prompt of {p} tokens leaves no room in "
                             f"max_seq {max_seq}")
        return Request(rng.integers(0, vocab, int(p)).astype(np.int32), o)

    ws = traffic["warm_start"]
    if len(ws["context"]) != traffic["clients"]:
        raise ValueError(f"warm_start has {len(ws['context'])} requests for "
                         f"{traffic['clients']} clients")
    order = rng.permutation(len(ws["context"]))
    warm = [req(ws["context"][i], ws["remaining"][i]) for i in order]
    reqs = [req(p, o) for p, o in zip(p_len, o_len)]
    return Mix(traffic["clients"], warm, reqs)
