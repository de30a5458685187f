"""The one traffic generator. A mix is a data file under ``bench/traffic``;
this module turns it and ``--seed`` into the requests of one run.

Every seed gets the same work in another order: the shares of request
kinds are fixed counts, shuffled, so two seeds differ by order and data,
never by how much there is to do.

Mix keys:

* ``loop``: ``closed`` (one client sends its next request when the last
  is answered);
* ``requests``: list of request kinds, each ``{"kind": ..., "share": ...}``
  plus the kind's own parameters (``bench/kinds/<kind>.py``);
* ``pool`` (optional): how many distinct requests set-up prepares;
* ``check``: how many answered requests the correctness check compares
  (``"all"`` or a number, drawn from the seed).
"""
from __future__ import annotations

import numpy as np

SEED_LIMIT = 2 ** 31 - 1


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per purpose, from any whole-number seed
    (numpy takes integers of any size)."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0),
                                  *stream.encode()])


def _fixed_counts(shares, total: int) -> list[int]:
    """Split ``total`` by ``shares`` into whole counts that sum to it
    (largest remainders first)."""
    shares = np.asarray(shares, float)
    exact = shares / shares.sum() * total
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[
            :total - counts.sum()]:
        counts[i] += 1
    return [int(c) for c in counts]


def closed_loop(mix: dict, seed: int, n: int) -> list[dict]:
    """The first ``n`` requests of a closed loop, in the order sent, each
    with a ``seed`` of its own."""
    if mix.get("loop", "closed") != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    rng = rng_for(seed, "closed")
    reqs = mix["requests"]
    counts = _fixed_counts([r.get("share", 1.0) for r in reqs], n)
    out = [dict(r) for r, c in zip(reqs, counts) for _ in range(c)]
    rng.shuffle(out)
    for r in out:
        r["seed"] = int(rng.integers(0, SEED_LIMIT))
    return out


def sample(mix: dict, answered: int, seed: int) -> list[int]:
    """Indices of the answered requests the check compares: all, or a
    sample of ``check`` of them drawn from the seed."""
    want = mix.get("check", "all")
    if want == "all" or answered <= int(want):
        return list(range(answered))
    picked = rng_for(seed, "check").choice(answered, int(want),
                                           replace=False)
    return sorted(int(i) for i in picked)
