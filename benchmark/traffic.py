"""Seeded inputs and the percentile arithmetic, for every cell.

One general generator per kind of input; a cell's file gives it
parameters and nothing else. The sizes a closed loop uses are a FIXED
set (evenly spaced quantiles of the stated distribution), paired by a
permutation fixed in the cell's file (``pairing_seed``). ``--seed``
draws the token values (and the weights) and the ORDER in which that
set is dealt to the callers: every seed offers the same set of sizes,
in another order.
"""

from __future__ import annotations

import math

import numpy as np

# numpy seeds take 32-bit words; the driver's seeds can exceed 2**31.
_WORD = 2 ** 32


def seed_words(seed: int, *more: int) -> list:
    seed = int(seed)
    return [seed % _WORD, (seed // _WORD) % _WORD] + [int(m) for m in more]


def seed31(seed: int) -> int:
    """A seed a 32-bit signed ``jax.random.PRNGKey`` argument holds."""
    return int(seed) % (2 ** 31 - 1)


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least p% of
    the sample at or below it. No interpolation, no rounded index."""
    if not values:
        raise ValueError("percentile of an empty sample")
    s = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(s)))
    return float(s[min(rank, len(s)) - 1])


def quantile_sizes(spec: dict, n: int) -> list:
    """n whole sizes at the quantiles (k + 0.5) / n of ``spec``:
    {"dist": "uniform" | "log_uniform", "min": a, "max": b} and
    optionally "multiple_of": m, which rounds each size to the nearest
    multiple of m inside [a, b] (prompt lengths in whole chunks of m
    bound the shapes a chunked prefill compiles)."""
    a, b = float(spec["min"]), float(spec["max"])
    m = int(spec.get("multiple_of", 1))
    out = []
    for k in range(n):
        q = (k + 0.5) / n
        if spec["dist"] == "log_uniform":
            v = a * (b / a) ** q
        elif spec["dist"] == "uniform":
            v = a + (b - a) * q
        else:
            raise ValueError(f"unknown distribution {spec['dist']!r}")
        v = int(round(v / m)) * m
        out.append(min(max(v, -(-int(a) // m) * m), int(b) // m * m))
    return out


def size_pool(traffic: dict) -> list:
    """The cell's fixed set of (body_tokens, max_tokens) pairs. Bodies
    ascend; answers are the same quantiles in an order fixed by
    ``pairing_seed``, so long bodies do not always get long answers."""
    n = int(traffic["pool_size"])
    bodies = quantile_sizes(traffic["body_tokens"], n)
    answers = quantile_sizes(traffic["max_tokens"], n)
    order = np.random.default_rng(int(traffic["pairing_seed"])).permutation(n)
    cap = int(traffic["max_total_tokens"])
    prefix = int(traffic.get("prefixes", {}).get("tokens", 0))
    pool = []
    for k in range(n):
        body, ans = bodies[k], answers[int(order[k])]
        if prefix + body + ans > cap:
            raise ValueError(
                f"size {k}: {prefix} + {body} + {ans} tokens exceed the "
                f"cell's max_total_tokens {cap}")
        pool.append((body, ans))
    return pool


def closed_loop_plan(traffic: dict, seed: int, vocab: int) -> dict:
    """What each caller of a closed loop sends, in order.

    Returns {"prefixes": [[token]], "callers": [{"prefix": i | None,
    "sizes": [(body, max_tokens)]}]} and a ``tokens(caller, index, n)``
    function for a request's own body. Caller c is bound to prefix
    c % count; the pool is shuffled by ``--seed`` and dealt
    round-robin, and a caller that runs out starts its share again
    with new tokens. ``first_share`` is the part of its first answer
    each caller asks for, evenly spread over (0, 1]: callers that start
    within a few seconds of each other are then at mixed phases of
    their answers, as a loop that has run for long is, and the ramp
    before the window can be short.
    """
    callers = int(traffic["callers"])
    pool = size_pool(traffic)
    rng = np.random.default_rng(seed_words(seed, 5))
    order = rng.permutation(len(pool))
    shares = [(int(k) + 1) / callers for k in rng.permutation(callers)]
    dealt = [[] for _ in range(callers)]
    for j, k in enumerate(order):
        dealt[j % callers].append(pool[int(k)])
    pre = traffic.get("prefixes") or {"count": 0, "tokens": 0}
    prefixes = [np.random.default_rng(seed_words(seed, 2, i)).integers(
        0, vocab, int(pre["tokens"])).tolist()
        for i in range(int(pre["count"]))]

    def tokens(caller: int, index: int, n: int) -> list:
        return np.random.default_rng(
            seed_words(seed, 3, caller, index)).integers(0, vocab, n).tolist()

    return {"prefixes": prefixes,
            "callers": [{"prefix": (c % len(prefixes)) if prefixes else None,
                         "sizes": dealt[c], "first_share": shares[c]}
                        for c in range(callers)],
            "tokens": tokens}


def token_rows(seed: int, rows: int, seq: int, vocab: int) -> list:
    """Training rows: ``rows`` sequences of ``seq`` seeded token ids."""
    rng = np.random.default_rng(seed_words(seed, 4))
    return [{"tokens": rng.integers(0, vocab, seq, dtype=np.int32)}
            for _ in range(rows)]
