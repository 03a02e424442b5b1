"""One generator for every traffic mix: closed-loop clients whose requests
are drawn from a mix file's parameters, with token ids from the run's seed.

Each client first sends its opener, a warm-up request whose sizes the mix
file fixes so that set-up runs every program shape the window will use;
openers finish before the window opens, and no metric or check reads them.
Then it sends its stream of mix requests.

Lengths come from a fixed grid of the mix's distribution, the same for
every seed. The grid is cut into ``stratum`` equal bands; stream ``c``
takes its ``k``-th prompt from band ``(c + k) % stratum`` and its reply
from another band, starting near each band's middle, so at any moment the
clients' requests span the bands and every stream runs through the whole
grid. The seed draws only the token ids, and so the routing. A window that
holds a request or two per client would otherwise measure whichever sizes
the seed put first.
"""
from __future__ import annotations

from statistics import NormalDist
from typing import List, Tuple

import numpy as np


def grid(dist: dict, n: int) -> List[int]:
    """``n`` lengths at the midpoints of ``n`` equal quantile bands of a
    lognormal given by its ``mean`` and ``sigma`` (the log's standard
    deviation), clipped to [min, max], ascending."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = NormalDist()
    median = dist["mean"] * np.exp(-dist["sigma"] ** 2 / 2)
    out = [median * np.exp(dist["sigma"] * z.inv_cdf((i + 0.5) / n))
           for i in range(n)]
    return [int(min(max(round(v), dist["min"]), dist["max"])) for v in out]


def streams(mix: dict) -> List[List[Tuple[int, int]]]:
    """Per stream, the (prompt tokens, output tokens) of its requests after
    its opener, the same for every seed."""
    n, s = mix["requests_per_client"], mix["stratum"]
    if n % s:
        raise ValueError("requests_per_client must be a multiple of stratum")
    m = n // s
    prompts = grid(mix["prompt_tokens"], n)
    outputs = grid(mix["output_tokens"], n)
    out = []
    for c in range(mix["clients"]):
        plan = []
        for k in range(n):
            r, q = k % s, k // s
            p = prompts[((c + r) % s) * m + (q + m // 2 + c) % m]
            o = outputs[((c + 3 * r + 1) % s) * m + (q + m // 2 - c) % m]
            plan.append((p, o))
        out.append(plan)
    return out


def client_plans(mix: dict) -> List[List[Tuple[int, int]]]:
    """Per client, the (prompt tokens, output tokens) of its requests in
    the order it sends them: its opener, then its stream."""
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    return [[tuple(opener)] + stream
            for opener, stream in zip(mix["openers"], streams(mix))]


def prompt_tokens(seed: int, client: int, index: int, length: int,
                  vocab: int) -> List[int]:
    """Token ids of one prompt, uniform over the vocabulary."""
    rng = np.random.default_rng([seed, client, index, 1])
    return rng.integers(0, vocab, length).tolist()


def max_tokens(mix: dict) -> int:
    """The longest sequence any request of the mix can reach."""
    return (max(mix["prompt_tokens"]["max"], *(p for p, _ in mix["openers"]))
            + max(mix["output_tokens"]["max"],
                  *(o for _, o in mix["openers"])))
