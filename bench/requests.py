"""Open-loop request traffic from a traffic file's parameters.

Every seed gets the same work: a block of ``n`` requests has prompt and
output lengths at the ``(i + 0.5) / n`` quantiles of their clipped
lognormals, and inter-arrival gaps at the same quantiles of an exponential
(a Poisson process), scaled so that the block spans exactly ``n / rate``
seconds.  The seed only orders them (lengths and gaps are shuffled apart,
so which prompt meets which answer, and when, changes) and draws the token
ids.  A second block follows the first, so the load goes on after the
window.  Where the traffic gives ``max_total``, an output is cut short so
that prompt and output together stay within it.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np

from bench import seeds


@dataclass
class Planned:
    uid: int
    due: float              # seconds after the window opens
    prompt: list[int]
    max_new: int
    in_window: bool
    # filled in by the driver
    submitted: float | None = None
    token_times: list[float] = field(default_factory=list)
    out: list[int] = field(default_factory=list)


def _lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(rate: float, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g / g.sum() * (n / rate)


def plan(traffic: dict, vocab: int, seed: int,
         window_s: float) -> list[Planned]:
    """The requests due in ``[0, window_s)``, then one more block of load
    after it."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * window_s)))
    rng = seeds.numpy_rng(seed, "serve.requests")
    prompts = _lognormal_quantiles(traffic["prompt"], n)
    outputs = _lognormal_quantiles(traffic["output"], n)
    gaps = _gaps(rate, n)
    out: list[Planned] = []
    t = 0.0
    total = int(traffic.get("max_total", 2**62))
    for b in range(2):
        p, o, g = rng.permutation(prompts), rng.permutation(outputs), \
            rng.permutation(gaps)
        for i in range(n):
            toks = rng.integers(0, vocab, int(p[i])).tolist()
            out.append(Planned(uid=len(out), due=t * window_s / (n / rate),
                               prompt=toks,
                               max_new=int(min(o[i], total - p[i])),
                               in_window=b == 0))
            t += g[i]
    return out
