"""The one generator of serving traffic, driven by a mix's data file.

A mix fixes its arrivals and the size of each request from its own
``shape_seed``, so every ``--seed`` sends the same work at the same
times: the seed draws only the prompt tokens (and the run's weights).
Where the seed also reordered the arrivals, the tail of time to first
token moved by a quarter from seed to seed, against a few percent between
two runs of one seed.  Arrivals are open loop: each request is sent at
its scheduled time, whether or not earlier ones have finished.

Mix keys (``bench/traffic/<mix>.json``)::

    "rate_per_s"   mean arrival rate (Poisson: N uniform arrival times
                   in the window, N = round(rate * seconds))
    "prompt", "output"   {"median", "sigma", "min", "max"}: lognormal
                   lengths, clipped
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np


def _lognormal(rng: np.random.Generator, spec: Dict[str, float], n: int) -> np.ndarray:
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def schedule(mix: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> List[Dict[str, Any]]:
    """Requests due in a window of ``seconds``: ``{"t", "prompt",
    "max_tokens"}`` sorted by ``t`` (seconds from the window's start)."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    shape = np.random.default_rng(mix["shape_seed"])
    arrivals = np.sort(shape.uniform(0.0, seconds, n))
    prompt_lens = _lognormal(shape, mix["prompt"], n)
    output_lens = _lognormal(shape, mix["output"], n)

    rng = np.random.default_rng(seed % (2 ** 63))
    return [{"t": float(arrivals[i]),
             "prompt": rng.integers(0, vocab, int(prompt_lens[i])).tolist(),
             "max_tokens": int(output_lens[i])} for i in range(n)]
