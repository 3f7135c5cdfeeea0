"""Traffic from a seed: arrival times, prompt and output lengths, tokens.

One general generator reads a traffic file (``traffic/<name>.json``) and
the cell's rate.  Arrivals, prompt lengths and output lengths come from
separate streams (``np.random.SeedSequence.spawn``), so the timing of a
request never correlates with its shape.

The traffic is made segment by segment (the warm-up, then the measured
window).  A segment of ``T`` seconds at rate ``r`` holds ``n = round(r*T)``
requests:

* arrivals: ``n`` due times drawn i.i.d. uniform over the segment and
  sorted, which is a Poisson process of rate ``r`` conditioned on its
  count ``n``: the gaps are as bursty as Poisson gaps;
* lengths: the ``n`` quantiles ``(i + 0.5) / n`` of each law, in a drawn
  order.

That schedule (due times, and which size comes when) is drawn from the
segment's own streams, the same for every seed: at a few tens of requests
a window, an order drawn by the seed changed the work itself (which long
prompt queues behind which), so TTFT read twice as high on one seed as on
another.  The seed draws every token id, and the weights.

Laws (the traffic file's ``prompt`` and ``output`` entries):

* ``lognormal``: ``median`` and ``sigma`` of log-length, clipped to
  ``[min, max]``.

``percentile`` is a copy of the program's
``repro.serving.metrics.percentile``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Sequence, Tuple

import numpy as np

_SCHEDULE = ("arrivals", "prompt", "output")


def streams(seed: int, segment: int = 0) -> dict:
    """The schedule's generators of ``segment`` (the same for every seed)
    and the generator of ``seed``'s token ids there."""
    kids = np.random.SeedSequence([segment]).spawn(len(_SCHEDULE))
    out = {k: np.random.default_rng(s) for k, s in zip(_SCHEDULE, kids)}
    out["tokens"] = np.random.default_rng([int(seed), segment])
    return out


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(law: dict, n: int, rng) -> np.ndarray:
    """The ``n`` quantiles of ``law``, in the order ``rng`` draws."""
    if law["law"] != "lognormal":
        raise ValueError(f"unknown length law {law['law']!r}")
    z = np.array([NormalDist().inv_cdf(float(q)) for q in quantiles(n)])
    x = np.exp(math.log(law["median"]) + law["sigma"] * z)
    x = np.clip(np.rint(x), law["min"], law["max"]).astype(np.int64)
    return rng.permutation(x)


def arrivals(n: int, span: float, rng) -> np.ndarray:
    """``n`` due times of a Poisson process conditioned on ``n`` arrivals
    in ``[0, span)``."""
    return np.sort(rng.uniform(0.0, span, n))


@dataclass
class Job:
    """One request as the generator makes it."""
    req_id: int
    prompt: List[int]
    max_new_tokens: int
    due: float = 0.0            # seconds after the traffic starts


def segment(traffic: dict, rate: float, t0: float, span: float, vocab: int,
            seed: int, index: int, first_id: int = 0) -> List[Job]:
    """The open-loop requests due in ``[t0, t0 + span)``."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {traffic['arrivals']!r}")
    if rate <= 0:
        raise ValueError("rate must be positive")
    s = streams(seed, index)
    n = int(round(rate * span))
    due = t0 + arrivals(n, span, s["arrivals"])
    p = lengths(traffic["prompt"], n, s["prompt"])
    o = lengths(traffic["output"], n, s["output"])
    return [Job(first_id + i, s["tokens"].integers(0, vocab, int(p[i]))
                .tolist(), int(o[i]), float(due[i])) for i in range(n)]


def open_loop(traffic: dict, rate: float, spans: Sequence[float],
              vocab: int, seed: int) -> Tuple[List[Job], List[float]]:
    """Requests of consecutive segments of the given lengths; returns them
    and the segments' start times."""
    jobs, starts, t = [], [], 0.0
    for i, span in enumerate(spans):
        starts.append(t)
        jobs += segment(traffic, rate, t, span, vocab, seed, i, len(jobs))
        t += span
    return jobs, starts


def percentile(values: Sequence[float], q: float) -> float:
    """q-th percentile (0..100), linear interpolation between ranks."""
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q={q} outside [0, 100]")
    v = sorted(values)
    if not v:
        raise ValueError("percentile of empty sequence")
    if len(v) == 1:
        return float(v[0])
    rank = (len(v) - 1) * (q / 100.0)
    lo, hi = math.floor(rank), math.ceil(rank)
    return float(v[lo] + (v[hi] - v[lo]) * (rank - lo))
