"""Wall-clock traffic from a traffic file and ``--seed``.

One general generator reads every traffic file under ``bench/traffic/``.
A file gives the loop (``open`` with an arrival process, or ``closed``
with a number of clients), the prompt and output length distributions,
and the serving sizes the cell runs at.

Every seed gets the same multiset of lengths and of arrival gaps, in
another order: lengths are the distribution's quantiles at
``(i + 0.5) / n`` and gaps the unit exponential's, each list put in a
uniformly random order drawn from the seed.  Any order is as likely as
any other, so long prompts and short gaps bunch together as often as
independent draws of the same values would; what the seed cannot change
is how much work a run offers in all.  The seed also draws the token ids.

Arrival processes, in seconds from the opening of the window:

* ``poisson``: exponential gaps at ``rate_per_s``.
* ``bursty``: the shape of ``serving/trace.bursty_arrivals`` on the wall
  clock.  The rate swings sinusoidally with ``period_s`` between
  ``rate_per_s`` (trough) and ``burst * rate_per_s`` (peak); unit-rate
  gaps are mapped through the inverse of the cumulative rate.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

LENGTH_DISTS = ("lognormal", "loguniform", "uniform")
PROCESSES = ("poisson", "bursty")
# requests drawn per open-loop run: the arrival gaps of this many sum to
# OPEN_MARGIN times the window, so every permutation covers it
OPEN_MARGIN = 1.3
OPEN_EXTRA = 8
# closed loop: requests per client in the shared list before it wraps
CLOSED_PER_CLIENT = 8


@dataclasses.dataclass
class Item:
    """One request of the schedule: ``due`` is its time in seconds after
    the window opens (open loop; 0 for a closed loop's list)."""
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    due: float = 0.0


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The `n` stratified quantiles of a length distribution, as ints
    clipped to ``[min, max]`` (sorted ascending)."""
    dist = spec["dist"]
    lo, hi = int(spec["min"]), int(spec["max"])
    if not 1 <= lo <= hi:
        raise ValueError(f"length range [{lo}, {hi}]")
    u = (np.arange(n) + 0.5) / n
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif dist == "loguniform":
        x = np.exp(math.log(lo) + u * (math.log(hi + 1) - math.log(lo)))
    elif dist == "uniform":
        x = lo + u * (hi + 1 - lo)
    else:
        raise ValueError(f"length dist {dist!r} not in {LENGTH_DISTS}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def unit_gaps(n: int) -> np.ndarray:
    """Stratified quantiles of the unit exponential (mean 1)."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u)


def bursty_times(unit_times: np.ndarray, rate: float, burst: float,
                 period: float) -> np.ndarray:
    """Map unit-rate arrival times through the inverse cumulative rate
    ``Lambda(t) = integral of rate * (1 + (burst - 1) * phase(t))`` with
    ``phase(t) = (1 + sin(2 pi t / period)) / 2``."""
    if burst < 1.0 or period <= 0 or rate <= 0:
        raise ValueError((rate, burst, period))
    a = 1.0 + (burst - 1.0) / 2.0             # mean multiplier
    b = (burst - 1.0) / 2.0
    horizon = float(unit_times[-1]) / (rate * a) + 2 * period
    t = np.linspace(0.0, horizon, int(horizon / period * 2048) + 2)
    cum = rate * (a * t + b * period / (2 * np.pi)
                  * (1.0 - np.cos(2 * np.pi * t / period)))
    return np.interp(unit_times, cum, t)


def request_count(traffic: dict, seconds: float) -> int:
    if traffic["loop"] == "closed":
        return int(traffic["clients"]) * CLOSED_PER_CLIENT
    arr = traffic["arrivals"]
    mean_rate = float(arr["rate_per_s"])
    if arr["process"] == "bursty":
        mean_rate *= 1.0 + (float(arr["burst"]) - 1.0) / 2.0
    return int(math.ceil(mean_rate * seconds * OPEN_MARGIN)) + OPEN_EXTRA


def schedule(traffic: dict, seed: int, seconds: float,
             vocab: int) -> list[Item]:
    """The run's requests from the traffic file and the seed.  Open loop:
    sorted by ``due``.  Closed loop: the shared list clients take from."""
    n = request_count(traffic, seconds)
    rng = np.random.default_rng(seed)
    prompts = rng.permutation(quantiles(traffic["prompt"], n))
    outputs = rng.permutation(quantiles(traffic["output"], n))
    due = np.zeros(n)
    if traffic["loop"] == "open":
        arr = traffic["arrivals"]
        if arr["process"] not in PROCESSES:
            raise ValueError(f"process {arr['process']!r} not in {PROCESSES}")
        times = np.cumsum(rng.permutation(unit_gaps(n)))
        rate = float(arr["rate_per_s"])
        if arr["process"] == "poisson":
            due = times / rate
        else:
            due = bursty_times(times, rate, float(arr["burst"]),
                               float(arr["period_s"]))
    elif traffic["loop"] != "closed":
        raise ValueError(f"loop {traffic['loop']!r}")
    items = []
    for i in range(n):
        ids = rng.integers(0, vocab, int(prompts[i]), dtype=np.int32)
        items.append(Item(rid=i, prompt=ids, max_new_tokens=int(outputs[i]),
                          due=float(due[i])))
    return items


def max_request_tokens(traffic: dict) -> int:
    """The most KV positions one request of this traffic can hold."""
    return int(traffic["prompt"]["max"]) + int(traffic["output"]["max"])
