"""The one general traffic generator.  A traffic mix is a data file
(`cellbench/traffic/<name>.json`) whose `generator` names a plan class
here, or a module `cellbench/generators/<name>.py` with a `Plan` of
the same shape, so that new traffic is new files only.

Every seed gets the SAME set of sizes and inter-arrival gaps in
another order: lengths are the evenly spaced quantiles of the stated
distribution (not draws), and the seed permutes them and makes the
token ids.  A run's work then differs between seeds only in its
order, which keeps runs on different seeds comparable.

A plan is driven by the harness on `time.monotonic`'s clock:
``start(t0)``, then ``due(now)`` hands out the items whose time has
come, and ``on_finish(item, now)`` tells it a request has completed
(a closed loop sends that client's next one then).  A plan also says
what it can draw — ``prompt_range`` (shortest, longest prompt),
``output_max`` and ``total_max`` (longest prompt + answer) — which is
all the harness reads of a mix: it warms the prefill buckets in that
range and pads the reference to that length, and never looks inside a
traffic file.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Item:
    """One request to send: timed from ``due``."""
    index: int
    prompt: List[int]
    max_new: int
    due: float = 0.0
    client: int = -1


def _norm_ppf(p):
    """Inverse of the standard normal distribution (Acklam's rational
    approximation, relative error < 1.2e-9)."""
    a = (-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00)
    if p < 0.02425:
        q = math.sqrt(-2 * math.log(p))
        return ((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4])
                 * q + c[5])
                / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1))
    if p > 1 - 0.02425:
        return -_norm_ppf(1 - p)
    q = p - 0.5
    r = q * q
    return ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
             + a[5]) * q
            / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r + b[4])
               * r + 1))


def length_set(spec: dict, n: int) -> np.ndarray:
    """The n evenly spaced quantiles of a length distribution, as whole
    numbers clipped to ``[min, max]``: `lognormal` (median, sigma) or
    `uniform`."""
    ps = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([_norm_ppf(float(p)) for p in ps])
        xs = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        xs = spec["min"] + ps * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(xs), spec["min"], spec["max"]).astype(int)


def gap_set(arrivals: dict, n: int, rate: float) -> np.ndarray:
    """The n evenly spaced quantiles of the inter-arrival distribution,
    rescaled to the mean ``1 / rate`` exactly: `poisson` (exponential
    gaps) or `gamma` with a coefficient of variation (bursts)."""
    ps = (np.arange(n) + 0.5) / n
    if arrivals["process"] == "poisson":
        xs = -np.log1p(-ps)
    elif arrivals["process"] == "gamma":
        # Wilson-Hilferty: quantiles of a gamma with shape 1 / cv**2
        k = 1.0 / arrivals["cv"] ** 2
        z = np.array([_norm_ppf(float(p)) for p in ps])
        xs = k * np.maximum(1 - 1 / (9 * k) + z / (3 * math.sqrt(k)),
                            0.0) ** 3
    else:
        raise ValueError(f"unknown arrival process "
                         f"{arrivals['process']!r}")
    return xs / xs.mean() / rate


class Lengths:
    """What a plan of independent prompt and answer lengths can draw
    (`prompt` and `output` of the traffic file, capped together at
    `max_total`)."""

    def __init__(self, traffic: dict):
        p, o = traffic["prompt"], traffic["output"]
        self.prompt_range = (int(p["min"]), int(p["max"]))
        self.output_max = int(o["max"])
        self.total_max = min(int(p["max"]) + int(o["max"]),
                             int(traffic["max_total"]))


def _sizes(traffic: dict, n: int, rng) -> tuple:
    prompts = rng.permutation(length_set(traffic["prompt"], n))
    outputs = rng.permutation(length_set(traffic["output"], n))
    cap = traffic["max_total"]
    outputs = np.maximum(np.minimum(outputs, cap - prompts), 1)
    return prompts, outputs


class OpenLoop(Lengths):
    """Independent users: requests are due on a schedule fixed before
    the run, whether or not earlier ones have finished.  The cell's
    ``load`` gives ``rate`` in requests a second.  The arrivals are
    STRATIFIED: the gaps are the evenly spaced quantiles of the stated
    process in an order drawn from the seed, so every window holds the
    same number of requests and no seed draws a burst beyond them."""

    def __init__(self, traffic: dict, load: dict, seed: int,
                 vocab: int, horizon_s: float):
        super().__init__(traffic)
        rng = np.random.default_rng(int(seed))
        rate = float(load["rate"])
        n = max(int(math.ceil(rate * horizon_s)), 1)
        prompts, outputs = _sizes(traffic, n, rng)
        offsets = np.cumsum(rng.permutation(
            gap_set(traffic["arrivals"], n, rate)))
        self.items = [
            Item(i, rng.integers(0, vocab, int(p)).tolist(), int(o),
                 due=float(t))
            for i, (p, o, t) in enumerate(zip(prompts, outputs, offsets))]
        self._next = 0

    def start(self, t0: float) -> None:
        for it in self.items:
            it.due += t0

    def due(self, now: float) -> List[Item]:
        out = []
        while (self._next < len(self.items)
               and self.items[self._next].due <= now):
            out.append(self.items[self._next])
            self._next += 1
        return out

    def next_due(self) -> Optional[float]:
        if self._next < len(self.items):
            return self.items[self._next].due
        return None

    def on_finish(self, item: Item, now: float) -> None:
        pass


class ClosedLoop(Lengths):
    """Callers that each wait for their reply: ``load["clients"]``
    clients, each sending its next request the moment its last one
    completes.  The first request of each client is cut to a fraction
    of its output length (the fractions evenly spaced over the
    clients), so that the clients start out of step as they would be
    in a job that has been running.

    The plan cannot run out: ``load["rounds"]`` is the size of a block
    (clients x rounds requests, their lengths stratified over the
    block), and when a client has used up its list a further block is
    drawn from the same generator — however many requests a fast
    program completes, each is a fresh one.  ``wrapped`` counts
    requests handed out a second time, which fails a run's `correct`:
    this plan leaves it at 0."""

    def __init__(self, traffic: dict, load: dict, seed: int,
                 vocab: int, horizon_s: float):
        super().__init__(traffic)
        self._traffic, self._vocab = traffic, vocab
        self._rng = np.random.default_rng(int(seed))
        self.clients = int(load["clients"])
        self._rounds = int(load["rounds"])
        self._lists: List[List[Item]] = [[] for _ in range(self.clients)]
        self._extend()
        self._pos = [0] * self.clients
        self._ready: List[Item] = []
        self.wrapped = 0

    def _extend(self) -> None:
        """One more block on every client's list."""
        rng, clients = self._rng, self.clients
        n = clients * self._rounds
        base = sum(len(items) for items in self._lists)
        prompts, outputs = _sizes(self._traffic, n, rng)
        first_cut = None
        if base == 0:
            first_cut = rng.permutation(
                (np.arange(clients) + 0.5) / clients)
        for c in range(clients):
            for r in range(self._rounds):
                i = r * clients + c
                out = int(outputs[i])
                if first_cut is not None and r == 0:
                    out = max(int(out * first_cut[c]), 1)
                self._lists[c].append(Item(
                    base + i,
                    rng.integers(0, self._vocab, int(prompts[i])).tolist(),
                    out, client=c))

    def _take(self, c: int, due: float) -> None:
        if self._pos[c] >= len(self._lists[c]):
            self._extend()
        src = self._lists[c][self._pos[c]]
        self._pos[c] += 1
        self._ready.append(dataclasses.replace(src, due=due))

    def start(self, t0: float) -> None:
        for c in range(self.clients):
            self._take(c, t0)

    def due(self, now: float) -> List[Item]:
        out, self._ready = self._ready, []
        return out

    def next_due(self) -> Optional[float]:
        return self._ready[0].due if self._ready else None

    def on_finish(self, item: Item, now: float) -> None:
        self._take(item.client, now)


PLANS = {"open_loop": OpenLoop, "closed_loop": ClosedLoop}


def make_plan(traffic: dict, load: dict, seed: int, vocab: int,
              horizon_s: float):
    """The plan a traffic file names: one of `PLANS`, or `Plan` of
    `cellbench/generators/<generator>.py`."""
    name = traffic["generator"]
    cls = PLANS.get(name)
    if cls is None:
        cls = importlib.import_module(f"cellbench.generators.{name}").Plan
    return cls(traffic, load, seed, vocab, horizon_s)
