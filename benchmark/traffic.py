"""The one general traffic generator. JAX-free, and a pure function of the seed.

A traffic mix is a JSON file of parameters under ``traffic/``; this
module turns (parameters, seed) into sessions of requests and, for an
open loop, the times they are due. The same seed gives the same bytes.

Parameters (every key a mix may set):

``loop``            ``"open"`` (a schedule fixed before the run, fired
                    whether or not earlier requests have ended) or
                    ``"closed"`` (``clients`` callers, each sending its
                    next request when its last one ended).
``rate_rps``        open loop: mean sessions per second.
``arrivals``        open loop: ``{"process": "poisson"}``: a Poisson process
                    conditioned on its count, that is ``round(rate x
                    horizon)`` arrivals at independent uniform times. The
                    gaps are exponential as in any Poisson process; the
                    number of requests a run offers does not vary with
                    the seed (a free count swings by 1/sqrt(n), 11% at 80
                    requests, and the tails with it). Or
                    ``{"process": "bursts", "size": [lo, hi],
                    "within_s": w}``: bursts of lo..hi sessions spread
                    over ``w`` seconds, burst starts Poisson at
                    ``rate_rps`` / mean burst size, so the mean rate is
                    the same.
``clients``         closed loop: number of callers.
``prompt``          ``{"head": text, "body_tokens": dist, "tail": text}``.
                    ``head`` is what every prompt shares (the co-pilot's
                    template head); the body is seeded random printable
                    ASCII, different in every request, one byte a token.
``output_tokens``   dist of ``num_predict``.
``session``         absent: one request a session. Else ``{"turns":
                    [lo, hi], "system_tokens": n, "think_s": [lo, hi]}``:
                    every session of a run shares one seeded system text
                    of n bytes after the head; turn k's prompt is the
                    system text and the bodies of turns 1..k; turn k+1 is
                    due ``think_s`` after turn k ended.
``options``         further Ollama options sent with every request
                    (``{"temperature": 0}`` is greedy).
``stratify``        n > 1: lengths are drawn by stratified sampling in
                    blocks of n consecutive sessions: each block takes one
                    draw from each of n equal-probability slices of the
                    dist, in a seeded order. The distribution is the
                    same; the work a run offers hardly varies with the
                    seed. Absent or 1: independent draws.

``design_seed``     absent: arrival times, length quantiles and their
                    order all come from the run's ``--seed``. Set: they
                    come from this seed, the same in every run, and the
                    run's seed only perturbs them (``seed_jitter``) and
                    picks the bodies' bytes. Why a mix would want that:
                    where a window holds a few dozen requests, two free
                    draws of the same mix differ by more than any
                    regression a bound could name (PERF.md, PR 22:
                    13-30% between seeds at 40 requests a window), and
                    the benchmark could resolve nothing. Every run then
                    replays one realisation of the mix, each slightly
                    displaced.
``seed_jitter``     with ``design_seed``: ``{"arrival_s": a, "length":
                    f}``: each arrival moves by up to +-a seconds and
                    each length by up to +-f of itself, by the run's
                    seed.

A dist is ``{"dist": "lognormal", "median": m, "sigma": s, "min": a,
"max": b}``, ``{"dist": "uniform", "min": a, "max": b}`` or
``{"dist": "fixed", "value": v}``.
"""

from __future__ import annotations

import math
import random
import statistics
from dataclasses import dataclass

# Printable ASCII: one byte, one token under the byte tokenizer.
_ALPHABET = "".join(chr(c) for c in range(0x20, 0x7F))


@dataclass(frozen=True)
class Turn:
    prompt: str
    num_predict: int
    think_s: float      # wait after the previous turn ended (0 for turn 1)


@dataclass(frozen=True)
class Session:
    index: int
    turns: tuple        # of Turn


_NORMAL = statistics.NormalDist()


def draw(dist: dict, u: float) -> float:
    """The dist's value at quantile ``u`` in (0, 1)."""
    kind = dist.get("dist", "fixed")
    if kind == "fixed":
        return float(dist["value"])
    if kind == "uniform":
        return dist["min"] + u * (dist["max"] - dist["min"])
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
        return min(max(x, dist["min"]), dist["max"])
    raise ValueError(f"unknown dist {kind!r}")


class _Quantiles:
    """The quantiles at which one session draws its lengths: uniform, or
    one slice of its block's stratification per stream of draws."""

    def __init__(self, traffic: dict, seed: int, index: int,
                 rng: random.Random) -> None:
        self.run_rng = rng
        self.seed = traffic.get("design_seed", seed)
        self.rng = (rng if self.seed == seed else
                    random.Random(f"{self.seed}/session/{index}"))
        self.jitter = (float(traffic.get("seed_jitter", {}).get("length", 0))
                       if "design_seed" in traffic else 0.0)
        self.n = int(traffic.get("stratify") or 1)
        self.block, self.pos = index // self.n, index % self.n
        self.streams = 0

    def next(self) -> float:
        u = min(max(self.rng.random(), 1e-9), 1 - 1e-9)
        if self.n <= 1:
            return u
        order = list(range(self.n))
        random.Random(f"{self.seed}/strata/{self.block}/{self.streams}"
                      ).shuffle(order)
        self.streams += 1
        return (order[self.pos] + u) / self.n


def draw_int(dist: dict, q: "_Quantiles") -> int:
    x = draw(dist, q.next())
    if q.jitter:
        lo = dist.get("min", dist.get("value", x))
        hi = dist.get("max", dist.get("value", x))
        x = min(max(x * (1 + q.run_rng.uniform(-q.jitter, q.jitter)), lo), hi)
    return int(round(x))


def _text(n: int, rng: random.Random) -> str:
    return "".join(rng.choices(_ALPHABET, k=n))


def make_session(traffic: dict, seed: int, index: int) -> Session:
    """Session ``index`` of the run: a pure function of (traffic, seed,
    index), whichever client sends it and whenever."""
    rng = random.Random(f"{seed}/session/{index}")
    q = _Quantiles(traffic, seed, index, rng)
    p = traffic["prompt"]
    head, tail = p.get("head", ""), p.get("tail", "")
    sess = traffic.get("session")
    if not sess:
        body = _text(draw_int(p["body_tokens"], q), rng)
        return Session(index, (Turn(head + body + tail,
                                    draw_int(traffic["output_tokens"], q),
                                    0.0),))
    system = _text(int(sess["system_tokens"]),
                   random.Random(f"{seed}/system"))
    n_turns = rng.randint(*sess["turns"])
    turns, history = [], ""
    for k in range(n_turns):
        history += _text(draw_int(p["body_tokens"], q), rng)
        turns.append(Turn(head + system + history + tail,
                          draw_int(traffic["output_tokens"], q),
                          0.0 if k == 0 else rng.uniform(*sess["think_s"])))
    return Session(index, tuple(turns))


def arrival_times(traffic: dict, seed: int, horizon_s: float) -> list:
    """Open loop: the times at which sessions are due, in [0, horizon)."""
    rate = float(traffic["rate_rps"])
    if rate <= 0 or horizon_s <= 0:
        raise ValueError("rate_rps and the horizon must be positive")
    rng = random.Random(f"{traffic.get('design_seed', seed)}/arrivals")
    arr = traffic.get("arrivals", {"process": "poisson"})
    out: list = []
    t = 0.0
    if arr["process"] == "poisson":
        n = max(1, int(round(rate * horizon_s)))
        out = sorted(rng.uniform(0.0, horizon_s) for _ in range(n))
    elif arr["process"] == "bursts":
        lo, hi = arr["size"]
        burst_rate = rate / ((lo + hi) / 2.0)
        while True:
            t += rng.expovariate(burst_rate)
            if t >= horizon_s:
                break
            out.extend(t + rng.uniform(0.0, arr["within_s"])
                       for _ in range(rng.randint(lo, hi)))
        out = sorted(x for x in out if x < horizon_s)
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    jitter = (float(traffic.get("seed_jitter", {}).get("arrival_s", 0))
              if "design_seed" in traffic else 0.0)
    if jitter:
        run_rng = random.Random(f"{seed}/arrival-jitter")
        out = sorted(min(max(x + run_rng.uniform(-jitter, jitter), 0.0),
                         horizon_s * (1 - 1e-9)) for x in out)
    return out


def describe(traffic: dict, seed: int, n: int = 2000) -> dict:
    """Length histogram of the first ``n`` sessions (an earlier line of
    the run's output, so a reader sees what was sent)."""
    prompts, outs = [], []
    for i in range(n):
        for t in make_session(traffic, seed, i).turns:
            prompts.append(len(t.prompt))
            outs.append(t.num_predict)
    prompts.sort()
    outs.sort()

    def q(xs, p):
        return xs[min(len(xs) - 1, int(p * (len(xs) - 1)))]
    return {"prompt_bytes": {k: q(prompts, v) for k, v in
                             (("min", 0), ("p50", .5), ("p95", .95),
                              ("max", 1))},
            "num_predict": {k: q(outs, v) for k, v in
                            (("min", 0), ("p50", .5), ("p95", .95),
                             ("max", 1))},
            "mean_prompt_bytes": sum(prompts) / len(prompts),
            "mean_num_predict": sum(outs) / len(outs)}
