"""The one traffic generator. A traffic mix is a data file under
benchmarks/traffic/; this module turns it and a seed into work.

After paddle_tpu/serving/loadgen.py (`Scenario`): the same length grammar and
arrival processes, stdlib only. Two things differ, both for steadiness of a
run that lasts seconds:

- Draws are STRATIFIED. Every block of `block` requests holds the same set of
  lengths (the distribution's quantiles at (k + 0.5) / block) and the same set
  of exponential gaps, in an order the seed decides. So every seed offers the
  same work at the same mean rate and only the order changes; a window sees
  the distribution's tail every time and not only when a draw happens to land
  there.
- Timing is not the generator's business. It gives each request the instant
  it is DUE; the runner measures from that instant, not from `submit_ts`.
"""
from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import List

ARRIVALS = ("poisson", "spike", "backlog")
LENGTH_DISTS = ("fixed", "lognormal", "uniform", "choice")


def _quantile(spec: dict, p: float) -> int:
    """The length at quantile p of one length spec."""
    dist = spec.get("dist", "fixed")
    if dist == "fixed":
        return int(spec["value"])
    lo, hi = int(spec.get("min", 1)), int(spec.get("max", 1 << 30))
    if dist == "lognormal":
        v = float(spec["median"]) * math.exp(
            float(spec.get("sigma", 0.5)) * NormalDist().inv_cdf(p))
    elif dist == "uniform":
        v = lo + (hi - lo) * p
    elif dist == "choice":
        values = spec["values"]
        weights = spec.get("weights") or [1.0] * len(values)
        acc, total = 0.0, float(sum(weights))
        v = values[-1]
        for val, w in zip(values, weights):
            acc += w / total
            if p <= acc:
                v = val
                break
    else:
        raise ValueError(f"unknown length dist {dist!r} "
                         f"(expected one of {LENGTH_DISTS})")
    return max(lo, min(hi, int(round(v))))


def _block(spec: dict, block: int, rnd: random.Random) -> List[int]:
    out = [_quantile(spec, (k + 0.5) / block) for k in range(block)]
    rnd.shuffle(out)
    return out


def _gap_block(block: int, rnd: random.Random) -> List[float]:
    """`block` exponential gaps of mean exactly 1, in seeded order."""
    gaps = [-math.log(1.0 - (k + 0.5) / block) for k in range(block)]
    scale = block / sum(gaps)
    gaps = [g * scale for g in gaps]
    rnd.shuffle(gaps)
    return gaps


def _rate_pieces(arrival: dict, lead_in_s: float, window_s: float):
    """[(t_from, t_to, rate)] over scenario time; the last piece is open."""
    rate = float(arrival["rate_rps"])
    if arrival["process"] == "poisson":
        return [(0.0, math.inf, rate)]
    # spike: `spike_factor` times the rate over a share of the WINDOW
    a = lead_in_s + float(arrival.get("spike_from", 1 / 3)) * window_s
    b = lead_in_s + float(arrival.get("spike_to", 2 / 3)) * window_s
    return [(0.0, a, rate), (a, b, rate * float(arrival["spike_factor"])),
            (b, math.inf, rate)]


def requests(traffic: dict, seed: int, window_s: float, vocab: int,
             count: int = 0) -> List[dict]:
    """Rows {"i", "due", "prompt", "max_new", "seed"} in arrival order.

    Open loop (`poisson`, `spike`): `due` is seconds after the start of the
    lead-in; rows cover lead_in_s + window_s + drain_s, so load is offered
    at the same rate until the run ends. Closed loop (`backlog`): `due` is
    0.0 for every row and `count` rows are made; the runner keeps the queue
    `depth` deep. `prompt` is a list of token ids; with `shared_prefix`
    {"groups": g, "len": n} the first n ids of a prompt are its group's.
    The first `stagger` requests get output budgets spread evenly up to
    their drawn one, so that slots filled together do not retire together.
    """
    arrival = traffic["arrival"]
    proc = arrival["process"]
    if proc not in ARRIVALS:
        raise ValueError(f"unknown arrival process {proc!r} "
                         f"(expected one of {ARRIVALS})")
    block = int(traffic.get("block", 64))
    rnd = random.Random(f"bench-traffic:{int(seed)}")
    lead_in = float(traffic.get("lead_in_s", 0.0))
    horizon = lead_in + window_s + float(traffic.get("drain_s", 0.0))

    dues: List[float] = []
    if proc == "backlog":
        dues = [0.0] * int(count)
    else:
        pieces = _rate_pieces(arrival, lead_in, window_s)
        t, piece = 0.0, 0
        while t < horizon:
            for gap in _gap_block(block, rnd):   # gap in units of 1 / rate
                while True:
                    a, b, rate = pieces[piece]
                    if t + gap / rate <= b:
                        t += gap / rate
                        break
                    gap -= (b - t) * rate        # spend the piece, go on
                    t, piece = b, piece + 1
                if t >= horizon:
                    break
                dues.append(t)
            if len(dues) > 1_000_000:
                raise ValueError("over a million arrivals: rate x horizon "
                                 "is mistyped")

    n = len(dues)
    prompt_lens: List[int] = []
    max_news: List[int] = []
    while len(prompt_lens) < n:
        prompt_lens += _block(traffic["prompt_len"], block, rnd)
        max_news += _block(traffic["max_new"], block, rnd)
    stagger = int(traffic.get("stagger", 0))
    shared = traffic.get("shared_prefix")
    prefixes = []
    if shared:
        prefixes = [[rnd.randrange(vocab) for _ in range(int(shared["len"]))]
                    for _ in range(int(shared["groups"]))]
    rows = []
    for i in range(n):
        plen, new = prompt_lens[i], max_news[i]
        if i < stagger:
            new = max(1, round(new * (i + 1) / stagger))
        head = prefixes[rnd.randrange(len(prefixes))][:plen] if shared else []
        prompt = head + [rnd.randrange(vocab)
                         for _ in range(plen - len(head))]
        rows.append({"i": i, "due": dues[i], "prompt": prompt,
                     "max_new": new, "seed": i})
    return rows


def batches(traffic: dict, seed: int, vocab: int, n_chips: int):
    """Training data: `distinct_batches` pairs (ids, labels) of int64
    [batch_per_chip * n_chips, seq_len], labels the ids shifted left by one
    (the convention of bench.py and chip_smoke.py)."""
    import numpy as np

    rng = np.random.default_rng(int(seed))
    shape = (int(traffic["batch_per_chip"]) * n_chips,
             int(traffic["seq_len"]))
    out = []
    for _ in range(int(traffic.get("distinct_batches", 1))):
        ids = rng.integers(0, vocab, shape, dtype=np.int64)
        out.append((ids, np.roll(ids, -1, 1)))
    return out
