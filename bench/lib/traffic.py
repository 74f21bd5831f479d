"""The one traffic generator: a mix file's parameters plus a seed give
the requests a run sends.

Every seed of a mix sends the same set of sizes and the same set of
gaps between arrivals, with other token ids.  The sizes are the
lognormal's quantiles at (i + 1/2) / n; the gaps are the exponential's
quantiles, so open-loop arrivals are Poisson-like at the mix's rate.
So two seeds never differ in how much work a run holds.

Open loop, the schedule is cut into blocks of `block` consecutive
arrivals.  Each quantile set (prompts, outputs, gaps) is cut into
`block` strata, and every block takes one member of each stratum, in
an order fixed by the mix, as are the member and how prompts meet
outputs.  So the work is spread evenly over the run, and every seed
sends the same lengths at the same due times: only token ids and
weights come from the seed.  Which requests fall inside the measured
window, which admissions share a step, and so the tokens and the gaps
the window holds, then do not depend on the seed.  (A seed that orders
the requests inside each block moved `out_tok_s` by ~1% and put the
95th-percentile token gap on another prefill-chunk level for some
seeds.)  Closed loop, each client block is one set of sizes in an order
of the seed's.

Mix file keys (bench/traffic/<name>.json):

    loop          "open" (arrivals on a schedule) | "closed" (clients)
    rate_per_s    open loop: mean arrivals per second
    block         open loop: arrivals per block of equal work (above)
    clients       closed loop: concurrent clients, no think time
    prompt_len    {"median", "sigma", "min", "max"} lognormal, clipped
    output_len    the same, for the tokens generated per request
    ramp_s        arrivals start this long before the measured window
    set_size      closed loop: the clients draw blocks of this many
                  requests, each block the same set of sizes in an order
                  of the seed's, so any run sends the same work
    pool          closed loop: requests generated (a multiple of
                  set_size, more than a window completes)
    trace_s       seconds of the window a --trace 1 run profiles
    prefix_cache  false where no prompt shares a prefix: the server is
                  built with the scheduler's prefix cache off
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import List

import numpy as np

_PAIRING_SEED = 0     # fixed: the pairing of sizes is part of the mix


@dataclass
class Request:
    """One request as generated: token ids and the time it is due,
    in seconds from the window's nominal start (negative in the ramp;
    None for a closed-loop request, which is due when its client's
    previous request completes)."""

    rid: int
    prompt: np.ndarray
    max_new: int
    due: float = None
    # filled by the harness
    sent: float = None
    admitted: float = None
    first: float = None
    token_times: List[float] = field(default_factory=list)
    handle: object = None


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """Independent stream `stream` of a seed of any size."""
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def lognormal_quantiles(n: int, p: dict) -> np.ndarray:
    """n lengths: the lognormal's quantiles at (i + 1/2) / n, rounded and
    clipped to [min, max]."""
    nd = NormalDist()
    mu = math.log(p["median"])
    q = np.array([math.exp(mu + p["sigma"] * nd.inv_cdf((i + 0.5) / n))
                  for i in range(n)])
    return np.clip(np.rint(q), p["min"], p["max"]).astype(np.int64)


def sizes(n: int, mix: dict, max_positions: int) -> np.ndarray:
    """(n, 2) prompt and output lengths: the fixed set for n requests.
    Outputs are cut so prompt + output <= max_positions."""
    pl = lognormal_quantiles(n, mix["prompt_len"])
    ol = lognormal_quantiles(n, mix["output_len"])
    ol = ol[np.random.default_rng(_PAIRING_SEED).permutation(n)]
    if int(pl.max()) >= max_positions:
        raise ValueError(f"prompt_len max {int(pl.max())} leaves no room "
                         f"for output under {max_positions} positions")
    ol = np.minimum(ol, max_positions - pl)
    return np.stack([pl, ol], 1)


def exp_gaps(n: int, rate: float) -> np.ndarray:
    """The exponential's quantiles at (i + 1/2) / n: n gaps of mean
    close to 1 / rate."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def n_open(mix: dict, seconds: float) -> int:
    return int(math.ceil(mix["rate_per_s"] * (mix["ramp_s"] + seconds)))


def open_schedule(mix: dict, seconds: float, max_positions: int):
    """(n, 2) prompt and output lengths and n gaps, in the order sent:
    blocks of `block` arrivals, each of one member of every stratum
    (module doc), all fixed by the mix.  Outputs are cut so prompt +
    output <= max_positions."""
    k = int(mix.get("block", 1))
    nb = -(-n_open(mix, seconds) // k)
    n = nb * k
    pl = lognormal_quantiles(n, mix["prompt_len"])
    ol = lognormal_quantiles(n, mix["output_len"])
    gl = exp_gaps(n, mix["rate_per_s"])
    if int(pl.max()) >= max_positions:
        raise ValueError(f"prompt_len max {int(pl.max())} leaves no room "
                         f"for output under {max_positions} positions")
    fix = np.random.default_rng(_PAIRING_SEED)
    member = {q: [fix.permutation(nb) for _ in range(k)]
              for q in ("prompt", "output", "gap")}
    sz = np.zeros((n, 2), np.int64)
    gaps = np.zeros(n)
    for b in range(nb):
        meet = fix.permutation(k)          # prompt stratum -> output's
        gap_order = fix.permutation(k)
        for j, s in enumerate(fix.permutation(k)):
            i = b * k + j
            sz[i, 0] = pl[s * nb + member["prompt"][s][b]]
            t = meet[s]
            sz[i, 1] = ol[t * nb + member["output"][t][b]]
            g = gap_order[j]
            gaps[i] = gl[g * nb + member["gap"][g][b]]
    sz[:, 1] = np.minimum(sz[:, 1], max_positions - sz[:, 0])
    return sz, gaps


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             max_positions: int) -> List[Request]:
    """The requests of one run, in the order they are sent.  Open loop:
    due times from -ramp_s on; closed loop: `pool` requests that the
    clients take in turn."""
    if mix["loop"] == "open":
        sz, gaps = open_schedule(mix, seconds, max_positions)
        n = len(sz)
        due = list(np.cumsum(gaps) - gaps[0] - mix["ramp_s"])
    elif mix["loop"] == "closed":
        order_rng = seed_rng(seed, 0)
        k = int(mix["set_size"])
        n = int(mix["pool"]) // k * k
        base = sizes(k, mix, max_positions)
        sz = np.concatenate([base[order_rng.permutation(k)]
                             for _ in range(n // k)])
        due = [None] * n
    else:
        raise ValueError(f"loop must be open or closed: {mix['loop']!r}")
    tok_rng = seed_rng(seed, 1)
    out = []
    for i in range(n):
        p, o = int(sz[i, 0]), int(sz[i, 1])
        out.append(Request(
            rid=i, prompt=tok_rng.integers(0, vocab, p).astype(np.int32),
            max_new=o, due=None if due[i] is None else float(due[i])))
    return out
