"""How `correct` is decided for a served model.

Once the window has closed and the program's device state is freed, a
sample of the requests the run finished, drawn from the seed and always
holding the longest, goes through the plain float32 reference
(bench/lib/decoder.py): each prompt followed by the tokens the program
served.  For every served token the reference reads how far that
token's logit lies below its own best logit at that position.  Greedy
serving of a sound program picks the reference's best token or one
within rounding of it, so the widest such gap stays small; a wrong
token, a lost cache write or a missing sync puts it far down.

The limit per cell lives in bench/checks/<cell>.json with the readings
it was set from."""
from __future__ import annotations

from typing import List

import numpy as np

from bench.lib import decoder as D
from bench.lib import traffic as T


def sample(finished: List[T.Request], seed: int, tokens: int,
           max_requests: int, min_requests: int = 1) -> List[T.Request]:
    """The longest finished request, then others in an order drawn from
    the seed, until `tokens` served tokens and `min_requests` requests,
    or `max_requests`."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: len(r.prompt) + len(r.handle.out))
    rest = [r for r in finished if r is not longest]
    order = T.seed_rng(seed, 3).permutation(len(rest))
    out, n = [longest], len(longest.handle.out)
    for i in order:
        if (n >= tokens and len(out) >= min_requests) \
                or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].handle.out)
    return out


def sequences(reqs: List[T.Request]):
    seqs = [np.concatenate([r.prompt, np.asarray(r.handle.out, np.int32)])
            for r in reqs]
    return seqs, [len(r.handle.out) for r in reqs]


def control_readings(readings: List[D.Reading]) -> List[D.Reading]:
    """The control in the program's place: at every position the token
    the control's forward put first stands as the token served."""
    return [D.Reading(r.best, r.other, r.other, r.argmax)
            for r in readings]


def judge(readings: List[D.Reading], limits: dict, n_requests: int) -> dict:
    """{name: {"value", "limit"}} for every number compared."""
    gaps = np.concatenate([r.gap for r in readings]) if readings \
        else np.asarray([np.inf])
    gap = float(np.max(gaps)) if np.all(np.isfinite(gaps)) else float("inf")
    return {"max_logit_gap": {"value": gap,
                              "limit": limits["max_logit_gap"]},
            "requests_compared": {"value": n_requests,
                                  "limit": limits["min_requests"]}}


def passed(result: dict) -> bool:
    return (result["max_logit_gap"]["value"]
            <= result["max_logit_gap"]["limit"]
            and result["requests_compared"]["value"]
            >= result["requests_compared"]["limit"])
