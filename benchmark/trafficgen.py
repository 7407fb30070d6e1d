"""The one general traffic generator: stratified, seeded only in order.

A traffic file states each distribution and a count ``n``. The generator takes
the ``n`` quantiles ``(i + 0.5) / n`` of it, so every seed offers the same
multiset of work; ``--seed`` decides only the order, the pairing of prompt
with output lengths, and the token ids. Nothing here touches JAX.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

_MASK = (1 << 63) - 1


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, named stream); any whole seed."""
    tag = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([int(seed) & _MASK, tag & _MASK])


def quantile(dist: Dict, u: float) -> float:
    """The inverse CDF of a distribution stated in a traffic file."""
    kind = dist["dist"]
    if kind == "uniform":
        return dist["lo"] + (dist["hi"] - dist["lo"]) * u
    if kind == "exponential":
        return -dist["mean"] * math.log1p(-u)
    raise ValueError(f"unknown distribution {kind!r}")


def strata(dist: Dict, n: int, integer: bool = True) -> List:
    """The n quantiles (i + 0.5) / n, in rising order."""
    vals = [quantile(dist, (i + 0.5) / n) for i in range(n)]
    return [int(round(v)) for v in vals] if integer else vals


def length_biased_strata(dist: Dict, n: int, fine: int = 4096) -> List[int]:
    """n quantiles of the distribution whose density is x * p(x).

    What a closed loop holds at a random instant: a request is found in a
    slot with a chance in proportion to how long it lives there.
    """
    base = np.asarray(strata(dist, fine, integer=False))
    cdf = np.cumsum(base) / base.sum()
    idx = np.searchsorted(cdf, (np.arange(n) + 0.5) / n)
    return [int(round(base[min(i, fine - 1)])) for i in idx]


def token_ids(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return [int(t) for t in rng.integers(1, vocab, n)]


def realised(values: Sequence[float]) -> Dict:
    v = np.asarray(values, float)
    return {"n": int(v.size), "min": float(v.min()), "mean": float(v.mean()),
            "max": float(v.max()), "sum": float(v.sum())}


def _balanced_order(values: Sequence, sub: int, rng: np.random.Generator) -> List:
    """The sorted ``values`` in a seeded order in which every run of ``sub``
    consecutive items (a sub-block) spans the whole range: the values are cut
    into ``sub`` groups of neighbours, each sub-block takes one value of each
    group, and is then shuffled. Every value is used once; any stretch of the
    order carries nearly the same total."""
    n = len(values)
    if sub >= n or n % sub:
        out = list(values)
        rng.shuffle(out)
        return out
    m = n // sub                                  # values to a group
    groups = [list(rng.permutation(values[g * m:(g + 1) * m])) for g in range(sub)]
    out: List = []
    for j in range(m):
        part = [groups[g][j] for g in range(sub)]
        rng.shuffle(part)
        out.extend(int(x) for x in part)
    return out


# -- closed loop --------------------------------------------------------------

def closed_loop_plan(traffic: Dict, seed: int) -> Dict:
    """Requests for the closed-loop driver.

    ``first``: one request per slot, built so that when the window opens the
    slots hold every phase of a request's life (see traffic/decode-batch.json
    ``start``). ``blocks``: an endless supply in blocks of ``block_requests``,
    each block the same stratified multiset in another order and pairing.
    """
    slots = int(traffic["engine"]["slots"])
    chunk = int(traffic["engine"]["chunk"])
    nb = int(traffic["block_requests"])
    start = traffic["start"]
    rng = rng_for(seed, "closed")

    prompts = strata(traffic["prompt_tokens"], slots)
    outputs = length_biased_strata(traffic["output_tokens"], slots)
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    order = rng.permutation(slots)          # which phase each admission gets
    first = []
    for k in range(slots):
        # Admission k of `slots` decodes while the later ones are admitted,
        # `ramp_tokens_per_admission` tokens for each, and `pre_window_chunks`
        # chunks more before the window opens. Its phase f is of the instant
        # the window opens: so it starts that much earlier in its life.
        f = (int(order[k]) + 0.5) / slots
        out = outputs[k]
        lived_at_open = int(f * out)
        ramp = ((slots - 1 - k) * int(start["ramp_tokens_per_admission"])
                + int(start["pre_window_chunks"]) * chunk)
        lived_at_submit = max(0, lived_at_open - ramp)
        remaining = out - lived_at_submit
        first.append({"prompt_tokens": prompts[k] + lived_at_submit,
                      "max_new_tokens": int(remaining),
                      "phase": f, "own_prompt": prompts[k], "own_output": out})

    p_strata = strata(traffic["prompt_tokens"], nb)
    o_strata = strata(traffic["output_tokens"], nb)
    sub = int(traffic.get("sub_block_requests", nb))

    def block(b: int) -> List[Dict]:
        brng = rng_for(seed, f"blk{b}")
        p = _balanced_order(p_strata, sub, brng)
        o = _balanced_order(o_strata, sub, brng)
        return [{"prompt_tokens": pi, "max_new_tokens": oi}
                for pi, oi in zip(p, o)]

    return {"first": first, "block": block,
            "realised": {"prompt_tokens": realised(p_strata),
                         "output_tokens": realised(o_strata),
                         "first_output_tokens": realised(outputs)}}


# -- sessions -----------------------------------------------------------------

def session_plan(traffic: Dict, seed: int, rate_per_s: float,
                 horizon_s: float) -> List[Dict]:
    """Sessions that start in an open loop over ``horizon_s`` seconds.

    Starts: the stratified exponential gaps of one block of
    ``block_sessions`` sessions, permuted per block and rescaled so that each
    block spans exactly block_sessions / rate seconds. Lengths: per block the
    same stratified multiset of (user, answer) lengths for every turn.
    """
    nb = int(traffic["block_sessions"])
    turns = int(traffic["turns"])
    n_sys = int(traffic["system_prompts"])
    gaps = np.asarray(strata({"dist": "exponential", "mean": 1.0}, nb,
                             integer=False))
    gaps *= (nb / rate_per_s) / gaps.sum()
    users = strata(traffic["user_tokens"], nb * turns)
    answers = strata(traffic["answer_tokens"], nb * turns)
    sessions: List[Dict] = []
    t = 0.0
    b = 0
    while t < horizon_s:
        brng = rng_for(seed, f"ses{b}")
        g = brng.permutation(gaps)
        u = brng.permutation(users).reshape(nb, turns)
        a = brng.permutation(answers).reshape(nb, turns)
        sysp = brng.permutation(np.arange(nb) % n_sys)
        for i in range(nb):
            t += float(g[i])
            sessions.append({
                "id": len(sessions), "start_s": t,
                "system_prompt": int(sysp[i]),
                "user_tokens": [int(x) for x in u[i]],
                "answer_tokens": [int(x) for x in a[i]]})
        b += 1
    return [s for s in sessions if s["start_s"] < horizon_s]
