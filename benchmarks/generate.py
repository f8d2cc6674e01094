"""The one traffic generator. A traffic mix is a data file under
``traffic/`` that names a driver and gives parameters; everything a run
feeds the system is drawn here from ``--seed`` and those parameters, on
the host, during set-up.

Steadiness by construction: a run's amount of work does not depend on the
seed. Lengths and inter-arrival gaps are the evenly spaced quantiles of
their distributions (the same multiset in every run) and the seed only
shuffles them, so two seeds differ in order and coincidence, not in how
many long prompts or how much total work the window holds.
"""

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def zipf_tokens(rng: np.random.Generator, vocab: int, shape,
                exponent: float = 1.0) -> np.ndarray:
    """Token ids with rank-frequency ``1/rank**exponent`` over the whole
    vocabulary (natural text is close to exponent 1), ranks mapped to ids
    by a seeded permutation. Unlike uniform ids this gives the loss
    somewhere to fall: the unigram distribution is learnable in a few
    steps."""
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    p /= p.sum()
    ids = rng.permutation(vocab).astype(np.int32)
    return ids[rng.choice(vocab, size=shape, p=p)]


def train_pool(traffic: Dict, vocab: int, chips: int,
               seed: int) -> List[np.ndarray]:
    """``pool_batches`` global batches of token ids, each
    ``[gas, micro_batch_per_chip * chips, seq_len]``; a driver cycles
    through them, so no host RNG runs inside the window."""
    rng = np.random.default_rng(seed)
    shape = (traffic["gradient_accumulation_steps"],
             traffic["micro_batch_per_chip"] * chips, traffic["seq_len"])
    return [zipf_tokens(rng, vocab, shape, traffic["token_zipf_exponent"])
            for _ in range(traffic["pool_batches"])]


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n: int, spec: Dict) -> np.ndarray:
    """The ``n`` evenly spaced quantiles of a lognormal with the given
    ``median`` and ``sigma``, clipped to [``min``, ``max``], as ints."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """The ``n`` evenly spaced quantiles of the exponential inter-arrival
    distribution of a Poisson process of ``rate`` per second."""
    return -np.log1p(-_quantiles(n)) / rate


def paired_outputs(n: int, output_spec: Dict) -> np.ndarray:
    """The ``n`` quantile output lengths in the order that pairs them with
    the ``n`` quantile prompt lengths (ascending): prompt ``i`` meets
    output ``i * s mod n``, ``s`` the whole number nearest to ``n`` over
    the golden ratio that shares no factor with ``n``, so long prompts
    meet short and long outputs alike (36 arrivals: the lengths correlate
    at -0.02). A function of ``n`` alone, never of ``--seed``."""
    s = max(1, round(n * 2 / (1 + math.sqrt(5))))
    while math.gcd(s, n) != 1:
        s += 1
    return lognormal_lengths(n, output_spec)[np.arange(n) * s % n]


def _strata(traffic: Dict, seconds: float) -> List:
    """``(start, length)`` of every stratum of ``[-prime_seconds,
    seconds)``: the priming stretch and the window are each cut into whole
    strata of about ``stratum_seconds`` (at least one), so no stratum
    straddles the window's opening."""
    size = float(traffic["stratum_seconds"])
    out = []
    for start, length in ((-float(traffic["prime_seconds"]),
                           float(traffic["prime_seconds"])),
                          (0.0, float(seconds))):
        if length <= 0:
            continue
        k = max(1, int(round(length / size)))
        out += [(start + i * length / k, length / k) for i in range(k)]
    return out


def open_loop_requests(traffic: Dict, vocab: int, seed: int,
                       seconds: float) -> List[Dict]:
    """The requests due in ``[-prime_seconds, seconds)``, each ``{"due":
    s, "prompt": [ids], "max_new_tokens": n}``, in order of ``due``. Open
    loop: when a request is due does not depend on how the system is
    doing.

    ``prime_seconds`` puts the same mix at the same rate before the
    window, due at negative times: a driver sends it and measures nothing
    of it, so the window opens on an engine in steady state.
    ``stratum_seconds`` cuts the schedule into strata in time. Every
    stratum holds ``round(rate * its length)`` arrivals whose gaps, prompt
    lengths and output lengths are each the evenly spaced quantiles of
    their distribution, prompts paired with outputs by a rule on the
    stratum's size alone (``paired_outputs``): the same multiset of gaps
    and of (prompt, output) pairs in every stratum of one length, whatever
    the seed, and ``--seed`` shuffles their ORDER within a stratum. Every
    seed is still another schedule; what it cannot do is pile the window's
    long requests into one second, or pair the long prompts with the long
    outputs: which prompt meets which output decides how many positions
    stay in the cache for how long, which is work (PR 34: six seeds whose
    pairings held 11.9-12.9 million prompt x output read 21.0-22.3 ms)."""
    rng = np.random.default_rng(seed)
    rate = traffic["rate_per_s"]
    strata = _strata(traffic, seconds)
    counts = [max(1, int(round(rate * length))) for _, length in strata]
    due = []
    for (start, length), n in zip(strata, counts):
        # The quantile gaps sum to ~n/rate; scale so the last arrival falls
        # just inside the stratum whatever n is.
        at = np.cumsum(rng.permutation(exponential_gaps(n, rate)))
        at *= length * (n - 0.5) / n / at[-1]
        due.append(start + at)
    due = np.concatenate(due)
    order = [rng.permutation(n) for n in counts]
    prompts = np.concatenate([lognormal_lengths(n, traffic["prompt_len"])[i]
                              for n, i in zip(counts, order)])
    outputs = np.concatenate([paired_outputs(n, traffic["output_len"])[i]
                              for n, i in zip(counts, order)])
    total_cap = traffic.get("max_total_len")
    if total_cap:
        outputs = np.minimum(outputs, total_cap - prompts)
        if outputs.min() < 1:
            raise ValueError("prompt_len.max leaves no room for output "
                             f"under max_total_len={total_cap}")
    tokens = zipf_tokens(rng, vocab, int(prompts.sum()),
                         traffic["token_zipf_exponent"])
    cuts = np.cumsum(prompts)[:-1]
    return [{"due": float(d), "prompt": p.tolist(), "max_new_tokens": int(o)}
            for d, p, o in zip(due, np.split(tokens, cuts), outputs)]


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of a non-empty sample."""
    if not len(values):
        raise ValueError("percentile of an empty sample")
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def tail_is_supported(n: int, q: float) -> bool:
    """A percentile is reported only with at least ten samples beyond it."""
    return math.floor(n * (1.0 - q / 100.0)) >= 10
