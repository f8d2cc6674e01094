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


def open_loop_requests(traffic: Dict, vocab: int, seed: int,
                       seconds: float) -> List[Dict]:
    """The requests due in ``[0, seconds)``: ``round(rate * seconds)`` of
    them, each ``{"due": s, "prompt": [ids], "max_new_tokens": n}``, in
    order of ``due``. Open loop: when a request is due does not depend on
    how the system is doing."""
    rng = np.random.default_rng(seed)
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    gaps = rng.permutation(exponential_gaps(n, traffic["rate_per_s"]))
    # The quantile gaps sum to ~n/rate; scale so the last arrival falls
    # just inside the window whatever n is.
    due = np.cumsum(gaps)
    due *= seconds * (n - 0.5) / n / due[-1]
    prompts = rng.permutation(lognormal_lengths(n, traffic["prompt_len"]))
    outputs = rng.permutation(lognormal_lengths(n, traffic["output_len"]))
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
