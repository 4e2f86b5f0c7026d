"""Inputs from the seed: the one general generator of every traffic mix.
The same seed gives the same inputs; the program receives only what is
generated here. Parameters come from the cell's file; a new mix is a new
data file, never new code.

Fixed file; ``tests/test_traffic.py`` checks the seeding.
"""

from __future__ import annotations

import numpy as np


def _rng(seed, stream):
    """Independent streams from one seed (arrivals, lengths, tokens...)."""
    return np.random.default_rng([int(seed), int(stream)])


# -------------------------------------------------------------------- serving

def lognormal_lengths(rng, n, median, sigma, lo, hi):
    """n independent lengths, lognormal with the given median, clipped to
    [lo, hi]."""
    x = rng.lognormal(np.log(median), sigma, n)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def arrivals(rng, rate_per_s, horizon_s):
    """Due times (seconds from the start of traffic) of a Poisson process:
    independent exponential gaps at ``rate_per_s``, up to the horizon. How
    many fall in a window, and how they bunch, is the seed's."""
    rate = float(rate_per_s)
    n = int(rate * horizon_s + 8 * np.sqrt(rate * horizon_s)) + 64
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    return t[t < horizon_s]


def serve_requests(seed, spec, vocab_size, horizon_s):
    """The run's requests: [{"due", "prompt" (1-based ids), "max_new"}].
    ``spec`` is the cell's ``traffic_params``: ``rate_per_s`` (Poisson
    arrivals), ``prompt`` and ``output`` ({"median", "sigma", "min", "max"},
    lognormal, drawn independently). Every prompt is distinct from its
    first tokens on."""
    due = arrivals(_rng(seed, 1), spec["rate_per_s"], horizon_s)
    rng = _rng(seed, 2)
    p, o = spec["prompt"], spec["output"]
    plen = lognormal_lengths(rng, len(due), p["median"], p["sigma"],
                             p["min"], p["max"])
    olen = lognormal_lengths(rng, len(due), o["median"], o["sigma"],
                             o["min"], o["max"])
    tok = _rng(seed, 3)
    return [{"due": float(t),
             "prompt": tok.integers(1, vocab_size + 1, int(n)).tolist(),
             "max_new": int(m)} for t, n, m in zip(due, plen, olen)]


def probe_prompts(seed, vocab_size, count, prompt_len, new_tokens):
    """The seeded prompts served before the window for the reference
    check."""
    rng = _rng(seed, 4)
    return [{"prompt": rng.integers(1, vocab_size + 1, prompt_len).tolist(),
             "max_new": new_tokens} for _ in range(count)]


# ------------------------------------------------------------------- training

def zipf_tokens(seed, rows, seq, vocab_size, exponent):
    """(rows, seq + 1) 1-based token ids, Zipf(exponent) over the whole
    vocabulary (rank r with probability ~ r^-exponent, ranks mapped to ids
    by a seeded permutation): inputs are [:, :-1], targets [:, 1:]."""
    rng = _rng(seed, 5)
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    p = ranks ** -float(exponent)
    p /= p.sum()
    ids = rng.permutation(vocab_size) + 1
    draw = rng.choice(vocab_size, size=(rows, seq + 1), p=p)
    return ids[draw].astype(np.int64)


def noise_images(seed, n, size, channels, label_classes, dtype="float32"):
    """(images (n, size, size, channels) of unit normal noise, labels (n,)
    1-based floats from ``label_classes`` classes)."""
    rng = _rng(seed, 6)
    x = rng.standard_normal((n, size, size, channels), dtype=np.float32)
    if dtype != "float32":
        import ml_dtypes  # noqa: F401 - registers bfloat16 with numpy
        x = x.astype(dtype)
    y = rng.integers(1, label_classes + 1, n).astype(np.float32)
    return x, y
