"""Same seed, same inputs; another seed, other inputs; rates and lengths as
the parameters say."""

import numpy as np

from benchmark import traffic

SPEC = {"rate_per_s": 20.0,
        "prompt": {"median": 1020, "sigma": 0.5, "min": 16, "max": 1536},
        "output": {"median": 129, "sigma": 1.0, "min": 8, "max": 512}}


def test_same_seed_same_requests_other_seed_other():
    a = traffic.serve_requests(7, SPEC, 151936, 30.0)
    b = traffic.serve_requests(7, SPEC, 151936, 30.0)
    c = traffic.serve_requests(8, SPEC, 151936, 30.0)
    assert a == b
    assert [r["due"] for r in a] != [r["due"] for r in c]
    assert a[0]["prompt"] != c[0]["prompt"]


def test_rate_lengths_and_ids_follow_the_parameters():
    reqs = traffic.serve_requests(1, SPEC, 151936, 200.0)
    assert abs(len(reqs) / 200.0 - 20.0) < 1.0          # ~4000 arrivals
    due = np.array([r["due"] for r in reqs])
    assert (np.diff(due) >= 0).all() and due[-1] < 200.0
    plen = np.array([len(r["prompt"]) for r in reqs])
    olen = np.array([r["max_new"] for r in reqs])
    assert plen.min() >= 16 and plen.max() <= 1536
    assert olen.min() >= 8 and olen.max() <= 512
    assert 970 < np.median(plen) < 1070 and 118 < np.median(olen) < 141
    assert 0.17 < (plen == 1536).mean() < 0.25          # the clip's share
    ids = np.concatenate([r["prompt"] for r in reqs[:50]])
    assert ids.min() >= 1 and ids.max() <= 151936
    # no shared prefix: every prompt distinct from its first tokens on
    assert len({tuple(r["prompt"][:8]) for r in reqs}) == len(reqs)


def test_arrivals_are_a_poisson_process_not_a_smoothed_one():
    """Independent exponential gaps: the count in a window varies from seed
    to seed as a Poisson count does, and so do the counts of its parts."""
    rng = np.random.default_rng(0)
    t = traffic.arrivals(rng, 5.0, 4000.0)
    gaps = np.diff(t)
    assert abs(gaps.mean() - 0.2) < 0.01
    assert 0.95 < gaps.std() / gaps.mean() < 1.05       # exponential: CV 1
    counts = np.histogram(t, bins=np.arange(0, 4001, 5.0))[0]
    assert 0.85 < counts.var() / counts.mean() < 1.15   # Poisson: var = mean
    per_seed = [len(traffic.serve_requests(s, dict(SPEC, rate_per_s=5.0),
                                           99, 30.0)) for s in range(40)]
    assert np.std(per_seed) > 8                 # sqrt(150) = 12, not ~0


def test_training_inputs_from_the_seed():
    a = traffic.zipf_tokens(5, 4, 128, 151936, 1.1)
    assert a.shape == (4, 129) and a.min() >= 1 and a.max() <= 151936
    assert (a == traffic.zipf_tokens(5, 4, 128, 151936, 1.1)).all()
    assert (a != traffic.zipf_tokens(6, 4, 128, 151936, 1.1)).any()
    # Zipf: a few ids carry much of the mass
    big = traffic.zipf_tokens(5, 64, 512, 151936, 1.1).ravel()
    _, counts = np.unique(big, return_counts=True)
    assert np.sort(counts)[-10:].sum() / big.size > 0.2
    x, y = traffic.noise_images(5, 6, 32, 3, 100)
    x2, _ = traffic.noise_images(5, 6, 32, 3, 100)
    assert x.shape == (6, 32, 32, 3) and (x == x2).all()
    assert y.min() >= 1 and y.max() <= 100
    p = traffic.probe_prompts(5, 1000, 4, 40, 9)
    assert p == traffic.probe_prompts(5, 1000, 4, 40, 9) and len(p) == 4
