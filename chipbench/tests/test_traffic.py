"""The traffic generator: same seed, same bytes; every seed the same work;
the stationary start."""
import json

import numpy as np
import pytest
from conftest import BENCH_DIR

from harness.traffic import GRID, Traffic, length_grid

#: every mix a cell runs, and the tests' tiny one
MIXES = ("decode", "tiny_mix")
BIG_SEED = 2 ** 31 + 12345


def mix(name):
    path = BENCH_DIR / "traffic" / f"{name}.json"
    if not path.exists():
        path = BENCH_DIR / "tests" / "data" / f"{name}.json"
    with open(path) as f:
        return json.load(f)


def draw(name, seed, n_slots=16, n_next=80):
    t = Traffic(mix(name), 100352, n_slots, seed)
    return t.initial(), [t.next() for _ in range(n_next)]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_bytes(name):
    a, b = draw(name, BIG_SEED), draw(name, BIG_SEED)
    for ra, rb in zip(a[0] + a[1], b[0] + b[1]):
        assert ra.prompt.tobytes() == rb.prompt.tobytes()
        assert (ra.max_new, ra.served_before) == (rb.max_new, rb.served_before)
    c = draw(name, BIG_SEED + 1)
    assert any(ra.prompt.tobytes() != rc.prompt.tobytes()
               for ra, rc in zip(a[1], c[1]))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_sizes(name):
    """A seed changes tokens, not work: the initial requests' (prompt,
    budget) pairs are one multiset and the follow-ups' sizes one sequence
    for every seed; a grid block of follow-ups holds every grid length."""
    n = GRID
    sizes = []
    for seed in (1, 99, BIG_SEED):
        init, nxt = draw(name, seed, n_next=2 * n)
        sizes.append((sorted((len(r.prompt), r.max_new) for r in init),
                      [(len(r.prompt), r.max_new) for r in nxt]))
    assert sizes[0] == sizes[1] == sizes[2]
    p = mix(name)
    assert sorted(s[0] for s in sizes[0][1][:n]) == \
        sorted(length_grid(p["prompt"], n))


@pytest.mark.parametrize("name", MIXES)
def test_stationary_start(name):
    """Each initial request is a drawn request g tokens into its answer:
    prompt P + g and budget O - g with P and O on the mix's grids, g below
    O, and the whole request within max_len; g spreads over the answer."""
    p = mix(name)
    C = 16
    init, _ = draw(name, 7, n_slots=C)
    P = length_grid(p["prompt"], C)
    assert len(init) == C
    frac = []
    for r in init:
        g = r.served_before
        P0 = len(r.prompt) - g
        assert P0 in P
        assert g >= 0 and r.max_new >= 1
        assert len(r.prompt) + r.max_new <= p["max_len"]
        frac.append(g / (g + r.max_new))
    assert 0.3 < float(np.mean(frac)) < 0.7
    assert min(frac) < 0.15 and max(frac) > 0.85


@pytest.mark.parametrize("name", MIXES)
def test_lengths_respect_bounds(name):
    p = mix(name)
    _, nxt = draw(name, 3, n_next=200)
    for r in nxt:
        assert p["prompt"]["min"] <= len(r.prompt) <= p["prompt"]["max"]
        assert 1 <= r.max_new <= p["output"]["max"]
        assert len(r.prompt) + r.max_new <= p["max_len"]
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < 100352
    med = np.median([len(r.prompt) for r in nxt])
    assert abs(med / p["prompt"]["median"] - 1) < 0.15
