"""Every seed asks for the same sizes in the same order, with other
tokens; seeds past 32 bits work; the same seed gives the same requests."""
import itertools
import json

import numpy as np
import pytest

import spec
import traffic

MIXES = sorted(p.stem for p in (spec.HERE / "traffic").glob("*.json"))
BLOCKS = 6


def first(mix, seed):
    """The fill block and ``BLOCKS`` more."""
    return list(itertools.islice(traffic.generate(mix, 1000, seed),
                                 mix["slots"] * (BLOCKS + 1)))


def sizes(reqs):
    return sorted((len(r.prompt), r.max_new) for r in reqs)


@pytest.mark.parametrize("name", MIXES)
def test_same_sizes_every_seed(name):
    mix = json.load(open(spec.HERE / "traffic" / f"{name}.json"))
    n = mix["slots"]
    a = first(mix, 3)
    b = first(mix, 2 ** 40 + 17)
    assert ([(len(r.prompt), r.max_new) for r in a]
            == [(len(r.prompt), r.max_new) for r in b])
    for k in range(2, BLOCKS + 1):
        assert sizes(a[k * n:(k + 1) * n]) == sizes(a[n:2 * n])
    assert not np.array_equal(a[n].prompt, b[n].prompt)
    assert [r.rid for r in a] == list(range(len(a)))
    assert all(len(r.prompt) + r.max_new <= mix["max_len"] for r in a)


@pytest.mark.parametrize("name", MIXES)
def test_seed_fixes_the_requests(name):
    mix = json.load(open(spec.HERE / "traffic" / f"{name}.json"))
    a = first(mix, 2 ** 33 + 5)
    b = first(mix, 2 ** 33 + 5)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))
