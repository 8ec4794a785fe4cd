"""The traffic generator: the same seed gives the same inputs, another
seed others, and ids follow the Zipf law over the vocabulary slice."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import workload  # noqa: E402

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "bench",
                                                        "traffic")))


def _mix(name):
    with open(os.path.join(ROOT, "bench", "traffic", name + ".json")) as f:
        t = json.load(f)
    return {**t, **t["rehearsal"], "pool": 3}


def _tokens(t, seed, vocab=512):
    return np.stack([np.asarray(b["tokens"])
                     for b in workload.pool(t, vocab, seed)])


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_inputs(mix):
    t = _mix(mix)
    np.testing.assert_array_equal(_tokens(t, 7), _tokens(t, 7))


@pytest.mark.parametrize("mix", MIXES)
def test_other_seeds_other_inputs(mix):
    t = _mix(mix)
    base = _tokens(t, 7)
    # the high 32 bits of a seed count too
    for seed in (8, 7 + 2 ** 32, 2 ** 31 + 7):
        assert not np.array_equal(base, _tokens(t, seed))


@pytest.mark.parametrize("mix", MIXES)
def test_batches_of_a_pool_differ(mix):
    toks = _tokens(_mix(mix), 3)
    assert not np.array_equal(toks[0], toks[1])
    if "labels" in workload.pool(_mix(mix), 512, 3)[0]:
        b = workload.pool(_mix(mix), 512, 3)[0]
        np.testing.assert_array_equal(b["tokens"], b["labels"])


def test_zipf_law():
    import jax
    ids = np.asarray(workload.zipf_tokens(jax.random.PRNGKey(0), (200_000,),
                                          1000, 1.0))
    assert ids.min() >= 0 and ids.max() < 1000
    counts = np.bincount(ids, minlength=1000)
    h = np.sum(1.0 / np.arange(1, 1001))
    # rank r has probability 1 / (r h)
    for r in (1, 2, 10):
        assert counts[r - 1] / len(ids) == pytest.approx(1 / (r * h),
                                                         rel=0.05)
