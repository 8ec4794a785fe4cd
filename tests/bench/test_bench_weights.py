"""The benchmark's weights (``bench/weights.py``): without an ``init`` in
the configuration they are drawn as they always were, leaf by leaf; a
stated ``embedding_std`` changes the embedding and nothing else."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import weights  # noqa: E402
from bench.cells import train  # noqa: E402
from bench.workload import WEIGHTS, seed_key  # noqa: E402

SEED = 2 ** 33 + 17
D = 64


def _shapes():
    sds = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
    return {"embed": {"embedding": sds((512, D)), "lm_head": sds((D, 512))},
            "layer": {"bq": sds((D,)), "scale": sds((D,)),
                      "wq": sds((D, 2 * D))}}


def _by_hand(shapes, seed):
    """Each leaf as drawn before ``init`` existed: N(0, 1) from the leaf's
    key, then scaled by its kind."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    key = seed_key(seed, WEIGHTS)
    out = []
    for i, (path, s) in enumerate(leaves):
        x = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                              jnp.float32)
        name = path[-1].key
        if name == "scale":
            x = 1.0 + 0.1 * x
        elif name == "bq":
            x = 0.02 * x
        elif name == "embedding":
            x = x * s.shape[-1] ** -0.5
        else:
            x = x * s.shape[-2] ** -0.5
        out.append(x.astype(s.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _bits(tree):
    return [np.asarray(x).view(np.uint16) for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("init", [None, {}])
def test_without_init_the_weights_are_bitwise_unchanged(init):
    got = weights.make(_shapes(), SEED, init=init)
    for a, b in zip(_bits(got), _bits(_by_hand(_shapes(), SEED))):
        np.testing.assert_array_equal(a, b)


def test_embedding_std_changes_the_embedding_alone():
    base = weights.make(_shapes(), SEED)
    wide = weights.make(_shapes(), SEED, init={"embedding_std": 1.0})
    emb = np.asarray(wide["embed"]["embedding"], np.float32)
    # 32768 draws: the sample std's standard error is 1 / sqrt(2 n) = 0.004
    assert abs(emb.std() - 1.0) < 0.02
    assert abs(emb.mean()) < 0.02
    assert np.asarray(base["embed"]["embedding"], np.float32).std() < 0.2
    for path, leaf in jax.tree_util.tree_flatten_with_path(wide)[0]:
        if path[-1].key != "embedding":
            np.testing.assert_array_equal(
                np.asarray(leaf).view(np.uint16),
                np.asarray(base[path[0].key][path[1].key]).view(np.uint16))


def test_unknown_init_key_is_refused():
    with pytest.raises(ValueError, match="embedding_sd"):
        weights.make(_shapes(), SEED, init={"embedding_sd": 1.0})


def test_train_state_takes_the_configuration_init():
    from repro.optim import adamw
    prog = train.Program(cfg=None, shapes=_shapes(),
                         opt_cfg=adamw.OptConfig(), compiled=None,
                         resolutions=[], init={"embedding_std": 1.0})
    params, opt = train.init_state(prog, SEED)
    emb = np.asarray(params["embed"]["embedding"], np.float32)
    assert abs(emb.std() - 1.0) < 0.02
    np.testing.assert_array_equal(
        np.asarray(opt["master"]["embed"]["embedding"]), emb)
