"""The traffic generator: the same seed gives the same inputs, and a mix
with a fixed draw gives every seed the same sizes and arrivals, block by
block, in another order."""
import numpy as np

from bench import generator, loader

FARM = "farm20k_c6.websrv_j100k"


def _farm(seed, **traffic):
    cell = loader.cell(FARM)
    mix = dict(cell.traffic, jobs=4000, **traffic)
    return generator.farm(mix, cell.config["sim"], cell.config["tau_s"],
                          seed)


def test_same_seed_same_inputs():
    seed = 2**31 + 77
    for a, b in zip(_farm(seed), _farm(seed)):
        np.testing.assert_array_equal(a, b)


def test_fixed_draw_reorders_within_blocks_only():
    block = loader.cell(FARM).traffic["order"]["block"]
    (arr_a, svc_a, _), (arr_b, svc_b, _) = _farm(3), _farm(2**33 + 1)
    gaps_a, gaps_b = np.diff(arr_a, prepend=0.0), np.diff(arr_b, prepend=0.0)
    assert not np.array_equal(svc_a, svc_b)
    assert not np.array_equal(gaps_a, gaps_b)
    for a, b in ((gaps_a, gaps_b), (svc_a, svc_b)):
        blocks = lambda x: np.sort(x.reshape(-1, block), axis=1)  # noqa: E731
        np.testing.assert_allclose(blocks(a), blocks(b), rtol=1e-12)
    # the arrivals agree wherever a block ends
    np.testing.assert_allclose(arr_a[block - 1::block],
                               arr_b[block - 1::block], rtol=1e-12)


def test_block_shuffle_keeps_each_block():
    gen = generator.rng(5)
    x = np.arange(40.0)
    y = generator.block_shuffle(x, 8, gen)
    assert not np.array_equal(x, y)
    np.testing.assert_array_equal(np.sort(y.reshape(5, 8), axis=1),
                                  x.reshape(5, 8))


def test_independent_draws_without_order():
    (arr_a, svc_a, _), (arr_b, svc_b, _) = (_farm(3, order=None),
                                            _farm(4, order=None))
    assert not np.allclose(np.sort(svc_a), np.sort(svc_b))
