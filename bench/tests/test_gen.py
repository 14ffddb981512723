"""The benchmark's generators: structure, determinism, skew."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from bench import gen
from bench.structures import fd9, rmat

FD = {"structure": "fd9", "log2_rows": 8, "nnz_per_row": 9,
      "value_low": 0.5, "value_high": 1.5}
RMAT = {"structure": "rmat", "log2_rows": 12, "edge_factor": 8, "a": 0.57,
        "b": 0.19, "c": 0.19, "d": 0.05, "permute": True, "structure_seed": 0,
        "value_low": 0.5, "value_high": 1.5}


def _same(a, b) -> bool:
    return all(np.array_equal(getattr(a, f), getattr(b, f))
               for f in ("indptr", "indices", "data"))


def test_fd_rows_hold_their_nine_periodic_neighbours():
    a = gen.generate(FD, seed=3)
    h, w = fd9.grid(FD)
    assert (a.n_rows, a.nnz) == (h * w, 9 * h * w)
    assert np.all(np.diff(a.indptr) == 9)
    cols = a.indices.reshape(-1, 9)
    r = np.arange(a.n_rows)
    want = np.sort(np.stack([((r // w + di) % h) * w + (r % w + dj) % w
                             for di in (-1, 0, 1) for dj in (-1, 0, 1)],
                            axis=1), axis=1)
    np.testing.assert_array_equal(cols, want)
    assert a.data.dtype == np.float32 and a.indices.dtype == np.int32
    assert a.data.min() >= 0.5 and a.data.max() < 1.5


def test_fd_refuses_a_grid_that_folds():
    with pytest.raises(ValueError, match="sides >= 3"):
        gen.generate(dict(FD, log2_rows=2), seed=0)


@pytest.mark.parametrize("cfg", [FD, RMAT], ids=["fd9", "rmat"])
def test_same_seed_same_matrix_and_large_seeds(cfg):
    big = 2 ** 31 + 12345
    a, b = gen.generate(cfg, big), gen.generate(cfg, big)
    assert _same(a, b)
    assert not _same(a, gen.generate(cfg, big + 1))
    x = gen.start_vector(big, a.n_cols)
    np.testing.assert_array_equal(x, gen.start_vector(big, a.n_cols))


def test_rmat_has_no_duplicate_coordinates_and_keeps_every_edge():
    a = gen.generate(RMAT, seed=5)
    rows = a.rows()
    key = rows * a.n_cols + a.indices
    assert np.all(np.diff(key) > 0)          # sorted, strictly: no repeats
    n_edges = RMAT["edge_factor"] << RMAT["log2_rows"]
    assert a.nnz < n_edges                   # some edges repeated
    # repeated edges sum their values: the total is that of n_edges draws
    assert n_edges * 0.5 <= a.data.sum() < n_edges * 1.5


def test_rmat_quadrants_follow_the_probabilities():
    levels, n_edges = 10, 1 << 17
    r, c = map(np.asarray, rmat.edges(jax.random.key(0), levels, n_edges,
                                      0.57, 0.19, 0.19))
    top_r, top_c = r >> (levels - 1), c >> (levels - 1)
    for frac, want in (((top_r == 0) & (top_c == 0), 0.57),
                       ((top_r == 0) & (top_c == 1), 0.19),
                       ((top_r == 1) & (top_c == 0), 0.19),
                       ((top_r == 1) & (top_c == 1), 0.05)):
        assert abs(frac.mean() - want) < 0.01


def test_rmat_permutation_relabels_without_changing_degrees():
    plain = gen.generate(dict(RMAT, permute=False), seed=9)
    mixed = gen.generate(RMAT, seed=9)
    deg_p, deg_m = np.diff(plain.indptr), np.diff(mixed.indptr)
    # the power law: most rows empty or light, a hub far above the mean
    assert deg_m.max() > 20 * deg_m.mean()
    assert (deg_m == 0).mean() > 0.3
    # unpermuted, the hubs sit in the first rows (quadrant a)
    assert deg_p[: len(deg_p) // 2].sum() > 0.7 * plain.nnz
    assert abs(deg_m[: len(deg_m) // 2].sum() / mixed.nnz - 0.5) < 0.2


def test_rmat_relabels_both_endpoints_by_one_permutation():
    # P A P^T: each vertex keeps its (out-degree, in-degree) pair and
    # self-loops stay self-loops, which two permutations would break
    def profile(a):
        rows = a.rows()
        pairs = np.stack([np.diff(a.indptr),
                          np.bincount(a.indices, minlength=a.n_cols)])
        pairs = pairs[:, np.lexsort(pairs)]
        return a.nnz, int((rows == a.indices).sum()), pairs
    plain = profile(gen.generate(dict(RMAT, permute=False), seed=9))
    for seed in (9, 2 ** 31 + 5):
        nnz, loops, pairs = profile(gen.generate(RMAT, seed=seed))
        assert (nnz, loops) == plain[:2] and loops > 0
        np.testing.assert_array_equal(pairs, plain[2])
