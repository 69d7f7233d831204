"""The benchmark's generator keeps the structure of make_vectors."""

import numpy as np
import pytest

from bench import data


@pytest.mark.parametrize("seed", [0, 2**40 + 7])
def test_generator_structure(seed):
    rows, queries, mix = data.generate(seed, 4000, 32, 64, "ed",
                                       with_components=True)
    rows, queries, mix = map(np.asarray, (rows, queries, mix))
    assert rows.shape == (4000, 32) and queries.shape == (64, 32)
    assert rows.dtype == np.float32
    # Folded non-negative, rescaled so that p99.5 of the index rows is 5.
    assert rows.min() >= 0.0 and queries.min() >= 0.0
    assert np.percentile(rows, 99.5) == pytest.approx(5.0, rel=1e-3)
    # 16 mixture components, with energies that differ between them.
    assert set(np.unique(mix).tolist()) == set(range(data.COMPONENTS))
    energy = np.array([rows[mix[:4000] == c].mean()
                       for c in range(data.COMPONENTS)])
    assert energy.max() / energy.min() > 2.0
    # Queries are held out: no query is a row of the index.
    assert not (queries[:, None, :] == rows[None, :, :]).all(-1).any()


def test_seed_shuffles_the_rows_and_draws_the_queries():
    a, qa = map(np.asarray, data.generate(5, 500, 16, 8, "ed"))
    b, qb = map(np.asarray, data.generate(5, 500, 16, 8, "ed"))
    c, qc = map(np.asarray, data.generate(2**40, 500, 16, 8, "ed"))
    assert np.array_equal(a, b) and np.array_equal(qa, qb)
    # Another seed stores the same set of rows in another order, and
    # sends other queries.
    assert not np.array_equal(a, c)
    assert np.array_equal(np.unique(a, axis=0), np.unique(c, axis=0))
    assert not np.array_equal(qa, qc)


def test_every_seed_gets_the_same_pccp_partition():
    from repro.core.partition import correlation_matrix, pccp_order

    orders = [pccp_order(correlation_matrix(np.asarray(
        data.generate(seed, 20000, 64, 8, "ed")[0])), 8, 0)
        for seed in (1, 2**35, 77)]
    assert all(np.array_equal(orders[0], o) for o in orders[1:])


def test_structure_matches_make_vectors():
    from repro.data.pipeline import VectorDatasetSpec, make_vectors

    ref = make_vectors(VectorDatasetSpec("deep", 4000, 32, "ed", 4), seed=3)
    ours = np.asarray(data.generate(3, 4000, 32, 8, "ed")[0])
    for x in (ref, ours):
        assert x.min() >= 0.0
        assert np.percentile(x, 99.5) == pytest.approx(5.0, rel=1e-3)
    # Similar spread of magnitudes: per-coordinate means vary alike.
    cv = [x.mean(0).std() / x.mean(0).mean() for x in (ref, ours)]
    assert 0.25 < cv[0] / cv[1] < 4.0


def test_positive_family_keeps_rows_in_domain():
    rows = np.asarray(data.generate(1, 500, 16, 8, "isd")[0])
    assert rows.min() >= 0.1
