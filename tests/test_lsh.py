"""p-stable LSH behaviour tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.lsh.pstable import (LSHParams, bucket_sizes, build_lsh, hash_points,
                               probe_rows, query_batch, query_salts)


def _recall(points, queries, truth_sets, params, seed=0):
    tables = build_lsh(jnp.asarray(points), params, jax.random.PRNGKey(seed))
    cands = np.asarray(query_batch(tables, jnp.asarray(queries), params))
    recalls = []
    for i, ts in enumerate(truth_sets):
        got = set(c for c in cands[i].tolist() if c >= 0)
        recalls.append(len(got & ts) / max(len(ts), 1))
    return float(np.mean(recalls))


def test_near_points_collide_more_than_far():
    rng = np.random.default_rng(0)
    base = rng.normal(size=(64, 8)).astype(np.float32)
    near = base + 0.05 * rng.normal(size=base.shape).astype(np.float32)
    far = base + 5.0 * rng.normal(size=base.shape).astype(np.float32)
    data = np.concatenate([near, far]).astype(np.float32)
    params = LSHParams(n_tables=6, n_projections=6, seg_len=1.0, probe=32)
    near_sets = [{i} for i in range(64)]
    far_sets = [{64 + i} for i in range(64)]
    r_near = _recall(data, base, near_sets, params)
    r_far = _recall(data, base, far_sets, params)
    assert r_near > r_far + 0.3, (r_near, r_far)
    assert r_near > 0.8, r_near


def test_bucket_sizes_sum():
    rng = np.random.default_rng(1)
    data = rng.normal(size=(200, 4)).astype(np.float32)
    params = LSHParams(n_tables=2, n_projections=4, seg_len=2.0, probe=8)
    tables = build_lsh(jnp.asarray(data), params, jax.random.PRNGKey(0))
    sizes = np.asarray(bucket_sizes(tables))
    assert sizes.shape == (200,)
    assert (sizes >= 1).all()  # every point is in its own bucket
    # group check: points with the same key must report the same size
    keys = np.asarray(hash_points(jnp.asarray(data), tables.proj, tables.bias,
                                  params.seg_len))[0]
    for key in np.unique(keys):
        members = np.where(keys == key)[0]
        assert (sizes[members] == len(members)).all()


def test_query_shapes_and_miss():
    rng = np.random.default_rng(2)
    data = rng.normal(size=(50, 4)).astype(np.float32)
    params = LSHParams(n_tables=3, n_projections=4, seg_len=0.5, probe=4)
    tables = build_lsh(jnp.asarray(data), params, jax.random.PRNGKey(0))
    # far-away query should mostly miss
    q = 100.0 + rng.normal(size=(2, 4)).astype(np.float32)
    out = np.asarray(query_batch(tables, jnp.asarray(q), params))
    assert out.shape == (2, 3 * 4)
    assert (out == -1).mean() > 0.9


def test_probe_window_spreads_within_bucket():
    """All points identical => one giant bucket; distinct queries must not all
    return the same probe window (the CIVS coverage fix)."""
    rng = np.random.default_rng(3)
    data = np.zeros((256, 4), np.float32) + 0.001 * rng.normal(size=(256, 4)).astype(np.float32)
    params = LSHParams(n_tables=1, n_projections=2, seg_len=100.0, probe=8)
    tables = build_lsh(jnp.asarray(data), params, jax.random.PRNGKey(0))
    out = np.asarray(query_batch(tables, jnp.asarray(data[:32]), params))
    distinct = {tuple(row.tolist()) for row in out}
    assert len(distinct) > 4, "probe windows did not spread across the bucket"


# (seg_len, probe, n_tables) over 300 points: one bucket per table, larger
# than the probe, so the salt places every window; buckets of one or two
# points, smaller than the probe; and a mix of both
_PROBE_CASES = {"larger_than_probe": (100.0, 4, 2),
                "smaller_than_probe": (0.7, 12, 3),
                "mixed": (3.0, 8, 4)}


def _tables_for(case, dtype=jnp.float32, n=300):
    seg_len, probe, n_tables = _PROBE_CASES[case]
    rng = np.random.default_rng(5)
    data = jnp.asarray(rng.normal(size=(n, 6)).astype(np.float32)).astype(dtype)
    params = LSHParams(n_tables=n_tables, n_projections=4, seg_len=seg_len,
                       probe=probe)
    return data, params, build_lsh(data, params, jax.random.PRNGKey(0))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", list(_PROBE_CASES))
def test_probe_rows_equals_query_batch_on_data_rows(case, dtype):
    data, params, tables = _tables_for(case, dtype)
    n, probe, n_tables = data.shape[0], params.probe, params.n_tables
    idx = jnp.asarray(np.random.default_rng(6).permutation(n).astype(np.int32))
    salts = query_salts(data[idx], tables.proj, tables.bias)
    want = np.asarray(query_batch(tables, data[idx], params))
    got = np.asarray(probe_rows(tables, idx, salts, probe))
    np.testing.assert_array_equal(got, want)

    # the cases cover what they are named for, and in each some window's
    # last member is the table's last sorted slot
    dirs = np.asarray(tables.directory)[np.asarray(idx)]
    head, size = dirs[:, :n_tables].T, dirs[:, n_tables:].T       # (L, Q)
    span = np.maximum(size - probe, 0)
    offset = np.where(span > 0, np.asarray(salts) % (span + 1), 0)
    assert (head + offset + np.minimum(size - offset, probe) == n).any()
    if case == "larger_than_probe":
        assert (size > probe).all()
    elif case == "smaller_than_probe":
        assert (size < probe).all()
    else:
        assert (size > probe).any() and (size < probe).any()


@pytest.mark.parametrize("case", list(_PROBE_CASES))
def test_bucket_directory_matches_binary_search(case):
    data, params, tables = _tables_for(case)
    n_tables = params.n_tables
    sk = np.asarray(tables.sorted_keys)
    perm = np.asarray(tables.perm)
    dirs = np.asarray(tables.directory)
    assert dirs.shape == (data.shape[0], 2 * n_tables)
    for l in range(n_tables):
        keys = np.empty_like(sk[l])
        keys[perm[l]] = sk[l]                     # each point's own key
        left = np.searchsorted(sk[l], keys, "left")
        right = np.searchsorted(sk[l], keys, "right")
        np.testing.assert_array_equal(dirs[:, l], left)
        np.testing.assert_array_equal(dirs[:, n_tables + l], right - left)
    np.testing.assert_array_equal(np.asarray(bucket_sizes(tables)),
                                  dirs[:, n_tables])
