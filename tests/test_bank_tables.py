"""The stored form of the bank-wide tables (``core/state.py``): kept as
(banks, width) up to ``ONEHOT_MAX_COLS`` columns, in rows of 128 lanes
above it; every helper reads and writes the same cells in either form."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import state
from repro.core.controller import _cell, _col, _put


@pytest.mark.parametrize("width", [200, state.ONEHOT_MAX_COLS,
                                   state.ONEHOT_MAX_COLS + 1, 4224, 16385])
def test_helpers_match_the_logical_table(width):
    rng = np.random.default_rng(width)
    logical = rng.integers(0, 1000, (12, width)).astype(np.int32)
    stored = state.bank_table(jnp.asarray(logical))
    laned = width > state.ONEHOT_MAX_COLS
    assert stored.ndim == (3 if laned else 2)
    if laned:
        assert stored.shape == (12, -(-width // 128), 128)
    np.testing.assert_array_equal(state.bank_view(np.asarray(stored), width),
                                  logical)

    rows = jnp.asarray(rng.integers(0, 12, 40), jnp.int32)
    cols = jnp.asarray(rng.integers(0, width, 40), jnp.int32)
    np.testing.assert_array_equal(state.cells(stored, rows, cols),
                                  logical[rows, cols])
    np.testing.assert_array_equal(_cell(stored, rows, cols),
                                  logical[rows, cols])
    np.testing.assert_array_equal(_col(stored, cols), logical[:, cols].T)

    vals = jnp.arange(40, dtype=jnp.int32) + 5000
    want = logical.copy()
    keep = np.asarray(rows) % 3 > 0
    # a cell written twice takes equal values, as ``_put`` requires
    first = {}
    for r, c, v, k in zip(np.asarray(rows), np.asarray(cols),
                          np.asarray(vals), keep):
        if k:
            first.setdefault((r, c), v)
    v_eq = jnp.asarray([first.get((r, c), 0) for r, c in
                        zip(np.asarray(rows), np.asarray(cols))], jnp.int32)
    for (r, c), v in first.items():
        want[r, c] = v
    got = _put(stored, rows, cols, v_eq, jnp.asarray(keep))
    np.testing.assert_array_equal(state.bank_view(np.asarray(got), width),
                                  want)
    drop = jnp.where(jnp.asarray(keep), rows, 12)
    got = state.set_cells(stored, drop, cols, v_eq)
    np.testing.assert_array_equal(state.bank_view(np.asarray(got), width),
                                  want)


@pytest.mark.parametrize("width,size", [(4224, 410), (16385, 3277),
                                        (2300, 2300), (300, 26)])
def test_column_windows_read_and_write_in_range(width, size):
    rng = np.random.default_rng(size)
    logical = rng.integers(0, 1000, (8, width)).astype(np.int32)
    stored = state.bank_table(jnp.asarray(logical))
    vals = rng.integers(0, 1000, (8, size)).astype(np.int32)
    read = jax.jit(state.columns, static_argnums=2)
    write = jax.jit(state.set_columns)
    for start in sorted({s for s in (0, 1, 127, 128, width // 3)
                         if s < width - size} | {width - size}):
        np.testing.assert_array_equal(
            read(stored, jnp.int32(start), size),
            logical[:, start:start + size], err_msg=str(start))
        want = logical.copy()
        want[:, start:start + size] = vals
        got = write(stored, jnp.asarray(vals), jnp.int32(start))
        flat = np.asarray(got).reshape(8, -1)
        np.testing.assert_array_equal(flat[:, :width], want,
                                      err_msg=str(start))
        assert not flat[:, width:].any()
