"""Pallas TPU kernel: coded row gather (the read-pattern datapath, §IV-B).

Executes one memory cycle's read pattern against streamed bank row tiles:
each request is served either directly (``banks[bank, row]``), by a degraded
read (``parities[par, prow] ^ banks[sib0, row] ^ banks[sib1, row]``), or by a
redirect of a parked value (``parities[par, prow]``). All lanes are unsigned
integers (raw bits); callers bitcast float data outside.

Tiling (docs/kernels.md): grid ``(N / RB, L / BT)`` — request tiles in the
outer dimension, data-bank row tiles streamed through VMEM in the inner
dimension, so the data banks never live whole in VMEM. Requests bucket to
row tiles by compare (a request only contributes lanes from the tile that
holds its row), and the out tile XOR-accumulates across the inner grid
dimension. The parity banks — the small arrays, and reachable from any row
tile via redirects — stay VMEM-resident and contribute on the first tile.

The request lane is fully vectorized (no scalar per-request loop): one-hot
masks over the ``(ND, BT)`` tile select only the lanes each mode needs —
the direct lane for modes 0/1, the two sibling lanes for degraded options,
the parity lane for options and redirects — and the XOR of the selected
lanes IS the decode.

Mode encoding matches repro.core.controller: 0 FROM_SYM, 1 DIRECT,
2..2+MAX_OPTS-1 degraded options, 2+MAX_OPTS REDIRECT; -1 entries yield 0.
Served requests carry in-range lane indices by contract (the read plan's
served address); -1 padding rows added by the wrapper select nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.codes import MAX_OPTS
from repro.kernels.common import resolve_interpret

MODE_REDIRECT = 2 + MAX_OPTS


def _lane_xor(sel, tile):
    """XOR of the selected lanes: ``sel`` (RB, NB, BT) marks at most one row
    per (request, bank), so the row reduction is an exact select via sum;
    banks fold with XOR (a degraded read keeps two sibling lanes live)."""
    picked = jnp.where(sel[..., None], tile[None], 0)
    per_bank = jnp.sum(picked, axis=2, dtype=tile.dtype)    # (RB, NB, W)
    acc = per_bank[:, 0]
    for bi in range(1, per_bank.shape[1]):
        acc = acc ^ per_bank[:, bi]
    return acc


def _gather_kernel(bank_ref, row_ref, mode_ref, par_ref, prow_ref,
                   sib0_ref, sib1_ref, banks_ref, par_banks_ref, out_ref):
    rt = pl.program_id(1)
    rb = bank_ref.shape[0]
    nd, bt, _ = banks_ref.shape
    n_par, lp, _ = par_banks_ref.shape

    mode = mode_ref[:]
    served = mode >= 0
    is_opt = (mode >= 2) & (mode < MODE_REDIRECT)
    need_dir = served & (mode < 2)           # FROM_SYM / DIRECT lane
    need_par = served & (mode >= 2)          # degraded options + redirect

    # data lanes: this row tile covers rows [rt*BT, rt*BT + BT)
    row = row_ref[:] - rt * bt               # tile-local request row
    b_ids = jax.lax.broadcasted_iota(jnp.int32, (rb, nd, bt), 1)
    r_ids = jax.lax.broadcasted_iota(jnp.int32, (rb, nd, bt), 2)
    at_row = r_ids == row[:, None, None]
    sel = need_dir[:, None, None] & at_row \
        & (b_ids == bank_ref[:][:, None, None])
    sel |= ((is_opt & (sib0_ref[:] >= 0))[:, None, None] & at_row
            & (b_ids == sib0_ref[:][:, None, None]))
    sel |= ((is_opt & (sib1_ref[:] >= 0))[:, None, None] & at_row
            & (b_ids == sib1_ref[:][:, None, None]))
    acc = _lane_xor(sel, banks_ref[:])

    # parity lane (VMEM-resident block): contribute on the first tile only,
    # so accumulation over row tiles never double-XORs it
    p_ids = jax.lax.broadcasted_iota(jnp.int32, (rb, n_par, lp), 1)
    q_ids = jax.lax.broadcasted_iota(jnp.int32, (rb, n_par, lp), 2)
    psel = ((need_par & (rt == 0))[:, None, None]
            & (p_ids == par_ref[:][:, None, None])
            & (q_ids == prow_ref[:][:, None, None]))
    acc = acc ^ _lane_xor(psel, par_banks_ref[:])

    @pl.when(rt == 0)
    def _init():
        out_ref[...] = acc

    @pl.when(rt > 0)
    def _fold():
        out_ref[...] ^= acc


def _row_tile(n_rows: int, want: int) -> int:
    """Largest divisor of ``n_rows`` that is <= ``want`` (at least 1)."""
    bt = max(1, min(want, n_rows))
    while n_rows % bt:
        bt -= 1
    return bt


@functools.partial(
    jax.jit, static_argnames=("req_block", "row_block", "interpret"))
def gather_decode_pallas(
    banks: jnp.ndarray,      # (n_data, L, W) uint lanes
    parities: jnp.ndarray,   # (n_par, Lp, W) uint lanes
    bank: jnp.ndarray,       # (N,) int32
    row: jnp.ndarray,        # (N,) int32
    mode: jnp.ndarray,       # (N,) int32
    par: jnp.ndarray,        # (N,) int32 logical parity index
    prow: jnp.ndarray,       # (N,) int32 parity row
    sib0: jnp.ndarray,       # (N,) int32
    sib1: jnp.ndarray,       # (N,) int32
    *,
    req_block: int = 8,
    row_block: int = 128,
    interpret=None,
) -> jnp.ndarray:
    """(N, W) gathered rows for any N — requests are padded to a full
    request tile with -1 (mode -1 selects nothing) and the pad is stripped
    on return, so direct callers never hit a tile-divisibility assert. An
    empty plan (N=0) short-circuits without tracing the kernel (a 0-size
    grid would divide by zero)."""
    assert jnp.issubdtype(banks.dtype, jnp.integer), banks.dtype
    interpret = resolve_interpret(interpret)
    n_data, L, W = banks.shape
    n_par, Lp, _ = parities.shape
    n = bank.shape[0]
    if n == 0:
        return jnp.zeros((0, W), banks.dtype)
    rb = min(req_block, n)
    pad = (-n) % rb
    cols = (bank, row, mode, par, prow, sib0, sib1)
    if pad:
        cols = tuple(jnp.pad(c, (0, pad), constant_values=-1) for c in cols)
    n_pad = n + pad
    bt = _row_tile(L, row_block)
    grid = (n_pad // rb, L // bt)
    col_spec = pl.BlockSpec((rb,), lambda t, r: (t,))
    out = pl.pallas_call(
        _gather_kernel,
        out_shape=jax.ShapeDtypeStruct((n_pad, W), banks.dtype),
        grid=grid,
        in_specs=[col_spec] * 7 + [
            pl.BlockSpec((n_data, bt, W), lambda t, r: (0, r, 0)),
            pl.BlockSpec((n_par, Lp, W), lambda t, r: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((rb, W), lambda t, r: (t, 0)),
        interpret=interpret,
    )(*cols, banks, parities)
    return out[:n] if pad else out
