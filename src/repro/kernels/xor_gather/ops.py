"""Public wrapper: coded gather of one cycle's served reads."""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.common import uint_view_dtype
from repro.kernels.xor_gather.kernel import gather_decode_pallas


class PlanColumns(NamedTuple):
    """The kernel's per-request columns: a read's data cell, its mode
    (-1 unserved), and the address it decodes from, as
    ``core.controller.ReadPlan`` carries it (parity ``par`` at row
    ``prow``, siblings ``sib0`` / ``sib1``, -1 where none)."""

    bank: jnp.ndarray
    row: jnp.ndarray
    mode: jnp.ndarray
    par: jnp.ndarray
    prow: jnp.ndarray
    sib0: jnp.ndarray
    sib1: jnp.ndarray


def gather_decode(
    banks: jnp.ndarray,
    parities: jnp.ndarray,
    cols: PlanColumns,
    *,
    req_block: int = 8,
    interpret=None,
    value_dtype=None,
) -> jnp.ndarray:
    """Serve one cycle's read pattern. Returns (N, W) rows in ``value_dtype``
    (defaults to ``banks.dtype``); unserved entries are zero-filled. Any N
    is accepted, including an empty plan — the pallas wrapper pads requests
    to a full tile with -1 and strips the pad on return."""
    if value_dtype is None:
        value_dtype = banks.dtype
    if jnp.issubdtype(banks.dtype, jnp.floating):
        banks = jax.lax.bitcast_convert_type(banks, uint_view_dtype(banks.dtype))
    if jnp.issubdtype(parities.dtype, jnp.floating):
        parities = jax.lax.bitcast_convert_type(parities, uint_view_dtype(parities.dtype))
    if parities.dtype != banks.dtype:
        raise TypeError(f"lane dtype mismatch: {banks.dtype} vs {parities.dtype}")
    out = gather_decode_pallas(
        banks, parities, cols.bank, cols.row, cols.mode, cols.par, cols.prow,
        cols.sib0, cols.sib1, req_block=req_block, interpret=interpret,
    )
    if jnp.dtype(value_dtype) != out.dtype:
        out = jax.lax.bitcast_convert_type(out, value_dtype)
    return out
