"""Public wrapper: coded gather + the controller-plan → kernel-plan bridge."""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.codes import MAX_OPTS, CodeTables
from repro.core.controller import MODE_OPT0, MODE_REDIRECT, ReadPlan
from repro.core.state import cells
from repro.kernels.common import uint_view_dtype
from repro.kernels.xor_gather.kernel import gather_decode_pallas


class PlanColumns(NamedTuple):
    bank: jnp.ndarray
    row: jnp.ndarray
    mode: jnp.ndarray
    par: jnp.ndarray
    prow: jnp.ndarray
    sib0: jnp.ndarray
    sib1: jnp.ndarray


def plan_columns(
    tables: CodeTables,
    plan: ReadPlan,
    cand_bank: jnp.ndarray,
    cand_row: jnp.ndarray,
    region_slot: jnp.ndarray,
    region_size: int,
    fresh_loc: jnp.ndarray,
) -> PlanColumns:
    """Expand a controller ReadPlan into the kernel's per-request columns."""
    b = jnp.maximum(cand_bank, 0)
    i = jnp.maximum(cand_row, 0)
    opt_parity = jnp.asarray(tables.opt_parity)
    opt_sibs = jnp.asarray(tables.opt_sibs)
    k = jnp.clip(plan.mode - MODE_OPT0, 0, MAX_OPTS - 1)
    is_opt = (plan.mode >= MODE_OPT0) & (plan.mode < MODE_REDIRECT)
    is_rd = plan.mode == MODE_REDIRECT
    j_opt = opt_parity[b, k]
    j_rd = jnp.maximum(cells(fresh_loc, b, i) - 1, 0)
    par = jnp.where(is_opt, j_opt, jnp.where(is_rd, j_rd, 0))
    slot = region_slot[i // region_size]
    prow = jnp.maximum(slot, 0) * region_size + i % region_size
    sib0 = jnp.where(is_opt, opt_sibs[b, k, 0], -1)
    sib1 = jnp.where(is_opt, opt_sibs[b, k, 1], -1)
    mode = jnp.where(plan.served, plan.mode, -1)
    return PlanColumns(b.astype(jnp.int32), i.astype(jnp.int32), mode,
                       par.astype(jnp.int32), prow.astype(jnp.int32),
                       sib0.astype(jnp.int32), sib1.astype(jnp.int32))


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
