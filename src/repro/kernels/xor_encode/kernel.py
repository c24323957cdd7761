"""Pallas TPU kernel: XOR parity encode (the ReCoding-unit datapath, §IV-D).

Given stacked data banks ``(n_data, L, W)`` and a member table
``(n_par, 3)`` (-1 padded), produce parity banks ``(n_par, L, W)`` with
``p_j(i) = XOR_{m in members_j} bank_m(i)``.

Tiling: grid ``(L / BL, n_par)``; each step holds a ``(n_data, BL, W)``
slab of all data banks in VMEM (the encode reads every member anyway, and
row tiles are reused across the ``n_par`` inner grid dimension so the slab
is fetched once per row tile, not once per parity) and writes one
``(1, BL, W)`` parity tile. ``W`` should be a multiple of 128 (VPU lanes)
and ``BL`` a multiple of 8 (f32 sublanes; 16 for bf16).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret


def _encode_kernel(members_ref, banks_ref, out_ref):
    j = pl.program_id(1)
    acc = None
    for mm in range(members_ref.shape[1]):
        m = members_ref[j, mm]
        slab = banks_ref[pl.ds(jnp.maximum(m, 0), 1), :, :]
        slab = jnp.where(m >= 0, slab, jnp.zeros_like(slab))
        acc = slab if acc is None else acc ^ slab
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def encode_parities_pallas(
    banks: jnp.ndarray,     # (n_data, L, W) unsigned-int lane view
    members: jnp.ndarray,   # (n_par, 3) int32, -1 padded
    *,
    block_rows: int = 128,
    interpret=None,
) -> jnp.ndarray:
    """Integer-lane parity encode. Callers bitcast float banks to their uint
    lane view first (see ops.encode_parities): parity banks are raw bits, not
    numbers, and float ops on CPU/TPU may canonicalize NaN payloads.
    ``interpret=None`` resolves from the backend (docs/kernels.md)."""
    assert jnp.issubdtype(banks.dtype, jnp.integer), banks.dtype
    interpret = resolve_interpret(interpret)
    n_data, L, W = banks.shape
    n_par = members.shape[0]
    bl = min(block_rows, L)
    assert L % bl == 0, (L, bl)
    grid = (L // bl, n_par)
    return pl.pallas_call(
        _encode_kernel,
        out_shape=jax.ShapeDtypeStruct((n_par, L, W), banks.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_par, members.shape[1]), lambda t, j: (0, 0)),
            pl.BlockSpec((n_data, bl, W), lambda t, j: (0, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, bl, W), lambda t, j: (j, t, 0)),
        interpret=interpret,
    )(members, banks)
