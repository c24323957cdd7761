"""Pallas TPU kernels: decode attention over a *banked, coded* paged KV cache.

The TPU adaptation of the paper's §IV read path for serving: KV pages are
striped across ``NB`` single-ported banks (page ``t`` → bank ``t % NB``,
slot ``t // NB``); bank pairs ``(2g, 2g+1)`` carry an XOR parity bank
(Scheme-I pairwise code, locality 2). When the per-step page schedule marks a
page as conflicted (its bank's DMA queue is over-subscribed), the kernel
reconstructs that page from its *pair sibling* + the parity page instead of
touching the hot bank — trading a hot-bank read for two idle-bank reads,
exactly the paper's degraded read.

All KV lanes enter as raw ``uint16``/``uint32`` bits (bit-exact coding);
they are bitcast to the compute dtype after reconstruction. Softmax is
accumulated flash-style in f32 over pages; the page walk is a
``fori_loop`` with dynamic bank/slot addressing, so the traced program —
and the compile time — is O(1) in the page count (docs/kernels.md).

Two kernels share the layout:

* ``coded_kv_decode_pallas`` — full attention over per-sequence banks,
  grid ``(B,)``; per-sequence blocks q ``(1, H, D)``, banks
  ``(1, NB, S, P, Hkv, D)``, parity ``(1, NB/2, S, P, Hkv, D)``.
* ``gather_pool_pallas`` — the SERVING pool gather (shared pool, per-batch
  page table), grid ``(B, MP)``: one logical page reconstructed per step
  from pages the scalar-prefetched page table addresses, bit-exact vs
  ``ops.gather_pool_layer`` (the reference anchor), so the
  ``ServeConfig(kernel="pallas")`` switch is token-identical by
  construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret


def _kv_decode_kernel(q_ref, kb_ref, vb_ref, kp_ref, vp_ref, upar_ref,
                      slen_ref, out_ref, *, value_dtype, n_pages, nb, page):
    h, d = q_ref.shape[1], q_ref.shape[2]
    q = q_ref[0].astype(jnp.float32)                       # (H, D)
    hkv = kb_ref.shape[4]
    g = h // hkv
    qr = q.reshape(g, hkv, d)
    slen = slen_ref[0]

    def load_page(ref, b_, s_):
        return ref[0, b_, s_]

    def step(t, carry):
        m, s, acc = carry
        bank = t % nb
        slot = t // nb
        sib = bank ^ 1
        grp = bank // 2
        use_par = upar_ref[0, t] > 0
        k_dir = load_page(kb_ref, bank, slot)              # (P, Hkv, D) uint
        k_rec = load_page(kb_ref, sib, slot) ^ load_page(kp_ref, grp, slot)
        v_dir = load_page(vb_ref, bank, slot)
        v_rec = load_page(vb_ref, sib, slot) ^ load_page(vp_ref, grp, slot)
        k_bits = jnp.where(use_par, k_rec, k_dir)
        v_bits = jnp.where(use_par, v_rec, v_dir)
        k = jax.lax.bitcast_convert_type(k_bits, value_dtype).astype(jnp.float32)
        v = jax.lax.bitcast_convert_type(v_bits, value_dtype).astype(jnp.float32)
        # scores (G, Hkv, P)
        logits = jax.lax.dot_general(
            qr, k, (((2,), (2,)), ((1,), (1,))),
            preferred_element_type=jnp.float32,
        )  # dims: contract D, batch Hkv -> (Hkv, G, P)
        logits = jnp.transpose(logits, (1, 0, 2)) * (d ** -0.5)  # (G, Hkv, P)
        tok = t * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
        logits = jnp.where(tok < slen, logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
        probs = jnp.exp(logits - m_new[..., None])
        probs = jnp.where(tok < slen, probs, 0.0)
        s = s * alpha + jnp.sum(probs, axis=-1)
        # pv: (G, Hkv, P) x (P, Hkv, D) -> (G, Hkv, D)
        pv = jax.lax.dot_general(
            probs, v, (((2,), (0,)), ((1,), (1,))),
            preferred_element_type=jnp.float32,
        )  # (Hkv, G, D)
        acc = acc * alpha[..., None] + jnp.transpose(pv, (1, 0, 2))
        return m_new, s, acc

    m0 = jnp.full((g, hkv), -jnp.inf, jnp.float32)
    s0 = jnp.zeros((g, hkv), jnp.float32)
    a0 = jnp.zeros((g, hkv, d), jnp.float32)
    m, s, acc = jax.lax.fori_loop(0, n_pages, step, (m0, s0, a0))

    out = acc / jnp.maximum(s, 1e-30)[..., None]
    out_ref[0] = out.reshape(h, d).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("value_dtype", "interpret")
)
def coded_kv_decode_pallas(
    q: jnp.ndarray,        # (B, H, D) value dtype
    k_banks: jnp.ndarray,  # (B, NB, S, P, Hkv, D) uint lanes
    v_banks: jnp.ndarray,
    k_par: jnp.ndarray,    # (B, NB//2, S, P, Hkv, D) uint lanes
    v_par: jnp.ndarray,
    use_parity: jnp.ndarray,  # (B, n_pages) int32
    seq_len: jnp.ndarray,     # (B,) int32
    *,
    value_dtype=jnp.float32,
    interpret=None,
) -> jnp.ndarray:
    b, h, d = q.shape
    _, nb, s_, p_, hkv, _ = k_banks.shape
    n_pages = use_parity.shape[1]
    assert n_pages <= nb * s_
    interpret = resolve_interpret(interpret)
    kernel = functools.partial(
        _kv_decode_kernel, value_dtype=jnp.dtype(value_dtype),
        n_pages=n_pages, nb=nb, page=p_,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, nb, s_, p_, hkv, d), lambda i: (i, 0, 0, 0, 0, 0)),
            pl.BlockSpec((1, nb, s_, p_, hkv, d), lambda i: (i, 0, 0, 0, 0, 0)),
            pl.BlockSpec((1, nb // 2, s_, p_, hkv, d), lambda i: (i, 0, 0, 0, 0, 0)),
            pl.BlockSpec((1, nb // 2, s_, p_, hkv, d), lambda i: (i, 0, 0, 0, 0, 0)),
            pl.BlockSpec((1, n_pages), lambda i: (i, 0)),
            pl.BlockSpec((1,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda i: (i, 0, 0)),
        interpret=interpret,
    )(q, k_banks, v_banks, k_par, v_par, use_parity, seq_len)


# ---------------------------------------------------------------------------
# Serving pool gather: pool-indirected page reconstruction
# ---------------------------------------------------------------------------

def _pool_gather_kernel(pt_ref, up_ref, kd_ref, vd_ref, ks_ref, vs_ref,
                        kp_ref, vp_ref, ko_ref, vo_ref, *, mp):
    n = pl.program_id(0) * mp + pl.program_id(1)
    alloc = pt_ref[n] >= 0
    use_par = up_ref[n] > 0
    k = jnp.where(use_par, ks_ref[0, 0] ^ kp_ref[0, 0], kd_ref[0, 0])
    v = jnp.where(use_par, vs_ref[0, 0] ^ vp_ref[0, 0], vd_ref[0, 0])
    ko_ref[0, 0] = jnp.where(alloc, k, 0)
    vo_ref[0, 0] = jnp.where(alloc, v, 0)


def _pool_gather_uncoded_kernel(pt_ref, kd_ref, vd_ref, ko_ref, vo_ref, *,
                                mp):
    alloc = pt_ref[pl.program_id(0) * mp + pl.program_id(1)] >= 0
    ko_ref[0, 0] = jnp.where(alloc, kd_ref[0, 0], 0)
    vo_ref[0, 0] = jnp.where(alloc, vd_ref[0, 0], 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_pool_pallas(
    k_banks: jnp.ndarray,     # (NB, S, P, Hkv, D) uint lanes (shared pool)
    v_banks: jnp.ndarray,
    k_par: jnp.ndarray,       # (NG, S, P, Hkv, D); NG == 0 ⇒ uncoded
    v_par: jnp.ndarray,
    page_table: jnp.ndarray,  # (B, MP) int32 physical page id, -1 free
    use_parity: jnp.ndarray,  # (B, MP) bool/int
    *,
    interpret=None,
):
    """Pool-indirected coded page gather: (B, MP, P, Hkv, D) uint K/V.

    Grid ``(B, MP)`` — one logical page per step, reconstructed with the
    planned direct or degraded (sibling ^ parity) read. The page table and
    the plan are scalar-prefetched, and each page operand's ``index_map``
    resolves its block from them: the direct page ``(bank, slot)``, the
    sibling ``(bank ^ 1, slot)`` and the parity page ``(bank // 2, slot)``
    arrive as ``(1, 1, P, Hkv, D)`` blocks, so VMEM holds pages, never the
    pool. Free (-1) entries fetch page 0 and are zeroed in the body. Pure
    uint select/XOR, so the result is bit-exact vs the reference
    ``gather_pool_layer`` for any plan. The uncoded pool (NG == 0)
    compiles a kernel with no parity operands.
    """
    interpret = resolve_interpret(interpret)
    nb, _, pg, hkv, d = k_banks.shape
    b, mp = page_table.shape
    ng = k_par.shape[0]
    pt = page_table.astype(jnp.int32).reshape(b * mp)

    def page_spec(which):
        def index_map(i, p, pt_ref, *_):
            ph = jnp.maximum(pt_ref[i * mp + p], 0)
            bank, slot = ph % nb, ph // nb
            return (which(bank), slot, 0, 0, 0)
        return pl.BlockSpec((1, 1, pg, hkv, d), index_map)

    direct = page_spec(lambda bank: bank)
    sibling = page_spec(lambda bank: bank ^ 1)
    parity = page_spec(lambda bank: bank // 2)
    out_spec = pl.BlockSpec((1, 1, pg, hkv, d),
                            lambda i, p, *_: (i, p, 0, 0, 0))
    out_shape = [jax.ShapeDtypeStruct((b, mp, pg, hkv, d), k_banks.dtype)] * 2
    if ng == 0:
        return pl.pallas_call(
            functools.partial(_pool_gather_uncoded_kernel, mp=mp),
            out_shape=out_shape,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(b, mp),
                in_specs=[direct, direct], out_specs=[out_spec, out_spec]),
            interpret=interpret,
        )(pt, k_banks, v_banks)
    return pl.pallas_call(
        functools.partial(_pool_gather_kernel, mp=mp),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, mp),
            in_specs=[direct, direct, sibling, sibling, parity, parity],
            out_specs=[out_spec, out_spec]),
        interpret=interpret,
    )(pt, use_parity.astype(jnp.int32).reshape(b * mp),
      k_banks, v_banks, k_banks, v_banks, k_par, v_par)
