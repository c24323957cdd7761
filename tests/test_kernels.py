"""Pallas kernel tests: shape/dtype sweeps against the pure-jnp oracles
(interpret=True — the kernel body executes on CPU; BlockSpecs target TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import guard as anl_guard
from repro.core import controller as ctl
from repro.core.codes import get_tables
from repro.kernels.coded_kv_decode import ops as kv_ops
from repro.kernels.coded_kv_decode import ref as kv_ref
from repro.kernels.xor_encode import ops as enc_ops
from repro.kernels.xor_encode import ref as enc_ref
from repro.kernels.xor_gather import ops as g_ops
from repro.kernels.xor_gather import ref as g_ref


def _no_recompiles(name, budget=1):
    """Bound the kernel compiles of a region."""
    return anl_guard.recompile_guard(name, max_compiles=budget)


# ------------------------------------------------------------- xor_encode
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32, jnp.uint16,
                                   jnp.int32])
@pytest.mark.parametrize("rows,width", [(16, 128), (32, 256), (8, 384)])
@pytest.mark.parametrize("scheme", ["scheme_i", "scheme_iii"])
def test_xor_encode_sweep(dtype, rows, width, scheme):
    t = get_tables(scheme, n_data=t_nd(scheme))
    key = jax.random.key(hash((rows, width)) % (2**31))
    if jnp.issubdtype(dtype, jnp.floating):
        banks = jax.random.normal(key, (t.n_data, rows, width), dtype)
    else:
        banks = jax.random.randint(key, (t.n_data, rows, width), 0, 1 << 15
                                   ).astype(dtype)
    # one program per shape class: a second call with fresh values (same
    # shapes) must hit the jit cache, not recompile
    with _no_recompiles("kernels.xor_encode", budget=1):
        out = enc_ops.encode_parities(banks, t.par_members, block_rows=8)
        out2 = enc_ops.encode_parities(jnp.roll(banks, 1, axis=1),
                                       t.par_members, block_rows=8)
    banks_u = banks
    if jnp.issubdtype(dtype, jnp.floating):
        from repro.kernels.common import uint_view_dtype
        banks_u = jax.lax.bitcast_convert_type(banks, uint_view_dtype(dtype))
    ref = enc_ref.encode_parities_ref(banks_u, jnp.asarray(t.par_members))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    ref2 = enc_ref.encode_parities_ref(jnp.roll(banks_u, 1, axis=1),
                                       jnp.asarray(t.par_members))
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(ref2))


def t_nd(scheme):
    return 9 if scheme == "scheme_iii" else 8


# ------------------------------------------------------------- xor_gather
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("n_req", [4, 16, 30])
def test_xor_gather_modes(dtype, n_req):
    """Random mix of direct / degraded / redirect / unserved requests."""
    t = get_tables("scheme_i")
    rows, width = 16, 128
    key = jax.random.key(n_req)
    banks = jax.random.normal(key, (8, rows, width), dtype)
    par = enc_ops.encode_parities(banks, t.par_members, block_rows=8)

    rng = np.random.default_rng(n_req)
    bank = rng.integers(0, 8, n_req).astype(np.int32)
    row = rng.integers(0, rows, n_req).astype(np.int32)
    mode = np.full(n_req, ctl.MODE_DIRECT, np.int32)
    par_col = np.zeros(n_req, np.int32)
    sib0 = np.full(n_req, -1, np.int32)
    sib1 = np.full(n_req, -1, np.int32)
    for i in range(n_req):
        c = rng.random()
        if c < 0.4:                        # degraded via a random option
            k = rng.integers(0, int(t.opt_n[bank[i]]))
            mode[i] = ctl.MODE_OPT0 + k
            par_col[i] = t.opt_parity[bank[i], k]
            sib0[i] = t.opt_sibs[bank[i], k, 0]
            sib1[i] = t.opt_sibs[bank[i], k, 1]
        elif c < 0.5:
            mode[i] = ctl.MODE_UNSERVED
    cols = g_ops.PlanColumns(*(jnp.asarray(a) for a in
                               (bank, row, mode, par_col, row, sib0, sib1)))
    with _no_recompiles("kernels.xor_gather", budget=1):
        out = g_ops.gather_decode(banks, par, cols, req_block=8,
                                  value_dtype=dtype)
    from repro.kernels.common import uint_view_dtype
    u = uint_view_dtype(dtype)
    ref = g_ref.gather_decode_ref(
        jax.lax.bitcast_convert_type(banks, u), par,
        cols.bank, cols.row, cols.mode, cols.par, cols.prow, cols.sib0,
        cols.sib1)
    ref = jax.lax.bitcast_convert_type(ref, dtype)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # degraded reads reconstruct the *logical* row bit-exactly
    for i in range(n_req):
        if ctl.MODE_OPT0 <= mode[i] < ctl.MODE_REDIRECT:
            np.testing.assert_array_equal(
                np.asarray(out[i]), np.asarray(banks[bank[i], row[i]]))


# --------------------------------------------------------- coded_kv_decode
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("t_len,h,hkv,d", [(128, 4, 2, 32), (256, 8, 2, 64),
                                           (64, 4, 4, 128)])
def test_coded_kv_decode_sweep(dtype, t_len, h, hkv, d):
    nb, page = 4, t_len // 8
    b = 2
    k = jax.random.normal(jax.random.key(1), (b, t_len, hkv, d), dtype)
    v = jax.random.normal(jax.random.key(2), (b, t_len, hkv, d), dtype)
    q = jax.random.normal(jax.random.key(3), (b, h, d), dtype)
    ku, vu, kp, vp, n_pages = kv_ops.pack_kv_banks(k, v, nb, page)
    seq = jnp.asarray([t_len, t_len // 2], jnp.int32)
    use_par = jax.random.bernoulli(jax.random.key(4), 0.5, (b, n_pages))
    with _no_recompiles("kernels.coded_kv_decode", budget=1):
        out = kv_ops.coded_kv_decode(q, ku, vu, kp, vp, use_par, seq)
    ref = kv_ref.decode_attention_ref(q, k, v, seq)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_xor_gather_empty_plan():
    """Regression: an N=0 plan used to divide by zero sizing the request
    grid. Every public entry point must return an empty (0, W) result."""
    from repro.kernels.xor_gather.kernel import gather_decode_pallas
    banks = jnp.zeros((8, 16, 128), jnp.uint32)
    par = jnp.zeros((4, 16, 128), jnp.uint32)
    empty = jnp.zeros((0,), jnp.int32)
    out = gather_decode_pallas(banks, par, *([empty] * 7), interpret=True)
    assert out.shape == (0, 128) and out.dtype == jnp.uint32
    cols = g_ops.PlanColumns(*([empty] * 7))
    out2 = g_ops.gather_decode(banks, par, cols, interpret=True,
                               value_dtype=jnp.float32)
    assert out2.shape == (0, 128) and out2.dtype == jnp.float32


@pytest.mark.parametrize("n_req", [1, 5, 13])
def test_xor_gather_ragged_requests_direct(n_req):
    """Regression: the pallas wrapper itself (not just gather_decode) must
    accept any N — it used to assert on N % req_block != 0. The -1 pad rows
    select nothing and are stripped from the result."""
    from repro.kernels.xor_gather.kernel import gather_decode_pallas
    rng = np.random.default_rng(n_req)
    banks = jnp.asarray(rng.integers(0, 2**32, (8, 16, 128),
                                     dtype=np.uint32))
    par = jnp.asarray(rng.integers(0, 2**32, (4, 16, 128), dtype=np.uint32))
    bank = jnp.asarray(rng.integers(0, 8, n_req), jnp.int32)
    row = jnp.asarray(rng.integers(0, 16, n_req), jnp.int32)
    mode = jnp.ones((n_req,), jnp.int32)
    zero = jnp.zeros((n_req,), jnp.int32)
    neg = jnp.full((n_req,), -1, jnp.int32)
    out = gather_decode_pallas(banks, par, bank, row, mode, zero, zero,
                               neg, neg, req_block=8, interpret=True)
    assert out.shape == (n_req, 128)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(banks[bank, row]))


def test_resolve_interpret_backend_policy():
    from repro.kernels.common import resolve_interpret
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    expect = jax.default_backend() != "tpu"
    assert resolve_interpret(None) is expect
    assert resolve_interpret() is expect


def _count_eqns(jaxpr) -> int:
    n = len(jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):          # ClosedJaxpr
                n += _count_eqns(v.jaxpr)
            elif hasattr(v, "eqns"):         # raw Jaxpr
                n += _count_eqns(v)
    return n


def test_kv_decode_compile_size_independent_of_pages():
    """The page walk is a fori_loop, not a Python unroll: the traced
    program must have the same equation count for 8 and 32 pages."""
    from repro.kernels.coded_kv_decode.kernel import coded_kv_decode_pallas

    def trace(n_slots):
        b, nb, page, hkv, d, h = 1, 4, 8, 2, 32, 4
        shape = (b, nb, n_slots, page, hkv, d)
        pshape = (b, nb // 2, n_slots, page, hkv, d)
        n_pages = nb * n_slots
        jx = jax.make_jaxpr(
            lambda q, kb, vb, kp, vp, up, sl: coded_kv_decode_pallas(
                q, kb, vb, kp, vp, up, sl, interpret=True))(
            jnp.zeros((b, h, d), jnp.float32),
            jnp.zeros(shape, jnp.uint32), jnp.zeros(shape, jnp.uint32),
            jnp.zeros(pshape, jnp.uint32), jnp.zeros(pshape, jnp.uint32),
            jnp.zeros((b, n_pages), jnp.int32),
            jnp.zeros((b,), jnp.int32))
        return _count_eqns(jx.jaxpr)

    assert trace(2) == trace(8)


# -------------------------------------------------------------- pool gather
@pytest.mark.parametrize("coded", [True, False])
def test_pool_gather_pallas_matches_reference(coded):
    """The serving-pool Pallas gather is bit-exact vs the jnp reference on
    randomized plans (mixed direct/degraded, unallocated -1 pages)."""
    nb, slots, pg, hkv, d = 4, 4, 2, 2, 32
    b, mp = 3, 6
    ng = nb // 2 if coded else 0
    rng = np.random.default_rng(7 + coded)
    kb = jnp.asarray(rng.integers(0, 2**32, (nb, slots, pg, hkv, d),
                                  dtype=np.uint32))
    vb = jnp.asarray(rng.integers(0, 2**32, (nb, slots, pg, hkv, d),
                                  dtype=np.uint32))
    kp = (kb[0::2] ^ kb[1::2])[:ng]
    vp = (vb[0::2] ^ vb[1::2])[:ng]
    pt = np.full((b, mp), -1, np.int32)
    flat = rng.permutation(nb * slots)[: b * mp - 4]      # leave some -1
    pt.reshape(-1)[: flat.size] = flat
    pt = jnp.asarray(pt)
    upar = jnp.asarray(rng.integers(0, 2, (b, mp)).astype(bool) if coded
                       else np.zeros((b, mp), bool))
    with _no_recompiles("kernels.pool_gather", budget=1):
        got_k, got_v = kv_ops.gather_pool_layer(
            kb, vb, kp, vp, pt, upar, jnp.float32, kernel="pallas",
            interpret=True)
    ref_k, ref_v = kv_ops.gather_pool_layer(kb, vb, kp, vp, pt, upar,
                                            jnp.float32)
    np.testing.assert_array_equal(np.asarray(got_k).view(np.uint32),
                                  np.asarray(ref_k).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(got_v).view(np.uint32),
                                  np.asarray(ref_v).view(np.uint32))


def test_coded_kv_parity_mix_invariance():
    """The answer must not depend on WHICH pages use the parity path."""
    dtype = jnp.bfloat16
    b, t_len, h, hkv, d = 1, 128, 4, 2, 32
    nb, page = 4, 16
    k = jax.random.normal(jax.random.key(5), (b, t_len, hkv, d), dtype)
    v = jax.random.normal(jax.random.key(6), (b, t_len, hkv, d), dtype)
    q = jax.random.normal(jax.random.key(7), (b, h, d), dtype)
    ku, vu, kp, vp, n_pages = kv_ops.pack_kv_banks(k, v, nb, page)
    seq = jnp.asarray([t_len], jnp.int32)
    outs = []
    # the parity mask is carry data, not a compile key: all three mixes
    # must run through at most one compiled program
    with _no_recompiles("kernels.coded_kv_decode", budget=1):
        for seed in range(3):
            up = jax.random.bernoulli(jax.random.key(seed), 0.5, (b, n_pages))
            outs.append(np.asarray(
                kv_ops.coded_kv_decode(q, ku, vu, kp, vp, up, seq),
                np.float32))
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])
