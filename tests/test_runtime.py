"""Runtime tests: fault-tolerant trainer (bit-deterministic recovery),
continuous-batching server, coded KV bank serving path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.launch.mesh import make_debug_mesh
from repro.models import lm
from repro.runtime import kvbank as kb
from repro.runtime.server import Request, ServeConfig, Server
from repro.runtime.trainer import FaultPlan, TrainConfig, Trainer


@pytest.fixture(scope="module")
def mesh():
    return make_debug_mesh(1, 1)


def _tc(tmp, **kw):
    base = dict(steps=12, log_every=100, ckpt_every=5, ckpt_dir=tmp,
                global_batch=4, seq_len=32)
    base.update(kw)
    return TrainConfig(**base)


def test_fault_recovery_is_bit_deterministic(tmp_path, mesh):
    """A crash + restore-from-checkpoint run reaches the SAME final loss as
    an uninterrupted run (pure-function data pipeline + deterministic jit)."""
    cfg = get_config("yi-6b").reduced()
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    t1 = Trainer(cfg, _tc(d1), mesh)
    out1 = t1.run()
    t2 = Trainer(cfg, _tc(d2), mesh)
    out2 = t2.run(fault_plan=FaultPlan([7]))
    assert any("recovering" in e for e in out2["events"])
    assert out1["final_loss"] == pytest.approx(out2["final_loss"], abs=1e-6)


def test_loss_decreases_over_training(tmp_path, mesh):
    cfg = get_config("qwen2.5-3b").reduced()
    tc = _tc(str(tmp_path / "c"), steps=40, ckpt_every=0, global_batch=8)
    tr = Trainer(cfg, tc, mesh)
    out = tr.run()
    losses = [m["loss"] for m in tr.metrics_log]
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_microbatch_equivalence(tmp_path, mesh):
    """Gradient accumulation (n_micro=2) ≈ single-shot on the same batch."""
    cfg = get_config("yi-6b").reduced()
    t1 = Trainer(cfg, _tc(str(tmp_path / "m1"), steps=3, ckpt_every=0), mesh)
    o1 = t1.run()
    t2 = Trainer(cfg, _tc(str(tmp_path / "m2"), steps=3, ckpt_every=0,
                          n_micro=2), mesh)
    o2 = t2.run()
    assert o1["final_loss"] == pytest.approx(o2["final_loss"], rel=2e-2)


def test_straggler_detection(tmp_path, mesh, monkeypatch):
    cfg = get_config("yi-6b").reduced()
    tr = Trainer(cfg, _tc(str(tmp_path / "s"), steps=8, ckpt_every=0), mesh)
    orig = tr.train_step
    calls = {"n": 0}

    def slow_step(*a):
        calls["n"] += 1
        if calls["n"] == 6:
            import time
            time.sleep(1.0)                 # synthetic straggler
        return orig(*a)

    tr.train_step = slow_step
    out = tr.run()
    assert out["stragglers"] >= 1
    assert any("straggler" in e for e in out["events"])


# ------------------------------------------------------------------- server
def test_server_continuous_batching():
    cfg = get_config("qwen2.5-3b").reduced()
    params = lm.init_params(cfg, jax.random.key(0), max_seq=128)
    sc = ServeConfig(n_slots=2, max_prompt=16, max_seq=64, max_new_tokens=6)
    srv = Server(cfg, sc, params)
    reqs = [Request(rid=i, prompt=[1 + i, 2 + i, 3 + i]) for i in range(5)]
    for r in reqs:
        srv.submit(r)
    finished = srv.run_until_drained()
    assert sorted(r.rid for r in finished) == [r.rid for r in reqs]
    assert all(r.done for r in reqs)
    assert all(len(r.out) == sc.max_new_tokens for r in reqs)
    # more requests than slots => batching actually interleaved
    assert srv.steps_run < sum(len(r.out) for r in reqs)


def test_server_snapshot_recovery():
    cfg = get_config("yi-6b").reduced()
    params = lm.init_params(cfg, jax.random.key(0), max_seq=128)
    sc = ServeConfig(n_slots=2, max_prompt=16, max_seq=64, max_new_tokens=8)
    srv = Server(cfg, sc, params)
    for i in range(2):
        srv.submit(Request(rid=i, prompt=[5, 6, 7]))
    srv.step()
    srv.step()
    snap = srv.snapshot()
    cont = [list(r.out) if r else None for r in srv.slots]
    # simulate node replacement
    srv2 = Server(cfg, sc, params)
    srv2.restore_snapshot(snap)
    srv2.step()
    srv.step()
    t_a = np.asarray(srv.tokens)
    t_b = np.asarray(srv2.tokens)
    np.testing.assert_array_equal(t_a, t_b)   # identical continuation


# ------------------------------------------------------------------ kv bank
def _grow(cfg, lengths, n_kv=1, hd=8):
    b = len(lengths)
    st = kb.init_state(cfg, b, n_kv, hd, jnp.bfloat16)
    k = jnp.ones((b, n_kv, hd), jnp.bfloat16)
    for t in range(max(lengths)):
        active = jnp.asarray([t < L for L in lengths])
        st = kb.append_token(cfg, st, k, k, active=active)
    return st


def test_kvbank_cycles_improve_under_conflict():
    """A churned pool (free-list placement after serving turnover) loads
    banks unevenly — the paper's bank conflict; the coded planner must beat
    the uncoded port count. A lone fresh sequence stripes evenly — no idle
    ports, the paper's worst case — coded == uncoded."""
    cfg = kb.KVBankConfig(n_banks=4, page=4, pool_pages=64, max_pages=32)
    st = _grow(cfg, [80, 16, 16, 16])
    # churned placement with a deterministic hot bank: the long sequence's
    # pages mostly landed where bank-0 pages were freed (phys ≡ 0 mod 4)
    table = np.array(st.page_table)     # writable copy
    hot = [4 * i for i in range(12)]            # 12 pages on bank 0
    rest = [4 * i + 1 + (i % 3) for i in range(8)]   # spread over banks 1-3
    table[0, :20] = hot + rest
    for s_, base in ((1, 32), (2, 44), (3, 56)):
        table[s_, :4] = [base + j for j in range(4)]  # striped small seqs
    st = st._replace(page_table=jnp.asarray(table))
    st = kb.recode(cfg, st)
    plan = kb.plan_reads(cfg, st)
    assert int(plan.coded_cycles) < int(plan.uncoded_cycles)

    stb = _grow(cfg, [64])                      # lone sequence: even striping
    stb = kb.recode(cfg, stb)
    planb = kb.plan_reads(cfg, stb)
    assert int(planb.coded_cycles) == int(planb.uncoded_cycles)


def test_pool_recode_row_gather_matches_masked_reference():
    """Budgeted pool_recode now gathers only the taken rows' member banks;
    the result must stay bit-identical to the historical full-recompute +
    mask formulation for every budget (incl. 0 and over-budget)."""
    cfg = kb.KVBankConfig(n_banks=4, page=2, pool_pages=16, max_pages=8)
    rng = np.random.default_rng(11)
    pool = kb.pool_init(cfg, 2, 2, 1, 8, jnp.bfloat16)
    shape = pool.k_banks.shape
    pool = pool._replace(
        k_banks=jnp.asarray(rng.integers(0, 2**16, shape, dtype=np.uint16)),
        v_banks=jnp.asarray(rng.integers(0, 2**16, shape, dtype=np.uint16)),
        parity_fresh=jnp.asarray(rng.integers(0, 2, pool.parity_fresh.shape)
                                 .astype(bool)))
    full_k = pool.k_banks[:, 0::2] ^ pool.k_banks[:, 1::2]
    stale = ~np.asarray(pool.parity_fresh)
    order = np.cumsum(stale.reshape(-1)).reshape(stale.shape)
    for budget in (0, 1, 3, 100):
        got, n = kb.pool_recode(cfg, pool, budget=budget)
        take = stale & (order <= budget)
        assert int(n) == int(take.sum())
        ref_k = np.where(take[None, ..., None, None, None],
                         np.asarray(full_k), np.asarray(pool.k_par))
        np.testing.assert_array_equal(np.asarray(got.k_par), ref_k)
        np.testing.assert_array_equal(np.asarray(got.parity_fresh),
                                      ~stale | take)


def test_pool_write_fused_keeps_parity_consistent():
    """Encode-on-write: the fused layer write must land the same bank bits
    as the plain write AND leave parity equal to a full re-encode —
    including when pair-sibling lanes hit the same parity element (the
    cross-pass collision case) and when a lane is the inactive sink."""
    cfg = kb.KVBankConfig(n_banks=4, page=4, pool_pages=16, max_pages=4)
    rng = np.random.default_rng(5)
    nb, slots, pg = 4, 4, 4
    shape = (nb, slots, pg, 2, 8)
    kbank = jnp.asarray(rng.integers(0, 2**32, shape, dtype=np.uint32))
    vbank = jnp.asarray(rng.integers(0, 2**32, shape, dtype=np.uint32))
    kpar = kbank[0::2] ^ kbank[1::2]
    vpar = vbank[0::2] ^ vbank[1::2]
    # lanes 0/1: sibling banks, same slot, same in_page (parity collision);
    # lane 2: unrelated; lane 3: inactive sink
    bank = jnp.asarray([0, 1, 2, nb], jnp.int32)
    slot = jnp.asarray([1, 1, 3, 0], jnp.int32)
    in_page = jnp.asarray([2, 2, 0, 0], jnp.int32)
    k_new = jnp.asarray(rng.integers(0, 2**32, (4, 2, 8), dtype=np.uint32))
    v_new = jnp.asarray(rng.integers(0, 2**32, (4, 2, 8), dtype=np.uint32))
    widx = (bank, slot, in_page)
    k2, v2, kp2, vp2 = kb.pool_write_layer_fused(
        cfg, kbank, vbank, kpar, vpar, widx, k_new, v_new)
    k2u, v2u = kb.pool_write_layer(cfg, kbank, vbank, widx, k_new, v_new)
    np.testing.assert_array_equal(np.asarray(k2), np.asarray(k2u))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v2u))
    np.testing.assert_array_equal(np.asarray(kp2),
                                  np.asarray(k2u[0::2] ^ k2u[1::2]))
    np.testing.assert_array_equal(np.asarray(vp2),
                                  np.asarray(v2u[0::2] ^ v2u[1::2]))


def test_pool_install_fused_matches_recode():
    """Fused-encode install must leave parity equal to install + full
    re-encode, with the same status-table evolution."""
    cfg = kb.KVBankConfig(n_banks=4, page=2, pool_pages=16, max_pages=8)
    rng = np.random.default_rng(9)
    pool = kb.pool_init(cfg, 2, 2, 1, 8, jnp.float32)
    pt = np.full((2, 8), -1, np.int32)
    pt[0, :5] = [3, 4, 0, 1, 9]     # includes a sibling pair (0, 1)
    pool = pool._replace(page_table=jnp.asarray(pt))
    k_seq = jnp.asarray(rng.normal(size=(2, 10, 1, 8)), jnp.float32)
    v_seq = jnp.asarray(rng.normal(size=(2, 10, 1, 8)), jnp.float32)
    fused = kb.pool_install(cfg, pool, jnp.int32(0), k_seq, v_seq,
                            fuse_encode=True)
    plain = kb.pool_install(cfg, pool, jnp.int32(0), k_seq, v_seq)
    plain_full, _ = kb.pool_recode(cfg, plain, budget=None)
    np.testing.assert_array_equal(np.asarray(fused.k_banks),
                                  np.asarray(plain.k_banks))
    np.testing.assert_array_equal(np.asarray(fused.k_par),
                                  np.asarray(plain_full.k_par))
    np.testing.assert_array_equal(np.asarray(fused.v_par),
                                  np.asarray(plain_full.v_par))
    np.testing.assert_array_equal(np.asarray(fused.parity_fresh),
                                  np.asarray(plain.parity_fresh))


def test_kvbank_stale_parity_never_used():
    cfg = kb.KVBankConfig(n_banks=4, page=4, pool_pages=32, max_pages=16)
    st = _grow(cfg, [40, 8])                    # NO recode → parities stale
    plan = kb.plan_reads(cfg, st)
    fresh = np.asarray(st.parity_fresh)
    phys = np.maximum(np.asarray(st.page_table), 0)
    page_fresh = fresh[(phys % 4) // 2, phys // 4]
    used = np.asarray(plan.use_parity)
    assert not (used & ~page_fresh).any()
    # reconstruction still exact (falls back to direct reads)
    k_log, _ = kb.gather_kv(cfg, st, plan, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(k_log[0, :40], np.float32),
                                  np.ones((40, 1, 8), np.float32))
    np.testing.assert_array_equal(np.asarray(k_log[1, :8], np.float32),
                                  np.ones((8, 1, 8), np.float32))
