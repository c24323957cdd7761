"""Serving runtime: continuous batching over the canonical prefill/decode
steps, with the coded banked KV cache as the storage backend.

Request lifecycle: queued -> prefill (one jit call per admitted request,
padded to ``max_prompt``) -> decode slot (joins the batched decode step) ->
finished (EOS / max_new_tokens). Slots are fixed (``n_slots``) so the decode
step compiles once; free slots decode garbage that is masked out — the
standard continuous-batching trick (vLLM-style, static-shape variant).

Storage backend: when the model config declares KV banks
(``cfg.kv_banks > 0``, global-attention decoder families), decode runs over
the coded KV page pool (``runtime/kvbank.PooledKV``): admission assigns
physical pages from a FIFO free list (freed pages recycle at the tail, so a
long-running server naturally churns placement), appends mark the code
status table, reads follow ``plan_reads``' degraded-read plan through the
pool-indirected ``coded_kv_decode`` gather, and the ReCoding unit refreshes
parity between steps. ``ServeConfig.coded=False`` switches to the uncoded
pool (zero-size parity arrays — a genuinely different compiled program),
and ``ServeConfig.telemetry=True`` rides the ``repro.obs.serve`` metric
planes in the decode cache. Every request's lifecycle is spanned host-side
in a ``repro.obs.serve.ServeLog``.

Fault tolerance: the server state (cache + slot table + page accounting) is
``snapshot()``/``restore_snapshot()`` round-tripped through host memory so
a serving node can be replaced mid-stream (exercised in tests).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import lm
from repro.obs import serve as obs_serve
from repro.runtime import kvbank as kb
from repro.runtime import steps as steps_mod


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    n_slots: int = 4
    max_prompt: int = 64
    max_seq: int = 256
    max_new_tokens: int = 32
    eos_id: int = -1            # -1: never stop early
    # ---- coded KV page pool (active when cfg.kv_banks > 0) ----
    coded: bool = True          # False: uncoded pool (no parity arrays)
    telemetry: bool = False     # device serve metric planes on the carry
    recode_budget: Optional[int] = None  # None: full recode; -1: never
    page: int = 0               # tokens per page; 0 -> cfg.kv_page
    pool_pages: int = 0         # physical pool size; 0 -> 2x working set
    kernel: str = "reference"   # pool gather datapath: "reference"|"pallas"


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


def _wants_pool(cfg: ModelConfig) -> bool:
    # vision prefixes make the prefill cache longer than max_prompt, so the
    # page-table sizing below would not cover them — keep vlm on the ring.
    return (cfg.kv_banks > 0 and cfg.family in ("dense", "moe")
            and not cfg.is_encdec and cfg.sliding_window == 0
            and cfg.frontend == "none")


class Server:
    def __init__(self, cfg: ModelConfig, sc: ServeConfig, params, clock=None):
        self.cfg, self.sc = cfg, sc
        # ring-buffer slot mapping must agree between prefill and decode
        # caches: any attention window must fit inside max_prompt.
        for w in (cfg.sliding_window, cfg.local_window):
            assert w == 0 or w <= sc.max_prompt, (w, sc.max_prompt)
        self.params = params
        self.prefill = jax.jit(steps_mod.make_prefill_step(cfg))
        self.queue: List[Request] = []
        self.slots: List[Optional[Request]] = [None] * sc.n_slots
        self.log = obs_serve.ServeLog(clock=clock)
        b = sc.n_slots
        self.pooled = _wants_pool(cfg)
        if self.pooled:
            page = sc.page or cfg.kv_page
            mp = -(-sc.max_seq // page)
            need = b * mp
            pool_pages = sc.pool_pages or -(-2 * need // cfg.kv_banks) \
                * cfg.kv_banks
            assert pool_pages % cfg.kv_banks == 0, (pool_pages, cfg.kv_banks)
            assert pool_pages >= need, (pool_pages, need)
            self.kvcfg = kb.KVBankConfig(
                n_banks=cfg.kv_banks, page=page, pool_pages=pool_pages,
                max_pages=mp)
            pool = kb.pool_init(self.kvcfg, cfg.n_layers, b, cfg.n_kv,
                                cfg.head_dim, jnp.dtype(cfg.compute_dtype),
                                coded=sc.coded)
            tele = (obs_serve.init_serve_telemetry(cfg.kv_banks)
                    if sc.telemetry else None)
            self.cache: Dict[str, Any] = {"pool": pool, "tele": tele}
            self.free_pages: List[int] = list(range(pool_pages))
            self.slot_pages: List[List[int]] = [[] for _ in range(b)]
            self.decode = jax.jit(steps_mod.make_pooled_serve_step(
                cfg, self.kvcfg, recode_budget=sc.recode_budget,
                kernel=sc.kernel))
            # encode-on-write at install matches the fused decode path (the
            # status table still goes stale-then-fresh identically)
            fuse = sc.coded and sc.recode_budget is None
            self._install_pool = jax.jit(
                lambda pool, i, k, v: kb.pool_install(self.kvcfg, pool,
                                                      i, k, v,
                                                      fuse_encode=fuse))
        else:
            self.decode = jax.jit(steps_mod.make_serve_step(cfg))
            self.cache = lm.cache_spec(cfg, b, sc.max_seq)
        self.tokens = jnp.zeros((b,), jnp.int32)
        self.steps_run = 0

    # ------------------------------------------------------------- admission
    def submit(self, req: Request):
        self.log.submit(req.rid)
        self.queue.append(req)

    def _admit(self):
        for i, slot in enumerate(self.slots):
            if slot is not None or not self.queue:
                continue
            req = self.queue.pop(0)
            prompt = req.prompt[-self.sc.max_prompt:]
            self.log.admit(req.rid, i, len(prompt))
            pad = self.sc.max_prompt - len(prompt)
            toks = jnp.asarray([[0] * pad + prompt], jnp.int32)
            batch = {"tokens": toks}
            if self.cfg.is_encdec:
                batch["frames"] = jnp.zeros(
                    (1, max(self.cfg.enc_frames, 8), self.cfg.d_model),
                    jnp.dtype(self.cfg.compute_dtype))
            if self.cfg.frontend == "vision_stub" and self.cfg.n_patches:
                batch["patches"] = jnp.zeros(
                    (1, self.cfg.n_patches, self.cfg.d_model),
                    jnp.dtype(self.cfg.compute_dtype))
            tok, cache1 = self.prefill(self.params, batch)
            self._install(i, tok, cache1)
            req.out.append(int(tok[0]))
            self.log.prefill_done(req.rid)
            self.slots[i] = req

    def _install(self, i: int, tok, cache1):
        if self.pooled:
            self._install_pooled(i, tok, cache1)
            return
        self._install_ring(i, tok, cache1)

    def _install_ring(self, i: int, tok, cache1):
        """Copy a 1-batch prefill cache into slot i of the decode cache."""
        def put(dst, src):
            # dst (B, ...) or (L, B, ...); src has batch 1 in the same spot
            if dst.ndim >= 2 and src.shape[0] == dst.shape[0] and dst.ndim > 1 \
               and src.shape[1] == 1 and dst.shape[0] != 1:
                # (L, 1, ...) -> slot i of (L, B, ...), seq-padded
                pads = [(0, 0)] * src.ndim
                for ax in range(2, src.ndim):
                    pads[ax] = (0, dst.shape[ax] - src.shape[ax])
                src = jnp.pad(src, pads)
                return dst.at[:, i].set(src[:, 0])
            # (1, ...) -> slot i of (B, ...)
            pads = [(0, 0)] * src.ndim
            for ax in range(1, src.ndim):
                pads[ax] = (0, dst.shape[ax] - src.shape[ax])
            src = jnp.pad(src, pads)
            return dst.at[i].set(src[0])

        self.cache = jax.tree.map(put, self.cache, cache1)
        self.tokens = self.tokens.at[i].set(tok[0])

    def _install_pooled(self, i: int, tok, cache1):
        """Assign pool pages to slot i and install the prefilled KV."""
        need = self.kvcfg.max_pages
        assert len(self.free_pages) >= need, "pool sized below working set"
        phys = [self.free_pages.pop(0) for _ in range(need)]
        pool = self.cache["pool"]
        pool = pool._replace(
            page_table=pool.page_table.at[i].set(
                jnp.asarray(phys, jnp.int32)))
        pool = self._install_pool(pool, jnp.int32(i),
                                  cache1["k"][:, 0], cache1["v"][:, 0])
        self.cache["pool"] = pool
        self.slot_pages[i] = phys
        self.tokens = self.tokens.at[i].set(tok[0])

    def _retire(self, i: int):
        if not self.pooled:
            return
        self.free_pages.extend(self.slot_pages[i])
        self.slot_pages[i] = []
        pool = self.cache["pool"]
        self.cache["pool"] = pool._replace(
            page_table=pool.page_table.at[i].set(-1),
            length=pool.length.at[i].set(0))

    # ----------------------------------------------------------------- step
    def step(self) -> List[Request]:
        """Admit, then decode one step; return the requests it finished."""
        self._admit()
        return self.step_decode()

    def step_decode(self) -> List[Request]:
        """One batched decode step (no admission) — exposed so telemetry
        conformance checks can observe the pool between admit and decode.
        Returns the requests this step finished."""
        finished: List[Request] = []
        if not any(s is not None for s in self.slots):
            return finished
        self.tokens, self.cache = self.decode(self.params, self.tokens,
                                              self.cache)
        self.steps_run += 1
        toks = np.asarray(self.tokens)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            t = int(toks[i])
            req.out.append(t)
            self.log.token(req.rid)
            if (self.sc.eos_id >= 0 and t == self.sc.eos_id) or \
               len(req.out) >= self.sc.max_new_tokens:
                req.done = True
                self.log.finish(req.rid)
                self.slots[i] = None
                self._retire(i)
                finished.append(req)
        return finished

    def run_until_drained(self, max_steps: int = 10_000) -> List[Request]:
        """Step until the queue and every slot are empty; return the
        requests finished meanwhile, in the order they finished."""
        finished: List[Request] = []
        for _ in range(max_steps):
            finished += self.step()
            if not self.queue and all(s is None for s in self.slots):
                break
        return finished

    # ------------------------------------------------------------ telemetry
    def serve_snapshot(self) -> Optional[obs_serve.ServeSnapshot]:
        """Host view of the device serve planes (None when telemetry off)."""
        tele = self.cache.get("tele") if self.pooled else None
        return None if tele is None else obs_serve.snapshot(tele)

    def permute_pool(self, perm):
        """Relocate physical pages (placement churn / defrag model): page p
        moves to ``perm[p]``; tables, free list and parity follow, so decode
        output is invariant."""
        assert self.pooled, "permute_pool requires the paged pool backend"
        perm = np.asarray(perm)
        self.cache["pool"] = kb.pool_permute(
            self.kvcfg, self.cache["pool"], jnp.asarray(perm, jnp.int32))
        self.free_pages = [int(perm[p]) for p in self.free_pages]
        self.slot_pages = [[int(perm[p]) for p in pp]
                           for pp in self.slot_pages]

    # -------------------------------------------------------- fault recovery
    def snapshot(self) -> Dict[str, Any]:
        snap = {
            "cache": jax.tree.map(lambda a: np.asarray(a), self.cache),
            "tokens": np.asarray(self.tokens),
            "slots": [(r.rid, list(r.prompt), list(r.out)) if r else None
                      for r in self.slots],
        }
        if self.pooled:
            snap["free_pages"] = list(self.free_pages)
            snap["slot_pages"] = [list(p) for p in self.slot_pages]
        return snap

    def restore_snapshot(self, snap: Dict[str, Any]):
        self.cache = jax.tree.map(jnp.asarray, snap["cache"])
        self.tokens = jnp.asarray(snap["tokens"])
        self.slots = [Request(rid=s[0], prompt=s[1], out=s[2]) if s else None
                      for s in snap["slots"]]
        if self.pooled:
            self.free_pages = list(snap["free_pages"])
            self.slot_pages = [list(p) for p in snap["slot_pages"]]
