"""Unified language model over all assigned architecture families.

Families:
  dense / moe — pre-norm decoder blocks (GQA attention + MLP/MoE), scanned
                over stacked per-layer params.
  ssm         — mamba2 SSD mixer blocks (attention-free).
  hybrid      — recurrentgemma: repeating (rec, rec, local-attn) pattern.
  audio/vlm   — whisper enc-dec (audio_stub frontend) / phi3+vision_stub;
                modality frontends provide precomputed embeddings.

Three entry points per the assigned shapes:
  loss_fn(cfg, params, batch)            — train_4k         (train_step)
  prefill(cfg, params, batch)            — prefill_32k      (serve prefill)
  decode_step(cfg, params, token, cache) — decode_32k/long_500k (serve decode)

Params are nested dicts with per-layer leaves stacked on axis 0; layer loops
are ``lax.scan`` with configurable ``unroll`` (full unroll for trip-count-
accurate dry-run cost analysis) and per-layer ``jax.checkpoint`` for train.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.axes import shard
from repro.configs.base import ModelConfig
from repro.models import layers as ly
from repro.models import moe as moe_mod
from repro.models import rglru as rg
from repro.models import ssm as ssm_mod
from repro.models.embedding import embed_init, embed_lookup, full_table

Params = Dict[str, Any]
Cache = Dict[str, Any]


def _dtype(name: str):
    return jnp.dtype(name)


def _cast_params(params, cd):
    """Cast float param leaves to the compute dtype (mixed-precision matmuls).
    Numerically-sensitive scalars (A_log, lam, …) are re-upcast to f32 inside
    their modules."""
    return jax.tree.map(
        lambda a: a.astype(cd) if jnp.issubdtype(a.dtype, jnp.floating) else a,
        params,
    )


def _stack(fn, key, n: int):
    """Stack n per-layer param trees on axis 0. n == 0 yields zero-length
    leading dims (NOT None) so scans/tree.maps stay total — hybrid probe
    configs can have zero attention layers. ``vmap`` builds the stacked
    leaves directly (the same values as one ``fn`` call per key): no
    per-layer copies are held beside them, and under ``jit`` the program
    is one op per leaf, not one per layer."""
    stacked = jax.vmap(fn)(jax.random.split(key, max(n, 1)))
    return jax.tree.map(lambda a: a[:n], stacked)


def hybrid_layout(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_superblocks, n_rem_rec, n_attn) for the repeating block pattern."""
    pat = cfg.block_pattern or ("rec", "rec", "attn")
    per = len(pat)
    n_super = cfg.n_layers // per
    rem = cfg.n_layers - n_super * per
    # remainder layers follow the pattern prefix; only 'rec' prefixes occur
    n_rem_rec = sum(1 for b in pat[:rem] if b == "rec")
    n_attn = n_super * sum(1 for b in pat if b == "attn")
    return n_super, n_rem_rec, n_attn


# ======================================================================
# init
# ======================================================================
def init_params(cfg: ModelConfig, key, max_seq: int = 2048) -> Params:
    pd = _dtype(cfg.param_dtype)
    k_embed, k_blocks, k_head, k_enc, k_pos = jax.random.split(key, 5)
    params: Params = {
        "embed": embed_init(cfg, k_embed, pd),
        "final_norm": ly.norm_init(cfg, pd),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (cfg.d_model, cfg.vocab_pad), pd)
            * cfg.d_model ** -0.5
        )
    if cfg.pos == "learned":
        params["pos_embed"] = (
            jax.random.normal(k_pos, (max_seq, cfg.d_model), pd) * 0.02
        )

    def dense_block(k):
        k1, k2 = jax.random.split(k)
        p = {"norm1": ly.norm_init(cfg, pd), "norm2": ly.norm_init(cfg, pd),
             "attn": ly.attn_init(cfg, k1, pd)}
        if cfg.family == "moe":
            p["moe"] = moe_mod.moe_init(cfg, k2, pd)
        else:
            p["mlp"] = ly.mlp_init(cfg, k2, pd)
        return p

    if cfg.family in ("dense", "moe", "vlm"):
        params["blocks"] = _stack(dense_block, k_blocks, cfg.n_layers)
    elif cfg.family == "ssm":
        params["blocks"] = _stack(
            lambda k: {"norm1": ly.norm_init(cfg, pd),
                       "ssm": ssm_mod.ssm_init(cfg, k, pd)},
            k_blocks, cfg.n_layers)
    elif cfg.family == "hybrid":
        n_super, n_rem_rec, n_attn = hybrid_layout(cfg)
        n_rec = cfg.n_layers - n_attn

        def rec_block(k):
            k1, k2 = jax.random.split(k)
            return {"norm1": ly.norm_init(cfg, pd), "norm2": ly.norm_init(cfg, pd),
                    "rglru": rg.rglru_init(cfg, k1, pd),
                    "mlp": ly.mlp_init(cfg, k2, pd)}

        k_rec, k_attn = jax.random.split(k_blocks)
        params["rec_blocks"] = _stack(rec_block, k_rec, n_rec)
        params["attn_blocks"] = _stack(dense_block, k_attn, n_attn)
    elif cfg.family == "audio":  # whisper enc-dec
        def enc_block(k):
            k1, k2 = jax.random.split(k)
            return {"norm1": ly.norm_init(cfg, pd), "norm2": ly.norm_init(cfg, pd),
                    "attn": ly.attn_init(cfg, k1, pd), "mlp": ly.mlp_init(cfg, k2, pd)}

        def dec_block(k):
            k1, k2, k3 = jax.random.split(k, 3)
            return {"norm1": ly.norm_init(cfg, pd), "norm2": ly.norm_init(cfg, pd),
                    "norm3": ly.norm_init(cfg, pd),
                    "attn": ly.attn_init(cfg, k1, pd),
                    "xattn": ly.attn_init(cfg, k2, pd),
                    "mlp": ly.mlp_init(cfg, k3, pd)}

        params["enc_blocks"] = _stack(enc_block, k_enc, cfg.enc_layers)
        params["enc_final_norm"] = ly.norm_init(cfg, pd)
        params["blocks"] = _stack(dec_block, k_blocks, cfg.n_layers)
    else:
        raise ValueError(cfg.family)
    return params


def init_serving_params(cfg: ModelConfig, key, max_seq: int = 2048) -> Params:
    """Serving weights: built in the compute dtype (bf16, the dtype Qwen2.5's
    published checkpoints ship in) inside one ``jit``, so only the result
    is ever allocated on the device. The f32 training master copy would not
    fit one chip's HBM beside the decode step for a 3B model."""
    scfg = dataclasses.replace(cfg, param_dtype=cfg.compute_dtype)
    return jax.jit(functools.partial(init_params, scfg,
                                     max_seq=max_seq))(key)


def abstract_params(cfg: ModelConfig, max_seq: int = 2048):
    """Shape-only params for the dry-run (no allocation)."""
    return jax.eval_shape(
        lambda k: init_params(cfg, k, max_seq), jax.random.key(0)
    )


# ======================================================================
# shared pieces
# ======================================================================
def _sinusoid(t: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(t)[:, None].astype(jnp.float32)
    i = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    ang = pos / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def _embed_tokens(cfg, params, tokens, cd, offset=0):
    x = embed_lookup(cfg, params["embed"], tokens, cd)
    if cfg.pos == "learned":
        pos = offset + jnp.arange(tokens.shape[-1])
        x = x + params["pos_embed"][pos].astype(cd)
    elif cfg.pos == "sinusoidal":
        x = x + _sinusoid(tokens.shape[-1], cfg.d_model).astype(cd)
    # GSPMD replicates through table gathers — re-pin the batch sharding here
    # or every downstream activation is replicated (found the hard way; see
    # EXPERIMENTS.md §Perf iteration 0).
    return shard(x, "batch", None, None)


def _logits(cfg, params, x):
    """Project to the *padded* vocab (shardable over the model axis) and mask
    the padding ids to -inf so downstream softmax/argmax never pick them."""
    if cfg.tie_embeddings:
        head = full_table(cfg, params["embed"]).T
    else:
        head = params["lm_head"]
    logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
    if cfg.vocab_pad != cfg.vocab:
        pad_mask = jnp.arange(cfg.vocab_pad) >= cfg.vocab
        logits = jnp.where(pad_mask, -1e30, logits)
    return shard(logits, *(["batch"] + [None] * (logits.ndim - 2) + ["vocab"]))


def _dense_block_fwd(cfg, bp, x, positions, window, q_chunk=0, chunk_unroll=1):
    h = ly.apply_norm(cfg, bp["norm1"], x)
    x = x + ly.attention_block(cfg, bp["attn"], h, positions, window,
                               q_chunk=q_chunk, chunk_unroll=chunk_unroll)
    h = ly.apply_norm(cfg, bp["norm2"], x)
    if "moe" in bp:
        x = x + moe_mod.moe_block(cfg, bp["moe"], h)
    else:
        x = x + ly.mlp_block(cfg, bp["mlp"], h)
    return x


def _rec_block_fwd(cfg, bp, x):
    h = ly.apply_norm(cfg, bp["norm1"], x)
    x = x + rg.rglru_block(cfg, bp["rglru"], h)
    h = ly.apply_norm(cfg, bp["norm2"], x)
    return x + ly.mlp_block(cfg, bp["mlp"], h)


def _ssm_block_fwd(cfg, bp, x):
    h = ly.apply_norm(cfg, bp["norm1"], x)
    return x + ssm_mod.ssm_block(cfg, bp["ssm"], h)


def _remat(cfg, body, remat: bool):
    """Layer-scan remat wrapper. remat_policy="dots" saves matmul outputs
    and recomputes only elementwise chains in the bwd pass — for gate-heavy
    blocks (RG-LRU) this removes most of the recompute traffic at a small
    residency cost (§Perf)."""
    if not remat:
        return body
    if cfg.remat_policy == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(body, policy=pol)
    return jax.checkpoint(body)


# ======================================================================
# full-sequence forward (train / prefill backbone)
# ======================================================================
def backbone(cfg: ModelConfig, params: Params, x: jnp.ndarray,
             *, unroll: int = 1, remat: bool = True,
             enc: Optional[jnp.ndarray] = None,
             q_chunk: int = 0, chunk_unroll: int = 1) -> jnp.ndarray:
    """Run all blocks over x (B,S,D). ``enc`` is the encoder output for
    enc-dec decoders. ``q_chunk`` > 0 switches attention to the query-block
    streaming path (needed for the 32k shapes)."""
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]

    if cfg.family in ("dense", "moe", "vlm"):
        def body(xc, bp):
            return _dense_block_fwd(cfg, bp, xc, positions, cfg.sliding_window,
                                    q_chunk, chunk_unroll), None
        f = _remat(cfg, body, remat)
        x, _ = jax.lax.scan(f, x, params["blocks"], unroll=unroll)
    elif cfg.family == "ssm":
        def body(xc, bp):
            return _ssm_block_fwd(cfg, bp, xc), None
        f = _remat(cfg, body, remat)
        x, _ = jax.lax.scan(f, x, params["blocks"], unroll=unroll)
    elif cfg.family == "hybrid":
        n_super, n_rem_rec, n_attn = hybrid_layout(cfg)
        rec = params["rec_blocks"]
        rec_main = jax.tree.map(lambda a: a[: 2 * n_super].reshape(n_super, 2, *a.shape[1:]), rec)
        rec_rem = jax.tree.map(lambda a: a[2 * n_super:], rec)

        def sbody(xc, bps):
            rp2, ap = bps
            xc = _rec_block_fwd(cfg, jax.tree.map(lambda a: a[0], rp2), xc)
            xc = _rec_block_fwd(cfg, jax.tree.map(lambda a: a[1], rp2), xc)
            xc = _dense_block_fwd(cfg, ap, xc, positions, cfg.local_window,
                                  q_chunk, chunk_unroll)
            return xc, None

        f = _remat(cfg, sbody, remat)
        x, _ = jax.lax.scan(f, x, (rec_main, params["attn_blocks"]), unroll=unroll)
        if n_rem_rec:
            def rbody(xc, bp):
                return _rec_block_fwd(cfg, bp, xc), None
            fr = _remat(cfg, rbody, remat)
            x, _ = jax.lax.scan(fr, x, rec_rem, unroll=unroll)
    elif cfg.family == "audio":
        def body(xc, bp):
            h = ly.apply_norm(cfg, bp["norm1"], xc)
            xc = xc + ly.attention_block(cfg, bp["attn"], h, positions, 0,
                                         q_chunk=q_chunk, chunk_unroll=chunk_unroll)
            h = ly.apply_norm(cfg, bp["norm2"], xc)
            xc = xc + ly.cross_attention_block(cfg, bp["xattn"], h, enc)
            h = ly.apply_norm(cfg, bp["norm3"], xc)
            return xc + ly.mlp_block(cfg, bp["mlp"], h), None
        f = _remat(cfg, body, remat)
        x, _ = jax.lax.scan(f, x, params["blocks"], unroll=unroll)
    else:
        raise ValueError(cfg.family)
    return ly.apply_norm(cfg, params["final_norm"], x)


def encode(cfg: ModelConfig, params: Params, frames: jnp.ndarray,
           *, unroll: int = 1, remat: bool = True) -> jnp.ndarray:
    """Whisper encoder over stub frame embeddings (B, F, D)."""
    cd = _dtype(cfg.compute_dtype)
    x = frames.astype(cd) + _sinusoid(frames.shape[1], cfg.d_model).astype(cd)

    def body(xc, bp):
        h = ly.apply_norm(cfg, bp["norm1"], xc)
        b, t, _ = xc.shape
        q, k, v = ly.qkv_proj(cfg, bp["attn"], h)
        o = ly.mha(q, k, v, None).reshape(b, t, -1) @ bp["attn"]["wo"]
        xc = xc + o
        h = ly.apply_norm(cfg, bp["norm2"], xc)
        return xc + ly.mlp_block(cfg, bp["mlp"], h), None

    f = _remat(cfg, body, remat)
    x, _ = jax.lax.scan(f, x, params["enc_blocks"], unroll=unroll)
    return ly.apply_norm(cfg, params["enc_final_norm"], x)


def apply_frontend(cfg: ModelConfig, params: Params, x: jnp.ndarray,
                   batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """vision_stub: overwrite the first n_patches positions with the
    precomputed patch embeddings (prefix-image layout)."""
    if cfg.frontend == "vision_stub" and "patches" in batch:
        p = batch["patches"].astype(x.dtype)
        x = jax.lax.dynamic_update_slice(x, p, (0, 0, 0))
    return x


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray],
            *, unroll: int = 1, remat: bool = True,
            q_chunk: int = 0, chunk_unroll: int = 1) -> jnp.ndarray:
    """Full-sequence logits (B, S, V_pad) fp32."""
    cd = _dtype(cfg.compute_dtype)
    params = _cast_params(params, cd)
    tokens = batch["tokens"]
    x = _embed_tokens(cfg, params, tokens, cd)
    x = apply_frontend(cfg, params, x, batch)
    enc = None
    if cfg.is_encdec:
        enc = encode(cfg, params, batch["frames"], unroll=unroll, remat=remat)
    x = backbone(cfg, params, x, unroll=unroll, remat=remat, enc=enc,
                 q_chunk=q_chunk, chunk_unroll=chunk_unroll)
    return _logits(cfg, params, x)


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray],
            *, unroll: int = 1, remat: bool = True,
            q_chunk: int = 0, chunk_unroll: int = 1) -> jnp.ndarray:
    """Next-token cross entropy, written to be *vocab-sharding friendly*:
    ``log_softmax`` + ``take_along_axis`` over a model-sharded vocab dim
    force GSPMD to all-gather the full (B,S,V) logits (~40 GB/device for the
    train_4k shapes). Instead we compute logsumexp + a where-masked pick —
    every intermediate stays V-sharded and only (B,S) arrays cross shards."""
    logits = forward(cfg, params, batch, unroll=unroll, remat=remat,
                     q_chunk=q_chunk, chunk_unroll=chunk_unroll)
    targets = batch["tokens"][:, 1:]
    lg = logits[:, :-1]
    m = jax.lax.stop_gradient(jnp.max(lg, axis=-1, keepdims=True))
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1))
    iota = jnp.arange(cfg.vocab_pad)[None, None, :]
    pick = jnp.sum(jnp.where(iota == targets[..., None], lg, 0.0), axis=-1)
    return jnp.mean(lse - pick)


# ======================================================================
# serving: prefill + decode
# ======================================================================
def cache_spec(cfg: ModelConfig, batch: int, seq_len: int,
               enc_frames: int = 0) -> Dict[str, Any]:
    """Abstract cache shapes (used by init and by the dry-run input specs)."""
    cd = _dtype(cfg.compute_dtype)
    c: Dict[str, Any] = {"pos": jnp.zeros((batch,), jnp.int32)}
    hd = cfg.head_dim

    def kv(n_layers, window):
        clen = min(seq_len, window) if window else seq_len
        return jnp.zeros((n_layers, batch, clen, cfg.n_kv, hd), cd)

    if cfg.family in ("dense", "moe", "vlm"):
        c["k"] = kv(cfg.n_layers, cfg.sliding_window)
        c["v"] = kv(cfg.n_layers, cfg.sliding_window)
    elif cfg.family == "ssm":
        di, nh, hp, n = ssm_mod.ssm_dims(cfg)
        c["ssm"] = ssm_mod.SSMCache(
            conv=jnp.zeros((cfg.n_layers, batch, cfg.ssm_conv - 1, di + 2 * n), cd),
            state=jnp.zeros((cfg.n_layers, batch, nh, hp, n), jnp.float32),
        )
    elif cfg.family == "hybrid":
        n_super, n_rem_rec, n_attn = hybrid_layout(cfg)
        n_rec = cfg.n_layers - n_attn
        dr = cfg.d_model
        c["rg"] = rg.RGLRUCache(
            conv=jnp.zeros((n_rec, batch, rg._CONV_K - 1, dr), cd),
            h=jnp.zeros((n_rec, batch, dr), jnp.float32),
        )
        c["k"] = kv(n_attn, cfg.local_window)
        c["v"] = kv(n_attn, cfg.local_window)
    elif cfg.family == "audio":
        c["k"] = kv(cfg.n_layers, 0)
        c["v"] = kv(cfg.n_layers, 0)
        c["xk"] = jnp.zeros((cfg.n_layers, batch, enc_frames, cfg.n_kv, hd), cd)
        c["xv"] = jnp.zeros((cfg.n_layers, batch, enc_frames, cfg.n_kv, hd), cd)
    return c


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray],
            *, unroll: int = 1, q_chunk: int = 0,
            chunk_unroll: int = 1,
            max_seq: Optional[int] = None) -> Tuple[jnp.ndarray, Cache]:
    """Process the prompt; return (last-token logits (B,V) f32, cache).

    ``max_seq`` sets the KV ring capacity (decode headroom). Default = the
    prompt length — callers that decode afterwards must pass a larger value
    or repack (the Server repacks; direct decode_step needs headroom here).
    """
    cd = _dtype(cfg.compute_dtype)
    params = _cast_params(params, cd)
    tokens = batch["tokens"]
    b, s = tokens.shape
    cap_full = max(max_seq or s, s)
    positions = jnp.arange(s)[None, :]
    x = _embed_tokens(cfg, params, tokens, cd)
    x = apply_frontend(cfg, params, x, batch)
    cache: Cache = {"pos": jnp.full((b,), s, jnp.int32)}

    def ring(full_kv, window):
        """(B,S,Hkv,dh) -> ring cache (B,C,Hkv,dh) with slot i%C semantics."""
        cap = min(cap_full, window) if window else cap_full
        c = min(s, cap)
        last = full_kv[:, s - c:]
        if c == s == cap:
            return last
        # place token j at slot j % cap
        idx = (jnp.arange(s - c, s)) % cap
        out = jnp.zeros((b, cap) + full_kv.shape[2:], full_kv.dtype)
        return out.at[:, idx].set(last)

    if cfg.family in ("dense", "moe", "vlm", "audio", "hybrid"):
        enc = None
        if cfg.is_encdec:
            enc = encode(cfg, params, batch["frames"], unroll=unroll, remat=False)

        if cfg.family == "hybrid":
            n_super, n_rem_rec, n_attn = hybrid_layout(cfg)
            rec = params["rec_blocks"]
            rec_main = jax.tree.map(
                lambda a: a[: 2 * n_super].reshape(n_super, 2, *a.shape[1:]), rec)
            rec_rem = jax.tree.map(lambda a: a[2 * n_super:], rec)

            def sbody(xc, bps):
                rp2, ap = bps
                rcaches = []
                for i in range(2):
                    rp = jax.tree.map(lambda a: a[i], rp2)
                    h = ly.apply_norm(cfg, rp["norm1"], xc)
                    o, rc = rg.rglru_block(cfg, rp["rglru"], h, return_cache=True)
                    xc = xc + o
                    h = ly.apply_norm(cfg, rp["norm2"], xc)
                    xc = xc + ly.mlp_block(cfg, rp["mlp"], h)
                    rcaches.append(rc)
                h = ly.apply_norm(cfg, ap["norm1"], xc)
                q, k, v = ly.qkv_proj(cfg, ap["attn"], h)
                q = ly.rope(q, positions, cfg.rope_theta)
                k = ly.rope(k, positions, cfg.rope_theta)
                if q_chunk and q_chunk < s:
                    o = ly.mha_chunked(q, k, v, window=cfg.local_window,
                                       q_chunk=q_chunk, unroll=chunk_unroll)
                else:
                    o = ly.mha(q, k, v, ly.causal_mask(s, s, 0, cfg.local_window))
                xc = xc + o.reshape(b, s, -1) @ ap["attn"]["wo"]
                h = ly.apply_norm(cfg, ap["norm2"], xc)
                xc = xc + ly.mlp_block(cfg, ap["mlp"], h)
                rc2 = jax.tree.map(lambda a, bb: jnp.stack([a, bb]), rcaches[0], rcaches[1])
                return xc, (rc2, ring(k, cfg.local_window), ring(v, cfg.local_window))

            x, (rc_main, ks, vs) = jax.lax.scan(sbody, x, (rec_main, params["attn_blocks"]),
                                                unroll=unroll)
            rc_main = jax.tree.map(
                lambda a: a.reshape(a.shape[0] * 2, *a.shape[2:]), rc_main)
            if n_rem_rec:
                def rbody(xc, rp):
                    h = ly.apply_norm(cfg, rp["norm1"], xc)
                    o, rc = rg.rglru_block(cfg, rp["rglru"], h, return_cache=True)
                    xc = xc + o
                    h = ly.apply_norm(cfg, rp["norm2"], xc)
                    return xc + ly.mlp_block(cfg, rp["mlp"], h), rc
                x, rc_rem_out = jax.lax.scan(rbody, x, rec_rem, unroll=unroll)
                cache["rg"] = jax.tree.map(
                    lambda a, bb: jnp.concatenate([a, bb], 0), rc_main, rc_rem_out)
            else:
                cache["rg"] = rc_main
            cache["k"], cache["v"] = ks, vs
        else:
            window = cfg.sliding_window

            def body(xc, bp):
                h = ly.apply_norm(cfg, bp["norm1"], xc)
                q, k, v = ly.qkv_proj(cfg, bp["attn"], h)
                if cfg.pos == "rope":
                    q = ly.rope(q, positions, cfg.rope_theta)
                    k = ly.rope(k, positions, cfg.rope_theta)
                if q_chunk and q_chunk < s:
                    o = ly.mha_chunked(q, k, v, window=window,
                                       q_chunk=q_chunk, unroll=chunk_unroll)
                else:
                    o = ly.mha(q, k, v, ly.causal_mask(s, s, 0, window))
                xc = xc + o.reshape(b, s, -1) @ bp["attn"]["wo"]
                ys = [ring(k, window), ring(v, window)]
                if cfg.is_encdec:
                    h = ly.apply_norm(cfg, bp["norm2"], xc)
                    xk = (enc @ bp["xattn"]["wk"]).reshape(b, -1, cfg.n_kv, cfg.head_dim)
                    xv = (enc @ bp["xattn"]["wv"]).reshape(b, -1, cfg.n_kv, cfg.head_dim)
                    if "bk" in bp["xattn"]:
                        xk = xk + bp["xattn"]["bk"].reshape(cfg.n_kv, cfg.head_dim)
                        xv = xv + bp["xattn"]["bv"].reshape(cfg.n_kv, cfg.head_dim)
                    h2 = ly.cross_attention_block(cfg, bp["xattn"], h, enc)
                    xc = xc + h2
                    h = ly.apply_norm(cfg, bp["norm3"], xc)
                    xc = xc + ly.mlp_block(cfg, bp["mlp"], h)
                    ys += [xk, xv]
                else:
                    h = ly.apply_norm(cfg, bp["norm2"], xc)
                    if "moe" in bp:
                        xc = xc + moe_mod.moe_block(cfg, bp["moe"], h)
                    else:
                        xc = xc + ly.mlp_block(cfg, bp["mlp"], h)
                return xc, tuple(ys)

            x, ys = jax.lax.scan(body, x, params["blocks"], unroll=unroll)
            cache["k"], cache["v"] = ys[0], ys[1]
            if cfg.is_encdec:
                cache["xk"], cache["xv"] = ys[2], ys[3]
    elif cfg.family == "ssm":
        def body(xc, bp):
            h = ly.apply_norm(cfg, bp["norm1"], xc)
            o, sc = ssm_mod.ssm_block(cfg, bp["ssm"], h, return_cache=True)
            return xc + o, sc
        x, sc = jax.lax.scan(body, x, params["blocks"], unroll=unroll)
        cache["ssm"] = sc
    else:
        raise ValueError(cfg.family)

    x = ly.apply_norm(cfg, params["final_norm"], x)
    return _logits(cfg, params, x[:, -1:])[:, 0], cache


def decode_step(cfg: ModelConfig, params: Params, token: jnp.ndarray,
                cache: Cache, *, unroll: int = 1) -> Tuple[jnp.ndarray, Cache]:
    """One decode step. token (B,) int32 -> (logits (B,V) f32, cache')."""
    cd = _dtype(cfg.compute_dtype)
    params = _cast_params(params, cd)
    b = token.shape[0]
    pos = cache["pos"]
    x = embed_lookup(cfg, params["embed"], token[:, None], cd)
    if cfg.pos == "learned":
        mp = params["pos_embed"].shape[0]
        x = x + params["pos_embed"][jnp.minimum(pos, mp - 1)][:, None].astype(cd)
    new_cache = dict(cache)

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        window = cfg.sliding_window

        def body(xc, bps):
            if cfg.is_encdec:
                bp, kc, vc, xkc, xvc = bps
            else:
                bp, kc, vc = bps
            h = ly.apply_norm(cfg, bp["norm1"], xc)
            o, kc, vc = ly.attention_decode(cfg, bp["attn"], h, pos, kc, vc, window)
            xc = xc + o
            if cfg.is_encdec:
                h = ly.apply_norm(cfg, bp["norm2"], xc)
                q = (h @ bp["xattn"]["wq"]).reshape(b, 1, cfg.n_heads, cfg.head_dim)
                if "bq" in bp["xattn"]:
                    q = q + bp["xattn"]["bq"].reshape(cfg.n_heads, cfg.head_dim)
                o = ly.mha(q, xkc, xvc, None)
                xc = xc + o.reshape(b, 1, -1) @ bp["xattn"]["wo"]
                h = ly.apply_norm(cfg, bp["norm3"], xc)
                xc = xc + ly.mlp_block(cfg, bp["mlp"], h)
                return xc, (kc, vc)
            h = ly.apply_norm(cfg, bp["norm2"], xc)
            if "moe" in bp:
                xc = xc + moe_mod.moe_block(cfg, bp["moe"], h)
            else:
                xc = xc + ly.mlp_block(cfg, bp["mlp"], h)
            return xc, (kc, vc)

        xs = (params["blocks"], cache["k"], cache["v"])
        if cfg.is_encdec:
            xs = xs + (cache["xk"], cache["xv"])
        x, (k_new, v_new) = jax.lax.scan(body, x, xs, unroll=unroll)
        new_cache["k"], new_cache["v"] = k_new, v_new
    elif cfg.family == "ssm":
        def body(xc, bps):
            bp, sc = bps
            h = ly.apply_norm(cfg, bp["norm1"], xc)
            o, sc = ssm_mod.ssm_decode(cfg, bp["ssm"], h, sc)
            return xc + o, sc
        x, sc = jax.lax.scan(body, x, (params["blocks"], cache["ssm"]), unroll=unroll)
        new_cache["ssm"] = sc
    elif cfg.family == "hybrid":
        n_super, n_rem_rec, n_attn = hybrid_layout(cfg)
        rec = params["rec_blocks"]
        rgc = cache["rg"]
        rec_main = jax.tree.map(lambda a: a[: 2 * n_super].reshape(n_super, 2, *a.shape[1:]), rec)
        rgc_main = jax.tree.map(lambda a: a[: 2 * n_super].reshape(n_super, 2, *a.shape[1:]), rgc)

        def rec_step(xc, rp, rc):
            h = ly.apply_norm(cfg, rp["norm1"], xc)
            o, rc = rg.rglru_decode(cfg, rp["rglru"], h, rc)
            xc = xc + o
            h = ly.apply_norm(cfg, rp["norm2"], xc)
            return xc + ly.mlp_block(cfg, rp["mlp"], h), rc

        def sbody(xc, bps):
            rp2, rc2, ap, kc, vc = bps
            rcs = []
            for i in range(2):
                xc, rc = rec_step(xc, jax.tree.map(lambda a: a[i], rp2),
                                  jax.tree.map(lambda a: a[i], rc2))
                rcs.append(rc)
            h = ly.apply_norm(cfg, ap["norm1"], xc)
            o, kc, vc = ly.attention_decode(cfg, ap["attn"], h, pos, kc, vc,
                                            cfg.local_window)
            xc = xc + o
            h = ly.apply_norm(cfg, ap["norm2"], xc)
            xc = xc + ly.mlp_block(cfg, ap["mlp"], h)
            rc2 = jax.tree.map(lambda a, bb: jnp.stack([a, bb]), rcs[0], rcs[1])
            return xc, (rc2, kc, vc)

        x, (rc_main_new, k_new, v_new) = jax.lax.scan(
            sbody, x, (rec_main, rgc_main, params["attn_blocks"],
                       cache["k"], cache["v"]), unroll=unroll)
        rc_new = jax.tree.map(lambda a: a.reshape(a.shape[0] * 2, *a.shape[2:]),
                              rc_main_new)
        if n_rem_rec:
            rec_rem = jax.tree.map(lambda a: a[2 * n_super:], rec)
            rgc_rem = jax.tree.map(lambda a: a[2 * n_super:], rgc)

            def rbody(xc, bps):
                rp, rc = bps
                return rec_step(xc, rp, rc)
            x, rc_rem_new = jax.lax.scan(rbody, x, (rec_rem, rgc_rem), unroll=unroll)
            rc_new = jax.tree.map(lambda a, bb: jnp.concatenate([a, bb], 0),
                                  rc_new, rc_rem_new)
        new_cache["rg"] = rc_new
        new_cache["k"], new_cache["v"] = k_new, v_new
    else:
        raise ValueError(cfg.family)

    x = ly.apply_norm(cfg, params["final_norm"], x)
    new_cache["pos"] = pos + 1
    return _logits(cfg, params, x)[:, 0], new_cache


def decode_step_pooled(cfg: ModelConfig, kvcfg, params: Params,
                       token: jnp.ndarray, pool, tele, *, unroll: int = 1,
                       recode_budget: Optional[int] = None,
                       kernel: str = "reference"):
    """One decode step over the coded KV page pool (the serving path).

    token (B,) int32. ``pool`` is a ``runtime.kvbank.PooledKV`` whose
    page-table rows were assigned host-side at admission; ``tele`` is a
    ``repro.obs.serve.ServeTelemetry`` or ``None`` (metrics off — the
    compiled program is identical to a build that never traced telemetry).
    Returns ``(logits (B,V) f32, pool', tele')``.

    Appends go through the code-status table (touched parity rows stale),
    reads go through the shared ``plan_reads`` plan + the pool-indirected
    ``coded_kv_decode`` gather (``kernel`` picks the reference jnp gather or
    the bit-exact Pallas ``gather_pool_pallas`` datapath), and the ReCoding
    unit refreshes parity after the scan. With an unlimited recode budget on
    a coded pool, the encode is fused into the write path
    (``pool_write_layer_fused`` — parity is delta-maintained per append, no
    whole-pool re-read) which is bit-identical to write-then-full-recode;
    the status table evolves identically either way. Slots without a
    page-table row write via the bank sink and keep length 0; the server
    ignores their outputs.
    """
    from repro.kernels.coded_kv_decode import ops as ckd_ops
    from repro.obs import serve as obs_serve
    from repro.runtime import kvbank as kb

    assert cfg.family in ("dense", "moe", "vlm") and not cfg.is_encdec \
        and cfg.sliding_window == 0, \
        "pooled decode supports global-attention decoder families"
    cd = _dtype(cfg.compute_dtype)
    params = _cast_params(params, cd)
    b = token.shape[0]
    pos = pool.length
    active = (pool.page_table[:, 0] >= 0) & (pos > 0)
    x = embed_lookup(cfg, params["embed"], token[:, None], cd)
    if cfg.pos == "learned":
        mp = params["pos_embed"].shape[0]
        x = x + params["pos_embed"][jnp.minimum(pos, mp - 1)][:, None].astype(cd)

    widx = kb.pool_write_index(kvcfg, pool, active)
    pool = kb.pool_mark_stale(kvcfg, pool, widx)
    len_eff = pos + active.astype(jnp.int32)
    plan = kb.pool_plan(kvcfg, pool, length=len_eff)
    # encode-on-write when nothing rations the ReCoding unit (shape + host
    # config are compile-time)  # analysis: tracer-branch
    fused = recode_budget is None and pool.k_par.shape[1] > 0

    def body(xc, bps):
        bp, kbank, vbank, kpar, vpar = bps
        h = ly.apply_norm(cfg, bp["norm1"], xc)
        q, k, v = ly.qkv_proj(cfg, bp["attn"], h)
        if cfg.pos == "rope":
            q = ly.rope(q, pos[:, None], cfg.rope_theta)
            k = ly.rope(k, pos[:, None], cfg.rope_theta)
        if fused:
            kbank, vbank, kpar, vpar = kb.pool_write_layer_fused(
                kvcfg, kbank, vbank, kpar, vpar, widx, k[:, 0], v[:, 0])
        else:
            kbank, vbank = kb.pool_write_layer(kvcfg, kbank, vbank, widx,
                                               k[:, 0], v[:, 0])
        k_log, v_log = ckd_ops.gather_pool_layer(
            kbank, vbank, kpar, vpar, pool.page_table, plan.use_parity, cd,
            kernel=kernel)
        mask = jnp.arange(k_log.shape[1])[None, :] < len_eff[:, None]
        o = ly.mha(q, k_log, v_log, mask[:, None, None, None, :])
        xc = xc + o.reshape(b, 1, cfg.n_heads * cfg.head_dim) @ bp["attn"]["wo"]
        h = ly.apply_norm(cfg, bp["norm2"], xc)
        if "moe" in bp:
            xc = xc + moe_mod.moe_block(cfg, bp["moe"], h)
        else:
            xc = xc + ly.mlp_block(cfg, bp["mlp"], h)
        return xc, (kbank, vbank, kpar, vpar) if fused else (kbank, vbank)

    x, ys = jax.lax.scan(
        body, x, (params["blocks"], pool.k_banks, pool.v_banks,
                  pool.k_par, pool.v_par), unroll=unroll)
    k_new, v_new = ys[0], ys[1]
    pool = pool._replace(k_banks=k_new, v_banks=v_new, length=len_eff)
    stale_before = jnp.sum((~pool.parity_fresh).astype(jnp.int32))
    if fused:
        kp_new, vp_new = ys[2], ys[3]
        # parity was delta-maintained per layer; refreshing the status table
        # IS the recode (bit-identical to the unfused full re-encode)
        pool = pool._replace(
            k_par=kp_new, v_par=vp_new,
            parity_fresh=jnp.ones_like(pool.parity_fresh))
        recoded = stale_before
    else:
        pool, recoded = kb.pool_recode(kvcfg, pool, budget=recode_budget)

    if tele is not None:
        needed, bank = kb.pool_read_sets(kvcfg, pool.page_table, len_eff)
        lat = kb.read_latencies(kvcfg, pool.page_table, len_eff,
                                plan.use_parity)
        tele = obs_serve.update_serve_telemetry(
            tele, load=plan.load, needed=needed, bank=bank,
            use_parity=plan.use_parity, latencies=lat,
            stale_before=stale_before, recoded=recoded,
            appended=jnp.sum((widx[0] < kvcfg.n_banks).astype(jnp.int32)),
            uncoded_cycles=plan.uncoded_cycles,
            coded_cycles=plan.coded_cycles)

    x = ly.apply_norm(cfg, params["final_norm"], x)
    return _logits(cfg, params, x)[:, 0], pool, tele
