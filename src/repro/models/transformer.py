"""Model assembly: periodic layer stacks scanned over depth.

Layers repeat with a *period* = lcm(attn interleave, MoE interleave) — e.g.
Jamba's period is 8 (7 mamba + 1 attn, MoE on odd positions). Parameters for
each position in the period are stacked on a leading (n_layers // period) axis
and the whole stack is applied with one ``jax.lax.scan``, so HLO size is
depth-independent (required for 80-layer dry-runs to compile quickly).

Entry points:
  init_params(cfg, rng)                  -> params pytree
  lm_loss(cfg, params, batch)            -> (loss, metrics)   [train_4k]
  prefill(cfg, params, batch)            -> (logits, caches)  [prefill_32k]
  decode_step(cfg, params, token, pos, caches) -> (logits, caches) [decode]
  encode(cfg, params, batch)             -> pooled (b, d)     [dual-encoder tower]

Every entry point takes ``precision`` — a models.precision policy (object,
registry name, or None) governing compute/accum/projection dtypes
end-to-end; the legacy ``dtype=`` argument maps to a policy with that
compute dtype (fp32 norms/projections stay on). Vision-frontend archs
consume raw ``batch['image']`` through models.frontends.patch_embed.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_lib
from repro.models import frontends as fe
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import precision as prec_lib
from repro.models import ssm as ssm_lib


def period_of(cfg: ArchConfig) -> int:
    """Layer-stack period: lcm of attention and MoE interleaves (scan unit)."""
    p = cfg.attn_every if cfg.family == "hybrid" else 1
    if cfg.moe is not None:
        p = math.lcm(p, cfg.moe.every)
    assert cfg.n_layers % p == 0, (cfg.name, cfg.n_layers, p)
    return p


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_block(key, cfg: ArchConfig, kind: str, use_moe: bool, extra):
    k1, k2 = jax.random.split(key)
    d = cfg.d_model
    p = {"ln1": jnp.ones((*extra, d), jnp.float32)}
    if kind == "attn":
        p["attn"] = attn_lib.init_attn_params(k1, cfg, extra)
    else:
        p["mamba"] = ssm_lib.init_ssm_params(k1, cfg, extra)
    if cfg.family != "ssm":  # mamba2 blocks have no separate FFN
        p["ln2"] = jnp.ones((*extra, d), jnp.float32)
        if use_moe:
            p["moe"] = moe_lib.init_moe_params(k2, cfg, extra)
        else:
            ka, kb, kc = jax.random.split(k2, 3)
            p["ffn"] = {
                "wi": L.dense_init(ka, d, cfg.d_ff, extra),
                "wg": L.dense_init(kb, d, cfg.d_ff, extra),
                "wo": L.dense_init(kc, cfg.d_ff, d, extra),
            }
    return p


def init_params(cfg: ArchConfig, rng):
    """Full tower/LM params: scanned block stacks, final norm, frontend, embeddings/head."""
    period = period_of(cfg)
    n_periods = cfg.n_layers // period
    kinds = cfg.layer_kinds()[:period]
    moe_mask = cfg.moe_layer_mask()[:period]
    keys = jax.random.split(rng, period + 3)

    blocks = []
    for i in range(period):
        blocks.append(_init_block(keys[i], cfg, kinds[i], moe_mask[i],
                                  extra=(n_periods,)))
    params = {
        "blocks": blocks,
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if cfg.frontend == "vision":
        params["frontend"] = fe.init_vision_frontend(keys[-3], cfg)
    if cfg.vocab > 0 and cfg.frontend != "audio":
        params["embed"] = L.trunc_normal(keys[-1], (cfg.vocab, cfg.d_model),
                                         cfg.d_model ** -0.5)
    if cfg.vocab > 0 and not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(keys[-2], cfg.d_model, cfg.vocab)
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _apply_block(cfg, kind, use_moe, p, h, positions, cache, decode, moe_args,
                 collect_cache_len=None, key_mask=None):
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("attention" if kind == "attn" else "mixer"):
        hn = L.rms_norm(h, p["ln1"], cfg.norm_eps)
        if kind != "attn":
            mixer = ssm_lib.mamba_decode if decode else ssm_lib.mamba_mixer
            mix, new_cache = mixer(p["mamba"], cfg, hn, cache)
        elif decode:
            mix, new_cache = attn_lib.decode_attention(
                p["attn"], cfg, hn, cache, positions)
        elif collect_cache_len is not None:
            mix, (k, v) = attn_lib.attention(p["attn"], cfg, hn, positions,
                                             return_kv=True,
                                             key_mask=key_mask)
            new_cache = attn_lib.cache_from_prefill(cfg, k, v,
                                                    collect_cache_len)
        else:
            mix = attn_lib.attention(p["attn"], cfg, hn, positions,
                                     key_mask=key_mask)
            new_cache = None
    h = h + mix
    if cfg.family != "ssm":
        with jax.named_scope("mlp"):
            hn = L.rms_norm(h, p["ln2"], cfg.norm_eps)
            if use_moe:
                out, aux = moe_lib.moe_ffn(p["moe"], cfg, hn, **moe_args)
            else:
                out = L.swiglu(hn, p["ffn"]["wi"], p["ffn"]["wg"],
                               p["ffn"]["wo"])
        h = h + out
    return h, new_cache, aux


def forward(cfg: ArchConfig, params, h, positions, caches=None, decode=False,
            remat_policy=None, moe_args=None, collect_cache_len=None,
            unroll: int = 1, key_mask=None):
    """Run the full stack. h: (b, s, d). Returns (h, new_caches, aux_loss).

    caches: list (len=period) of stacked KV/SSM caches or None.
    remat_policy: optional jax.checkpoint policy applied per period-step.
    collect_cache_len: if set (prefill), build decode caches of this length.
    key_mask: optional (b, s) bool padding mask threaded into attention.
    """
    period = period_of(cfg)
    kinds = cfg.layer_kinds()[:period]
    moe_mask = cfg.moe_layer_mask()[:period]
    moe_args = moe_args or {}

    def period_step(h, sliced):
        blocks, caches_in = sliced
        new_caches, aux_total = [], jnp.zeros((), jnp.float32)
        for i in range(period):
            c = None if caches_in is None else caches_in[i]
            h, nc, aux = _apply_block(cfg, kinds[i], moe_mask[i], blocks[i], h,
                                      positions, c, decode, moe_args,
                                      collect_cache_len, key_mask)
            new_caches.append(nc)
            aux_total = aux_total + aux
        return h, (new_caches, aux_total)

    if remat_policy is not None:
        period_step = jax.checkpoint(period_step, policy=remat_policy)

    def scan_body(h, sliced):
        return period_step(h, sliced)

    xs = (params["blocks"], caches)
    if caches is None:
        # replace None with a per-step dummy so scan sees a consistent pytree
        xs = (params["blocks"],
              [jnp.zeros((cfg.n_layers // period,), jnp.float32)] * period)

        def scan_body(h, sliced):  # noqa: F811
            blocks, _ = sliced
            return period_step(h, (blocks, None))

    h, (new_caches, aux) = jax.lax.scan(scan_body, h, xs, unroll=unroll)
    if caches is None and collect_cache_len is None and not decode:
        new_caches = None
    return h, new_caches, jnp.sum(aux)


# ---------------------------------------------------------------------------
# Embedding / heads
# ---------------------------------------------------------------------------


def embed_inputs(cfg: ArchConfig, params, batch, dtype):
    """Returns (h (b, s, d), positions (b, s), text_mask (b, s) or None).

    Vision archs consume raw ``batch['image']`` (b, H, W, C) through the
    linear-patchify frontend (models.frontends); vlm archs append token
    embeddings after the patches (and accept token-only batches, e.g.
    text-only decode). Audio archs consume precomputed frame
    ``batch['embeddings']`` (the one remaining frontend stub)."""
    if cfg.frontend == "audio":
        h = batch["embeddings"].astype(dtype)           # (b, s, d) stub
        b, s, _ = h.shape
        pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        return h, pos, None
    if cfg.frontend == "vision" and "image" in batch:
        patches = fe.patch_embed(params["frontend"], cfg, batch["image"],
                                 dtype)                 # (b, P, d)
        b = patches.shape[0]
        if cfg.vocab > 0 and "tokens" in batch:         # vlm: patches + text
            tok = batch["tokens"]
            emb = jnp.take(params["embed"], tok, axis=0).astype(dtype)
            h = jnp.concatenate([patches, emb], axis=1)
            s = h.shape[1]
            pos = jnp.broadcast_to(jnp.arange(s), (b, s))
            text_mask = jnp.concatenate(
                [jnp.zeros((b, patches.shape[1]), bool),
                 jnp.ones((b, tok.shape[1]), bool)], axis=1)
            return h, pos, text_mask
        s = patches.shape[1]
        pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        return patches, pos, None
    tok = batch["tokens"]
    emb = jnp.take(params["embed"], tok, axis=0).astype(dtype)
    b, s = tok.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    return emb, pos, None


def logits_from_h(cfg: ArchConfig, params, h, pol: prec_lib.Precision = None):
    """Vocabulary logits from hidden states; the precision policy decides
    whether the head matmul (and hence the logits) runs in fp32."""
    if pol is not None:
        h = pol.project(h)
    if cfg.tie_embeddings:
        w = params["embed"].astype(h.dtype)
        return jnp.einsum("bsd,vd->bsv", h, w)
    return L.dense(h, params["lm_head"])


# ---------------------------------------------------------------------------
# Step functions
# ---------------------------------------------------------------------------


def lm_loss(cfg: ArchConfig, params, batch, *, dtype=jnp.float32,
            precision=None, remat_policy=None, moe_args=None,
            unroll: int = 1):
    """Training loss.

    decoder families: next-token CE over `tokens` (+`labels` if given).
    encoder (hubert): masked-frame CE over `targets` where `mask` is set.
    vlm: next-token CE on the text segment only.

    ``precision`` (policy object/name) governs compute/projection dtypes;
    the legacy ``dtype=`` maps to a policy with that compute dtype. The CE
    itself always accumulates fp32.
    """
    pol = prec_lib.resolve(precision, dtype)
    h, pos, text_mask = embed_inputs(cfg, params, batch, pol.compute_dtype)
    h, _, aux = forward(cfg, params, h, pos, remat_policy=remat_policy,
                        moe_args=moe_args, unroll=unroll)
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)

    if cfg.family == "encoder":
        logits = logits_from_h(cfg, params, h, pol).astype(jnp.float32)
        targets = batch["targets"]                       # (b, s)
        mask = batch["mask"].astype(jnp.float32)         # (b, s)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    else:
        logits = logits_from_h(cfg, params, h, pol).astype(jnp.float32)
        if text_mask is not None:                        # vlm: text tail only
            logits = logits[:, cfg.frontend_len:, :]
        tokens = batch["tokens"]
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        tgt = tokens[:, 1:]
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        mask = batch.get("loss_mask")
        if mask is not None:
            m = mask[:, 1:].astype(jnp.float32)
            loss = jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
        else:
            loss = jnp.mean(nll)
    return loss + aux, {"xent": loss, "aux": aux}


def init_caches(cfg: ArchConfig, batch: int, seq_len: int, dtype=jnp.bfloat16):
    """Stacked per-period-position caches for decode."""
    period = period_of(cfg)
    n_periods = cfg.n_layers // period
    kinds = cfg.layer_kinds()[:period]

    def stack(make):
        one = make()
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_periods, *x.shape)).copy(), one)

    caches = []
    for k in kinds:
        if k == "attn":
            caches.append(stack(
                lambda: attn_lib.init_kv_cache(cfg, batch, seq_len, dtype)))
        else:
            caches.append(stack(
                lambda: ssm_lib.init_ssm_cache(cfg, batch, dtype)))
    return caches


def prefill(cfg: ArchConfig, params, batch, *, dtype=jnp.bfloat16,
            precision=None, moe_args=None, collect_cache_len=None,
            unroll: int = 1):
    """Full forward emitting last-position logits; with ``collect_cache_len``
    also builds the decode caches (serving prefill). Returns logits or
    (logits, caches)."""
    pol = prec_lib.resolve(precision, dtype)
    h, pos, _ = embed_inputs(cfg, params, batch, pol.compute_dtype)
    h, caches, _ = forward(cfg, params, h, pos, moe_args=moe_args,
                           collect_cache_len=collect_cache_len, unroll=unroll)
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    out = (logits_from_h(cfg, params, h[:, -1:, :], pol) if cfg.vocab > 0
           else h[:, -1:, :])
    if collect_cache_len is not None:
        return out, caches
    return out


def decode_step(cfg: ArchConfig, params, token, pos, caches, *,
                dtype=jnp.bfloat16, precision=None, moe_args=None,
                unroll: int = 1):
    """One decode step. token: (b, 1) int32; pos: scalar int32 (all rows
    at one position, the legacy engine) or (b,) int32 per-slot positions
    (continuous batching: every cache row advances at its own depth)."""
    pol = prec_lib.resolve(precision, dtype)
    h = jnp.take(params["embed"], token, axis=0).astype(pol.compute_dtype)
    h, new_caches, _ = forward(cfg, params, h, pos, caches=caches, decode=True,
                               moe_args=moe_args, unroll=unroll)
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    return logits_from_h(cfg, params, h, pol), new_caches


def encode(cfg: ArchConfig, params, batch, *, dtype=jnp.float32,
           precision=None, remat_policy=None):
    """Pooled representation for dual-encoder towers. Returns (b, d_model)
    in the policy's projection dtype (fp32 under the default policies).

    ``batch['attn_mask']`` (b, s) masks padded text positions BOTH inside
    attention (threaded to the backend as a key-padding mask) and in the
    mean pooling; pooling always accumulates in fp32."""
    pol = prec_lib.resolve(precision, dtype)
    h, pos, _ = embed_inputs(cfg, params, batch, pol.compute_dtype)
    mask = batch.get("attn_mask")
    h, _, _ = forward(cfg, params, h, pos, remat_policy=remat_policy,
                      key_mask=mask)
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    h = pol.accum(h)
    if mask is not None:
        m = mask.astype(h.dtype)[..., None]
        pooled = jnp.sum(h * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
    else:
        pooled = jnp.mean(h, axis=1)
    return pol.project(pooled)
