"""Model assembly: init, train/prefill forward, decode step, cache management.

Two execution layouts share the same per-layer code:
  * scan layout — per-period-position stacked parameters, `lax.scan` over
    periods (fast compiles at 70+ layers; what train_step/serve_step lower);
  * list layout — per-layer parameter list (what the ResiHP pipeline engine
    partitions across stages and migrates during reconfiguration).
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import tracing
from repro.models import attention as attn_mod
from repro.models.attention import attention, init_attention, precompute_cross_kv
from repro.models.layers import norm_param, rms_norm
from repro.models.mlp import init_mlp, mlp
from repro.models.moe import init_moe, moe_ffn, router_aux_loss
from repro.models.ssm import init_mamba, init_mamba_cache, mamba
from repro.models.xlstm import (
    init_mlstm,
    init_mlstm_cache,
    init_slstm,
    init_slstm_cache,
    mlstm,
    slstm,
)
from repro.parallel.sharding import Annot, annotate, split_annotations

MIXER_INIT = {"attn": init_attention, "mamba": init_mamba, "mlstm": init_mlstm, "slstm": init_slstm}
MIXER_FN = {"attn": attention, "mamba": mamba, "mlstm": mlstm, "slstm": slstm}


# ------------------------------------------------------------------- init
def init_layer(key, cfg, spec, cross=False):
    ks = jax.random.split(key, 4)
    p = {"norm1": norm_param(ks[0], cfg.d_model), "mixer": MIXER_INIT[spec.mixer](ks[0], cfg)}
    if cross:
        p["norm_cross"] = norm_param(ks[1], cfg.d_model)
        p["cross"] = init_attention(ks[1], cfg)
    if spec.ffn == "dense":
        p["norm2"] = norm_param(ks[2], cfg.d_model)
        p["ffn"] = init_mlp(ks[2], cfg)
    elif spec.ffn == "moe":
        p["norm2"] = norm_param(ks[2], cfg.d_model)
        p["ffn"] = init_moe(ks[2], cfg)
    return p


def init_params(key, cfg):
    """Annotated parameter tree, list layout."""
    ks = jax.random.split(key, cfg.n_layers + 4)
    V, D = cfg.padded_vocab, cfg.d_model
    params: dict[str, Any] = {
        "embed": annotate(
            jax.random.normal(ks[0], (V, D), jnp.float32) * (1.0 / math.sqrt(D)),
            "vocab", "dmodel",
        ),
        "final_norm": norm_param(ks[1], D),
        "layers": [
            init_layer(ks[3 + i], cfg, cfg.layer_spec(i), cross=cfg.enc_dec)
            for i in range(cfg.n_layers)
        ],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = annotate(
            jax.random.normal(ks[2], (D, V), jnp.float32) * (1.0 / math.sqrt(D)),
            "dmodel", "vocab",
        )
    if cfg.enc_dec:
        eks = jax.random.split(ks[2], cfg.n_enc_layers + 1)
        enc_spec = cfg.period[0]
        params["enc_layers"] = [
            init_layer(eks[i], cfg, enc_spec, cross=False) for i in range(cfg.n_enc_layers)
        ]
        params["enc_norm"] = norm_param(eks[-1], D)
    return params


def stack_for_scan(cfg, layers, n_layers=None, period=None):
    """Group per-layer trees by period position and stack across periods."""
    period = period if period is not None else cfg.period
    n_layers = n_layers if n_layers is not None else len(layers)
    P = len(period)
    assert n_layers % P == 0
    stacked = []
    for pos in range(P):
        group = [layers[j * P + pos] for j in range(n_layers // P)]
        stacked.append(
            jax.tree.map(
                lambda *xs: Annot(jnp.stack([x.value for x in xs]), ("layers",) + xs[0].axes)
                if isinstance(xs[0], Annot)
                else jnp.stack(xs),
                *group,
                is_leaf=lambda x: isinstance(x, Annot),
            )
        )
    return tuple(stacked)


def unstack_from_scan(stacked, n_layers):
    """Inverse of stack_for_scan (plain arrays, no annotations)."""
    P = len(stacked)
    layers = [None] * n_layers
    for pos in range(P):
        n = n_layers // P
        for j in range(n):
            layers[j * P + pos] = jax.tree.map(lambda a: a[j], stacked[pos])
    return layers


def stacked_init(key, cfg):
    """Annotated params with layers in scan layout (the train-state layout)."""
    p = init_params(key, cfg)
    p["layers"] = stack_for_scan(cfg, p["layers"])
    if cfg.enc_dec:
        p["enc_layers"] = stack_for_scan(cfg, p["enc_layers"], period=(cfg.period[0],))
    return p


# ----------------------------------------------------------------- layers
def apply_layer(cfg, spec, p, x, md, policy, cache=None):
    mix_cache = cache.get("mixer") if cache else None
    h, new_mix = MIXER_FN[spec.mixer](
        cfg, spec, p["mixer"], rms_norm(x, p["norm1"], cfg.norm_eps), md, policy, cache=mix_cache
    )
    x = x + h
    new_cache = {"mixer": new_mix} if new_mix is not None else None
    if "cross" in p:
        cmd = dict(md)
        cmd["cross_x"] = md.get("enc_out")
        ccache = cache.get("cross") if cache else None
        h, new_cross = attention(
            cfg, spec, p["cross"], rms_norm(x, p["norm_cross"], cfg.norm_eps), cmd, policy,
            cache=ccache,
        )
        x = x + h
        if new_cross is not None:  # prefill collect
            new_cache = dict(new_cache or {})
            new_cache["cross"] = new_cross
        elif new_cache is not None and ccache is not None:
            new_cache["cross"] = ccache  # cross KV is constant during decode
    if spec.ffn == "dense":
        x = x + mlp(cfg, p["ffn"], rms_norm(x, p["norm2"], cfg.norm_eps), policy)
    elif spec.ffn == "moe":
        x = x + moe_ffn(cfg, p["ffn"], rms_norm(x, p["norm2"], cfg.norm_eps), policy)
    x = policy.constrain(x, "batch", "seq", None)
    return x, new_cache


def _run_layers(cfg, stacked_layers, x, md, policy, caches=None, *, period=None,
                use_scan=True, remat=False):
    """Run the stacked (scan-layout) layers; returns (x, new_caches)."""
    period = period if period is not None else cfg.period
    P = len(period)

    def block(x, xs):
        p_slices, c_slices = xs
        new_cs = []
        for pos in range(P):
            c = c_slices[pos] if c_slices is not None else None
            x, nc = apply_layer(cfg, period[pos], p_slices[pos], x, md, policy, cache=c)
            new_cs.append(nc if nc is not None else 0)
        return x, tuple(new_cs)

    if remat:
        block = jax.checkpoint(block, prevent_cse=False)

    if use_scan:
        xs = (stacked_layers, caches)
        x, new_caches = jax.lax.scan(block, x, xs)
    else:
        n = jax.tree.leaves(stacked_layers[0])[0].shape[0]
        new_list = []
        for j in range(n):
            p_slices = jax.tree.map(lambda a: a[j], stacked_layers)
            c_slices = jax.tree.map(lambda a: a[j], caches) if caches is not None else None
            x, ncs = block(x, (p_slices, c_slices))
            new_list.append(ncs)
        new_caches = (
            jax.tree.map(lambda *xs: jnp.stack(xs), *new_list) if new_list and caches is not None else None
        )
    return x, new_caches


# ----------------------------------------------------------------- embed
def embed_tokens(cfg, params, tokens, compute_dtype=jnp.bfloat16):
    e = jnp.take(params["embed"], tokens, axis=0)
    return e.astype(compute_dtype)


def lm_logits(cfg, params, x, policy):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
    return policy.constrain(logits, "batch", "seq", "vocab")


# ------------------------------------------------------------------ train
def _default_md(cfg, batch, flash_chunk):
    seg = batch["segment_ids"]
    B, S = seg.shape
    md = {
        "segment_ids": seg,
        "positions": batch["positions"],
        "abs_positions": jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S)),
        "flash_chunk": flash_chunk,
        "causal": True,
    }
    return md


def forward_train(cfg, params, batch, policy, *, use_scan=True, remat=True,
                  flash_chunk=1024, compute_dtype=jnp.bfloat16, _collect=None):
    """Returns logits (B, S, V) and aux dict. batch fields depend on family:

    LM:      tokens (B,S), segment_ids, positions
    VLM:     + vision_embeds (B,S_vis,D) replacing the first S_vis embeddings,
               positions (B,S,3) M-RoPE
    Audio:   frame_embeds (B,S_enc,D), dec_tokens (B,S_dec), (enc|dec)_segment_ids ...
    """
    aux = {"moe_aux": jnp.zeros((), jnp.float32)}
    if cfg.enc_dec:
        enc_x = batch["frame_embeds"].astype(compute_dtype)
        B, S_enc = enc_x.shape[:2]
        enc_md = {
            "segment_ids": batch["enc_segment_ids"],
            "positions": batch["enc_positions"],
            "abs_positions": jnp.broadcast_to(jnp.arange(S_enc, dtype=jnp.int32), (B, S_enc)),
            "flash_chunk": flash_chunk,
            "causal": False,
        }
        enc_x = policy.constrain(enc_x, "batch", "seq", None)
        enc_out, _ = _run_layers(
            cfg, params["enc_layers"], enc_x, enc_md, policy,
            period=(cfg.period[0],), use_scan=use_scan, remat=remat,
        )
        enc_out = rms_norm(enc_out, params["enc_norm"], cfg.norm_eps)
        tokens = batch["dec_tokens"]
        S_dec = tokens.shape[1]
        md = {
            "segment_ids": batch["dec_segment_ids"],
            "positions": batch["dec_positions"],
            "abs_positions": jnp.broadcast_to(jnp.arange(S_dec, dtype=jnp.int32), (B, S_dec)),
            "flash_chunk": flash_chunk,
            "causal": True,
            "enc_out": enc_out,
            "cross_segment_ids": batch["enc_segment_ids"],
            "cross_positions": enc_md["abs_positions"],
        }
        x = embed_tokens(cfg, params, tokens, compute_dtype)
    else:
        md = _default_md(cfg, batch, flash_chunk)
        x = embed_tokens(cfg, params, batch["tokens"], compute_dtype)
        if cfg.vlm and "vision_embeds" in batch:
            vis = batch["vision_embeds"].astype(compute_dtype)
            S_vis = vis.shape[1]
            x = jnp.concatenate([vis, x[:, S_vis:]], axis=1)

    if _collect is not None:
        md["collect_state"] = True
    x = policy.constrain(x, "batch", "seq", None)
    x, caches = _run_layers(cfg, params["layers"], x, md, policy, use_scan=use_scan, remat=remat)
    if _collect is not None:
        _collect["caches"] = caches
    with tracing.scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = lm_logits(cfg, params, x, policy)

    if cfg.n_experts:  # load-balance aux from a replicated router pass (cheap)
        moe_layers = [p for pos, p in enumerate(params["layers"]) if cfg.period[pos].ffn == "moe"]
        if moe_layers:
            first = jax.tree.map(lambda a: a[0], moe_layers[0])
            aux["moe_aux"] = router_aux_loss(cfg, first["ffn"], x.astype(jnp.float32))
    return logits, aux


def loss_fn(cfg, params, batch, policy, **fw_kwargs):
    logits, aux = forward_train(cfg, params, batch, policy, **fw_kwargs)
    with tracing.scope("lm_head"):
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        labels_c = jnp.maximum(labels, 0)
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels_c[..., None], axis=-1)[..., 0]
        nll = (lse - ll) * mask
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = nll.sum() / denom
        zloss = 1e-4 * jnp.sum(jnp.square(lse) * mask) / denom
    total = loss + zloss + 0.01 * aux["moe_aux"]
    return total, {"loss": loss, "zloss": zloss, "moe_aux": aux["moe_aux"], "ntokens": mask.sum()}


def prefill_forward(cfg, params, batch, policy, *, use_scan=True, flash_chunk=1024,
                    compute_dtype=jnp.bfloat16):
    """Inference prefill: last-token logits + filled decode caches."""
    batch = dict(batch)
    logits, aux, caches = _forward_collect(
        cfg, params, batch, policy, use_scan=use_scan, flash_chunk=flash_chunk,
        compute_dtype=compute_dtype,
    )
    return logits[:, -1:], caches


def _forward_collect(cfg, params, batch, policy, **kw):
    """forward_train with collect_state threaded through (prefill mode)."""
    # Implemented by temporarily flagging metadata; reuse forward_train body via
    # a collect container.
    holder = {}
    logits, aux = forward_train(
        cfg, params, batch, policy, remat=False, _collect=holder, **kw
    )
    return logits, aux, holder.get("caches")


# ----------------------------------------------------------------- decode
def _layer_cache(cfg, spec, B, max_len, cache_dtype, cross_len=0):
    c = {}
    if spec.mixer == "attn":
        T = min(2 * cfg.window, max_len) if spec.attn_kind == "swa" else max_len
        K, dh = cfg.n_kv_heads, cfg.head_dim
        c["mixer"] = {
            "k": jnp.zeros((B, T, K, dh), cache_dtype),
            "v": jnp.zeros((B, T, K, dh), cache_dtype),
            "pos": jnp.full((B, T), -1, jnp.int32),
        }
    elif spec.mixer == "mamba":
        c["mixer"] = init_mamba_cache(cfg, B)
    elif spec.mixer == "mlstm":
        c["mixer"] = init_mlstm_cache(cfg, B)
    elif spec.mixer == "slstm":
        c["mixer"] = init_slstm_cache(cfg, B)
    if cfg.enc_dec:
        K, dh = cfg.n_kv_heads, cfg.head_dim
        c["cross"] = {
            "k_const": jnp.zeros((B, cross_len, K, dh), cache_dtype),
            "v_const": jnp.zeros((B, cross_len, K, dh), cache_dtype),
        }
    return c


def init_cache(cfg, B, max_len, cache_dtype=jnp.bfloat16, cross_len=0):
    """Stacked (scan-layout) decode cache."""
    per_layer = [
        _layer_cache(cfg, cfg.layer_spec(i), B, max_len, cache_dtype, cross_len)
        for i in range(cfg.n_layers)
    ]
    P = len(cfg.period)
    stacked = []
    for pos in range(P):
        group = [per_layer[j * P + pos] for j in range(cfg.n_layers // P)]
        stacked.append(jax.tree.map(lambda *xs: jnp.stack(xs), *group))
    return tuple(stacked)


def serve_forward(cfg, params, cache, batch, policy, compute_dtype=jnp.bfloat16):
    """One decode step. batch: tokens (B,1), lengths (B,) current positions.

    Returns (logits (B,1,V), new_cache).
    """
    tokens, lengths = batch["tokens"], batch["lengths"]
    B = tokens.shape[0]
    if cfg.mrope_sections is not None:
        positions = jnp.broadcast_to(lengths[:, None, None], (B, 1, 3)).astype(jnp.int32)
    else:
        positions = lengths[:, None].astype(jnp.int32)
    md = {
        "positions": positions,
        "lengths": lengths,
        "segment_ids": jnp.ones((B, 1), jnp.int32),
        "causal": True,
    }
    if cfg.enc_dec:
        md["cross_segment_ids"] = batch["cross_segment_ids"]
        md["cross_positions"] = batch["cross_positions"]
    x = embed_tokens(cfg, params, tokens, compute_dtype)
    x = policy.constrain(x, "batch", None, None)
    x, new_cache = _run_layers(cfg, params["layers"], x, md, policy, caches=cache, use_scan=True)
    with tracing.scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = lm_logits(cfg, params, x, policy)
    return logits, new_cache
