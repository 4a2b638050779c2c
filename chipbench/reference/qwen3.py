"""Plain Qwen3 decoder training step, written from the published description
(Qwen3 technical report, arXiv:2505.09388, and the Qwen3 modelling code's
config on Hugging Face). It imports nothing of the program under test.

A layer is x + attn(rms(x)), then x + mlp(rms(x)):

- attn: q/k/v projections without bias; an RMSNorm over each head's
  head_dim on q and k (qk-norm) before rotary embedding (rotate-half,
  inv_freq = theta^(-2i/head_dim), at each token's position in its document);
  grouped-query attention, each of the K kv heads serving H/K query heads;
  softmax(q k^T / sqrt(head_dim)) over the keys of the same document at or
  before the query; an o projection.
- mlp: down(silu(gate(x)) * up(x)).

Then a final RMSNorm and an untied lm head. Training follows the recipe the
configuration states (`train`): the loss over microbatches, the z-loss, the
global-norm clip and AdamW with decoupled weight decay on every leaf.

Departures from the published model, all stated by the configuration:
RMSNorm scales are trained as offsets from 1 (scale = 1 + w); the logits cover
every row of the (padded) embedding matrices.

Precision: "f32" computes every matmul in float32 at the highest precision,
which a TPU otherwise rounds to bfloat16 passes. "fp8" is the control: both
operands of every matmul are rounded to float8 (e4m3 forward, e5m2 for the
cotangents, each tensor scaled to its largest magnitude), as fp8 training
does; everything else stays float32.

Attention is computed one kv-head group at a time under jax.checkpoint, so
that a 4096-token row fits beside the optimizer state.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3, E5M2 = jnp.float8_e4m3fn, jnp.float8_e5m2


# ------------------------------------------------------------- precision
def _scaled_round(x, dtype):
    top = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, top / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def fp8(x):
    return _scaled_round(x, E4M3)


def _fp8_fwd(x):
    return _scaled_round(x, E4M3), None


def _fp8_bwd(_, g):
    return (_scaled_round(g, E5M2),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def matmul(spec, a, b, mode):
    if mode == "fp8":
        a, b = fp8(a), fp8(b)
    elif mode != "f32":
        raise ValueError(f"unknown precision {mode!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


# ------------------------------------------------------------------ shapes
def dims(cfg):
    return dict(D=cfg["hidden_size"], F=cfg["intermediate_size"],
                H=cfg["num_attention_heads"], K=cfg["num_key_value_heads"],
                dh=cfg["head_dim"], L=cfg["num_hidden_layers"],
                V=cfg["assumed"]["padded_vocab_size"])


def shapes(cfg):
    """Canonical leaf name -> shape."""
    d = dims(cfg)
    D, F, H, K, dh, V = d["D"], d["F"], d["H"], d["K"], d["dh"], d["V"]
    out = {"embed": (V, D), "final_norm": (D,), "lm_head": (D, V)}
    for i in range(d["L"]):
        out.update({f"layers.{i}.{k}": s for k, s in {
            "attn_norm": (D,), "wq": (D, H, dh), "wk": (D, K, dh), "wv": (D, K, dh),
            "wo": (H, dh, D), "q_norm": (dh,), "k_norm": (dh,), "mlp_norm": (D,),
            "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}.items()})
    return out


def fan_in(name, shape):
    if name.endswith("wo"):
        return shape[0] * shape[1]
    if name == "embed":
        return shape[1]
    return shape[0]


def init_params(cfg, key):
    """Weights from a key: normal / sqrt(fan-in); norm offsets 0. One leaf
    per canonical name, each from its own fold of the key (sorted names)."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(cfg).items())):
        if len(shape) == 1:
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) \
                / math.sqrt(fan_in(name, shape))
    return out


def key_for_seed(seed):
    """A threefry key from any whole-number seed."""
    words = np.random.SeedSequence(int(seed) % 2**128).generate_state(2, dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words))


# ------------------------------------------------------------------ model
def rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def rope(x, positions, theta):
    """Rotate-half rotary embedding of x (B, S, N, dh) at positions (B, S)."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attend(q, k, v, seg, mode):
    """q (B,S,H,dh), k/v (B,S,K,dh) -> (B,S,H,dh); keys of the same document
    at or before the query; padding (segment 0) attends to nothing."""
    B, S, H, dh = q.shape
    K = k.shape[2]
    G = H // K
    idx = jnp.arange(S)
    mask = ((seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] != 0)
            & (idx[:, None] >= idx[None, :])[None])  # (B, S, S)
    scale = 1.0 / math.sqrt(dh)

    @jax.checkpoint
    def group(args):
        qg, kg, vg = args  # (B,S,G,dh), (B,S,dh), (B,S,dh)
        s = matmul("bqgd,bkd->bgqk", qg, kg, mode) * scale
        s = jnp.where(mask[:, None], s, -jnp.inf)
        m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask[:, None], jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        return matmul("bgqk,bkd->bqgd", p, vg, mode)

    qg = q.reshape(B, S, K, G, dh).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(group, (qg, k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)))
    return out.transpose(1, 2, 0, 3, 4).reshape(B, S, H, dh)


def layer(cfg, p, x, seg, pos, mode):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = rms(x, p["attn_norm"], eps)
    q = rms(matmul("bsd,dhk->bshk", h, p["wq"], mode), p["q_norm"], eps)
    k = rms(matmul("bsd,dhk->bshk", h, p["wk"], mode), p["k_norm"], eps)
    v = matmul("bsd,dhk->bshk", h, p["wv"], mode)
    o = attend(rope(q, pos, theta), rope(k, pos, theta), v, seg, mode)
    x = x + matmul("bshk,hkd->bsd", o, p["wo"], mode)
    h = rms(x, p["mlp_norm"], eps)
    g = jax.nn.silu(matmul("bsd,df->bsf", h, p["w_gate"], mode))
    u = matmul("bsd,df->bsf", h, p["w_up"], mode)
    return x + matmul("bsf,fd->bsd", g * u, p["w_down"], mode)


def nll_terms(cfg, params, mb, mode):
    """-> (sum of nll over target tokens, sum of lse^2 over them, count)."""
    x = jnp.take(params["embed"], mb["tokens"], axis=0)
    for i in range(cfg["num_hidden_layers"]):
        p = {k.split(".", 2)[2]: v for k, v in params.items() if k.startswith(f"layers.{i}.")}
        x = jax.checkpoint(functools.partial(layer, cfg, mode=mode))(
            p, x, mb["segment_ids"], mb["positions"])
    x = rms(x, params["final_norm"], cfg["rms_norm_eps"])
    logits = matmul("bsd,dv->bsv", x, params["lm_head"], mode)
    labels = mb["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - ll) * mask), jnp.sum(jnp.square(lse) * mask), jnp.sum(mask)


# ------------------------------------------------------------------- step
def _objective(cfg, train, params, mbs, mode):
    """The step's objective, which is also the loss it reports: per the
    recipe, the mean over microbatches of each one's token mean, or the
    token mean over all of them; with the z-loss term added."""
    terms = [nll_terms(cfg, params, mb, mode) for mb in mbs]
    z = train["zloss"]
    if train["microbatch_loss"] == "mean_of_means":
        return sum((nll + z * lse2) / jnp.maximum(n, 1.0) for nll, lse2, n in terms) / len(terms)
    if train["microbatch_loss"] == "token_weighted":
        n = jnp.maximum(sum(t[2] for t in terms), 1.0)
        return sum(t[0] + z * t[1] for t in terms) / n
    raise ValueError(train["microbatch_loss"])


def make_step(cfg, train, mode):
    """step(params, opt, step, batch) -> (params, opt, loss, grad_norms),
    with grad_norms the per-leaf norms of the gradient the optimizer gets."""
    o = train["optimizer"]
    lr, b1, b2, eps, wd = o["lr"], o["b1"], o["b2"], o["eps"], o["weight_decay"]
    n_mb = train["microbatches"]

    def step(params, opt, t, batch):
        rows = batch["tokens"].shape[0] // n_mb
        mbs = [{k: v[i * rows:(i + 1) * rows] for k, v in batch.items()} for i in range(n_mb)]
        loss, grads = jax.value_and_grad(lambda p: _objective(cfg, train, p, mbs, mode))(params)
        if train["clip_norm"] is not None:
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
            grads = jax.tree.map(
                lambda g: g * jnp.minimum(1.0, train["clip_norm"] / jnp.maximum(gnorm, 1e-9)),
                grads)
        tf = t.astype(jnp.float32) + 1.0
        bc1, bc2 = 1.0 - b1 ** tf, 1.0 - b2 ** tf
        m = {k: b1 * opt["m"][k] + (1 - b1) * g for k, g in grads.items()}
        v = {k: b2 * opt["v"][k] + (1 - b2) * jnp.square(g) for k, g in grads.items()}
        new = {k: p - lr * ((m[k] / bc1) / (jnp.sqrt(v[k] / bc2) + eps) + wd * p)
               for k, p in params.items()}
        norms = {k: jnp.sqrt(jnp.sum(jnp.square(g))) for k, g in grads.items()}
        return new, {"m": m, "v": v}, loss, norms

    return jax.jit(step, donate_argnums=(0, 1))


def train(cfg, train_recipe, key, batches, mode="f32"):
    """Run len(batches) steps from init_params(cfg, key).

    Returns (losses, grad norms of step 1 per leaf, final params on the host
    as float32 numpy)."""
    params = jax.jit(functools.partial(init_params, cfg))(key)
    opt = {"m": jax.tree.map(jnp.zeros_like, params),
           "v": jax.tree.map(jnp.zeros_like, params)}
    step = make_step(cfg, train_recipe, mode)
    losses, first_norms = [], None
    for t, b in enumerate(batches):
        params, opt, loss, norms = step(params, opt, jnp.asarray(t, jnp.int32),
                                        {k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(loss))
        if first_norms is None:
            first_norms = {k: float(v) for k, v in norms.items()}
    del opt
    return losses, first_norms, {k: np.asarray(v) for k, v in params.items()}
