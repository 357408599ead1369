"""Plain BASIC dual encoder in jax.numpy (arXiv:2111.10050 §3, §4.1).

Written from the paper and the configuration file, independent of the
program under test: two pre-norm transformer towers (RMSNorm, rotary
positions, bidirectional softmax attention, SwiGLU feed-forward), mean
pooling, a linear projection onto the unit sphere, and the symmetric
image-text softmax loss with a learned temperature (paper Eqs. 1-3).
The image tower reads raw pixels through a linear patchify, the stand-in
the configuration states for the CoAtNet convolution stages.

The weights are drawn from the run's seed with the same split order,
shapes and truncated-normal scales that the configuration's deployment
initialises with, so that both sides start from the same numbers; the
reference never receives a weight from the program.

``Numerics`` says how a forward is computed. ``REFERENCE`` is float32 with
every matrix product at ``highest`` precision. ``STATED`` is float32 at
the chip's default matrix precision, as a float32 configuration states
it for serving: on a TPU each operand of a product is rounded once to
bfloat16 (on the CPU it is exact float32). The controls drop below the
configuration's stated precision: ``BF16`` keeps activations and operands
in bfloat16 (the control of a float32 configuration), ``FP8`` additionally
rounds every matrix operand to float8 e4m3, and its gradient to e5m2,
with a per-tensor scale (the control of a bfloat16 configuration).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
DEFAULT = jax.lax.Precision.DEFAULT
NEG = -1e30


@dataclasses.dataclass(frozen=True)
class Numerics:
    name: str
    act: object = jnp.float32      # dtype of activations between products
    fp8: bool = False              # round matrix operands to e4m3
    precision: object = HIGHEST    # of every matrix product


REFERENCE = Numerics("f32")
STATED = Numerics("f32_default", precision=DEFAULT)
BF16 = Numerics("bf16", act=jnp.bfloat16)
FP8 = Numerics("fp8", act=jnp.bfloat16, fp8=True)
NUMERICS = {n.name: n for n in (REFERENCE, STATED, BF16, FP8)}


def _round8(x, dtype, top):
    """Round to a float8 type with a per-tensor scale (|x| max to ``top``)."""
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-30) / top
    q = (x32 / scale).astype(dtype).astype(jnp.float32)
    return (q * scale).astype(x.dtype)


@jax.custom_vjp
def _e4m3(x):
    """Float8 training's rounding: operands to e4m3 going forward, their
    gradients to e5m2 coming back, each scaled per tensor."""
    return _round8(x, jnp.float8_e4m3fn, 448.0)


def _e4m3_fwd(x):
    return _e4m3(x), None


def _e4m3_bwd(_, g):
    return (_round8(g, jnp.float8_e5m2, 57344.0),)


_e4m3.defvjp(_e4m3_fwd, _e4m3_bwd)


def mm(spec, a, b, num: Numerics):
    """One matrix product in the stated numerics (float32 accumulation)."""
    a, b = a.astype(num.act), b.astype(num.act)
    if num.fp8:
        a, b = _e4m3(a), _e4m3(b)
    out = jnp.einsum(spec, a, b, precision=num.precision,
                     preferred_element_type=jnp.float32)
    return out.astype(num.act)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _tn(key, shape, std):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                             jnp.float32)


def init_tower(t: dict, key):
    """One tower's weights; every block's weights stacked on a leading
    depth axis."""
    L, d, dff = t["n_layers"], t["d_model"], t["d_ff"]
    hd = t["head_dim"]
    q = t["n_heads"] * hd
    keys = jax.random.split(key, 4)
    k1, k2 = jax.random.split(keys[0])
    kq, kk, kv, ko = jax.random.split(k1, 4)
    ka, kb, kc = jax.random.split(k2, 3)
    block = {
        "ln1": jnp.ones((L, d), jnp.float32),
        "attn": {"wq": _tn(kq, (L, d, q), d ** -0.5),
                 "wk": _tn(kk, (L, d, q), d ** -0.5),
                 "wv": _tn(kv, (L, d, q), d ** -0.5),
                 "wo": _tn(ko, (L, q, d), q ** -0.5)},
        "ln2": jnp.ones((L, d), jnp.float32),
        "ffn": {"wi": _tn(ka, (L, d, dff), d ** -0.5),
                "wg": _tn(kb, (L, d, dff), d ** -0.5),
                "wo": _tn(kc, (L, dff, d), dff ** -0.5)},
    }
    p = {"blocks": [block], "final_norm": jnp.ones((d,), jnp.float32)}
    if t.get("image_size"):
        pd = t["patch_size"] ** 2 * t["channels"]
        p["frontend"] = {"patch_proj": _tn(keys[1], (pd, d), pd ** -0.5)}
    if t.get("vocab"):
        p["embed"] = _tn(keys[3], (t["vocab"], d), d ** -0.5)
        p["lm_head"] = _tn(keys[2], (d, t["vocab"]), d ** -0.5)
    return p


def init_params(cfg: dict, key):
    """The dual encoder's weights from ``key`` (``jax.random.key(seed)``)."""
    ki, kt, kpi, kpt = jax.random.split(key, 4)
    it, tt, e = cfg["image_tower"], cfg["text_tower"], cfg["embed_dim"]
    return {
        "image": {"tower": init_tower(it, ki),
                  "proj": _tn(kpi, (it["d_model"], e), it["d_model"] ** -0.5)},
        "text": {"tower": init_tower(tt, kt),
                 "proj": _tn(kpt, (tt["d_model"], e), tt["d_model"] ** -0.5)},
        "log_tau": jnp.asarray(jnp.log(cfg["init_temperature"]), jnp.float32),
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def rope(x, theta):
    """Rotary positions over the sequence axis of (b, s, h, hd)."""
    hd, s = x.shape[-1], x.shape[1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def block(t: dict, p, h, key_bias, num: Numerics):
    b, s, d = h.shape
    H, hd, eps = t["n_heads"], t["head_dim"], t["norm_eps"]
    x = rms_norm(h, p["ln1"], eps)
    q = mm("bsd,de->bse", x, p["attn"]["wq"], num).reshape(b, s, H, hd)
    k = mm("bsd,de->bse", x, p["attn"]["wk"], num).reshape(b, s, H, hd)
    v = mm("bsd,de->bse", x, p["attn"]["wv"], num).reshape(b, s, H, hd)
    q, k = rope(q, t["rope_theta"]), rope(k, t["rope_theta"])
    scores = mm("bshd,bthd->bhst", q, k, num).astype(jnp.float32) \
        * hd ** -0.5
    if key_bias is not None:
        scores = scores + key_bias[:, None, None, :]
    w = jax.nn.softmax(scores, axis=-1)
    o = mm("bhst,bthd->bshd", w, v, num).reshape(b, s, H * hd)
    h = h + mm("bse,ed->bsd", o, p["attn"]["wo"], num)
    x = rms_norm(h, p["ln2"], eps)
    f = mm("bsd,df->bsf", x, p["ffn"]["wi"], num) * jax.nn.silu(
        mm("bsd,df->bsf", x, p["ffn"]["wg"], num).astype(jnp.float32)
    ).astype(num.act)
    return h + mm("bsf,fd->bsd", f, p["ffn"]["wo"], num)


def tower(t: dict, p, inputs: dict, num: Numerics):
    """Pooled (b, d) float32 representation of one tower."""
    mask = None
    if "image" in inputs:
        img = inputs["image"]
        b, side, _, c = img.shape
        ps = t["patch_size"]
        g = side // ps
        x = img.reshape(b, g, ps, g, ps, c).transpose(0, 1, 3, 2, 4, 5)
        x = x.reshape(b, g * g, ps * ps * c)
        h = mm("bsp,pd->bsd", x, p["frontend"]["patch_proj"], num)
    else:
        h = jnp.take(p["embed"], inputs["tokens"], axis=0).astype(num.act)
        mask = inputs.get("attn_mask")
    key_bias = None if mask is None else jnp.where(mask, 0.0, NEG)

    def step(h, bp):
        return block(t, bp, h, key_bias, num), None

    h, _ = jax.lax.scan(step, h, p["blocks"][0])
    h = rms_norm(h, p["final_norm"], t["norm_eps"]).astype(jnp.float32)
    if mask is None:
        return jnp.mean(h, axis=1)
    m = mask.astype(jnp.float32)[..., None]
    return jnp.sum(h * m, 1) / jnp.maximum(jnp.sum(m, 1), 1.0)


def _unit(z):
    return z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-6)


def embed(cfg: dict, params, side: str, inputs: dict, num: Numerics):
    """(b, D) unit-norm float32 embeddings of one side ('image'/'text')."""
    t = cfg["image_tower" if side == "image" else "text_tower"]
    pooled = tower(t, params[side]["tower"], inputs, num)
    z = jnp.einsum("bd,de->be", pooled, params[side]["proj"],
                   precision=num.precision)
    return _unit(z)


def contrastive_loss(x, y, log_tau):
    """Paper Eq. 3: mean of the row and column softmax cross-entropies of
    A = X Yᵀ / tau against the diagonal."""
    a = jnp.einsum("id,jd->ij", x, y, precision=HIGHEST) / jnp.exp(log_tau)
    diag = jnp.diagonal(a)
    row = jnp.mean(jax.nn.logsumexp(a, axis=1) - diag)
    col = jnp.mean(jax.nn.logsumexp(a, axis=0) - diag)
    return 0.5 * (row + col)
