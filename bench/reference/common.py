"""Plain float32 pieces of the reference models, in jax.numpy only.

Every matrix product goes through `Mat`, which is float32 at `highest`
precision (a TPU otherwise feeds f32 matmuls to the MXU as bf16), or,
for the control, the same product with both operands rounded to fp8
(e4m3, one scale per tensor): the next precision below the bf16 that the
configurations compute in.

Linear attention is written as its definition, not as a scan: for query
i, o_i = sum_{j<=i} (a + b q_i.k_j) v_j / sum_{j<=i} (a + b q_i.k_j),
with q and k l2-normalized first (paper Eq. 22).  Rows are taken in
blocks against every key under a causal mask, so that the scores fit
in memory at 17k tokens.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _round_fp8(x):
    """x rounded to fp8 e4m3 with one scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(F32) * scale


@jax.custom_vjp
def _fp8(x):
    return _round_fp8(x)


def _fp8_fwd(x):
    return _round_fp8(x), None


def _fp8_bwd(_, ct):
    # the backward's matmuls take their cotangents in fp8 too, each
    # tensor with its own scale, as an fp8 training step would
    return (_round_fp8(ct),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


class Mat:
    """einsum in the reference's precision: "f32" or "fp8"."""

    def __init__(self, precision: str = "f32"):
        if precision not in ("f32", "fp8"):
            raise ValueError(precision)
        self.precision = precision

    def __call__(self, spec, x, y):
        x, y = x.astype(F32), y.astype(F32)
        if self.precision == "fp8":
            x, y = _fp8(x), _fp8(y)
        return jnp.einsum(spec, x, y, precision=HIGHEST,
                          preferred_element_type=F32)


def linear(mat, p, x):
    y = mat("nd,de->ne", x, p["w"])
    if "b" in p:
        y = y + p["b"].astype(F32)
    return y


def layer_norm(p, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rms_norm(p, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def rope(x, positions, rot_dim: int, theta: float):
    """GPT-NeoX rotary embedding (rotate-half) on the first rot_dim
    features of x: (H, N, D)."""
    half = rot_dim // 2
    inv = 1.0 / theta ** (jnp.arange(0, rot_dim, 2, dtype=F32) / rot_dim)
    ang = positions.astype(F32)[:, None] * inv[None, :]       # (N, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rot_dim]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rot, x[..., rot_dim:]], -1)


def l2_normalize(x, eps: float = 1e-6):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _blocks(n: int, block: int) -> tuple:
    """(rows per block, number of blocks) covering n rows."""
    b = min(block, n)
    return b, -(-n // b)


def linear_attention(mat, q, k, v, a: float, b: float, block: int):
    """Causal normalized linear attention.  q: (H, N, D); k, v: (Hkv, N,
    D) with Hkv dividing H (query head h reads KV head h // (H/Hkv))."""
    h, n, d = q.shape
    hkv = k.shape[0]
    rows, t = _blocks(n, block)
    qg = jnp.pad(q, ((0, 0), (0, rows * t - n), (0, 0)))
    qg = qg.reshape(hkv, h // hkv, t, rows, d).transpose(2, 0, 1, 3, 4)

    @jax.checkpoint
    def one(args):
        r, qb = args
        s = a + b * mat("hgid,hjd->hgij", qb, k)
        causal = (r * rows + jnp.arange(rows))[:, None] >= \
            jnp.arange(n)[None, :]
        s = jnp.where(causal, s, 0.0)
        return mat("hgij,hjd->hgid", s, v) / s.sum(-1, keepdims=True)

    o = jax.lax.map(one, (jnp.arange(t), qg))          # (T, Hkv, G, R, D)
    return o.transpose(1, 2, 0, 3, 4).reshape(h, rows * t, d)[:, :n]


def attention(mat, p, x, positions, cfg: dict, rot_dim: int, theta: float,
              num_kv_heads: int):
    """Projections, rope, l2 normalization, linear attention, output."""
    n = x.shape[0]
    heads = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // heads
    att = cfg["attention"]

    def split(y, nh):
        return y.reshape(n, nh, hd).transpose(1, 0, 2)

    q = split(linear(mat, p["wq"], x), heads)
    k = split(linear(mat, p["wk"], x), num_kv_heads)
    v = split(linear(mat, p["wv"], x), num_kv_heads)
    q = rope(q, positions, rot_dim, theta)
    k = rope(k, positions, rot_dim, theta)
    if att["normalize_qk"]:
        q, k = l2_normalize(q), l2_normalize(k)
    o = linear_attention(mat, q, k, v, att["a"], att["b"],
                         block=512)
    return linear(mat, p["wo"], o.transpose(1, 0, 2).reshape(n, heads * hd))


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def cross_entropy_rows(mat, h, w_unembed, labels, mask, tied: bool,
                       rows: int = 1024):
    """Sum over rows of -log softmax(h W)[label] * mask, in blocks of
    rows, each recomputed in the backward so no (N, V) logits persist."""
    n = h.shape[0]
    rows, t = _blocks(n, rows)
    pad = rows * t - n
    spec = "nd,vd->nv" if tied else "nd,dv->nv"

    @jax.checkpoint
    def block(args):
        hb, yb, mb = args
        logits = mat(spec, hb, w_unembed)
        z = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return jnp.sum((z - ll) * mb)

    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(t, rows, -1)
    yb = jnp.pad(labels, (0, pad)).reshape(t, rows)
    mb = jnp.pad(mask, (0, pad)).reshape(t, rows)
    return jax.lax.map(block, (hb, yb, mb)).sum()
