"""Pallas TPU kernels for the paper's linear attention.

TPU adaptation of the paper's CUDA kernels (§4, Algorithms 1-4):

  * the per-thread register state (x^(1), x^(2), alpha, beta recurrences)
    becomes f32 VMEM scratch carried across the sequential chunk axis of
    the grid;
  * the paper's "Constant term" and "Linear term" are fused by augmenting
    V with a ones column, so one MXU contraction produces numerator and
    denominator together;
  * the D/L-block warp reduction is unnecessary — the m-contraction lives
    inside a single systolic matmul;
  * coalesced off-chip access becomes BlockSpec HBM->VMEM streaming with
    D on lanes and the token chunk on sublanes.

Grid layout (forward & grad-Q): (B, H, N/C), semantics
("parallel", "parallel", "arbitrary") — B*H is the paper's outer-block
parallelism, the chunk axis is its sequential token loop.  Grad-K/V runs
the chunk axis in reverse via index maps (the paper's i = N..1 loops).
Grouped-query attention reads the KV block through an h // group index
map — no KV repetition is materialized.

Validated against kernels/ref.py and core/chunked.py in interpret mode
on the CPU; tests/test_tpu_compile.py compiles it for a TPU v5e, and
chip_smoke.py checks it against the xla impl on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.numerics import safe_div
from repro.kernels.defaults import BWD_VMEM_LIMIT_BYTES, DEFAULT_TILES
from repro.kernels.layout import as_row, col, folded_col, pad_seq

F32 = jnp.float32
_CHUNK = DEFAULT_TILES["linear"]["chunk"]


def _causal_mask(rows: int, cols: int, row_mod: int | None = None):
    ii = lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    if row_mod is not None:
        ii = ii % row_mod
    jj = lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return ii >= jj


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, g_ref, s_ref, p_ref, *,
                a: float, b: float):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        p_ref[...] = jnp.zeros_like(p_ref)

    q = q_ref[0, 0].astype(F32)
    k = k_ref[0, 0].astype(F32)
    v = v_ref[0, 0].astype(F32)
    c = q.shape[0]
    dv = v.shape[1]
    vaug = jnp.concatenate([v, jnp.ones((c, 1), F32)], axis=1)

    att = a + b * jnp.dot(q, k.T, preferred_element_type=F32)
    att = jnp.where(_causal_mask(c, c), att, 0.0)
    f = (jnp.dot(att, vaug, preferred_element_type=F32)
         + a * p_ref[...]
         + b * jnp.dot(q, s_ref[...], preferred_element_type=F32))
    g = f[:, dv:]                                    # (c, 1)
    o_ref[0, 0] = (f[:, :dv] / g).astype(o_ref.dtype)
    g_ref[0, 0] = g.T.astype(g_ref.dtype)

    s_ref[...] += jnp.dot(k.T, vaug, preferred_element_type=F32)
    p_ref[...] += jnp.sum(vaug, axis=0, keepdims=True)


def la_fwd_pallas(q, k, v, a: float, b: float, chunk: int = _CHUNK,
                  interpret: bool = False):
    """Returns (o, g).  q: (B,H,N,D); k,v: (B,Hkv,N,D)."""
    bsz, h, n, dk = q.shape
    dv = v.shape[-1]
    hkv = k.shape[1]
    group = h // hkv
    c = min(chunk, n)
    n_pad = -(-n // c) * c
    t = n_pad // c
    q, k, v = (pad_seq(x, n_pad) for x in (q, k, v))

    kernel = functools.partial(_fwd_kernel, a=a, b=b)
    o, g = pl.pallas_call(
        kernel,
        grid=(bsz, h, t),
        in_specs=[
            pl.BlockSpec((1, 1, c, dk), lambda bi, hi, ti: (bi, hi, ti, 0)),
            pl.BlockSpec((1, 1, c, dk),
                         lambda bi, hi, ti: (bi, hi // group, ti, 0)),
            pl.BlockSpec((1, 1, c, dv),
                         lambda bi, hi, ti: (bi, hi // group, ti, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c, dv), lambda bi, hi, ti: (bi, hi, ti, 0)),
            pl.BlockSpec((1, 1, 1, c), lambda bi, hi, ti: (bi, hi, 0, ti)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, n_pad, dv), q.dtype),
            jax.ShapeDtypeStruct((bsz, h, 1, n_pad), F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dk, dv + 1), F32),
            pltpu.VMEM((1, dv + 1), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="la_fwd",
    )(q, k, v)
    return o[:, :, :n], g[:, :, 0, :n]


# ---------------------------------------------------------------------------
# Backward — grad Q (forward chunk scan; paper alpha^Q/beta^Q, Eq. 21)
# ---------------------------------------------------------------------------

def _bwd_q_kernel(k_ref, v_ref, om_ref, h_ref, dq_ref, a_ref, *, b: float):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        a_ref[...] = jnp.zeros_like(a_ref)

    k = k_ref[0, 0].astype(F32)
    v = v_ref[0, 0].astype(F32)
    om = om_ref[0, 0].astype(F32)
    hv = col(h_ref[0, 0].astype(F32))                # (c, 1)
    c = k.shape[0]
    vaug = jnp.concatenate([v, jnp.ones((c, 1), F32)], axis=1)
    gmat = jnp.concatenate([om, -hv], axis=1)        # [om_hat, -h]

    sc = jnp.dot(gmat, vaug.T, preferred_element_type=F32)
    sc = jnp.where(_causal_mask(c, c), sc, 0.0)
    dq = jnp.dot(sc, k, preferred_element_type=F32) + jnp.dot(
        gmat, a_ref[...].T, preferred_element_type=F32)
    dq_ref[0, 0] = (b * dq).astype(dq_ref.dtype)

    a_ref[...] += jnp.dot(k.T, vaug, preferred_element_type=F32)


# ---------------------------------------------------------------------------
# Backward — grad K / grad V (reverse chunk scan; alpha/beta^{K,V}, Eq. 21)
# ---------------------------------------------------------------------------

def _bwd_kv_kernel(q_ref, k_ref, v_ref, om_ref, h_ref, dk_ref, dv_ref,
                   u_ref, *, a: float, b: float):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        u_ref[...] = jnp.zeros_like(u_ref)

    g_, c, dk = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    dv = v_ref.shape[3]
    q = q_ref[0].astype(F32).reshape(g_ * c, dk)
    om = om_ref[0].astype(F32).reshape(g_ * c, dv)
    hv = folded_col(h_ref[0].astype(F32))                  # (G*C, 1)
    k = k_ref[0, 0].astype(F32)
    v = v_ref[0, 0].astype(F32)

    vneg = jnp.concatenate([v, -jnp.ones((c, 1), F32)], axis=1)
    g2 = jnp.concatenate([om, hv], axis=1)                 # (G*C, D+1)
    u = u_ref[...]
    mask = _causal_mask(g_ * c, c, row_mod=c)              # i >= p per group

    sc = jnp.dot(g2, vneg.T, preferred_element_type=F32)
    sc = jnp.where(mask, sc, 0.0)
    dk_ = (jnp.dot(sc.T, q, preferred_element_type=F32)
           + jnp.dot(vneg, u[:dk, :].T, preferred_element_type=F32))
    dk_ref[0, 0] = (b * dk_).astype(dk_ref.dtype)

    att = a + b * jnp.dot(q, k.T, preferred_element_type=F32)
    att = jnp.where(mask, att, 0.0)
    dv_ = (jnp.dot(att.T, om, preferred_element_type=F32)
           + b * jnp.dot(k, u[:dk, :dv], preferred_element_type=F32)
           + a * u[dk:, :dv])
    dv_ref[0, 0] = dv_.astype(dv_ref.dtype)

    qaug = jnp.concatenate([q, jnp.ones((g_ * c, 1), F32)], axis=1)
    u_ref[...] += jnp.dot(qaug.T, g2, preferred_element_type=F32)


def la_bwd_pallas(q, k, v, o, g, omega, a: float, b: float,
                  chunk: int = _CHUNK, interpret: bool = False):
    """Analytic backward from residuals {q,k,v,o,g}; returns (dq, dk, dv)."""
    bsz, h, n, dk = q.shape
    dv = v.shape[-1]
    hkv = k.shape[1]
    group = h // hkv
    c = min(chunk, n)
    n_pad = -(-n // c) * c
    t = n_pad // c

    om_hat = safe_div(omega.astype(F32), g[..., None])
    h_vec = as_row(jnp.sum(o.astype(F32) * om_hat, axis=-1), n_pad)
    q, k, v, om_hat = (pad_seq(x, n_pad) for x in (q, k, v, om_hat))

    dq = pl.pallas_call(
        functools.partial(_bwd_q_kernel, b=b),
        grid=(bsz, h, t),
        in_specs=[
            pl.BlockSpec((1, 1, c, dk),
                         lambda bi, hi, ti: (bi, hi // group, ti, 0)),
            pl.BlockSpec((1, 1, c, dv),
                         lambda bi, hi, ti: (bi, hi // group, ti, 0)),
            pl.BlockSpec((1, 1, c, dv), lambda bi, hi, ti: (bi, hi, ti, 0)),
            pl.BlockSpec((1, 1, 1, c), lambda bi, hi, ti: (bi, hi, 0, ti)),
        ],
        out_specs=pl.BlockSpec((1, 1, c, dk),
                               lambda bi, hi, ti: (bi, hi, ti, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, n_pad, dk), q.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv + 1), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="la_bwd_dq",
    )(k, v, om_hat, h_vec)

    rev = lambda ti: t - 1 - ti  # noqa: E731 — reverse chunk iteration
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_kv_kernel, a=a, b=b),
        grid=(bsz, hkv, t),
        in_specs=[
            pl.BlockSpec((1, group, c, dk),
                         lambda bi, hi, ti: (bi, hi, rev(ti), 0)),
            pl.BlockSpec((1, 1, c, dk),
                         lambda bi, hi, ti: (bi, hi, rev(ti), 0)),
            pl.BlockSpec((1, 1, c, dv),
                         lambda bi, hi, ti: (bi, hi, rev(ti), 0)),
            pl.BlockSpec((1, group, c, dv),
                         lambda bi, hi, ti: (bi, hi, rev(ti), 0)),
            pl.BlockSpec((1, group, 1, c),
                         lambda bi, hi, ti: (bi, hi, 0, rev(ti))),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c, dk),
                         lambda bi, hi, ti: (bi, hi, rev(ti), 0)),
            pl.BlockSpec((1, 1, c, dv),
                         lambda bi, hi, ti: (bi, hi, rev(ti), 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, hkv, n_pad, dk), k.dtype),
            jax.ShapeDtypeStruct((bsz, hkv, n_pad, dv), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((dk + 1, dv + 1), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=BWD_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="la_bwd_dkv",
    )(q, k, v, om_hat, h_vec)

    return dq[:, :, :n], dk[:, :, :n], dv[:, :, :n]
