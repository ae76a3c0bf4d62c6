"""Closed-form (DCA) chunk calculators in pure jnp — jit/shard_map/Pallas-safe.

These mirror ``techniques.closed_form_sizes`` (numpy/float64 host versions) in
float32/int32 so they can run inside compiled TPU programs: the device-level
BSP self-scheduler (core/sspmd.py) and the Pallas chunk kernel
(kernels/dls_chunks) both call into this module.

Techniques are addressed by a stable integer id (``TECH_IDS``) so a technique
can be a traced scalar selected with ``lax.switch`` — the schedule technique
then becomes a runtime input instead of a recompilation trigger.

Parameters travel as a flat float32 vector (``pack_params``) with layout:
    [N, P, h, sigma, mu, va, fiss_b, viss_x, swr, min_chunk, seed]
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .techniques import DLSParams

__all__ = [
    "TECH_IDS",
    "TECH_NAMES_DCA",
    "pack_params",
    "sizes_for_steps",
    "prefix_for_steps",
    "default_head_cap",
    "PARAM_LEN",
]

# DCA-capable techniques only (AF excluded — no closed form; paper Sec. 4).
TECH_NAMES_DCA: Sequence[str] = (
    "static", "ss", "fsc", "gss", "tap", "tss",
    "fac", "tfss", "fiss", "viss", "rnd", "pls",
)
TECH_IDS = {n: i for i, n in enumerate(TECH_NAMES_DCA)}

PARAM_LEN = 11
(_N, _P, _H, _SIGMA, _MU, _VA, _FISS_B, _VISS_X, _SWR, _MINK, _SEED) = range(PARAM_LEN)


def pack_params(p: DLSParams) -> jnp.ndarray:
    """DLSParams -> flat float32 vector usable as a traced argument."""
    return jnp.asarray(
        [p.N, p.P, p.h, p.sigma, p.mu, p.va, p.fiss_b, p.viss_x, p.swr,
         p.min_chunk, p.seed],
        dtype=jnp.float32,
    )


# --- individual closed forms (i: float32 array of step indices) -------------


def _static(i, pv):
    base = jnp.floor(pv[_N] / pv[_P])
    rem = pv[_N] - base * pv[_P]
    return jnp.where(i < pv[_P], base + (i < rem), 1.0)


def _ss(i, pv):
    return jnp.ones_like(i)


def _fsc(i, pv):
    logp = jnp.log2(jnp.maximum(pv[_P], 2.0))
    k = (jnp.sqrt(2.0) * pv[_N] * pv[_H]) / (pv[_SIGMA] * pv[_P] * jnp.sqrt(logp) + 1e-30)
    return jnp.full_like(i, jnp.floor(k))


_POW_BITS = 24  # step indices stay below 2**24, where f32 counts exactly


def _pow_int(base, k):
    """base**k for 0 <= base <= 1 and integer-valued f32 k >= 0, from
    additions and products alone.

    Binary exponentiation: the product of base**(2**b) over the set bits of
    k.  Additions and products are correctly rounded on every backend and
    exp/log are not: the TPU's exp(i * log(0.75)) lies an ulp above 0.75**i
    where that power is exact, and the ceil in gss turns the ulp into a
    chunk one iteration too large.  So the result is the same bits on a TPU
    and a CPU, and exact wherever every power it forms is an f32 (0.5**k
    for fac; 0.75**k below k=16 for gss at P=4).

    Squaring a power near 1 doubles its relative error each time, so while
    the power is at least 1/2 its complement d = 1 - power is squared
    instead, as d * (2 - d), which keeps d's relative error from growing.
    k is capped at 2**24 - 1; base 0 (P=1) gives 0**0 = 1 and 0 beyond.
    """
    k = jnp.minimum(k, 2.0 ** _POW_BITS - 1.0)
    power, d = base, 1.0 - base
    powers = [power]
    for _ in range(_POW_BITS - 1):
        near_one = power >= 0.5
        d = jnp.where(near_one, d * (2.0 - d), 1.0 - power * power)
        power = jnp.where(near_one, 1.0 - d, power * power)
        powers.append(power)
    out = jnp.ones_like(k)
    for b in reversed(range(_POW_BITS)):
        take = k >= 2.0 ** b
        out = jnp.where(take, out * powers[b], out)
        k = jnp.where(take, k - 2.0 ** b, k)
    return out


def _gss(i, pv):
    ratio = (pv[_P] - 1.0) / pv[_P]
    return jnp.ceil(_pow_int(ratio, i) * (pv[_N] / pv[_P]))


def _tap(i, pv):
    ratio = (pv[_P] - 1.0) / pv[_P]
    raw = _pow_int(ratio, i) * (pv[_N] / pv[_P])
    va = pv[_VA]
    return jnp.ceil(raw + va * va / 2.0 - va * jnp.sqrt(2.0 * raw + va * va / 4.0))


def _tss_consts(pv):
    k0 = jnp.ceil(pv[_N] / (2.0 * pv[_P]))
    s = jnp.ceil(2.0 * pv[_N] / (k0 + 1.0))
    c = jnp.floor((k0 - 1.0) / jnp.maximum(s - 1.0, 1.0))
    return k0, c


def _tss(i, pv):
    k0, c = _tss_consts(pv)
    return jnp.maximum(k0 - i * c, 1.0)


def _fac(i, pv):
    i_new = jnp.floor(i / pv[_P]) + 1.0
    # exp2 is off by an ulp on some backends (XLA's CPU exp2(-15) > 2**-15)
    return jnp.ceil(_pow_int(0.5, i_new) * (pv[_N] / pv[_P]))


def _tfss(i, pv):
    k0, c = _tss_consts(pv)
    b = jnp.floor(i / pv[_P])
    j0 = b * pv[_P]
    # mean of P consecutive TSS terms starting at j0, with the max(.,1) clamp
    # handled exactly via the closed form of a clamped arithmetic series:
    # terms t_j = max(k0 - (j0+j)*c, 1), j in [0,P).  Let m = number of
    # unclamped terms = clip(ceil(((k0-1)/c - j0)), 0, P) (c>0 case).
    p_ = pv[_P]
    safe_c = jnp.maximum(c, 1e-9)
    m = jnp.clip(jnp.ceil((k0 - 1.0) / safe_c - j0), 0.0, p_)
    # sum of unclamped arithmetic part: m*k0 - c*(m*j0 + m*(m-1)/2)
    s_unclamped = m * k0 - c * (m * j0 + m * (m - 1.0) / 2.0)
    total = jnp.where(c > 0, s_unclamped + (p_ - m) * 1.0, p_ * k0)
    return jnp.floor(total / p_)


def _fiss(i, pv):
    b = pv[_FISS_B]
    k0 = jnp.floor(pv[_N] / ((2.0 + b) * pv[_P]))
    cc = jnp.floor((2.0 * pv[_N] * (1.0 - b / (2.0 + b)))
                   / (pv[_P] * b * jnp.maximum(b - 1.0, 1.0)))
    return k0 + jnp.floor(i / pv[_P]) * cc


def _viss(i, pv):
    k0_real = pv[_N] / (pv[_VISS_X] * pv[_P])
    batch = jnp.floor(i / pv[_P])
    # 32 halving terms (2^32 bounds any K0), unrolled so each is an
    # elementwise op on i's own layout; 2.0**-j is an exact power of two.
    total = jnp.zeros_like(i)
    for j in range(32):
        total = total + jnp.where(batch >= j, jnp.floor(k0_real * 2.0 ** -j), 0.0)
    return total


def _i32(c: int) -> np.int32:
    """A uint32 constant as the int32 with the same bits."""
    return np.uint32(c).view(np.int32)


def _rnd_u01_u32(seed, i):
    """Counter-based uniform [0, 1) from a 32-bit mix of int32 (seed, i).

    int32 multiply wraps like uint32, and the shifts are logical, so every
    bit matches the uint32 formulation; the final uint32 -> f32 conversion is
    the exactly-representable ``hi * 2**16`` plus ``lo`` rounded once.
    """
    srl = jax.lax.shift_right_logical
    x = i * _i32(0x9E3779B9) ^ (seed * _i32(0x85EBCA6B) + _i32(0xC2B2AE35))
    x = (x ^ srl(x, 16)) * _i32(0x7FEB352D)
    x = (x ^ srl(x, 15)) * _i32(0x846CA68B)
    x = x ^ srl(x, 16)
    hi = srl(x, 16).astype(jnp.float32)
    lo = (x & 0xFFFF).astype(jnp.float32)
    return (hi * 65536.0 + lo) / jnp.float32(4294967296.0)


def _rnd(i, pv):
    hi = jnp.maximum(jnp.floor(pv[_N] / pv[_P]), 1.0)
    u = _rnd_u01_u32(pv[_SEED].astype(jnp.int32), i.astype(jnp.int32))
    return jnp.floor(u * hi) + 1.0


def _pls(i, pv):
    static_chunk = jnp.floor(pv[_N] * pv[_SWR] / pv[_P])
    n_dyn = pv[_N] - static_chunk * pv[_P]
    ratio = (pv[_P] - 1.0) / pv[_P]
    dyn = jnp.ceil(_pow_int(ratio, jnp.maximum(i - pv[_P], 0.0)) * (n_dyn / pv[_P]))
    return jnp.where(i < pv[_P], static_chunk, dyn)


_FNS = (_static, _ss, _fsc, _gss, _tap, _tss, _fac, _tfss, _fiss, _viss, _rnd, _pls)


def sizes_for_steps(tech_id, i, pv):
    """DCA chunk sizes for step indices ``i`` (float32) — pure function of i.

    tech_id may be a Python int (static dispatch, Pallas-friendly) or a traced
    scalar (lax.switch dispatch).
    """
    i = jnp.asarray(i, dtype=jnp.float32)
    if isinstance(tech_id, (int, np.integer)):
        raw = _FNS[int(tech_id)](i, pv)
    else:
        raw = jax.lax.switch(tech_id, list(_FNS), i, pv)
    return jnp.maximum(raw, pv[_MINK])


# ---------------------------------------------------------------------------
# Closed-form prefixes (cumulative iterations before step i) — f32 mirror of
# techniques.closed_form_prefix, consistent with the f32 sizes above:
# prefix(i) == sum_{j<i} clip(round(sizes_for_steps(j)), 1, N) in exact f32
# integer arithmetic wherever the true prefix is < N (and >= N beyond, where
# assignment clamps anyway).  This is what makes the Pallas chunk kernel's
# grid fully parallel and the SPMD round state derivable from the round
# number alone — see DESIGN.md Sec. 7.
# ---------------------------------------------------------------------------


def _mce(pv):
    """Effective lower size clamp (>=1), top-clipped at N."""
    return jnp.clip(jnp.maximum(pv[_MINK], 1.0), 1.0, pv[_N])


def _clipped_size(fn, j, pv):
    """The schedule's view of fn: round + clamp to [max(min_chunk,1), N]."""
    return jnp.clip(jnp.round(jnp.maximum(fn(j, pv), pv[_MINK])), 1.0, pv[_N])


def _tri(x):
    # x*(x-1)/2 with the product formed first: x*(x-1) is an exact even f32
    # integer up to 2**25, so the halving stays exact in the pre-drain range.
    return x * (x - 1.0) * 0.5


def _index_tile(n: int) -> jnp.ndarray:
    """f32 indices 0, 1, ... covering [0, n) as an (R, 128) tile, R % 8 == 0.

    Built from integer iotas in the TPU's (8, 128) vreg layout, so the
    bounded summations below lower inside a Pallas kernel as well as in XLA;
    entries >= n are padding that callers mask out.
    """
    rows = -(-max(n, 1) // 1024) * 8
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 1)
    return (r * 128 + c).astype(jnp.float32)


def _head_prefix(fn, i, pv, head_cap: int):
    """Bounded head summation + constant-mc tail (gss/tap/pls/rnd).

    Requires every step >= head_cap to have size == min chunk (callers pick
    head_cap from ``default_head_cap``; for rnd the cap must cover the whole
    evaluated step range).
    """
    i = jnp.asarray(i, dtype=jnp.float32)
    cap = float(max(head_cap, 1))
    js = _index_tile(int(cap))
    sz = _clipped_size(fn, js, pv)
    head = jnp.sum(sz * (js < jnp.minimum(i, cap)[..., None, None]), axis=(-2, -1))
    return head + jnp.maximum(i - cap, 0.0) * _mce(pv)


def _batched_prefix(fn, i, pv, bcap: int):
    """Prefix for batched techniques whose batch value saturates by bcap-1."""
    i = jnp.asarray(i, dtype=jnp.float32)
    p_ = pv[_P]
    bs = _index_tile(bcap)
    vb = _clipped_size(fn, bs * p_, pv)  # batch values (padding masked below)
    b = jnp.floor(i / p_)
    rr = i - b * p_
    bc = jnp.minimum(b, float(bcap - 1))
    cum = jnp.sum(vb * (bs < bc[..., None, None]), axis=(-2, -1))
    vcur = jnp.sum(vb * (bs == bc[..., None, None]), axis=(-2, -1))
    tail = (b - bc) * jnp.sum(vb * (bs == float(bcap - 1)), axis=(-2, -1))
    return p_ * (cum + tail) + rr * vcur


def _static_pfx(i, pv, head_cap):
    base = jnp.floor(pv[_N] / pv[_P])
    rem = pv[_N] - base * pv[_P]
    mce = _mce(pv)
    a = jnp.clip(jnp.maximum(base + 1.0, mce), 1.0, pv[_N])
    bsz = jnp.clip(jnp.maximum(base, mce), 1.0, pv[_N])
    ip = jnp.minimum(i, pv[_P])
    return (
        jnp.minimum(i, rem) * a
        + jnp.maximum(ip - rem, 0.0) * bsz
        + jnp.maximum(i - pv[_P], 0.0) * mce
    )


def _ss_pfx(i, pv, head_cap):
    return i * _mce(pv)


def _fsc_pfx(i, pv, head_cap):
    logp = jnp.log2(jnp.maximum(pv[_P], 2.0))
    k = (jnp.sqrt(2.0) * pv[_N] * pv[_H]) / (pv[_SIGMA] * pv[_P] * jnp.sqrt(logp) + 1e-30)
    k_eff = jnp.clip(jnp.maximum(jnp.floor(k), _mce(pv)), 1.0, pv[_N])
    return i * k_eff


def _tss_pfx(i, pv, head_cap):
    k0, c = _tss_consts(pv)
    mce = _mce(pv)
    safe_c = jnp.maximum(c, 1.0)
    m_full = jnp.maximum(jnp.ceil((k0 - mce) / safe_c), 0.0)
    m = jnp.minimum(i, m_full)
    # sum of the unclamped arithmetic head: m*k0 - c*m*(m-1)/2
    lin = m * k0 - c * _tri(m) + (i - m) * mce
    return jnp.where(c > 0, lin, i * jnp.clip(k0, mce, pv[_N]))


def _fiss_pfx(i, pv, head_cap):
    b_ = pv[_FISS_B]
    k0 = jnp.floor(pv[_N] / ((2.0 + b_) * pv[_P]))
    cc = jnp.floor((2.0 * pv[_N] * (1.0 - b_ / (2.0 + b_)))
                   / (pv[_P] * b_ * jnp.maximum(b_ - 1.0, 1.0)))
    mce = _mce(pv)
    p_ = pv[_P]
    B = jnp.floor(i / p_)
    rr = i - B * p_
    safe_cc = jnp.maximum(cc, 1.0)
    b_lo = jnp.maximum(jnp.ceil((mce - k0) / safe_cc), 0.0)  # value==mce below
    b_hi = jnp.maximum(jnp.ceil((pv[_N] - k0) / safe_cc), b_lo)  # value==N above
    u = jnp.clip(B, b_lo, b_hi)
    s_mid = (u - b_lo) * k0 + cc * (_tri(u) - _tri(b_lo))
    s = mce * jnp.minimum(B, b_lo) + s_mid + pv[_N] * jnp.maximum(B - b_hi, 0.0)
    v_cur = jnp.clip(k0 + B * cc, mce, pv[_N])
    lin = p_ * s + rr * v_cur
    return jnp.where(cc > 0, lin, i * jnp.clip(k0, mce, pv[_N]))


def _fac_pfx(i, pv, head_cap):
    return _batched_prefix(_fac, i, pv, 40)


def _tfss_pfx(i, pv, head_cap):
    return _batched_prefix(_tfss, i, pv, 16)


def _viss_pfx(i, pv, head_cap):
    return _batched_prefix(_viss, i, pv, 40)


def _gss_pfx(i, pv, head_cap):
    return _head_prefix(_gss, i, pv, head_cap)


def _tap_pfx(i, pv, head_cap):
    return _head_prefix(_tap, i, pv, head_cap)


def _pls_pfx(i, pv, head_cap):
    return _head_prefix(_pls, i, pv, head_cap)


def _rnd_pfx(i, pv, head_cap):
    return _head_prefix(_rnd, i, pv, head_cap)


_PFX_FNS = (_static_pfx, _ss_pfx, _fsc_pfx, _gss_pfx, _tap_pfx, _tss_pfx,
            _fac_pfx, _tfss_pfx, _fiss_pfx, _viss_pfx, _rnd_pfx, _pls_pfx)


def default_head_cap(technique: str, params: DLSParams, max_steps: int) -> int:
    """Static head length for ``prefix_for_steps``' bounded summations.

    For gss/tap the head covers the geometric decay down to the min chunk
    (plus a safety margin absorbing f32 rounding at the boundary); pls adds its
    P static chunks; rnd has no analytic bound, so its head must span every
    step the caller will evaluate.  Exact-series techniques return 1 (unused).
    """
    mce = max(params.min_chunk, 1)

    def _decay_len(a: float) -> int:
        if params.P <= 1 or a <= mce:
            return 2
        return int(math.ceil(math.log(a / mce) / math.log(params.P / (params.P - 1.0)))) + 64

    if technique in ("gss", "tap"):
        return min(_decay_len(params.N / params.P), max_steps)
    if technique == "pls":
        static_chunk = math.floor(params.N * params.swr / params.P)
        n_dyn = max(params.N - static_chunk * params.P, 1)
        return min(params.P + _decay_len(n_dyn / params.P), max_steps)
    if technique == "rnd":
        return max_steps
    return 1


def prefix_for_steps(tech_id, i, pv, head_cap: int = 4096):
    """Cumulative f32 chunk iterations before step ``i`` — no carried state.

    Mirrors ``techniques.closed_form_prefix`` with the same exactness
    contract, expressed against this module's f32 sizes: wherever the true
    prefix is < N the result equals the f32 cumsum of
    ``clip(round(sizes_for_steps(j)), 1, N)`` bit-exactly (all quantities stay
    integral below 2**24); past the drain point it is only guaranteed >= N.
    ``head_cap`` must come from ``default_head_cap`` for gss/tap/pls/rnd and
    must be a Python int (static shape).
    """
    i = jnp.asarray(i, dtype=jnp.float32)
    if isinstance(tech_id, (int, np.integer)):
        return _PFX_FNS[int(tech_id)](i, pv, head_cap)
    fns = [lambda i_, pv_, f=f: f(i_, pv_, head_cap) for f in _PFX_FNS]
    return jax.lax.switch(tech_id, fns, i, pv)
