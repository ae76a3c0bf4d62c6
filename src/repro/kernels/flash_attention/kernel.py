"""Pallas TPU kernel: blocked online-softmax attention (FlashAttention-style).

TPU-native design (not a CUDA port): the (q_block, kv_block) tiles are sized
for VMEM residency and the MXU's 128x128 systolic array; the kv dimension is
the innermost *sequential* grid axis carrying (m, l, acc) in VMEM scratch —
the TPU analogue of the SRAM-resident accumulators of the GPU kernel.

Supports: causal masking, sliding-window (Mixtral SWA), grouped-query heads
(GQA/MQA: q head h attends kv head h // group).  Fully-masked tiles are
skipped on the VPU/MXU (pl.when), which is what makes causal attention ~2x
and SWA ~S/window cheaper than dense.

Validated in interpret mode against ref.py; block sizes default to (128, 128)
=> q/k/v tiles of 128xD and a 128x128 score tile (MXU-aligned).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(
    q_ref, k_ref, v_ref,  # (1,1,bq,D), (1,1,bk,D), (1,1,bk,D)
    o_ref,  # (1,1,bq,D)
    m_scr, l_scr, acc_scr,  # VMEM scratch: (bq,128), (bq,128), (bq,D)
    *,
    sm_scale: float,
    causal: bool,
    window: int | None,
    block_q: int,
    block_k: int,
    num_kv_blocks: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # tile visibility: skip tiles that the causal/window mask kills entirely
    q_lo = qi * block_q
    q_hi = q_lo + block_q - 1
    k_lo = ki * block_k
    k_hi = k_lo + block_k - 1
    visible = True
    if causal:
        visible = jnp.logical_and(visible, k_lo <= q_hi)
    if window is not None:
        visible = jnp.logical_and(visible, k_hi >= q_lo - window + 1)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (bq, bk)

        if causal or window is not None:
            q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            mask = jnp.ones((block_q, block_k), dtype=jnp.bool_)
            if causal:
                mask = jnp.logical_and(mask, k_pos <= q_pos)
            if window is not None:
                mask = jnp.logical_and(mask, k_pos > q_pos - window)
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]  # (bq, 128) — lanes replicated
        m_tile = jnp.max(s, axis=1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_tile, m_prev.shape))
        corr = jnp.exp(m_prev[:, :1] - m_new[:, :1])  # (bq, 1)
        p = jnp.exp(s - m_new[:, :1])  # (bq, bk)
        l_new = corr * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = corr * acc_scr[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0, 0, :, :] = (acc_scr[...] / safe).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,  # [B, Hq, S, D]
    k: jnp.ndarray,  # [B, Hkv, S, D]
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    b, hq, s, d = q.shape
    _, hkv, sk, _ = k.shape
    assert s % block_q == 0 and sk % block_k == 0, (s, sk, block_q, block_k)
    assert hq % hkv == 0, f"GQA needs Hq % Hkv == 0, got {hq}, {hkv}"
    group = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    num_q = s // block_q
    num_kv = sk // block_k

    kernel = functools.partial(
        _attn_kernel,
        sm_scale=sm_scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        num_kv_blocks=num_kv,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, hq, num_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, qi, ki, g=group: (b_, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, qi, ki, g=group: (b_, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=f"flash_attn_c{int(causal)}_w{window or 0}",
    )(q, k, v)
