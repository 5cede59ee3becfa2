"""Pallas TPU kernel: streaming (FlashAttention-style) fused attention.

The serving side of the framework spends most of its FLOPs in prefill
attention; this kernel keeps the O(Sq x Skv) score matrix out of HBM by
streaming KV blocks through VMEM with an online softmax (running max m,
normalizer l, accumulator acc), re-thought for the TPU memory hierarchy:

  * grid = (batch*heads, q_blocks, kv_blocks); the kv axis is the innermost,
    sequential ("arbitrary") dimension, so the fp32 scratch accumulators in
    VMEM persist across kv steps for one q block -- the TPU analogue of a CUDA
    thread block's registers/smem in FlashAttention-2.
  * every matmul operand is padded to MXU-aligned multiples of 128 lanes;
    blocks default to 128 x 128 so the q @ k^T and p @ v contractions map to
    full 128x128x128 MXU passes.
  * causal masking is applied blockwise; fully-masked kv blocks are skipped
    via `pl.when` on block indices (no wasted MXU work past the diagonal).
  * q is pre-scaled by 1/sqrt(d); logits stay in fp32 throughout (bf16 inputs,
    fp32 accumulation -- the usual numerics contract).

``q_offset`` places the q block in the kv timeline so the same kernel serves
prefill (offset 0) and single-step / chunked decode (offset = cache length).

Oracle: :func:`repro.kernels.ref.mha_ref`; wrapper: :func:`repro.kernels.ops.
flash_attention` (handles GQA head folding, padding, unpadding).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128
NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,
    m_scr, l_scr, acc_scr,
    *, block_q: int, block_kv: int, causal: bool, q_offset: int, kv_len: int,
):
    iq = pl.program_id(1)
    ikv = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q + q_offset          # first q position (global time)
    kv_start = ikv * block_kv

    def _body():
        q = q_ref[0].astype(jnp.float32)                  # [bq, d]
        k = k_ref[0].astype(jnp.float32)                  # [bkv, d]
        v = v_ref[0].astype(jnp.float32)                  # [bkv, d]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [bq, bkv]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
        kpos = kv_start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        mask = kpos < kv_len                               # kv padding
        if causal:
            mask &= kpos <= qpos
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_scr[...]                                # [bq, 1]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new
        l_scr[...] = l_new

    if causal:
        # Skip kv blocks strictly above the diagonal for this q block.
        last_q = q_start + block_q - 1
        pl.when(kv_start <= last_q)(_body)
    else:
        _body()

    @pl.when(ikv == n_kv - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "q_offset", "block_q", "block_kv", "interpret"),
)
def flash_attention_bhsd(
    q: jax.Array,            # [BH, Sq, D]   (heads folded into batch)
    k: jax.Array,            # [BH, Skv, D]
    v: jax.Array,            # [BH, Skv, D]
    causal: bool = True,
    q_offset: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool = False,
) -> jax.Array:
    bh, sq, d = q.shape
    skv = k.shape[1]
    scale = jnp.asarray(d, jnp.float32) ** -0.5
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)

    # Pad sequence dims to block multiples and head dim to 128 lanes.
    sq_p = -(-sq // block_q) * block_q
    skv_p = -(-skv // block_kv) * block_kv
    d_p = max(-(-d // 128) * 128, 128)
    qp = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, d_p - d)))
    kp = jnp.pad(k, ((0, 0), (0, skv_p - skv), (0, d_p - d)))
    vp = jnp.pad(v, ((0, 0), (0, skv_p - skv), (0, d_p - d)))

    grid = (bh, sq_p // block_q, skv_p // block_kv)
    kernel = functools.partial(
        _flash_kernel,
        block_q=block_q, block_kv=block_kv, causal=causal,
        q_offset=q_offset, kv_len=skv,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d_p), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d_p), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d_p), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d_p), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d_p), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d_p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(qp, kp, vp)
    return out[:, :sq, :d]
