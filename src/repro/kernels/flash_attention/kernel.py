"""Pallas TPU kernel: flash attention forward (online softmax, GQA, causal).

The LM-side compute hot-spot. Layout (B, H, S, hd); grid
(B, H, q_blocks, kv_blocks) with the kv axis innermost and sequential —
VMEM scratch carries the (bq, hd) f32 accumulator and the (bq,) running
max/sum across kv steps; the output block is written on the last kv step.
GQA is free: the K/V BlockSpec index maps query head h to kv head
h // group. Fully-masked causal blocks are skipped with pl.when (triangle
cost, like the pure-JAX pair-scan in models/attention.py — this kernel is
its TPU-production twin; the model keeps the scan on CPU/dry-run paths
because custom calls hide FLOPs from cost_analysis).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, n_kv_blocks: int, causal: bool,
                  scale: float) -> None:
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: the block is live iff its first kv position can be attended
    # by the block's last query position
    live = (kj * bk <= (qi + 1) * bq - 1) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kv_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= kv_pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == n_kv_blocks - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, bq: int = 128, bk: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, Sq, hd); k, v: (B, KV, Skv, hd); H % KV == 0."""
    b, h, sq, hd = q.shape
    _, n_kv, skv, _ = k.shape
    if h % n_kv:
        raise ValueError(f"H={h} must be a multiple of KV={n_kv}")
    group = h // n_kv
    bq, bk = min(bq, sq), min(bk, skv)
    if sq % bq or skv % bk:
        raise ValueError(f"seq ({sq},{skv}) must divide blocks ({bq},{bk})")
    nq, nkv = sq // bq, skv // bk
    scale = hd ** -0.5

    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, n_kv_blocks=nkv,
                               causal=causal, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b_, h_, i, j, g=group: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b_, h_, i, j, g=group: (b_, h_ // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
