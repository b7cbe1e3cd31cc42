"""Pallas TPU paged-attention decode kernel.

The Radiant mapping is structural here (DESIGN.md section 2):

  * the **block table is scalar-prefetched into SMEM**
    (``pltpu.PrefetchScalarGridSpec``) — the paper's BHi guarantee that the
    page-table levels feeding the walk live in the fastest tier.  The
    ``BlockSpec`` index maps *are* the page walk: they read the table in
    SMEM and direct the DMA engine at the right physical KV block in HBM;
  * KV blocks stream HBM -> VMEM one (block_size, head_dim) tile per grid
    step, flash-style running softmax in f32 VMEM scratch;
  * tiles are MXU/VPU-aligned: head_dim padded to a multiple of 128 by the
    ops wrapper, block_size a multiple of 8.

Layouts (kernel-native; ``ops.paged_attention`` adapts from memsys):
  q        [B, KH, G, Dh]      G = query heads per kv head (GQA group)
  k_pool   [KH, P, bs, Dh]     physical block pools
  v_pool   [KH, P, bs, Dh]
  tables   [B, NB] int32       physical block id per (seq, virtual block)
  lengths  [B] int32           valid tokens per sequence
  out      [B, KH, G, Dh]
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30


def _kernel(tables, lengths,            # scalar-prefetch refs (SMEM)
            q_ref, k_ref, v_ref,        # VMEM blocks
            o_ref,                      # VMEM output block
            m_ref, l_ref, acc_ref,      # VMEM scratch
            *, bs: int, nb: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(F32)                       # [G, Dh]
    k = k_ref[0, 0].astype(F32)                       # [bs, Dh]
    v = v_ref[0, 0].astype(F32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], F32))
    s = jnp.dot(q, k.T, preferred_element_type=F32) * scale   # [G, bs]

    # mask out positions beyond the sequence length in this block
    base = j * bs
    valid = (base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
             ) < lengths[b]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                               # [G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=F32)
    m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_attention_kernel(q, k_pool, v_pool, tables, lengths, *,
                           interpret: bool = False) -> jax.Array:
    """q [B,KH,G,Dh] x pools [KH,P,bs,Dh] -> [B,KH,G,Dh]."""
    B, KH, G, Dh = q.shape
    _, P, bs, _ = k_pool.shape
    NB = tables.shape[1]

    grid = (B, KH, NB)

    def q_map(b, h, j, tables, lengths):
        del j
        return (b, h, 0, 0)

    def kv_map(b, h, j, tables, lengths):
        # THE page walk: table lookup in SMEM chooses the physical block
        return (h, tables[b, j], 0, 0)

    def o_map(b, h, j, tables, lengths):
        del j
        return (b, h, 0, 0)

    kernel = functools.partial(_kernel, bs=bs, nb=NB)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, Dh), q_map),
                pl.BlockSpec((1, 1, bs, Dh), kv_map),
                pl.BlockSpec((1, 1, bs, Dh), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, G, Dh), o_map),
            scratch_shapes=[
                pltpu.VMEM((G, 1), F32),
                pltpu.VMEM((G, 1), F32),
                pltpu.VMEM((G, Dh), F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KH, G, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(tables, lengths, q, k_pool, v_pool)
