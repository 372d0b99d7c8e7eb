"""Pallas paged decode-attention kernel — GOP pages as KV-cache blocks.

This is the paper's storage idea (independently-decodable pages + a
temporal index) applied to the serving KV cache: KV lives in a global
page pool, each sequence owns a *block table* (the paper's non-clustered
temporal index) mapping logical positions to pages, and the decode
kernel walks that table with online softmax — so fragments cached /
evicted / deduplicated by LRU_VSS never need defragmentation copies.

Grid = (batch, kv_head, page). The block table and sequence lengths are
scalar-prefetched (SMEM) so the k/v BlockSpec index_maps can do the
data-dependent page lookup; accumulation state (m, l, acc) sits in VMEM
scratch across the page sweep, and the output tile is written once on
the final page ("arbitrary" semantics on the page axis).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(
    block_table_ref,  # (B, maxp) SMEM
    seq_lens_ref,  # (B,) SMEM
    q_ref,  # (1, 1, G, D)
    k_ref,  # (1, page, 1, D)
    v_ref,  # (1, page, 1, D)
    out_ref,  # (1, 1, G, D)
    m_ref,  # scratch (G, 1) f32
    l_ref,  # scratch (G, 1) f32
    acc_ref,  # scratch (G, D) f32
    *,
    scale: float,
    page: int,
):
    b = pl.program_id(0)
    i = pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = seq_lens_ref[b]
    page_id = block_table_ref[b, i]
    base = i * page

    @pl.when((base < seq_len) & (page_id >= 0))
    def _process():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
        k = k_ref[0, :, 0].astype(jnp.float32)  # (page, D)
        v = v_ref[0, :, 0].astype(jnp.float32)  # (page, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (G, page)
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < seq_len, s, NEG_INF)

        m_prev = m_ref[...]  # (G, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (G, page)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(i == n_pages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        out_ref[0, 0] = (acc_ref[...] / l).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention_pallas(
    q: jnp.ndarray,  # (B, Hq, D)
    k_pages: jnp.ndarray,  # (P, page, Hkv, D)
    v_pages: jnp.ndarray,  # (P, page, Hkv, D)
    block_table: jnp.ndarray,  # (B, maxp) int32 (-1 = absent)
    seq_lens: jnp.ndarray,  # (B,) int32
    *,
    scale: float | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    b, hq, d = q.shape
    p, page, hkv, _ = k_pages.shape
    maxp = block_table.shape[1]
    groups = hq // hkv
    if scale is None:
        scale = float(1.0 / (d ** 0.5))
    qg = q.reshape(b, hkv, groups, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, groups, d), lambda bi, hi, i, bt, sl: (bi, hi, 0, 0)),
            pl.BlockSpec(
                (1, page, 1, d),
                lambda bi, hi, i, bt, sl: (jnp.maximum(bt[bi, i], 0), 0, hi, 0),
            ),
            pl.BlockSpec(
                (1, page, 1, d),
                lambda bi, hi, i, bt, sl: (jnp.maximum(bt[bi, i], 0), 0, hi, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, groups, d), lambda bi, hi, i, bt, sl: (bi, hi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((groups, 1), jnp.float32),
            pltpu.VMEM((groups, 1), jnp.float32),
            pltpu.VMEM((groups, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, scale=scale, page=page),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, groups, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(block_table.astype(jnp.int32), seq_lens.astype(jnp.int32), qg,
      k_pages, v_pages)
    return out.reshape(b, hq, d)
