"""Paged decode attention (pl.pallas_call + PrefetchScalarGridSpec).

Single-token decode over a *paged* KV cache: pages are the chunk unit the
cost-based cache manager (repro.serve.kvcache) places in HBM; the page table
is scalar-prefetched so the BlockSpec index_map can fetch each request's
pages from arbitrary HBM slots — the TPU analogue of the paper's
"coordinator tells every node which chunk replica to use".

Grid (B, MAX_PAGES). Online-softmax accumulators live in VMEM scratch and
are carried across the page axis; out is written on the last page visit.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(page_table_ref, seq_lens_ref,      # scalar prefetch
                  q_ref, k_ref, v_ref,               # VMEM blocks
                  o_ref,                             # output
                  acc_ref, m_ref, l_ref,             # VMEM scratch
                  *, page_size: int, rep: int, sm_scale: float,
                  max_pages: int):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * sm_scale       # (H, D)
    k = k_ref[0].astype(jnp.float32)                  # (PS, Hk, D)
    v = v_ref[0].astype(jnp.float32)
    h, d = q.shape
    hk = k.shape[1]
    qg = q.reshape(hk, rep, d)
    s = jnp.einsum("krd,pkd->krp", qg, k)             # (Hk, rep, PS)
    s = s.reshape(h, page_size)
    pos = p * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (h, page_size), 1)
    live = pos < seq_lens_ref[b]
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_ref[:, 0]
    l_prev = l_ref[:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    pexp = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + pexp.sum(axis=1)
    pv = jnp.einsum("krp,pkd->krd", pexp.reshape(hk, rep, page_size), v)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv.reshape(h, d)
    m_ref[:, 0] = m_new
    l_ref[:, 0] = l_new

    @pl.when(p == max_pages - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_table: jax.Array, seq_lens: jax.Array, *,
                    interpret: bool = True) -> jax.Array:
    """q: (B, H, D); k_pages/v_pages: (NP, PS, Hk, D);
    page_table: (B, MAXP) int32 page ids (entries past the live length may
    point anywhere valid — they are masked by seq_lens); seq_lens: (B,)."""
    b, h, d = q.shape
    np_, ps, hk, _ = k_pages.shape
    maxp = page_table.shape[1]
    rep = h // hk
    kernel = functools.partial(_paged_kernel, page_size=ps, rep=rep,
                               sm_scale=1.0 / math.sqrt(d), max_pages=maxp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxp),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda ib, ip, pt, sl: (ib, 0, 0)),
            pl.BlockSpec((1, ps, hk, d),
                         lambda ib, ip, pt, sl: (pt[ib, ip], 0, 0, 0)),
            pl.BlockSpec((1, ps, hk, d),
                         lambda ib, ip, pt, sl: (pt[ib, ip], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda ib, ip, pt, sl: (ib, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(page_table, seq_lens, q, k_pages, v_pages)
