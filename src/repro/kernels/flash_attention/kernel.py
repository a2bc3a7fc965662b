"""Flash attention — Pallas TPU kernel (data-plane hot spot).

Adapted for the TPU memory hierarchy: the grid iterates (batch*head,
q-block, kv-block) with kv innermost so the online-softmax accumulators
(m, l, acc) live in VMEM scratch across the kv sweep.  Block shapes are MXU
aligned (q_block x d and kv_block x d tiles, d a multiple of 128 via
padding if needed).  VMEM budget per step: q_tile + k_tile + v_tile +
acc + (q_block x kv_block) logits ~= (2*bq*d + 2*bk*d + bq*bk) * 4 B —
with bq = bk = 512, d = 128 that's ~1.6 MB, leaving headroom for double
buffering.

Causality: kv-blocks strictly above the diagonal are masked per-element;
the index map still visits them (masked compute) — a production variant
would prune them from the grid (noted in EXPERIMENTS.md §Perf).

Validated shape/dtype-swept against ``ref.py`` in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _fa_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, causal, bq, bk, offset
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]  # [bq, d]
    k = k_ref[0]  # [bk, d]
    v = v_ref[0]
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [bq, bk]
    if causal:
        # align last query with last key (Sq may be < Sk: decode-style)
        qpos = qi * bq + offset + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        logits = jnp.where(qpos >= kpos, logits, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=1))
    p = jnp.exp(logits - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype),
        v,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal=True, bq=512, bk=512, interpret=None):
    """q: [B,Sq,H,dh]; k,v: [B,Sk,KV,dh] -> [B,Sq,H,dh].

    GQA is handled by folding the head-group repeat into the index map (no
    materialized k/v repeat)."""
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    rep = H // KV
    scale = dh**-0.5
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0

    # layout: fold batch & head into the leading grid axis
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, dh)

    grid = (B * H, Sq // bq, Sk // bk)

    def q_map(h, i, j):
        return (h, i, 0)

    def kv_map(h, i, j):
        return (h // rep, j, 0)

    out = pl.pallas_call(
        functools.partial(
            _fa_kernel,
            scale=scale,
            causal=causal,
            bq=bq,
            bk=bk,
            offset=Sk - Sq,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), q_map),
            pl.BlockSpec((1, bk, dh), kv_map),
            pl.BlockSpec((1, bk, dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, dh), q.dtype),
        scratch_shapes=[
            # (m, l, acc) accumulators persist across the kv sweep
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, dh).transpose(0, 2, 1, 3)
