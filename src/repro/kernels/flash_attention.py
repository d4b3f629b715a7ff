"""Pallas TPU kernel: causal flash attention (forward).

The §Perf analysis (EXPERIMENTS.md cells A/C) shows the pure-XLA
blockwise attention pays HBM traffic for score blocks and online-softmax
accumulator rewrites — traffic a fused kernel keeps entirely in VMEM.
This kernel is that fix for TPU: grid over (batch*kv_head, q block), an
inner loop over kv blocks with the running (m, l, acc) carried in VMEM
scratch; only q/k/v reads and the final output write touch HBM.

GQA is handled by folding query heads of one kv group into the q block's
row dimension (rows = q_heads_per_group * block_q tokens).

TARGET is TPU (pl.pallas_call + BlockSpec): compile-tested for TPU v5e
(tests/test_tpu_compile.py) and run compiled on the chip by
``chip_smoke.py``; CPU tests run it interpret=True against kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.phantom_fused import KernelConfigError

NEG_INF = -1e30


def flash_attention_supported(s_q: int, s_kv: int, n_heads: int,
                              n_kv: int, *, block: int = 128) -> bool:
    """Static conditions under which this kernel can replace the XLA
    blockwise core: equal self-attention lengths that tile evenly, and
    GQA-divisible head counts.  ``models/attention.py`` consults this to
    fall back to XLA instead of tripping the shape check."""
    if s_q != s_kv or n_kv <= 0 or n_heads % n_kv:
        return False
    bq = min(block, s_q)
    return s_q % bq == 0


def _kernel(q_ref, k_ref, v_ref, o_ref, *, block_q: int, block_k: int,
            seq_len: int, causal: bool, scale: float):
    # q_ref: [block_q, Hg, hd]; k_ref/v_ref: [seq, hd]; o_ref like q_ref
    iq = pl.program_id(1)
    bq, hg, hd = q_ref.shape
    q = q_ref[...].astype(jnp.float32).reshape(bq * hg, hd)

    m0 = jnp.full((bq * hg,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq * hg,), jnp.float32)
    a0 = jnp.zeros((bq * hg, hd), jnp.float32)

    nk = seq_len // block_k

    def body(ik, carry):
        m, l, acc = carry
        ks = k_ref[pl.ds(ik * block_k, block_k), :].astype(jnp.float32)
        vs = v_ref[pl.ds(ik * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                  # [bq*hg, block_k]
        if causal:
            # row r of the folded block is token iq*block_q + r // hg, so
            # k_pos <= q_pos  <=>  (k_pos - iq*block_q) * hg <= r.  2-D
            # iotas keep the mask free of in-kernel reshapes, which Mosaic
            # refuses for GQA (hg > 1)
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            k_rel = ik * block_k - iq * block_q + cols
            s = jnp.where(k_rel * hg <= rows, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        acc = acc * corr[:, None] + jax.lax.dot_general(
            p, vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        l = l * corr + jnp.sum(p, axis=-1)
        return m_new, l, acc

    if causal:
        # only kv blocks at or before this q block contribute
        nk_eff = jnp.minimum(nk, (iq + 1) * block_q // block_k
                             + (1 if block_q % block_k else 0))
    else:
        nk_eff = nk
    m, l, acc = jax.lax.fori_loop(0, nk_eff, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[...] = out.reshape(bq, hg, hd).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q [B, S, H, hd]; k, v [B, S, KV, hd] -> [B, S, H, hd].

    H % KV == 0 (GQA).  S % block == 0 (pad upstream).
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    if H % KV:
        raise KernelConfigError(f"q heads {H} not divisible by kv heads "
                                f"{KV} (GQA grouping)")
    Hg = H // KV
    bq = min(block_q, S)
    bk = min(block_k, S)
    if S % bq or S % bk:
        raise KernelConfigError(
            f"seq len {S} does not tile into blocks ({bq}, {bk}); pad "
            f"upstream or check flash_attention_supported() first")
    scale = hd ** -0.5

    # [B, S, KV, Hg, hd] -> grid (B*KV, S/bq)
    qg = q.reshape(B, S, KV, Hg, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B * KV, S, Hg, hd)
    kg = k.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vg = v.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)

    out = pl.pallas_call(
        functools.partial(_kernel, block_q=bq, block_k=bk, seq_len=S,
                          causal=causal, scale=scale),
        grid=(B * KV, S // bq),
        in_specs=[
            pl.BlockSpec((None, bq, Hg, hd),
                         lambda b, i: (b, i, 0, 0)),          # q block
            pl.BlockSpec((None, S, hd), lambda b, i: (b, 0, 0)),  # k full
            pl.BlockSpec((None, S, hd), lambda b, i: (b, 0, 0)),  # v full
        ],
        out_specs=pl.BlockSpec((None, bq, Hg, hd),
                               lambda b, i: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, S, Hg, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qg, kg, vg)

    return out.reshape(B, KV, S, Hg, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, S, H, hd)
