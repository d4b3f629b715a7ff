"""Pallas TPU kernels: fused phantom-layer forward and backward.

Forward:   z  = x @ L  +  g_cat @ D_cat
Backward:  [dx | dg] = dz @ [L ; D]^T          (one fused dgrad kernel)
           [dL ; dD] = [x | g]^T @ dz          (one fused wgrad kernel)

i.e. the per-rank phantom update (local diagonal block + concatenated
ghost decompression, DESIGN.md §2) as ONE kernel per pass so the small
decompress GEMM shares the output tile residency of the local GEMM
instead of issuing a second pass over HBM.  This is the op the paper
identifies as the performance cliff at large p (the "flip-flop"):
(p-1) skinny GEMMs die on GPU; on TPU we concatenate them and fuse with
the local update.

Tiling: the forward grid is (M/bm, N/bn, nk + npk) — one arbitrary-order
contraction axis that first walks the x@L blocks (nk steps of width bk),
then the ghost blocks (npk steps of width bpk), all into the same fp32
VMEM accumulator; the output tile is written once on the last step.  The
ghost operand is therefore tiled like any other contraction (never
resident at full p*k width), and every dimension is padded up to its
tile multiple with zeros (exact for a matmul) and sliced back, so
non-multiple-of-128 shapes are legal.  MXU-aligned tile defaults
(128x128x128).

TARGET is TPU (compiled via pl.pallas_call + BlockSpec).  The kernels
are compile-tested for TPU v5e at the paper-ffn-16k per-rank shapes
(tests/test_tpu_compile.py) and run compiled on the chip by
``chip_smoke.py``; CPU tests run them interpret=True against ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Per-core VMEM on current TPU generations (v4/v5e/v5p ~= 16 MiB); tile
# configs whose working set exceeds this cannot be scheduled on-chip.
VMEM_BUDGET_BYTES = 16 * 1024 * 1024


class KernelConfigError(ValueError):
    """A kernel shape/tile configuration that cannot run: mismatched
    operand shapes or a tile working set over the VMEM budget.  Callers
    that can should fall back to the XLA path (kernel_backend="xla")."""


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad2(a, rows: int, cols: int):
    """Zero-pad a 2-D operand up to the tile grid (zeros contribute
    exactly 0 to the accumulation; the caller slices the result back)."""
    r, c = a.shape
    if (r, c) == (rows, cols):
        return a
    return jnp.pad(a, ((0, rows - r), (0, cols - c)))


def kernel_vmem_bytes(bm: int, bn: int, bk: int, bpk: int, dtype,
                      acc_dtype=jnp.float32) -> int:
    """Worst-case VMEM residency of one fused-forward grid step: the four
    operand blocks double-buffered, plus the output tile and the fp32
    accumulator scratch.  Shared with ``analysis/rules.py`` so the audit
    can statically assert the bound for any planned tile config."""
    ib = jnp.dtype(dtype).itemsize
    operands = (bm * bk + bk * bn + bm * bpk + bpk * bn) * ib
    tile = bm * bn * (ib + jnp.dtype(acc_dtype).itemsize)
    return 2 * operands + tile


def check_kernel_fits(bm: int, bn: int, bk: int, bpk: int, dtype,
                      budget: int = VMEM_BUDGET_BYTES) -> int:
    need = kernel_vmem_bytes(bm, bn, bk, bpk, dtype)
    if need > budget:
        raise KernelConfigError(
            f"fused-kernel tiles bm={bm} bn={bn} bk={bk} bpk={bpk} "
            f"({jnp.dtype(dtype).name}) need ~{need} B VMEM, over the "
            f"{budget} B budget; shrink the tiles or fall back to "
            f"kernel_backend='xla'")
    return need


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, l_ref, g_ref, d_ref, o_ref, acc_ref, *, nk: int,
                npk: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kk < nk)
    def _local():
        acc_ref[...] += jnp.dot(x_ref[...], l_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(kk >= nk)
    def _ghost():
        acc_ref[...] += jnp.dot(g_ref[...], d_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(kk == nk + npk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "bpk", "interpret"))
def phantom_fused_matmul(x, L, g, D, *, bm: int = 128, bn: int = 128,
                         bk: int = 128, bpk: int = 128,
                         interpret: bool = False):
    """z = x @ L + g @ D.

    x [M, K]   local activation shard      (K = n_in / p)
    L [K, N]   local diagonal block        (N = n_out / p)
    g [M, PK]  gathered ghosts             (PK = p * k)
    D [PK, N]  concatenated decompressors
    -> z [M, N]

    Any shape is accepted (padded to the tile grid and sliced back); the
    ghost contraction is tiled over ``bpk`` so large p*k never exceeds
    the VMEM budget.
    """
    M, K = x.shape
    PK = g.shape[1]
    if L.shape[0] != K:
        raise KernelConfigError(
            f"L rows {L.shape[0]} != x contraction dim {K}")
    N = L.shape[1]
    if tuple(D.shape) != (PK, N):
        raise KernelConfigError(
            f"D shape {tuple(D.shape)} != ghost-width x n_out ({PK}, {N})")
    if g.shape[0] != M:
        raise KernelConfigError(f"g rows {g.shape[0]} != x rows {M}")

    bm_, bn_ = min(bm, M), min(bn, N)
    bk_, bpk_ = min(bk, K), min(bpk, PK)
    check_kernel_fits(bm_, bn_, bk_, bpk_, x.dtype)

    Mp, Np = _round_up(M, bm_), _round_up(N, bn_)
    Kp, PKp = _round_up(K, bk_), _round_up(PK, bpk_)
    x = _pad2(x, Mp, Kp)
    L = _pad2(L, Kp, Np)
    g = _pad2(g, Mp, PKp)
    D = _pad2(D, PKp, Np)
    nk, npk = Kp // bk_, PKp // bpk_

    out = pl.pallas_call(
        functools.partial(_fwd_kernel, nk=nk, npk=npk),
        grid=(Mp // bm_, Np // bn_, nk + npk),
        in_specs=[
            # steps < nk walk the local contraction; later steps pin to
            # the last local block (unread — @pl.when gates the math)
            pl.BlockSpec((bm_, bk_),
                         lambda i, j, kk: (i, jnp.minimum(kk, nk - 1))),
            pl.BlockSpec((bk_, bn_),
                         lambda i, j, kk: (jnp.minimum(kk, nk - 1), j)),
            # steps >= nk walk the ghost contraction bpk at a time
            pl.BlockSpec((bm_, bpk_),
                         lambda i, j, kk: (i, jnp.clip(kk - nk, 0,
                                                       npk - 1))),
            pl.BlockSpec((bpk_, bn_),
                         lambda i, j, kk: (jnp.clip(kk - nk, 0, npk - 1),
                                           j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, L, g, D)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# backward (two generic tiled GEMMs with the forward's accumulator pattern)
# ---------------------------------------------------------------------------

def _acc_kernel(a_ref, b_ref, o_ref, acc_ref, *, nsteps: int, dims):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (dims, ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == nsteps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul_nt(a, b, *, bm: int = 128, bn: int = 128, bk: int = 128,
              interpret: bool = False):
    """c[M, J] = a[M, N] @ b[J, N]^T, fp32 accumulation (the dgrad shape:
    b rows are the stacked [L ; D] weight, c columns split into dx|dg)."""
    M, N = a.shape
    J, N2 = b.shape
    if N2 != N:
        raise KernelConfigError(f"b cols {N2} != a cols {N}")
    bm_, bn_, bk_ = min(bm, M), min(bn, J), min(bk, N)
    check_kernel_fits(bm_, bn_, bk_, 0, a.dtype)
    Mp, Jp, Np = _round_up(M, bm_), _round_up(J, bn_), _round_up(N, bk_)
    a = _pad2(a, Mp, Np)
    b = _pad2(b, Jp, Np)
    nsteps = Np // bk_

    out = pl.pallas_call(
        functools.partial(_acc_kernel, nsteps=nsteps, dims=((1,), (1,))),
        grid=(Mp // bm_, Jp // bn_, nsteps),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn_, bk_), lambda i, j, kk: (j, kk)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Jp), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return out[:M, :J]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul_tn(a, b, *, bm: int = 128, bn: int = 128, bk: int = 128,
              interpret: bool = False):
    """c[I, N] = a[M, I]^T @ b[M, N], fp32 accumulation (the wgrad shape:
    a columns are the stacked [x | g] activations, c rows split dL;dD)."""
    M, I = a.shape
    M2, N = b.shape
    if M2 != M:
        raise KernelConfigError(f"b rows {M2} != a rows {M}")
    bm_, bn_, bk_ = min(bm, I), min(bn, N), min(bk, M)
    check_kernel_fits(bm_, bn_, bk_, 0, a.dtype)
    Ip, Np, Mp = _round_up(I, bm_), _round_up(N, bn_), _round_up(M, bk_)
    a = _pad2(a, Mp, Ip)
    b = _pad2(b, Mp, Np)
    nsteps = Mp // bk_

    out = pl.pallas_call(
        functools.partial(_acc_kernel, nsteps=nsteps, dims=((0,), (0,))),
        grid=(Ip // bm_, Np // bn_, nsteps),
        in_specs=[
            pl.BlockSpec((bk_, bm_), lambda i, j, kk: (kk, i)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Ip, Np), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return out[:I, :N]


def phantom_fused_dgrad(dz, L, D, *, interpret: bool = False):
    """dx [M, K], dg [M, PK] = dz @ [L ; D]^T as ONE fused kernel call —
    the input and ghost gradients share the dz tile residency."""
    K = L.shape[0]
    W = jnp.concatenate([L, D], axis=0)          # [K + PK, N]
    din = matmul_nt(dz, W, interpret=interpret)  # [M, K + PK]
    return din[:, :K], din[:, K:]


def phantom_fused_wgrad(x, g, dz, *, interpret: bool = False):
    """dL [K, N], dD [PK, N] = [x | g]^T @ dz as ONE fused kernel call —
    both weight gradients share the dz tile residency."""
    K = x.shape[1]
    A = jnp.concatenate([x, g], axis=1)          # [M, K + PK]
    dW = matmul_tn(A, dz, interpret=interpret)   # [K + PK, N]
    return dW[:K], dW[K:]
