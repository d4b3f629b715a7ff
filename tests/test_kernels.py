"""Pallas kernel validation (interpret=True on CPU; TPU is the target):
shape/dtype sweep against the pure-jnp oracle in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.phantom_fused import phantom_fused_matmul
from repro.kernels.ref import phantom_fused_ref
from helpers import allclose, rand


@pytest.mark.parametrize("M,K,N,PK", [
    (128, 128, 128, 64),
    (256, 128, 128, 128),
    (128, 256, 384, 32),
    (512, 128, 256, 256),
    (128, 512, 128, 16),
])
def test_phantom_fused_shapes(M, K, N, PK):
    x = rand(0, (M, K), scale=0.3)
    L = rand(1, (K, N), scale=0.3)
    g = rand(2, (M, PK), scale=0.3)
    D = rand(3, (PK, N), scale=0.3)
    out = phantom_fused_matmul(x, L, g, D, interpret=True)
    ref = phantom_fused_ref(x, L, g, D)
    allclose(out, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_phantom_fused_dtypes(dtype):
    M, K, N, PK = 128, 128, 128, 64
    x = rand(4, (M, K), scale=0.3).astype(dtype)
    L = rand(5, (K, N), scale=0.3).astype(dtype)
    g = rand(6, (M, PK), scale=0.3).astype(dtype)
    D = rand(7, (PK, N), scale=0.3).astype(dtype)
    out = phantom_fused_matmul(x, L, g, D, interpret=True)
    ref = phantom_fused_ref(x, L, g, D)
    rtol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    allclose(out, ref, rtol=rtol, atol=rtol)
    assert out.dtype == dtype


@pytest.mark.parametrize("bm,bn,bk", [(64, 64, 64), (128, 128, 128),
                                      (32, 128, 64)])
def test_phantom_fused_block_shapes(bm, bn, bk):
    M, K, N, PK = 128, 128, 128, 32
    x = rand(8, (M, K), scale=0.3)
    L = rand(9, (K, N), scale=0.3)
    g = rand(10, (M, PK), scale=0.3)
    D = rand(11, (PK, N), scale=0.3)
    out = phantom_fused_matmul(x, L, g, D, bm=bm, bn=bn, bk=bk,
                               interpret=True)
    ref = phantom_fused_ref(x, L, g, D)
    allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_kernel_matches_phantom_layer_math():
    """The kernel computes exactly the per-rank phantom forward: local
    update + concatenated decompress (self-term already zeroed in D)."""
    M, n_in_loc, n_out_loc, p, k = 128, 128, 128, 4, 32
    x = rand(12, (M, n_in_loc), scale=0.3)
    L = rand(13, (n_in_loc, n_out_loc), scale=0.3)
    g_all = rand(14, (M, p * k), scale=0.3)
    D = rand(15, (p * k, n_out_loc), scale=0.3)
    out = phantom_fused_matmul(x, L, g_all, D, interpret=True)
    z = x @ L + g_all @ D
    allclose(out, z, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,S,H,KV,hd,causal", [
    (2, 128, 4, 4, 32, True),
    (1, 256, 8, 2, 32, True),
    (2, 128, 4, 1, 64, True),
    (1, 128, 4, 4, 32, False),
])
def test_flash_attention_kernel(B, S, H, KV, hd, causal):
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ref import flash_attention_ref
    q = rand(20, (B, S, H, hd), scale=0.5)
    k = rand(21, (B, S, KV, hd), scale=0.5)
    v = rand(22, (B, S, KV, hd), scale=0.5)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    allclose(out, ref, rtol=2e-3, atol=2e-4)


def test_flash_attention_bf16():
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ref import flash_attention_ref
    B, S, H, KV, hd = 1, 128, 4, 2, 32
    q = rand(23, (B, S, H, hd), scale=0.5).astype(jnp.bfloat16)
    k = rand(24, (B, S, KV, hd), scale=0.5).astype(jnp.bfloat16)
    v = rand(25, (B, S, KV, hd), scale=0.5).astype(jnp.bfloat16)
    out = flash_attention(q, k, v, interpret=True)
    ref = flash_attention_ref(q, k, v)
    allclose(out, ref, rtol=3e-2, atol=3e-2)
    assert out.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# shape handling: pad-and-slice to the tile grid (PR-10 bugfix #1) and the
# bpk-tiled ghost contraction (bugfix #2) — these shapes crashed the
# pre-fix kernel (bare AssertionError on M=192; full-PK ghost residency)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,K,N,PK", [
    (192, 128, 128, 64),       # M not a multiple of the 128 tile
    (192, 192, 192, 48),       # nothing divides 128
    (100, 72, 56, 24),         # small odd everything
    (130, 257, 129, 65),       # just past tile boundaries
    (128, 128, 300, 64),       # N padded
])
def test_phantom_fused_non_tile_multiple_shapes(M, K, N, PK):
    x = rand(30, (M, K), scale=0.3)
    L = rand(31, (K, N), scale=0.3)
    g = rand(32, (M, PK), scale=0.3)
    D = rand(33, (PK, N), scale=0.3)
    out = phantom_fused_matmul(x, L, g, D, interpret=True)
    ref = phantom_fused_ref(x, L, g, D)
    allclose(out, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("PK,bpk", [(512, 128), (384, 128), (1024, 64)])
def test_phantom_fused_ghost_tiled_over_bpk(PK, bpk):
    """Large p*k ghost widths stream through bpk-wide blocks instead of
    sitting in VMEM at full width (the pre-fix OOM footgun)."""
    from repro.kernels.phantom_fused import kernel_vmem_bytes
    M, K, N = 128, 128, 128
    x = rand(34, (M, K), scale=0.3)
    L = rand(35, (K, N), scale=0.3)
    g = rand(36, (M, PK), scale=0.2)
    D = rand(37, (PK, N), scale=0.2)
    out = phantom_fused_matmul(x, L, g, D, bpk=bpk, interpret=True)
    allclose(out, phantom_fused_ref(x, L, g, D), rtol=5e-4, atol=5e-4)
    # the working set is bounded by the tile config, not by PK
    assert (kernel_vmem_bytes(128, 128, 128, bpk, jnp.float32)
            < kernel_vmem_bytes(128, 128, 128, PK, jnp.float32))


def test_phantom_fused_typed_errors():
    from repro.kernels.phantom_fused import (KernelConfigError,
                                             VMEM_BUDGET_BYTES,
                                             check_kernel_fits)
    x = rand(38, (64, 64))
    L = rand(39, (64, 64))
    g = rand(40, (64, 32))
    with pytest.raises(KernelConfigError, match="D shape"):
        phantom_fused_matmul(x, L, g, jnp.zeros((8, 8)), interpret=True)
    with pytest.raises(KernelConfigError, match="L rows"):
        phantom_fused_matmul(x, jnp.zeros((32, 64)), g,
                             jnp.zeros((32, 64)), interpret=True)
    # tile working set past the VMEM budget is a typed error, not an OOM
    with pytest.raises(KernelConfigError, match="VMEM"):
        check_kernel_fits(2048, 2048, 2048, 2048, jnp.float32)
    assert check_kernel_fits(128, 128, 128, 128,
                             jnp.float32) < VMEM_BUDGET_BYTES


# ---------------------------------------------------------------------------
# fused backward kernels + the custom_vjp op (PR-10 tentpole)
# ---------------------------------------------------------------------------

def test_backward_kernels_match_transpose_math():
    from repro.kernels.phantom_fused import matmul_nt, matmul_tn
    a = rand(41, (96, 160), scale=0.3)
    b = rand(42, (72, 160), scale=0.3)
    allclose(matmul_nt(a, b, interpret=True), a @ b.T,
             rtol=2e-4, atol=2e-4)
    c = rand(43, (96, 112), scale=0.3)
    allclose(matmul_tn(a, c, interpret=True), a.T @ c,
             rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,K,N,k,p", [
    (128, 128, 128, 16, 4),
    (192, 96, 80, 8, 2),       # non-tile-multiple shapes through the vjp
    (64, 64, 64, 4, 8),
])
def test_phantom_fused_linear_grads(dtype, M, K, N, k, p):
    """custom_vjp fused backward vs jax.grad of the pure-jnp oracle,
    across dtype x shape x ghost width."""
    import jax
    from repro.kernels.ops import phantom_fused_linear
    PK = p * k
    x = rand(50, (M, K), scale=0.3).astype(dtype)
    L = rand(51, (K, N), scale=0.3).astype(dtype)
    g = rand(52, (M, PK), scale=0.3).astype(dtype)
    D = rand(53, (PK, N), scale=0.3).astype(dtype)

    def loss_kernel(x, L, g, D):
        return jnp.sum(jnp.square(
            phantom_fused_linear(x, L, g, D, interpret=True)))

    def loss_ref(x, L, g, D):
        return jnp.sum(jnp.square(phantom_fused_ref(x, L, g, D)))

    lk, gk = jax.value_and_grad(loss_kernel, argnums=(0, 1, 2, 3))(
        x, L, g, D)
    lr, gr = jax.value_and_grad(loss_ref, argnums=(0, 1, 2, 3))(
        x, L, g, D)
    tol = 6e-2 if dtype == jnp.bfloat16 else 2e-3
    allclose(lk, lr, rtol=tol, atol=tol)
    for name, a, b in zip(("dx", "dL", "dg", "dD"), gk, gr):
        assert a.dtype == dtype, name
        allclose(a, b, rtol=tol, atol=tol, msg=name)


def test_phantom_fused_linear_batch_dims():
    from repro.kernels.ops import phantom_fused_linear
    B, S, K, N, PK = 2, 24, 64, 48, 32
    x = rand(54, (B, S, K), scale=0.3)
    L = rand(55, (K, N), scale=0.3)
    g = rand(56, (B, S, PK), scale=0.3)
    D = rand(57, (PK, N), scale=0.3)
    out = phantom_fused_linear(x, L, g, D, interpret=True)
    assert out.shape == (B, S, N)
    ref = phantom_fused_ref(x.reshape(-1, K), L, g.reshape(-1, PK), D)
    allclose(out.reshape(-1, N), ref, rtol=2e-4, atol=2e-4)


def test_resolve_kernel_backend():
    import jax
    from repro.kernels.ops import resolve_kernel_backend
    assert resolve_kernel_backend("xla") == "xla"
    assert resolve_kernel_backend("pallas") == "pallas"
    expect = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert resolve_kernel_backend("auto") == expect
    with pytest.raises(ValueError, match="kernel_backend"):
        resolve_kernel_backend("cuda")


# ---------------------------------------------------------------------------
# trainer-level pin: the phantom FFN probe step (the trainer's schedule)
# must produce identical loss/grads under kernel_backend pallas vs xla
# ---------------------------------------------------------------------------

def _kernel_cfg(backend, n=128, L=2, k=8):
    from repro.configs.base import (ModelConfig, PhantomConfig,
                                    phantom_projection_map)
    return ModelConfig(name=f"kernel-pin-{backend}", family="ffn",
                       num_layers=L, d_model=n, ffn_width=n, ffn_depth=L,
                       mlp="relu", phantom=PhantomConfig(k=k),
                       projections=phantom_projection_map(
                           k, ffn_layer=True, kernel_backend=backend))


@pytest.mark.parametrize("meshname", ["mesh18", "mesh24"])
def test_ffn_step_pallas_matches_xla(meshname, request):
    import jax
    from repro.parallel.params import materialize
    from repro.telemetry.probe import make_ffn_probe_step
    mesh = request.getfixturevalue(meshname)
    batch = 16
    results = {}
    for backend in ("xla", "pallas"):
        cfg = _kernel_cfg(backend)
        fn, decls = make_ffn_probe_step(cfg, mesh, batch)
        params = materialize(decls, seed=5)
        x = rand(60, (batch, cfg.ffn_width), scale=0.5)
        y = rand(61, (batch, cfg.ffn_width), scale=0.5)
        loss, (gp, gx) = fn(params, x, y)
        results[backend] = (loss, gp, gx)
    lx, gpx, gxx = results["xla"]
    lp, gpp, gxp = results["pallas"]
    allclose(lx, lp, rtol=1e-5, atol=1e-6)
    leaves_x = jax.tree_util.tree_leaves_with_path(gpx)
    leaves_p = jax.tree_util.tree_leaves_with_path(gpp)
    assert [k for k, _ in leaves_x] == [k for k, _ in leaves_p]
    for (path, a), (_, b) in zip(leaves_x, leaves_p):
        allclose(a, b, rtol=1e-4, atol=1e-5,
                 msg=f"param grad {jax.tree_util.keystr(path)}")
    allclose(gxx, gxp, rtol=1e-4, atol=1e-5, msg="input grad")


# ---------------------------------------------------------------------------
# plumbing: comm/compute overlap XLA flags, config + planner backend knobs
# ---------------------------------------------------------------------------

def test_comm_overlap_flags():
    from repro.parallel.compat import (COMM_OVERLAP_FLAGS,
                                       comm_overlap_flags,
                                       enable_comm_overlap)
    assert "--xla_gpu_enable_latency_hiding_scheduler=true" in \
        comm_overlap_flags("gpu")
    assert "--xla_tpu_enable_latency_hiding_scheduler=true" in \
        comm_overlap_flags("tpu")
    assert "async" in comm_overlap_flags("tpu")
    # cpu XLA rejects the accelerator flags -> the cpu entry MUST be empty
    assert comm_overlap_flags("cpu") == ""
    with pytest.raises(ValueError, match="platform"):
        comm_overlap_flags("rocm")
    assert set(COMM_OVERLAP_FLAGS) == {"cpu", "gpu", "tpu"}

    import os
    saved = {v: os.environ.get(v) for v in ("XLA_FLAGS", "LIBTPU_INIT_ARGS")}
    try:
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ.pop("LIBTPU_INIT_ARGS", None)
        applied = enable_comm_overlap("gpu")
        assert applied == comm_overlap_flags("gpu")
        first = os.environ["XLA_FLAGS"]
        assert "--xla_gpu_enable_async_collectives=true" in first
        assert "device_count=8" in first          # existing flags kept
        assert enable_comm_overlap("gpu") == ""   # idempotent: no re-add
        assert os.environ["XLA_FLAGS"] == first
        assert enable_comm_overlap("cpu") == ""   # cpu is a no-op
        # the TPU recipe goes to libtpu: XLA_FLAGS aborts on those flags
        assert enable_comm_overlap("tpu") == comm_overlap_flags("tpu")
        assert os.environ["LIBTPU_INIT_ARGS"] == comm_overlap_flags("tpu")
        assert os.environ["XLA_FLAGS"] == first
        assert enable_comm_overlap("tpu") == ""   # idempotent: no re-add
        assert os.environ["LIBTPU_INIT_ARGS"] == comm_overlap_flags("tpu")
    finally:
        for var, val in saved.items():
            if val is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = val


def test_with_kernel_backend_config():
    from repro.configs.base import with_kernel_backend
    cfg = _kernel_cfg("xla")
    out = with_kernel_backend(cfg, "pallas")
    assert out.projections.ffn_layer.kernel_backend == "pallas"
    assert out.phantom.kernel_backend == "pallas"
    # entries that were None stay None (must NOT materialize a tensor
    # default — that would shadow the legacy ffn_impl shim)
    assert out.projections.attn_q is None
    assert cfg.projections.ffn_layer.kernel_backend == "xla"  # no mutation


def test_enumerate_plans_kernel_backends():
    from repro.planner.space import enumerate_plans
    plans = enumerate_plans(8, width=256, depth=2, batch=32,
                            ks=(8,), pps=(1,),
                            kernel_backends=("xla", "pallas"))
    phantom = [c for c in plans if c.strategy == "phantom"]
    tensor = [c for c in plans if c.strategy != "phantom"]
    assert {c.kernel_backend for c in phantom} == {"xla", "pallas"}
    # non-phantom candidates don't fan out over backends
    assert {c.kernel_backend for c in tensor} == {"xla"}
    pal = next(c for c in phantom if c.kernel_backend == "pallas")
    assert pal.name.endswith("_pallas")
    assert pal.spec().kernel_backend == "pallas"
