"""The main path's Pallas kernels and a whole phantom p=4 Pallas train
step, compiled for a described TPU v5e:2x2 topology (no chip needed).

Interpret-mode tests cannot see what the chip's compiler refuses (an
in-kernel reshape, a tile over the VMEM budget); these compiles can.
The topology is described only inside the module-scoped fixture, never
at import: a described topology loads libtpu, which one process at a
time may hold.  The persistent compilation cache is off around these
tests, because a compile for a described chip cannot be read back.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels.flash_attention import flash_attention
from repro.kernels.phantom_fused import (matmul_nt, matmul_tn,
                                         phantom_fused_matmul)

# paper-ffn-16k (n=16384, k=16) per-rank shapes at p=4
M, K, N, PK = 256, 16384 // 4, 16384 // 4, 4 * 16
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", saved)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("kernel", ["fused_fwd", "dgrad_nt", "wgrad_tn"])
def test_phantom_kernels_compile(one_chip, kernel, dtype):
    s = lambda *shape: _sds(shape, dtype, one_chip)  # noqa: E731
    if kernel == "fused_fwd":
        txt = _compiled_text(phantom_fused_matmul, s(M, K), s(K, N),
                             s(M, PK), s(PK, N))
    elif kernel == "dgrad_nt":     # dz [M, N] @ [L ; D]^T
        txt = _compiled_text(matmul_nt, s(M, N), s(K + PK, N))
    else:                          # [x | g]^T @ dz
        txt = _compiled_text(matmul_tn, s(M, K + PK), s(M, N))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_flash_attention_gqa_compiles(one_chip, dtype):
    """chatglm3-6b on one chip of four: 8 query heads share 2 KV heads
    (Hg=4 rows folded per token) — the case Mosaic refused while the
    causal mask reshaped an iota inside the kernel."""
    q = _sds((1, 2048, 8, 128), dtype, one_chip)
    kv = _sds((1, 2048, 2, 128), dtype, one_chip)
    txt = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True), q, kv, kv)
    assert "tpu_custom_call" in txt


def _compile_ffn_step(topo, cfg, chips, batch):
    """The paper-FFN train step with AdamW, compiled for a 1 x ``chips``
    mesh of the described chips; returns it and its parameters' shapes
    on one device."""
    from repro.core.ffn import abstract_ffn, ffn_decls, make_ffn_train_step
    from repro.optim import AdamW
    from repro.parallel.axes import MeshAxes, resolve_spec
    from repro.parallel.params import specs

    mesh = Mesh(np.array(topo.devices[:chips]).reshape(1, chips),
                ("data", "model"))
    opt = AdamW(1e-5, weight_decay=0.0)
    step, decls, opt_decls = make_ffn_train_step(cfg, mesh, opt, batch)

    axes = MeshAxes.from_mesh(mesh)

    def place(abstract_tree, decl_tree):
        return jax.tree.map(
            lambda a, s: _sds(a.shape, a.dtype,
                              NamedSharding(mesh, resolve_spec(s, axes))),
            abstract_tree, specs(decl_tree))

    a_params, a_opt = abstract_ffn(cfg, mesh, opt)
    params = place(a_params, ffn_decls(cfg, axes))
    xs = _sds((batch, cfg.ffn_width), jnp.float32,
              NamedSharding(mesh, resolve_spec(P("dp", "tp"), axes)))
    compiled = step.lower(
        params, place(a_opt, opt_decls),
        _sds((), jnp.int32, NamedSharding(mesh, P())), xs, xs).compile()
    local = [tuple(a.sharding.shard_shape(a.shape))
             for a in jax.tree.leaves(params)]
    return compiled, local


def test_phantom_pallas_train_step_compiles(topo, monkeypatch):
    """One paper-ffn-16k phantom p=4 train step with the fused Pallas
    kernels, on a 1x4 mesh of the described chips."""
    import repro.kernels.ops as ops
    from repro.configs.base import with_kernel_backend
    from repro.configs.paper_ffn import config

    # the process's backend is the CPU, so the kernels would default to
    # the interpreter; this compile is for the chip
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    cfg = with_kernel_backend(config("paper-ffn-16k"), "pallas")
    compiled, _ = _compile_ffn_step(topo, cfg, 4, M)
    assert "tpu_custom_call" in compiled.as_text()
    # fits one v5e chip's 16 GB with room to spare (~0.9 GiB per device)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 4 * 2**30


def _entry(hlo: str):
    """The instructions of the module's entry computation."""
    lines = hlo.splitlines()
    start = next(i for i, line in enumerate(lines)
                 if line.startswith("ENTRY "))
    end = next(i for i in range(start, len(lines)) if lines[i] == "}")
    return lines[start + 1:end]


def _shape(instr: str):
    """The dimensions of an instruction's (array) result."""
    m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]", instr)
    return tuple(int(d) for d in m.group(1).split(",") if d) if m else None


@pytest.mark.parametrize("plan,chips,temp_limit", [
    ("tensor", 1, 2.5 * 2**30),
    ("phantom", 4, 128 * 2**20),
])
def test_ffn_train_step_runs_its_layers_unrolled(topo, plan, chips,
                                                 temp_limit):
    """The paper-ffn-16k train step (batch 256, the XLA path) compiled
    for the chip keeps no whole-stack work: no cast of the stacked
    weights to bf16 and no zero fill of a stacked gradient in the entry
    computation, no layer loop, and the dense step's five products (two
    forward, two weight and one input gradient; the first layer's input
    gradient is dead)."""
    from repro.configs.base import dense_projection_map, with_kernel_backend
    from repro.configs.paper_ffn import config

    cfg = with_kernel_backend(config("paper-ffn-16k"), "xla")
    if plan == "tensor":
        cfg = cfg.replace(projections=dense_projection_map())
    compiled, stacked = _compile_ffn_step(topo, cfg, chips, M)
    assert all(s[0] == cfg.num_layers == 2 for s in stacked)
    hlo = compiled.as_text()
    whole_stack = [i.strip()[:120] for i in _entry(hlo)
                   if re.search(r" (convert|broadcast)\(", i)
                   and _shape(i) in stacked]
    assert whole_stack == []
    assert " while(" not in hlo
    if plan == "tensor":
        assert len(re.findall(r" convolution\(", hlo)) == 5
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit
