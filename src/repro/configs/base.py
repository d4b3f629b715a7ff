"""Config system for the phantom-parallelism framework.

Plain dataclasses (no external deps). A ``ModelConfig`` fully describes one
architecture; ``ShapeConfig`` describes one (seq_len, global_batch, kind)
cell; ``RunConfig`` binds the two to a mesh and training hyper-params.

Every assigned architecture lives in ``src/repro/configs/<id>.py`` and
exposes ``config()`` (the exact published geometry) and ``smoke_config()``
(a reduced same-family geometry for CPU tests).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional


# ---------------------------------------------------------------------------
# sub-configs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    # apply MoE on layers where (layer_idx % every_n) == offset
    every_n: int = 1
    offset: int = 0
    # "expert": shard the expert dim over the model axis (needs E % tp == 0)
    # "tensor": shard each expert's d_ff over the model axis
    partition: str = "expert"
    capacity_factor: float = 1.25
    router_jitter: float = 0.0


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk: int = 128          # SSD chunk length
    ngroups: int = 1


@dataclass(frozen=True)
class PhantomConfig:
    """The paper's technique — knobs for where/how it is applied.

    DEPRECATED selection surface: ``apply_ffn``/``apply_attn_proj`` (and
    ``ModelConfig.ffn_impl``) are legacy shims that expand to per-site
    ``ProjectionSpec`` entries via ``ModelConfig.projection_spec()``.  New
    code should set ``ModelConfig.projections`` directly.
    """
    k: int = 64                     # ghost neurons per phantom layer
    apply_ffn: bool = True          # factorize the MLP projections
    apply_attn_proj: bool = False   # factorize QKV/O projections (beyond-paper)
    include_self_term: bool = False # False = faithful (self block excluded)
    variant: str = "fused"          # "faithful" | "fused" | "ring"
    kernel_backend: str = "xla"     # "xla" | "pallas" | "auto" (fused only)
    # faithful: per-source decompress GEMMs + custom_vjp AllGather (paper Alg. 1)
    # fused:    single concatenated decompress GEMM (TPU/MXU adaptation)
    # ring:     ppermute ring with overlapped partial decompress GEMMs


@dataclass(frozen=True)
class PipelineConfig:
    """Layer-to-stage partitioning for pipeline-parallel (pp) training.

    ``stages`` is a MODEL property (how the layer stack is cut), the mesh's
    ``pipe`` axis is the resource it maps onto: a config with S stages runs
    1F1B on a pp=S mesh, or sequentially (stage by stage, per microbatch)
    on a pp=1 mesh — both compute the identical function, which is what
    the equivalence suite pins.  ``stage_specs`` optionally gives each
    stage its own ``ProjectionSpec`` (tensor or phantom per stage, the
    paper-FFN subject); empty means every stage uses the site's spec.
    """
    stages: int = 1
    stage_specs: tuple = ()          # per-stage ProjectionSpec overrides

    def __post_init__(self):
        if self.stages < 1:
            raise ValueError(f"pipeline stages must be >= 1, "
                             f"got {self.stages}")
        if self.stage_specs and len(self.stage_specs) != self.stages:
            raise ValueError(
                f"stage_specs has {len(self.stage_specs)} entries for "
                f"{self.stages} stages")
        if self.stages == 1 and self.stage_specs:
            raise ValueError(
                "stage_specs requires stages > 1 — a single-stage config "
                "takes its strategy from the projection site spec")

    @property
    def mixed(self) -> bool:
        """True when stages run DIFFERENT strategies (per-stage param
        subtrees + runtime dispatch instead of one pipe-sharded stack)."""
        return bool(self.stage_specs) and len(set(self.stage_specs)) > 1


# ---------------------------------------------------------------------------
# projection strategy selection (the ProjectionStrategy API's config side)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ProjectionSpec:
    """Selects and parameterizes one projection strategy at one site.

    ``kind`` is a key into ``repro.parallel.strategies`` registry:
    ``tensor_col`` | ``tensor_row`` | ``phantom`` | ``lowrank_distill`` —
    or the pseudo-kind ``tensor`` which resolves to the site's natural
    dense sharding (col for input-side projections, row for output-side).
    The remaining fields only matter for the phantom-family kinds.
    """
    kind: str = "tensor"
    k: int = 64                     # ghost width (phantom family)
    variant: str = "fused"          # faithful | fused | ring
    include_self_term: bool = False
    # Executing kernel for the hot inner op at this site: "xla" composes
    # the GEMMs in XLA; "pallas" runs the fused Pallas kernels (phantom
    # fused projection / flash-attention core); "auto" picks pallas on
    # TPU, xla elsewhere.  See docs/kernels.md.
    kernel_backend: str = "xla"     # xla | pallas | auto


# every projection site the model families expose, with its natural dense
# strategy (what `kind="tensor"` resolves to)
PROJECTION_SITES = {
    "ffn_layer": "tensor_col",      # paper square FFN (core/ffn.py)
    "ffn_gate": "tensor_col",
    "ffn_up": "tensor_col",
    "ffn_down": "tensor_row",
    "attn_q": "tensor_col",
    "attn_k": "tensor_col",
    "attn_v": "tensor_col",
    "attn_o": "tensor_row",
    "ssm_in": "tensor_col",
    "ssm_out": "tensor_row",
    "moe_experts": "tensor_col",
}

_FFN_SITES = ("ffn_gate", "ffn_up", "ffn_down")
_PROJ_LEGACY_ATTN_SITES = ("attn_q", "attn_k", "attn_v", "attn_o",
                           "ssm_in", "ssm_out")

PHANTOM_KINDS = ("phantom", "lowrank_distill")


@dataclass(frozen=True)
class ProjectionMap:
    """Per-site ProjectionSpec overrides.  ``default`` applies to any site
    without an explicit entry; ``None`` everywhere falls back to the
    legacy ``ffn_impl``/``PhantomConfig.apply_*`` shim."""
    default: Optional[ProjectionSpec] = None
    ffn_layer: Optional[ProjectionSpec] = None
    ffn_gate: Optional[ProjectionSpec] = None
    ffn_up: Optional[ProjectionSpec] = None
    ffn_down: Optional[ProjectionSpec] = None
    attn_q: Optional[ProjectionSpec] = None
    attn_k: Optional[ProjectionSpec] = None
    attn_v: Optional[ProjectionSpec] = None
    attn_o: Optional[ProjectionSpec] = None
    ssm_in: Optional[ProjectionSpec] = None
    ssm_out: Optional[ProjectionSpec] = None
    moe_experts: Optional[ProjectionSpec] = None

    def get(self, site: str) -> Optional[ProjectionSpec]:
        return getattr(self, site) or self.default


def dense_projection_map() -> ProjectionMap:
    """Every site at its natural dense (Megatron-TP) strategy — the
    explicit replacement for the old ``ffn_impl="dense"`` /
    ``apply_*=False`` combination (shadows the legacy shim)."""
    return ProjectionMap(default=ProjectionSpec(kind="tensor"))


def with_phantom_overrides(cfg: "ModelConfig", **kw) -> "ModelConfig":
    """Apply ``PhantomConfig``-style overrides (``k``, ``variant``,
    ``include_self_term``) to the legacy phantom sub-config AND to every
    phantom-family entry of the explicit ``ProjectionMap`` — the CLI
    ``--variant`` / ``phantom.k`` override path, which must keep working
    now that shipped configs carry explicit per-site specs."""
    spec_kw = {key: v for key, v in kw.items()
               if key in ("k", "variant", "include_self_term",
                          "kernel_backend")}
    entries = {}
    for f in dataclasses.fields(ProjectionMap):
        spec = getattr(cfg.projections, f.name)
        if spec is not None and spec.kind in PHANTOM_KINDS and spec_kw:
            spec = dataclasses.replace(spec, **spec_kw)
        entries[f.name] = spec
    return cfg.replace(phantom=dataclasses.replace(cfg.phantom, **kw),
                       projections=ProjectionMap(**entries))


def phantom_projection_map(k: int, *, variant: str = "fused",
                           include_self_term: bool = False,
                           ffn: bool = False, attn: bool = False,
                           ffn_layer: bool = False,
                           kernel_backend: str = "xla") -> ProjectionMap:
    """The explicit per-site ``ProjectionMap`` equivalent of the
    deprecated ``ffn_impl`` / ``PhantomConfig.apply_*`` flags: phantom
    at the selected site families, the natural dense strategy
    everywhere else (``default="tensor"`` shadows the legacy shim
    completely, so configs built this way never consult it).

      ffn_layer  the paper square-FFN site (old ``ffn_impl="phantom"``)
      ffn        the MLP sites           (old ``apply_ffn=True``)
      attn       QKV/O + SSM in/out      (old ``apply_attn_proj=True``)
    """
    ph = ProjectionSpec(kind="phantom", k=k, variant=variant,
                        include_self_term=include_self_term,
                        kernel_backend=kernel_backend)
    entries: dict = {"default": ProjectionSpec(kind="tensor")}
    if ffn_layer:
        entries["ffn_layer"] = ph
    if ffn:
        entries.update({s: ph for s in _FFN_SITES})
    if attn:
        entries.update({s: ph for s in _PROJ_LEGACY_ATTN_SITES})
    return ProjectionMap(**entries)


def with_kernel_backend(cfg: "ModelConfig",
                        backend: str) -> "ModelConfig":
    """Config with ``kernel_backend`` set on every explicit projection
    entry AND the legacy phantom sub-config (so sites falling through to
    the shim pick it up too) — the launcher ``--kernel-backend`` path.
    The switch takes effect at phantom ``fused`` sites (the fused
    projection kernel) and at the attn q/k/v/o sites (the
    flash-attention core); all other strategies ignore it."""
    entries = {}
    for f in dataclasses.fields(ProjectionMap):
        spec = getattr(cfg.projections, f.name)
        entries[f.name] = (None if spec is None else
                           dataclasses.replace(spec,
                                               kernel_backend=backend))
    return cfg.replace(
        projections=ProjectionMap(**entries),
        phantom=dataclasses.replace(cfg.phantom, kernel_backend=backend))


# ---------------------------------------------------------------------------
# model config
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | encdec | vlm | ffn
    num_layers: int
    d_model: int
    num_heads: int = 0
    num_kv_heads: int = 0
    d_ff: int = 0
    vocab_size: int = 0
    head_dim: int = 0               # 0 -> d_model // num_heads

    # encoder/decoder (seamless)
    encoder_layers: int = 0

    # norm / activation / misc
    norm: str = "rmsnorm"           # rmsnorm | layernorm
    mlp: str = "swiglu"             # swiglu | gelu | relu
    qkv_bias: bool = False
    tie_embeddings: bool = False
    norm_eps: float = 1e-5

    # rope
    rope: str = "full"              # full | partial | mrope | none
    rope_fraction: float = 1.0      # chatglm3 "2d rope" == rotate half the dims
    rope_theta: float = 10000.0

    # hybrid interleave: one attention layer per `attn_period` layers
    # (0 = every layer is attention, -1 = attention-free)
    attn_period: int = 0

    # frontends (stubbed per spec: input_specs() yields embeddings)
    frontend: str = "none"          # none | audio | vision

    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None

    # --- parallelism / technique selection -------------------------------
    # DEPRECATED: ffn_impl + phantom.apply_* are legacy shims; they expand
    # into per-site ProjectionSpecs via projection_spec() below.
    ffn_impl: str = "dense"         # dense (Megatron TP baseline) | phantom
    phantom: PhantomConfig = field(default_factory=PhantomConfig)
    # per-site strategy selection (wins over the legacy shim when set)
    projections: ProjectionMap = field(default_factory=ProjectionMap)
    # pipeline-parallel layer-to-stage partitioning (pp mesh axis)
    pipeline: PipelineConfig = field(default_factory=PipelineConfig)
    attn_shard: str = "auto"        # auto | head | ring
    # decode-time: model axis factors into (gcd(kv,p) kv-groups x seq chunks)

    # --- numerics / memory -----------------------------------------------
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: str = "full"             # full | none  (checkpoint each block)
    optimizer: str = "adamw"        # adamw | adafactor | sgd
    fsdp: bool = False              # additionally shard params over data axis
    loss_chunk: int = 2048          # seq chunk for sharded cross-entropy
    microbatches: int = 1           # gradient-accumulation microbatching
    attn_bf16_scores: bool = False  # bf16 attention score blocks (§Perf)
    kv_cache_quant: bool = False    # int8 KV cache w/ per-head scales
    attn_kv_chunk: int = 0          # 0 = default blockwise chunking;
                                    # -1 = unrolled (dry-run cost analysis:
                                    # XLA counts scan bodies once)
    scan_layers: bool = True        # False = python-loop layer stack
                                    # and 1F1B tick loop (dry-run cost
                                    # analysis only); the paper-FFN
                                    # layer stack is always unrolled
    fsdp_gather_quant: bool = False  # int8-quantize FSDP weight gathers
                                     # (serving: halves gather wire bytes)
    attn_ring_gather_kv: bool = False  # ring mode: gather KV once instead
                                       # of p ppermute hops (same wire
                                       # bytes, 1 accumulator pass instead
                                       # of p — §Perf cell C)

    # paper-FFN-specific (family == "ffn")
    ffn_width: int = 0
    ffn_depth: int = 0

    def projection_spec(self, site: str) -> ProjectionSpec:
        """Resolve the ProjectionSpec governing one projection site.

        Order: explicit per-site entry in ``projections`` > ``projections.
        default`` > the legacy ``ffn_impl``/``PhantomConfig.apply_*`` shim
        > the site's natural dense strategy.  The pseudo-kind ``tensor``
        resolves to the site default (col/row).
        """
        if site not in PROJECTION_SITES:
            raise KeyError(f"unknown projection site {site!r}; "
                           f"known: {sorted(PROJECTION_SITES)}")
        spec = self.projections.get(site)
        if spec is None:
            spec = self._legacy_projection_spec(site)
        if spec.kind == "tensor":
            spec = dataclasses.replace(spec, kind=PROJECTION_SITES[site])
        return spec

    def _legacy_projection_spec(self, site: str) -> ProjectionSpec:
        """Deprecation shim: expand ffn_impl / PhantomConfig.apply_* flags
        into the equivalent per-site spec.  Warns when the shim ACTIVELY
        selects phantom (a plain dense config hitting the fallback is
        not using the deprecated surface, just its default)."""
        pp = self.phantom

        def ph() -> ProjectionSpec:
            import warnings
            warnings.warn(
                f"config {self.name!r} selects phantom at site {site!r} "
                f"through the deprecated ffn_impl/PhantomConfig.apply_* "
                f"shim; set ModelConfig.projections (e.g. "
                f"phantom_projection_map) instead",
                DeprecationWarning, stacklevel=4)
            return ProjectionSpec(kind="phantom", k=pp.k,
                                  variant=pp.variant,
                                  include_self_term=pp.include_self_term,
                                  kernel_backend=pp.kernel_backend)

        if site == "ffn_layer":
            return ph() if self.ffn_impl == "phantom" else ProjectionSpec()
        if site in _FFN_SITES and pp.apply_ffn \
                and self.ffn_impl != "dense_force":
            return ph()
        if site in _PROJ_LEGACY_ATTN_SITES and pp.apply_attn_proj:
            return ph()
        return ProjectionSpec()

    def stage_projection_spec(self, stage: int,
                              site: str = "ffn_layer") -> ProjectionSpec:
        """The ProjectionSpec governing `site` on pipeline stage `stage`
        (per-stage override when ``pipeline.stage_specs`` is set, else the
        site's spec)."""
        if self.pipeline.stage_specs:
            spec = self.pipeline.stage_specs[stage]
            if spec.kind == "tensor":
                spec = dataclasses.replace(spec, kind=PROJECTION_SITES[site])
            return spec
        return self.projection_spec(site)

    def uses_phantom_sites(self, sites=None) -> bool:
        """True if any (given) projection site resolves to a phantom-family
        strategy — decides the residual-stream layout (fp)."""
        sites = sites or tuple(PROJECTION_SITES)
        return any(self.projection_spec(s).kind in PHANTOM_KINDS
                   for s in sites)

    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        if self.num_heads:
            return self.d_model // self.num_heads
        return 0

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # rough parameter counts, used for MODEL_FLOPS and memory napkin math ---
    def param_count(self) -> int:
        from repro.models.model import count_params  # lazy, avoids cycle
        return count_params(self)

    def active_param_count(self) -> int:
        from repro.models.model import count_params
        return count_params(self, active_only=True)


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode


SHAPES = {
    "train_4k":    ShapeConfig("train_4k",    4_096,   256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768,   32, "prefill"),
    "decode_32k":  ShapeConfig("decode_32k",  32_768,  128, "decode"),
    "long_500k":   ShapeConfig("long_500k",  524_288,    1, "decode"),
}


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 1000
    grad_clip: float = 1.0
    microbatches: int = 1            # gradient accumulation
    seed: int = 0


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

ARCH_IDS = [
    "granite-moe-3b-a800m",
    "olmoe-1b-7b",
    "seamless-m4t-large-v2",
    "chatglm3-6b",
    "qwen2.5-14b",
    "stablelm-3b",
    "phi3-mini-3.8b",
    "mamba2-370m",
    "qwen2-vl-72b",
    "jamba-1.5-large-398b",
]

_MODULES = {
    "granite-moe-3b-a800m": "granite_moe_3b",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "seamless-m4t-large-v2": "seamless_m4t_large",
    "chatglm3-6b": "chatglm3_6b",
    "qwen2.5-14b": "qwen2_5_14b",
    "stablelm-3b": "stablelm_3b",
    "phi3-mini-3.8b": "phi3_mini",
    "mamba2-370m": "mamba2_370m",
    "qwen2-vl-72b": "qwen2_vl_72b",
    "jamba-1.5-large-398b": "jamba_1_5_large",
    # the paper's own FFN models
    "paper-ffn-4k": "paper_ffn",
    "paper-ffn-16k": "paper_ffn",
    "paper-ffn-64k": "paper_ffn",
    "paper-ffn-131k": "paper_ffn",
    "paper-ffn-262k": "paper_ffn",
}


def get_config(arch: str, smoke: bool = False, **overrides) -> ModelConfig:
    """Load an architecture config by id (``--arch`` flag)."""
    import importlib
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    if arch.startswith("paper-ffn"):
        cfg = (mod.smoke_config if smoke else mod.config)(arch)
    else:
        cfg = (mod.smoke_config if smoke else mod.config)()
    if overrides:
        cfg = cfg.replace(**overrides)
    return cfg


def applicable_shapes(cfg: ModelConfig) -> list[str]:
    """Which of the 4 assigned shapes apply to this architecture.

    ``long_500k`` needs sub-quadratic attention: only SSM/hybrid run it
    (skip recorded for full-attention archs, per DESIGN.md).
    """
    names = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.family in ("ssm", "hybrid"):
        names.append("long_500k")
    return names
