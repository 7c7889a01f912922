"""Config system: architecture + shape + run configuration.

Every assigned architecture is a frozen ``ModelConfig`` built in its own
module (``repro/configs/<arch>.py``) with the exact published dimensions.
Shapes (seq_len x global_batch cells) live here; the registry in
``repro/configs/__init__.py`` exposes ``get_config(name)`` / ``get_shape``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration."""

    num_experts: int
    top_k: int
    expert_ff: int
    num_shared_experts: int = 0      # deepseek-v2: 2 shared experts
    shared_ff: int = 0               # hidden dim of each shared expert
    capacity_factor: float = 1.25
    router_zloss: float = 1e-3
    # dense residual branch computed in parallel with the MoE branch (arctic)
    parallel_dense: bool = False
    # routing (deepseek-v2: group-limited greedy over 8 groups, 3 kept,
    # weights scaled by 16 and not renormalised)
    router_experts: int = 0          # router outputs; 0 => num_experts
    first_expert: int = 0            # router index of the first held expert
    expert_groups: int = 1           # the router's experts in equal groups
    top_k_groups: int = 1            # groups a token's top_k is drawn from
    routed_scale: float = 1.0        # multiplies the top-k weights
    renormalize_top_k: bool = True   # top-k weights divided by their sum
    # router scores: softmax over the router's outputs, or sigmoid of each
    # (DeepSeek-V3, Nemotron-H), whose top-k is chosen on the score plus a
    # learned per-expert correction bias and weighted by the score alone
    score: str = "softmax"

    def __post_init__(self):
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"router score must be 'softmax' or 'sigmoid', "
                             f"got {self.score!r}")
        if self.router_experts == 0:
            object.__setattr__(self, "router_experts", self.num_experts)
        if self.first_expert + self.num_experts > self.router_experts:
            raise ValueError("held experts lie beyond the router's outputs")
        if self.router_experts % self.expert_groups or \
                not 1 <= self.top_k_groups <= self.expert_groups:
            raise ValueError("expert groups must split the router evenly "
                             "and keep between 1 and all of them")

    @property
    def holds_share(self) -> bool:
        """The layer holds only some of the router's experts (expert
        parallelism): it computes their part of the result, dropless."""
        return self.num_experts < self.router_experts


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 (SSD) block configuration.

    The inner width is ``num_heads x head_dim`` where ``num_heads`` is
    given (Nemotron-H: 64 heads of 64 beside a d_model of 2688), else
    ``expand x d_model``. B and C come in ``n_groups`` groups of
    ``state_dim``, head h reading group ``h // (heads / n_groups)``, and
    the gated RMSNorm is taken over each group's share of the inner
    width. ``conv_bias``: the depthwise conv adds a bias per channel."""

    state_dim: int            # N
    head_dim: int = 64        # P
    expand: int = 2           # d_inner = expand * d_model, where num_heads is 0
    conv_width: int = 4
    chunk: int = 256          # SSD chunk length
    dt_min: float = 0.001
    dt_max: float = 0.1
    num_heads: int = 0        # 0 => expand * d_model // head_dim
    n_groups: int = 1
    conv_bias: bool = False

    def __post_init__(self):
        if self.num_heads and self.num_heads % self.n_groups:
            raise ValueError("B/C groups must split the heads evenly")

    def d_inner(self, d_model: int) -> int:
        if self.num_heads:
            return self.num_heads * self.head_dim
        return self.expand * d_model

    def heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 multi-head latent attention, with YaRN rope scaling.

    ``rope_factor`` 1 is plain rope; above 1 the rope frequencies are
    YaRN's blend of interpolated and original ones (``layers.yarn_inv_freq``)
    and the softmax scale gains ``yarn_mscale(factor, mscale_all_dim)**2``.
    """

    kv_lora_rank: int = 512
    q_lora_rank: int = 0          # 0 => full-rank q projection
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_factor: float = 1.0
    rope_original_max_positions: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------

# a layer_pattern's letters and the block kind each one runs
# (models.blocks.apply_block)
PATTERN_KINDS = {"M": "ssm", "E": "experts", "*": "attn"}


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: int = 0                # 0 => d_model // num_heads
    activation: str = "silu_glu"     # silu_glu | relu2 | gelu
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    rope_theta: float = 500000.0
    qk_norm: bool = False            # chameleon uses qk layernorm
    tie_embeddings: bool = True
    logit_softcap: float = 0.0
    norm_eps: float = 1e-5

    # --- latent attention (deepseek-v2) ---
    mla: Optional[MLAConfig] = None

    # --- MoE ---
    moe: Optional[MoEConfig] = None
    # layer indices that use a plain dense FFN instead of MoE (deepseek: (0,))
    dense_layer_prefix: int = 0
    dense_prefix_ff: int = 0         # d_ff of the dense prefix layers

    # --- SSM / hybrid ---
    ssm: Optional[SSMConfig] = None
    # Nemotron-H's published hybrid_override_pattern: block i is "M" (a
    # Mamba-2 mixer), "E" (an expert layer) or "*" (attention), each one
    # x + mixer(RMSNorm(x)); the model's blocks are the pattern's first
    # num_layers (models.model.build_schedule)
    layer_pattern: str = ""
    # hybrid (hymba): per-layer attention windows; layers listed here use
    # full/global attention, all others use sliding-window attention.
    attn_window: int = 0             # 0 => full causal attention
    global_attn_layers: Tuple[int, ...] = ()

    # --- encoder-decoder (whisper) ---
    encoder_layers: int = 0
    encoder_seq: int = 0             # fixed source length (1500 audio frames)
    frontend: str = "none"           # none | audio_stub | token

    # --- numerics ---
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"

    # ------------------------------------------------------------------
    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // max(self.num_heads, 1))
        if self.layer_pattern and (
                len(self.layer_pattern) < self.num_layers
                or set(self.layer_pattern) - set(PATTERN_KINDS)):
            raise ValueError(
                f"layer_pattern must give each of the {self.num_layers} "
                f"blocks one of {sorted(PATTERN_KINDS)}")

    @property
    def blocks(self) -> str:
        """The pattern's letter of each block (``layer_pattern``), or ""."""
        return self.layer_pattern[:self.num_layers]

    # --- derived quantities -------------------------------------------------
    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def subquadratic(self) -> bool:
        """Eligible for the long_500k cell (per assignment instructions)."""
        return self.family in ("ssm", "hybrid")

    def attn_params_per_layer(self) -> int:
        d, h, kv, hd = self.d_model, self.num_heads, self.num_kv_heads, self.head_dim
        mla = self.mla
        if mla is not None:
            qk_hd = mla.qk_nope_head_dim + mla.qk_rope_head_dim
            if mla.q_lora_rank:                                 # low-rank q
                p = d * mla.q_lora_rank + mla.q_lora_rank       # + its norm
                p += mla.q_lora_rank * h * qk_hd
            else:
                p = d * h * qk_hd                               # q proj
            p += d * (mla.kv_lora_rank + mla.qk_rope_head_dim)  # down proj
            p += mla.kv_lora_rank                               # its norm
            p += mla.kv_lora_rank * h * (mla.qk_nope_head_dim + mla.v_head_dim)
            p += h * mla.v_head_dim * d                         # out proj
            return p
        return d * h * hd + 2 * d * kv * hd + h * hd * d

    def ffn_params_per_layer(self) -> int:
        if self.moe is not None:
            m = self.moe
            e = m.num_experts * self._expert_ffn(m.expert_ff)
            e += m.num_shared_experts * self._expert_ffn(m.shared_ff or m.expert_ff)
            e += self.d_model * m.router_experts                 # router
            if m.score == "sigmoid":
                e += m.router_experts                # correction bias
            if m.parallel_dense:
                e += self._expert_ffn(self.d_ff)
            return e
        if self.d_ff == 0:
            return 0
        return self._expert_ffn(self.d_ff)

    def _expert_ffn(self, ff: int) -> int:
        mult = 3 if self.activation == "silu_glu" else 2
        return mult * self.d_model * ff

    def ssm_params_per_layer(self) -> int:
        if self.ssm is None:
            return 0
        s = self.ssm
        di = s.d_inner(self.d_model)
        nh = s.heads(self.d_model)
        conv = di + 2 * s.n_groups * s.state_dim      # x, B and C channels
        # in_proj produces (x, z, B, C, dt); out_proj back to d_model
        p = self.d_model * (di + conv + nh)
        p += di * self.d_model
        p += (s.conv_width + s.conv_bias) * conv      # depthwise conv
        p += 3 * nh + di                      # A_log, D, dt_bias; gated norm
        return p

    def ffn_active_params_per_layer(self) -> int:
        if self.moe is None:
            return self.ffn_params_per_layer()
        m = self.moe
        a = m.top_k * self._expert_ffn(m.expert_ff)
        a += m.num_shared_experts * self._expert_ffn(m.shared_ff or m.expert_ff)
        a += self.d_model * m.router_experts
        if m.score == "sigmoid":
            a += m.router_experts
        if m.parallel_dense:
            a += self._expert_ffn(self.d_ff)
        return a

    def _layer_params(self, active: bool) -> int:
        ffn = self.ffn_active_params_per_layer() if active else self.ffn_params_per_layer()
        if self.family == "ssm":
            return self.ssm_params_per_layer() + 2 * self.d_model
        per = ffn + 2 * self.d_model
        if self.family == "hybrid":
            per += self.attn_params_per_layer() + self.ssm_params_per_layer()
        else:
            per += self.attn_params_per_layer()
        return per

    def num_params(self) -> int:
        """Total parameter count (analytic)."""
        n = self.vocab_size * self.d_model
        if not self.tie_embeddings:
            n += self.vocab_size * self.d_model
        body = 0
        for kind in self.blocks:
            body += self.d_model + {
                "ssm": self.ssm_params_per_layer,
                "experts": self.ffn_params_per_layer,
                "attn": self.attn_params_per_layer}[PATTERN_KINDS[kind]]()
        for i in range(0 if self.layer_pattern else self.num_layers):
            if self.moe is not None and i < self.dense_layer_prefix:
                dense = ModelConfig(
                    name="_tmp", family="dense", num_layers=1, d_model=self.d_model,
                    num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                    d_ff=self.dense_prefix_ff or self.d_ff, vocab_size=1,
                    activation=self.activation)
                body += dense._layer_params(False) + self.attn_params_per_layer() - dense.attn_params_per_layer()
                continue
            body += self._layer_params(False)
        n += body + self.d_model
        if self.encoder_layers:
            enc_layer = self.attn_params_per_layer() + self._expert_ffn(self.d_ff) + 2 * self.d_model
            cross = self.attn_params_per_layer()
            n += self.encoder_layers * enc_layer + self.num_layers * cross
        return n

    def num_active_params(self) -> int:
        """Active parameters per token (= num_params for non-MoE)."""
        if self.moe is None:
            return self.num_params()
        n = self.num_params()
        n -= self.num_layers_moe() * (self.ffn_params_per_layer() - self.ffn_active_params_per_layer())
        return n

    def num_layers_moe(self) -> int:
        if self.layer_pattern:
            return self.blocks.count("E")
        return 0 if self.moe is None else self.num_layers - self.dense_layer_prefix


# ---------------------------------------------------------------------------
# Shapes
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether an (arch x shape) cell is runnable, and why not if skipped."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, "long_500k requires sub-quadratic attention (skip per assignment)"
    return True, ""


# ---------------------------------------------------------------------------
# Run config (training/serving knobs; the operator-owned side)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RunConfig:
    """Operator-owned knobs: parallelism, NSM policy, numerics, FT."""

    # parallelism
    multi_pod: bool = False
    fsdp: bool = True                       # shard params/opt over 'data'
    seq_parallel_activations: bool = False  # Megatron-SP between blocks
    pipeline_stages: int = 1                # >1: GPipe over 'pod'
    grad_accum: int = 1

    # NetKernel stack policy (the paper's contribution surface)
    nsm_policy: str = "xla"       # xla | ring | hierarchical | compressed | shm-first
    explicit_pod_sync: bool = False  # route cross-pod grad sync through CoreEngine
    # track the int8 error-feedback residual of the gradients each step
    # (metrics["ef_residual_max"]) — the measured signal an EF-aware
    # numerics tolerance derives from (see test_nsm_conformance.py)
    track_ef_residual: bool = False

    # numerics / memory
    remat: str = "full"           # full | dots | none
    rules_variant: str = "2d"     # 2d (FSDP+TP) | fsdp (pure FSDP over mesh)
    grad_accum_dtype: str = "float32"   # float32 | bfloat16 (>=300B models)
    factored_nu: bool = False     # Adafactor-style second moment (>=300B)
    # roofline probes: unroll scanned segments so XLA cost_analysis (which
    # counts a while body once) attributes per-layer cost exactly
    force_unroll_segments: bool = False
    moment_dtype: str = "float32"  # float32 | bfloat16 (>=100B models)
    kv_cache_dtype: str = "bfloat16"  # bfloat16 | int8
    attention_impl: str = "chunked"   # chunked | naive
    attn_q_block: int = 512
    attn_kv_block: int = 512

    # optimizer
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: float = 1.0
    z_loss: float = 1e-4

    # fault tolerance
    checkpoint_every: int = 50
    keep_checkpoints: int = 3
    async_checkpoint: bool = True
    straggler_factor: float = 3.0

    def __post_init__(self):
        # models/attention.py implements exactly these; any other name
        # would silently run the chunked path
        if self.attention_impl not in ("chunked", "naive"):
            raise ValueError(
                f"attention_impl must be 'chunked' or 'naive', got "
                f"{self.attention_impl!r}")


def reduce_for_smoke(cfg: ModelConfig) -> ModelConfig:
    """Family-preserving reduction for CPU smoke tests (tiny dims)."""
    kw: dict = dict(
        name=cfg.name + "-smoke",
        # a layer pattern keeps its shortest prefix that holds every kind
        num_layers=min(cfg.num_layers, 1 + max(
            cfg.layer_pattern.index(c) for c in set(cfg.layer_pattern)))
        if cfg.layer_pattern else
        min(cfg.num_layers, 2 + (cfg.dense_layer_prefix or 0)),
        d_model=64,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 2) if cfg.num_kv_heads < cfg.num_heads else 4,
        d_ff=0 if cfg.d_ff == 0 else 128,
        vocab_size=256,
        head_dim=16,
    )
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe, num_experts=4, top_k=min(cfg.moe.top_k, 2), expert_ff=64,
            shared_ff=64 if cfg.moe.num_shared_experts else 0,
            router_experts=4, first_expert=0,
            expert_groups=min(cfg.moe.expert_groups, 2),
            top_k_groups=min(cfg.moe.top_k_groups, 1),
            # the scale that offsets small scores over many experts, cut
            # with them (deepseek: 16 over 160 -> 0.4 over 4); weights
            # renormalised to sum to 1 keep theirs
            routed_scale=cfg.moe.routed_scale * 4 / cfg.moe.router_experts
            if cfg.moe.routed_scale != 1.0 and not cfg.moe.renormalize_top_k
            else cfg.moe.routed_scale)
        kw["dense_prefix_ff"] = 128 if cfg.dense_layer_prefix else 0
        if cfg.dense_layer_prefix:
            kw["num_layers"] = max(kw["num_layers"], cfg.dense_layer_prefix + 2)
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(
            cfg.ssm, state_dim=16, head_dim=16, chunk=32,
            num_heads=8 if cfg.ssm.num_heads else 0,
            n_groups=min(cfg.ssm.n_groups, 2))
    if cfg.global_attn_layers:
        kw["global_attn_layers"] = (0,)
        kw["attn_window"] = 32
    if cfg.encoder_layers:
        kw["encoder_layers"] = 2
        kw["encoder_seq"] = 24
    if cfg.mla is not None:
        kw["mla"] = dataclasses.replace(
            cfg.mla, kv_lora_rank=32, q_lora_rank=24 if cfg.mla.q_lora_rank
            else 0, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
    return dataclasses.replace(cfg, **kw)
