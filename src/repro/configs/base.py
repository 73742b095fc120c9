"""Architecture config schema shared by the model zoo.

Every assigned architecture gets one ``<arch>.py`` module defining ``CONFIG``
with the exact dimensions from the assignment (source cited in the module
docstring).  ``reduced()`` produces the family-preserving smoke-test variant
(<= 2 layers, d_model <= 512, <= 4 experts) exercised on CPU; the full configs
are exercised only through the dry-run (ShapeDtypeStruct, no allocation).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN's stretch of RoPE (a ``rope_scaling`` of type ``yarn``): the
    rotary frequencies slowed by ``factor`` below a ramp between
    ``beta_fast`` and ``beta_slow`` rotations over ``original_len``
    positions, and the attention logits scaled by the square of
    ``0.1 mscale_all_dim ln(factor) + 1``."""
    factor: float
    original_len: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    # attention details
    head_dim: int = 0           # 0 -> d_model // n_heads
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    sliding_window: int = 4096  # used only by long-context serving variants
    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # DeepSeekMoE (family "deepseek"): n_experts is the router's width and
    # d_ff the leading dense layers' width
    moe_d_ff: int = 0           # width of each routed expert
    n_shared_experts: int = 0   # shared experts, one SwiGLU n x moe_d_ff wide
    n_dense_layers: int = 0     # leading dense layers (of n_layers)
    expert_shares: int = 1      # chips one layer's routed experts are split over
    expert_share_index: int = 0  # the share held here
    aux_loss_alpha: float = 0.0  # weight of the sequence-wise balance loss
    # multi-head latent attention (family "deepseek"; kv_lora_rank 0 = none)
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    yarn: Yarn | None = None
    # SSM / hybrid
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 256
    attn_every: int = 0         # hybrid: shared attention block every k SSM layers
    # encoder-decoder (audio)
    enc_layers: int = 0
    dec_ctx: int = 0            # decoder context limit (whisper: 448)
    # modality frontend stubs (audio frames / vision patches)
    n_frontend_tokens: int = 0
    # numerics
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    # citation
    source: str = ""

    # ------------------------------------------------------------- derived
    @property
    def head_dim_(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def qk_head_dim(self) -> int:
        """Width of a query and key head: latent attention's nope and rope
        parts, else ``head_dim_``."""
        if self.kv_lora_rank:
            return self.qk_nope_dim + self.qk_rope_dim
        return self.head_dim_

    @property
    def v_head_dim_(self) -> int:
        return self.v_head_dim or self.head_dim_

    @property
    def experts_held(self) -> int:
        """Routed experts held here: one of ``expert_shares`` equal shares."""
        return self.n_experts // self.expert_shares

    @property
    def first_expert_held(self) -> int:
        return self.expert_share_index * self.experts_held

    def __post_init__(self):
        if self.n_heads % max(self.n_kv_heads, 1) != 0:
            raise ValueError(f"{self.name}: n_heads must be divisible by n_kv_heads")
        if self.family in ("moe", "deepseek") and (self.n_experts < 1
                                                   or self.top_k < 1):
            raise ValueError(f"{self.name}: moe needs n_experts/top_k")
        if self.n_experts % self.expert_shares or not (
                0 <= self.expert_share_index < self.expert_shares):
            raise ValueError(
                f"{self.name}: {self.n_experts} experts do not split into "
                f"{self.expert_shares} shares holding share "
                f"{self.expert_share_index}")
        if self.n_dense_layers >= self.n_layers > 0 and self.n_experts:
            raise ValueError(f"{self.name}: no expert layer follows the "
                             f"{self.n_dense_layers} dense ones")

    def cut(self, n_layers: int, vocab_share: int = 1,
            expert_share: int = 1) -> "ModelConfig":
        """This chip's share of the published model: every width as
        published, depth cut to ``n_layers`` (leading dense layers
        included), the vocabulary to one ``1/vocab_share`` slice of its rows
        (the slices of a vocabulary split over ``vocab_share`` chips; the
        data draws its ids from the slice), and the routed experts to the
        first of ``expert_share`` equal shares (the router keeps its
        published width).  The cut is named, e.g.
        ``qwen3-1.7b-4l-vocab18992`` or ``...-vocab12800-8of64e``."""
        if not 1 <= n_layers <= self.n_layers:
            raise ValueError(f"{self.name}: n_layers={n_layers} is outside "
                             f"1..{self.n_layers}")
        if vocab_share < 1 or self.vocab % vocab_share:
            raise ValueError(f"{self.name}: vocab {self.vocab} does not "
                             f"split into {vocab_share} equal slices")
        vocab = self.vocab // vocab_share
        name = f"{self.name}-{n_layers}l-vocab{vocab}"
        if expert_share != 1:
            name += f"-{self.n_experts // expert_share}of{self.n_experts}e"
        return dataclasses.replace(
            self, n_layers=n_layers, vocab=vocab, expert_shares=expert_share,
            expert_share_index=0, name=name)

    def reduced(self) -> "ModelConfig":
        """Family-preserving smoke-test variant (2 layers, d_model <= 512,
        <= 4 experts) that runs a real fwd/train step on CPU."""
        d_model = min(self.d_model, 256)
        n_heads = min(self.n_heads, 4)
        n_kv = max(1, min(self.n_kv_heads, n_heads))
        while n_heads % n_kv:
            n_kv -= 1
        kw = dict(
            n_layers=2,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            vocab=min(self.vocab, 512),
            head_dim=d_model // n_heads,
            sliding_window=64,
            ssm_head_dim=min(self.ssm_head_dim, 32) if self.ssm_state else self.ssm_head_dim,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_chunk=16,
            name=self.name + "-reduced",
        )
        if self.n_experts:
            kw["n_experts"] = min(self.n_experts, 4)
            kw["top_k"] = min(self.top_k, 2)
            kw["expert_shares"], kw["expert_share_index"] = 1, 0
            kw["moe_d_ff"] = min(self.moe_d_ff, 128)
            kw["n_dense_layers"] = min(self.n_dense_layers, 1)
        if self.kv_lora_rank:
            kw.update(kv_lora_rank=64, qk_nope_dim=32, qk_rope_dim=16,
                      v_head_dim=32)
        if self.attn_every:
            kw["attn_every"] = 1
        if self.enc_layers:
            kw["enc_layers"] = 2
            kw["dec_ctx"] = min(self.dec_ctx or 64, 64)
        if self.n_frontend_tokens:
            kw["n_frontend_tokens"] = 8
        return dataclasses.replace(self, **kw)
