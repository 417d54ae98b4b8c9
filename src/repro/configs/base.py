"""Model configuration schema + the input-shape suite for every arch.

Shapes (assignment):
  train_4k     seq 4096,   global batch 256   (training, lowers train_step)
  prefill_32k  seq 32768,  global batch 32    (inference prefill)
  decode_32k   seq 32768,  global batch 128   (decode: 1 new token, KV cache)
  long_500k    seq 524288, global batch 1     (long-context decode; needs a
                                               sub-quadratic path — see
                                               ``supports_long_context``)
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace

ATTN_KINDS = ("sliding", "full")


@dataclass(frozen=True)
class Yarn:
    """YaRN scaling of the rotary embedding (arXiv:2309.00071), with the
    keys of a Hugging Face ``rope_parameters`` entry of ``rope_type`` yarn."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # 'dense' | 'moe' | 'vlm' | 'audio' | 'hybrid' | 'ssm'
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 0  # 0 -> d_model // n_heads

    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_period: int = 1        # layer l is MoE iff l % moe_period == moe_offset
    moe_offset: int = 0
    experts_held: int = 0      # experts 0..held-1 live here (0 = all of them)

    # attention
    window: int | None = None  # sliding-window size (None = full)
    # attention kind of each layer, repeating, 'sliding' (the window) or
    # 'full'; empty = every layer alike (``window`` on all of them in
    # ``forward``; the serve path keeps full-length caches for them)
    layer_types: tuple[str, ...] = ()
    qkv_bias: bool = False
    rope_theta: float = 1e6
    yarn: Yarn | None = None   # YaRN on the 'full' layers' rotary embedding

    # hybrid (Jamba): one attention layer per `attn_period` layers, rest Mamba
    attn_period: int = 0       # 0 = every layer is attention
    attn_offset: int = 0
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2

    # xLSTM: repeating per-layer block kinds
    block_pattern: tuple[str, ...] = ()  # e.g. ('m','m','m','s')

    # encoder-decoder
    enc_layers: int = 0        # >0 -> enc-dec; n_layers = decoder layers

    # modality frontend stub ('vision' | 'audio' | None): input_specs()
    # provides precomputed patch/frame embeddings of this length
    frontend: str | None = None
    frontend_len: int = 576    # anyres tiles x patches / audio frames

    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    tie_embeddings: bool = False
    remat: str = "block"  # 'block' | 'none' | 'block_save_moe' (keep dispatch)
    seq_parallel: bool = False  # Megatron SP: seq-shard activations between
    #                             layers (RS+AG instead of all-reduce)

    def __post_init__(self):
        # accept the JSON forms (a list, a dict) and keep the config hashable
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if isinstance(self.yarn, dict):
            object.__setattr__(self, "yarn", Yarn(**self.yarn))
        if not set(self.layer_types) <= set(ATTN_KINDS):
            raise ValueError(f"layer_types {self.layer_types} not all in {ATTN_KINDS}")
        if self.layer_types and (self.attn_period or self.block_pattern):
            raise ValueError("layer_types gives the attention kinds of an all-attention stack")
        if "sliding" in self.layer_types and not self.window:
            raise ValueError("a 'sliding' layer needs a window")
        if self.layer_types and self.n_layers % len(self.layer_types):
            raise ValueError("n_layers is not a whole number of layer_types periods")
        if not 0 <= self.experts_held <= self.n_experts:
            raise ValueError(f"experts_held {self.experts_held} of {self.n_experts}")

    # --- derived -------------------------------------------------------------
    @property
    def head_dim(self) -> int:
        return self.d_head or (self.d_model // self.n_heads)

    @property
    def is_encdec(self) -> bool:
        return self.enc_layers > 0

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    def layer_kind(self, l: int) -> str:
        """'attn' | 'mamba' | 'slstm' | 'mlstm' for layer l."""
        if self.block_pattern:
            return {"m": "mlstm", "s": "slstm"}[
                self.block_pattern[l % len(self.block_pattern)]
            ]
        if self.attn_period and l % self.attn_period != self.attn_offset:
            return "mamba"
        return "attn"

    def layer_is_moe(self, l: int) -> bool:
        return self.is_moe and l % self.moe_period == self.moe_offset

    @property
    def held_experts(self) -> int:
        """Experts whose weights this chip holds: 0..held_experts-1."""
        return self.experts_held or self.n_experts

    @property
    def period(self) -> int:
        """Layers of the repeating pattern the layer stack scans over, one
        parameter stack per position: Jamba's attention period, xLSTM's
        block pattern, or ``layer_types`` (the positions differ in window,
        cache and rotary embedding).  0: every layer is layer 0's, one
        stack."""
        if self.family == "hybrid":
            return self.attn_period
        return len(self.block_pattern) or len(self.layer_types)

    def attn_kind(self, l: int) -> str | None:
        """'sliding' or 'full' for layer l, None without ``layer_types``."""
        return self.layer_types[l % len(self.layer_types)] if self.layer_types else None

    def layer_window(self, l: int) -> int | None:
        """The window layer l's attention sees: ``window`` on a 'sliding'
        layer, None on a 'full' one; without ``layer_types``, ``window``."""
        return None if self.attn_kind(l) == "full" else self.window

    def layer_yarn(self, l: int) -> Yarn | None:
        """YaRN parameters of layer l's rotary embedding (full layers only)."""
        return self.yarn if self.attn_kind(l) == "full" else None

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic path exists: SWA, SSM, or hybrid."""
        if self.family in ("ssm", "hybrid"):
            return True
        return self.window is not None

    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        pat = self.block_pattern[:4] if self.block_pattern else ()
        period = self.attn_period or len(self.layer_types)
        return replace(
            self,
            n_layers=period or max(2, min(4, self.n_layers)),  # one whole period
            d_model=128,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads < self.n_heads else 4,
            d_head=32,
            d_ff=256 if self.d_ff else 0,
            vocab=512,
            n_experts=min(self.n_experts, 4) if not self.experts_held else 8,
            top_k=min(self.top_k, 2),
            # fewer held than routed, top-k within what is held
            experts_held=4 if self.experts_held else 0,
            # per-kind layers: a window that test prompts outgrow
            window=(16 if self.layer_types else min(self.window, 64)) if self.window else None,
            enc_layers=2 if self.enc_layers else 0,
            frontend_len=8 if self.frontend else self.frontend_len,
            mamba_d_state=8,
            block_pattern=pat,
            dtype="float32",
            remat="none",
        )


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode'


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Whether this (arch, shape) cell runs, and why not if skipped."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return False, "pure O(L^2) full attention; no sub-quadratic path (see DESIGN.md)"
    return True, ""
