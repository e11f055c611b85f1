"""Model configuration (ref: model.py:9-21 ``TransformerModelArgs``).

The reference's dataclass defaults (dim 4096 / 32 layers / rope_theta 10000 /
multiple_of 256) are *overridden* by the trainer to the Llama-3-8B shape
(ref: train.py:43-53: n_kv_heads=8, ffn_dim_multiplier=1.3, multiple_of=1024,
rope_theta=500000, vocab from tokenizer). Both shapes are exposed here as
named presets; the headline benchmark preset is the GPT-2-125M-class config
from BASELINE.json.
"""

import dataclasses
from typing import Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    # --- architecture (ref: model.py:9-21) ---
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: Optional[int] = None
    multiple_of: int = 256  # SwiGLU hidden rounded up to a multiple of this
    ffn_dim_multiplier: Optional[float] = None
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    seq_len: int = 2048
    vocab_size: int = -1
    # --- TPU compute options (reference: global default dtype, train.py:54) ---
    dtype: jnp.dtype = jnp.bfloat16  # activations / compute
    param_dtype: jnp.dtype = jnp.bfloat16  # weights (and hence AdamW moments)
    attention_impl: str = "auto"
    # Paged-KV attention kernel (every serving read through block tables:
    # S=1 decode AND S>1 chunked prefill / chunk-mode spec-verify):
    # "gather" assembles each slot's blocks into a contiguous view and
    # runs the ring kernel on it (bit-exact reference), "pallas" reads
    # pool blocks in place through the block table — the decode kernel
    # for S=1, the chunk kernel for S>1 (ops/paged_attention.py; no
    # gathered copy either way; equal to gather within fp32 accumulation
    # tolerance). "auto", the default, takes one of the two per call by
    # ops/attention.py resolve_paged_kernel's rule on shape and backend:
    # in place for S=1 on one TPU device, the gather elsewhere. Training
    # never reads this field.
    paged_kernel: str = "auto"
    # Sequence layout under sequence parallelism: "zigzag" (each shard holds
    # one early + one mirrored late chunk — balances causal work around the
    # ring at ~2x fewer FLOPs; ops/ring_attention.py) or "contiguous".
    sp_layout: str = "zigzag"
    # Token-embedding lookup: "gather" (jnp.take), "one_hot" (iota one-hot
    # matmul — contracts the vocab axis on the MXU with a psum, which is how
    # a vocab-sharded table must be read under tensor parallelism), or
    # "auto" (one_hot iff the mesh's tensor axis is >1).
    embed_impl: str = "auto"
    # Trunk form: "loop" unrolls n_layers distinct blocks (params
    # layers_{i}/...); "scan" runs one block body under lax.scan over
    # layer-stacked params (params layers/block/... with a leading
    # n_layers axis) — XLA compiles the body once, so compile time is
    # O(1) in depth instead of O(n_layers) (measured on CPU: 53 s vs 9 s
    # at depth 64), at ~19% step-time cost on TPU from lost cross-layer
    # fusion (98.3k -> 80.0k tokens/s on the headline bench). Both compute
    # identical functions; models/llama.py has the param-layout converters.
    layer_impl: str = "loop"
    # Merge the three attention projections into ONE matmul against the
    # concatenated (D, (H+2K)*dh) kernel (params stay the separate
    # wq/wk/wv trees — the concat is a per-step weight-side reshape that
    # XLA folds). Measured REJECTION on v5e (BASELINE.md round 4:
    # 110.3k vs 113.8k base, and still -2% on top of the other round-4
    # wins) — kept as an option for other generations.
    fused_qkv: bool = False
    # Contract wo against the flash kernel's head-major output via einsum
    # instead of transpose+reshape+Dense (rope_impl="fused" path only).
    # Param tree unchanged (_Kernel). Default ON: +2.1% headline, +0.8%
    # at bs 16 (BASELINE.md round 4).
    fused_wo: bool = True
    # Project q/k/v via 'bsd,dhe->bhse' einsums so they land head-major
    # (the input-side mirror of fused_wo). Measured neutral in round 4;
    # under round 5's blocked lse layout it WINS both regimes — +0.9%
    # headline (124.2k vs 123.1k) and +3.6% at bs 16 (118.6k vs 114.5k),
    # the reduced allocator pressure evidently freeing the input-side
    # transpose elision to pay off (BASELINE.md round 5). Default ON.
    qkv_einsum: bool = True
    # SwiGLU gate+up in one (D, 2*hidden) matmul, split after. Default ON:
    # +2.2% on the headline bench stacked on the in-kernel rope
    # (BASELINE.md round 4); parity with separate matmuls is reduction-
    # order-only (tested).
    fused_w13: bool = True
    # Where RoPE is computed: "xla" = elementwise fp32 rope on (B,S,H,D)
    # activations (ops/rope.py apply_rope — reference-parity math);
    # "fused" = inside the Pallas flash kernels via the J-matrix rotation
    # (ops/flash_attention.py flash_attention_rope) — no rotated q/k or
    # fp32 rope intermediate ever materializes at the XLA level, which
    # removes the rope-adjacent relayout-copy family at the custom-call
    # boundary. "fused" engages only on the single-chip pallas path with
    # prefix positions AND within its own measured S*D bound (the
    # streaming kernels re-rope K per tile fetch, measured net-negative
    # past S=4096/D=64 — ops/flash_attention.py rope_fused_profitable);
    # other shapes/paths fall back to "xla" automatically. Default "fused": +3.7% headline and the
    # fp32 relayout-copy family at the custom-call boundary disappears
    # from the profile (BASELINE.md round 4); parity with the xla path is
    # pinned to fp32 noise in tests/test_flash_attention.py.
    rope_impl: str = "fused"
    # Layout of the rope+flash-attention chain: "bshd" reshapes to
    # (B, S, H, D), applies rope, and lets the kernel wrapper transpose to
    # the (B, H, S, D) the TPU tiles need — XLA inserts fp32 layout copies
    # at the custom-call boundary (the 11.5 ms/step "copy" family in the
    # BASELINE.md profile). "bhsd" transposes FIRST and applies rope in
    # the kernel-native layout so the rope fusion emits exactly what the
    # custom call consumes. Only the single-chip pallas path honors
    # "bhsd"; ring/xla paths keep bshd — and rope_impl="fused" (the
    # default) SUPERSEDES it entirely: the fused-rope branch feeds the
    # kernel raw head-major operands itself, so "bhsd" only changes
    # anything under rope_impl="xla" (measured +0.5% there, round 4 —
    # kept as the layout experiment knob for the non-fused path).
    qkv_layout: str = "bshd"
    # Pipeline-parallel schedule (parallel/pipeline.py; only read when the
    # mesh's pipe axis is >1): "1f1b" interleaves each microbatch's
    # backward as soon as its loss gradient exists — activation memory
    # O(pp) with the head+CE fused into the tick loop; "gpipe" is the
    # store-everything forward scan whose autodiff replays the reverse
    # pipeline — memory O(microbatches), kept as a fallback/baseline.
    pp_schedule: str = "1f1b"
    # Unroll each pipeline STAGE's layer loop (a static Python loop over
    # the stage's slice of the layer stack) instead of lax.scan-ing it.
    # Params stay scan-form/stacked (the 'pipe' sharding needs the
    # leading layer axis); only the stage body's control flow changes —
    # this is the PP analogue of layer_impl="loop", recovering the
    # cross-layer fusion whose loss costs the scan trunk ~19-28% on TPU
    # (BASELINE.md rounds 2/4). Default ON, on two measurements of the
    # exact compute pattern: the static unroll over stacked params is
    # 22.5% faster than the lax.scan form ON THE CHIP
    # (scripts/stage_unroll_bench.py: 148.4 vs 191.5 ms fwd+bwd at the
    # bench shape — distinct from the REJECTED nn.scan(unroll=N), whose
    # in-scan dynamic param slicing regressed 22%) and 20% faster on the
    # CPU mesh through the full 1F1B step (scripts/pp_bench.py), with
    # bit-identical losses. The price is compile time proportional to
    # layers-per-stage (L/P — already P-fold smaller than the loop
    # trunk's); --no-pp-stage-unroll restores O(1)-compile scanning for
    # very deep stages.
    pp_stage_unroll: bool = True
    remat: bool = False
    # --- Mixture of Experts (models/moe.py; 0 experts = dense reference
    # FFN). Experts shard over the mesh's 'expert' axis (--ep). ---
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # Dispatch implementation: "capacity" = GShard static capacity slots
    # (drops overflow; its static (E, B, C, D) layout is what XLA turns
    # into the expert all-to-all under --ep), "sorted" = dropless
    # sort + ragged-dot grouped GEMMs (single expert group only). "auto"
    # resolves to capacity everywhere — measured faster on v5e than the
    # ragged-dot path (models/moe.py) — sorted is an explicit opt-in for
    # its no-token-dropping semantics.
    moe_impl: str = "auto"

    def __post_init__(self):
        # Unknown values would otherwise silently select a default branch
        # (e.g. a layer_impl typo benchmarking the wrong trunk form).
        for field, allowed in (("layer_impl", ("loop", "scan")),
                               ("pp_schedule", ("1f1b", "gpipe")),
                               ("sp_layout", ("zigzag", "contiguous")),
                               ("qkv_layout", ("bshd", "bhsd")),
                               ("rope_impl", ("xla", "fused")),
                               ("attention_impl",
                                ("auto", "xla", "pallas", "ring")),
                               ("paged_kernel", ("auto", "gather", "pallas")),
                               ("embed_impl", ("auto", "gather", "one_hot")),
                               ("moe_impl",
                                ("auto", "capacity", "sorted"))):
            if getattr(self, field) not in allowed:
                raise ValueError(
                    f"{field}={getattr(self, field)!r} not in {allowed}")
        if self.moe_experts:
            if not 1 <= self.moe_top_k <= self.moe_experts:
                raise ValueError(
                    f"moe_top_k={self.moe_top_k} must be in "
                    f"[1, moe_experts={self.moe_experts}]")
            if self.moe_capacity_factor <= 0:
                raise ValueError("moe_capacity_factor must be positive")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_heads

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def ffn_hidden_dim(self) -> int:
        """SwiGLU hidden size with the reference's exact rounding
        (ref: model.py:243-247): int(2/3 * 4d), scaled by the multiplier,
        rounded *up* to a multiple of ``multiple_of``.
        8B preset: 4*4096=16384 -> 10922 -> *1.3 -> 14198 -> 14336."""
        hidden = int(2 * (4 * self.dim) / 3)
        if self.ffn_dim_multiplier is not None:
            hidden = int(self.ffn_dim_multiplier * hidden)
        return self.multiple_of * ((hidden + self.multiple_of - 1) // self.multiple_of)

    def param_count(self) -> int:
        """Exact parameter count (untied output head, ref: model.py:350-352).
        With MoE: E expert FFNs plus the router matrix per block."""
        d, v, h = self.dim, self.vocab_size, self.ffn_hidden_dim
        qkv = d * (self.n_heads * self.head_dim) + 2 * d * (self.kv_heads * self.head_dim)
        attn = qkv + (self.n_heads * self.head_dim) * d
        ffn = 3 * d * h
        if self.moe_experts:
            ffn = self.moe_experts * ffn + d * self.moe_experts  # + router
        per_layer = attn + ffn + 2 * d  # two RMSNorm scales per block
        return v * d + self.n_layers * per_layer + d + d * v  # embed + blocks + final norm + head

    def replace(self, **kw) -> "TransformerConfig":
        return dataclasses.replace(self, **kw)



@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    """A decoder whose layers follow a per-layer schedule (models/
    latent_moe.py ``LatentMoETransformer``): every mixer is latent
    attention (a low-rank query, one cached latent + one shared rope key a
    token, a head-wise sigmoid gate), of one of two kinds with its own
    sizes — ``"full"`` layers attend to the ``index_topk`` positions a
    learned indexer picks, ``"sliding"`` layers to the last
    ``sliding_window`` positions (the query's own included) — and the FFN is
    a dense SwiGLU in the first ``first_dense_layers`` layers and a
    sigmoid-routed expert layer after them. The expert layer routes over
    all ``n_routed_experts``, computes the ``held_experts`` =
    (first, count) it is told it holds, and adds the shared experts: the
    chip's share of an expert-parallel deployment, with no exchange.

    What a published model leaves out is a value of these fields, not a
    flag: ``q_lora_rank=None`` projects the query straight from the
    hidden state (one ``wq``); ``index_n_heads=0`` gives the full layers
    no indexer (dense causal attention); ``head_gate=False`` drops the
    head-wise output gate; ``lora_rescale=False`` the latent rescale.

    Served (``inference/engine.py`` finds the class by this type) where the
    full layers have an indexer; trained (``training/loop.py``, the
    uncached forward of :attr:`trains`) where every layer is a full layer
    without one. The fields the engine and the trainer read of any
    configuration (``vocab_size``, ``seq_len``, ``layer_impl``, ``remat``,
    ``attention_impl``, ``paged_kernel``, the dtypes, ``replace``) are
    here under the same names."""

    dim: int = 5120
    n_layers: int = 5
    layer_types: tuple = ("full", "full", "sliding", "sliding", "sliding")
    norm_eps: float = 1e-5
    seq_len: int = 2048
    vocab_size: int = -1
    # full-attention mixer
    n_heads: int = 128
    q_lora_rank: Optional[int] = 1024     # None: the query from x directly
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    # its indexer (0 heads: none, the full layers attend densely)
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    # sliding-window mixer
    swa_n_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    sliding_window: int = 513
    # variance alignment of both latents (alpha = sqrt(dim / rank))
    lora_rescale: bool = True
    # head-wise sigmoid gate on the attention output, both mixers
    head_gate: bool = True
    # FFNs
    first_dense_layers: int = 1
    dense_hidden_dim: int = 13824
    moe_hidden_dim: int = 1536
    n_routed_experts: int = 256
    held_experts: tuple = (0, 32)
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    # compute options, as TransformerConfig's
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.bfloat16
    paged_kernel: str = "auto"
    embed_impl: str = "auto"
    layer_impl: str = "loop"
    remat: bool = False
    # the uncached training forward's attention: "auto" = the Pallas
    # kernel (ops/flash_attention.py flash_attention_bhsd) on a TPU, XLA
    # elsewhere; "pallas" / "xla" force one
    attention_impl: str = "auto"

    def __post_init__(self):
        kinds = set(self.layer_types)
        if len(self.layer_types) != self.n_layers or not kinds <= {
                "full", "sliding"}:
            raise ValueError(
                f"layer_types {self.layer_types} must name 'full' or "
                f"'sliding' for each of n_layers={self.n_layers}")
        first, count = self.held_experts
        if not (0 <= first and 0 < count
                and first + count <= self.n_routed_experts):
            raise ValueError(
                f"held_experts {self.held_experts} outside the "
                f"{self.n_routed_experts} routed experts")
        if not 1 <= self.num_experts_per_tok <= self.n_routed_experts:
            raise ValueError("num_experts_per_tok outside "
                             "[1, n_routed_experts]")
        if self.layer_impl != "loop":
            raise ValueError("LatentMoEConfig: layer_impl='loop' only (the "
                             "layers differ, there is no scan form)")
        if self.index_topk < 1 or self.sliding_window < 1:
            raise ValueError("index_topk and sliding_window must be >= 1")
        if self.lora_rescale and self.q_lora_rank is None:
            raise ValueError("lora_rescale needs a query latent "
                             "(q_lora_rank)")
        if self.attention_impl not in ("auto", "pallas", "xla"):
            raise ValueError(f"attention_impl {self.attention_impl!r}: "
                             f"auto, pallas or xla")

    @property
    def trains(self) -> bool:
        """Whether the uncached training forward covers this schedule:
        every layer full, with no indexer (dense causal attention)."""
        return not self.index_n_heads and not self.sliding_layers

    def mixer(self, kind: str) -> dict:
        """The latent-attention sizes of a layer kind."""
        if kind == "full":
            return dict(heads=self.n_heads, q_rank=self.q_lora_rank,
                        kv_rank=self.kv_lora_rank,
                        nope=self.qk_nope_head_dim,
                        rope=self.qk_rope_head_dim, v=self.v_head_dim,
                        theta=self.rope_theta)
        return dict(heads=self.swa_n_heads, q_rank=self.swa_q_lora_rank,
                    kv_rank=self.swa_kv_lora_rank,
                    nope=self.swa_qk_nope_head_dim,
                    rope=self.swa_qk_rope_head_dim, v=self.swa_v_head_dim,
                    theta=self.swa_rope_theta)

    @property
    def full_layers(self) -> tuple:
        return tuple(i for i, k in enumerate(self.layer_types)
                     if k == "full")

    @property
    def sliding_layers(self) -> tuple:
        return tuple(i for i, k in enumerate(self.layer_types)
                     if k == "sliding")

    @property
    def window_ring(self) -> int:
        """Rows a slot keeps of a sliding layer: the window, rounded up."""
        return -(-self.sliding_window // 16) * 16

    @property
    def rebuild_span(self) -> int:
        """Positions before a resume point that a prefill recomputes so
        that every sliding layer's window is exact at the point: each
        sliding layer needs its input exact ``sliding_window - 1`` further
        back than its output."""
        return (self.sliding_window - 1) * len(self.sliding_layers)

    def replace(self, **kw) -> "LatentMoEConfig":
        return dataclasses.replace(self, **kw)


PRESETS = {
    # Exact reference trainer shape (ref: train.py:43-53); ~8.05B params at
    # the Mistral-Nemo vocab of 131072.
    "llama3-8b": TransformerConfig(
        dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        ffn_dim_multiplier=1.3, multiple_of=1024, rope_theta=500000.0,
        vocab_size=131072, seq_len=2048,
    ),
    # BASELINE.json headline config: GPT-2-125M-class decoder in the same
    # Llama-style architecture family (SwiGLU/RoPE/RMSNorm).
    "gpt2-125m": TransformerConfig(
        dim=768, n_layers=12, n_heads=12, n_kv_heads=12,
        multiple_of=256, rope_theta=10000.0, vocab_size=50257, seq_len=2048,
    ),
    # Hermetic-test shape.
    "tiny": TransformerConfig(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        multiple_of=32, rope_theta=10000.0, vocab_size=512, seq_len=128,
    ),
    # Hermetic 4-layer shape: a speculative-decoding target ("tiny" is its
    # natural draft).
    "tiny-4l": TransformerConfig(
        dim=64, n_layers=4, n_heads=4, n_kv_heads=2,
        multiple_of=32, rope_theta=10000.0, vocab_size=512, seq_len=128,
    ),
    # Hermetic MoE shape (models/moe.py): 4 experts, top-2 routing.
    "tiny-moe": TransformerConfig(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        multiple_of=32, rope_theta=10000.0, vocab_size=512, seq_len=128,
        moe_experts=4, moe_top_k=2,
    ),
    # Hermetic shape of the latent-attention / indexer / window / expert
    # class (models/latent_moe.py): every mechanism at a size the CPU tests
    # can pass (top-k 8 and window 9 under contexts of a few dozen tokens).
    "tiny-latent-moe": LatentMoEConfig(
        dim=64, n_layers=5, n_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        index_n_heads=2, index_head_dim=16, index_topk=8,
        swa_n_heads=2, swa_q_lora_rank=32, swa_kv_lora_rank=24,
        swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
        sliding_window=9, dense_hidden_dim=96, moe_hidden_dim=32,
        n_routed_experts=8, held_experts=(0, 4), num_experts_per_tok=2,
        vocab_size=512, seq_len=128,
    ),
    # Hermetic shape of the class as it trains: no query latent, no
    # indexer, no head gate, no latent rescale, two shared experts, 4 of
    # 16 routed experts held, top-3 (the shape of the kanana2 cell's
    # configuration at a size the CPU tests can pass).
    "tiny-latent-train": LatentMoEConfig(
        dim=64, n_layers=3, layer_types=("full",) * 3, norm_eps=1e-6,
        n_heads=4, q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e6,
        index_n_heads=0, lora_rescale=False, head_gate=False,
        dense_hidden_dim=96, moe_hidden_dim=32, n_routed_experts=16,
        held_experts=(0, 4), num_experts_per_tok=3, n_shared_experts=2,
        routed_scaling_factor=2.448, vocab_size=512, seq_len=128,
    ),
}


def get_config(name: str, **overrides):
    if name not in PRESETS:
        raise ValueError(f"unknown model preset {name!r}; have {sorted(PRESETS)}")
    return PRESETS[name].replace(**overrides)
