"""Model configurations.

Sizes follow the published architectures for the model families named in
BASELINE.json (llama3.1 tags served via Ollama in the reference —
README.md:52, web/streamlit_app.py:28 — and Mixtral-8x7B for config 5).
``tiny``/``tiny-moe`` are test/CI sizes exercising the exact same code.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional


@dataclass(frozen=True)
class RopeScaling:
    """How the rotary table is stretched past the trained context; ``kind``
    names the rule (models/layers.rope_table computes both):

    - ``"llama3"``: llama3.1's NTK-by-parts: wavelengths past
      ``original_max_position / low_freq_factor`` slowed by ``factor``,
      those under ``original_max_position / high_freq_factor`` kept, a
      ramp between.
    - ``"yarn"``: a blend by frequency INDEX: index ``i`` below ``low``
      keeps its frequency, above ``high`` has it divided by ``factor``,
      a linear ramp between, ``low`` / ``high`` the indices that turn
      ``beta_fast`` / ``beta_slow`` times over ``original_max_position``
      (floor / ceil). cos and sin are both multiplied by
      ``attention_factor`` (0: ``0.1 ln(factor) + 1``), so q.k carries
      its square.
    """

    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position: int = 8192
    kind: str = "llama3"
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 0.0


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    # The rule of the layers that read their whole context. A window
    # layer (``w`` of a hybrid pattern) rotates by the plain table of
    # ``rope_theta`` whatever this says: its keys are never further than
    # ``sliding_window`` from their query (Mellum's ``rope_parameters``:
    # ``sliding_attention`` default, ``full_attention`` YaRN).
    rope_scaling: Optional[RopeScaling] = None
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # MoE (0 experts => dense MLP)
    num_experts: int = 0
    num_experts_per_tok: int = 0
    # GShard-style per-expert capacity factor for large prefill chunks:
    # bucket C = factor * tokens * k / num_experts. None = exact/dropless
    # (models/mixtral.py moe_mlp; decode is always exact).
    moe_capacity_factor: Optional[float] = None
    # Renormalise the kept top-k router weights to sum to 1 (Mixtral).
    # False keeps the softmax's own weights (OLMoE: norm_topk_prob false).
    moe_renormalize: bool = True
    # RMSNorm with learned weights over the WHOLE q and k projections
    # (all heads together), between the projection and the split into
    # heads, before RoPE (OLMoE). Leaves q_norm [L, q_dim], k_norm
    # [L, kv_dim].
    qk_norm_whole: bool = False
    # RMSNorm over EACH head's ``head_dim`` numbers, one learned vector
    # for q and one for k a layer, shared by the heads, between the split
    # into heads and RoPE (LFM2; a hybrid pattern's ``*`` layers). Leaves
    # q_norm, k_norm [L, head_dim].
    qk_norm_head: bool = False
    # Latent attention (MLA; models/pangu.py). ``kv_lora_rank`` > 0
    # selects the family: queries through a low-rank pair (``q_lora_rank``),
    # one latent row a token (``kv_lora_rank`` normed numbers and
    # ``qk_rope_head_dim`` rotated ones) shared by every head, expanded
    # per head into ``qk_nope_head_dim`` key and ``v_head_dim`` value
    # numbers. ``head_dim`` is then the width of q.k (nope + rope) and
    # ``num_kv_heads`` 1: the cache's geometry is ``cache_*`` below.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # RMSNorms after the attention and after the MLP too, before each
    # residual add (four norms a layer): models/pangu.py's layers, and
    # models/llama.py's (leaves ``attn_out_norm`` / ``mlp_out_norm``).
    sandwich_norm: bool = False
    # A looped stack (Ouro's ``total_ut_steps``; models/llama.py's
    # ``_walk``): the ``num_layers`` layers run this many times a token
    # with the same weights. The final norm runs after EVERY pass and its
    # output enters the next; pass ``t``, layer ``l`` keeps its own K and V
    # (cache layer ``t * num_layers + l``: ``cache_layers``); an exit
    # gate (leaves ``exit_gate_w`` / ``exit_gate_b``) reads each pass's
    # output, and the logits are the last pass's whatever it says.
    ut_steps: int = 1
    # Leading dense layers (SwiGLU of ``dense_intermediate_size``) before
    # the routed ones; ``intermediate_size`` is then one expert's width.
    first_k_dense: int = 0
    dense_intermediate_size: int = 0
    # Shared experts (each of ``intermediate_size``) added to every token.
    num_shared_experts: int = 0
    # Experts the router scores. ``num_experts`` of them, ids [0,
    # num_experts), are held here; a pair routed to another is some other
    # chip's work and adds nothing here. 0 = ``num_experts`` (all held).
    moe_router_width: int = 0
    # ``softmax`` over all experts (Mixtral, OLMoE) or ``sigmoid`` of
    # each logit; ``routed_scaling_factor`` multiplies the kept weights.
    moe_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    # What ``moe_renormalize`` adds to the kept weights' sum before it
    # divides by it (LFM2 publishes 1e-6).
    moe_renorm_eps: float = 1e-20
    # Hybrid stack (models/nemotron_h.py). ``hybrid_pattern`` selects the
    # family: one letter a layer, each layer ONE mixer behind a pre-norm
    # and a residual: ``M`` a Mamba-2 layer, ``E`` a routed feed-forward
    # layer, ``*`` an attention layer. ``num_layers`` is its length. Only
    # the ``*`` layers hold pages (``cache_layers``), each its own page
    # layer, read by itself alone (Mellum: one of every four attention
    # layers) unless ``x`` layers above it read it too; an ``M`` layer keeps
    # per row a float32 state [mamba_num_heads, mamba_head_dim,
    # ssm_state_size] and the last ``conv_kernel - 1`` inputs of its
    # convolution (ops/state_pool.py).
    hybrid_pattern: str = ""
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_groups: int = 1          # B and C are shared by heads // groups
    conv_kernel: int = 4
    ssm_chunk: int = 128         # block of the chunked (SSD) scan
    # The experts' MLP: ``silu`` = gated SwiGLU (gate|up then down),
    # ``relu2`` = ungated ``relu(x U)^2 D``.
    mlp_activation: str = "silu"
    # Experts that live in a latent of this width, reached through one
    # shared down-projection before and one up-projection after them
    # (0: the experts read the hidden state; a hybrid's ``E`` is then the
    # plain routed layer, ``wgu_e`` / ``w_down`` and no shared expert).
    moe_latent_size: int = 0
    # Width of the shared expert when it is not ``intermediate_size``.
    shared_intermediate_size: int = 0
    # A learned bias added to the router's scores for the CHOICE of the
    # top-k only; the kept weights are the unbiased scores.
    moe_selection_bias: bool = False
    # False: attention without rotary embedding (position comes from the
    # recurrent layers). True in a hybrid pattern: ``w`` and ``*`` layers
    # rotate q and k, each kind by its own table (``rope_scaling``).
    attn_rope: bool = True
    # Further layer kinds of the hybrid walk (models/nemotron_h.py; the
    # SambaY stack of Phi-4-mini-flash). In ``hybrid_pattern``: ``1`` a
    # Mamba-1 layer (``Y`` one that also publishes its scan output, the
    # memory the ``g`` layers above it gate), ``w`` attention over the
    # last ``sliding_window`` positions (a ring a row in the state pool,
    # no pages: differential under ``attn_diff``, else plain GQA, whose
    # ring keeps its KV heads apart, ``cache_kv_heads`` x ``cache_k_dim``),
    # ``g`` a gated memory unit, ``x`` attention whose K and V
    # are the ``*`` layer's below it (it owns neither), ``-`` a dense
    # gated MLP of ``dense_intermediate_size`` (0: ``intermediate_size``).
    # A published layer of that family is two letters, its mixer and
    # ``-``.
    # ``c`` a gated short convolution (LFM2): ``[B | C | x] = u W_in``,
    # ``out = (C * conv(B * x)) W_out`` with a causal depthwise
    # convolution over ``conv_kernel`` positions, no bias, no activation;
    # per row it keeps the last ``conv_kernel - 1`` values of ``B * x``
    # (``hidden_size`` wide) in the state pool's ``conv`` rows and no
    # recurrent state behind them.
    # ``norm_kind`` "layer": biased LayerNorm in place of RMSNorm.
    norm_kind: str = "rms"
    attn_bias: bool = False          # biases on the q/k/v and output proj.
    # Differential attention: heads paired, two softmaxes a pair, the
    # second subtracted ``lam`` times, over both value heads of the KV
    # pair (ops/diff_attention.py).
    attn_diff: bool = False
    sliding_window: int = 0          # keys a ``w`` layer's query reads
    # Mamba-1: channels, state numbers a channel, rank of the time step.
    mamba1_inner: int = 0
    mamba1_state: int = 0
    mamba1_dt_rank: int = 0
    # ``s`` in ``hybrid_pattern``: attention whose queries read only the
    # ``index_topk`` keys a learned indexer picks (DeepSeek-V3.2's
    # "lightning indexer" over GQA: Keye-VL-2.0). ``index_heads`` index
    # queries of ``index_head_dim`` numbers and one weight each a token,
    # against ONE index key a token; the key is a third kind of per-token
    # past, kept beside K and V in the layer's pages (``PagedKVCache.idx``,
    # one head of ``cache_idx_dim`` lanes). A page layer like ``*``
    # (``cache_layers`` counts both).
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # Four scalars of a hybrid pattern (the Granite 4.0-H family's muP
    # parameterisation; models/nemotron_h.py applies them, each default
    # leaves a program the text it was): the embedding's rows times
    # ``embedding_multiplier``; every residual add ``h + residual_multiplier
    # x out``, a mixer's and an MLP's alike; the softmax scale
    # ``attention_multiplier`` in place of ``1 / sqrt(head_dim)`` (0: that
    # default), folded into the queries so that the prefill attention and
    # both decode paths read it alike; the head's logits divided by
    # ``logits_scaling``, before anything samples them.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    # token ids (llama3 defaults; byte tokenizer overrides)
    bos_token_id: int = 128000
    eos_token_ids: tuple[int, ...] = (128001, 128008, 128009)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def is_latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def is_hybrid(self) -> bool:
        return bool(self.hybrid_pattern)

    @property
    def ssm_layers(self) -> int:
        """Layers with a recurrent state: Mamba-2 (``M``) or Mamba-1."""
        p = self.hybrid_pattern
        return p.count("M") + p.count("1") + p.count("Y")

    @property
    def short_conv_layers(self) -> int:
        """Gated short convolutions (``c``): a convolution window a row
        and nothing recurrent."""
        return self.hybrid_pattern.count("c")

    @property
    def conv_layers(self) -> int:
        """Layers with rows in the state pool's ``conv``: the recurrent
        ones' convolution, or the ``c`` layers' (a pattern has one kind
        or the other, models/nemotron_h._build)."""
        return self.ssm_layers + self.short_conv_layers

    @property
    def window_layers(self) -> int:
        """Layers that keep a ring of ``sliding_window`` positions a row."""
        return self.hybrid_pattern.count("w")

    @property
    def state_layers(self) -> int:
        """Layers whose per-row past is a row of the state pool
        (ops/state_pool.py) and not pages: recurrent ones, short
        convolutions and window rings. 0: the caches carry no
        ``state``."""
        return self.conv_layers + self.window_layers

    @property
    def state_kinds(self) -> str:
        """``state_layers`` in words, for a refusal or a log line."""
        return " and ".join(
            f"{what} ({n} {of})" for n, what, of in (
                (self.ssm_layers, "recurrent state", "Mamba layers"),
                (self.short_conv_layers, "convolution windows", "layers"),
                (self.window_layers, "window rings", "layers")) if n)

    @property
    def ssm_state_shape(self) -> tuple:
        """One row's float32 state in one recurrent layer. Mamba-1's has
        its channels minor (ops/state_pool.py says why)."""
        if self.mamba1_inner:
            return (self.mamba1_state, self.mamba1_inner)
        return (self.mamba_num_heads, self.mamba_head_dim,
                self.ssm_state_size)

    @property
    def routed_layers(self) -> int:
        """Layers with a routed MLP: a hybrid's ``E``, else every layer
        past the leading dense ones (0 for a dense model)."""
        if not self.is_moe:
            return 0
        if self.is_hybrid:
            return self.hybrid_pattern.count("E")
        return self.num_layers - self.first_k_dense

    @property
    def cache_layers(self) -> int:
        """Layers that hold pages: all of them, once a pass of a looped
        stack (``ut_steps``), or a hybrid's ``*`` and ``s``."""
        if self.is_hybrid:
            return (self.hybrid_pattern.count("*")
                    + self.hybrid_pattern.count("s"))
        return self.num_layers * self.ut_steps

    @property
    def is_indexed(self) -> bool:
        """Page layers that keep an index key a token beside K and V."""
        return "s" in self.hybrid_pattern

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x | B | C (Mamba-2), x
        alone (Mamba-1), ``B * x`` (a ``c`` layer)."""
        if self.short_conv_layers:
            return self.hidden_size
        if self.mamba1_inner:
            return self.mamba1_inner
        return self.mamba_inner + 2 * self.ssm_groups * self.ssm_state_size

    # What one token holds in a KV cache (models/llama.KVCache,
    # ops/paged_kv.PagedKVCache): ``cache_kv_heads`` rows of
    # ``cache_k_dim`` numbers in ``k`` and of ``cache_v_dim`` in ``v``.
    # Per-head keys and values, or for a latent model the normed latent
    # in ``k`` and the rotated shared key in ``v``, the latter padded to
    # whole 128-lane tiles (a 64-wide minor dimension makes every pool
    # write relayout the array, and no page DMA can be aligned to it).
    # Differential attention caches ALL its KV heads side by side as one
    # row a position (ops/diff_attention.py says why: a query reads its
    # pair's two value heads together, a head of 64 is half a lane tile,
    # and a second-minor dimension of 10 or 20 heads is padded to 32 in
    # an int8 array); an int8 scale is then one a position.
    # A hybrid pattern's plain GQA at a head of 64 (LFM2) caches its KV
    # heads in PAIRS side by side (``kv_paired``: heads 2g and 2g + 1 are
    # row g, 128 numbers, an int8 scale a pair a position): whole lanes
    # for every write and page DMA, and the geometry the flash-append
    # kernel takes, which reads a pair's row with its queries
    # zero-extended onto their own half
    # (ops/paged_attention.paged_attention_append_paired).
    @property
    def kv_paired(self) -> bool:
        return (self.is_hybrid and not self.attn_diff
                and self.head_dim == 64 and self.num_kv_heads % 2 == 0)

    @property
    def cache_kv_heads(self) -> int:
        if self.attn_diff or self.is_latent:
            return 1
        return self.num_kv_heads // 2 if self.kv_paired \
            else self.num_kv_heads

    @property
    def cache_k_dim(self) -> int:
        if self.attn_diff:
            return self.kv_dim
        if self.kv_paired:
            return 2 * self.head_dim
        return self.kv_lora_rank if self.is_latent else self.head_dim

    @property
    def cache_v_dim(self) -> int:
        if self.is_latent:
            return -(-self.qk_rope_head_dim // 128) * 128
        return self.cache_k_dim if self.attn_diff or self.kv_paired \
            else self.head_dim

    @property
    def cache_idx_dim(self) -> int:
        """Lanes of an index key's row in the caches: ``index_head_dim``
        padded to whole 128-lane tiles, as a latent model's rotated key
        is (``cache_v_dim``; a 64-wide minor dimension makes every pool
        write relayout the array: the compiled decode step of such a pool
        copied it whole in every layer). 0: no index keys."""
        return -(-self.index_head_dim // 128) * 128 if self.is_indexed else 0

    @property
    def router_width(self) -> int:
        return self.moe_router_width or self.num_experts

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)


_LLAMA31_SCALING = RopeScaling(factor=8.0, low_freq_factor=1.0,
                               high_freq_factor=4.0, original_max_position=8192)

CONFIGS: dict[str, ModelConfig] = {}


def _register(cfg: ModelConfig) -> ModelConfig:
    CONFIGS[cfg.name] = cfg
    return cfg


# -- llama family ------------------------------------------------------------

_register(ModelConfig(
    name="llama3.1-8b", vocab_size=128256, hidden_size=4096,
    intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
    head_dim=128, rope_theta=500000.0, rope_scaling=_LLAMA31_SCALING,
))

_register(ModelConfig(
    name="llama3.1-70b", vocab_size=128256, hidden_size=8192,
    intermediate_size=28672, num_layers=80, num_heads=64, num_kv_heads=8,
    head_dim=128, rope_theta=500000.0, rope_scaling=_LLAMA31_SCALING,
))

_register(ModelConfig(
    name="llama3.2-1b", vocab_size=128256, hidden_size=2048,
    intermediate_size=8192, num_layers=16, num_heads=32, num_kv_heads=8,
    head_dim=64, rope_theta=500000.0, rope_scaling=RopeScaling(factor=32.0),
    tie_embeddings=True,
))

_register(ModelConfig(
    name="llama3.2-3b", vocab_size=128256, hidden_size=3072,
    intermediate_size=8192, num_layers=28, num_heads=24, num_kv_heads=8,
    head_dim=128, rope_theta=500000.0, rope_scaling=RopeScaling(factor=32.0),
    tie_embeddings=True,
))

# -- Mixtral -----------------------------------------------------------------

_register(ModelConfig(
    name="mixtral-8x7b", vocab_size=32000, hidden_size=4096,
    intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
    head_dim=128, rope_theta=1e6, num_experts=8, num_experts_per_tok=2,
    moe_capacity_factor=2.0,
    bos_token_id=1, eos_token_ids=(2,), max_seq_len=32768,
))

# OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct config.json): 64 thin
# experts, top-8 with the softmax's own weights, whole-projection
# QK-norm, MHA. 6.92 G parameters, 7.0 GB int8: whole on one 16 GB chip.
# ``intermediate_size`` is ONE expert's width, as published. No
# ``moe_capacity_factor``: dropless, as published (a prefill bucket holds
# every token of its chunk, 8 / 64 of it used on average). A 64-way
# router is too uneven for a bounded bucket: under the benchmark's random
# weights factor 2.0 dropped 25% of the prefill's routed pairs and 4.0
# still 8% (PERF.md section 6, PR 26; Mixtral's 8 experts at 2.0: 0.05%).
_register(ModelConfig(
    name="olmoe-1b-7b", vocab_size=50304, hidden_size=2048,
    intermediate_size=1024, num_layers=16, num_heads=16, num_kv_heads=16,
    head_dim=128, rope_theta=10000.0, rms_norm_eps=1e-5,
    num_experts=64, num_experts_per_tok=8,
    moe_renormalize=False, qk_norm_whole=True,
    bos_token_id=1, eos_token_ids=(50279,), max_seq_len=4096,
))

# ~7.3B-total MoE config for single-chip benching at REAL expert scale:
# each expert is 3*4096*11520 ≈ 141.6M params — 16.4x bench-moe's 8.65M,
# Mixtral-8x7B-class expert width at Mixtral's 8-expert top-2 routing —
# with depth cut to 6 layers so the streamed quantized load fits a 16 GB
# chip next to its KV pool (int8 ≈ 7.3 GB, int4 ≈ 3.9 GB incl. group
# scales; 32 layers of these experts would be a 37B model, BASELINE.json
# config-5 territory — multi-chip). The per-layer MoE arithmetic the
# round-18 bench measures (expert weight streaming, wgu_e fusion,
# dispatch overheads) is layer-count-invariant, so 6 honest layers beat
# 32 unloadable ones. intermediate 11520 = 45*256 = 90*128: divisible
# for the expert-stripe kernels in BOTH int4 groupings (group 256 at
# ng=45 — the odd-count segment walk — and group 128 at ng=90), and by
# every w8a16 block candidate via 128.
_register(ModelConfig(
    name="mixtral-large", vocab_size=32000, hidden_size=4096,
    intermediate_size=11520, num_layers=6, num_heads=32, num_kv_heads=8,
    head_dim=128, rope_theta=1e6, num_experts=8, num_experts_per_tok=2,
    moe_capacity_factor=2.0,
    bos_token_id=1, eos_token_ids=(2,), max_seq_len=8192,
))

# -- test sizes (same code paths, CI-sized) ----------------------------------

_register(ModelConfig(
    name="tiny", vocab_size=512, hidden_size=128, intermediate_size=256,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32, max_seq_len=256,
    rope_theta=10000.0, bos_token_id=1, eos_token_ids=(2,),
))

_register(ModelConfig(
    name="tiny-moe", vocab_size=512, hidden_size=128, intermediate_size=256,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32, max_seq_len=256,
    rope_theta=10000.0, num_experts=4, num_experts_per_tok=2,
    bos_token_id=1, eos_token_ids=(2,),
))

# OLMoE's block at test size: MHA, QK-norm, top-4 of 8 without
# renormalisation.
_register(ModelConfig(
    name="tiny-olmoe", vocab_size=512, hidden_size=128,
    intermediate_size=64, num_layers=2, num_heads=4, num_kv_heads=4,
    head_dim=32, max_seq_len=256, rope_theta=10000.0,
    num_experts=8, num_experts_per_tok=4, moe_renormalize=False,
    qk_norm_whole=True, bos_token_id=1, eos_token_ids=(2,),
))

# openPangu-Ultra-MoE-718B (FreedomIntelligence/openPangu-Ultra-MoE-718B
# config.json), ONE chip's share of it: 16 chips share each layer (16 of
# the 256 routed experts held, the router 256 wide, top-8 over all;
# attention and the shared expert whole), 8 vocabulary slices (19,200
# ids), 8 pipeline stages of which this is a chip of the first (one of
# the three leading dense layers, 8 routed layers). No width, head
# count, rank, top-k or router width is cut. MLA: 9.1 GB int8.
_register(ModelConfig(
    name="openpangu-ultra-moe-718b-l9e16", vocab_size=19200,
    hidden_size=7680, intermediate_size=2048, num_layers=9, num_heads=128,
    num_kv_heads=1, head_dim=192, max_seq_len=131072, rope_theta=25600000.0,
    rms_norm_eps=1e-5, num_experts=16, num_experts_per_tok=8,
    moe_renormalize=True, q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    sandwich_norm=True, first_k_dense=1, dense_intermediate_size=18432,
    num_shared_experts=1, moe_router_width=256, moe_scoring="sigmoid",
    routed_scaling_factor=2.5,
    # The catalog's config.json has no token ids; the serving tokenizer
    # supplies its own stop id.
    bos_token_id=1, eos_token_ids=(2,),
))

# The same block at test size: MLA, sandwich norms, one dense layer,
# then routed layers holding 4 of 16 sigmoid-scored experts (top-4)
# beside a shared one.
_register(ModelConfig(
    name="tiny-pangu", vocab_size=512, hidden_size=128,
    intermediate_size=64, num_layers=3, num_heads=4, num_kv_heads=1,
    head_dim=48, max_seq_len=256, rope_theta=10000.0,
    num_experts=4, num_experts_per_tok=4, moe_renormalize=True,
    q_lora_rank=48, kv_lora_rank=64, qk_nope_head_dim=32,
    qk_rope_head_dim=16, v_head_dim=32, sandwich_norm=True,
    first_k_dense=1, dense_intermediate_size=256, num_shared_experts=1,
    moe_router_width=16, moe_scoring="sigmoid", routed_scaling_factor=2.5,
    bos_token_id=1, eos_token_ids=(2,),
))

# NVIDIA-Nemotron-3-Super-120B-A12B (nvidia/NVIDIA-Nemotron-3-Super-120B-
# A12B-BF16 config.json, model_type nemotron_h), ONE chip's share of it:
# the first of 4 pipeline stages (22 of 88 layers, two periods of the
# 5:5:1 pattern: 10 Mamba-2, 10 LatentMoE, 2 attention), whose 4 chips
# share each layer: 128 of the 512 routed experts held (the router 512
# wide, top-22 over all), Mamba, attention and the shared expert whole,
# a quarter of the vocabulary. No width, head count, state size, latent
# size, top-k or router width is cut. About 9.2 GB int8.
_register(ModelConfig(
    name="nemotron-3-super-120b-a12b-l22e128", vocab_size=32768,
    hidden_size=4096, intermediate_size=2688, num_layers=22, num_heads=32,
    num_kv_heads=2, head_dim=128, max_seq_len=262144, rope_theta=10000.0,
    rms_norm_eps=1e-5, hybrid_pattern="MEMEMEM*EMEMEMEM*EMEME",
    mamba_num_heads=128, mamba_head_dim=64, ssm_state_size=128,
    ssm_groups=8, conv_kernel=4, ssm_chunk=128, num_experts=128,
    num_experts_per_tok=22, moe_router_width=512, moe_scoring="sigmoid",
    moe_renormalize=True, routed_scaling_factor=5.0,
    moe_selection_bias=True, mlp_activation="relu2", moe_latent_size=1024,
    num_shared_experts=1, shared_intermediate_size=5376, attn_rope=False,
    bos_token_id=1, eos_token_ids=(2,),
))

# NVIDIA-Nemotron-3-Super-120B-A12B's three kinds of layer at test size
# (models/nemotron_h.py): Mamba-2 (8 heads x 16, 2 groups, state 16),
# GQA attention without rotary embedding, and a LatentMoE layer holding
# 4 of 16 sigmoid-scored experts (top-3, selection bias, relu^2, latent
# 64) beside a shared expert. The pattern has a lone M, an ME run, an EM
# run and a trailing E, as the benchmark's 22-layer cut has.
_register(ModelConfig(
    name="tiny-nemotron-h", vocab_size=512, hidden_size=128,
    intermediate_size=96, num_layers=11, num_heads=4, num_kv_heads=2,
    head_dim=32, max_seq_len=256, rope_theta=10000.0,
    hybrid_pattern="MEMEM*EMEME", mamba_num_heads=8, mamba_head_dim=16,
    ssm_state_size=16, ssm_groups=2, conv_kernel=4, ssm_chunk=16,
    num_experts=4, num_experts_per_tok=3, moe_router_width=16,
    moe_scoring="sigmoid", moe_renormalize=True, routed_scaling_factor=5.0,
    moe_selection_bias=True, mlp_activation="relu2", moe_latent_size=64,
    num_shared_experts=1, shared_intermediate_size=192, attn_rope=False,
    bos_token_id=1, eos_token_ids=(2,),
))

# Phi-4-mini-flash-reasoning (microsoft/Phi-4-mini-flash-reasoning
# config.json, model_type phi4flash), whole: 32 published layers, each a
# mixer and a gated MLP behind biased LayerNorms. Layers 0-15 alternate
# Mamba-1 and 512-token window attention, 16 is the Mamba-1 layer whose
# scan output every gated memory unit above reads, 17 the one full
# attention layer, the only one that holds pages, and 18-31 alternate
# gated memory units and cross attention over layer 17's pages.
# Differential attention, no positional encoding. 3.85 G parameters.
PHI4FLASH_PATTERN = "1-w-" * 8 + "Y-*-" + "g-x-" * 7

_register(ModelConfig(
    name="phi-4-mini-flash-reasoning", vocab_size=200064, hidden_size=2560,
    intermediate_size=10240, num_layers=32, num_heads=40, num_kv_heads=20,
    head_dim=64, max_seq_len=262144, rms_norm_eps=1e-5,
    tie_embeddings=True, hybrid_pattern=PHI4FLASH_PATTERN,
    norm_kind="layer", attn_bias=True, attn_diff=True, attn_rope=False,
    sliding_window=512, mamba1_inner=5120, mamba1_state=16,
    mamba1_dt_rank=160, conv_kernel=4,
    bos_token_id=1, eos_token_ids=(2,),
))

# The same eight kinds of step at test size: 8 published layers, so that
# 0-3 are the lower half (two Mamba-1 / window pairs: one scan), 4
# publishes the memory, 5 is the full layer, 6 a gated memory unit and 7
# a cross layer; window 8.
_register(ModelConfig(
    name="tiny-phi4flash", vocab_size=512, hidden_size=128,
    intermediate_size=256, num_layers=8, num_heads=8, num_kv_heads=4,
    head_dim=16, max_seq_len=256, rms_norm_eps=1e-5, tie_embeddings=True,
    hybrid_pattern="1-w-1-w-Y-*-g-x-", norm_kind="layer", attn_bias=True,
    attn_diff=True, attn_rope=False, sliding_window=8, mamba1_inner=256,
    mamba1_state=16, mamba1_dt_rank=8, conv_kernel=4,
    bos_token_id=1, eos_token_ids=(2,),
))

# Mellum2-12B-A2.5B-Instruct (JetBrains/Mellum2-12B-A2.5B-Instruct
# config.json, model_type mellum) at test size: two periods of three
# window layers to one full layer, each followed by a routed layer that
# reads the hidden state (8 softmax-routed SwiGLU experts, the 2 largest
# renormalised, no shared expert); GQA 4 / 2 heads rotated over the whole
# head, the window layers by the plain table and the full ones by YaRN
# (factor 4 over an original 16, betas 2 and 0.02: of 8 frequencies the
# first is kept, four are blended and three divided by 4), so a 40-token
# sequence wraps the window of 8 four times and leaves the original 16.
MELLUM_PERIOD = "wEwEwE*E"

_register(ModelConfig(
    name="tiny-mellum2", vocab_size=512, hidden_size=64,
    intermediate_size=32, num_layers=16, num_heads=4, num_kv_heads=2,
    head_dim=16, max_seq_len=256, rope_theta=10000.0, rms_norm_eps=1e-6,
    rope_scaling=RopeScaling(kind="yarn", factor=4.0,
                             original_max_position=16, beta_fast=2.0,
                             beta_slow=0.02),
    hybrid_pattern=MELLUM_PERIOD * 2, sliding_window=8, num_experts=8,
    num_experts_per_tok=2, moe_renormalize=True,
    bos_token_id=1, eos_token_ids=(2,),
))

# LFM2-8B-A1B (LiquidAI/LFM2-8B-A1B config.json, model_type lfm2_moe),
# whole: 24 published layers, each an operator and a feed-forward behind
# RMSNorms. Eighteen operators are gated short convolutions (a window of
# ``conv_L_cache - 1`` = 2 positions a row and no pages), six are GQA
# (32 / 8 heads x 64, per-head QK-norm, rotated, theta 1e6) at layers 2,
# 6, 10, 14, 18, 21 and own pages, KV heads in pairs (``kv_paired``).
# The first two feed-forwards are dense SwiGLU of 7,168, the other 22
# route over 32 SwiGLU experts of 1,792: sigmoid scores, the 4 largest
# of score + bias kept, weighed by their unbiased scores over (their sum
# + 1e-6). Tied head. 8.34 G parameters, about 1.5 G a token.
LFM2_PATTERN = "c-c-*E" + "cEcEcE*E" * 4 + "cEcE*E" + "cEcE"

_register(ModelConfig(
    name="lfm2-8b-a1b", vocab_size=65536, hidden_size=2048,
    intermediate_size=1792, dense_intermediate_size=7168, num_layers=48,
    num_heads=32, num_kv_heads=8, head_dim=64, max_seq_len=128000,
    rope_theta=1e6, rms_norm_eps=1e-5, tie_embeddings=True,
    hybrid_pattern=LFM2_PATTERN, conv_kernel=3, qk_norm_head=True,
    num_experts=32, num_experts_per_tok=4, moe_scoring="sigmoid",
    moe_renormalize=True, moe_renorm_eps=1e-6, moe_selection_bias=True,
    routed_scaling_factor=1.0, bos_token_id=1, eos_token_ids=(2,),
))

# The same kinds of step at test size, with the head (two dense layers),
# three whole periods and the short tail the published pattern has (one
# scan over the five, models/nemotron_h._segments): 15 published layers,
# attention at 2, 5, 8, 11, 13; a
# head of 64 (8 / 4 heads) so that the pool keeps two pairs of KV heads;
# 8 experts, the 2 largest of score + bias.
_register(ModelConfig(
    name="tiny-lfm2", vocab_size=512, hidden_size=128,
    intermediate_size=64, dense_intermediate_size=192, num_layers=30,
    num_heads=8, num_kv_heads=4, head_dim=64, max_seq_len=256,
    rope_theta=10000.0, rms_norm_eps=1e-5, tie_embeddings=True,
    hybrid_pattern="c-c-*E" + "cEcE*E" * 3 + "cE*E" + "cE", conv_kernel=3,
    qk_norm_head=True, num_experts=8, num_experts_per_tok=2,
    moe_scoring="sigmoid", moe_renormalize=True, moe_renorm_eps=1e-6,
    moe_selection_bias=True, bos_token_id=1, eos_token_ids=(2,),
))

# Keye-VL-2.0-30B-A3B's language model (Kwai-Keye/Keye-VL-2.0-30B-A3B
# config.json) at test size: four layers of GQA 4 / 2 heads x 32 whose
# queries read the 16 keys an indexer of 4 heads x 16 picks (``s``), per-
# head QK-norm, the plain rotate-half table, each followed by a routed
# layer of 8 softmax-routed SwiGLU experts, the 2 largest renormalised.
# 16 of 48-96 positions: the selection binds from position 16 on.
_register(ModelConfig(
    name="tiny-keye", vocab_size=512, hidden_size=64,
    intermediate_size=32, num_layers=8, num_heads=4, num_kv_heads=2,
    head_dim=32, max_seq_len=256, rope_theta=10000.0, rms_norm_eps=1e-6,
    hybrid_pattern="sE" * 4, qk_norm_head=True, index_heads=4,
    index_head_dim=16, index_topk=16, num_experts=8,
    num_experts_per_tok=2, moe_renormalize=True,
    bos_token_id=1, eos_token_ids=(2,),
))

# Granite-4.0-H-Micro (ibm-granite/granite-4.0-h-micro config.json,
# model_type granitemoehybrid, no routed part), whole: 40 published
# layers, each a mixer and a dense SwiGLU MLP of 8,192 behind RMSNorms.
# Thirty-six mixers are Mamba-2 (64 heads x 64, ONE group, state 128, a
# convolution with a bias over 4,352 channels, SSD block 256), four are
# GQA 32 / 8 x 64 without positional encoding at layers 5, 15, 25, 35
# (KV heads in pairs, ``kv_paired``). Four scalars: embedding x 12, both
# halves' outputs x 0.22, softmax scale 1/64, logits / 8. Tied head.
# 3.19 G parameters. A row's past is 75.5 MB of float32 state whatever
# its length and 4.1 KB of pages a token.
GRANITE_H_PERIOD = "M-M-M-M-M-*-M-M-M-M-"

_register(ModelConfig(
    name="granite-4.0-h-micro", vocab_size=100352, hidden_size=2048,
    intermediate_size=8192, num_layers=40, num_heads=32, num_kv_heads=8,
    head_dim=64, max_seq_len=131072, rope_theta=10000.0, rms_norm_eps=1e-5,
    tie_embeddings=True, hybrid_pattern=GRANITE_H_PERIOD * 4,
    mamba_num_heads=64, mamba_head_dim=64, ssm_state_size=128,
    ssm_groups=1, conv_kernel=4, ssm_chunk=256, attn_rope=False,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8.0,
    bos_token_id=1, eos_token_ids=(2,),
))

# The same two kinds of layer at test size: two periods of three Mamba-2
# layers (4 heads x 16, one group, state 16) to one attention layer at a
# head of 64 = 256 / 4 (4 / 2 heads: one pair of KV heads), an MLP behind
# each, and the four scalars away from their neutral values (a softmax
# scale of 1/32 where 1 / sqrt(64) is 1/8).
_register(ModelConfig(
    name="tiny-granite-h", vocab_size=512, hidden_size=256,
    intermediate_size=192, num_layers=8, num_heads=4, num_kv_heads=2,
    head_dim=64, max_seq_len=256, rope_theta=10000.0, rms_norm_eps=1e-5,
    tie_embeddings=True, hybrid_pattern="M-M-*-M-" * 2,
    mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16, ssm_groups=1,
    conv_kernel=4, ssm_chunk=16, attn_rope=False,
    embedding_multiplier=6.0, residual_multiplier=0.3,
    attention_multiplier=0.03125, logits_scaling=4.0,
    bos_token_id=1, eos_token_ids=(2,),
))

# Ouro's looped stack at test size (ByteDance/Ouro-2.6B config.json,
# model_type ouro): three layers walked four times with one set of
# weights, plain multi-head attention (4 / 4 heads), four norms a layer,
# the final norm and the exit gate after every pass; 12 cache layers.
_register(ModelConfig(
    name="tiny-ouro", vocab_size=512, hidden_size=128,
    intermediate_size=256, num_layers=3, num_heads=4, num_kv_heads=4,
    head_dim=32, max_seq_len=256, rope_theta=10000.0, rms_norm_eps=1e-6,
    sandwich_norm=True, ut_steps=4, bos_token_id=1, eos_token_ids=(2,),
))

# Loadgen CPU profile: ``tiny`` dims with a real context window, so the
# e2e long-context scenario (docs/loadtest.md) prefills thousands of
# tokens through chunked admission on CPU-class hosts instead of
# truncating at tiny's 256.
_register(ModelConfig(
    name="tiny-long", vocab_size=512, hidden_size=128,
    intermediate_size=256, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=32, max_seq_len=4096, rope_theta=10000.0,
    bos_token_id=1, eos_token_ids=(2,),
))

# Like ``tiny`` but every tp-sharded dim (heads, KV heads, mlp, vocab)
# divides a tp=4 mesh: the multi-chip dryrun validates SHARDED wk/wv/KV
# paths with it — `tiny`'s 2 kv heads at tp=4 silently fall back to
# replication (parallel/sharding.constrain), which would leave the
# sharded-KV path unexercised (the production 8B/70B configs' 8 kv heads
# divide their meshes).
_register(ModelConfig(
    name="tiny-tp", vocab_size=512, hidden_size=128, intermediate_size=256,
    num_layers=2, num_heads=4, num_kv_heads=4, head_dim=32, max_seq_len=256,
    rope_theta=10000.0, bos_token_id=1, eos_token_ids=(2,),
))

# ~1B-class dense config for a single v5e chip (fits HBM in bf16 with
# room for KV cache; same architecture family as the 8B).
# max_seq_len 16384: long contexts (4k-12k) need headroom past 2048;
# rope_theta 500000 (the llama3 base) is stable at these lengths, and
# actual KV allocation is sized per run (SERVE_MAX_SEQ / the scheduler's
# right-sized pool), so the cap costs nothing when unused.
_register(ModelConfig(
    name="bench-1b", vocab_size=32768, hidden_size=2048,
    intermediate_size=5632, num_layers=22, num_heads=16, num_kv_heads=8,
    head_dim=128, max_seq_len=16384, rope_theta=500000.0,
    bos_token_id=1, eos_token_ids=(2,),
))

# ~0.4B-param draft model for draft-target speculative decoding: resident
# alongside a big target on the SAME chip (llama3.1-8b int8 ~8.6 GB +
# this config int8 ~0.45 GB + both KV pools fit one 16 GB v5e), it
# proposes K greedy tokens per spec tick that the target verifies in one
# forward (serve/draft_model.py). vocab matches llama3.1-8b — a drafter
# MUST share its target's vocabulary (draft ids feed the target's verify
# forward directly); pair it with a different-vocab target by cloning
# the config at the target's vocab (`get_config("draft-400m").with_(
# vocab_size=target.vocab_size)` — serve/engine.py's SERVE_DRAFT path
# does this). Embeddings are untied so the synthetic quote/
# freeform workloads (models/synth.py) can install their successor-map
# lm_head for CPU tests without real checkpoints.
_register(ModelConfig(
    name="draft-400m", vocab_size=128256, hidden_size=1024,
    intermediate_size=4096, num_layers=16, num_heads=8, num_kv_heads=4,
    head_dim=128, max_seq_len=16384, rope_theta=500000.0,
))

# ~1.2B-param MoE config (8 experts, top-2) for single-chip MoE benching:
# measures the scatter/gather expert-dispatch cost of models/mixtral.py on
# real hardware (BASELINE.json config 5's family; ep=1 on one chip).
_register(ModelConfig(
    name="bench-moe", vocab_size=32768, hidden_size=1024,
    intermediate_size=2816, num_layers=16, num_heads=8, num_kv_heads=4,
    # max_seq 8192 for the round-5 long-context MoE rows (rope_theta 1e6
    # covers it; KV is allocated per run, so the cap is free unused).
    head_dim=128, max_seq_len=8192, rope_theta=1e6,
    num_experts=8, num_experts_per_tok=2, moe_capacity_factor=2.0,
    bos_token_id=1, eos_token_ids=(2,),
))


def get_config(name: str) -> ModelConfig:
    try:
        return CONFIGS[name]
    except KeyError:
        raise KeyError(f"unknown model config {name!r}; have {sorted(CONFIGS)}") from None
