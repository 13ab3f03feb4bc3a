"""Llama 3.x family in functional JAX.

Design (TPU-first, not a torch port):
- Pure functions over a params pytree (dict), so ``jax.jit`` /
  ``shard_map`` / ``jax.grad`` compose without module plumbing.
- Per-layer ``jax.checkpoint`` (remat) so long-sequence training fits
  HBM; matmuls stay bf16 on the MXU with fp32 softmax/norm accums.
- GQA + RoPE + RMSNorm + SwiGLU as in Llama 3 (reference recipe:
  ``llm/llama-3_1-finetuning`` trains meta-llama/Llama-3.1-8B with
  torchtune; here the model itself is in-tree).
- ``param_sharding_rules`` gives each param a PartitionSpec over the
  (dp, fsdp, tp) mesh — embedding/attention/MLP sharded tensor-parallel
  on 'tp', everything weight-sharded on 'fsdp' (ZeRO-3 style).
"""
import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from skypilot_tpu import exceptions
from skypilot_tpu.ops import attention as attention_ops

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    name: str
    vocab_size: int
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    ffn_hidden: int
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    # Llama-3.1 RoPE frequency scaling (rope_scaling in HF config).
    rope_scaling: bool = False
    remat: bool = True
    # What per-layer remat keeps besides the flash-attention kernel
    # outputs ('+'-joined tokens, validated in forward_hidden):
    #   'attn'        — rematerialize everything else (min memory);
    #   '+mlp_up'     — also save the up-proj output (~268 MB/layer
    #                   at B=8,T=2048 for 1B; skips one [d, ffn]
    #                   matmul recompute — bench default on 16 GB v5e)
    #   '+mlp'        — save gate AND up (~536 MB/layer, both matmul
    #                   recomputes skipped);
    #   '+qkv'        — save pre-rotation q/k/v (~100 MB/layer; RoPE
    #                   is fused into the attention kernels).
    # Frozen-base LoRA makes the saved activations pure speed: no
    # weight grads need them.
    remat_saves: str = 'attn'
    # ---- family knobs (Gemma / Qwen / Mistral share the Llama block
    # modulo these; same approach as MaxText's decoder config) ----
    # Explicit head dim (Gemma: 256 with 8 heads at dim 2048);
    # None -> dim // n_heads.
    head_dim_override: Optional[int] = None
    # MLP activation: 'silu' (Llama/Qwen/Mistral) or 'gelu_tanh'
    # (Gemma's GeGLU).
    mlp_activation: str = 'silu'
    # Tie lm_head to embed^T (Gemma, Qwen2.5<=1.5B).
    tie_embeddings: bool = False
    # RMSNorm computes x * (1 + w) (Gemma's zero-centered weights).
    norm_offset: bool = False
    # Scale embeddings by sqrt(dim) after lookup (Gemma).
    scale_embeddings: bool = False
    # Bias on the q/k/v projections (Qwen2).
    qkv_bias: bool = False
    # ---- Mixture-of-Experts (Mixtral family). n_experts == 0 means
    # a dense MLP; > 0 replaces every layer's MLP with a top-k-routed
    # expert layer (GShard-style static capacity dispatch, experts
    # sharded over the 'ep' mesh axis — the all-to-all is inserted by
    # GSPMD from the expert-weight shardings). ----
    n_experts: int = 0
    moe_top_k: int = 2
    # Per-expert buffer = ceil(top_k * T / E * capacity_factor)
    # tokens; overflow drops (residual passes through). Static shapes
    # keep the dispatch XLA/MXU-friendly.
    moe_capacity_factor: float = 2.0
    # Coefficient on the load-balance aux loss (≈1.0 at perfect
    # balance; Switch Transformer's alpha).
    moe_aux_coef: float = 0.02
    # ---- Looped layer stack (Ouro / LoopLM). The same ``n_layers``
    # weights are run ``loop_passes`` times; pass t, layer l keeps
    # its keys and values in a KV entry of its own, t * n_layers + l
    # (``kv_entries``), and the final norm closes EVERY pass, the
    # normed state going on into the next. Served by the paged
    # engine's three layer bodies only; the dense bodies refuse
    # (``require_plain_stack``). ----
    loop_passes: int = 1
    # A second RMSNorm on each branch's output, ahead of the residual
    # add (leaves ``attn_out_norm`` / ``mlp_out_norm``).
    sandwich_norms: bool = False
    # Exit gate after each pass: lam_t = sigmoid(w . h_t + b) on the
    # normed state; the pass served is the first whose cumulative
    # exit probability reaches this threshold, else the last. None:
    # no gate, the last pass is served. Every pass always runs for
    # every row (static shapes); the gate selects by value.
    exit_threshold: Optional[float] = None
    # ---- Window and global layers in one stack (Cohere2 / Command A
    # family). ``sliding_window`` W: a sliding layer's query at i sees
    # keys j with 0 <= i - j < W (its own position counts). With
    # ``global_every`` = p every p-th layer (l % p == p - 1) is a
    # global layer that sees the whole context; 0 = every layer
    # slides. None: every layer is global, what the repo ran before
    # these keys. Served by the paged engine only (two block groups,
    # ``serve/kv_pool.py``). ----
    sliding_window: Optional[int] = None
    global_every: int = 0
    # Global layers apply no positional transform ("NoPE").
    global_rope: bool = True
    # RoPE over interleaved pairs (x0, x1), (x2, x3), ... ("rope_gptj")
    # in place of the rotate-half pairing (x_i, x_{i + hd/2}).
    rope_interleaved: bool = False
    # LayerNorm without bias (mean removed) in place of RMSNorm.
    layer_norm: bool = False
    # One norm a layer feeds attention AND the MLP / expert layer, and
    # both results are added to the stream at once (no ``mlp_norm``).
    parallel_block: bool = False
    # ---- The dropless expert layer of the paged bodies
    # (``models/moe.py``). ``moe_score``: how router logits become
    # weights ('softmax' over all experts, Mixtral; 'sigmoid' each on
    # its own); the top-k weights are normalised to sum 1 either way.
    # ``n_shared_experts`` gated MLPs of width ``ffn_hidden`` see every
    # token, their mean added beside the routed sum (leaves ``ws_*``,
    # the experts side by side along the hidden axis).
    # ``experts_held`` = (first, count): the routed experts whose
    # weights this chip holds (expert parallelism's share; the router
    # keeps all ``n_experts`` outputs and the result is this share's
    # part). None: all of them. ----
    moe_score: str = 'softmax'
    n_shared_experts: int = 0
    experts_held: Optional[tuple] = None
    # Selection by score plus a per-expert bias (leaf ``router_bias``),
    # weights from the scores without it ("noaux_tc"), and a factor on
    # the routed sum after the top-k weights were normalised.
    moe_select_bias: bool = False
    moe_routed_scale: float = 1.0
    # ---- Latent attention (MLA; DeepSeek-V2 family). With
    # ``kv_lora_rank`` set a layer caches ONE row a token,
    # ``[c_kv ; k_pe]`` of ``kv_lora_rank + qk_rope_head_dim`` values
    # shared by all heads (``serve/kv_pool.py``: a block group of kind
    # 'latent'); queries come through a rank-``q_lora_rank``
    # bottleneck, each head has ``qk_nope_head_dim`` values without
    # positions and ``qk_rope_head_dim`` rotated ones, and values are
    # ``v_head_dim`` wide. Served by the paged engine only, in two
    # forms (``ops/decode_attention.py``: absorbed for a decode step,
    # expanded for a prefill chunk). ----
    kv_lora_rank: Optional[int] = None
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN: (factor, original context, beta_fast, beta_slow,
    # mscale_all_dim). Scales the RoPE frequencies by wavelength and
    # the softmax scale by (0.1 mscale_all_dim ln factor + 1)^2.
    rope_yarn: Optional[tuple] = None
    # The first ``dense_first`` layers keep a dense gated MLP of width
    # ``dense_ffn_hidden`` (leaves under ``params['dense_layers']``);
    # the expert layers after them are ``params['layers']``.
    dense_first: int = 0
    dense_ffn_hidden: int = 0
    # ---- Several residual streams (mHC, arXiv 2512.24880). With
    # ``hc_mult`` = n > 1 a token carries n streams; each sublayer
    # reads a mix of them and writes back through a doubly stochastic
    # n x n matrix (``hc_sinkhorn_iters`` Sinkhorn passes over
    # exp(clamp(.)), ``hc_eps`` in each division) and a gate a stream
    # (``models/decode.py``: ``hc_pre``, ``hc_post``). ----
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: tuple = (-30.0, 30.0)
    # ---- Next-token-prediction modules (DeepSeek-V3 report, section
    # 2.2; ``num_nextn_predict_layers``). With ``nextn_layers`` = 1
    # the model carries one module (leaves ``params['mtp']``): two
    # norms, a ``2 d -> d`` projection of [the next token's embedding
    # ; the main stack's normed state], one expert layer in the main
    # layers' form with a cache entry of its own (the latent group's
    # last, ``kv_entries``), a norm, and the main model's head. The
    # paged engine runs it as its drafter (``models/decode.py``:
    # ``mtp_module``, ``mtp_rounds_paged``); it changes no logit of
    # the model. ----
    nextn_layers: int = 0

    def __post_init__(self):
        unknown = set(self.remat_saves.split('+')) - {
            'attn', 'mlp', 'mlp_up', 'qkv'}
        if unknown:
            raise ValueError(
                f'unknown remat_saves token(s) {sorted(unknown)} in '
                f'{self.remat_saves!r}; valid: attn, mlp, mlp_up, qkv')
        if self.mlp_activation not in ('silu', 'gelu_tanh'):
            raise ValueError(
                f'unknown mlp_activation {self.mlp_activation!r}')
        if self.loop_passes < 1:
            raise ValueError(
                f'loop_passes must be >= 1: {self.loop_passes}')
        if self.moe_score not in ('softmax', 'sigmoid'):
            raise ValueError(f'unknown moe_score {self.moe_score!r}')
        if self.global_every and (
                self.sliding_window is None
                or self.n_layers % self.global_every):
            raise ValueError(
                f'global_every={self.global_every} needs a '
                f'sliding_window and a whole number of periods in '
                f'n_layers={self.n_layers}')
        if self.experts_held is not None:
            first, count = self.experts_held
            if not (0 <= first and count >= 1
                    and first + count <= self.n_experts):
                raise ValueError(
                    f'experts_held={self.experts_held} lies outside '
                    f'the {self.n_experts} routed experts')
        if self.kv_lora_rank is not None and not (
                self.q_lora_rank and self.qk_nope_head_dim
                and self.qk_rope_head_dim and self.v_head_dim):
            raise ValueError(
                f'kv_lora_rank={self.kv_lora_rank} needs q_lora_rank, '
                f'qk_nope_head_dim, qk_rope_head_dim and v_head_dim')
        if self.nextn_layers not in (0, 1) or (self.nextn_layers and (
                self.kv_lora_rank is None or self.hc_mult != 1
                or not self.n_experts)):
            raise ValueError(
                f'nextn_layers={self.nextn_layers}: one module, on a '
                f'latent stack with expert layers and one residual '
                f'stream, is what is implemented')
        if not 0 <= self.dense_first < self.n_layers or (
                self.dense_first and not self.dense_ffn_hidden):
            raise ValueError(
                f'dense_first={self.dense_first} needs a '
                f'dense_ffn_hidden and an expert layer after it '
                f'(n_layers={self.n_layers})')

    @property
    def head_dim(self) -> int:
        if self.kv_lora_rank is not None:
            # What a query head is scored over.
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        if self.head_dim_override is not None:
            return self.head_dim_override
        return self.dim // self.n_heads

    @property
    def latent_width(self) -> int:
        """Values a token keeps in a latent layer's cache entry."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kv_entries(self) -> int:
        """KV caches a token holds: one for every pass and layer,
        and one for a next-token-prediction module's layer (the
        last). The leading axis of the paged pool
        (serve/kv_pool.py)."""
        return self.loop_passes * self.n_layers + self.nextn_layers

    @property
    def layer_kinds(self) -> tuple:
        """'window' or 'global' for each layer of one period of the
        stack (the layer scan's body runs one period); ('latent',)
        where every layer caches a latent row (``kv_lora_rank``)."""
        if self.kv_lora_rank is not None:
            return ('latent',)
        if self.sliding_window is None:
            return ('global',)
        if not self.global_every:
            return ('window',)
        return ('window',) * (self.global_every - 1) + ('global',)

    def kind_entries(self, kind: str) -> int:
        """KV entries of one kind of layer: the leading axis of that
        kind's block group (``serve/kv_pool.py``)."""
        kinds = self.layer_kinds
        return self.kv_entries * kinds.count(kind) // len(kinds)

    @property
    def n_experts_held(self) -> int:
        return (self.experts_held[1] if self.experts_held is not None
                else self.n_experts)

    @property
    def plain_stack(self) -> bool:
        """Each layer run once over the whole context, two RMSNorms,
        no exit gate, no expert share: what the dense layer bodies
        of the repo compute."""
        return (self.loop_passes == 1 and not self.sandwich_norms
                and self.exit_threshold is None
                and self.sliding_window is None
                and not self.parallel_block and not self.layer_norm
                and not self.n_shared_experts
                and self.experts_held is None
                and self.moe_score == 'softmax'
                and not self.rope_interleaved
                and self.kv_lora_rank is None and self.hc_mult == 1
                and not self.dense_first
                and not self.moe_select_bias
                and self.moe_routed_scale == 1.0
                and self.rope_yarn is None)

    def num_params(self) -> int:
        d, v, h = self.dim, self.vocab_size, self.ffn_hidden
        nh, nkv, hd = self.n_heads, self.n_kv_heads, self.head_dim
        if self.kv_lora_rank is not None:
            return self._num_params_latent()
        mlp = 3 * d * h
        if self.n_experts:
            # What is held here: a share of the routed experts, the
            # whole router, the shared experts.
            mlp = ((self.n_experts_held + self.n_shared_experts) * mlp
                   + d * self.n_experts)
        per_layer = (
            d * nh * hd + 2 * d * nkv * hd + nh * hd * d +
            mlp + (d if self.parallel_block else 2 * d))
        if self.qkv_bias:
            per_layer += (nh + 2 * nkv) * hd
        if self.sandwich_norms:
            per_layer += 2 * d
        head = 0 if self.tie_embeddings else v * d
        gate = 0 if self.exit_threshold is None else d + 1
        return v * d + head + self.n_layers * per_layer + d + gate

    def _num_params_latent(self) -> int:
        """A latent-attention stack: the MLA projections and their
        two inner norms, the stream mixers of both sublayers, dense
        layers first and expert layers after them, an untied head."""
        d, v, nh = self.dim, self.vocab_size, self.n_heads
        rq, rkv = self.q_lora_rank, self.kv_lora_rank
        attn = (d * rq + rq + rq * nh * self.head_dim +
                d * self.latent_width + rkv +
                rkv * nh * (self.qk_nope_head_dim + self.v_head_dim) +
                nh * self.v_head_dim * d)
        mixers = 0
        if self.hc_mult > 1:
            n = self.hc_mult
            mixers = 2 * (n * d * (2 * n + n * n) + 2 * n + n * n + 3)
        expert = 3 * d * self.ffn_hidden
        moe = ((self.n_experts_held + self.n_shared_experts) * expert
               + d * self.n_experts
               + (self.n_experts if self.moe_select_bias else 0))
        shared = attn + mixers + 2 * d
        # A module: an expert layer, its three norms, the projection.
        module = shared + moe + 3 * d + 2 * d * d
        return (2 * v * d + d +
                self.dense_first * (shared +
                                    3 * d * self.dense_ffn_hidden) +
                (self.n_layers - self.dense_first) * (shared + moe) +
                self.nextn_layers * module)

    def num_active_params(self) -> int:
        """Params touched per token (== num_params for dense; for MoE
        only top_k of the n_experts MLPs) — the FLOPs/token basis."""
        if not self.n_experts:
            return self.num_params()
        unused = ((self.n_experts_held - self.moe_top_k) *
                  3 * self.dim * self.ffn_hidden *
                  (self.n_layers - self.dense_first +
                   self.nextn_layers))
        return self.num_params() - max(unused, 0)


CONFIGS: Dict[str, LlamaConfig] = {
    'llama3-8b': LlamaConfig(
        name='llama3-8b', vocab_size=128256, dim=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, ffn_hidden=14336,
        rope_theta=500000.0),
    'llama3.1-8b': LlamaConfig(
        name='llama3.1-8b', vocab_size=128256, dim=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, ffn_hidden=14336,
        rope_theta=500000.0, rope_scaling=True, max_seq_len=131072),
    'llama3.2-1b': LlamaConfig(
        name='llama3.2-1b', vocab_size=128256, dim=2048, n_layers=16,
        n_heads=32, n_kv_heads=8, ffn_hidden=8192,
        rope_theta=500000.0, rope_scaling=True),
    'llama2-7b': LlamaConfig(
        name='llama2-7b', vocab_size=32000, dim=4096, n_layers=32,
        n_heads=32, n_kv_heads=32, ffn_hidden=11008,
        rope_theta=10000.0, max_seq_len=4096),
    # Other families sharing the block (HF config.json values).
    'gemma-2b': LlamaConfig(
        name='gemma-2b', vocab_size=256000, dim=2048, n_layers=18,
        n_heads=8, n_kv_heads=1, ffn_hidden=16384,
        head_dim_override=256, rope_theta=10000.0, max_seq_len=8192,
        mlp_activation='gelu_tanh', tie_embeddings=True,
        norm_offset=True, scale_embeddings=True),
    'gemma-7b': LlamaConfig(
        name='gemma-7b', vocab_size=256000, dim=3072, n_layers=28,
        n_heads=16, n_kv_heads=16, ffn_hidden=24576,
        head_dim_override=256, rope_theta=10000.0, max_seq_len=8192,
        mlp_activation='gelu_tanh', tie_embeddings=True,
        norm_offset=True, scale_embeddings=True),
    'qwen2.5-7b': LlamaConfig(
        name='qwen2.5-7b', vocab_size=152064, dim=3584, n_layers=28,
        n_heads=28, n_kv_heads=4, ffn_hidden=18944,
        rope_theta=1000000.0, max_seq_len=32768, qkv_bias=True),
    'qwen2.5-1.5b': LlamaConfig(
        name='qwen2.5-1.5b', vocab_size=151936, dim=1536, n_layers=28,
        n_heads=12, n_kv_heads=2, ffn_hidden=8960,
        rope_theta=1000000.0, max_seq_len=32768, qkv_bias=True,
        tie_embeddings=True),
    'mistral-7b': LlamaConfig(
        name='mistral-7b', vocab_size=32000, dim=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, ffn_hidden=14336,
        rope_theta=10000.0, max_seq_len=8192),
    # Looped stack (HF ByteDance/Ouro-2.6B config.json: 48 layers run
    # total_ut_steps = 4 times over shared weights, 16 heads of 128
    # with no grouping, early_exit_threshold 1.0; published
    # max_position_embeddings 65,536). KV a token: 192 entries x 2 x
    # 16 x 128 B of int8 codes + 12,288 B of scales = 798,720 B.
    'ouro-2.6b': LlamaConfig(
        name='ouro-2.6b', vocab_size=49152, dim=2048, n_layers=48,
        n_heads=16, n_kv_heads=16, ffn_hidden=5632,
        rope_theta=1000000.0, norm_eps=1e-6, max_seq_len=4096,
        loop_passes=4, sandwich_norms=True, exit_threshold=1.0),
    # MoE family: Mistral attention geometry + 8 routed experts, top-2
    # (HF mistralai/Mixtral-8x7B config.json).
    'mixtral-8x7b': LlamaConfig(
        name='mixtral-8x7b', vocab_size=32000, dim=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, ffn_hidden=14336,
        rope_theta=1000000.0, max_seq_len=32768,
        n_experts=8, moe_top_k=2),
    # Window and global layers, a parallel block, a dropless expert
    # layer (HF CohereLabs/command-a-plus-05-2026 config.json,
    # model_type cohere2_moe: 3 sliding layers of window 4,096 with
    # interleaved RoPE, then 1 global layer without positions; one
    # LayerNorm a layer feeds attention and experts; 128 routed
    # experts of width 4,096, 8 a token by sigmoid scores normalised,
    # 4 shared experts averaged; tied embeddings, logit_scale 1).
    # Served by the paged engine only, at a chip's share
    # (``experts_held``: ``recipes/serve_model --experts-held``;
    # fewer layers and vocabulary rows as ``get_config`` overrides).
    'command-a-plus': LlamaConfig(
        name='command-a-plus', vocab_size=262144, dim=4096,
        n_layers=32, n_heads=128, n_kv_heads=8, ffn_hidden=4096,
        head_dim_override=128, rope_theta=50000.0, norm_eps=1e-5,
        max_seq_len=12288, tie_embeddings=True,
        sliding_window=4096, global_every=4, global_rope=False,
        rope_interleaved=True, layer_norm=True, parallel_block=True,
        n_experts=128, moe_top_k=8, moe_score='sigmoid',
        n_shared_experts=4),
    # Latent attention, four residual streams, dense layers before
    # the expert layers (HF XingChen-AGI/Xing4.0-29B-A4B config.json,
    # model_type xing4_0: 40 layers of which the first 2 are dense at
    # width 9,216; 32 heads of 128 + 64 rotated query values over a
    # 512 + 64 latent row, q rank 768; 64 routed experts of width
    # 1,024, 4 a token by sigmoid score plus a selection bias,
    # weights normalised and doubled, 1 shared expert; YaRN factor 64
    # over 4,096; hc_mult 4 with 20 Sinkhorn passes; an untied head.
    # The next-token-prediction module is not built). Served by the
    # paged engine only; fewer layers as a ``get_config`` override.
    'xing4.0-29b-a4b': LlamaConfig(
        name='xing4.0-29b-a4b', vocab_size=131072, dim=3584,
        n_layers=40, n_heads=32, n_kv_heads=32, ffn_hidden=1024,
        rope_theta=10000.0, norm_eps=1e-6, max_seq_len=20480,
        rope_interleaved=True, kv_lora_rank=512, q_lora_rank=768,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_yarn=(64.0, 4096, 32.0, 1.0, 1.0),
        dense_first=2, dense_ffn_hidden=9216,
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
        hc_clamp=(-30.0, 30.0),
        n_experts=64, moe_top_k=4, moe_score='sigmoid',
        n_shared_experts=1, moe_select_bias=True,
        moe_routed_scale=2.0),
    # Latent attention and a next-token-prediction module (HF
    # jdopensource/JoyAI-LLM-Flash config.json, model_type
    # joyai_llm_flash, 48B-A2.7B: 40 layers of which the first is
    # dense at width 7,168; 32 heads of 128 + 64 rotated query values
    # over a 512 + 64 latent row, q rank 1,536; 256 routed experts of
    # width 768, 8 a token by sigmoid score plus a selection bias,
    # weights normalised and multiplied by 2.5, 1 shared expert;
    # interleaved RoPE at theta 32,000,000 with NO rope_scaling, so
    # the softmax scale is 192^-0.5; an untied head over 129,280 ids;
    # num_nextn_predict_layers 1, built: ``nextn_layers``). Served by
    # the paged engine only; fewer layers as a ``get_config``
    # override.
    'joyai-llm-flash': LlamaConfig(
        name='joyai-llm-flash', vocab_size=129280, dim=2048,
        n_layers=40, n_heads=32, n_kv_heads=32, ffn_hidden=768,
        rope_theta=32000000.0, norm_eps=1e-6, max_seq_len=4096,
        rope_interleaved=True, kv_lora_rank=512, q_lora_rank=1536,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        dense_first=1, dense_ffn_hidden=7168,
        n_experts=256, moe_top_k=8, moe_score='sigmoid',
        n_shared_experts=1, moe_select_bias=True,
        moe_routed_scale=2.5, nextn_layers=1),
    # Small configs for tests / CPU dryruns.
    'debug-250m': LlamaConfig(
        name='debug-250m', vocab_size=32000, dim=1024, n_layers=8,
        n_heads=16, n_kv_heads=4, ffn_hidden=2816),
    'tiny': LlamaConfig(
        name='tiny', vocab_size=512, dim=128, n_layers=2, n_heads=4,
        n_kv_heads=2, ffn_hidden=256, max_seq_len=512,
        dtype=jnp.float32, remat=False),
    'tiny-moe': LlamaConfig(
        name='tiny-moe', vocab_size=512, dim=128, n_layers=2,
        n_heads=4, n_kv_heads=2, ffn_hidden=256, max_seq_len=512,
        dtype=jnp.float32, remat=False, n_experts=4, moe_top_k=2),
    'tiny-window-moe': LlamaConfig(
        name='tiny-window-moe', vocab_size=512, dim=128, n_layers=4,
        n_heads=8, n_kv_heads=2, ffn_hidden=64, head_dim_override=32,
        rope_theta=50000.0, norm_eps=1e-5, max_seq_len=512,
        dtype=jnp.float32, remat=False, tie_embeddings=True,
        sliding_window=32, global_every=4, global_rope=False,
        rope_interleaved=True, layer_norm=True, parallel_block=True,
        n_experts=16, moe_top_k=4, moe_score='sigmoid',
        n_shared_experts=2),
    'tiny-latent-moe': LlamaConfig(
        name='tiny-latent-moe', vocab_size=512, dim=128, n_layers=4,
        n_heads=4, n_kv_heads=4, ffn_hidden=64, rope_theta=10000.0,
        norm_eps=1e-6, max_seq_len=512, dtype=jnp.float32,
        remat=False, rope_interleaved=True, kv_lora_rank=48,
        q_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32, rope_yarn=(64.0, 64, 32.0, 1.0, 1.0),
        dense_first=2, dense_ffn_hidden=256, hc_mult=4,
        n_experts=8, moe_top_k=2, moe_score='sigmoid',
        n_shared_experts=1, moe_select_bias=True,
        moe_routed_scale=2.0),
    'tiny-latent-mtp': LlamaConfig(
        name='tiny-latent-mtp', vocab_size=512, dim=128, n_layers=4,
        n_heads=4, n_kv_heads=4, ffn_hidden=64, rope_theta=10000.0,
        norm_eps=1e-6, max_seq_len=512, dtype=jnp.float32,
        remat=False, rope_interleaved=True, kv_lora_rank=48,
        q_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32, dense_first=1, dense_ffn_hidden=256,
        n_experts=8, moe_top_k=2, moe_score='sigmoid',
        n_shared_experts=1, moe_select_bias=True,
        moe_routed_scale=2.0, nextn_layers=1),
    'tiny-loop': LlamaConfig(
        name='tiny-loop', vocab_size=512, dim=128, n_layers=2,
        n_heads=4, n_kv_heads=4, ffn_hidden=256, max_seq_len=512,
        rope_theta=1000000.0, norm_eps=1e-6, dtype=jnp.float32,
        remat=False, loop_passes=4, sandwich_norms=True,
        exit_threshold=1.0),
}


def get_config(name: str, **overrides) -> LlamaConfig:
    cfg = CONFIGS[name]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def require_plain_stack(config: LlamaConfig, where: str) -> None:
    """The dense layer bodies (training forward, contiguous-cache
    decode) run each layer once with two norms and serve the last
    state: a looped / sandwich-normed / gated configuration must not
    silently run as something else there."""
    if not config.plain_stack:
        raise exceptions.NotSupportedError(
            f'{where} does not implement {config.name!r} '
            f'(loop_passes={config.loop_passes}, sandwich_norms='
            f'{config.sandwich_norms}, exit_threshold='
            f'{config.exit_threshold}, sliding_window='
            f'{config.sliding_window}, parallel_block='
            f'{config.parallel_block}, layer_norm='
            f'{config.layer_norm}, n_shared_experts='
            f'{config.n_shared_experts}, experts_held='
            f'{config.experts_held}, moe_score={config.moe_score!r}, '
            f'kv_lora_rank={config.kv_lora_rank}, hc_mult='
            f'{config.hc_mult}, dense_first={config.dense_first}): '
            f'only the paged engine '
            f'(serve/batching.BatchingEngine; serve_model --slots N) '
            f'runs a looped layer stack, window layers, a parallel '
            f'block, a share of the experts, latent attention or '
            f'several residual streams')


# ---------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------


def init_params(config: LlamaConfig, key: jax.Array,
                dtype: Optional[Any] = None) -> Params:
    """Random-init a params pytree. Layers are STACKED along a leading
    axis so the forward pass is a single ``lax.scan`` — one compiled
    layer body regardless of depth (fast compiles, XLA-friendly)."""
    dtype = dtype or config.dtype
    d = config.dim
    hd = config.head_dim
    nh, nkv = config.n_heads, config.n_kv_heads
    ffn = config.ffn_hidden
    L = config.n_layers

    k_embed, k_layers, k_out = jax.random.split(key, 3)

    def dense(key, shape, fan_in):
        scale = 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(key, shape, jnp.float32) *
                scale).astype(dtype)

    def norm_init(shape):
        # norm_offset (Gemma): weights are zero-centered, applied as
        # (1 + w) — init to zeros; plain RMSNorm inits to ones.
        return (jnp.zeros(shape, dtype) if config.norm_offset
                else jnp.ones(shape, dtype))

    if config.kv_lora_rank is not None:
        return _init_latent_params(config, key, dense, norm_init,
                                   dtype)

    # Dense configs keep the historical 7-way split so a fixed seed
    # reproduces pre-MoE initializations exactly.
    E = config.n_experts
    ks = jax.random.split(k_layers, 8 if E else 7)
    if E:
        held = config.n_experts_held
        mlp_params = {
            'router': dense(ks[7], (L, d, E), d),
            'w_gate': dense(ks[4], (L, held, d, ffn), d),
            'w_up': dense(ks[5], (L, held, d, ffn), d),
            'w_down': dense(ks[6], (L, held, ffn, d), ffn),
        }
        if config.n_shared_experts:
            # Keys of their own: the other leaves keep their seeds.
            sk = jax.random.split(jax.random.fold_in(key, 0x73), 3)
            wide = config.n_shared_experts * ffn
            mlp_params['ws_gate'] = dense(sk[0], (L, d, wide), d)
            mlp_params['ws_up'] = dense(sk[1], (L, d, wide), d)
            mlp_params['ws_down'] = dense(sk[2], (L, wide, d), ffn)
    else:
        mlp_params = {
            'w_gate': dense(ks[4], (L, d, ffn), d),
            'w_up': dense(ks[5], (L, d, ffn), d),
            'w_down': dense(ks[6], (L, ffn, d), ffn),
        }
    params: Params = {
        'embed': dense(k_embed, (config.vocab_size, d), d),
        'layers': {
            'wq': dense(ks[0], (L, d, nh * hd), d),
            'wk': dense(ks[1], (L, d, nkv * hd), d),
            'wv': dense(ks[2], (L, d, nkv * hd), d),
            'wo': dense(ks[3], (L, nh * hd, d), nh * hd),
            **mlp_params,
            'attn_norm': norm_init((L, d)),
            'mlp_norm': norm_init((L, d)),
        },
        'final_norm': norm_init((d,)),
    }
    if config.parallel_block:
        del params['layers']['mlp_norm']
    if config.qkv_bias:
        params['layers']['bq'] = jnp.zeros((L, nh * hd), dtype)
        params['layers']['bk'] = jnp.zeros((L, nkv * hd), dtype)
        params['layers']['bv'] = jnp.zeros((L, nkv * hd), dtype)
    if config.sandwich_norms:
        params['layers']['attn_out_norm'] = norm_init((L, d))
        params['layers']['mlp_out_norm'] = norm_init((L, d))
    if config.exit_threshold is not None:
        # A key of its own: the other leaves keep their seeds.
        params['exit_gate_w'] = dense(jax.random.fold_in(key, 0x67),
                                      (d, 1), d)
        params['exit_gate_b'] = jnp.zeros((1,), dtype)
    if not config.tie_embeddings:
        params['lm_head'] = dense(k_out, (d, config.vocab_size), d)
    return params


def latent_leaf_shapes(config: LlamaConfig) -> Dict[str, tuple]:
    """Shape and fan-in of the leaves every layer of a latent
    stack has, dense or expert: the MLA projections (``wq_a`` ->
    ``q_norm`` -> ``wq_b``; ``wkv_a`` -> ``kv_norm`` on the latent
    part -> ``wkv_b``, whose columns are a head's ``qk_nope_head_dim``
    key values then its ``v_head_dim`` value values; ``wo``), the two
    sublayer norms, and with ``hc_mult`` > 1 each sublayer's stream
    mixer: ``hc_*_phi`` [n d, 2 n + n n], ``hc_*_b`` [2 n + n n] and
    the three scalars ``hc_*_a`` (pre, post, res)."""
    d, nh, n = config.dim, config.n_heads, config.hc_mult
    rq, rkv = config.q_lora_rank, config.kv_lora_rank
    shapes = {
        'wq_a': ((d, rq), d), 'q_norm': ((rq,), None),
        'wq_b': ((rq, nh * config.head_dim), rq),
        'wkv_a': ((d, config.latent_width), d),
        'kv_norm': ((rkv,), None),
        'wkv_b': ((rkv, nh * (config.qk_nope_head_dim +
                              config.v_head_dim)), rkv),
        'wo': ((nh * config.v_head_dim, d), nh * config.v_head_dim),
        'attn_norm': ((d,), None), 'mlp_norm': ((d,), None),
    }
    if n > 1:
        for sub in ('attn', 'mlp'):
            shapes[f'hc_{sub}_phi'] = ((n * d, 2 * n + n * n), n * d)
            shapes[f'hc_{sub}_b'] = ((2 * n + n * n,), 0)
            shapes[f'hc_{sub}_a'] = ((3,), 0)
    return shapes


def hc_bias_init(n: int) -> jax.Array:
    """A stream mixer's bias [2 n + n n] at init: both gates at
    rest, the residual matrix favouring the identity (2 on the
    diagonal, -2 off it, ahead of the Sinkhorn passes)."""
    return jnp.concatenate([jnp.zeros((2 * n,)),
                            4.0 * jnp.eye(n).reshape(-1) - 2.0])


def _init_latent_params(config: LlamaConfig, key: jax.Array, dense,
                        norm_init, dtype) -> Params:
    """``init_params`` of a latent stack: ``dense_layers`` (the
    first ``dense_first`` layers, a gated MLP of ``dense_ffn_hidden``)
    and ``layers`` (the expert layers), each stacked along a leading
    axis. The mixers start as the paper's: ``phi`` small, the gates'
    biases 0 and the residual matrix's bias favouring the identity,
    the three scalars 1."""
    d, ffn, e = config.dim, config.ffn_hidden, config.n_experts
    n = config.hc_mult

    def shared_leaves(k, count):
        out = {}
        for i, (name, (shape, fan_in)) in enumerate(
                latent_leaf_shapes(config).items()):
            kk = jax.random.fold_in(k, i)
            if fan_in is None:
                out[name] = norm_init((count, *shape))
            elif name.startswith('hc_') and name.endswith('_a'):
                out[name] = jnp.ones((count, 3), dtype)
            elif name.startswith('hc_') and name.endswith('_b'):
                out[name] = jnp.tile(hc_bias_init(n), (count, 1)
                                     ).astype(dtype)
            else:
                out[name] = dense(kk, (count, *shape), fan_in)
        return out

    kd, ke, k_embed, k_out = jax.random.split(key, 4)
    held = config.n_experts_held
    wide = config.n_shared_experts * ffn

    def expert_layers(k, count):
        ks = jax.random.split(k, 8)
        layers = dict(
            shared_leaves(ks[0], count),
            router=dense(ks[1], (count, d, e), d),
            w_gate=dense(ks[2], (count, held, d, ffn), d),
            w_up=dense(ks[3], (count, held, d, ffn), d),
            w_down=dense(ks[4], (count, held, ffn, d), ffn))
        if config.moe_select_bias:
            layers['router_bias'] = jnp.zeros((count, e), dtype)
        if config.n_shared_experts:
            layers.update(ws_gate=dense(ks[5], (count, d, wide), d),
                          ws_up=dense(ks[6], (count, d, wide), d),
                          ws_down=dense(ks[7], (count, wide, d), ffn))
        return layers

    params = {'embed': dense(k_embed, (config.vocab_size, d), d),
              'layers': expert_layers(
                  ke, config.n_layers - config.dense_first),
              'final_norm': norm_init((d,)),
              'lm_head': dense(k_out, (d, config.vocab_size), d)}
    if config.nextn_layers:
        # A key of its own: the other leaves keep their seeds.
        km = jax.random.split(jax.random.fold_in(key, 0x6d7470))
        params['mtp'] = {
            'enorm': norm_init((d,)), 'hnorm': norm_init((d,)),
            'eh_proj': dense(km[0], (2 * d, d), 2 * d),
            'layers': expert_layers(km[1], config.nextn_layers),
            'final_norm': norm_init((d,))}
    if config.dense_first:
        kf = jax.random.split(kd, 4)
        nd, wd = config.dense_first, config.dense_ffn_hidden
        params['dense_layers'] = dict(
            shared_leaves(kf[0], nd),
            w_gate=dense(kf[1], (nd, d, wd), d),
            w_up=dense(kf[2], (nd, d, wd), d),
            w_down=dense(kf[3], (nd, wd, d), wd))
    return params


def param_sharding_rules(config: LlamaConfig,
                         pipeline: bool = False) -> Params:
    """PartitionSpec per param over mesh axes (pp, fsdp, ep, tp).

    TP shards heads / ffn-hidden / vocab; FSDP shards the other big
    axis (ZeRO-3). Non-expert params fold 'ep' into the fsdp group
    (so an expert-parallel mesh still ZeRO-shards the dense weights);
    expert-stacked weights shard their expert axis over 'ep'. The
    scan-stacked layer axis is replicated, EXCEPT under pipeline
    parallelism (``pipeline=True``) where it shards over 'pp' so each
    stage holds only its own layers.
    """
    pl = 'pp' if pipeline else None
    fs = ('fsdp', 'ep')
    if config.kv_lora_rank is not None:
        return _latent_sharding_rules(config, pl, fs)
    if config.n_experts:
        mlp_rules = {
            'router': P(pl, fs, None),
            'w_gate': P(pl, 'ep', 'fsdp', 'tp'),
            'w_up': P(pl, 'ep', 'fsdp', 'tp'),
            'w_down': P(pl, 'ep', 'tp', 'fsdp'),
        }
        if config.n_shared_experts:
            mlp_rules.update(ws_gate=P(pl, fs, 'tp'),
                             ws_up=P(pl, fs, 'tp'),
                             ws_down=P(pl, 'tp', fs))
    else:
        mlp_rules = {
            'w_gate': P(pl, fs, 'tp'),
            'w_up': P(pl, fs, 'tp'),
            'w_down': P(pl, 'tp', fs),
        }
    rules = {
        'embed': P('tp', fs),
        'layers': {
            'wq': P(pl, fs, 'tp'),
            'wk': P(pl, fs, 'tp'),
            'wv': P(pl, fs, 'tp'),
            'wo': P(pl, 'tp', fs),
            **mlp_rules,
            'attn_norm': P(pl, None),
            'mlp_norm': P(pl, None),
        },
        'final_norm': P(None),
    }
    if config.parallel_block:
        del rules['layers']['mlp_norm']
    if config.qkv_bias:
        rules['layers']['bq'] = P(pl, 'tp')
        rules['layers']['bk'] = P(pl, 'tp')
        rules['layers']['bv'] = P(pl, 'tp')
    if config.sandwich_norms:
        rules['layers']['attn_out_norm'] = P(pl, None)
        rules['layers']['mlp_out_norm'] = P(pl, None)
    if config.exit_threshold is not None:
        rules['exit_gate_w'] = P(None, None)
        rules['exit_gate_b'] = P(None)
    if not config.tie_embeddings:
        rules['lm_head'] = P(fs, 'tp')
    return rules


def _latent_sharding_rules(config: LlamaConfig, pl, fs) -> Params:
    """A latent stack over (pp, fsdp, ep, tp): the per-head
    projections (``wq_b``, ``wkv_b``, ``wo``) shard their head axis
    over 'tp'; the two bottlenecks, their norms and the stream mixers
    are whole on every chip (the latent row has no head axis)."""
    whole = {name: P(pl, *([None] * len(shape)))
             for name, (shape, _) in latent_leaf_shapes(config).items()}
    whole.update(wq_a=P(pl, fs, None), wkv_a=P(pl, fs, None),
                 wq_b=P(pl, None, 'tp'), wkv_b=P(pl, None, 'tp'),
                 wo=P(pl, 'tp', fs))
    layers = dict(whole, router=P(pl, fs, None),
                  w_gate=P(pl, 'ep', 'fsdp', 'tp'),
                  w_up=P(pl, 'ep', 'fsdp', 'tp'),
                  w_down=P(pl, 'ep', 'tp', 'fsdp'))
    if config.moe_select_bias:
        layers['router_bias'] = P(pl, None)
    if config.n_shared_experts:
        layers.update(ws_gate=P(pl, fs, 'tp'), ws_up=P(pl, fs, 'tp'),
                      ws_down=P(pl, 'tp', fs))
    rules = {'embed': P('tp', fs), 'layers': layers,
             'final_norm': P(None), 'lm_head': P(fs, 'tp')}
    if config.nextn_layers:
        # The module's layer as the expert layers; its projection
        # and norms whole (a tp mesh has not run it: ROADMAP R4).
        rules['mtp'] = {'enorm': P(None), 'hnorm': P(None),
                        'eh_proj': P(fs, None), 'layers': layers,
                        'final_norm': P(None)}
    if config.dense_first:
        rules['dense_layers'] = dict(
            whole, w_gate=P(pl, fs, 'tp'), w_up=P(pl, fs, 'tp'),
            w_down=P(pl, 'tp', fs))
    return rules


# ---------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------


def matmul(x: jax.Array, w) -> jax.Array:
    """x @ w for plain or int8-quantized ({'q','s'}) weights — the
    canonical impl (``models.quant`` re-exports it). The int8 operand
    converts in-register (XLA fuses it into the dot); the per-output-
    channel scale applies after the matmul (exact for that scaling).
    Lives here so the TRAINING forward can run over an int8 frozen
    base (QLoRA) without an import cycle (quant imports llama)."""
    if isinstance(w, dict) and 'q' in w:
        out = x @ w['q'].astype(x.dtype)
        return out * w['s'].astype(out.dtype)
    return x @ w


def _rms_norm(x: jax.Array, weight: jax.Array, eps: float,
              offset: bool = False) -> jax.Array:
    xf = x.astype(jnp.float32)
    norm = xf * jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    w = weight.astype(jnp.float32)
    if offset:
        w = 1.0 + w  # Gemma's zero-centered norm weights
    return (norm * w).astype(x.dtype)


def norm(config: LlamaConfig, x: jax.Array,
         weight: jax.Array) -> jax.Array:
    """The configuration's norm: RMSNorm, or with
    ``config.layer_norm`` LayerNorm without bias,
    (x - mean) / sqrt(var + eps) * w, in float32."""
    if not config.layer_norm:
        return _rms_norm(x, weight, config.norm_eps, config.norm_offset)
    xf = x.astype(jnp.float32)
    centred = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    return (centred * jax.lax.rsqrt(var + config.norm_eps) *
            weight.astype(jnp.float32)).astype(x.dtype)


def _rope_frequencies(config: LlamaConfig, positions: jax.Array
                      ) -> jax.Array:
    """[T, head_dim/2] complex rotation angles (of a latent
    stack: over the ``qk_rope_head_dim`` rotated values)."""
    hd = (config.qk_rope_head_dim if config.kv_lora_rank is not None
          else config.head_dim)
    freqs = 1.0 / (config.rope_theta ** (
        jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    if config.rope_yarn is not None:
        # YaRN: pairs that turn more than beta_fast times over the
        # original context keep their frequency, those that turn
        # fewer than beta_slow times are slowed by ``factor``, a
        # linear ramp between (the published modelling code's
        # ``find_correction_range``, the bounds rounded outwards).
        factor, orig, fast, slow, _ = config.rope_yarn

        def turns_at(turns):
            return (hd * math.log(orig / (turns * 2 * math.pi)) /
                    (2 * math.log(config.rope_theta)))
        low = max(math.floor(turns_at(fast)), 0)
        high = min(math.ceil(turns_at(slow)), hd - 1)
        ramp = jnp.clip(
            (jnp.arange(hd // 2, dtype=jnp.float32) - low) /
            max(high - low, 0.001), 0.0, 1.0)
        freqs = freqs / factor * ramp + freqs * (1.0 - ramp)
    if config.rope_scaling:
        # Llama-3.1 NTK-style frequency scaling (factor 8, low/high
        # freq cutoffs 1 and 4, original context 8192).
        factor, low, high, orig = 8.0, 1.0, 4.0, 8192.0
        wavelen = 2.0 * jnp.pi / freqs
        ratio = orig / wavelen
        smooth = jnp.clip((ratio - low) / (high - low), 0.0, 1.0)
        scaled = jnp.where(ratio < low, freqs / factor,
                           jnp.where(ratio > high, freqs,
                                     (1 - smooth) * freqs / factor +
                                     smooth * freqs))
        freqs = scaled
    return positions.astype(jnp.float32)[:, None] * freqs[None, :]


def attention_scale(config: LlamaConfig) -> float:
    """What scores are multiplied by ahead of the softmax:
    head_dim^-0.5, under YaRN times (0.1 mscale_all_dim ln factor +
    1)^2."""
    scale = config.head_dim ** -0.5
    if config.rope_yarn is not None:
        factor, _, _, _, all_dim = config.rope_yarn
        scale *= (0.1 * all_dim * math.log(factor) + 1.0) ** 2
    return scale


def mlp_act(config: LlamaConfig):
    """The family's gated-MLP activation (single source of truth —
    llama._layer and decode._layer_cached both use it; the valid set
    is enforced in LlamaConfig.__post_init__)."""
    if config.mlp_activation == 'silu':
        return jax.nn.silu
    return functools.partial(jax.nn.gelu, approximate=True)


# Sentinel for the `mesh` argument of _moe_mlp/_layer/forward_hidden:
# bind sharding constraints to the AMBIENT mesh via bare PartitionSpecs
# (required inside a partial-manual shard_map, where a concrete
# NamedSharding would clash with the manual axis types).
AMBIENT_MESH = 'context'


def _moe_mlp(config: LlamaConfig, h: jax.Array, layer_params: Params,
             mesh=None, out_spec=None):
    """Top-k routed expert MLP (GShard-style static capacity
    dispatch; reference has no MoE — new scope, cf. SURVEY §2.11).

    h: [B, T, D] -> ([B, T, D], aux_loss scalar f32). Each batch row
    is a routing group with per-expert capacity
    ``ceil(top_k * T / E * capacity_factor)``; overflow tokens fall
    back to the residual stream (standard token dropping). All shapes
    are static so XLA tiles every einsum onto the MXU; the expert
    dimension is sharded over 'ep' (propagated by GSPMD from the
    expert-weight shardings), which lowers the dispatch/combine
    einsums to an all-to-all over ICI.
    """
    b, t, d = h.shape
    E, k = config.n_experts, config.moe_top_k
    # Router in fp32 (selective precision, Switch Transformer §2.4):
    # near-tie top-k flips on bf16 logits destabilize routing. The
    # [D, E] matmul is negligible next to the expert FFNs.
    logits = h.astype(jnp.float32) @ \
        layer_params['router'].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)             # [B, T, E]
    gate, idx = jax.lax.top_k(probs, k)                 # [B, T, k]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    sel = jax.nn.one_hot(idx, E, dtype=jnp.float32)     # [B, T, k, E]
    # Load-balance aux (Switch Transformer eq. 4, generalized to
    # top-k): fraction of routed slots x mean router prob per expert,
    # scaled so perfect balance gives exactly 1.0.
    frac = sel.sum(2).mean((0, 1)) / k
    aux = E * jnp.sum(frac * probs.mean((0, 1)))

    cap = min(int(math.ceil(k * t * config.moe_capacity_factor / E)),
              t)
    # Slot order is token-major: earlier tokens win buffer space.
    sel_flat = sel.reshape(b, t * k, E)
    pos = (jnp.cumsum(sel_flat, axis=1) - sel_flat).astype(jnp.int32)
    keep = sel_flat * (pos < cap)
    disp = keep[..., None] * jax.nn.one_hot(pos, cap,
                                            dtype=jnp.float32)
    comb = disp * gate.reshape(b, t * k)[:, :, None, None]
    disp = disp.reshape(b, t, k, E, cap).sum(2).astype(h.dtype)
    comb = comb.reshape(b, t, k, E, cap).sum(2).astype(h.dtype)

    def pin(arr, spec):
        # Explicit expert-major shardings: without these GSPMD falls
        # back to "involuntary full rematerialization" (replicate +
        # repartition) on the dispatch transposes.
        if mesh is None:
            return arr
        if mesh is AMBIENT_MESH:
            return jax.lax.with_sharding_constraint(arr, spec)
        from jax.sharding import NamedSharding
        return jax.lax.with_sharding_constraint(
            arr, NamedSharding(mesh, spec))

    # Remat save points mirror the dense MLP's: 'mlp'/'mlp_up' in
    # ``remat_saves`` keep the [E, B, C, ffn] expert activations, and
    # the dispatch/combine one-hots are always cheap-to-save names so
    # backward need not rebuild the [B, T*k, E, C] cumsum tensors.
    disp = checkpoint_name(disp, 'moe_dispatch')
    comb = checkpoint_name(comb, 'moe_dispatch')
    # expert_einsum: plain einsum for bf16 weights, int8-aware
    # (per-expert-channel scales applied post-contraction) for
    # weight-only-quantized serving.
    from skypilot_tpu.models.quant import expert_einsum

    xin = jnp.einsum('btec,btd->ebcd', disp, h)      # a2a: tok→exp
    xin = pin(xin, P('ep', ('dp', 'fsdp'), None, None))
    g = checkpoint_name(
        expert_einsum('ebcd,edf->ebcf', xin, layer_params['w_gate']),
        'mlp_gate')
    up = checkpoint_name(
        expert_einsum('ebcd,edf->ebcf', xin, layer_params['w_up']),
        'mlp_up')
    act = mlp_act(config)(g.astype(jnp.float32)).astype(h.dtype)
    xout = expert_einsum('ebcf,efd->ebcd', act * up,
                         layer_params['w_down'])
    xout = pin(xout, P('ep', ('dp', 'fsdp'), None, None))
    out = jnp.einsum('ebcd,btec->btd', xout, comb)   # a2a: exp→tok
    out = pin(out, out_spec if out_spec is not None
              else P(('dp', 'fsdp', 'ep'), None, None))
    return out, aux


_QKV_LEAVES = ('wq', 'wk', 'wv', 'bq', 'bk', 'bv')
# LoRA factors by how the tp axis meets them (lora_sharding_rules):
# the B factors are column-parallel like wq/wv, the A factors whole.
_LORA_COLS = ('wq_b', 'wv_b')
_LORA_WHOLE = ('wq_a', 'wv_a')


def _qkv_proj(config: LlamaConfig, h: jax.Array, w: Params,
              lora_params: Optional[Params], lora_scale: float):
    """h [B, t, D] -> pre-rotation q, k, v [B, t, heads, head_dim]
    for the heads whose columns ``w`` holds (all of them, or a tp
    device's share inside a collective product)."""
    b, t, _ = h.shape
    hd = config.head_dim
    # ``matmul`` (not @): base projections may be int8-quantized
    # dicts — frozen-base QLoRA trains bf16 adapters over an int8
    # base that would not fit HBM in bf16 (8B on a 16 GB chip).
    q = matmul(h, w['wq'])
    k = matmul(h, w['wk'])
    v = matmul(h, w['wv'])
    if config.qkv_bias:
        q = q + w['bq']
        k = k + w['bk']
        v = v + w['bv']
    q = q.reshape(b, t, -1, hd)
    k = k.reshape(b, t, -1, hd)
    v = v.reshape(b, t, -1, hd)
    if lora_params is not None:
        # LoRA on q/v projections (torchtune's default target set for
        # the reference recipe llm/llama-3_1-finetuning/lora.yaml).
        dq = ((h @ lora_params['wq_a']) @ lora_params['wq_b']) * \
            lora_scale
        dv = ((h @ lora_params['wv_a']) @ lora_params['wv_b']) * \
            lora_scale
        q = q + dq.reshape(q.shape).astype(q.dtype)
        v = v + dv.reshape(v.shape).astype(v.dtype)
    return q, k, v


def _mlp_up(config: LlamaConfig, h: jax.Array, w: Params) -> jax.Array:
    """The gated MLP up to its down projection's input."""
    # Save the PRE-activation gate (its backward needs it anyway) and up:
    # with these two named values kept, backward recomputes only
    # elementwise ops here, not the two [d, ffn] matmuls. Separate
    # names so remat_saves can keep just one of them when HBM is
    # tight.
    g_pre = checkpoint_name(matmul(h, w['w_gate']), 'mlp_gate')
    up = checkpoint_name(matmul(h, w['w_up']), 'mlp_up')
    gate = mlp_act(config)(g_pre.astype(jnp.float32)).astype(h.dtype)
    return gate * up


def _pick(tree: Params, names) -> Params:
    return {n: tree[n] for n in names if n in tree}


def _layer(config: LlamaConfig, x: jax.Array, layer_params: Params,
           angles: jax.Array, attn_impl,
           lora_params: Optional[Params] = None,
           lora_scale: float = 1.0, mesh=None, act_spec=None,
           tp_overlap=None):
    """One transformer block. Returns (y, moe_aux_loss) — the aux is
    0 for dense configs so the scan carry has one static shape.
    ``mesh``: a concrete Mesh for the MoE sharding pins, or
    ``AMBIENT_MESH`` to bind them to the ambient mesh (inside a
    partial-manual shard_map), or None to skip them.
    ``act_spec``: the [B, T, D] activation PartitionSpec (so the MoE
    combine restores e.g. the 'sp' sequence sharding).
    ``tp_overlap``: a ``parallel.collective_matmul.TpOverlap`` when x
    is sharded along the sequence over 'tp' (dense configs only): the
    four tp products then run as collective matmuls, their transfers
    beside them, and the norms and residual adds on a device's own
    sequence block."""
    require_plain_stack(config, 'llama._layer (the dense forward)')
    b, t, d = x.shape

    h = _rms_norm(x, layer_params['attn_norm'], config.norm_eps,
                  config.norm_offset)
    if tp_overlap is None:
        q, k, v = _qkv_proj(config, h, layer_params, lora_params,
                            lora_scale)
    else:
        # One gathered copy of h feeds all three products and the
        # LoRA A factors.
        lora_cols = lora_whole = None
        if lora_params is not None:
            lora_cols = _pick(lora_params, _LORA_COLS)
            lora_whole = _pick(lora_params, _LORA_WHOLE)

        def qkv_of_block(h_blk, cols, whole):
            w, lora_b = cols
            lora = None if lora_b is None else {**lora_b, **whole}
            return _qkv_proj(config, h_blk, w, lora, lora_scale)

        q, k, v = tp_overlap.gather_apply(
            qkv_of_block, h,
            (_pick(layer_params, _QKV_LEAVES), lora_cols), lora_whole)
    # RoPE is delegated to the attention impl: the Pallas kernels
    # rotate q/k blocks in VMEM (no separate f32 pass over HBM);
    # non-kernel impls (ring shards, XLA fallback) apply it via
    # ``attention_ops.apply_rope``.
    q = checkpoint_name(q, 'qkv')
    k = checkpoint_name(k, 'qkv')
    v = checkpoint_name(v, 'qkv')
    attn = attn_impl(q, k, v, angles)
    attn = attn.reshape(b, t, -1)
    if tp_overlap is None:
        x = x + matmul(attn, layer_params['wo'])
    else:
        x = x + tp_overlap.scatter_apply(matmul, attn,
                                         layer_params['wo'])

    h = _rms_norm(x, layer_params['mlp_norm'], config.norm_eps,
                  config.norm_offset)
    if config.n_experts:
        moe_out, aux = _moe_mlp(config, h, layer_params, mesh=mesh,
                                out_spec=act_spec)
        return x + moe_out, aux
    if tp_overlap is None:
        x = x + matmul(_mlp_up(config, h, layer_params),
                       layer_params['w_down'])
    else:
        x = x + tp_overlap.gather_scatter_apply(
            functools.partial(_mlp_up, config), matmul, h,
            _pick(layer_params, ('w_gate', 'w_up')),
            layer_params['w_down'])
    return x, jnp.zeros((), jnp.float32)


def default_attn_impl():
    """Single-device/auto-sharded attention: the Pallas flash kernel
    with RoPE fused in (shared default of ``forward_hidden`` and the
    pipeline-parallel path)."""
    return lambda q, k, v, ang: attention_ops.flash_attention(
        q, k, v, causal=True, rope_angles=ang)


def embed_tokens(cparams: Params, tokens: jax.Array,
                 config: LlamaConfig) -> jax.Array:
    """Token embedding lookup (+ Gemma's sqrt(dim) scaling) on
    compute-dtype params."""
    x = cparams['embed'][tokens]
    if config.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(config.dim), x.dtype)
    return x


def layer_remat_policy(config: LlamaConfig):
    """The per-layer remat save policy implied by
    ``config.remat_saves`` (+ flash-attention outputs, + MoE dispatch
    one-hots) — shared by ``forward_hidden`` and
    ``parallel/pipeline.py`` so pipelined stages save exactly what the
    plain scan does."""
    tokens_ = config.remat_saves.split('+')  # validated in config
    extra = []
    if 'mlp' in tokens_:
        extra += ['mlp_gate', 'mlp_up']
    if 'mlp_up' in tokens_:
        extra.append('mlp_up')
    if 'qkv' in tokens_:
        extra.append('qkv')
    if config.n_experts:
        # Dispatch/combine one-hots are cheap to keep and costly to
        # rebuild (cumsum over [B, T*k, E]) — always save.
        extra.append('moe_dispatch')
    base = (jax.checkpoint_policies.save_only_these_names(*extra)
            if extra else None)
    return attention_ops.remat_policy(base_policy=base)


def shifted_loss_mask(batch: Dict[str, jax.Array],
                      targets: jax.Array) -> jax.Array:
    """loss_mask aligns with ``tokens``: position i contributes iff
    its *target* token i+1 is unmasked."""
    mask = batch.get('loss_mask')
    return (jnp.ones_like(targets, jnp.float32) if mask is None
            else mask.astype(jnp.float32)[:, 1:])


def forward_hidden(params: Params, tokens: jax.Array,
                   config: LlamaConfig,
                   positions: Optional[jax.Array] = None,
                   attn_impl=None,
                   lora: Optional[Params] = None,
                   lora_scale: float = 1.0,
                   activation_sharding=None,
                   with_aux: bool = False, mesh=None,
                   tp_overlap=None):
    """tokens [B, T] int32 -> final hidden states [B, T, D]
    (post-final-norm, compute dtype). With ``with_aux`` returns
    (hidden, moe_aux_loss) — the layer-mean load-balance loss
    (always 0 for dense configs).

    Master params may be fp32; compute happens in ``config.dtype``
    (bf16 on the MXU). ``lora`` is an optional pytree of stacked
    [L, ...] adapters trained with the base frozen.

    ``activation_sharding``: optional PartitionSpec for [B, T, D]
    activations — used by sequence parallelism to pin the T axis onto
    the 'sp' mesh axis (ring attention supplies the cross-shard
    communication).

    ``tp_overlap``: a ``parallel.collective_matmul.TpOverlap`` from
    ``build_train_step`` on a mesh with 'tp' > 1 (dense configs
    only). Where T divides by tp the residual stream between the
    blocks is sharded along the sequence over 'tp' and each layer's
    tp products are collective matmuls (``_layer``); the hidden state
    is gathered once for the head.
    """
    if config.kv_lora_rank is not None:
        # Other leaves altogether: refused ahead of the layer scan.
        require_plain_stack(config, 'llama.forward_hidden (the dense '
                            'forward)')
    if attn_impl is None:
        attn_impl = default_attn_impl()
    _, t = tokens.shape
    if tp_overlap is not None:
        tp_overlap = tp_overlap.for_sequence(t)
    if tp_overlap is not None:
        activation_sharding = tp_overlap.seq_sharding
    if positions is None:
        positions = jnp.arange(t)
    angles = _rope_frequencies(config, positions)

    # Mixed precision: cast weights to the compute dtype at use site;
    # gradients flow back to the (possibly fp32) master params. int8
    # leaves (weight-only-quantized frozen base) must NOT upcast —
    # they cross HBM as int8 and convert in-register inside matmul.
    cparams = jax.tree.map(
        lambda p: p if p.dtype == jnp.int8 else p.astype(config.dtype),
        params)

    x = embed_tokens(cparams, tokens, config)  # [B, T, D] gather
    if activation_sharding is not None:
        x = jax.lax.with_sharding_constraint(x, activation_sharding)

    def scan_body(carry, scanned):
        x_c, aux_c = carry
        layer_params, layer_lora = scanned
        y, aux = _layer(config, x_c, layer_params, angles, attn_impl,
                        lora_params=layer_lora, lora_scale=lora_scale,
                        mesh=mesh,
                        act_spec=(activation_sharding.spec
                                  if activation_sharding is not None
                                  else None),
                        tp_overlap=tp_overlap)
        return (y, aux_c + aux), None

    body = scan_body
    if config.remat:
        # Per-layer remat, EXCEPT the flash-attention kernel outputs
        # (re-running the kernel costs ~3.4 ms/layer at (8, 2048) on
        # v5e vs ~66 MB/layer to save out+lse) and, depending on
        # ``config.remat_saves``, the big matmul outputs — see the
        # field's docstring for the memory/recompute trade.
        body = jax.checkpoint(scan_body, prevent_cse=False,
                              policy=layer_remat_policy(config))
    clora = None
    if lora is not None:
        clora = jax.tree.map(lambda p: p.astype(config.dtype), lora)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               (cparams['layers'], clora))

    hidden = _rms_norm(x, cparams['final_norm'], config.norm_eps,
                       config.norm_offset)
    if tp_overlap is not None:
        # The head shards the vocabulary over tp and reads every
        # position: one gather a step, not one a loss chunk.
        hidden = jax.lax.with_sharding_constraint(
            hidden, tp_overlap.whole_sharding)
    if with_aux:
        return hidden, aux / config.n_layers
    return hidden


def output_head(params: Params, config: LlamaConfig):
    """[D, V] output projection — the transposed embedding when the
    config ties them (Gemma, small Qwen; gradients flow back to the
    embedding through the transpose). May be an int8 {'q','s'} pair
    (weight-only-quantized serving / QLoRA frozen base) — consume it
    with ``matmul`` / the fused CE, not ``@``."""
    if config.tie_embeddings:
        return params['embed'].astype(config.dtype).T
    head = params['lm_head']
    if isinstance(head, dict) and 'q' in head:
        return head
    return head.astype(config.dtype)


def forward(params: Params, tokens: jax.Array, config: LlamaConfig,
            positions: Optional[jax.Array] = None,
            attn_impl=None,
            lora: Optional[Params] = None,
            lora_scale: float = 1.0) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, vocab] (fp32)."""
    x = forward_hidden(params, tokens, config, positions, attn_impl,
                       lora, lora_scale)
    return matmul(x, output_head(params, config)).astype(jnp.float32)


def _ce_from_logits(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-position NLL without materializing fp32 log-softmax of the
    full [.., V] tensor: lse is a reduction, the target logit a
    gather."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None],
                              axis=-1)[..., 0].astype(jnp.float32)
    return lse - tgt


def _head_shape(lm_head) -> tuple:
    if isinstance(lm_head, dict):
        return lm_head['q'].shape
    return lm_head.shape


def _head_mm(h: jax.Array, lm_head) -> jax.Array:
    """h @ W for a plain or int8 {'q','s'} head."""
    return matmul(h, lm_head)


def _head_mm_t(dlog: jax.Array, lm_head) -> jax.Array:
    """dlog @ W^T. For the quantized head W = q * s with s per
    OUTPUT channel (the V axis), dlog @ (q s)^T == (dlog * s) @ q^T —
    scale the cotangent columns, then contract against int8 codes."""
    if isinstance(lm_head, dict):
        scaled = dlog * lm_head['s'].astype(dlog.dtype)
        return scaled @ lm_head['q'].astype(dlog.dtype).T
    return dlog @ lm_head.T


@functools.lru_cache(maxsize=None)
def _fused_ce(train_lm_head: bool):
    """Chunked LM-head + cross-entropy with the hidden-state gradient
    computed EAGERLY in the forward pass (custom_vjp).

    dloss/dlogits = softmax - onehot is known in closed form, so each
    chunk's dhidden = dlogits @ W^T can be produced while the logits
    are still live — the backward then reads a tiny [B, T, D]
    residual instead of re-running the [D, 128k-vocab] matmul under
    remat. Per chunk: 2 vocab-size matmuls (3 with a trainable head)
    vs 3 (4) for checkpoint-and-recompute. Cotangents scale linearly
    in the upstream scalar, so deferring the g * (1/denom) factor to
    the backward is exact.

    Args (to the returned fn): hid [n, B, C, D]; lm_head [D, V] (or
    an int8 {'q','s'} pair — FROZEN heads only: QLoRA); tgt/msk
    [n, B, C]. Returns mean NLL over unmasked positions.
    """

    @jax.custom_vjp
    def fused(hid, lm_head, tgt, msk):
        def body(carry, xs):
            ns, ms = carry
            h, tg, mk = xs
            nll = _ce_from_logits(_head_mm(h, lm_head), tg)
            return (ns + (nll * mk).sum(), ms + mk.sum()), None

        (ns, ms), _ = jax.lax.scan(
            jax.checkpoint(body, prevent_cse=False),
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (hid, tgt, msk))
        return ns / jnp.maximum(ms, 1.0)

    def fwd(hid, lm_head, tgt, msk):
        d, v = _head_shape(lm_head)

        def body(carry, xs):
            ns, ms, dw = carry
            h, tg, mk = xs
            logits = _head_mm(h, lm_head).astype(
                jnp.float32)  # [B, C, V]
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt_logit = jnp.take_along_axis(
                logits, tg[..., None], axis=-1)[..., 0]
            nll = lse - tgt_logit
            # XLA fuses softmax-minus-onehot into one pass over the
            # bf16 logits; no fp32 [B, C, V] temp is materialized.
            dlog = jnp.exp(logits - lse[..., None])
            dlog = (dlog - jax.nn.one_hot(tg, v, dtype=jnp.float32))
            dlog = (dlog * mk[..., None]).astype(h.dtype)
            dh = _head_mm_t(dlog, lm_head)
            if train_lm_head:
                dw = dw + jnp.einsum(
                    'bcd,bcv->dv', h, dlog,
                    preferred_element_type=jnp.float32)
            return (ns + (nll * mk).sum(), ms + mk.sum(), dw), dh

        dw0 = (jnp.zeros((d, v), jnp.float32) if train_lm_head
               else jnp.zeros((0, v), jnp.float32))
        (ns, ms, dw), dh = jax.lax.scan(
            body,
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
             dw0),
            (hid, tgt, msk))
        denom = jnp.maximum(ms, 1.0)
        # A quantized frozen head needs a STRUCTURE-matching zero
        # cotangent: float0 for the int8 codes (0 bytes) + a tiny
        # zeros 's'. Dense frozen heads rebuild their zeros in bwd
        # from shape info instead (a [D, V] zeros residual would not
        # be free).
        dlm_zero = None
        if not train_lm_head and isinstance(lm_head, dict):
            import numpy as np

            from jax import dtypes as jax_dtypes
            dlm_zero = {'q': np.zeros(lm_head['q'].shape,
                                      dtype=jax_dtypes.float0),
                        's': jnp.zeros_like(lm_head['s'])}
        return ns / denom, (dh, dw, denom, dlm_zero)

    def bwd(res, g):
        dh, dw, denom, dlm_zero = res
        scale = g / denom
        dhid = dh * scale.astype(dh.dtype)
        if train_lm_head:
            dlm = (dw * scale).astype(dh.dtype)
        elif dlm_zero is not None:
            dlm = dlm_zero  # frozen quantized head: dead cotangent
        else:
            # Frozen dense head: shape carried by the 0-byte residual.
            dlm = jnp.zeros((dh.shape[-1], dw.shape[-1]), dh.dtype)
        return dhid, dlm, None, None

    fused.defvjp(fwd, bwd)
    return fused


# Sequence-chunk size for the fused head+CE scan. 512 keeps the fp32
# temp at B*512*V — ~0.25 GB/B-row for the 128k Llama-3 vocab.
LOSS_CHUNK = 512


def loss_fn(params: Params, batch: Dict[str, jax.Array],
            config: LlamaConfig,
            lora: Optional[Params] = None,
            lora_scale: float = 1.0,
            attn_impl=None,
            activation_sharding=None, mesh=None,
            tp_overlap=None) -> jax.Array:
    """Causal LM cross-entropy over positions predicting
    ``tokens[:, 1:]`` (mask-aware if batch has 'loss_mask').

    The LM head and the CE are fused in a sequence-chunked
    ``lax.scan`` so the [B, T, vocab] logits are never materialized —
    with Llama-3's 128k vocab that temp alone would exceed a v5e
    chip's HBM at batch 16 (observed: 15.7 GB fp32).
    """
    tokens = batch['tokens']
    # Contract: ``tokens`` is [B, T+1]. The forward runs on the first
    # T positions and position i predicts tokens[:, i+1]. T (not T±1)
    # is the activation length everywhere, so batches built with
    # T % sp == 0 keep ring-attention shards even AND T stays
    # block-divisible for the Pallas flash kernels (a T+1 activation
    # length silently fell back to the O(T^2) XLA attention path —
    # ~30% step-time regression at seq 2048).
    inputs = tokens[:, :-1]
    targets = tokens[:, 1:]
    hidden, moe_aux = forward_hidden(
        params, inputs, config, lora=lora, lora_scale=lora_scale,
        attn_impl=attn_impl, activation_sharding=activation_sharding,
        with_aux=True, mesh=mesh, tp_overlap=tp_overlap)
    mask = shifted_loss_mask(batch, targets)

    # The head is frozen exactly when training LoRA adapters — skip
    # the [D, V] grad matmul then (its cotangent would be dead).
    ce = loss_from_hidden(params, hidden, targets, mask, config,
                          train_lm_head=lora is None)
    if config.n_experts:
        ce = ce + config.moe_aux_coef * moe_aux
    return ce


def loss_from_hidden(params: Params, hidden: jax.Array,
                     targets: jax.Array, mask: jax.Array,
                     config: LlamaConfig,
                     train_lm_head: bool = True) -> jax.Array:
    """Chunked fused LM-head + CE over final hidden states (shared by
    ``loss_fn`` and the pipeline-parallel loss in
    ``parallel/pipeline.py``)."""
    lm_head = output_head(params, config)
    b, t, d = hidden.shape
    chunk = LOSS_CHUNK if t % LOSS_CHUNK == 0 else t
    n = t // chunk
    # [n, B, chunk, ...] so scan iterates sequence chunks.
    hid = hidden.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    tgt = targets.reshape(b, n, chunk).transpose(1, 0, 2)
    msk = mask.reshape(b, n, chunk).transpose(1, 0, 2)
    return _fused_ce(train_lm_head=train_lm_head)(hid, lm_head, tgt,
                                                  msk)
