"""Model configuration (reference: `python/triton_dist/models/config.py`
`ModelConfig:31`)."""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class ModelConfig:
    architecture: str = "qwen3"
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 6144
    num_layers: int = 28
    num_heads: int = 16
    num_kv_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    qk_norm: bool = True
    tie_word_embeddings: bool = True
    max_seq_len: int = 4096
    dtype: str = "bfloat16"
    # MoE (Qwen3-MoE style: every MLP is an expert layer when
    # num_experts > 0; reference e2e: test_ep_moe_inference.py)
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_intermediate_size: Optional[int] = None   # per-expert ffn
    moe_capacity_factor: float = 2.0
    #: Int8-quantize the KV cache (per-token scales): halves the cache
    #: footprint and decode's KV bandwidth (kernels/flash_decode.py).
    quantize_kv_cache: bool = False
    # Latent attention (MLA; `models/glm4_moe_lite.py`): queries go
    # through a rank-``q_lora_rank`` bottleneck, keys and values are
    # expanded from ONE ``kv_lora_rank``-wide latent a token, and a
    # ``qk_rope_head_dim``-wide rotated key is shared by all heads.
    # ``kv_lora_rank`` 0 = ordinary attention (``head_dim`` above).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Sparse feed-forward of the same family: layers below
    # ``first_k_dense_replace`` are dense (``intermediate_size``), the
    # rest route each token to ``num_experts_per_tok`` of
    # ``num_experts`` by sigmoid scores (dropless) beside
    # ``n_shared_experts`` always-on experts.
    first_k_dense_replace: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    #: This chip's share of a layer's experts, ``(lo, hi)`` of
    #: ``num_experts`` (`layers.moe_mlp.SparseMoE.held`); None: all.
    experts_held: Optional[tuple] = None
    # Hybrid of softmax and linear attention
    # (`models/solar_open2.py`): the layers named in ``gqa_layers``
    # are grouped-query attention (``num_heads`` / ``num_kv_heads`` /
    # ``head_dim`` above; ``use_rope`` False: no positional encoding;
    # ``use_gqa_gate``: an output gate), every other layer is Kimi
    # Delta Attention with ``kda_num_heads`` heads of ``kda_head_dim``,
    # a short convolution of ``kda_conv_size`` taps and decay and gate
    # maps of rank ``kda_rank``.  ``kda_num_heads`` 0: no such layer.
    gqa_layers: tuple = ()
    use_rope: bool = True
    use_gqa_gate: bool = False
    kda_num_heads: int = 0
    kda_head_dim: int = 128
    kda_conv_size: int = 4
    kda_rank: int = 128
    kda_allow_neg_eigval: bool = True
    # Generation by diffusion over blocks (`models/sdar_moe.py`):
    # ``block_length`` > 1 positions are denoised together — attention
    # is causal across blocks and bidirectional inside one — in
    # ``denoising_steps`` passes a block (it divides the block), the
    # positions to reveal chosen by ``remasking`` (``"sequential"`` |
    # ``"low_confidence_static"``); ``mask_token_id`` stands where a
    # position is not revealed yet.  0: one token a step.
    block_length: int = 0
    mask_token_id: int = 0
    denoising_steps: int = 1
    remasking: str = "sequential"
    #: The sparse layer's router (`layers.moe_mlp.SparseMoE.scoring`).
    moe_scoring: str = "sigmoid"
    # State-space hybrid (`models/nemotron_h.py`): a layer is ONE
    # mixer, named by its character of ``layer_pattern`` — ``M`` a
    # Mamba-2 layer (``mamba_num_heads`` heads of ``mamba_head_dim``
    # channels over a state of ``ssm_state_size``, ``mamba_n_groups``
    # groups of B and C, a convolution of ``mamba_conv_size`` taps),
    # ``*`` grouped-query attention (the keys above), ``E`` a sparse
    # feed-forward whose experts are of the form ``moe_act``
    # (`layers.moe_mlp.SparseMoE.act`) in a latent of
    # ``moe_latent_size`` (None: the hidden stream) beside a shared
    # expert ``moe_shared_intermediate_size`` wide.  "": not this
    # family.
    layer_pattern: str = ""
    mamba_num_heads: int = 0
    mamba_head_dim: int = 64
    mamba_n_groups: int = 8
    ssm_state_size: int = 128
    mamba_conv_size: int = 4
    moe_act: str = "silu"
    moe_latent_size: Optional[int] = None
    moe_shared_intermediate_size: Optional[int] = None
    # Window and full attention mixed (`models/cohere2_moe.py`): layer
    # l is named by ``layer_types[l]`` — ``"sliding_attention"`` sees
    # the last ``sliding_window`` tokens and rotates ADJACENT pairs
    # (``rope_pairs``), ``"full_attention"`` is causal with no
    # positions — in a PARALLEL block: ONE LayerNorm (``rms_norm_eps``
    # its epsilon) feeds attention and the expert layer alike, and both
    # are added to the residual.  The ``n_shared_experts`` are combined
    # by ``moe_shared_combine`` (`layers.moe_mlp.SparseMoE`), the
    # sigmoid router has a selection bias or none, the tied head's
    # logits are scaled by ``logit_scale``.  (): not this family.
    layer_types: tuple = ()
    sliding_window: int = 0
    rope_pairs: bool = False
    moe_shared_combine: str = "sum"
    moe_selection_bias: bool = True
    logit_scale: float = 1.0

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @classmethod
    def qwen3_0_6b(cls):
        return cls(hidden_size=1024, intermediate_size=3072,
                   num_layers=28, num_heads=16, num_kv_heads=8,
                   head_dim=128)

    @classmethod
    def qwen3_8b(cls):
        return cls(hidden_size=4096, intermediate_size=12288,
                   num_layers=36, num_heads=32, num_kv_heads=8,
                   head_dim=128, tie_word_embeddings=False)

    @classmethod
    def qwen3_32b(cls):
        return cls(hidden_size=5120, intermediate_size=25600,
                   num_layers=64, num_heads=64, num_kv_heads=8,
                   head_dim=128, tie_word_embeddings=False)

    @classmethod
    def draft_of(cls, target: "ModelConfig", **kw):
        """A cheap DRAFT model beside ``target`` for speculative
        decoding (`serving.speculative.DraftModelDrafter`): same
        vocabulary (the draft must share the target's tokenizer —
        proposals are token ids), same sequence capacity and dtype,
        but a fraction of the depth/width, so one draft step costs a
        small slice of a target step.  Defaults give a ~0.1B-class
        drafter beside the 0.6B–32B Qwen3 configs; override any field
        via ``kw``."""
        d = dict(architecture=target.architecture,
                 vocab_size=target.vocab_size,
                 hidden_size=512, intermediate_size=1536,
                 num_layers=4, num_heads=8, num_kv_heads=4,
                 head_dim=64, rms_norm_eps=target.rms_norm_eps,
                 rope_theta=target.rope_theta,
                 tie_word_embeddings=True,
                 max_seq_len=target.max_seq_len,
                 dtype=target.dtype,
                 quantize_kv_cache=target.quantize_kv_cache)
        d.update(kw)
        return cls(**d)

    @classmethod
    def tiny(cls, **kw):
        """Test-size config."""
        d = dict(vocab_size=256, hidden_size=128, intermediate_size=256,
                 num_layers=2, num_heads=8, num_kv_heads=4, head_dim=16,
                 max_seq_len=128)
        d.update(kw)
        return cls(**d)

    @classmethod
    def tiny_moe(cls, **kw):
        """Test-size MoE config."""
        d = dict(num_experts=4, num_experts_per_tok=2,
                 moe_intermediate_size=128)
        d.update(kw)
        return cls.tiny(**d)

    @classmethod
    def tiny_glm4_moe_lite(cls, **kw):
        """Test-size latent-attention + sparse-expert config: the
        published ratios (rope 64 of a 256 head; one leading dense
        layer; top-4 with one shared expert) at widths the CPU walks."""
        d = dict(architecture="glm4_moe_lite", vocab_size=256,
                 hidden_size=128, intermediate_size=256, num_layers=3,
                 num_heads=4, num_kv_heads=4, head_dim=0,
                 rms_norm_eps=1e-5, rope_theta=1e6, qk_norm=False,
                 tie_word_embeddings=False, max_seq_len=128,
                 q_lora_rank=64, kv_lora_rank=128, qk_nope_head_dim=32,
                 qk_rope_head_dim=32, v_head_dim=64,
                 num_experts=8, num_experts_per_tok=4,
                 moe_intermediate_size=128, first_k_dense_replace=1,
                 n_shared_experts=1, routed_scaling_factor=1.8,
                 norm_topk_prob=True)
        d.update(kw)
        return cls(**d)

    @classmethod
    def tiny_solar_open2(cls, **kw):
        """Test-size hybrid: one grouped-query layer (no positions,
        gated) before two delta-rule layers, every feed-forward a
        share (8 of 16) of a top-4 expert layer with a shared expert.
        The delta-rule heads keep their published 128: the kernels'
        state tile."""
        d = dict(architecture="solar_open2", vocab_size=256,
                 hidden_size=128, intermediate_size=256, num_layers=3,
                 num_heads=8, num_kv_heads=2, head_dim=16,
                 rms_norm_eps=1e-5, qk_norm=False,
                 tie_word_embeddings=False, max_seq_len=128,
                 num_experts=16, num_experts_per_tok=4,
                 moe_intermediate_size=128, n_shared_experts=1,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 experts_held=(0, 8), gqa_layers=(0,), use_rope=False,
                 use_gqa_gate=True, kda_num_heads=8, kda_head_dim=128,
                 kda_conv_size=4, kda_rank=128,
                 kda_allow_neg_eigval=True)
        d.update(kw)
        return cls(**d)

    @classmethod
    def tiny_sdar_moe(cls, **kw):
        """Test-size block-diffusion decoder: grouped-query attention
        with q/k norms (8 query heads a KV head, as published), every
        feed-forward a dropless top-4 of 16 experts by softmax scores
        with no shared expert; blocks of 4 positions denoised in 2
        passes; the mask token is the vocabulary's last id."""
        d = dict(architecture="sdar_moe", vocab_size=256,
                 hidden_size=128, intermediate_size=256, num_layers=2,
                 num_heads=8, num_kv_heads=1, head_dim=16,
                 rms_norm_eps=1e-6, rope_theta=1e6, qk_norm=True,
                 tie_word_embeddings=False, max_seq_len=128,
                 num_experts=16, num_experts_per_tok=4,
                 moe_intermediate_size=64, n_shared_experts=0,
                 norm_topk_prob=True, moe_scoring="softmax",
                 block_length=4, mask_token_id=255, denoising_steps=2,
                 remasking="sequential")
        d.update(kw)
        return cls(**d)

    @classmethod
    def tiny_nemotron_h(cls, **kw):
        """Test-size state-space hybrid: two Mamba-2 layers, one
        grouped-query layer without positions and two latent expert
        layers (a share, 8 of 32, of a top-6 layer of two-matrix
        squared-ReLU experts in a latent half the hidden size, beside
        an ungated shared expert), one mixer a layer.  The Mamba-2
        head keeps its published 64 x 128 state: the kernels' tile."""
        d = dict(architecture="nemotron_h", vocab_size=256,
                 hidden_size=128, intermediate_size=96, num_layers=5,
                 num_heads=8, num_kv_heads=2, head_dim=16,
                 rms_norm_eps=1e-5, qk_norm=False, use_rope=False,
                 tie_word_embeddings=False, max_seq_len=128,
                 layer_pattern="MEM*E", mamba_num_heads=8,
                 mamba_head_dim=64, mamba_n_groups=2,
                 ssm_state_size=128, mamba_conv_size=4,
                 num_experts=32, num_experts_per_tok=6,
                 moe_intermediate_size=96, n_shared_experts=1,
                 moe_shared_intermediate_size=192, moe_latent_size=64,
                 moe_act="relu2", routed_scaling_factor=5.0,
                 norm_topk_prob=True, experts_held=(0, 8))
        d.update(kw)
        return cls(**d)

    @classmethod
    def from_smallthinker(cls, d: dict, **kw):
        """From the SmallThinker family's published `config.json` keys
        (``d``), mapped onto the fields the layers read: the layer
        kinds from ``sliding_window_layout`` (1: a window layer, 0: a
        full one) — ``rope_layout`` is the same list: a window layer
        rotates, a full one has no positions —, the gated-ReLU expert
        layer (whose router the model class hands the layer's INPUT) from
        the ``moe_*primary*`` keys (softmax over the chosen =
        ``moe_primary_router_apply_softmax`` + ``norm_topk_prob``)."""
        layout = [int(x) for x in d["sliding_window_layout"]]
        n = d["num_hidden_layers"]
        assert [int(x) for x in d["rope_layout"]] == layout, (
            "a window layer rotates and a full layer does not")
        assert len(layout) >= n, (layout, n)
        assert d["moe_primary_router_apply_softmax"]
        assert d.get("rope_scaling") is None
        fields = dict(
            architecture="smallthinker", vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["moe_ffn_hidden_size"], num_layers=n,
            num_heads=d["num_attention_heads"],
            num_kv_heads=d["num_key_value_heads"],
            head_dim=d["head_dim"], rms_norm_eps=d["rms_norm_eps"],
            rope_theta=d["rope_theta"], qk_norm=False, rope_pairs=False,
            tie_word_embeddings=d["tie_word_embeddings"],
            max_seq_len=d["max_position_embeddings"],
            num_experts=d["moe_num_primary_experts"],
            num_experts_per_tok=d["moe_num_active_primary_experts"],
            moe_intermediate_size=d["moe_ffn_hidden_size"],
            n_shared_experts=0, norm_topk_prob=d["norm_topk_prob"],
            moe_scoring="softmax", moe_act="relu",
            layer_types=tuple("sliding_attention" if x else
                              "full_attention" for x in layout[:n]),
            sliding_window=d["sliding_window_size"])
        fields.update(kw)
        return cls(**fields)

    @classmethod
    def from_hf(cls, model_name_or_path: str):
        """Build from a HuggingFace config (reference loads HF weights;
        here we map the config; weights via `Qwen3.load_hf_weights`)."""
        from transformers import AutoConfig
        hf = AutoConfig.from_pretrained(model_name_or_path)
        return cls(
            architecture=(hf.architectures or ["qwen3"])[0],
            vocab_size=hf.vocab_size,
            hidden_size=hf.hidden_size,
            intermediate_size=hf.intermediate_size,
            num_layers=hf.num_hidden_layers,
            num_heads=hf.num_attention_heads,
            num_kv_heads=getattr(hf, "num_key_value_heads",
                                 hf.num_attention_heads),
            head_dim=getattr(hf, "head_dim",
                             hf.hidden_size // hf.num_attention_heads),
            rms_norm_eps=getattr(hf, "rms_norm_eps", 1e-6),
            rope_theta=getattr(hf, "rope_theta", 1e6),
            tie_word_embeddings=getattr(hf, "tie_word_embeddings", False),
            num_experts=getattr(hf, "num_experts", 0),
            num_experts_per_tok=getattr(hf, "num_experts_per_tok", 2),
            moe_intermediate_size=getattr(hf, "moe_intermediate_size",
                                          None),
        )
