"""Transformer model family — the modern sequence stack.

BERT-base is the driver's stretch import target (BASELINE.md #5); long-context
causal LMs are where the framework's sequence parallelism earns its keep.
These models are plain Sequential stacks of TransformerEncoderBlock, so they
serialize/train/evaluate through the same machinery as every zoo CNN — plus
``sharded_lm`` builds the fully-sharded (dp x tp x sp) training step used by
``__graft_entry__.dryrun_multichip``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import optax
from jax.sharding import Mesh

from ..nn import layers as L
from ..nn.model import NetConfig, Sequential, SequentialBuilder
from ..parallel.sharding import TRANSFORMER_RULES
from .zoo import ZooModel, register_model


@register_model
class BertBase(ZooModel):
    """BERT-base-uncased shape: 12 layers, d=768, h=12, vocab 30522.

    Built from the generic layer catalog; the Keras/HF import path
    (keras_import/) targets this architecture.
    """

    num_layers = 12
    d_model = 768
    num_heads = 12
    vocab = 30522
    max_len = 512
    input_shape = (128,)  # (T,) int token ids
    num_classes = 2  # default classification head

    def __init__(self, num_classes=None, seed=12345, input_shape=None, *, small=False,
                 flash=False, remat=False, ragged=True, **kw):
        super().__init__(num_classes, seed, input_shape, **kw)
        self.flash = flash
        self.remat = remat
        # ragged=True (default): (B, T) masks are treated as RIGHT-PADDED
        # (how BERT tokenizers pad) and ride the flash kernel's faster
        # per-example-lengths path. Pass ragged=False for gappy/packed
        # masks — they then take the exact key_mask path bit-for-bit.
        self.ragged = ragged
        if small:  # test-sized variant
            self.num_layers, self.d_model, self.num_heads, self.vocab, self.max_len = 2, 64, 4, 1000, 128

    def build(self) -> Sequential:
        T = self.input_shape[0]
        b = (SequentialBuilder(NetConfig(seed=self.seed,
                                         updater={"type": "adamw", "learning_rate": 1e-4}))
             .input_shape(T)
             .layer(L.EmbeddingSequence(n_in=self.vocab, n_out=self.d_model))
             .layer(L.PositionalEmbedding(max_len=self.max_len)))
        for _ in range(self.num_layers):
            b.layer(L.TransformerEncoderBlock(num_heads=self.num_heads, causal=False,
                                              flash=self.flash, remat=self.remat,
                                              ragged=self.ragged))
        return (b.layer(L.LayerNorm())
                .layer(L.GlobalPooling(mode="avg"))
                .layer(L.Output(n_out=self.num_classes, activation="softmax", loss="mcxent"))
                .build())


@register_model
class CausalLM(ZooModel):
    """GPT-style causal LM — the long-context flagship."""

    num_layers = 4
    d_model = 256
    num_heads = 8
    vocab = 512
    input_shape = (256,)

    def __init__(self, num_classes=None, seed=12345, input_shape=None, *,
                 num_layers=None, d_model=None, num_heads=None, vocab=None,
                 flash=False, remat=False, ring=False, pos="learned",
                 num_kv_heads=None, window=None, **kw):
        super().__init__(num_classes, seed, input_shape, **kw)
        self.num_layers = num_layers or self.num_layers
        self.d_model = d_model or self.d_model
        self.num_heads = num_heads or self.num_heads
        self.vocab = vocab or self.vocab
        self.num_classes = self.vocab
        self.flash = flash
        self.remat = remat
        self.ring = ring
        if pos not in ("learned", "rope"):
            raise ValueError(f"pos must be 'learned' or 'rope', got {pos!r}")
        self.pos = pos
        self.num_kv_heads = num_kv_heads  # GQA: shrink KV proj + decode cache
        self.window = window  # sliding-window attention (Mistral-style)

    def build(self) -> Sequential:
        T = self.input_shape[0]
        b = (SequentialBuilder(NetConfig(seed=self.seed,
                                         updater={"type": "adamw", "learning_rate": 3e-4}))
             .input_shape(T)
             .layer(L.EmbeddingSequence(n_in=self.vocab, n_out=self.d_model)))
        rope = self.pos == "rope"
        if not rope:
            # learned absolute table; at long context prefer pos="rope"
            # (a T=64k table is 100M params at d=1536 and cannot
            # extrapolate past max_len)
            b.layer(L.PositionalEmbedding(max_len=max(T, 512)))
        for _ in range(self.num_layers):
            b.layer(L.TransformerEncoderBlock(num_heads=self.num_heads, causal=True,
                                              flash=self.flash, remat=self.remat,
                                              ring=self.ring, rope=rope,
                                              num_kv_heads=self.num_kv_heads,
                                              window=self.window))
        b.layer(L.LayerNorm())
        b.layer(L.RnnOutput(n_out=self.vocab, activation="softmax", loss="mcxent"))
        return b.build()


@register_model
class OlmoeLM(ZooModel):
    """OLMoE (``allenai/OLMoE-1B-7B``): RoPE decoder blocks with q/k RMSNorm
    and a dropless top-k sparse SwiGLU expert layer (``nn/layers/olmoe.py``),
    a final RMSNorm and an untied, bias-free head. The defaults are the
    published 1B-7B sizes; ``dtype`` is the dtype the parameters are HELD in
    (``NetConfig.dtype``): ``"bfloat16"`` serves one bf16 tree, as the
    publication ships it, with no second copy (no ``compute_dtype``)."""

    input_shape = (4096,)

    def __init__(self, num_classes=None, seed=12345, input_shape=None, *,
                 num_layers=16, d_model=2048, num_heads=16, num_kv_heads=None,
                 num_experts=64, top_k=8, expert_width=1024, vocab=50304,
                 rms_eps=1e-5, rope_base=10000.0, dtype="float32", **kw):
        super().__init__(num_classes, seed, input_shape, **kw)
        self.num_layers, self.d_model, self.vocab = num_layers, d_model, vocab
        self.num_classes = vocab
        self.rms_eps = rms_eps
        self.dtype = dtype
        self.block = L.OlmoeBlock(
            num_heads=num_heads, num_kv_heads=num_kv_heads,
            num_experts=num_experts, top_k=top_k, expert_width=expert_width,
            eps=rms_eps, rope_base=rope_base)

    def build(self) -> Sequential:
        init = "normal_0.02"   # initializer_range
        b = (SequentialBuilder(NetConfig(
                seed=self.seed, dtype=self.dtype,
                updater={"type": "adamw", "learning_rate": 3e-4}))
             .input_shape(self.input_shape[0])
             .layer(L.EmbeddingSequence(n_in=self.vocab, n_out=self.d_model,
                                        weight_init=init)))
        for _ in range(self.num_layers):
            b.layer(self.block)
        b.layer(L.RMSNorm(eps=self.rms_eps))
        b.layer(L.RnnOutput(n_out=self.vocab, activation="softmax",
                            loss="mcxent", use_bias=False, weight_init=init))
        return b.build()


@register_model
class Glm4MoeLiteLM(ZooModel):
    """GLM-4.7-Flash (``zai-org/GLM-4.7-Flash``, ``glm4_moe_lite``): latent
    attention in every layer, ``first_k_dense`` leading layers with a dense
    SwiGLU and the rest with sigmoid-routed experts beside a shared one
    (``nn/layers/glm4_moe_lite.py``), a final RMSNorm and an untied,
    bias-free head. The first zoo model whose layers are not all alike. The
    defaults are the published sizes; ``dtype`` is the dtype the parameters
    and the cache are HELD in (``NetConfig.dtype``), as for ``OlmoeLM``."""

    input_shape = (8192,)

    def __init__(self, num_classes=None, seed=12345, input_shape=None, *,
                 num_layers=47, first_k_dense=1, d_model=2048, num_heads=20,
                 q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
                 qk_rope_head_dim=64, v_head_dim=256, dense_width=10240,
                 num_experts=64, top_k=4, expert_width=1536,
                 shared_experts=1, routed_scale=1.8, vocab=154880,
                 rms_eps=1e-5, rope_base=1e6, dtype="float32", **kw):
        super().__init__(num_classes, seed, input_shape, **kw)
        self.num_layers, self.first_k_dense = num_layers, first_k_dense
        self.d_model, self.vocab = d_model, vocab
        self.num_classes = vocab
        self.rms_eps = rms_eps
        self.dtype = dtype
        attention = dict(
            num_heads=num_heads, q_lora_rank=q_lora_rank,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            eps=rms_eps, rope_base=rope_base)
        self.dense_block = L.Glm4MoeLiteBlock(
            num_experts=0, ffn_width=dense_width, **attention)
        self.expert_block = L.Glm4MoeLiteBlock(
            num_experts=num_experts, top_k=top_k, ffn_width=expert_width,
            shared_experts=shared_experts, routed_scale=routed_scale,
            **attention)

    def build(self) -> Sequential:
        init = "normal_0.02"   # initializer_range
        b = (SequentialBuilder(NetConfig(
                seed=self.seed, dtype=self.dtype,
                updater={"type": "adamw", "learning_rate": 3e-4}))
             .input_shape(self.input_shape[0])
             .layer(L.EmbeddingSequence(n_in=self.vocab, n_out=self.d_model,
                                        weight_init=init)))
        for i in range(self.num_layers):
            b.layer(self.dense_block if i < self.first_k_dense
                    else self.expert_block)
        b.layer(L.RMSNorm(eps=self.rms_eps))
        b.layer(L.RnnOutput(n_out=self.vocab, activation="softmax",
                            loss="mcxent", use_bias=False, weight_init=init))
        return b.build()


@register_model
class LagunaLM(ZooModel):
    """Laguna (``poolside/Laguna-S-2.1``, ``laguna``): decoder layers of two
    kinds, full attention (YaRN on half of each head) at every ``period``-th
    layer from the first and sliding-window attention (plain rope) between,
    with DIFFERENT numbers of query heads and one output gate a head;
    ``first_k_dense`` leading layers with a dense SwiGLU and the rest with
    softmax-routed experts beside a shared one (``nn/layers/laguna.py``), a
    final RMSNorm and an untied, bias-free head. The defaults are the
    published sizes. ``experts_held = (first, count)`` builds one chip's share
    of an expert-parallel deployment: every expert layer holds ``count`` of
    the ``num_experts`` its router chooses among. ``dtype`` is the dtype the
    parameters and the cache are HELD in, as for ``OlmoeLM``."""

    input_shape = (8192,)

    def __init__(self, num_classes=None, seed=12345, input_shape=None, *,
                 num_layers=48, first_k_dense=1, period=4, d_model=3072,
                 full_heads=48, sliding_heads=72, num_kv_heads=8,
                 head_dim=128, window=512, dense_width=12288,
                 num_experts=256, top_k=10, expert_width=1024,
                 shared_width=1024, routed_scale=2.5, experts_held=None,
                 router_score="softmax", gate_act="sigmoid",
                 full_rope_base=5e5, full_rotary_dim=64, yarn_factor=128.0,
                 yarn_original=8192, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
                 attention_factor=1.4852030263919618, sliding_rope_base=1e4,
                 vocab=100352, rms_eps=1e-6, dtype="float32", **kw):
        super().__init__(num_classes, seed, input_shape, **kw)
        self.num_layers, self.first_k_dense = num_layers, first_k_dense
        self.period = period
        self.d_model, self.vocab = d_model, vocab
        self.num_classes = vocab
        self.rms_eps = rms_eps
        self.dtype = dtype
        kinds = {
            "full": dict(
                num_heads=full_heads, window=None, rope_base=full_rope_base,
                rotary_dim=full_rotary_dim, yarn_factor=yarn_factor,
                yarn_original=yarn_original, yarn_beta_fast=yarn_beta_fast,
                yarn_beta_slow=yarn_beta_slow,
                attention_factor=attention_factor),
            "sliding": dict(num_heads=sliding_heads, window=window,
                            rope_base=sliding_rope_base)}
        ffns = {
            "dense": dict(num_experts=0, ffn_width=dense_width),
            "experts": dict(
                num_experts=num_experts, top_k=top_k, ffn_width=expert_width,
                shared_width=shared_width, routed_scale=routed_scale,
                experts_held=(None if experts_held is None
                              else tuple(experts_held)),
                router_score=router_score)}
        self.blocks = {
            (kind, ffn): L.LagunaBlock(
                num_kv_heads=num_kv_heads, head_dim=head_dim,
                gate_act=gate_act, eps=rms_eps, **kinds[kind], **ffns[ffn])
            for kind in kinds for ffn in ffns}

    def layer_kind(self, i: int):
        """(attention kind, feed-forward kind) of layer ``i``."""
        return ("full" if i % self.period == 0 else "sliding",
                "dense" if i < self.first_k_dense else "experts")

    def build(self) -> Sequential:
        init = "normal_0.02"   # initializer_range
        b = (SequentialBuilder(NetConfig(
                seed=self.seed, dtype=self.dtype,
                updater={"type": "adamw", "learning_rate": 3e-4}))
             .input_shape(self.input_shape[0])
             .layer(L.EmbeddingSequence(n_in=self.vocab, n_out=self.d_model,
                                        weight_init=init)))
        for i in range(self.num_layers):
            b.layer(self.blocks[self.layer_kind(i)])
        b.layer(L.RMSNorm(eps=self.rms_eps))
        b.layer(L.RnnOutput(n_out=self.vocab, activation="softmax",
                            loss="mcxent", use_bias=False, weight_init=init))
        return b.build()


@register_model
class MiniCpmSalaLM(ZooModel):
    """MiniCPM-SALA (``openbmb/MiniCPM-SALA``, ``minicpm_sala``): decoder
    layers whose mixer is ``mixer_types[first_layer + i]``, block-sparse
    softmax attention over few KV heads (``minicpm4``) or decayed linear
    attention (``lightning-attn``), a dense SwiGLU behind both
    (``nn/layers/minicpm_sala.py``), the family's scaled residual stream
    (embeddings x ``scale_emb``, every sublayer x ``scale_depth /
    sqrt(published depth)``, the head fed the final norm over ``d_model /
    dim_model_base``) and an untied, bias-free head. The defaults are the
    published sizes; ``sparse`` holds the family's ``sparse_config``.
    ``num_layers`` layers run, the published layers ``first_layer ..``: a
    linear layer's decay follows its PUBLISHED index. ``dtype`` is the dtype
    the parameters and the per-token cache are HELD in, as for ``OlmoeLM``;
    a linear layer's state is ``state_dtype``."""

    input_shape = (8192,)
    MIXER_TYPES = tuple(
        "minicpm4" if i in (0, 9, 16, 17, 22, 29, 30, 31) else "lightning-attn"
        for i in range(32))
    SPARSE = dict(kernel_size=32, kernel_stride=16, block_size=64, topk=64,
                  init_blocks=1, window_size=2048, dense_len=8192)

    def __init__(self, num_classes=None, seed=12345, input_shape=None, *,
                 num_layers=32, first_layer=0, mixer_types=None,
                 published_layers=32, d_model=4096, num_heads=32,
                 num_kv_heads=2, head_dim=128, lightning_heads=32,
                 ffn_width=16384, scale_emb=12.0, scale_depth=1.4,
                 dim_model_base=256, rope_base=10000.0, sparse=None,
                 forced_in_topk=True, state_dtype="float32",
                 vocab=73448, rms_eps=1e-6, dtype="float32", **kw):
        super().__init__(num_classes, seed, input_shape, **kw)
        self.d_model, self.vocab = d_model, vocab
        self.num_classes = vocab
        self.rms_eps, self.dtype = rms_eps, dtype
        self.divide = d_model / dim_model_base
        mixers = tuple(mixer_types or self.MIXER_TYPES)
        if first_layer + num_layers > len(mixers):
            raise ValueError(f"layers {first_layer}..{first_layer + num_layers - 1} "
                             f"of {len(mixers)} mixer_types")
        common = dict(head_dim=head_dim, ffn_width=ffn_width, eps=rms_eps,
                      residual_scale=scale_depth / published_layers ** 0.5)
        self.blocks = []
        for i in range(num_layers):
            layer = first_layer + i
            own = dict(embed_scale=float(scale_emb) if i == 0 else 1.0)
            if mixers[layer] == "lightning-attn":
                own.update(num_heads=lightning_heads,
                           num_kv_heads=lightning_heads, rope_base=rope_base,
                           decay_layer=layer, decay_depth=published_layers,
                           state_dtype=state_dtype)
            else:
                own.update(num_heads=num_heads, num_kv_heads=num_kv_heads,
                           forced_in_topk=forced_in_topk,
                           **{**self.SPARSE, **(sparse or {})})
            self.blocks.append(L.MiniCpmSalaBlock(mixer=mixers[layer],
                                                  **common, **own))

    def build(self) -> Sequential:
        init = "normal_0.02"   # initializer_range
        b = (SequentialBuilder(NetConfig(
                seed=self.seed, dtype=self.dtype,
                updater={"type": "adamw", "learning_rate": 3e-4}))
             .input_shape(self.input_shape[0])
             .layer(L.EmbeddingSequence(n_in=self.vocab, n_out=self.d_model,
                                        weight_init=init)))
        for block in self.blocks:
            b.layer(block)
        b.layer(L.ScaledRMSNorm(eps=self.rms_eps, divide=self.divide))
        b.layer(L.RnnOutput(n_out=self.vocab, activation="softmax",
                            loss="mcxent", use_bias=False, weight_init=init))
        return b.build()


# ---------------------------------------------------------------------------
# Fully-sharded training step: dp x tp x sp over one mesh.
# ---------------------------------------------------------------------------

def sharded_lm_step(model: Sequential, mesh: Mesh, tx: optax.GradientTransformation):
    """Build a jit-compiled train step with:

    - params sharded per TRANSFORMER_RULES over the ``model`` axis (TP),
    - batch sharded over ``data`` (DP),
    - activations sequence-sharded over ``seq`` (SP) via sharding constraints —
      GSPMD decomposes the attention einsums into collective-permuted blocks.

    A thin functional wrapper over the one sharding API
    (``parallel.sharding``: place_params / batch_sharding /
    activation_sharding — the same machinery behind
    ``Trainer(mesh=, rules=)``). Returns (step_fn, placed_params,
    opt_state, placement helper).
    """
    assert model.params is not None, "init() the model first"
    from ..parallel.sharding import (activation_sharding, batch_sharding,
                                     place_params)

    params = place_params(model.params, mesh, TRANSFORMER_RULES)
    # eager init: moments inherit the params' shardings (a jitted init
    # would give constants fresh single-device layouts)
    opt_state = tx.init(params)

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens, targets, rng):
        def loss_fn(p):
            with activation_sharding(mesh):
                loss, _ = model.score(p, {}, tokens, targets, training=True,
                                      rng=rng)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    def place_batch(tokens, targets):
        return (jax.device_put(tokens, batch_sharding(mesh, tokens)),
                jax.device_put(targets, batch_sharding(mesh, targets)))

    return step, params, opt_state, place_batch
