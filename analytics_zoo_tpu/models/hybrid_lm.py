"""HybridLM: a decoder language model whose layers are of two kinds,
Mamba-2 state-space layers and grouped-query attention layers, in the order
its configuration states.

The Granite 4.0-H family (IBM; ``model_type`` ``granitemoehybrid``,
https://huggingface.co/ibm-granite/granite-4.0-h-micro): pre-norm layers
``a = x + r * Mixer(N1 x)``, ``y = a + r * MLP(N2 a)`` with RMSNorm, a
SwiGLU MLP shared by both kinds, no biases and no positional encoding; the
embedding scaled by ``embedding_multiplier``, the attention scores by
``attention_multiplier``, the residual branches by ``residual_multiplier``,
and a head tied to the embedding whose logits are divided by
``logits_scaling``.

    model = HybridLM.from_config(json.load(open("config.json")))
    model.compile(optimizer=Adam(lr=3e-4, beta_2=0.95),
                  loss="chunked_token_crossentropy")
    model.fit(ids, next_ids, batch_size=2)        # (rows, L) int32 each

In training the net hands the loss a ``TiedHead`` (the final hidden states,
the embedding, the logits' factor) and the loss forms the tokens x vocab
logits a chunk of tokens at a time
(``nn.objectives.chunked_token_crossentropy``); ``predict`` returns the
logits.  ``jax.named_scope``s mark the step for a device trace:
``zoo:lm/embed``, ``zoo:lm/stack``, ``zoo:lm/head_loss`` as ``LoopedLM``'s,
and inside the stack ``zoo:ssm/mixer``, ``zoo:ssm/scan`` and ``zoo:lm/attn``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.models.common import ZooModel, register_model
from analytics_zoo_tpu.nn import initializers
from analytics_zoo_tpu.nn.layers.attention import (
    HybridDecoderStack, MultiHeadAttention, PreNormDecoderBlock)
from analytics_zoo_tpu.nn.layers.ssm import Mamba2Mixer
from analytics_zoo_tpu.nn.objectives import TiedHead
from analytics_zoo_tpu.nn.topology import KerasNet

# the keys of the family's config.json that the constructor takes, by name
_CONFIG_KEYS = ("vocab_size", "hidden_size", "layer_types",
                "num_attention_heads", "num_key_value_heads",
                "shared_intermediate_size", "mamba_n_heads", "mamba_d_head",
                "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
                "mamba_chunk_size", "mamba_conv_bias", "embedding_multiplier",
                "attention_multiplier", "residual_multiplier",
                "logits_scaling", "rms_norm_eps", "hidden_act")


class HybridLMNet(KerasNet):
    """Token ids (B, L) -> ``TiedHead`` in training, logits (B, L, V)
    otherwise."""

    def __init__(self, vocab_size: int, hidden_size: int, stack,
                 embedding_multiplier: float, logits_scaling: float, **kw):
        super().__init__(**kw)
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.stack = stack
        self.embedding_multiplier = embedding_multiplier
        self.logits_scaling = logits_scaling
        self.initializer = initializers.get("glorot_uniform")

    @property
    def layers(self):
        return [self.stack]

    def build(self, rng, ids_shape, *rest):
        ke, ks = jax.random.split(rng)
        d = self.hidden_size
        stack_params, stack_state = self.stack.init(
            ks, tuple(ids_shape) + (d,))
        params = {"embed": self.initializer(ke, (self.vocab_size, d),
                                            jnp.float32),
                  self.stack.name: stack_params}
        return params, {self.stack.name: stack_state}

    def call(self, params, state, ids, *, training=False, rng=None):
        ids = ids.astype(jnp.int32)  # container abstract-eval passes f32
        embed = params["embed"]
        with jax.named_scope("zoo:lm/embed"):
            x = embed[ids] * jnp.asarray(self.embedding_multiplier,
                                         embed.dtype)
        with jax.named_scope("zoo:lm/stack"):
            h = self.stack.forward(params[self.stack.name], x,
                                   training=training)
        if training:
            return TiedHead(h, embed, 1.0 / self.logits_scaling), state
        return jnp.dot(h, embed.T) / self.logits_scaling, state


@register_model
class HybridLM(ZooModel):
    """A decoder of Mamba-2 and attention layers, built from the keys of the
    family's ``config.json`` (``from_config`` takes the whole dictionary and
    refuses what the model cannot run)."""

    def __init__(self, vocab_size: int, hidden_size: int,
                 layer_types: Sequence[str], num_attention_heads: int,
                 num_key_value_heads: int, shared_intermediate_size: int,
                 mamba_n_heads: int, mamba_d_head: int, mamba_d_state: int,
                 mamba_n_groups: int = 1, mamba_d_conv: int = 4,
                 mamba_chunk_size: int = 256, mamba_conv_bias: bool = True,
                 embedding_multiplier: float = 1.0,
                 attention_multiplier: Optional[float] = None,
                 residual_multiplier: float = 1.0,
                 logits_scaling: float = 1.0, rms_norm_eps: float = 1e-5,
                 hidden_act: str = "silu"):
        super().__init__()
        self._config = dict(
            vocab_size=vocab_size, hidden_size=hidden_size,
            layer_types=list(layer_types),
            num_attention_heads=num_attention_heads,
            num_key_value_heads=num_key_value_heads,
            shared_intermediate_size=shared_intermediate_size,
            mamba_n_heads=mamba_n_heads, mamba_d_head=mamba_d_head,
            mamba_d_state=mamba_d_state, mamba_n_groups=mamba_n_groups,
            mamba_d_conv=mamba_d_conv, mamba_chunk_size=mamba_chunk_size,
            mamba_conv_bias=mamba_conv_bias,
            embedding_multiplier=embedding_multiplier,
            attention_multiplier=attention_multiplier,
            residual_multiplier=residual_multiplier,
            logits_scaling=logits_scaling, rms_norm_eps=rms_norm_eps,
            hidden_act=hidden_act)

        def block(kind, mixer, scope=None):
            return PreNormDecoderBlock(
                mixer, hidden_size, shared_intermediate_size,
                residual_multiplier, rms_norm_eps, hidden_act, scope=scope,
                name=f"hybrid_lm_{kind}_block")

        blocks = {
            "mamba": block("mamba", Mamba2Mixer(
                hidden_size, mamba_n_heads, mamba_d_head, mamba_d_state,
                mamba_n_groups, mamba_d_conv, mamba_chunk_size,
                mamba_conv_bias, rms_norm_eps, name="hybrid_lm_mamba")),
            "attention": block("attention", MultiHeadAttention(
                num_attention_heads, hidden_size, causal=True,
                use_bias=False, n_kv_head=num_key_value_heads,
                sm_scale=attention_multiplier, name="hybrid_lm_attn"),
                scope="zoo:lm/attn"),
        }
        stack = HybridDecoderStack(layer_types, blocks, hidden_size,
                                   rms_norm_eps, name="hybrid_lm_stack")
        self.model = HybridLMNet(vocab_size, hidden_size, stack,
                                 embedding_multiplier, logits_scaling,
                                 name="hybrid_lm")

    @classmethod
    def from_config(cls, config: Dict[str, Any]) -> "HybridLM":
        def refuse(what):
            raise ValueError(f"HybridLM cannot run this configuration: "
                             f"{what}")

        if config.get("num_local_experts", 0) > 0:
            refuse(f"num_local_experts {config['num_local_experts']}: "
                   "routed experts are not supported, only the shared MLP")
        if config.get("position_embedding_type", "nope") != "nope":
            refuse("position_embedding_type "
                   f"{config['position_embedding_type']!r}: the attention "
                   "layers take no positions ('nope')")
        if config.get("rope_scaling") is not None:
            refuse("rope_scaling is set")
        for key in ("attention_bias", "mamba_proj_bias"):
            if config.get(key, False):
                refuse(f"{key}: the projections have no biases")
        if not config.get("tie_word_embeddings", True):
            refuse("tie_word_embeddings false: the head is the embedding")
        if len(config["layer_types"]) != config.get(
                "num_hidden_layers", len(config["layer_types"])):
            refuse(f"{len(config['layer_types'])} layer_types for "
                   f"num_hidden_layers {config['num_hidden_layers']}")
        inner = config["mamba_n_heads"] * config["mamba_d_head"]
        if config.get("mamba_expand", inner / config["hidden_size"]) \
                * config["hidden_size"] != inner:
            refuse("mamba_expand * hidden_size is not mamba_n_heads * "
                   "mamba_d_head")
        return cls(**{k: config[k] for k in _CONFIG_KEYS if k in config})

    def config(self):
        return dict(self._config)
