"""LoopedLM: a looped ("universal-transformer") decoder language model.

The Ouro family (ByteDance, "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741; https://huggingface.co/ByteDance/Ouro-2.6B):
``num_hidden_layers`` sandwich-normalised decoder layers (RMSNorm, rotary
positions, SwiGLU, no biases) applied ``total_ut_steps`` times with the
same weights, an output head and an exit gate read after every pass.

    model = LoopedLM.from_config(json.load(open("config.json")))
    model.compile(optimizer=Adam(lr=3e-4, beta_2=0.95),
                  loss="expected_exit_crossentropy")
    model.fit(ids, next_ids, batch_size=2)        # (rows, L) int32 each

In training the net hands the loss ``ExitHeads`` (every pass's hidden
states and gate logits, and the head's kernel) and the loss forms the
``total_ut_steps x tokens x vocab`` logits a chunk of tokens at a time
(``nn.objectives.expected_exit_crossentropy``, which weighs the exit
distribution's entropy by the model's ``exit_entropy_beta``); ``predict``
returns the last pass's logits (``early_exit_threshold`` 1: every pass
always runs).
Three ``jax.named_scope``s mark the step for a device trace:
``zoo:lm/embed``, ``zoo:lm/stack``, ``zoo:lm/head_loss``.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.models.common import ZooModel, register_model
from analytics_zoo_tpu.nn import initializers
from analytics_zoo_tpu.nn.layers.attention import LoopedDecoderStack
from analytics_zoo_tpu.nn.objectives import ExitHeads
from analytics_zoo_tpu.nn.topology import KerasNet

# the keys of the family's config.json that the constructor takes, by name
_CONFIG_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
                "num_attention_heads", "intermediate_size", "total_ut_steps",
                "rope_theta", "rms_norm_eps", "hidden_act",
                "exit_entropy_beta")


class LoopedLMNet(KerasNet):
    """Token ids (B, L) -> ``ExitHeads`` in training, the last pass's
    logits (B, L, V) otherwise."""

    def __init__(self, vocab_size: int, hidden_size: int, stack,
                 exit_entropy_beta: float, **kw):
        super().__init__(**kw)
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.stack, self.exit_entropy_beta = stack, exit_entropy_beta
        self.initializer = initializers.get("glorot_uniform")

    @property
    def layers(self):
        return [self.stack]

    def build(self, rng, ids_shape, *rest):
        ke, ks, kh, kg = jax.random.split(rng, 4)
        d, v = self.hidden_size, self.vocab_size
        stack_params, stack_state = self.stack.init(
            ks, tuple(ids_shape) + (d,))
        params = {
            "embed": self.initializer(ke, (v, d), jnp.float32),
            self.stack.name: stack_params,
            "head": {"kernel": self.initializer(kh, (d, v), jnp.float32)},
            "exit_gate": {
                "kernel": self.initializer(kg, (d, 1), jnp.float32),
                "bias": jnp.zeros((1,), jnp.float32)},
        }
        return params, {self.stack.name: stack_state}

    def call(self, params, state, ids, *, training=False, rng=None):
        ids = ids.astype(jnp.int32)  # container abstract-eval passes f32
        with jax.named_scope("zoo:lm/embed"):
            x = params["embed"][ids]
        with jax.named_scope("zoo:lm/stack"):
            hs = self.stack.forward(params[self.stack.name], x,
                                    training=training)
        kernel = params["head"]["kernel"]
        if not training:
            return jnp.dot(hs[-1], kernel), state
        with jax.named_scope("zoo:lm/head_loss"):
            gate = params["exit_gate"]
            gates = (jnp.einsum("tbld,d->tbl", hs, gate["kernel"][:, 0])
                     + gate["bias"][0])
        return ExitHeads(hs, gates, kernel, self.exit_entropy_beta), state


@register_model
class LoopedLM(ZooModel):
    """A looped decoder, built from the keys of the family's
    ``config.json`` (``from_config`` takes the whole dictionary)."""

    def __init__(self, vocab_size: int, hidden_size: int,
                 num_hidden_layers: int, num_attention_heads: int,
                 intermediate_size: int, total_ut_steps: int = 4,
                 rope_theta: float = 10000.0, rms_norm_eps: float = 1e-6,
                 hidden_act: str = "silu", exit_entropy_beta: float = 0.1):
        super().__init__()
        self._config = dict(
            vocab_size=vocab_size, hidden_size=hidden_size,
            num_hidden_layers=num_hidden_layers,
            num_attention_heads=num_attention_heads,
            intermediate_size=intermediate_size,
            total_ut_steps=total_ut_steps, rope_theta=rope_theta,
            rms_norm_eps=rms_norm_eps, hidden_act=hidden_act,
            exit_entropy_beta=exit_entropy_beta)
        stack = LoopedDecoderStack(
            num_hidden_layers, num_attention_heads, hidden_size,
            intermediate_size, passes=total_ut_steps,
            rotary_theta=rope_theta, epsilon=rms_norm_eps,
            activation=hidden_act, name="looped_lm_stack")
        self.model = LoopedLMNet(vocab_size, hidden_size, stack,
                                 exit_entropy_beta, name="looped_lm")

    @classmethod
    def from_config(cls, config: Dict[str, Any]) -> "LoopedLM":
        heads = config.get("num_attention_heads")
        if config.get("num_key_value_heads", heads) != heads:
            raise ValueError(
                "grouped-query attention is not supported here "
                "(models.HybridLM takes grouped heads): "
                f"num_key_value_heads {config['num_key_value_heads']} != "
                f"num_attention_heads {heads}")
        if config.get("head_dim", config["hidden_size"] // heads) \
                != config["hidden_size"] // heads:
            raise ValueError("head_dim must be hidden_size / "
                             "num_attention_heads")
        return cls(**{k: config[k] for k in _CONFIG_KEYS if k in config})

    def config(self):
        return dict(self._config)
