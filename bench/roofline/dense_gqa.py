"""Model operations of a dense GQA decoder: what a served token needs.

Counted as a plain forward pass does them, 2 operations per multiply-add:
every matmul of every layer, the head where logits are needed, and
attention's two products (scores and values) over the keys a token
attends to.  The crossbar
unpack, padding, and recomputation are not work.
"""
from __future__ import annotations


def layer_ops_per_token(model: dict) -> float:
    """The matmuls of every layer, for one token."""
    d, f, hd = model["d_model"], model["d_ff"], model["head_dim"]
    q_out, kv_out = model["n_heads"] * hd, model["n_kv_heads"] * hd
    return 2.0 * model["n_layers"] * (d * q_out + 2 * d * kv_out + q_out * d + 3 * d * f)


def head_ops(model: dict) -> float:
    """The output head, for one token whose logits are needed."""
    return 2.0 * model["d_model"] * model["vocab_size"]


def attention_ops(model: dict, keys: float) -> float:
    """Scores and weighted values of one query over ``keys`` keys, all layers."""
    return 4.0 * model["n_layers"] * model["n_heads"] * model["head_dim"] * keys


def prefill_ops(model: dict, prompt: int) -> float:
    """A causal pass over ``prompt`` tokens (query i attends to i keys), with
    the head at the last position only: it gives the first output token."""
    return (prompt * layer_ops_per_token(model) + head_ops(model)
            + attention_ops(model, prompt * (prompt + 1) / 2))


def decode_ops(model: dict, context: int) -> float:
    """One decoded token whose query attends to ``context`` keys."""
    return layer_ops_per_token(model) + head_ops(model) + attention_ops(model, context)
