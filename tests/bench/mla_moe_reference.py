"""Fixture reference module for an MLA + MoE decoder (the DeepSeek-V2 layer).

It declares what the serving harness needs to build a configuration's
served parameters (``dims`` and ``leaves``; ``bench/reference/__init__.py``)
and nothing more: no ``Weights`` or ``served_gaps``, so it serves the tests
that a non-dense tree passes through ``serve.served_params`` and that a
configuration of another architecture is found as files.  Keys are those of
DeepSeek-V2's published ``config.json``; every layer is MLA + MoE with a
``q_lora`` projection, as the program's ``mla_moe`` block builds it.
"""
from __future__ import annotations


def dims(conf: dict) -> dict:
    return {
        "n_layers": conf["num_hidden_layers"],
        "d_model": conf["hidden_size"],
        "n_heads": conf["num_attention_heads"],
        "vocab_size": conf["vocab_size"],
        "kv_lora_rank": conf["kv_lora_rank"],
        "q_lora_rank": conf["q_lora_rank"],
        "qk_nope_head_dim": conf["qk_nope_head_dim"],
        "qk_rope_head_dim": conf["qk_rope_head_dim"],
        "v_head_dim": conf["v_head_dim"],
        "n_experts": conf["n_routed_experts"],
        "n_shared": conf["n_shared_experts"],
        "d_expert": conf["moe_intermediate_size"],
    }


def leaves(dims: dict) -> list[tuple[str, tuple[int, ...], bool]]:
    """Every leaf of one segment of layer-stacked MLA + MoE blocks; the
    routed experts' stacks are (layers, experts, K, N)."""
    L, d, v, h = dims["n_layers"], dims["d_model"], dims["vocab_size"], dims["n_heads"]
    lr, qr = dims["kv_lora_rank"], dims["q_lora_rank"]
    nope, rope, vd = dims["qk_nope_head_dim"], dims["qk_rope_head_dim"], dims["v_head_dim"]
    e, de, sh = dims["n_experts"], dims["d_expert"], dims["n_shared"] * dims["d_expert"]
    seg = "segments/0/"
    return [
        ("embed/table", (v, d), False),
        ("final_norm/g", (d,), False),
        ("head/w", (d, v), False),
        (seg + "ln1/g", (L, d), True),
        (seg + "ln2/g", (L, d), True),
        (seg + "mla/kv_norm/g", (L, lr), True),
        (seg + "mla/q_norm/g", (L, qr), True),
        (seg + "mla/wk_b", (L, lr, h * nope), True),
        (seg + "mla/wkv_a", (L, d, lr + rope), True),
        (seg + "mla/wo", (L, h * vd, d), True),
        (seg + "mla/wq_a", (L, d, qr), True),
        (seg + "mla/wq_b", (L, qr, h * (nope + rope)), True),
        (seg + "mla/wv_b", (L, lr, h * vd), True),
        (seg + "moe/router", (L, d, e), True),
        (seg + "moe/shared/wi_gate", (L, d, sh), True),
        (seg + "moe/shared/wi_up", (L, d, sh), True),
        (seg + "moe/shared/wo", (L, sh, d), True),
        (seg + "moe/wi_gate", (L, e, d, de), True),
        (seg + "moe/wi_up", (L, e, d, de), True),
        (seg + "moe/wo", (L, e, de, d), True),
    ]
