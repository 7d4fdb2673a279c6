"""Operation and byte counts of DeepSeek-V2-Lite on one chip of its
expert-parallel deployment (the chip's share: its held experts, all of
attention, the dense layer, the shared experts, routers and vocabulary),
from the configuration's shapes alone.  They count what the algorithm
must do, never what one kernel happens to do."""
from __future__ import annotations


def _dims(cfg: dict) -> dict:
    e0, e1 = cfg["experts_held"]
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        r=cfg["kv_lora_rank"], vd=cfg["v_head_dim"],
        f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        fs=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        e=cfg["n_routed_experts_published"], held=e1 - e0,
        k=cfg["num_experts_per_tok"], v=cfg["vocab_size"],
        dense=cfg["first_k_dense_replace"],
        routed=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
        layers=cfg["num_hidden_layers"])


def _attn_weights(n: dict) -> int:
    d, h, r = n["d"], n["h"], n["r"]
    return d * h * (n["nope"] + n["rope"]) + d * (r + n["rope"]) + r \
        + r * h * (n["nope"] + n["vd"]) + h * n["vd"] * d + 2 * d


def flops_per_token(cfg: dict, context: float, *, prefill: bool) -> float:
    """Model FLOPs of one token at `context` cached positions (2 per
    multiply-add): attention decompressed in prefill and absorbed in
    decode, the least work for each (decode reads only the latent); the
    dense layer; each routed layer's router, the expected held slots
    (top-k × held / experts) and the shared experts; the output head."""
    n = _dims(cfg)
    d, h, r, nope, rope, vd = (n[k] for k in ("d", "h", "r", "nope",
                                               "rope", "vd"))
    proj = d * h * (nope + rope) + d * (r + rope) + h * vd * d
    if prefill:     # k_nope and v from the latent, scores and values
        attn = proj + r * h * (nope + vd) + h * (nope + rope + vd) * context
    else:           # q_nope into the latent, latent scores and values
        attn = proj + h * nope * r + h * (2 * r + rope) * context \
            + h * r * vd
    held_slots = n["k"] * n["held"] / n["e"]
    moe = d * n["e"] + held_slots * 3 * d * n["fe"] + 3 * d * n["fs"]
    return 2.0 * (n["layers"] * attn + n["dense"] * 3 * d * n["f"]
                  + n["routed"] * moe + d * n["v"])


def expert_bytes(cfg: dict) -> int:
    n = _dims(cfg)
    return 3 * n["d"] * n["fe"] * 2


def decode_bytes(cfg: dict, *, steps: int, batch: int, context_sum: int,
                 experts_hit: int) -> int:
    """Least bytes that `steps` decode steps of `batch` rows must read:
    every held weight in bf16 except the expert weights and the embedding
    rows that no token of the batch reads, each step; each held expert's
    weights once for each (layer, step) that routes a slot to it
    (`experts_hit`); and each row's latent cache up to its step's context
    (`context_sum`, summed over the steps)."""
    n = _dims(cfg)
    d = n["d"]
    fixed = n["layers"] * _attn_weights(n) + n["dense"] * 3 * d * n["f"] \
        + n["routed"] * (d * n["e"] + 3 * d * n["fs"]) + d * n["v"] + d \
        + batch * d
    cache = n["layers"] * batch * (n["r"] + n["rope"])
    return 2 * (steps * fixed + cache * context_sum) \
        + experts_hit * expert_bytes(cfg)
