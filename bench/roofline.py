"""Operation and byte counts of the calls the benchmark times, from their
shapes alone.  These set the floor that a roofline share or an MFU divides
by, so they count only what the algorithm must do, never what one kernel
happens to do."""
from __future__ import annotations

WORD_BYTES = 4


def egress_bytes(rows: int, words: int, shard_entries) -> int:
    """Least bytes one fabric egress call must move: the data and tagged
    address words in, the released and fault words out (4 words of 4 bytes
    per lane), and each row's resident shard read once (start, end and the
    row's permission field, 3 words per entry).  The count is the same
    whatever search the kernel uses; a search needs about log2(N) compares
    per word, far below the chip's compute peak, so the HBM bound is the
    binding one."""
    lanes = rows * words * 4 * WORD_BYTES
    shards = sum(int(n) for n in shard_entries) * 3 * WORD_BYTES
    return lanes + shards


def decoder_flops_per_token(cfg: dict, context: float) -> float:
    """Model FLOPs of one decoded token of a dense decoder with tied or
    untied head (2 per multiply-add): the projections, the SwiGLU MLP, the
    attention scores and values over `context` cached positions, and the
    output head over the published vocabulary."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    f = cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    proj = d * hd * (2 * h + 2 * kv)            # q, o and k, v
    mlp = 3 * d * f
    attn = 2 * h * hd * context                 # q.k and p.v
    head = d * cfg["vocab_size"]
    return 2.0 * (layers * (proj + mlp + attn) + head)
