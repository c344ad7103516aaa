"""Operations and bytes the algorithm needs, from shapes, and the table of
chip peaks.

Bytes are counted from what the computation must move at storage size,
not from what today's implementation moves: a SplitQuant weight element
is its code plus its cluster id, bits + ceil(log2 k) bits (4 for INT2,
k = 3), however it is packed; the int8 KV cache stores one byte per
element and an fp32 scale and zero per sub-channel chunk. A later change
that moves fewer bytes than today's code can only approach these counts,
never pass them.
"""
from __future__ import annotations

import math

#: Google Cloud documentation, "TPU v5e": per-chip peaks
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    """Peaks of a device kind; an unknown kind is an error, never a
    default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"add them to chipbench/harness/counts.py with their "
                       f"source") from None


ACT_BYTES = 2        # bfloat16 activations (q, k, v, attention output)


def layer_matrix_elements(c: dict) -> int:
    d, Hq, Hkv, D, ff = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                         c["head_dim"], c["d_ff"])
    return d * Hq * D + 2 * d * Hkv * D + Hq * D * d + 3 * d * ff


def weight_elements(c: dict) -> int:
    """Matmul weight elements of the model: every layer and the head."""
    return c["n_layers"] * layer_matrix_elements(c) \
        + c["d_model"] * c["vocab"]


def weight_bytes(c: dict) -> float:
    """Least bytes to read every quantized matrix once: codes and cluster
    ids, plus each matrix's per-cluster scale and zero (fp32)."""
    q = c["quant"]
    per_elt = (q["bits"] + math.ceil(math.log2(q["k"]))) / 8
    n_mats = 7 * c["n_layers"] + 1
    return weight_elements(c) * per_elt + n_mats * q["k"] * 2 * 4


def kv_bytes_per_token_layer(c: dict) -> int:
    """Stored bytes of one token's K and V in one layer."""
    Hkv, D = c["n_kv_heads"], c["head_dim"]
    if c["kv_mode"] == "int8":
        return 2 * (Hkv * D + Hkv * c["kv_qchunks"] * 2 * 4)
    return 2 * Hkv * D * ACT_BYTES


def kv_bytes_per_token(c: dict) -> int:
    return c["n_layers"] * kv_bytes_per_token_layer(c)


def attn_flops(c: dict, keys: int) -> float:
    """QK^T and PV of one query over `keys` keys, every layer."""
    return 4.0 * c["n_layers"] * c["n_heads"] * c["head_dim"] * keys


def token_flops(c: dict, keys: int) -> float:
    """Model FLOPs of one token that attends `keys` keys: 2 per matmul
    weight element plus attention."""
    return 2.0 * weight_elements(c) + attn_flops(c, keys)


# ----------------------------------------------------------- decode step --
def decode_step_bytes(c: dict, positions) -> float:
    """Least bytes of one decode step whose active slots sit at
    `positions`: the weights once, the embedding rows and norm vectors
    (a gain, and a bias under LayerNorm), each active slot's live KV rows
    (positions 0..p) read and its new row written."""
    n = len(positions)
    d, L = c["d_model"], c["n_layers"]
    per_norm = 2 if c["norm"] == "layer" else 1
    unquantized = n * d * 2 + (2 * L + 1) * per_norm * d * 2
    kv = kv_bytes_per_token(c)
    return weight_bytes(c) + unquantized \
        + sum(p + 1 for p in positions) * kv + n * kv


def decode_step_flops(c: dict, positions) -> float:
    return sum(token_flops(c, p + 1) for p in positions)


def decode_attn_cost(c: dict, positions) -> tuple[float, float]:
    """(FLOPs, bytes) of the decode-attention kernel over every layer of
    one step: live rows' codes and scales, q and the output."""
    L, Hq, D = c["n_layers"], c["n_heads"], c["head_dim"]
    keys = sum(p + 1 for p in positions)
    flops = attn_flops(c, keys)
    bytes_ = keys * kv_bytes_per_token(c) \
        + len(positions) * L * 2 * Hq * D * ACT_BYTES
    return flops, bytes_


# ---------------------------------------------------------- chunk prefill --
def _chunk_keys(pos_start: int, n: int) -> int:
    """Keys attended by a chunk's n queries at pos_start.. (causal)."""
    return n * pos_start + n * (n + 1) // 2


def chunk_flops(c: dict, pos_start: int, n: int) -> float:
    """n prompt tokens through every layer, and the head for the chunk's
    last token (the only logits row a chunk needs)."""
    d, V = c["d_model"], c["vocab"]
    return 2.0 * n * (weight_elements(c) - d * V) + 2.0 * d * V \
        + attn_flops(c, _chunk_keys(pos_start, n))


def chunk_bytes(c: dict, pos_start: int, n: int) -> float:
    """The weights once, n embedding rows, the slot's prefix rows read and
    the chunk's rows written."""
    d = c["d_model"]
    kv = kv_bytes_per_token(c)
    return weight_bytes(c) + n * d * 2 + pos_start * kv + n * kv


def prefill_attn_cost(c: dict, pos_start: int, n: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the prefill-attention kernel over every layer of
    one chunk: the prefix rows read, the chunk's q, k, v in and output
    out, its codes and scales written."""
    L, Hq, Hkv, D = (c["n_layers"], c["n_heads"], c["n_kv_heads"],
                     c["head_dim"])
    flops = attn_flops(c, _chunk_keys(pos_start, n))
    kv = kv_bytes_per_token(c)
    bytes_ = pos_start * kv + n * kv \
        + L * n * (2 * Hq + 2 * Hkv) * D * ACT_BYTES
    return flops, bytes_


def least_time(flops: float, bytes_: float, pk: dict) -> float:
    """Roofline bound: the larger of compute time and memory time."""
    return max(flops / pk["bf16_flops"], bytes_ / pk["hbm_bytes_per_s"])
