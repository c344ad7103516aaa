"""Operations and bytes the algorithm needs, from shapes, and the table of
chip peaks.

Bytes are counted from what the computation must move at storage size,
not from what today's implementation moves: a SplitQuant weight element
is its code plus its cluster id, bits + ceil(log2 k) bits (4 for INT2,
k = 3), however it is packed; the int8 KV cache stores one byte per
element and an fp32 scale and zero per sub-channel chunk. A later change
that moves fewer bytes than today's code can only approach these counts,
never pass them.

Every count that depends on the model's shape is its architecture's
(``architectures/<name>.py``, found by ``harness/arch.py``): each
function below hands the configuration to that module, so the metric
readers call one name for every architecture.
"""
from __future__ import annotations

from . import arch

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


def _chunk_keys(pos_start: int, n: int) -> int:
    """Keys attended by a chunk's n queries at pos_start.. (causal)."""
    return n * pos_start + n * (n + 1) // 2


# ------------------------------------------- by the configuration's module --
def weight_elements(c: dict) -> int:
    """Matmul weight elements of the model."""
    return arch.of(c).weight_elements(c)


def weight_bytes(c: dict) -> float:
    """Least bytes to read every quantized matrix once."""
    return arch.of(c).weight_bytes(c)


def kv_bytes_per_token(c: dict) -> int:
    """Stored cache bytes of one token, every layer."""
    return arch.of(c).kv_bytes_per_token(c)


def attn_flops(c: dict, keys: int) -> float:
    """Attention FLOPs of one query over `keys` keys, every layer."""
    return arch.of(c).attn_flops(c, keys)


def token_flops(c: dict, keys: int) -> float:
    """Model FLOPs of one token that attends `keys` keys."""
    return arch.of(c).token_flops(c, keys)


def decode_step_bytes(c: dict, positions) -> float:
    """Least bytes of one decode step whose active slots sit at
    `positions`."""
    return arch.of(c).decode_step_bytes(c, positions)


def decode_step_flops(c: dict, positions) -> float:
    return arch.of(c).decode_step_flops(c, positions)


def decode_attn_cost(c: dict, positions) -> tuple[float, float]:
    """(FLOPs, bytes) of the decode-attention kernel over one step."""
    return arch.of(c).decode_attn_cost(c, positions)


def chunk_flops(c: dict, pos_start: int, n: int) -> float:
    """One chunk of n prompt tokens at pos_start."""
    return arch.of(c).chunk_flops(c, pos_start, n)


def chunk_bytes(c: dict, pos_start: int, n: int) -> float:
    return arch.of(c).chunk_bytes(c, pos_start, n)


def prefill_attn_cost(c: dict, pos_start: int, n: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the prefill-attention kernel over one chunk."""
    return arch.of(c).prefill_attn_cost(c, pos_start, n)


def least_time(flops: float, bytes_: float, pk: dict) -> float:
    """Roofline bound: the larger of compute time and memory time."""
    return max(flops / pk["bf16_flops"], bytes_ / pk["hbm_bytes_per_s"])
