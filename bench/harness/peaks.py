"""Chip peaks, keyed by ``device_kind``, and the operation and byte
counts of the kernels and steps the benchmark reports against them.

Peaks of one TPU v5e chip: Google Cloud documentation, "TPU v5e"
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM at 819 GB/s.  JAX names the chip ``TPU v5 lite``.  A kind
that is not in the table is an error, never a default.

Counts are what the algorithm needs, worked out from shapes: the least
the chip must move or compute, so a share of the roofline stays at or
under 100% when the time is measured honestly.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Sequence, Tuple


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    hbm_bytes_s: float       # bytes/s
    hbm_bytes: float         # bytes
    source: str


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_s=819e9,
                         hbm_bytes=16e9,
                         source="Google Cloud documentation, 'TPU v5e'"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add "
                       f"them to bench/harness/peaks.py with a source") \
            from None


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: Peaks) -> Tuple[float, str]:
    """Least time the chip could take (the larger of the compute and
    the memory bound) over the measured time, in %, and which bound."""
    t_c = flops / peaks.bf16_flops
    t_m = nbytes / peaks.hbm_bytes_s
    return 100.0 * max(t_c, t_m) / seconds, ("compute" if t_c > t_m
                                             else "memory")


# ---------------------------------------------------------------------- #
# feasibility kernel (kernels/feasibility._feasible_pallas)
# ---------------------------------------------------------------------- #
def feasibility_bytes(arg_shapes: Sequence[Tuple[int, ...]],
                      itemsize: int = 4) -> int:
    """Bytes one call must read and write: every (padded) int32 input
    once, and the [Np, Vp] int32 mask it writes."""
    def size(shape):
        n = 1
        for d in shape:
            n *= d
        return n
    n_in = sum(size(s) for s in arg_shapes)
    n_p = arg_shapes[0][0]               # tid: [Np, 1]
    v_p = arg_shapes[5][1]               # vtype: [1, Vp]
    return itemsize * (n_in + n_p * v_p)


# ---------------------------------------------------------------------- #
# dense decoder (models/transformer, the phi4-mini path)
# ---------------------------------------------------------------------- #
def dense_lm_matmul_params(c: dict) -> int:
    """Parameters that a token multiplies through: attention and MLP
    projections of every layer and the LM head (the embedding is a
    lookup and costs no FLOPs)."""
    e, L = c["hidden_size"], c["num_hidden_layers"]
    hd = c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    attn = e * h * hd * 2 + e * kv * hd * 2
    mlp = 3 * e * c["intermediate_size"]
    return L * (attn + mlp) + e * c["vocab_size"]


def dense_lm_weight_bytes(c: dict, itemsize: int = 2) -> int:
    """Bytes of every weight a decode step reads once: the projections,
    the LM head, the norms, and one embedding row per token (negligible,
    left out)."""
    e, L = c["hidden_size"], c["num_hidden_layers"]
    norms = L * 2 * e + e
    return itemsize * (dense_lm_matmul_params(c) + norms)


def kv_bytes_per_position(c: dict, batch: int, itemsize: int = 2) -> int:
    return (itemsize * 2 * c["num_hidden_layers"] * batch
            * c["num_key_value_heads"] * c["head_dim"])


def dense_lm_token_flops(c: dict, context: float) -> float:
    """Forward FLOPs of one token that attends over ``context``
    positions: 2 per multiply-add through the weights, plus QK^T and
    PV over the context (causal: the positions it can see)."""
    attn = 4.0 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * c["head_dim"] * context
    return 2.0 * dense_lm_matmul_params(c) + attn


def decode_step_cost(c: dict, batch: int,
                     position: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step writing ``position`` for every
    row: all weights once, the keys and values of positions
    0..position once, the new key and value written."""
    ctx = position + 1
    flops = batch * dense_lm_token_flops(c, ctx)
    nbytes = dense_lm_weight_bytes(c) \
        + kv_bytes_per_position(c, batch) * (ctx + 1)
    return flops, nbytes


def prefill_flops(c: dict, batch: int, prompt: int) -> float:
    """Forward FLOPs of a causal prefill: token i sees i+1 positions;
    only the last position goes through the LM head."""
    e, V = c["hidden_size"], c["vocab_size"]
    body = 2.0 * (dense_lm_matmul_params(c) - e * V)
    attn = 4.0 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * c["head_dim"] * (prompt * (prompt + 1) / 2)
    return batch * (prompt * body + attn + 2.0 * e * V)
