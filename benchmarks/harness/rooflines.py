"""The operations and bytes a kernel's work needs, computed from shapes, and
the least time a chip could take for them: the larger of operations over peak
FLOP/s and bytes over peak bytes/s. A roofline share is that least time over
the measured device time; it cannot pass 100%."""

from __future__ import annotations


def least_time_s(flops: float, nbytes: float, peaks: dict) -> dict:
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes), "bound": "flops" if by_flops > by_bytes else "bytes"}


def weight_count(m: dict) -> dict:
    """Parameters a decode step reads: every layer's six matrices and the
    output head (the embedding is a gather of ``batch`` rows)."""
    d, f, v, n = m["d_model"], m["d_ff"], m["vocab_size"], m["n_layers"]
    per_layer = 4 * d * d + 2 * d * f
    return {"per_layer": per_layer, "head": d * v, "total": n * per_layer + d * v}


def paged_decode_step(m: dict, batch: int, live_rows: float, itemsize: int = 2) -> dict:
    """One decode step of ``batch`` sequences that hold ``live_rows`` cached
    positions in all (their sum). Bytes: the weights once, the live K and V
    rows read once, the batch's new rows written. FLOPs: two per weight per
    sequence, and four per cached position per model dim (scores and the
    weighted sum)."""
    w = weight_count(m)
    d, n = m["d_model"], m["n_layers"]
    kv_row = 2 * d * itemsize * n  # one position's K and V over all layers
    nbytes = w["total"] * itemsize + live_rows * kv_row + batch * kv_row
    flops = 2.0 * w["total"] * batch + 4.0 * d * n * live_rows
    return {"flops": flops, "bytes": nbytes}


def flash_attention_call(batch: int, heads: int, seq: int, head_dim: int, causal: bool = True,
                         itemsize: int = 2) -> dict:
    """Forward and backward of one attention call over (batch, heads, seq,
    head_dim). Forward: QK^T and PV, 4 x seq^2 x head_dim FLOPs a head,
    halved by the causal mask. Backward: dQ, dK, dV and the recomputed scores
    and dP, 2.5 x the forward. Bytes: q, k, v read and o written forward;
    q, k, v, o, do read and dq, dk, dv written backward."""
    fwd = 4.0 * batch * heads * seq * seq * head_dim * (0.5 if causal else 1.0)
    tensor = batch * heads * seq * head_dim * itemsize
    return {
        "fwd": {"flops": fwd, "bytes": 4 * tensor},
        "bwd": {"flops": 2.5 * fwd, "bytes": 8 * tensor},
    }
