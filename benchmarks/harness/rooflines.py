"""The least time a chip could take for a kernel's work: the larger of its
operations over peak FLOP/s and its bytes over peak bytes/s. A roofline share
is that least time over the measured device time; it cannot pass 100%. The
operations and bytes come from shapes: those of a call that knows no model
are counted here, those of a model's decode step by its family
(``benchmarks/families/<name>.py``)."""

from __future__ import annotations


def least_time_s(flops: float, nbytes: float, peaks: dict) -> dict:
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes), "bound": "flops" if by_flops > by_bytes else "bytes"}


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
