"""Peaks of the chips the benchmark runs on, and the operations and bytes
that the served work needs, counted from the benchmark's own token record.

A device kind missing from ``PEAKS`` is an error: a share of an unknown
peak is not a number.
"""
from __future__ import annotations

#: ``Device.device_kind`` -> published peaks of one chip.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


class UnknownDevice(KeyError):
    pass


def peaks_for(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise UnknownDevice(f"no peaks for device kind {kind!r}; known: "
                            f"{sorted(PEAKS)}") from None


def matmul_params_per_layer(m: dict) -> int:
    d, hd = m["d_model"], m["head_dim"]
    hq, hkv = m["num_heads"], m["num_kv_heads"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * m["d_ff"]


def forward_flops(m: dict, prefill_spans, decode_ctx, logit_rows: int) -> float:
    """Forward FLOPs that the algorithm needs.

    ``prefill_spans``: (start, end) prompt positions computed, end excluded
    (a position p attends over p + 1 keys). ``decode_ctx``: for each decoded
    token, the keys its step attends over. ``logit_rows``: tokens that
    produced a logit row (one per token received)."""
    L, hq, hd = m["num_layers"], m["num_heads"], m["head_dim"]
    per_tok = 2 * L * matmul_params_per_layer(m)
    attn = 4 * L * hq * hd
    n = 0
    keys = 0
    for start, end in prefill_spans:
        n += end - start
        keys += (end * (end + 1) - start * (start + 1)) // 2
    n += len(decode_ctx)
    keys += sum(decode_ctx)
    return (float(per_tok) * n + float(attn) * keys
            + 2.0 * m["d_model"] * m["vocab_size"] * logit_rows)


def decode_attention_bytes(m: dict, decode_ctx) -> float:
    """HBM bytes the paged decode attention needs: each decoded token reads
    its whole KV context once per layer, and its query and output."""
    L, hq, hkv, hd = (m["num_layers"], m["num_heads"], m["num_kv_heads"],
                      m["head_dim"])
    item = ITEMSIZE[m["dtype"]]
    kv = 2 * hkv * hd * item * L
    qo = 2 * hq * hd * item * L
    return float(kv) * sum(decode_ctx) + float(qo) * len(decode_ctx)
