"""Published peaks of one chip, keyed by jax's ``device_kind``, and the
operations and bytes an algorithm needs, computed from shapes.

A device that is not in the table is an error, never a default.
Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s); the table is copied from
``bench.py`` ``TPU_PEAKS`` (PR 21).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peak for device_kind {device_kind!r}; known: {sorted(PEAKS)}. "
            "Add the chip to benchmarks/harness/peaks.py with its source.") from None


def gpt2_prefill_flops(model: dict, prompt_tokens: float, prompts: float = 1.0) -> float:
    """FLOPs the forward pass of ``prompts`` prompts totalling
    ``prompt_tokens`` tokens needs, when only each prompt's last position
    is unembedded.  Per token and layer 24 d^2 (qkv 6, projection 2, MLP
    16, two FLOPs per multiply-add); causal attention 2 n^2 d per prompt
    and layer (scores and weighted values, half the square each), taken
    at the mean length, which is never above the true mean of squares;
    the head 2 d V per prompt.  Padding to a bucket, logits of other
    positions and recomputation are not needed work and not counted."""
    d, layers, vocab = model["n_embd"], model["n_layer"], model["vocab_size"]
    mean_len = prompt_tokens / max(prompts, 1.0)
    return (layers * (24.0 * d * d * prompt_tokens + 2.0 * mean_len * mean_len * d * prompts)
            + 2.0 * d * vocab * prompts)


def gpt2_decode_attention_bytes(model: dict, context_tokens: float, kv_bytes: int = 2) -> float:
    """Bytes one decode step's attention must read over all layers for
    lanes holding ``context_tokens`` cached tokens in total: each cached
    token's key and value, d elements each.  Queries, outputs and block
    tables are left out (under 1% at these contexts), and a page is
    counted by the tokens it holds, not whole."""
    return 2.0 * model["n_embd"] * kv_bytes * context_tokens * model["n_layer"]
