"""Operations and bytes the workload's shapes require, from a
configuration file's sizes (``bench/configs``). They count the work, not
what an implementation pads to: attention at each row's live depth, rows
that are live, adapter slots that are in use. A multiply-add is 2 FLOPs.
The matmul arithmetic follows the repository's analytic FLOP counts, with
the skip-LoRA terms added."""

from __future__ import annotations


def _d(cfg: dict):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (cfg["num_hidden_layers"], d, h, cfg["num_key_value_heads"], d // h,
            cfg["intermediate_size"], cfg["vocab_size"],
            3 if cfg["ffn_gated"] else 2, int(cfg["skip_lora_rank"]))


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one layer."""
    L, D, H, KV, hd, F, V, nf, R = _d(cfg)
    return D * (H + 2 * KV) * hd + H * hd * D + nf * D * F


def matmul_params(cfg: dict) -> int:
    """All layers' matmul weights (no embedding gather, no head)."""
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg)


def attention_flops(cfg: dict, keys: int) -> float:
    """QK^T and PV for one query over ``keys`` keys, all layers."""
    L, D, H, KV, hd, F, V, nf, R = _d(cfg)
    return 4.0 * H * hd * keys * L


def readout_flops(cfg: dict) -> float:
    L, D, H, KV, hd, F, V, nf, R = _d(cfg)
    return 2.0 * D * V


def skip_flops(cfg: dict) -> float:
    """sum_l x_l A_l B_l for one token."""
    L, D, H, KV, hd, F, V, nf, R = _d(cfg)
    return 4.0 * L * D * R


def decode_token_flops(cfg: dict, position: int) -> float:
    """One decode step of one row whose new token sits at ``position``
    (it attends ``position + 1`` keys), with its skip term and readout."""
    return (2.0 * matmul_params(cfg) + attention_flops(cfg, position + 1)
            + skip_flops(cfg) + readout_flops(cfg))


def prefill_flops(cfg: dict, length: int) -> float:
    """A causal forward over ``length`` real tokens, read out (with the
    skip term) at the last one only."""
    pairs = length * (length + 1) / 2
    return (2.0 * matmul_params(cfg) * length
            + 4.0 * cfg["num_attention_heads"] * (cfg["hidden_size"]
                                                 // cfg["num_attention_heads"])
            * pairs * cfg["num_hidden_layers"]
            + skip_flops(cfg) + readout_flops(cfg))


def adapt_token_flops(cfg: dict) -> float:
    """One cached adapt step, per token: skip term forward (4LDR), its
    backward to A and B (8LDR), readout forward and its backward to the
    hidden state (the head is frozen: 2DV each)."""
    return 3.0 * skip_flops(cfg) + 2.0 * readout_flops(cfg)


def skip_serve_call(cfg: dict, rows: int, slots: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one grouped skip-LoRA serve call over ``rows``
    token rows that use ``slots`` adapter slots: bf16 activations in and
    out, float32 adapters of the slots in use."""
    L, D, H, KV, hd, F, V, nf, R = _d(cfg)
    flops = 4.0 * L * D * R * rows
    nbytes = 2.0 * L * rows * D + 4.0 * slots * L * 2 * D * R + 2.0 * rows * D
    return flops, nbytes


def skip_adapt_bwd_call(cfg: dict, rows: int, tenants: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one grouped skip-LoRA backward over ``rows`` token
    rows of ``tenants`` tenants: gB = (xA)^T g, gz = g B^T, gA = x^T gz;
    bf16 activations and cotangent in, float32 adapters in, their
    gradients out."""
    L, D, H, KV, hd, F, V, nf, R = _d(cfg)
    flops = 6.0 * L * D * R * rows
    nbytes = (2.0 * L * rows * D + 2.0 * rows * D
              + 2 * 4.0 * tenants * L * 2 * D * R)
    return flops, nbytes
