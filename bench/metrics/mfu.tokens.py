"""Whole decode step: model FLOPs of the tokens the host received in the
window (2 x matmul weights, attention at each row's live depth, skip term
and readout per decode token; the prompt's prefill for a first token),
over window time x peak bf16 FLOP/s, in %. Moves ``tokens_per_s``."""

from bench import counts, peaks


def read(run):
    s = run.session
    if s.stream is None:
        return None
    cfg = run.cfg
    total = 0.0
    per_tok = counts.decode_token_flops(cfg, 0) - counts.attention_flops(cfg, 1)
    per_key = counts.attention_flops(cfg, 1)
    for r in s.served:
        if r.win_lo is None:
            continue
        p = r.planned.prompt.size
        lo, hi = r.win_lo, r.win_hi
        if lo == 0:
            total += counts.prefill_flops(cfg, p)
            lo = 1
        if hi > lo:
            # token j attends p + j keys (its position p + j - 1, plus one)
            keys = (hi - lo) * p + (lo + hi - 1) * (hi - lo) / 2
            total += (hi - lo) * per_tok + per_key * keys
    t0, t1 = s.window
    return 100.0 * total / ((t1 - t0) * peaks.peaks(run.device_kind)["bf16_flops"])
