"""Whole fine-tune job: model FLOPs of the jobs completed in the window
(each sample's ingest forward, read out at its last token, plus per
epoch and token the adapt step's skip term forward and backward and the
readout forward and backward to the hidden state), over the time from the
window's start to the last completion x peak bf16 FLOP/s, in %. Moves
``finetune_samples_per_s``."""

from bench import counts, peaks


def read(run):
    s = run.session
    if s.ft is None or not s.jobs_done:
        return None
    ft = s.ft
    seq, samples, epochs = int(ft["seq"]), int(ft["samples"]), int(ft["epochs"])
    per_job = samples * (counts.prefill_flops(run.cfg, seq)
                         + epochs * seq * counts.adapt_token_flops(run.cfg))
    jobs = sum(len(c["jobs"]) for c in s.jobs_done)
    secs = s.jobs_done[-1]["end"] - s.window[0]
    return 100.0 * jobs * per_job / (secs * peaks.peaks(run.device_kind)["bf16_flops"])
