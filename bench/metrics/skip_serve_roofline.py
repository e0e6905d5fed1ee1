"""Kernels: the grouped skip-LoRA serve kernel (``_grouped_fwd_kernel``,
trace ops ``skip_lora_grouped_fwd.N``) in the decode steps, as a share of
its roofline, in %: least time (the larger of FLOPs at peak and bytes at
peak bandwidth, ``bench/counts.py``) over the kernel's summed device time
in the traced window. The least time
counts the rows that received a token and the adapter slots they use; the
device time is every call of the kernel, admission calls included. Moves
``tokens_per_s``."""

from bench import counts, peaks
from bench import trace_reduce as TR

KERNEL = "skip_lora_grouped_fwd"


def read(run):
    if run.trace is None:
        return None
    spent = TR.kernel_seconds(run.trace["op_s"], KERNEL)
    s = run.session
    if spent <= 0 or not s.step_log:
        return None
    chunk = s.sched_kw["chunk"]
    flops = nbytes = 0.0
    for st in s.step_log:
        calls = chunk * max(1, st["admits"])
        f, b = counts.skip_serve_call(run.cfg, st["tokens"], 0)
        _, per_call = counts.skip_serve_call(run.cfg, 0, st["slots"])
        flops += f
        nbytes += b + calls * per_call
    return 100.0 * peaks.least_seconds(flops, nbytes, run.device_kind) / spent
