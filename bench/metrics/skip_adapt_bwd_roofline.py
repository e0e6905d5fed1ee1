"""Kernels: the grouped skip-LoRA backward (``_grouped_bwd_kernel``, which
only ``adapt`` runs; trace ops ``transpose_jvp_jit_skip_lora_grouped_bwd``) as a share of its roofline, in %: least time of every
adapt step in the traced window (``bench/counts.py``: rows = tenants x
batch per tenant x tokens) over the kernel's summed device time there.
An adapt call that overruns the window counts in proportion to the part
of it inside. Moves ``finetune_samples_per_s``."""

from bench import counts, peaks
from bench import trace_reduce as TR

KERNEL = "transpose_jvp_jit_skip_lora_grouped_bwd"


def read(run):
    if run.trace is None:
        return None
    spent = TR.kernel_seconds(run.trace["op_s"], KERNEL)
    s = run.session
    if spent <= 0 or s.ft is None:
        return None
    ft = s.ft
    n, bpt, seq = int(ft["cohort"]), int(ft["batch_per_tenant"]), int(ft["seq"])
    steps = int(ft["epochs"]) * -(-int(ft["samples"]) // bpt)
    f, b = counts.skip_adapt_bwd_call(run.cfg, n * bpt * seq, n)
    per_step = peaks.least_seconds(f, b, run.device_kind)
    t0, t1 = s.window
    share = sum((min(e, t1) - a) / (e - a) for name, a, e in s.spans.spans
                if name == "adapt" and t0 <= a < t1 and e > a)
    return 100.0 * share * steps * per_step / spent
