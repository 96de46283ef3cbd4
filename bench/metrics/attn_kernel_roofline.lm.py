"""Roofline share of the flash attention forward kernel in the
language-model sweep cells: the least time the window's attention work
could take on the device, over the device time of the kernel's ops.

The work: every sequence the kernel ran over in the window's calls
(``Workload.flash_sequences_per_call``: local training, each layer's
recomputation in the backward pass, the evals), each through every layer;
one layer over one sequence takes at least the larger of its causal
attention FLOPs over the bf16 peak and its queries, keys, values and
output over the HBM bandwidth (``bench.lm_flops``). At the cells' 64-wide
heads the bytes bound it; the bound that applies is printed with the
share. The kernel's ops are those whose HLO instruction is named for the
kernel (``flash_attention``). Nothing where no such op ran."""
import sys

KERNEL = "flash_attention"


def read(run):
    from bench import lm_flops

    if run.trace is None or run.units == 0:
        return None
    peak = run.peak.get("bf16_flops_per_s")
    bandwidth = run.peak.get("hbm_bytes_per_s")
    kernel_s = sum(s for op, s in run.trace.device_ops
                   if KERNEL in op.split("=", 1)[0])
    if not (peak and bandwidth and kernel_s > 0):
        return None
    wl = run.workload
    calls = run.units // wl.work_per_step
    per_layer, bound = lm_flops.attention_roofline_seconds(
        wl.arch, wl.spec.lm_seq, peak, bandwidth)
    least = calls * wl.flash_sequences_per_call() * wl.arch.layers * per_layer
    print(f"attn_kernel_roofline.lm: bound={bound} least_s={least!r} "
          f"kernel_s={kernel_s!r}", file=sys.stderr, flush=True)
    return 100.0 * least / kernel_s
