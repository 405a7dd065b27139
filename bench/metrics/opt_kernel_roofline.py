"""Share of its roofline that the fused COAP update kernel reaches, in
percent.

Kernel time is the summed device time of the kernel's operations in the
traced steps. The least time is, per launch (one per bucket of matrices
of one shape), the larger of its operations over the bf16 peak and its
bytes over the HBM bandwidth (``bench/flops.fused_update_cost``), times
the traced steps. Which of the two bounds is in PERF.md.
"""
import collections

from bench import flops

KERNELS = {False: "coap_fused_update_bp_pallas", True: "coap_fused_update_q8_pallas"}


def read(run):
    if run.trace is None:
        return None
    opt = run.cell.traffic["optimizer"]
    quant = opt["name"].startswith("8bit-")
    ops = run.trace.matching(KERNELS[quant])
    if not ops:
        return None
    buckets = collections.Counter()
    for path, count, m, n, r in flops.projected_matrices(run.shapes, opt):
        buckets[(m, n, r)] += count
    least = 0.0
    for (m, n, r), count in buckets.items():
        f, b = flops.fused_update_cost(m, n, r, quant, block=opt["quant_block"])
        least += max(count * f / run.peaks["bf16_flop_per_s"],
                     count * b / run.peaks["hbm_bytes_per_s"])
    kernel_s = sum(op.dur for op in ops) * 1e-9 / run.trace.chips
    return 100.0 * least * run.traced_steps / kernel_s
