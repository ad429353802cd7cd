"""The segment-reduce kernel's share of its roofline, in %: per round, the
least time of every kernel call of the compiled step (the larger of its
bytes over HBM bandwidth and its adds over peak FLOP/s, from the call's
operand and result shapes), summed and times the rounds completed, over the
kernel's summed duration in the trace."""


def read(run):
    t = run.trace.op_seconds(run.is_kernel)
    if t <= 0 or not run.kernel_calls or not run.rounds_traced:
        return None
    least = sum(max(c["bytes"] / run.peaks["hbm_bytes_per_s"],
                    c["flops"] / run.peaks["flops_bf16"]) for c in run.kernel_calls)
    return 100.0 * least * run.rounds_traced / t
