"""Model FLOP/s utilization of the FL round, in %: the model FLOPs one round
needs (``flops.round_model_flops``) times the rounds completed per second of
the traced window, over the chip's bf16 peak. The model runs in float32 at
default matmul precision, which the TPU executes as bf16 passes."""
from flops import round_model_flops


def read(run):
    if not run.rounds_traced or run.trace.window_s <= 0:
        return None
    rate = run.rounds_traced / run.trace.window_s
    return 100.0 * round_model_flops(run.cfg, run.traffic) * rate / run.peaks["flops_bf16"]
