"""Operations and bytes, computed from shapes.

* ``round_model_flops(cfg, traffic)`` - the FL model FLOPs one served round
  needs: local SGD (the model's ``train_flops`` per example: an image, or a
  sequence) over participants x local iterations x batch, the verify
  gate's forward over every BS aggregate on the holdout slice (where the
  configuration has the gate), and one forward of the new global model on
  it (the accuracy's second forward is recomputation and does not count).
* ``custom_calls(hlo_text)`` - every ``tpu_custom_call`` of a compiled HLO
  module with its operand and result shapes, and the bytes and operations
  the segment reduction needs for it: each operand read once, each result
  written once, one add per value element.
"""
import re

from world import model_module

_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1, "u8": 1,
          "pred": 1, "f64": 8, "s64": 8}
_SHAPE = re.compile(r"\b(f32|s32|u32|bf16|f16|s8|u8|pred|f64|s64)\[([0-9,]*)\]")
# an optimized HLO line of a Pallas call: its name and result shape before
# ``custom-call(``, its operand shapes in ``operand_layout_constraints``
_CALL = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*?)\s+custom-call\(.*"
                   r"custom_call_target=\"tpu_custom_call\".*?"
                   r"operand_layout_constraints=\{(.*?\})\}")


def round_model_flops(cfg, traffic) -> float:
    mod = model_module(cfg)
    f, t = mod.forward_flops(cfg), mod.train_flops(cfg)
    train = traffic["participants"] * cfg["local_iters"] * cfg["batch_size"] * t
    evals = (cfg["n_bs"] if cfg["verify"] else 0) * cfg["n_eval"] * f + cfg["n_eval"] * f
    return float(train + evals)


def _shapes(text):
    out = []
    for dt, dims in _SHAPE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append((dt, n))
    return out


def custom_calls(hlo_text: str):
    """[{name, bytes, flops}] for every ``tpu_custom_call`` instruction of an
    optimized HLO module's text."""
    calls = []
    for line in hlo_text.splitlines():
        m = _CALL.match(line)
        if not m:
            continue
        name, result, operands = m.groups()
        ins, outs = _shapes(operands), _shapes(result)
        nbytes = sum(_BYTES[dt] * n for dt, n in ins + outs)
        # one add per element of the largest (value) operand
        flops = max((n for _, n in ins), default=0)
        calls.append({"name": name, "bytes": nbytes, "flops": flops})
    return calls
