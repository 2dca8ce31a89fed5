"""Model FLOPs utilization of the Kimi-Linear decode step: the model
operations of the window's steps (costs_kimi_linear.step_flops, from
each step's slots and attended context) over the traced window x chips x
the bf16 peak, %."""

import costs_kimi_linear
import decode_parts


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    steps = decode_parts.steps(run)
    w = run.trace.window()
    if not steps or w is None:
        return None
    flops = sum(costs_kimi_linear.step_flops(run.config, s["slots"],
                                             s["context"]) for s in steps)
    return 100.0 * flops / ((w[1] - w[0]) / 1e9 * run.world
                            * run.peaks["bf16_flops_per_s"])
