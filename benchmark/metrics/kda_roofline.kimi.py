"""The KDA consumers' share of their roofline: the least time of the
step's KDA layers, on average over the traced steps, (every KDA layer's
conv tails and S read and written, and its weights read;
costs_kimi_linear.kda_part) over the device time of the KDA operations
in a step, %.

A v5e trace names an operation by its HLO instruction, which carries no
named scope, so the KDA operations are found by shapes that only KDA
has (`pattern`): the slots' states (B, H, K, K) and conv tails (B,
taps - 1, 3, H*K), those blocks flat as the consumer reads and writes
them in the layer state, and the step's new projected inputs (B, 1, 3,
H*K). Not by the per-head tensors (B, H, K): MLA's o has that shape
too. Checked against the op names of a traced run of the cell
(tests/data/kimi_ops.txt).

A served step runs several programs (the inputs' prefix write, the
step, the logits row read), so a step's KDA time is summed over every
program execution that lies in its call.
"""

import statistics

import costs_kimi_linear
import costs_mla_moe
import decode_parts
import trace_reduce


def pattern(c: dict) -> str:
    lin = c["linear_attn_config"]
    B, H, K = c["slots"], lin["num_heads"], lin["head_dim"]
    taps = lin["short_conv_kernel_size"]
    tails = f"{B},{taps - 1},3,{H * K}"
    return (rf"\[{B},{H},{K},{K}\]|\[{B * H * K * K}\]|\[{tails}\]"
            rf"|\[{B * (taps - 1) * 3 * H * K}\]|\[{B},1,3,{H * K}\]")


def step_ns(run, rx: str) -> list[float]:
    """Device ns of the matching operations in each traced step, summed
    over the step's program executions and averaged over the chips."""
    per: dict[int, dict[int, float]] = {}
    for dev in run.device_ids:
        for span, mod in trace_reduce.call_executions(run.trace, dev):
            ns = trace_reduce.covered(trace_reduce.union(
                (o.start, o.end) for o in trace_reduce.ops_in(
                    run.trace, dev, mod.start, mod.end, rx)),
                mod.start, mod.end)
            chips = per.setdefault(id(span), {})
            chips[dev] = chips.get(dev, 0.0) + ns
    return [statistics.fmean(chips.values()) for chips in per.values()
            if len(chips) == len(run.device_ids)]


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    steps = decode_parts.steps(run)
    ns = step_ns(run, pattern(run.config))
    if not steps or not ns or not any(ns):
        return None
    least = statistics.fmean(costs_mla_moe.least_time_s(
        *costs_kimi_linear.kda_part(run.config, s["slots"]), run.peaks)
        for s in steps)
    return 100.0 * least / (statistics.fmean(ns) / 1e9)
