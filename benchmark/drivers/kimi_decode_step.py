"""Drives Kimi-Linear decode as a serving host runs it: `DecodeServer`
over `make_program`'s fused step, one `ACCL.sequence()` program a step
on one chip, with the model's weights bound to its consumers as
device-resident operands.

Set-up makes the weights from the seed on the device, records the step,
and prefills every slot to a context drawn from the seed (uniform on the
configuration's context_min..context_max); the first slot's prefill
compiles the prefill program, and the first warm-up call the step. It
prints the time of each phase. A
call is one `DecodeServer.step()`: every slot is fed its previous greedy
token, and the call returns when the step's logits are on the host.

The comparison runs the plain reference (reference_kimi_linear.py) over
the whole histories of four slots drawn from the seed, after the
program's buffers are freed, and reads the widest logit gap at the kept
steps over the reference's largest |logit| at that position. The
control (`--control`) runs the consumers and the prefill at the default
matmul precision instead of fp32's.
"""

from __future__ import annotations

import gc
import time

import numpy as np

import reference_kimi_linear as reference

COMPARED_SLOTS = 4


class Driver:
    def __init__(self, accl, config: dict, sizes: list[int], seed: int,
                 control: bool = False):
        from accl_tpu.models import kimi_linear
        from accl_tpu.models.serve import DecodeServer

        slots, max_len = int(config["slots"]), int(config["max_len"])
        if sizes != [slots]:
            raise ValueError(f"traffic sends {sizes} tokens a step; the "
                             f"configuration has {slots} slots")
        self.config = config
        self.seed = seed
        self.cfg = kimi_linear.KimiLinearConfig.from_json(config)
        phases = {}
        t = time.perf_counter()
        self.model = kimi_linear.KimiLinear(
            accl, self.cfg, seed=seed,
            precision="default" if control else "highest",
            prefill_chunk=int(config["prefill_chunk"]))
        phases["weights"] = time.perf_counter() - t
        t = time.perf_counter()
        self.server = DecodeServer(accl, self.model, batch=slots,
                                   max_len=max_len)
        phases["buffers and step recording"] = time.perf_counter() - t
        rng = np.random.default_rng(seed)
        lengths = rng.integers(int(config["context_min"]),
                               int(config["context_max"]) + 1, slots)
        self.contexts = [rng.integers(0, self.cfg.vocab_size, n).tolist()
                         for n in lengths]
        self.compared = sorted(rng.choice(slots, COMPARED_SLOTS,
                                          replace=False).tolist())
        self.requests = []
        for first, contexts in ((True, self.contexts[:1]),
                                (False, self.contexts[1:])):
            t = time.perf_counter()
            self.requests += [
                self.server.submit(c[-1:], max_len - len(c), context=c[:-1])
                for c in contexts]
            self.server.admit()  # prefills the queued slots, and waits
            phases["prefill, first slot (compiles)" if first else
                   f"prefill, {len(contexts)} more slots"] = \
                time.perf_counter() - t
        print("decode set-up: " + ", ".join(
            f"{k} {v:.3f} s" for k, v in phases.items()), flush=True)
        self.steps = 0

    def call(self, size: int) -> None:
        """One decode step for every slot; returns with its logits on
        the host."""
        self.server.step()
        self.steps += 1

    def answer(self, size: int):
        """The last step's number and its logits (B, V), as the server
        read them: no copy."""
        return self.steps, self.server.last_logits

    def free(self) -> None:
        """Drop the program's buffers and the weights; keep the
        histories for the reference."""
        self.histories = [c + list(r.generated)
                          for c, r in zip(self.contexts, self.requests)]
        self.server.free()
        self.model.free()
        self.server = self.model = None
        gc.collect()

    def compare(self, kept) -> dict:
        """The widest gap over the kept steps and the compared slots:
        |logits - reference| at the slot's position, over the
        reference's largest |logit| there."""
        steps = sorted({step: logits for _, (step, logits) in kept}.items())
        # step s fed slot i its token at position len(context_i) - 1 + s - 1
        want = {i: [(len(self.contexts[i]) - 2 + s, logits[i])
                    for s, logits in steps
                    if len(self.contexts[i]) - 2 + s
                    < len(self.histories[i]) - 1]
                for i in self.compared}
        if not all(want.values()):
            return {"max_logit_gap": float("inf")}
        ref = reference.DecodeReference(self.config, self.seed)
        got = ref.logits_at(
            [self.histories[i][:max(p for p, _ in want[i]) + 1]
             for i in self.compared],
            [[p for p, _ in want[i]] for i in self.compared])
        worst = 0.0
        for i, rows in zip(self.compared, got):
            for (_, ans), r in zip(want[i], rows):
                gap = np.max(np.abs(np.asarray(ans, np.float64) - r)) \
                    / np.max(np.abs(r))
                worst = max(worst, float(gap) if np.isfinite(gap)
                            else float("inf"))
        return {"max_logit_gap": worst}
