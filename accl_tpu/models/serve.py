"""Continuous-batching decode serving over the fused decode step.

The latency floor of interactive inference is the per-token decode
step: one token's compute is tiny, so at production request rates the
dispatch seams — N layers x (kernel launch + TP-allreduce launch) —
dominate the step, not the math. transformer.record_decode_step fuses
the whole step (attention consumer + tp allreduce + MLP consumer + tp
allreduce per layer, plus the logits head) into ONE SequenceProgram
dispatch; this module multiplexes concurrent requests over that single
program:

  - the batch axis is STATIC (the program is compiled once for B
    slots); requests join and leave at STEP BOUNDARIES only, so the
    steady state never recompiles — the continuous-batching model of
    Orca/vLLM, at the descriptor-batch layer;
  - per-slot state is one integer (the slot's position): the KV cache
    itself lives device-resident in the program's state buffers, and a
    freshly admitted request simply starts writing rows at pos 0 — the
    causal mask (t > pos) makes the previous occupant's stale tail
    unreachable, so slot reuse needs NO cache reset or extra dispatch
    (a recurrent state, which no mask hides, is zeroed by the model's own
    step and prefill at pos 0: kimi_linear's KDA);
  - a prompt streams in teacher-forced, one token per step riding the
    SAME decode program: a joining request streams its prompt through
    its slot while neighbours keep decoding — join never stalls the
    batch. A request's `context` goes in at admission through the
    model's prefill program instead, where the model has one (the
    flagship has none, and streams the context as prompt);
  - every step is measured into the telemetry registry
    (accl_serve_step_seconds p50/p95/p99/p99.9, accl_serve_tokens_total),
    the same always-on surface the rest of the data plane reports to.

Batched decode is bitwise-equal to sequential per-request decode
through the same program (tests/test_decode.py pins it): every per-slot
computation in the step is row-independent — einsums contract only
model dims, softmax/rmsnorm normalize per (slot, position), and cache
appends write only the slot's own rows — so occupancy cannot leak
between requests.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque

import numpy as np

from ..telemetry import get_tracer, metrics


@dataclasses.dataclass
class DecodeRequest:
    """One inference request: `context` is prefilled into its slot at
    admission, `prompt` streams in one token per step (teacher-forced),
    then up to `max_new_tokens` tokens decode greedily. `generated`
    fills as the request runs; `done` flips when it leaves its slot."""

    rid: int
    prompt: list[int]
    max_new_tokens: int
    generated: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    context: list[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Slot:
    req: DecodeRequest
    pos: int = 0  # next position to feed (== tokens consumed so far)
    fed: int = 0  # prompt tokens fed so far


class DecodeServer:
    """Multiplex concurrent decode requests over one fused decode-step
    program (mode="fused", the production path) or its dispatch-per-
    layer eager twin (mode="eager", the unfused baseline). One
    instance owns its ACCL facade's decode
    buffers; all requests share them, one slot each.

    `model` is a decode model (`transformer.FlagshipDecode`,
    `deepseek_v2.DeepSeekV2`, `kimi_linear.KimiLinear`): its
    `create_buffers`, `make_program`, `register_consumers`, `run_eager`,
    `write_inputs` (which returns the bytes it put on the device),
    `read_logits`, `prefill` (None where it has none) and, where it
    counts them, `layer_counts` for the step's span."""

    def __init__(self, accl, model, *, batch: int,
                 max_len: int, mode: str = "fused", lint: str = "error",
                 registry=None, time_fn=time.perf_counter,
                 scheduler=None, tenant: str = "serve"):
        if mode not in ("fused", "eager"):
            raise ValueError(f"mode must be 'fused'|'eager', got {mode!r}")
        self.model = model
        self.vocab = model.vocab
        self.batch = batch
        self.max_len = max_len
        self.mode = mode
        self._accl = accl
        self._time = time_fn
        self._buffers = model.create_buffers(accl, batch, max_len)
        if mode == "fused":
            self._program = model.make_program(accl, self._buffers, lint)
        else:
            self._program = None
            model.register_consumers(accl, self._buffers)
        # the last step's logits (B, V) as the host read them
        self.last_logits: np.ndarray | None = None
        # the multi-tenant seam (ROADMAP item 4's deferred "admission
        # = item 1"): with a scheduler attached, request admission
        # consults its backpressure (typed SchedulerSaturatedError
        # when the ring is saturated) and every fused step dispatches
        # through scheduler.dispatch_now — the same program, the same
        # run(from_device=True, to_device=True), so batched==sequential
        # bitwise parity is untouched; what the scheduler adds is tenant
        # metering, SLO residuals and the concurrency/certificate
        # discipline next to any co-running tenants.
        self._scheduler = scheduler
        self._tenant = tenant
        self._step_cost_s: float | None = None
        if scheduler is not None:
            if tenant not in scheduler.tenants:
                scheduler.register_tenant(tenant, priority=0)
            if self._program is not None:
                self._step_cost_s = scheduler.predict_cost_s(
                    self._program)
        self._slots: list[_Slot | None] = [None] * batch
        self._queue: deque[DecodeRequest] = deque()
        self._next_rid = 0
        self.n_steps = 0
        reg = registry if registry is not None else metrics.get_registry()
        self._m_step = reg.histogram("accl_serve_step_seconds",
                                     mode=mode, batch=batch)
        self._m_tokens = reg.counter("accl_serve_tokens_total", mode=mode)
        self._m_active = reg.gauge("accl_serve_active_requests", mode=mode)

    # -- request intake ----------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               context=()) -> DecodeRequest:
        """Queue a request; it joins the batch at the next step
        boundary with a free slot, its `context` prefilled there. The
        prompt must be non-empty and context+prompt+generation must fit
        the compiled max_len window."""
        prompt = [int(t) for t in prompt]
        context = [int(t) for t in context]
        if self.model.prefill is None:  # the context streams in as prompt
            prompt, context = context + prompt, []
        if not prompt:
            raise ValueError("empty prompt")
        if any(not 0 <= t < self.vocab for t in context + prompt):
            raise ValueError("prompt token outside vocab")
        if len(context) + len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"context ({len(context)}) + prompt ({len(prompt)}) + "
                f"max_new_tokens ({max_new_tokens}) exceeds max_len "
                f"{self.max_len}")
        if self._scheduler is not None:
            # admission through the scheduler seam: the request's
            # predicted cost is (steps it will occupy) x (one fused
            # step's price); a saturated scheduler rejects HERE with
            # the typed error, before the request ever holds a slot
            step_cost = (self._step_cost_s
                         if self._step_cost_s is not None else 1e-5)
            n_steps = len(prompt) + int(max_new_tokens)  # decode steps
            self._scheduler.admit_request(self._tenant,
                                          cost_s=step_cost * n_steps)
        req = DecodeRequest(rid=self._next_rid, prompt=prompt,
                            max_new_tokens=int(max_new_tokens),
                            context=context)
        self._next_rid += 1
        self._queue.append(req)
        return req

    @property
    def active(self) -> bool:
        return bool(self._queue) or any(s is not None for s in self._slots)

    @property
    def n_active_slots(self) -> int:
        return sum(s is not None for s in self._slots)

    # -- the step loop -----------------------------------------------------

    def admit(self) -> None:
        """Join at the step boundary: fill free slots from the queue,
        prefilling each joining request's context into its slot's cache
        rows. No cache reset — the mask hides everything past the rows
        the request itself writes."""
        for i in range(self.batch):
            if self._slots[i] is None and self._queue:
                req = self._queue.popleft()
                if req.context:
                    self.model.prefill(self._buffers, i, req.context)
                self._slots[i] = _Slot(req, pos=len(req.context))

    def step(self) -> int:
        """One fused decode step for every occupied slot: admit at the
        boundary, stage [token, pos] rows, ONE dispatch, harvest
        argmax tokens, retire finished requests. Returns the number of
        generated (non-prompt) tokens this step. The step is one `call`
        span (while spans are collected) with children `decode.inputs`
        and `decode.logits`, each with the `bytes` it moved to or from
        the device; the sequence's dispatch carries its id."""
        self.admit()
        with get_tracer().call("decode_step") as sp:
            return self._step(sp if sp.keep else None)

    def _step(self, call) -> int:
        ph = call.begin("decode.inputs") if call else None
        tokens = np.zeros((self.batch,), np.int64)
        pos = np.zeros((self.batch,), np.int64)
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue  # idle rows feed (token 0, pos 0): harmless —
                # they touch only their own slot's cache row 0
            r = slot.req
            if slot.fed < len(r.prompt):
                tokens[i] = r.prompt[slot.fed]
            else:
                tokens[i] = r.generated[-1]
            pos[i] = slot.pos
        if call:  # what the step computes: its tokens, attended rows and
            # the layers of each kind it runs (where the model counts them)
            live = [s.pos + 1 for s in self._slots if s is not None]
            call.set(slots=len(live), context=sum(live),
                     **getattr(self.model, "layer_counts", {}))
        moved = self.model.write_inputs(self._buffers, tokens, pos)
        if ph:
            ph.end(bytes=moved)
        t0 = self._time()
        if self._program is not None:
            # steady state: one dispatch over device-resident buffers
            # (write_inputs put xp's [x, pos] prefix on the device, the
            # only bytes the step reads from the host); one rank's
            # logits come back
            if self._scheduler is not None:
                self._scheduler.dispatch_now(self._tenant,
                                             self._program,
                                             from_device=True,
                                             to_device=True)
            else:
                self._program.run(from_device=True, to_device=True)
            ph = call.begin("decode.logits") if call else None
            logits = self.model.read_logits(self._buffers, sync=True)
            moved = logits.nbytes
        else:
            self.model.run_eager(self._accl, self._buffers)
            ph = call.begin("decode.logits") if call else None
            logits = self.model.read_logits(self._buffers)
            moved = 0  # the eager twin's last call brought them back
        self.last_logits = logits
        dt = self._time() - t0
        n_generated = 0
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            r = slot.req
            nxt = int(np.argmax(logits[i]))
            slot.pos += 1
            slot.fed += 1
            if slot.fed >= len(r.prompt):
                # fed the last prompt token (or a generated one): the
                # argmax is a real generated token
                r.generated.append(nxt)
                n_generated += 1
            if (len(r.generated) >= r.max_new_tokens
                    or slot.pos >= self.max_len):
                r.done = True
                self._slots[i] = None  # leave at the boundary
        if ph:
            ph.end(bytes=moved)
        self.n_steps += 1
        self._m_step.observe(dt)
        if n_generated:
            self._m_tokens.inc(n_generated)
        self._m_active.set(self.n_active_slots + len(self._queue))
        return n_generated

    def free(self) -> None:
        """Release the decode buffers; the server serves no more."""
        b = self._buffers
        for buf in (b.xp, b.logits, *b.state, b.attn_sum, b.x2,
                    b.mlp_partial, b.mlp_sum):
            self._accl.free_buffer(buf)
            buf.device = None
        self._program = None

    def run(self, max_steps: int | None = None) -> int:
        """Drive steps until every request drained (or max_steps).
        Returns total generated tokens."""
        total = 0
        while self.active:
            if max_steps is not None and self.n_steps >= max_steps:
                break
            total += self.step()
        return total


def generate(server: DecodeServer, prompts, max_new_tokens: int):
    """Convenience batch API: submit every prompt, drain, return the
    generated token lists in submission order."""
    reqs = [server.submit(p, max_new_tokens) for p in prompts]
    server.run()
    return [r.generated for r in reqs]
