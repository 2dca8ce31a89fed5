"""The Kimi-Linear cell's benchmark code on the CPU: the driver and its
comparison end to end at a small size (sound, and with a planted
fault), the step's operations and bytes checked by hand, the KDA
operations' pattern against operation names of a traced v5e run, and
the new readers on a program without step spans."""

from __future__ import annotations

import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

import costs_kimi_linear as ck  # noqa: E402
import costs_mla_moe  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

from accl_tpu import telemetry  # noqa: E402

CELL = "seq.kda.decode.w1"
CONFIG = json.loads((BENCH / "configs/kimi_linear_48b_a3b_ep32.json")
                    .read_text())
PEAKS = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
# the configuration at a size the CPU runs in seconds; every other key
# (NoPE, sigmoid routing, the layer pattern, the check's limit) as
# published
SMALL = dict(hidden_size=64, vocab_size=256, num_attention_heads=4,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, intermediate_size=96, moe_intermediate_size=24,
             router_experts=32, num_experts=8, num_experts_per_token=4,
             linear_attn_config=dict(CONFIG["linear_attn_config"],
                                     num_heads=2, head_dim=16),
             slots=4, max_len=1024, context_min=9, context_max=40,
             prefill_chunk=16)

DRIVE = """
import json, sys
sys.path.insert(0, 'benchmark')
import run
small, fault = json.loads(sys.argv[1]), sys.argv[2]
orig = run.load_cell
def load_cell(name):
    spec = orig(name)
    spec['config'] = dict(spec['config'], **small)
    spec['traffic'] = dict(spec['traffic'], tokens_per_step=small['slots'])
    return spec
run.load_cell = load_cell
from accl_tpu.models import kimi_linear as kl
if fault == 'no_decay':  # the KDA state never decays
    decay = kl._decay
    kl._decay = lambda *a: 0 * decay(*a)
sys.exit(run.main(['--workload', 'seq.kda.decode.w1', '--seed',
                   '4294967311', '--seconds', '0.5', '--trace', sys.argv[3]],
                  require_tpu=False))
"""


def _drive(tmp_path, fault: str = "none", trace: int = 0) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run(
        [sys.executable, "-c", DRIVE, json.dumps(SMALL), fault, str(trace)],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=BENCH.parent)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct_and_reads_the_host_part(tmp_path):
    res = _drive(tmp_path, trace=1)
    assert res["correct"] is True, res
    assert res["checks"]["max_logit_gap"]["value"] < 1e-4
    # the CPU has no peaks and no TPU planes: only the span metric reads
    assert set(res["metrics"]) == {"decode_host_us.decode"}


def test_a_state_that_never_decays_is_not_correct(tmp_path):
    res = _drive(tmp_path, "no_decay")
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]


# -- operations and bytes, by hand -------------------------------------------

TINY = dict(hidden_size=8, num_hidden_layers=5, first_k_dense_replace=1,
            intermediate_size=12, moe_intermediate_size=6, router_experts=16,
            num_experts=4, num_experts_per_token=2, num_shared_experts=1,
            num_attention_heads=2, kv_lora_rank=4, qk_nope_head_dim=2,
            qk_rope_head_dim=2, v_head_dim=2, vocab_size=10,
            linear_attn_config=dict(full_attn_layers=[4], num_heads=2,
                                    head_dim=4, short_conv_kernel_size=4))


def test_kda_operations_and_bytes_by_hand():
    """D 8, 2 heads of 4 (HK 8), 4 taps, 3 tokens, 4 KDA layers."""
    # a token: q,k,v 2*8*24 = 384; conv 2*4*24 = 192; the two rank-4
    # gates 2*(2*8*4 + 2*4*8) = 256; b 2*8*2 = 32; the recurrence
    # 7*2*4*4 = 224; W_o 2*8*8 = 128: 1216
    assert ck.kda_flops(TINY, 3) == 3 * 1216
    # a slot's state: tails 3*3*8 = 72 and S 2*4*4 = 32; the weights:
    # norm 8, qkv 192, conv 96, gates 2*(32 + 32) = 128, dt_bias 8,
    # A_log 2, W_b 16, o norm 4, W_o 64: 518 numbers
    assert ck.kda_state_numbers(TINY, 3) == 3 * 104
    assert ck.kda_weight_numbers(TINY) == 518
    flops, nbytes = ck.kda_part(TINY, 3)
    assert ck.kda_layers(TINY) == 4
    assert flops == 4 * 3 * 1216
    assert nbytes == 4 * 4 * (2 * 3 * 104 + 518)


def test_step_operations_by_hand():
    """The dense layer 6*8*12 a token; a MoE layer's router 2*8*16, the
    held share of the 2 choices (2 * 4/16) of 6*8*6, the shared expert
    6*8*6; MLA as costs_mla_moe counts it; the head 2*8*10."""
    tokens, context = 3, 40
    moe = 2 * 8 * 16 + 0.5 * 6 * 8 * 6 + 6 * 8 * 6
    want = tokens * (6 * 8 * 12 + 4 * moe + 2 * 8 * 10) \
        + 4 * ck.kda_flops(TINY, tokens) \
        + costs_mla_moe.attn_flops(TINY, tokens, context, 2)
    assert ck.step_flops(TINY, tokens, context) == pytest.approx(want)


def test_the_cells_memory_and_least_time():
    """At the cell's sizes: a slot's KDA state is 2.24 MB, the step's
    four KDA layers read and write 2.30 GB of it, and the KDA consumers
    are bound by HBM."""
    assert ck.kda_state_numbers(CONFIG, 1) * 4 == 2_244_608
    flops, nbytes = ck.kda_part(CONFIG, 128)
    assert 4 * 4 * 2 * ck.kda_state_numbers(CONFIG, 128) == \
        pytest.approx(2.30e9, rel=2e-3)
    assert nbytes / PEAKS["hbm_bytes_per_s"] > \
        flops / PEAKS["bf16_flops_per_s"]


# -- the KDA pattern on a traced v5e run's operation names --------------------

def _ops(kind: str) -> list[str]:
    text = (BENCH / "tests" / "data" / "kimi_ops.txt").read_text()
    return [line.split("\t", 1)[1] for line in text.splitlines()
            if line.startswith(kind + "\t")]


def test_kda_pattern_matches_kda_operations_only():
    """Operation names saved from the cell's traced v5e run, each
    labelled with the part of the step it belongs to: the pattern
    matches every KDA state operation and no MLA, expert, head or
    sequencer one."""
    rx = re.compile(run.load_module("metrics", "kda_roofline.kimi")
                    .pattern(CONFIG))
    kda = _ops("kda")
    others = [op for kind in ("mla", "experts", "head", "sequencer")
              for op in _ops(kind)]
    assert kda and others
    assert all(rx.search(op) for op in kda), [op for op in kda
                                              if not rx.search(op)]
    assert not [op for op in others if rx.search(op)]


@pytest.fixture
def ring():
    tr = telemetry.get_tracer()
    tr.clear()
    tr.enable()
    yield tr
    tr.clear()
    tr.disable()


def test_readers_sum_a_step_over_its_programs(ring):
    """Five steps 100 ms apart, each a call span (with `slots` and
    `context`) holding three program executions on the chip: the
    inputs' prefix write, the step (one KDA operation of 20 ms and one
    MLA operation of 30 ms), the logits row read. The roofline reads the
    KDA operation's time a step; the MFU the window's operations."""
    kda, mla = _ops("kda")[0], _ops("mla")[0]
    calls, spans, ops, mods = [], [], [], []
    for i in range(5):
        t0 = 1_000_000_000 + 100_000_000 * i
        calls.append((128, t0, t0 + 80_000_000))
        spans.append(trace_reduce.Span("bench.call", t0, t0 + 80_000_000,
                                       {}))
        ring.emit("decode_step", "call", "facade", ts_ns=t0 + 10,
                  dur_ns=79_000_000, args={"call_id": i + 1, "slots": 128,
                                           "context": 128 * 3000})
        at = t0 + 1_000_000
        mods.append(trace_reduce.Op("jit_put_prefix", at, at + 100_000))
        at += 200_000
        mods.append(trace_reduce.Op("jit_step", at, at + 60_000_000))
        ops.append(trace_reduce.Op(kda, at, at + 20_000_000))
        ops.append(trace_reduce.Op(mla, at + 20_000_000, at + 50_000_000))
        at += 61_000_000
        mods.append(trace_reduce.Op("jit_fetch_row", at, at + 100_000))
    w0, w1 = calls[0][1], calls[-1][2]
    spans.append(trace_reduce.Span("bench.window", w0, w1, {}))
    r = run.Run(calls=calls, world=1, peaks=PEAKS, config=CONFIG,
                device_ids=[0],
                trace=trace_reduce.Trace({0: ops}, spans, {0: mods}))
    least = costs_mla_moe.least_time_s(*ck.kda_part(CONFIG, 128), PEAKS)
    assert run.load_module("metrics", "kda_roofline.kimi").read(r) == \
        pytest.approx(100 * least / 0.020)
    flops = 5 * ck.step_flops(CONFIG, 128, 128 * 3000)
    assert run.load_module("metrics", "step_mfu.kimi").read(r) == \
        pytest.approx(100 * flops / ((w1 - w0) / 1e9
                                     * PEAKS["bf16_flops_per_s"]))


@pytest.mark.parametrize("name", ["step_mfu.kimi", "kda_roofline.kimi"])
def test_nothing_to_read_from_a_program_without_step_spans(name):
    """A program that emits no decode step span (the parent's, which
    has no such model) gives no reading, and no reader raises."""
    telemetry.get_tracer().clear()
    trace = trace_reduce.Trace({0: []}, [], {0: []})
    r = run.Run(calls=[(128, 0, 100)], world=1, peaks=PEAKS, config=CONFIG,
                device_ids=[0], trace=trace)
    assert run.load_module("metrics", name).read(r) is None
