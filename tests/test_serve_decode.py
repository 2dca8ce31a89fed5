"""Fused decode-step + continuous-batching serving tests (the
latency-floor inference path): the whole decode step — N layers of
attention/MLP consumers and their TP allreduces plus the logits head —
runs as ONE recorded SequenceProgram over device-resident KV caches,
and must be bitwise-identical to the dispatch-per-layer eager twin and
agree with the full-context training forward; the serving layer's
continuous batching must be bitwise-equal to sequential per-request
decode under ragged join/leave; and the SYNTH_LATENCY_MAX_COUNT
register that routes the step's small allreduces must round-trip
through exchange memory and leave selection bit-for-bit unchanged at
register 0."""

import dataclasses

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from accl_tpu.accl import ACCL
from accl_tpu.constants import (
    DEFAULT_EAGER_RX_BUF_SIZE,
    DEFAULT_MAX_EAGER_SIZE,
    Operation,
    ReduceFunction,
    TuningParams,
    from_numpy_dtype,
)
from accl_tpu.descriptor import CallOptions
from accl_tpu.errors import LintError
from accl_tpu.models import serve
from accl_tpu.models import transformer as trf
from accl_tpu.parallel import make_mesh
from accl_tpu.sequencer import synthesis
from accl_tpu.sequencer.plan import Algorithm, select_algorithm

CFG = trf.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_kv_heads=2,
                            n_layers=2, d_ff=64)
WORLD = 2
B, T = 2, 12


def _mesh(world=WORLD):
    return Mesh(np.array(jax.devices()[:world]), ("ccl",))


def _params_np(seed=0):
    return jax.tree.map(np.asarray, trf.init_params(CFG, jax.random.key(seed)))


def _fused(params_np, batch=B, max_len=T):
    accl = ACCL(_mesh())
    prog, buffers = trf.make_decode_step_program(accl, CFG, params_np,
                                                 batch=batch,
                                                 max_len=max_len)
    return prog, buffers


def _eager(params_np, batch=B, max_len=T):
    accl = ACCL(_mesh())
    buffers = trf.create_decode_buffers(accl, CFG, batch, max_len)
    trf.register_decode_consumers(accl, CFG, params_np, buffers.dims)
    return accl, buffers


def test_fused_vs_eager_fuzz_bitwise():
    """30-seed fuzz: the one-dispatch fused step and the eager
    layer-by-layer twin produce BITWISE-equal logits on random tokens
    at random (per-slot ragged) positions. Both sides share identical
    cache-state evolution, so seeds chain without resets — exactly the
    long-running serving process."""
    params_np = _params_np()
    prog, bf = _fused(params_np)
    accl_e, be = _eager(params_np)
    for seed in range(30):
        rng = np.random.default_rng(52000 + seed)
        toks = rng.integers(1, CFG.vocab, B)
        pos = rng.integers(0, T, B)
        trf.write_decode_inputs(bf, params_np, toks, pos)
        prog.run(to_device=True)
        lf = trf.read_decode_logits(bf, sync=True)
        trf.write_decode_inputs(be, params_np, toks, pos)
        trf.run_decode_step_eager(accl_e, CFG, be)
        le = trf.read_decode_logits(be)
        np.testing.assert_array_equal(
            lf, le, err_msg=f"seed {seed}: fused != eager (bitwise)")


def _whole_image_step(prog, buffers, embed, toks, pos):
    """A step that stages xp's whole image in and reads every rank's
    logits back: the host mirror rebuilt in full, run(to_device=True),
    then sync_from_device and row 0."""
    d = buffers.dims
    b_d = d.batch * d.d_model
    row = np.zeros(d.n_out, np.float32)
    row[:b_d] = np.asarray(embed)[np.asarray(toks, np.int64)].reshape(-1)
    row[b_d:b_d + d.batch] = np.asarray(pos, np.float32)
    buffers.xp.host = np.repeat(row[None], buffers.xp.shape[0], 0)
    prog.run(to_device=True)
    buffers.logits.sync_from_device()
    return buffers.logits.host[0][:d.batch * d.vocab].reshape(d.batch,
                                                               d.vocab)


def _prefix_step(prog, buffers, params, toks, pos):
    """The serving step's I/O: the [x, pos] prefix in, the step run
    over device-resident buffers, rank 0's logits out."""
    trf.write_decode_inputs(buffers, params, toks, pos)
    prog.run(from_device=True, to_device=True)
    return trf.read_decode_logits(buffers, sync=True)


def test_prefix_in_row_out_equals_whole_images_bitwise():
    """Twelve chained steps of random tokens at ragged positions: the
    prefix put, run(from_device=True) and rank 0's row read give the
    logits of whole-image staging and a full read back, bit for bit."""
    params_np = _params_np(seed=4)
    prog_p, bp = _fused(params_np)
    prog_w, bw = _fused(params_np)
    for seed in range(12):
        rng = np.random.default_rng(53000 + seed)
        toks = rng.integers(1, CFG.vocab, B)
        pos = rng.integers(0, T, B)
        np.testing.assert_array_equal(
            _prefix_step(prog_p, bp, params_np, toks, pos),
            _whole_image_step(prog_w, bw, params_np["embed"], toks, pos),
            err_msg=f"step {seed}: prefix path != whole images")


def test_eager_twin_interleaved_with_prefix_steps_bitwise():
    """The eager twin (which stages xp from the host mirror) and the
    prefix-only fused step take turns on ONE set of buffers; each step
    matches a fused step on buffers of its own, bit for bit."""
    params_np = _params_np(seed=5)
    accl = ACCL(_mesh())
    prog, bf = trf.make_decode_step_program(accl, CFG, params_np, batch=B,
                                            max_len=T)
    prog_r, br = _fused(params_np)
    for seed in range(12):
        rng = np.random.default_rng(54000 + seed)
        toks = rng.integers(1, CFG.vocab, B)
        pos = rng.integers(0, T, B)
        if seed % 3 == 1:
            trf.write_decode_inputs(bf, params_np, toks, pos)
            trf.run_decode_step_eager(accl, CFG, bf)
            got = trf.read_decode_logits(bf)
        else:
            got = _prefix_step(prog, bf, params_np, toks, pos)
        np.testing.assert_array_equal(
            got, _prefix_step(prog_r, br, params_np, toks, pos),
            err_msg=f"step {seed}: interleaved != fused alone")


def _traced_steps(srv, steps):
    """The tracer ring's spans over `steps` server steps."""
    from accl_tpu import telemetry

    tr = telemetry.get_tracer()
    tr.clear()
    tr.enable()
    try:
        for _ in range(steps):
            srv.step()
        return tr.snapshot()
    finally:
        tr.disable()
        tr.clear()


def test_steady_step_spans_count_the_prefix_and_one_row():
    """A served fused step puts world x (B*D + B) floats on the device
    and reads B*V back, and stages nothing else; the eager server's
    logits arrive with its last call, so its read moves nothing."""
    params_np = _params_np(seed=6)
    for mode in ("fused", "eager"):
        srv = serve.DecodeServer(ACCL(_mesh()),
                                 trf.FlagshipDecode(CFG, params_np),
                                 batch=B, max_len=T, mode=mode)
        srv.submit([3, 5], 6)
        srv.submit([7], 6)
        srv.step()  # the first step compiles the prefix writer
        ring = _traced_steps(srv, 3)
        ins, outs = ([s for s in ring if s["name"] == name]
                     for name in ("decode.inputs", "decode.logits"))
        assert len(ins) == len(outs) == 3
        assert {s["args"]["bytes"] for s in ins} == {
            WORLD * (B * CFG.d_model + B) * 4}
        assert {s["args"]["bytes"] for s in outs} == {
            B * CFG.vocab * 4 if mode == "fused" else 0}
        if mode == "fused":
            assert not [s for s in ring
                        if s["name"] in ("stage_in", "stage_out")]


def test_whole_image_syncs_still_move_whole_images():
    """sync_to_device / sync_from_device move every rank's whole row;
    put_prefix writes only the prefix of each row (host mirror and
    device image alike) and fetch_row reads one rank's row."""
    accl = ACCL(_mesh())
    n, k = 40, 7
    buf = accl.create_buffer(n, np.float32)
    image = np.arange(WORLD * n, dtype=np.float32).reshape(WORLD, n)
    buf.host = image.copy()
    buf.sync_to_device()
    np.testing.assert_array_equal(np.asarray(buf.device), image)
    rows = -np.arange(WORLD * k, dtype=np.float32).reshape(WORLD, k) - 1
    assert buf.put_prefix(rows) == rows.nbytes
    want = image.copy()
    want[:, :k] = rows
    np.testing.assert_array_equal(buf.host, want)
    np.testing.assert_array_equal(np.asarray(buf.device), want)
    for r in range(WORLD):
        np.testing.assert_array_equal(buf.fetch_row(r, n), want[r])
        np.testing.assert_array_equal(buf.fetch_row(r, k + 2),
                                      want[r, :k + 2])
    np.testing.assert_array_equal(buf.host, want)  # fetch_row left it
    buf.host = np.zeros_like(image)
    buf.sync_from_device()
    assert buf.host.shape == (WORLD, n)
    np.testing.assert_array_equal(buf.host, want)
    # a whole-image run stages xp's whole image in, and its outputs out
    from accl_tpu import telemetry

    prog, bf = _fused(_params_np())
    trf.write_decode_inputs(bf, _params_np(), [1, 2], [0, 0])
    tr = telemetry.get_tracer()
    tr.clear()
    tr.enable()
    try:
        prog.run()
        ring = tr.snapshot()
    finally:
        tr.disable()
        tr.clear()
    (stage_in,) = [s for s in ring if s["name"] == "stage_in"]
    assert stage_in["args"]["bytes"] == bf.xp.nbytes
    (stage_out,) = [s for s in ring if s["name"] == "stage_out"]
    assert stage_out["args"]["bytes"] >= bf.xp.nbytes + bf.logits.nbytes
    # the read-only mirror that sync_from_device leaves takes a prefix
    trf.write_decode_inputs(bf, _params_np(), [3, 4], [1, 1])
    b_d = B * CFG.d_model
    np.testing.assert_array_equal(bf.xp.host[:, b_d:b_d + B],
                                  np.ones((WORLD, B)))
    np.testing.assert_array_equal(np.asarray(bf.xp.device), bf.xp.host)


def test_fused_decode_matches_full_forward_oracle():
    """KV-cache correctness: decoding a sequence token by token through
    the fused program reproduces the full-context training forward
    (make_forward) position by position — the cache IS the context."""
    params = trf.init_params(CFG, jax.random.key(1))
    params_np = jax.tree.map(np.asarray, params)
    prog, bf = _fused(params_np)
    toks = np.random.default_rng(7).integers(1, CFG.vocab, (B, T)) \
        .astype(np.int32)
    omesh = make_mesh({"dp": 1, "sp": 1, "tp": WORLD},
                      devices=jax.devices()[:WORLD])
    ref = np.asarray(trf.make_forward(CFG, omesh)(
        trf.shard_params(params, CFG, omesh), toks))
    for t in range(T):
        trf.write_decode_inputs(bf, params_np, toks[:, t],
                                np.full(B, t, np.int64))
        prog.run(to_device=True)
        lf = trf.read_decode_logits(bf, sync=True)
        np.testing.assert_allclose(lf, ref[:, t], rtol=2e-4, atol=2e-4,
                                   err_msg=f"position {t}")


def test_batched_equals_sequential_ragged_join_leave():
    """Continuous batching parity: ragged prompts multiplexed over
    fewer slots than requests (forced join/leave churn mid-stream)
    generate the SAME tokens as draining each request alone through the
    same program — and as the eager server."""
    params_np = _params_np(seed=2)
    rng = np.random.default_rng(5)
    prompts = [list(map(int, rng.integers(1, CFG.vocab,
                                          int(rng.integers(1, 5)))))
               for _ in range(5)]

    def run(mode, sequential):
        srv = serve.DecodeServer(ACCL(_mesh()),
                                 trf.FlagshipDecode(CFG, params_np),
                                 batch=3, max_len=T, mode=mode)
        if sequential:
            outs = []
            for p in prompts:
                outs.extend(serve.generate(srv, [p], 4))
            return outs
        return serve.generate(srv, prompts, 4)

    batched = run("fused", sequential=False)
    assert batched == run("fused", sequential=True), \
        "batched != sequential (join/leave churn leaked between slots)"
    assert batched == run("eager", sequential=False), \
        "fused server != eager server"
    assert all(len(g) == 4 for g in batched)


def test_serve_slot_reuse_needs_no_cache_reset():
    """A slot's next occupant starts at pos 0 and the causal mask hides
    the previous occupant's stale cache tail: one slot serving two
    requests back to back matches two fresh single-request servers."""
    params_np = _params_np(seed=3)
    model = trf.FlagshipDecode(CFG, params_np)
    srv = serve.DecodeServer(ACCL(_mesh()), model, batch=1, max_len=T)
    a = serve.generate(srv, [[5, 9, 2]], 4)[0]
    b = serve.generate(srv, [[7, 1]], 4)[0]  # reuses the dirty slot
    fresh = serve.DecodeServer(ACCL(_mesh()), model, batch=1, max_len=T)
    assert b == serve.generate(fresh, [[7, 1]], 4)[0]
    fresh2 = serve.DecodeServer(ACCL(_mesh()), model, batch=1, max_len=T)
    assert a == serve.generate(fresh2, [[5, 9, 2]], 4)[0]


def test_serve_rejects_bad_requests():
    params_np = _params_np()
    model = trf.FlagshipDecode(CFG, params_np)
    srv = serve.DecodeServer(ACCL(_mesh()), model, batch=1, max_len=8)
    with pytest.raises(ValueError, match="empty"):
        srv.submit([], 2)
    with pytest.raises(ValueError, match="vocab"):
        srv.submit([CFG.vocab], 2)
    with pytest.raises(ValueError, match="max_len"):
        srv.submit([1, 2, 3], 8)
    with pytest.raises(ValueError, match="mode"):
        serve.DecodeServer(ACCL(_mesh()), model, batch=1, max_len=8,
                           mode="speculative")


def test_decode_lint_requires_persistent_annotation(monkeypatch):
    """The fused step's cross-dispatch KV reads are admitted ONLY
    through the explicit persistent annotation: strip it and the linter
    rejects the recording (ACCL101 — reads wider than any in-sequence
    producer wrote), proving the waiver is scoped, not a lint hole."""
    monkeypatch.setattr(trf.DecodeBuffers, "persistent",
                        property(lambda self: ()))
    with pytest.raises(LintError):
        trf.make_decode_step_program(ACCL(_mesh()), CFG, _params_np(),
                                     batch=B, max_len=T)


def test_decode_dims_validation():
    with pytest.raises(ValueError):
        trf.decode_dims(CFG, 3, B, T)  # 3 does not divide heads/ff
    bad = dataclasses.replace(CFG, dtype="bfloat16")
    with pytest.raises(ValueError):
        trf.decode_dims(bad, WORLD, B, T)


# -- the SYNTH_LATENCY_MAX_COUNT register ------------------------------

_SEL_KW = dict(max_eager_size=DEFAULT_MAX_EAGER_SIZE,
               eager_rx_buf_size=DEFAULT_EAGER_RX_BUF_SIZE)


def _lat_worlds():
    """Worlds with committed latency-grid entries."""
    return sorted({e.spec.world for e in synthesis.library().values()
                   if e.spec.grid == "lat"})


def test_latency_register_round_trip_through_exchange_memory():
    """The register survives the facade -> exchange-memory -> device
    tuning() round trip, and inside its window the full facade plan
    resolution returns a latency-grid entry."""
    from accl_tpu.device.tpu_device import TPUDevice

    world = WORLD
    dev = TPUDevice(_mesh(world))
    accl = ACCL(device=dev)
    accl.configure_tuning_parameters(
        TuningParams(synth_latency_max_count=16384))
    assert dev.tuning().synth_latency_max_count == 16384
    count = 2048  # 8 KiB: inside the window
    plan, _, _ = dev._resolve_step(
        CallOptions(scenario=Operation.allreduce, count=count,
                    function=int(ReduceFunction.SUM),
                    data_type=from_numpy_dtype(np.dtype(np.float32))),
        dev._comm_ctx(0))
    assert plan.algorithm == Algorithm.SYNTHESIZED
    assert synthesis.entry_for_key(plan.synth_key).spec.grid == "lat"


def test_register_zero_selection_bit_for_bit_unchanged():
    """Register 0 (the default) must leave selection IDENTICAL to the
    pre-register behavior at every latency-grid size and beyond — the
    established compatibility pin for new crossover registers — and in
    particular must never pick a latency-grid entry."""
    explicit_zero = TuningParams(synth_latency_max_count=0)
    for world in _lat_worlds():
        for nbytes in (*synthesis.SIZE_GRID_LAT, 128 * 1024, 1 << 20):
            count = nbytes // 4
            a = select_algorithm(Operation.allreduce, count, 4, world,
                                 tuning=TuningParams.default(), **_SEL_KW)
            b = select_algorithm(Operation.allreduce, count, 4, world,
                                 tuning=explicit_zero, **_SEL_KW)
            assert a == b, f"w{world}/{nbytes}B: register-0 drifted"
            if a.algorithm == Algorithm.SYNTHESIZED:
                spec = synthesis.entry_for_key(a.synth_key).spec
                assert spec.grid != "lat", \
                    f"w{world}/{nbytes}B: lat entry leaked past register 0"


def test_latency_register_window_scopes_selection():
    """With the register open, selection changes ONLY inside the
    window: sizes above it match register-0 plans field-for-field."""
    reg = 16384
    lat = TuningParams(synth_latency_max_count=reg)
    for world in _lat_worlds():
        hits = 0
        for nbytes in (*synthesis.SIZE_GRID_LAT, 128 * 1024):
            count = nbytes // 4
            a = select_algorithm(Operation.allreduce, count, 4, world,
                                 tuning=lat, **_SEL_KW)
            b = select_algorithm(Operation.allreduce, count, 4, world,
                                 tuning=TuningParams.default(), **_SEL_KW)
            if nbytes > reg:
                assert a == b, \
                    f"w{world}/{nbytes}B: selection moved OUTSIDE window"
            elif a.algorithm == Algorithm.SYNTHESIZED and \
                    synthesis.entry_for_key(a.synth_key).spec.grid == "lat":
                hits += 1
        assert hits > 0, f"w{world}: window admitted no lat entry"
