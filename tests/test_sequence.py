"""Device-resident call sequences: record a batch, dispatch ONE program.

Pins the sequence layer's contract (accl_tpu/sequencer/sequence.py):
fused results bitwise-identical to the same calls issued eagerly, one
compiled program cached under the composite signature (a second identical
batch compiles nothing), stream endpoints spliced between stages, and the
slot-overlapped segmented pallas ring agreeing with the serialized
baseline.
"""

import numpy as np
import pytest

import jax
from accl_tpu import (
    CallOptions,
    DataType,
    Operation,
    ReduceFunction,
    SequenceDescriptor,
)
from accl_tpu.accl import ACCL

RNG = np.random.default_rng(77)


@pytest.fixture()
def accl4(mesh4):
    return ACCL(mesh4)


def _mk(accl, n, data=None):
    return accl.create_buffer(n, data=data)


def test_sequence_matches_eager_bitwise(accl4):
    """reduce_scatter -> allgather -> bcast recorded as one batch must be
    bitwise-identical to the same facade calls issued back to back."""
    world, n = 4, 64
    chunk = n // world
    x = RNG.standard_normal((world, n)).astype(np.float32)

    a1, b1, c1 = _mk(accl4, n, x), _mk(accl4, chunk), _mk(accl4, n)
    a2, b2, c2 = _mk(accl4, n, x), _mk(accl4, chunk), _mk(accl4, n)

    accl4.reduce_scatter(a1, b1, chunk, ReduceFunction.SUM)
    accl4.allgather(b1, c1, chunk)
    accl4.bcast(c1, n, 2)

    with accl4.sequence() as seq:
        seq.reduce_scatter(a2, b2, chunk, ReduceFunction.SUM)
        seq.allgather(b2, c2, chunk)
        seq.bcast(c2, n, 2)

    np.testing.assert_array_equal(b1.host, b2.host)
    np.testing.assert_array_equal(c1.host, c2.host)
    # and against the oracle
    np.testing.assert_allclose(c2.host, np.tile(x.sum(0), (world, 1)),
                               rtol=1e-4, atol=1e-4)


def test_sequence_one_dispatch_and_chaining(accl4):
    """The request reports one dispatch covering all steps; recorder
    methods chain fluently."""
    n = 32
    a, b = _mk(accl4, n, RNG.standard_normal((4, n)).astype(np.float32)), \
        _mk(accl4, n)
    req = (accl4.sequence()
           .allreduce(a, b, n, ReduceFunction.SUM)
           .bcast(b, n, 0)
           .run())
    assert req.num_dispatches == 1
    assert req.num_steps == 2
    assert len(req.plans) == 2
    assert accl4.get_duration_ns() >= 0


def test_sequence_cache_hit_compiles_nothing(accl4, monkeypatch):
    """A second identical batch (same shapes + dataflow, ANY buffers) must
    hit the composite-signature cache: no new cache entry, no new trace."""
    n = 48
    x = RNG.standard_normal((4, n)).astype(np.float32)
    a, b = _mk(accl4, n, x), _mk(accl4, n)

    with accl4.sequence() as s:
        s.allreduce(a, b, n, ReduceFunction.SUM)
        s.bcast(b, n, 1)

    compiler = accl4.cclo.compiler
    n_entries = len(compiler._cache)
    builds = []
    monkeypatch.setattr(
        type(compiler), "_finalize_sequence",
        lambda self, *a, **k: builds.append(1))

    # same buffers
    with accl4.sequence() as s:
        s.allreduce(a, b, n, ReduceFunction.SUM)
        s.bcast(b, n, 1)
    # DIFFERENT buffers, same shapes/dataflow: canonical renaming in the
    # composite signature must still hit
    a3, b3 = _mk(accl4, n, x), _mk(accl4, n)
    with accl4.sequence() as s:
        s.allreduce(a3, b3, n, ReduceFunction.SUM)
        s.bcast(b3, n, 1)

    assert builds == []
    assert len(compiler._cache) == n_entries


def test_sequence_streams_spliced(accl4):
    """Producer/consumer endpoints ride sequence steps exactly as they do
    eager streamed collectives."""
    import jax.numpy as jnp

    n = 16
    world = 4
    payload = np.arange(n, dtype=np.float32)
    accl4.register_stream_producer(5, lambda: jnp.asarray(payload))
    accl4.register_stream_consumer(6, lambda x: x * 2.0)
    a, b = _mk(accl4, n), _mk(accl4, n)

    with accl4.sequence() as s:
        s.bcast(a, n, 0, op0_stream=5)          # operand from producer
        s.allreduce(a, b, n, ReduceFunction.SUM, res_stream=6)

    np.testing.assert_allclose(a.host, np.tile(payload, (world, 1)),
                               rtol=1e-6)
    np.testing.assert_allclose(b.host, np.tile(payload * world * 2, (world, 1)),
                               rtol=1e-5)


def test_sequence_combine_and_copy_ride_along(accl4):
    """Local primitives (copy/combine) fuse into the same program."""
    n = 24
    x = RNG.standard_normal((4, n)).astype(np.float32)
    y = RNG.standard_normal((4, n)).astype(np.float32)
    a, b, c, d = _mk(accl4, n, x), _mk(accl4, n, y), _mk(accl4, n), \
        _mk(accl4, n)

    with accl4.sequence() as s:
        s.combine(n, ReduceFunction.SUM, a, b, c)
        s.allreduce(c, d, n, ReduceFunction.SUM)
        s.copy(d, c, n)

    np.testing.assert_allclose(c.host, np.tile((x + y).sum(0), (4, 1)),
                               rtol=1e-4, atol=1e-4)


def test_sequence_subcommunicator(accl4):
    """A batch on a split() communicator touches only member rows."""
    n = 16
    comm = accl4.split([0, 2])
    x = RNG.standard_normal((4, n)).astype(np.float32)
    a, b = _mk(accl4, n, x), _mk(accl4, n, np.zeros((4, n), np.float32))

    with accl4.sequence(comm=comm) as s:
        s.allreduce(a, b, n, ReduceFunction.SUM)
        s.bcast(b, n, 1)  # communicator-relative root -> global rank 2

    want = x[0] + x[2]
    np.testing.assert_allclose(b.host[0], want, rtol=1e-5)
    np.testing.assert_allclose(b.host[2], want, rtol=1e-5)
    np.testing.assert_array_equal(b.host[1], 0)
    np.testing.assert_array_equal(b.host[3], 0)


def test_sequence_run_async(accl4):
    n = 16
    x = RNG.standard_normal((4, n)).astype(np.float32)
    a, b = _mk(accl4, n, x), _mk(accl4, n)
    seq = accl4.sequence()
    seq.allreduce(a, b, n, ReduceFunction.SUM)
    req = seq.run(run_async=True)
    accl4.wait(req)
    np.testing.assert_allclose(b.host, np.tile(x.sum(0), (4, 1)),
                               rtol=1e-4, atol=1e-4)


def test_sequence_guards(accl4):
    n = 8
    a, b = _mk(accl4, n), _mk(accl4, n)
    seq = accl4.sequence()
    with pytest.raises(ValueError, match="empty sequence"):
        seq.run()
    seq.allreduce(a, b, n, ReduceFunction.SUM)
    seq.run()
    with pytest.raises(RuntimeError, match="already executed"):
        seq.allreduce(a, b, n, ReduceFunction.SUM)
    with pytest.raises(RuntimeError, match="already executed"):
        seq.run()
    # a failing body inside the context manager must not shadow the error
    with pytest.raises(ZeroDivisionError):
        with accl4.sequence() as s:
            s.allreduce(a, b, n, ReduceFunction.SUM)
            raise ZeroDivisionError


def test_sequence_descriptor_roundtrip_and_renaming():
    """Batched word-stream serialization round-trips; the composite
    signature canonically renames addresses (same wiring, different
    buffers -> same signature; different wiring -> different)."""
    def opts(addr0, addr2):
        return CallOptions(scenario=Operation.allreduce, count=8,
                           data_type=DataType.float32,
                           addr_0=addr0, addr_2=addr2)

    d1 = SequenceDescriptor((opts(0x100, 0x200), opts(0x200, 0x300)))
    d2 = SequenceDescriptor((opts(0x111, 0x222), opts(0x222, 0x333)))
    d3 = SequenceDescriptor((opts(0x111, 0x222), opts(0x111, 0x333)))
    assert d1.signature() == d2.signature()
    assert d1.signature() != d3.signature()

    # wire-form round-trip (data_type is a TPU-path extra, not serialized)
    rt = SequenceDescriptor.from_words(d1.to_words())
    assert rt.to_words() == d1.to_words()
    assert len(rt.steps) == 2 and rt.steps[0].addr_0 == 0x100

    with pytest.raises(ValueError, match="one communicator"):
        SequenceDescriptor((
            CallOptions(scenario=Operation.allreduce, count=8, comm_addr=0),
            CallOptions(scenario=Operation.allreduce, count=8,
                        comm_addr=0x1000),
        ))


def test_sequence_rejects_host_paired_ops(mesh4):
    """send/recv/barrier cannot ride a fused batch (device-level guard:
    the recorder has no method for them, so forge the descriptor)."""
    from accl_tpu.sequencer.sequence import SequencePlan
    from accl_tpu.sequencer.plan import Algorithm, Plan, Protocol

    opts = CallOptions(scenario=Operation.send, count=8,
                       data_type=DataType.float32, addr_0=1, addr_2=2)
    desc = SequenceDescriptor((opts,))
    plan = Plan(Protocol.EAGER, Algorithm.EAGER_SENDRECV, 8, 1)
    with pytest.raises(ValueError, match="cannot ride"):
        SequencePlan(desc, [plan], 4)


# ---------------------------------------------------------------------------
# segment-slot overlap (the de-serialized pallas ring substrate)
# ---------------------------------------------------------------------------


def test_segmented_apply_overlap_slots_correct():
    """overlap_slots pipelining must partition exactly like the serialized
    form: same segments, same ordering within a slot, correct tail."""
    import jax.numpy as jnp

    from accl_tpu.sequencer.schedules import segmented_apply

    calls = []

    def one_segment(seg, slot):
        calls.append((int(seg.shape[-1]), slot))
        return seg * 2.0

    x = jnp.arange(23, dtype=jnp.float32)
    out = segmented_apply(one_segment, x, 5, overlap_slots=2)
    np.testing.assert_allclose(np.asarray(out), np.arange(23) * 2.0)
    # 4 bulk segments of 5 alternating slots 0/1, then the 3-element tail
    assert calls == [(5, 0), (5, 1), (5, 0), (5, 1), (3, 0)]

    calls.clear()
    out = segmented_apply(one_segment, x, 64, overlap_slots=2)
    np.testing.assert_allclose(np.asarray(out), np.arange(23) * 2.0)
    assert calls == [(23, 0)]  # single segment: slot 0, no pipeline


def test_pallas_ring_overlap_matches_serialized(mesh4):
    """The slot-overlapped segmented pallas ring must agree with the
    serialized baseline (and the oracle) when the payload spans several
    kernel-resource segments."""
    from accl_tpu.sequencer.lowering import ScheduleCompiler
    from accl_tpu.sequencer import select_algorithm
    from accl_tpu import TuningParams

    world, count = 4, 4096  # several segments at the tiny cap below
    opts = CallOptions(scenario=Operation.allreduce, count=count,
                       function=int(ReduceFunction.SUM),
                       data_type=DataType.float32)
    plan = select_algorithm(Operation.allreduce, count, 4, world,
                            max_eager_size=1 << 30,
                            eager_rx_buf_size=1 << 22,
                            tuning=TuningParams.default())
    x = RNG.standard_normal((world, count)).astype(np.float32)
    outs = {}
    for overlap in (False, True):
        comp = ScheduleCompiler(mesh4, use_pallas_ring=True,
                                pallas_ring_overlap=overlap)
        comp.PALLAS_RING_MAX_BYTES = 4096  # force multi-segment
        outs[overlap] = np.asarray(comp.lower(opts, plan)(jax.device_put(x)))
    np.testing.assert_allclose(outs[True], np.tile(x.sum(0), (world, 1)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(outs[True], outs[False])


def test_ordered_after_depends_on_every_concat_segment():
    """The cross-step ring barrier must consume the WHOLE previous
    output: a segmented ring step's result is a concatenation, and a
    narrowed barrier operand (e.g. prev[:1]) lets XLA's slice-of-concat
    simplification drop the dependency on segments 2..N — two kernel
    instances sharing a collective_id slot would then run unordered."""
    import jax
    import jax.numpy as jnp

    from accl_tpu.sequencer.schedules import _ordered_after

    def f(x, a, b):
        prev = jnp.concatenate([a, b])  # a segmented step's output shape
        return _ordered_after(x, prev)

    jaxpr = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((4,), np.float32),
        jax.ShapeDtypeStruct((4,), np.float32),
        jax.ShapeDtypeStruct((4,), np.float32))
    concat_outs = {str(v) for e in jaxpr.jaxpr.eqns
                   if e.primitive.name == "concatenate" for v in e.outvars}
    barrier_ins = {str(v) for e in jaxpr.jaxpr.eqns
                   if e.primitive.name == "optimization_barrier"
                   for v in e.invars}
    assert concat_outs & barrier_ins, (
        "optimization_barrier no longer consumes the full concatenated "
        f"previous output\n{jaxpr}")


def test_splice_producer_preserves_placeholder_ordering():
    """A producer-spliced step's operand placeholder may carry the
    sequence builder's ring-ordering barrier; the splice must thread it
    into the traced graph, not drop the argument."""
    import jax
    import jax.numpy as jnp

    from accl_tpu.ops.streams import splice_producer

    wrapped = splice_producer(lambda d: d, lambda: jnp.ones(4), 4)
    jaxpr = jax.make_jaxpr(wrapped)(jax.ShapeDtypeStruct((4,), np.float32))
    placeholder = str(jaxpr.jaxpr.invars[0])
    used = {str(v) for e in jaxpr.jaxpr.eqns for v in e.invars}
    assert placeholder in used, (
        "splice_producer drops its placeholder operand — ordering edges "
        f"injected by the fused sequence path would vanish\n{jaxpr}")


def test_overlap_striped_sequence_jaxpr_structure():
    """Structural pin of the stripe-overlapped train-step batch: the
    fused program's allreduce step lowers to EXACTLY S independent
    RS+AG ring chains (S * 2*(world-1) ppermutes), and the serialized
    twin (overlap_serialize) threads S-1 order-only barriers between
    them while keeping the identical wire structure — the lowering
    seam bench --overlap-gate A/Bs."""
    import jax

    from accl_tpu.analysis.protocol import iter_ppermute_eqns
    from accl_tpu.constants import (DataType, Operation, ReduceFunction,
                                    StreamFlags)
    from accl_tpu.descriptor import CallOptions, SequenceDescriptor
    from accl_tpu.sequencer.lowering import AxisOnlyMesh, ScheduleCompiler
    from accl_tpu.sequencer.plan import Algorithm, Plan, Protocol
    from accl_tpu.sequencer.plan import select_algorithm
    from accl_tpu.sequencer.sequence import SequencePlan
    from accl_tpu.constants import (DEFAULT_EAGER_RX_BUF_SIZE,
                                    DEFAULT_MAX_EAGER_SIZE, TuningParams)

    world, n, S = 4, 4096, 4

    def consumer(x):
        return x * np.float32(0.5) + np.float32(1.0)

    def opts(scen, a0, a1, a2, streamed=False):
        return CallOptions(
            scenario=scen, count=n, function=int(ReduceFunction.SUM),
            data_type=DataType.float32,
            stream_flags=(StreamFlags.RES_STREAM if streamed
                          else StreamFlags.NO_STREAM),
            res_stream_id=31 if streamed else 0,
            addr_0=a0, addr_1=a1, addr_2=a2)

    desc = SequenceDescriptor((
        opts(Operation.copy, 1, 0, 2, streamed=True),
        opts(Operation.allreduce, 2, 0, 3),
        opts(Operation.combine, 1, 3, 4),
    ))
    kw = dict(max_eager_size=DEFAULT_MAX_EAGER_SIZE,
              eager_rx_buf_size=DEFAULT_EAGER_RX_BUF_SIZE,
              tuning=TuningParams.default())
    seg = -(-n // S)
    seg += (-seg) % world
    plans = [
        select_algorithm(Operation.copy, n, 4, world, **kw),
        Plan(Protocol.EAGER, Algorithm.EAGER_RING_RS_AG, seg,
             -(-n // seg), stripes=S),
        select_algorithm(Operation.combine, n, 4, world, **kw),
    ]
    counts = {}
    for serialize in (False, True):
        seq = SequencePlan(desc, plans, world,
                           endpoints=[(None, consumer), (None, None),
                                      (None, None)])
        comp = ScheduleCompiler(AxisOnlyMesh("ccl", world), "ccl",
                                use_pallas_ring=False,
                                overlap_serialize=serialize)
        body, n_in = seq.build(comp)
        avals = [jax.ShapeDtypeStruct((n,), np.float32)] * n_in
        closed = jax.make_jaxpr(body, axis_env=[("ccl", world)])(*avals)
        npp = len(list(iter_ppermute_eqns(closed)))
        nbar = sum(1 for e in closed.jaxpr.eqns
                   if e.primitive.name == "optimization_barrier")
        counts[serialize] = (npp, nbar)
    assert counts[False][0] == S * 2 * (world - 1)
    assert counts[True][0] == counts[False][0]
    # the serialized twin threads one order-only barrier per stripe
    # boundary on top of whatever the overlapped form carries
    assert counts[True][1] >= counts[False][1] + (S - 1)
