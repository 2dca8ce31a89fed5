"""Wire-fault injection and framing/FIFO fuzz for the native runtime.

The reference validates its datapath by driving the DUT through a
bus-functional model that can delay or corrupt streams (SURVEY.md §4,
test/model simulator/emulator harnesses); the TPU-native analog is the
runtime's ACCL_RT_FAULT_* levers (native/src/runtime.cpp): the first
multi-segment eager message can delay or lose its final segment, which
is exactly the stimulus the r4 protocol machinery — message-boundary
framing, orphan-segment drain, posted-order FIFO tickets — exists to
survive. These tests drive the state space the single-scenario r4 tests
pinned: mid-message recv death with live traffic after it, ticketed
TAG_ANY pairing under concurrency, mixed jumbo/normal segment
interleave on shared links, and the datagram message-ceiling split.
"""

import os

import numpy as np
import pytest

from accl_tpu import ACCLError, CallOptions, ReduceFunction, TAG_ANY
from accl_tpu.constants import CfgFunc, Operation, from_numpy_dtype
from accl_tpu.device.emu_device import EmuWorld

RNG = np.random.default_rng(77)
F32 = from_numpy_dtype(np.dtype(np.float32))


@pytest.fixture
def fault_env(monkeypatch):
    """Set/clear the fault levers around one test (env is read at
    runtime creation)."""
    def set_fault(**kv):
        for k, v in kv.items():
            monkeypatch.setenv(k, str(v))
    yield set_fault


@pytest.mark.parametrize("transport", ["tcp", "local"])
@pytest.mark.parametrize("segs,m2_count", [(3, 40), (6, 700), (9, 64)])
def test_orphan_drain_after_mid_message_death(fault_env, segs, m2_count,
                                              transport):
    """A recv that dies mid-message (slow tail outlives its deadline)
    must arm the orphan drain; when the stale tail finally lands, a
    later recv on the same link discards it and receives the NEXT
    message intact (runtime.cpp drain_orphans_locked). Parametrized
    over segment counts, follow-up sizes, and the session vs
    intra-process transports (the fault lever delivers the delayed tail
    through whichever wire is active)."""
    fault_env(ACCL_RT_FAULT_DELAY_TAIL_MS=700)
    rx_buf = 256
    count = (segs * rx_buf) // 4  # exactly `segs` wire segments
    m1 = RNG.standard_normal(count).astype(np.float32)
    m2 = RNG.standard_normal(m2_count).astype(np.float32)
    w = EmuWorld(2, max_eager=1 << 20, rx_buf_bytes=rx_buf,
                 transport=transport)
    try:
        def body(rank, i):
            import time

            if i == 1:
                rank.send(m1.copy(), count, dst=0, tag=5)  # tail delayed
                time.sleep(1.0)  # let the tail land before M2 (order)
                rank.send(m2.copy(), m2_count, dst=0, tag=5)
                return None
            rank.call(CallOptions(scenario=Operation.config,
                                  function=int(CfgFunc.set_timeout),
                                  count=300))
            buf = np.zeros(count, np.float32)
            h = rank.start(CallOptions(scenario=Operation.recv, count=count,
                                       root_src_dst=1, tag=5,
                                       data_type=F32), res=buf)
            with pytest.raises(ACCLError, match="RECEIVE_TIMEOUT"):
                rank.wait(h)  # died mid-message: some segments consumed
            rank.call(CallOptions(scenario=Operation.config,
                                  function=int(CfgFunc.set_timeout),
                                  count=5000))
            out = np.zeros(m2_count, np.float32)
            rank.recv(out, m2_count, src=1, tag=5)
            return out

        res = w.run(body)
    finally:
        w.close()
    np.testing.assert_allclose(res[0], m2, rtol=0)


def test_landing_revocation_mid_message(fault_env):
    """Direct-placement landing + mid-message death: a strict collective
    recv big enough to register a landing (>= 64 KB) loses its delayed
    tail past the deadline. The revocation path must drop the landing
    without freeing the buffer under the rx thread, arm the orphan
    drain for the stale tail, and leave the link usable for the next
    collective on it."""
    fault_env(ACCL_RT_FAULT_DELAY_TAIL_MS=700)
    count = 400_000  # 1.6 MB: two jumbo segments, tail delayed
    m2_count = 5000
    x1 = RNG.standard_normal(count).astype(np.float32)
    x2 = RNG.standard_normal(m2_count).astype(np.float32)
    w = EmuWorld(2, max_eager=1 << 24, rx_buf_bytes=4096)
    try:
        def body(rank, i):
            import time

            if i == 1:
                rank.bcast(x1.copy(), count, root=1)  # tail delayed
                time.sleep(1.0)  # tail lands (as orphan) before M2
                rank.bcast(x2.copy(), m2_count, root=1)
                return None
            rank.call(CallOptions(scenario=Operation.config,
                                  function=int(CfgFunc.set_timeout),
                                  count=300))
            buf = np.zeros(count, np.float32)
            h = rank.start(CallOptions(scenario=Operation.bcast,
                                       count=count, root_src_dst=1,
                                       data_type=F32), op0=buf)
            with pytest.raises(ACCLError, match="RECEIVE_TIMEOUT"):
                rank.wait(h)
            rank.call(CallOptions(scenario=Operation.config,
                                  function=int(CfgFunc.set_timeout),
                                  count=5000))
            out = np.zeros(m2_count, np.float32)
            rank.call(CallOptions(scenario=Operation.bcast, count=m2_count,
                                  root_src_dst=1, data_type=F32), op0=out)
            return out

        res = w.run(body)
    finally:
        w.close()
    np.testing.assert_allclose(res[0], x2, rtol=0)


def test_udp_lost_tail_is_a_clean_timeout(fault_env):
    """Datagram loss of a message's final segment: the seqn gap must
    surface as RECEIVE_TIMEOUT on the consumer — never as corrupt data
    or a misleading sequencing error (the datagram POE treats a gap as
    possibly-in-flight until the deadline)."""
    fault_env(ACCL_RT_FAULT_DROP_TAIL=1)
    rx_buf = 256
    count = (4 * rx_buf) // 4
    w = EmuWorld(2, max_eager=1 << 20, rx_buf_bytes=rx_buf,
                 transport="udp", max_rndzv=1 << 20)
    try:
        def body(rank, i):
            if i == 1:
                rank.send(np.ones(count, np.float32), count, dst=0, tag=3)
                return None
            rank.call(CallOptions(scenario=Operation.config,
                                  function=int(CfgFunc.set_timeout),
                                  count=400))
            buf = np.zeros(count, np.float32)
            h = rank.start(CallOptions(scenario=Operation.recv, count=count,
                                       root_src_dst=1, tag=3,
                                       data_type=F32), res=buf)
            with pytest.raises(ACCLError, match="RECEIVE_TIMEOUT"):
                rank.wait(h)
            return True

        res = w.run(body)
        assert res[0] is True
    finally:
        w.close()


TICKET_CONFIGS = 6


@pytest.mark.parametrize("seed", range(TICKET_CONFIGS))
@pytest.mark.parametrize("transport", ["tcp", "udp"])
def test_ticketed_tag_any_fifo_under_concurrency(seed, transport):
    """N TAG_ANY recvs posted async BEFORE any message arrives all park
    with tickets; when the sends fire, pairing must follow posted order
    within each eligible (length-matched) class — the posted-order FIFO
    contract, fuzzed over message multisets that include same-length
    duplicates (where only the ticket order decides)."""
    rng = np.random.default_rng(900 + seed)
    n_msgs = int(rng.integers(3, 7))
    # sizes drawn from a small pool so duplicates are common
    pool = [32, 32, 200, 1024]
    counts = [int(rng.choice(pool)) for _ in range(n_msgs)]
    payloads = [rng.standard_normal(c).astype(np.float32) for c in counts]
    w = EmuWorld(2, max_eager=4096, rx_buf_bytes=1024, transport=transport)
    try:
        def body(rank, i):
            import time

            if i == 1:
                time.sleep(0.3)  # recvs post (and ticket) first
                for p, c in zip(payloads, counts):
                    rank.send(p.copy(), c, dst=0, tag=TAG_ANY)
                return None
            outs = [np.zeros(c, np.float32) for c in counts]
            handles = [rank.start(
                CallOptions(scenario=Operation.recv, count=c,
                            root_src_dst=1, tag=TAG_ANY, data_type=F32),
                res=o) for c, o in zip(counts, outs)]
            for h in handles:
                rank.wait(h)
            return outs

        res = w.run(body)
    finally:
        w.close()
    # FIFO within each length class: the k-th posted recv of length c
    # gets the k-th sent message of length c
    by_len = {}
    for c, p in zip(counts, payloads):
        by_len.setdefault(c, []).append(p)
    taken = {c: 0 for c in by_len}
    for c, out in zip(counts, res[0]):
        expect = by_len[c][taken[c]]
        taken[c] += 1
        np.testing.assert_allclose(out, expect, rtol=0,
                                   err_msg=f"seed {seed} len {c}")


@pytest.mark.parametrize("seed", range(4))
def test_mixed_jumbo_and_normal_segments_share_links(seed):
    """A streamed collective (whole-chunk jumbo segments) interleaved
    with small tagged p2p messages (rx-buf segments) on the SAME links:
    message-boundary framing must keep both intact. The collective is
    issued async so its chunks and the p2p traffic genuinely interleave
    in the sequencer."""
    rng = np.random.default_rng(1300 + seed)
    world = 4
    count = int(rng.integers(20_000, 120_000))  # rendezvous-size chunks
    n_small = int(rng.integers(2, 5))
    small_counts = [int(rng.integers(1, 900)) for _ in range(n_small)]
    xs = rng.standard_normal((world, count)).astype(np.float32)
    smalls = [rng.standard_normal(c).astype(np.float32)
              for c in small_counts]
    w = EmuWorld(world)
    try:
        def body(rank, i):
            out = np.zeros(count, np.float32)
            h = rank.start(
                CallOptions(scenario=Operation.allreduce, count=count,
                            function=int(ReduceFunction.SUM),
                            data_type=F32), op0=xs[i].copy(), res=out)
            # p2p to the next rank with a distinct tag while the
            # collective's jumbo chunks stream on the same links
            nxt, prv = (i + 1) % world, (i - 1) % world
            got = []
            for k, (c, p) in enumerate(zip(small_counts, smalls)):
                sh = rank.start(
                    CallOptions(scenario=Operation.send, count=c,
                                root_src_dst=nxt, tag=0x7000 + k,
                                data_type=F32), op0=p.copy())
                rb = np.zeros(c, np.float32)
                rh = rank.start(
                    CallOptions(scenario=Operation.recv, count=c,
                                root_src_dst=prv, tag=0x7000 + k,
                                data_type=F32), res=rb)
                rank.wait(sh)
                rank.wait(rh)
                got.append(rb)
            rank.wait(h)
            return out, got

        res = w.run(body)
    finally:
        w.close()
    for out, got in res:
        np.testing.assert_allclose(out, xs.sum(0), rtol=1e-4, atol=1e-4)
        for rb, p in zip(got, smalls):
            np.testing.assert_allclose(rb, p, rtol=0)


@pytest.mark.parametrize("seed", range(5))
def test_udp_ceiling_split_fuzz(seed):
    """Datagram-transport collectives around the message-ceiling
    boundary: counts at cap/4 +- 1 elements and far beyond, across
    collectives — every chunk stream must split under max_rndzv and
    reassemble exactly (the r4 advisory regression, fuzzed)."""
    rng = np.random.default_rng(1700 + seed)
    cap = int(rng.choice([4096, 65536]))
    world = int(rng.choice([2, 4]))
    cap_elems = cap // 4
    count = int(rng.choice([cap_elems - 1, cap_elems, cap_elems + 1,
                            cap_elems * world + 3, cap_elems * 7]))
    op = str(rng.choice(["allreduce", "allgather", "alltoall"]))
    xs = rng.standard_normal((world, count * (world if op == "alltoall"
                                              else 1))).astype(np.float32)
    w = EmuWorld(world, transport="udp", max_rndzv=cap)
    try:
        def body(rank, i):
            if op == "allreduce":
                out = np.zeros(count, np.float32)
                rank.allreduce(xs[i].copy(), out, count, ReduceFunction.SUM)
            elif op == "allgather":
                out = np.zeros(count * world, np.float32)
                rank.allgather(xs[i].copy(), out, count)
            else:
                out = np.zeros(count * world, np.float32)
                rank.alltoall(xs[i].copy(), out, count)
            return out

        res = w.run(body)
    finally:
        w.close()
    for r, out in enumerate(res):
        if op == "allreduce":
            np.testing.assert_allclose(out, xs.sum(0), rtol=1e-4,
                                       atol=1e-4)
        elif op == "allgather":
            np.testing.assert_allclose(out, xs.ravel(), rtol=0)
        else:
            expect = xs.reshape(world, world, count)[:, r, :].ravel()
            np.testing.assert_allclose(out, expect, rtol=0)


# ---------------------------------------------------------------------------
# Reliable wire: CRC32C integrity + selective retransmit under the
# seeded ACCL_RT_FAULT_{LOSS,CORRUPT,DUP,REORDER}_PCT chaos model
# (runtime.cpp reliability sublayer). The transport must absorb every
# injected transient BELOW the resilience layer: answers bitwise vs the
# no-fault oracle, repair counters strictly positive, and NO call ever
# surfacing a timeout (zero reconfigurations: nothing for the recovery
# loop to even see).
# ---------------------------------------------------------------------------


def _wire_totals(world_obj):
    agg: dict = {}
    for r in world_obj.ranks:
        if r is None:
            continue
        for k, v in r.wire_stats().items():
            agg[k] = agg.get(k, 0) + v
    return agg


CHAOS_SEEDS = 30


@pytest.mark.parametrize("seed", range(CHAOS_SEEDS))
def test_chaos_fuzz_transport_absorbs_seeded_faults(fault_env, seed):
    """30-seed chaos fuzz over all three POEs (the seed picks the
    transport, so the session TCP wire, the sessionless datagram wire,
    and the in-process registry each absorb a third of the seeds):
    random seeded loss/corrupt/dup/reorder rates over a p2p frame storm
    (rx-buf-sized segments, so the fault model gets hundreds of draws)
    plus collective dispatches. Every answer
    must be BITWISE vs the no-fault oracle (integer payloads), the
    retransmit counters strictly positive (the faults provably fired
    and were provably repaired), and zero calls may surface an error —
    the transport absorbs the chaos below the resilience layer, so no
    retry budget is consumed and no reconfiguration can trigger."""
    rng = np.random.default_rng(4200 + seed)
    # floors keep expected injection counts high enough that the
    # strictly-positive counter assertions are deterministic in
    # practice (hundreds of frames * >=1.5% loss)
    loss = 1.5 + float(rng.uniform(0, 1.5))
    corrupt = 1.0 + float(rng.uniform(0, 1.0))
    dup = 0.5 + float(rng.uniform(0, 1.0))
    reorder = float(rng.uniform(0, 1.5))
    transport = ("tcp", "udp", "local")[seed % 3]
    world = int(rng.choice([2, 4]))
    op = str(rng.choice(["allreduce", "allgather", "alltoall"]))
    fault_env(ACCL_RT_FAULT_LOSS_PCT=loss, ACCL_RT_FAULT_CORRUPT_PCT=corrupt,
              ACCL_RT_FAULT_DUP_PCT=dup, ACCL_RT_FAULT_REORDER_PCT=reorder,
              ACCL_RT_FAULT_SEED=1000 + seed)
    p2p_count = 12288  # 48 KB -> 192 rx-buf frames per directed link
    coll_count = int(rng.integers(1000, 4000))
    p2p = rng.integers(-64, 64, size=(world, p2p_count)).astype(np.float32)
    xs = rng.integers(-32, 32, size=(world, coll_count * (
        world if op == "alltoall" else 1))).astype(np.float32)
    w = EmuWorld(world, max_eager=1 << 20, rx_buf_bytes=256,
                 transport=transport)
    try:
        def body(rank, i):
            # phase 1: p2p frame storm around the ring (many small
            # frames -> many fault-model draws)
            nxt, prv = (i + 1) % world, (i - 1) % world
            sh = rank.start(CallOptions(
                scenario=Operation.send, count=p2p_count,
                root_src_dst=nxt, tag=0x6100, data_type=F32),
                op0=p2p[i].copy())
            rb = np.zeros(p2p_count, np.float32)
            rh = rank.start(CallOptions(
                scenario=Operation.recv, count=p2p_count,
                root_src_dst=prv, tag=0x6100, data_type=F32), res=rb)
            rank.wait(sh)
            rank.wait(rh)
            # phase 2: collective dispatches
            if op == "allreduce":
                out = np.zeros(coll_count, np.float32)
                for _ in range(3):
                    rank.allreduce(xs[i].copy(), out, coll_count,
                                   ReduceFunction.SUM)
            else:
                out = np.zeros(coll_count * world, np.float32)
                for _ in range(3):
                    if op == "allgather":
                        rank.allgather(xs[i].copy(), out, coll_count)
                    else:
                        rank.alltoall(xs[i].copy(), out, coll_count)
            return rb, out

        res = w.run(body)
        agg = _wire_totals(w)
    finally:
        w.close()
    for i, (rb, out) in enumerate(res):
        np.testing.assert_array_equal(
            rb, p2p[(i - 1) % world],
            err_msg=f"seed {seed}: p2p payload not bitwise")
        if op == "allreduce":
            want = xs.sum(0)
        elif op == "allgather":
            want = xs.ravel()
        else:
            want = xs.reshape(world, world, coll_count)[:, i, :].ravel()
        np.testing.assert_array_equal(
            out, want, err_msg=f"seed {seed}: {op} not bitwise")
    # the faults provably fired ...
    assert agg["inj_loss"] > 0, f"seed {seed}: no loss drawn ({agg})"
    # ... and were provably repaired at the transport
    assert agg["retx_sent"] > 0, \
        f"seed {seed}: lost frames never retransmitted ({agg})"
    if agg["inj_corrupt"]:
        assert agg["crc_drops"] > 0, \
            f"seed {seed}: corrupt frames not caught by CRC ({agg})"
    if agg["inj_dup"]:
        assert agg["dup_drops"] > 0, \
            f"seed {seed}: duplicate frames not deduped ({agg})"


@pytest.mark.parametrize("transport", ["tcp", "udp"])
def test_chaos_kill_rank_control(fault_env, transport):
    """Seeded chaos PLUS the kill-rank control lever, on both socket
    POEs: mid-chaos the killed rank's wire goes dark after its call
    budget — the calls inside the budget still complete bitwise
    (repair keeps working right up to the kill), and the first call
    past it surfaces the timeout escalation on every rank instead of
    hanging or delivering junk."""
    fault_env(ACCL_RT_FAULT_LOSS_PCT=2, ACCL_RT_FAULT_CORRUPT_PCT=1,
              ACCL_RT_FAULT_SEED=31, ACCL_RT_FAULT_KILL_RANK=1,
              ACCL_RT_FAULT_KILL_AFTER=2)
    n = 2048
    xs = RNG.integers(-64, 64, size=(2, n)).astype(np.float32)
    w = EmuWorld(2, max_eager=1 << 20, rx_buf_bytes=512,
                 transport=transport)
    try:
        def body(rank, i):
            rank.call(CallOptions(scenario=Operation.config,
                                  function=int(CfgFunc.set_timeout),
                                  count=800))
            outs = []
            for _k in range(2):  # inside the kill budget: bitwise
                out = np.zeros(n, np.float32)
                rank.allreduce(xs[i].copy(), out, n, ReduceFunction.SUM)
                outs.append(out)
            try:  # past the budget: rank 1 is dark
                out = np.zeros(n, np.float32)
                rank.allreduce(xs[i].copy(), out, n, ReduceFunction.SUM)
                return outs, "completed"
            except ACCLError as e:
                return outs, e.retcode

        res = w.run(body)
    finally:
        w.close()
    for outs, verdict in res:
        for out in outs:
            np.testing.assert_array_equal(out, xs.sum(0))
        assert verdict != "completed" and verdict & 0x800


def test_two_lanes_break_head_of_line_blocking(fault_env):
    """ACCL_RT_LANES=2: a 16 MiB jumbo eager message and a 1 KiB
    message to the SAME peer ride separate per-peer lanes (separate
    seqn streams over separate links), so the receiver completes the
    small recv while the jumbo is still unconsumed — out-of-order
    completion across lanes, which the single-lane wire forbids by
    construction (see the companion HOL test below)."""
    fault_env(ACCL_RT_LANES=2)
    jumbo_n = (16 << 20) // 4
    small_n = 1024 // 4
    jumbo = RNG.integers(-100, 100, size=jumbo_n).astype(np.int32)
    small = RNG.integers(-100, 100, size=small_n).astype(np.int32)
    w = EmuWorld(2, max_eager=32 << 20, max_rndzv=64 << 20)
    try:
        def body(rank, i):
            if i == 0:
                # jumbo FIRST: on one lane it would occupy the link head
                rank.send(jumbo.copy(), jumbo_n, dst=1, tag=7)
                rank.send(small.copy(), small_n, dst=1, tag=9)
                return None
            # the small recv is the ONLY posted recv: it must complete
            # even though the jumbo ahead of it is entirely unconsumed
            got_small = np.zeros(small_n, np.int32)
            rank.recv(got_small, small_n, src=0, tag=9)
            got_jumbo = np.zeros(jumbo_n, np.int32)
            rank.recv(got_jumbo, jumbo_n, src=0, tag=7)
            return got_small, got_jumbo

        res = w.run(body)
    finally:
        w.close()
    got_small, got_jumbo = res[1]
    np.testing.assert_array_equal(got_small, small)
    np.testing.assert_array_equal(got_jumbo, jumbo)


def test_single_lane_head_of_line_blocks(fault_env):
    """The single-lane control for the test above: with the default
    one-lane wire the jumbo at the stream head DOES head-of-line-block
    the small recv (it times out), and the stream drains in wire order
    afterwards — proving the lanes, not some matching quirk, are what
    reorder completion."""
    jumbo_n = (16 << 20) // 4
    small_n = 1024 // 4
    jumbo = RNG.integers(-100, 100, size=jumbo_n).astype(np.int32)
    small = RNG.integers(-100, 100, size=small_n).astype(np.int32)
    w = EmuWorld(2, max_eager=32 << 20, max_rndzv=64 << 20)
    try:
        def body(rank, i):
            if i == 0:
                rank.send(jumbo.copy(), jumbo_n, dst=1, tag=7)
                rank.send(small.copy(), small_n, dst=1, tag=9)
                return None
            rank.call(CallOptions(scenario=Operation.config,
                                  function=int(CfgFunc.set_timeout),
                                  count=500))
            got_small = np.zeros(small_n, np.int32)
            try:
                rank.recv(got_small, small_n, src=0, tag=9)
                blocked = False
            except ACCLError as e:
                blocked = bool(e.retcode & 0x800)
            # drain in wire order: jumbo, then the small message
            rank.call(CallOptions(scenario=Operation.config,
                                  function=int(CfgFunc.set_timeout),
                                  count=5000))
            got_jumbo = np.zeros(jumbo_n, np.int32)
            rank.recv(got_jumbo, jumbo_n, src=0, tag=7)
            rank.recv(got_small, small_n, src=0, tag=9)
            return blocked, got_small, got_jumbo

        res = w.run(body)
    finally:
        w.close()
    blocked, got_small, got_jumbo = res[1]
    assert blocked, "single-lane wire should HOL-block the small recv"
    np.testing.assert_array_equal(got_small, small)
    np.testing.assert_array_equal(got_jumbo, jumbo)


def test_stats2_versioned_counter_surface():
    """accl_rt_get_stats2 keeps the classic 5 sequencer counters as its
    prefix (the ABI-stable accl_rt_get_stats view), carries the wire
    counters behind them, and EmuRank.wire_stats renders every known
    field; TPUDevice's mirror carries the same schema."""
    from accl_tpu.device.emu_device import STATS2_FIELDS
    from accl_tpu.telemetry.export import WIRE_FAULT_KEYS

    w = EmuWorld(2, transport="local")
    try:
        def body(rank, i):
            out = np.zeros(64, np.float32)
            rank.allreduce(np.ones(64, np.float32), out, 64,
                           ReduceFunction.SUM)

        w.run(body)
        ws = w.ranks[0].wire_stats()
        seq = w.ranks[0].sequencer_stats()
    finally:
        w.close()
    assert tuple(ws) == STATS2_FIELDS
    assert set(seq) == set(STATS2_FIELDS[:5])
    assert set(WIRE_FAULT_KEYS) < set(STATS2_FIELDS)
    assert ws["passes"] > 0 and ws["tx_frames"] > 0
    assert all(isinstance(v, int) for v in ws.values())


@pytest.mark.parametrize("wire", ["vectored", "legacy"])
def test_tcp_transmit_shape(fault_env, wire):
    """A multi-frame eager message on the TCP wire lands bitwise either
    way, and the sender's stats2 counters show which transmit path ran:
    the vectored wire (default) gathers many frames into one writev
    (tx_batched > 0, fewer syscalls than frames); ACCL_RT_WIRE_LEGACY=1
    sends frame by frame and batches nothing."""
    fault_env(ACCL_RT_RELY=0)
    if wire == "legacy":
        fault_env(ACCL_RT_WIRE_LEGACY=1)
    n = (256 << 10) // 4
    msg = RNG.integers(-100, 100, size=n).astype(np.int32)
    w = EmuWorld(2, transport="tcp", max_eager=1 << 20, rx_buf_bytes=4096)
    try:
        def body(rank, i):
            if i == 0:
                rank.send(msg.copy(), n, dst=1, tag=21)
                return None
            out = np.zeros(n, np.int32)
            rank.recv(out, n, src=0, tag=21)
            return out

        res = w.run(body)
        ws = w.ranks[0].wire_stats()
    finally:
        w.close()
    np.testing.assert_array_equal(res[1], msg)
    assert ws["tx_frames"] > 1
    if wire == "vectored":
        assert ws["tx_batched"] > 0
        assert ws["tx_syscalls"] < ws["tx_frames"]
    else:
        assert ws["tx_batched"] == 0
        assert ws["tx_syscalls"] >= ws["tx_frames"]


def test_corrupt_frames_counted_dropped_and_repaired(fault_env):
    """A heavy corrupt rate: every flipped frame must be caught by the
    CRC (counted, dropped, never landed) and repaired by the nack
    path — the payload arrives bitwise anyway."""
    fault_env(ACCL_RT_FAULT_CORRUPT_PCT=40, ACCL_RT_FAULT_SEED=5)
    msg = RNG.integers(-100, 100, size=8192).astype(np.float32)
    w = EmuWorld(2, max_eager=1 << 20, rx_buf_bytes=256, transport="local")
    try:
        def body(rank, i):
            if i == 1:
                rank.send(msg.copy(), len(msg), dst=0, tag=9)
                return None
            out = np.zeros(len(msg), np.float32)
            rank.recv(out, len(msg), src=1, tag=9)
            return out

        res = w.run(body)
        agg = _wire_totals(w)
    finally:
        w.close()
    np.testing.assert_array_equal(res[0], msg)
    assert agg["inj_corrupt"] > 0
    assert agg["crc_drops"] >= agg["inj_corrupt"] > 0
    assert agg["retx_sent"] > 0


def test_duplicate_frames_land_idempotently(fault_env):
    """100% dup: every data frame is delivered twice; the dedup path
    must drop every second copy and the message must assemble exactly
    once."""
    fault_env(ACCL_RT_FAULT_DUP_PCT=100, ACCL_RT_FAULT_SEED=6)
    msg = RNG.integers(-100, 100, size=4096).astype(np.float32)
    w = EmuWorld(2, max_eager=1 << 20, rx_buf_bytes=256, transport="local")
    try:
        def body(rank, i):
            if i == 1:
                rank.send(msg.copy(), len(msg), dst=0, tag=2)
                return None
            out = np.zeros(len(msg), np.float32)
            rank.recv(out, len(msg), src=1, tag=2)
            return out

        res = w.run(body)
        agg = _wire_totals(w)
    finally:
        w.close()
    np.testing.assert_array_equal(res[0], msg)
    assert agg["inj_dup"] > 0
    assert agg["dup_drops"] >= agg["inj_dup"]


def test_rely_off_is_the_legacy_wire(fault_env):
    """ACCL_RT_RELY=0: no CRC, no acks, no retransmit machinery — the
    pre-reliability wire, still fully functional on a clean link (the
    A/B baseline the chaos gate reports against)."""
    fault_env(ACCL_RT_RELY=0)
    w = EmuWorld(2, max_eager=1 << 20, rx_buf_bytes=256, transport="local")
    try:
        def body(rank, i):
            out = np.zeros(512, np.float32)
            rank.allreduce(np.full(512, i + 1, np.float32), out, 512,
                           ReduceFunction.SUM)
            return out

        res = w.run(body)
        agg = _wire_totals(w)
    finally:
        w.close()
    for out in res:
        np.testing.assert_array_equal(out, np.full(512, 3, np.float32))
    assert agg["tx_frames"] > 0  # volume still counted
    for k in ("crc_drops", "retx_sent", "nack_sent", "ack_sent",
              "rely_ns"):
        assert agg[k] == 0, f"{k} active with rely off"


if os.environ.get("ACCL_RT_FAULT_DELAY_TAIL_MS") or \
        os.environ.get("ACCL_RT_FAULT_DROP_TAIL"):  # pragma: no cover
    raise RuntimeError("fault levers must not leak into the environment")
