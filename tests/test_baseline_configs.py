"""The five BASELINE.md target configurations, as executable tests.

1. 2-rank fp32 send/recv ping-pong (emulator, CPU-only)
2. 8-rank ring allreduce, fp32 sweep
3. 16-rank allgather + reduce-scatter, bf16, segmented pipeline
4. 32-rank full collective suite (bcast/scatter/gather/reduce)
5. 64-rank kernel-streamed allreduce with fp16 compression

Configs 1-4 run on the native emulator (per-rank runtimes over sockets);
config 5 runs the compiled-schedule path on a 64-device virtual mesh in a
subprocess (device count is fixed at backend init, so it needs its own
interpreter).
"""

import pathlib
import subprocess
import sys
import textwrap

import numpy as np

import ml_dtypes

from accl_tpu import ReduceFunction
from accl_tpu.device.emu_device import EmuWorld

RNG = np.random.default_rng(99)


def test_config1_two_rank_pingpong_latency():
    """Config 1 + a latency figure from the call duration counter."""
    w = EmuWorld(2)
    try:
        durs = []

        def body(rank, i):
            from accl_tpu import Operation
            x = np.ones(256, np.float32)
            o = np.zeros(256, np.float32)
            for it in range(20):
                if i == 0:
                    rank.send(x, 256, dst=1, tag=it)
                    rank.recv(o, 256, src=1, tag=100 + it)
                else:
                    rank.recv(o, 256, src=0, tag=it)
                    rank.send(o, 256, dst=0, tag=100 + it)
            h = rank.start(rank._opts(Operation.send if i == 0 else Operation.recv,
                                      256, np.float32, 1 - i if i == 0 else 0,
                                      tag=999), op0=x if i == 0 else None,
                           res=None if i == 0 else o)
            rank.wait(h)
            return rank.duration_ns(h)

        durs = w.run(body)
        assert all(d > 0 for d in durs)
    finally:
        w.close()


def test_config2_eight_rank_allreduce_sweep():
    w = EmuWorld(8)
    try:
        for count in (256, 4096, 65536):  # 1KB .. 256KB fp32
            xs = RNG.standard_normal((8, count)).astype(np.float32)

            def body(rank, i, _xs=xs, _n=count):
                out = np.zeros(_n, np.float32)
                rank.allreduce(_xs[i].copy(), out, _n, ReduceFunction.SUM)
                return out

            for out in w.run(body):
                np.testing.assert_allclose(out, xs.sum(0), rtol=1e-3,
                                           atol=1e-3)
    finally:
        w.close()


def test_config3_sixteen_rank_bf16_ag_rs():
    """16 ranks, bf16, allgather + reduce-scatter through the segmented
    eager pipeline (payloads span multiple rx-buffer segments)."""
    w = EmuWorld(16)
    try:
        count = 640  # 1280 B bf16 -> multiple 1 KB eager segments
        xs = (RNG.standard_normal((16, count)) * 0.1).astype(ml_dtypes.bfloat16)

        def ag_body(rank, i):
            out = np.zeros(16 * count, ml_dtypes.bfloat16)
            rank.allgather(xs[i].copy(), out, count)
            return out

        for out in w.run(ag_body):
            np.testing.assert_array_equal(out, xs.reshape(-1))

        rs_in = (RNG.standard_normal((16, 16 * 32)) * 0.1).astype(
            ml_dtypes.bfloat16)

        def rs_body(rank, i):
            out = np.zeros(32, ml_dtypes.bfloat16)
            rank.reduce_scatter(rs_in[i].copy(), out, 32, ReduceFunction.SUM)
            return out

        res = w.run(rs_body)
        # bf16 ring accumulation: compare against an fp32 oracle loosely
        full = rs_in.astype(np.float32).sum(0)
        for i, out in enumerate(res):
            np.testing.assert_allclose(out.astype(np.float32),
                                       full[i * 32:(i + 1) * 32],
                                       rtol=0.1, atol=0.3)
    finally:
        w.close()


def test_config4_thirtytwo_rank_collective_suite():
    """32 ranks: bcast / scatter / gather / reduce across both protocols'
    tree shapes (binary bcast tree depth 5, binomial reduce)."""
    w = EmuWorld(32)
    try:
        n = 3000  # 12 KB -> rendezvous: binary/binomial trees
        x = RNG.standard_normal(n).astype(np.float32)

        def bcast_body(rank, i):
            buf = x.copy() if i == 7 else np.zeros(n, np.float32)
            rank.bcast(buf, n, root=7)
            return buf

        for out in w.run(bcast_body):
            np.testing.assert_allclose(out, x, rtol=0)

        sc = RNG.standard_normal(32 * 64).astype(np.float32)

        def sg_body(rank, i):
            rb = np.zeros(64, np.float32)
            rank.scatter(sc.copy() if i == 0 else np.zeros(32 * 64, np.float32),
                         rb, 64, root=0)
            gb = np.zeros(32 * 64, np.float32)
            rank.gather(rb, gb, 64, root=31)
            return rb, gb

        res = w.run(sg_body)
        np.testing.assert_allclose(res[31][1], sc, rtol=0)

        red = RNG.standard_normal((32, 2000)).astype(np.float32)

        def red_body(rank, i):
            out = np.zeros(2000, np.float32)
            rank.reduce(red[i].copy(), out, 2000, root=3,
                        func=ReduceFunction.SUM)
            return out

        res = w.run(red_body)
        np.testing.assert_allclose(res[3], red.sum(0), rtol=1e-3, atol=1e-3)
    finally:
        w.close()


def test_config5_native_sixtyfour_rank_compressed_local_poe():
    """BASELINE config 5's world size on the NATIVE runtime: 64 ranks,
    fp16 wire-compressed allreduce plus an uncompressed allgather, over
    the intra-process POE (the socket mesh at w64 would need 64*63
    connections + rx threads; the direct-call transport brings the full
    world up instantly, which is exactly the intra-node fast path's
    job)."""
    from accl_tpu import CallOptions, CompressionFlags, DataType
    from accl_tpu.constants import Operation

    w = EmuWorld(64, transport="local")
    try:
        xs = (RNG.standard_normal((64, 512)) * 0.1).astype(np.float32)

        def body(rank, i):
            out = np.zeros(512, np.float32)
            rank.call(CallOptions(
                scenario=Operation.allreduce, count=512,
                function=int(ReduceFunction.SUM),
                compression_flags=CompressionFlags.ETH_COMPRESSED,
                data_type=DataType.float32),
                op0=xs[i].copy(), res=out)
            ag = np.zeros(64 * 64, np.float32)
            rank.allgather(xs[i, :64].copy(), ag, 64)
            return out, ag

        res = w.run(body)
    finally:
        w.close()
    exp = xs.astype(np.float16).sum(0).astype(np.float32)
    for out, ag in res:
        np.testing.assert_allclose(out, exp, rtol=5e-2, atol=5e-1)
        np.testing.assert_allclose(ag, xs[:, :64].ravel(), rtol=0)


def test_config5_sixtyfour_rank_streamed_compressed_allreduce():
    """64 virtual devices: allreduce with fp16 wire compression, plus a
    kernel-streamed producer (stream_put) feeding a rank. Runs in a
    subprocess because the CPU device count is fixed at backend init."""
    script = textwrap.dedent("""
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 64)
        jax.config.update("jax_enable_x64", True)
        import numpy as np, jax.numpy as jnp
        from jax.sharding import Mesh
        from accl_tpu.accl import ACCL
        from accl_tpu import ReduceFunction, DataType

        mesh = Mesh(np.array(jax.devices()), ("ccl",))
        accl = ACCL(mesh)
        x = np.random.default_rng(0).standard_normal((64, 512)).astype(np.float32)
        sb, rb = accl.create_buffer(512, data=x), accl.create_buffer(512)
        accl.allreduce(sb, rb, 512, ReduceFunction.SUM,
                       compress_dtype=DataType.float16)
        exp = x.astype(np.float16).astype(np.float32).sum(0)
        assert np.allclose(rb.host[0], exp, rtol=0.1, atol=1.0), "allreduce"

        accl.register_stream_producer(5, lambda: jnp.full(64, 3.0, jnp.float32))
        out = accl.create_buffer(64)
        accl.stream_put(64, stream_id=5, src=0, dst=63, recvbuf=out)
        assert np.allclose(out.host[63], 3.0), "stream_put"
        print("CONFIG5 OK")
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600,
                       cwd=pathlib.Path(__file__).resolve().parents[1])
    assert "CONFIG5 OK" in r.stdout, r.stderr[-2000:]
