"""Tests for the parallelism layer: ring attention, Ulysses SP, mesh
helpers, and the flagship transformer's multi-axis training step."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from accl_tpu.parallel import (
    factorize_devices,
    make_mesh,
    ring_attention,
    ulysses_attention,
)

RNG = np.random.default_rng(21)


def reference_attention(q, k, v, causal):
    s = np.einsum("bqhd,bkhd->bhqk", q, k).astype(np.float64)
    s /= np.sqrt(q.shape[-1])
    if causal:
        T = q.shape[1]
        mask = np.tril(np.ones((T, T), bool))
        s = np.where(mask[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


def run_sharded_attention(fn, world, B, T, H, D, causal):
    mesh = Mesh(np.array(jax.devices()[:world]), ("sp",))
    q, k, v = (RNG.standard_normal((B, T, H, D)).astype(np.float32)
               for _ in range(3))
    body = functools.partial(fn, axis_name="sp", causal=causal)

    def wrapped(q, k, v):
        return body(q, k, v)

    f = jax.jit(
        jax.shard_map(wrapped, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                      out_specs=P(None, "sp"), check_vma=False)
    )
    out = np.asarray(f(q, k, v))
    exp = reference_attention(q, k, v, causal)
    np.testing.assert_allclose(out, exp, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("world", [2, 4, 8])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(world, causal):
    run_sharded_attention(ring_attention, world, B=2, T=64, H=4, D=16,
                          causal=causal)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_long_sequence(causal):
    """Long-context check: 2048 tokens ring-sharded across sp=8 (256
    per shard, 7 ring hops) against the full fp64 oracle — the
    flagship's long-sequence claim at a context length where the
    log-sum-exp accumulation across hops actually has to work."""
    run_sharded_attention(ring_attention, 8, B=1, T=2048, H=2, D=32,
                          causal=causal)


@pytest.mark.parametrize("world", [2, 4])
@pytest.mark.parametrize("hkv", [1, 2])
def test_ring_attention_gqa_matches_full(world, hkv):
    """Grouped-query ring attention (Hkv < H): k/v ride the ring at
    kv-head width and must match the full-attention oracle with k/v
    repeated to all query heads."""
    B, T, H, D = 2, 32, 4, 16
    mesh = Mesh(np.array(jax.devices()[:world]), ("sp",))
    q = RNG.standard_normal((B, T, H, D)).astype(np.float32)
    k, v = (RNG.standard_normal((B, T, hkv, D)).astype(np.float32)
            for _ in range(2))

    def body(q, k, v):
        return ring_attention(q, k, v, axis_name="sp", causal=True)

    f = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                      out_specs=P(None, "sp"), check_vma=False)
    )
    out = np.asarray(f(q, k, v))
    G = H // hkv
    exp = reference_attention(q, np.repeat(k, G, axis=2),
                              np.repeat(v, G, axis=2), True)
    np.testing.assert_allclose(out, exp, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("world", [2, 4])
@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_full(world, causal):
    run_sharded_attention(ulysses_attention, world, B=2, T=32, H=4, D=8,
                          causal=causal)


@pytest.mark.parametrize("world", [2, 4])
def test_ulysses_resharding_matches_lax_all_to_all(world):
    """The framework-alltoall re-shardings must agree element-for-element
    with XLA's builtin all_to_all on both directions of the Ulysses
    exchange (seq-sharded <-> head-sharded)."""
    from accl_tpu.parallel.ulysses import _heads_to_seq, _seq_to_heads
    from accl_tpu.sequencer import schedules

    mesh = Mesh(np.array(jax.devices()[:world]), ("sp",))
    B, T, H, D = 2, 8, world * 2, 4
    x = RNG.standard_normal((B, T * world, H, D)).astype(np.float32)
    wire = schedules.Wire(None)

    def xla_seq_to_heads(xi):
        xi = xi.reshape(B, T, world, H // world, D)
        xi = jax.lax.all_to_all(xi, "sp", split_axis=2, concat_axis=1,
                                tiled=False)
        return xi.reshape(B, T * world, H // world, D)

    def body(xi):
        ours = _seq_to_heads(xi, "sp", world, wire)
        theirs = xla_seq_to_heads(xi)
        back = _heads_to_seq(ours, "sp", world, wire)
        return ours - theirs, back - xi

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(None, "sp"),),
                              out_specs=(P(None, "sp"), P(None, "sp")),
                              check_vma=False))
    d_fwd, d_round = f(x)
    np.testing.assert_array_equal(np.asarray(d_fwd), 0)
    np.testing.assert_array_equal(np.asarray(d_round), 0)


def test_ring_attention_differentiable():
    world = 4
    mesh = Mesh(np.array(jax.devices()[:world]), ("sp",))
    q, k, v = (RNG.standard_normal((1, 32, 2, 8)).astype(np.float32)
               for _ in range(3))

    def loss_body(q, k, v):
        out = ring_attention(q, k, v, axis_name="sp", causal=True)
        return jnp.sum(out ** 2), out

    def body(q, k, v):
        (l, _), g = jax.value_and_grad(lambda q: loss_body(q, k, v),
                                       has_aux=True)(q)
        return g

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                              out_specs=P(None, "sp"), check_vma=False))
    g = np.asarray(f(q, k, v))
    # numerical check on one element
    eps = 1e-3
    def full_loss(qq):
        out = reference_attention(qq, k, v, True)
        return float((out ** 2).sum())
    qp = q.copy(); qp[0, 5, 1, 3] += eps
    qm = q.copy(); qm[0, 5, 1, 3] -= eps
    num = (full_loss(qp) - full_loss(qm)) / (2 * eps)
    assert abs(g[0, 5, 1, 3] - num) < 5e-2


def test_factorize_and_make_mesh():
    sizes = factorize_devices(8)
    assert np.prod(list(sizes.values())) == 8
    mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    assert mesh.shape == {"dp": 2, "sp": 2, "tp": 2}
    with pytest.raises(ValueError):
        make_mesh({"dp": 3})


def test_transformer_train_step_decreases_loss():
    """Flagship end-to-end: 8 devices as dp2 x sp2 x tp2, five SGD steps
    through the fully framework-routed training program."""
    from accl_tpu.models import TransformerConfig, init_params, make_train_step
    from accl_tpu.models.transformer import demo_batch, shard_params

    mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64)
    params = init_params(cfg, jax.random.key(0))
    params = shard_params(params, cfg, mesh)
    tokens, targets = demo_batch(cfg, mesh, batch=4, seq=32)
    step = make_train_step(cfg, mesh, lr=5e-2)
    losses = []
    for _ in range(5):
        params, loss = step(params, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()


@pytest.mark.parametrize("axes", [{"dp": 1, "sp": 1, "tp": 2},
                                  {"dp": 2, "sp": 2, "tp": 2},
                                  {"dp": 1, "sp": 1, "tp": 1, "pp": 2},
                                  {"dp": 2, "sp": 1, "tp": 2, "pp": 2},
                                  {"dp": 1, "sp": 2, "tp": 2, "pp": 2}])
def test_transformer_train_step_matches_single_device(axes):
    """One SGD step on a tp-sharded mesh must produce the same updated
    params as the identical step on one device (the tp-aware gradient
    sync: replicated params mean-allreduced over tp, tp-sharded grads
    rescaled by 1/tp to undo the allreduce-transpose amplification)."""
    from accl_tpu.models import TransformerConfig, init_params, make_train_step
    from accl_tpu.models.transformer import demo_batch, shard_params

    cfg = TransformerConfig(vocab=32, d_model=16, n_heads=4, n_layers=2,
                            d_ff=32)
    params = init_params(cfg, jax.random.key(2))
    lr = 0.1

    mesh1 = make_mesh({"dp": 1, "sp": 1, "tp": 1}, devices=jax.devices()[:1])
    tokens1, targets1 = demo_batch(cfg, mesh1, batch=4, seq=16)
    step1 = make_train_step(cfg, mesh1, lr=lr)
    ref_params, ref_loss = step1(shard_params(params, cfg, mesh1),
                                 tokens1, targets1)

    n = int(np.prod(list(axes.values())))
    mesh = make_mesh(axes, devices=jax.devices()[:n])
    tokens, targets = demo_batch(cfg, mesh, batch=4, seq=16)
    np.testing.assert_array_equal(np.asarray(tokens), np.asarray(tokens1))
    step = make_train_step(cfg, mesh, lr=lr)
    new_params, loss = step(shard_params(params, cfg, mesh), tokens, targets)
    if axes.get("pp", 1) > 1:
        from accl_tpu.models.transformer import unstack_layer_params

        new_params = unstack_layer_params(new_params, cfg.n_layers)

    assert abs(float(loss) - float(ref_loss)) < 1e-5
    flat_ref = jax.tree_util.tree_flatten_with_path(ref_params)[0]
    flat_new = jax.tree.leaves(new_params)
    for (path, r), nw in zip(flat_ref, flat_new):
        np.testing.assert_allclose(
            np.asarray(nw), np.asarray(r), rtol=2e-4, atol=2e-5,
            err_msg=f"param {jax.tree_util.keystr(path)} diverged on {axes}")


def test_transformer_remat_step_matches_plain():
    """remat=True (jax.checkpoint around each block) must be numerically
    identical to the plain step — it changes memory, not math — on both
    the flat and the pipelined path."""
    from accl_tpu.models import TransformerConfig, init_params, make_train_step
    from accl_tpu.models.transformer import demo_batch, shard_params

    cfg = TransformerConfig(vocab=32, d_model=16, n_heads=4, n_layers=2,
                            d_ff=32)
    params = init_params(cfg, jax.random.key(9))
    for axes in ({"dp": 2, "sp": 2, "tp": 2},
                 {"dp": 2, "sp": 1, "tp": 2, "pp": 2}):
        mesh = make_mesh(axes)
        tokens, targets = demo_batch(cfg, mesh, batch=4, seq=16)
        p0 = shard_params(params, cfg, mesh)
        plain, l_plain = make_train_step(cfg, mesh, lr=0.1)(
            p0, tokens, targets)
        rem, l_rem = make_train_step(cfg, mesh, lr=0.1, remat=True)(
            p0, tokens, targets)
        assert float(l_plain) == pytest.approx(float(l_rem), abs=1e-6)
        for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(rem)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6,
                                       err_msg=str(axes))


def test_transformer_forward_parallel_equals_single():
    """The sharded forward must equal the same model on one device."""
    from accl_tpu.models import TransformerConfig, init_params, make_forward
    from accl_tpu.models.transformer import shard_params

    cfg = TransformerConfig(vocab=32, d_model=16, n_heads=4, n_layers=1,
                            d_ff=32)
    params = init_params(cfg, jax.random.key(1))
    tokens = RNG.integers(0, cfg.vocab, (2, 16)).astype(np.int32)

    mesh1 = make_mesh({"dp": 1, "sp": 1, "tp": 1}, devices=jax.devices()[:1])
    f1 = make_forward(cfg, mesh1)
    ref = np.asarray(f1(shard_params(params, cfg, mesh1), tokens))

    mesh8 = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    f8 = make_forward(cfg, mesh8)
    out = np.asarray(f8(shard_params(params, cfg, mesh8), tokens))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_ulysses_quantized_wire_within_bound():
    """Ulysses re-shardings over the blockwise int8 wire (one packed
    codes+scales message per hop): attention output within the
    quantization bound of the exact-wire result — the same lanes the
    MoE dispatch rides, on the other alltoall rider."""
    from accl_tpu.arithconfig import DEFAULT_ARITH_CONFIG
    from accl_tpu.constants import DataType
    from accl_tpu.sequencer import schedules

    world, B, T, H, D = 4, 2, 32, 4, 8
    mesh = Mesh(np.array(jax.devices()[:world]), ("sp",))
    q, k, v = (RNG.standard_normal((B, T, H, D)).astype(np.float32)
               for _ in range(3))
    qwire = schedules.Wire(
        DEFAULT_ARITH_CONFIG[(DataType.float32, DataType.int8)])

    def run(wire):
        body = functools.partial(ulysses_attention, axis_name="sp",
                                 causal=True, wire=wire)
        f = jax.jit(jax.shard_map(
            lambda a, b, c: body(a, b, c), mesh=mesh,
            in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
            check_vma=False))
        return np.asarray(f(q, k, v))

    exact = run(None)
    quant = run(qwire)
    assert not np.array_equal(quant, exact)  # the wire really engaged
    np.testing.assert_allclose(quant, exact, rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(
        exact, reference_attention(q, k, v, True), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Compute-communication overlap at the model layer (ROADMAP item 4)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stripes", [2, 4])
def test_ulysses_striped_matches_monolithic(stripes):
    """Double-buffered Ulysses: splitting the two re-sharding
    all-to-alls into head-group stripes (overlapped against the
    attention matmuls) computes the monolithic round trip's answer.
    Attention is per-head and alltoall is pure routing, but each stripe
    attends over fewer heads, and XLA compiles the softmax and the P.V
    product differently at that shape: the two agree to within 4 ulps
    of the output's largest magnitude, not bitwise (on the CPU mesh
    ~60% of elements differ, by at most 1.5 ulps). The serial twin
    (order-barriered groups) is the same shapes in another order, so it
    is bitwise the striped result."""
    world = 4
    B, T, H, D = 2, 8, 4 * stripes, 16
    mesh = Mesh(np.array(jax.devices()[:world]), ("sp",))
    q, k, v = (RNG.standard_normal((B, T * world, H, D))
               .astype(np.float32) for _ in range(3))

    def run(s, serial=False):
        def body(q, k, v):
            return ulysses_attention(q, k, v, axis_name="sp",
                                     stripes=s, serial=serial)
        f = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False))
        return np.asarray(f(q, k, v))

    base = run(1)
    ulps = 4 * np.spacing(np.abs(base).max())
    striped = run(stripes)
    serial = run(stripes, serial=True)
    np.testing.assert_allclose(striped, base, rtol=0, atol=ulps)
    np.testing.assert_allclose(serial, base, rtol=0, atol=ulps)
    np.testing.assert_array_equal(serial, striped)


def test_ulysses_striped_jaxpr_interleaves_compute():
    """The stripe-interleaving pin: the striped Ulysses body traces
    each head group's in-alltoall -> attention matmuls -> out-alltoall
    chain in turn, so the jaxpr carries dot_general equations BETWEEN
    the ppermute chains (compute the scheduler can overlap with the
    neighbouring group's wire), and the ppermute count scales by the
    stripe count (stripes x 4 alltoalls x (world-1) hops)."""
    from accl_tpu.analysis.protocol import iter_ppermute_eqns

    world, stripes = 4, 2
    B, T, H, D = 2, 8, 8, 16

    def body(q, k, v):
        return ulysses_attention(q, k, v, axis_name="sp",
                                 stripes=stripes)

    avals = [jax.ShapeDtypeStruct((B, T, H, D), np.float32)] * 3
    closed = jax.make_jaxpr(body, axis_env=[("sp", world)])(*avals)
    eqns = closed.jaxpr.eqns
    pidx = [i for i, e in enumerate(eqns)
            if e.primitive.name == "ppermute"]
    didx = [i for i, e in enumerate(eqns)
            if e.primitive.name == "dot_general"]
    assert len(pidx) == stripes * 4 * (world - 1)
    between = [i for i in didx if pidx[0] < i < pidx[-1]]
    assert between, "no compute equations between the ppermute chains"


def test_train_step_striped_grad_sync_matches_leaf():
    """make_train_step's bucketed grad sync: the striped flat dp+sp
    mean-allreduce must train the same model as the per-leaf form
    (same loss; parameters equal within reassociation tolerance — the
    chunking changes the ring's per-element fold order), and the
    serial twin is BITWISE the overlapped form."""
    from accl_tpu.models import transformer as trf
    from accl_tpu.parallel import make_mesh

    cfg = trf.TransformerConfig(vocab=32, d_model=16, n_heads=4,
                                n_layers=2, d_ff=32, n_kv_heads=2)
    mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2},
                     devices=jax.devices()[:8])
    params = trf.shard_params(trf.init_params(cfg, jax.random.key(0)),
                              cfg, mesh)
    tok, tgt = trf.demo_batch(cfg, mesh, batch=4, seq=16)

    def run(grad_sync, stripes=4):
        step = trf.make_train_step(cfg, mesh, grad_sync=grad_sync,
                                   grad_stripes=stripes)
        p2, loss = step(params, tok, tgt)
        flat = np.concatenate([np.asarray(x).ravel()
                               for x in jax.tree.leaves(p2)])
        return flat, float(loss)

    leaf, loss_leaf = run("leaf")
    olap, loss_olap = run("striped")
    serial, loss_serial = run("striped_serial")
    assert loss_leaf == loss_olap == loss_serial
    np.testing.assert_array_equal(olap, serial)
    np.testing.assert_allclose(olap, leaf, rtol=1e-5, atol=1e-6)
