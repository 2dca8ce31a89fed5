"""DeepSeek-V2 decode through `ACCL.sequence()` at a small size on the
CPU: prefill, then the fused step through the latent cache, against the
plain reference's full forward; the fused step bitwise equal to its
eager twin; the ranks' expert shares adding up to the whole layer; the
absorbed MLA against the naive one; and weights bound as operands, so a
recording with new weights of the same shapes compiles nothing and no
weight is a program constant."""

import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from accl_tpu.accl import ACCL
from accl_tpu.models import deepseek_v2 as ds
from accl_tpu.models import deepseek_v2_reference as ref
from accl_tpu.models import serve
from accl_tpu.models import transformer as trf

CFG = ds.DeepSeekV2Config(
    hidden_size=64, num_hidden_layers=3, vocab_size=256,
    num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
    moe_intermediate_size=24, n_routed_experts=8, num_experts_per_tok=2,
    n_shared_experts=2)
# fp32 on both sides; the program sums in other orders (absorbed MLA,
# grouped experts, ring allreduces) and reads 1e-6 of the largest logit
# here. 1e-4 leaves that room; one bf16 rounding of a product (4e-3)
# would not fit.
LOGIT_TOL = 1e-4


def _accl(world):
    return ACCL(Mesh(np.array(jax.devices()[:world]), ("ccl",)))


def _gap(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.fixture(scope="module")
def params():
    return ds.init_params(CFG, 20240507)


@pytest.mark.parametrize("world", [1, 2, 4])
def test_prefill_then_decode_matches_the_full_forward(params, world):
    """Contexts prefilled at admission (in chunks, one ragged), then
    greedy tokens through the fused step: every decoded position's
    logits against the reference's full forward of the same history."""
    accl = _accl(world)
    model = ds.DeepSeekV2(accl, CFG, params=params, prefill_chunk=4)
    srv = serve.DecodeServer(accl, model, batch=3, max_len=24)
    rng = np.random.default_rng(world)
    ctxs = [rng.integers(0, CFG.vocab_size, n).tolist() for n in (6, 11, 1)]
    reqs = [srv.submit(c[-1:], 5, context=c[:-1]) for c in ctxs]
    steps = []
    while srv.active:
        srv.step()
        steps.append(srv.last_logits.copy())
    for i, (c, r) in enumerate(zip(ctxs, reqs)):
        assert len(r.generated) == 5
        full = np.asarray(ref.forward(params, np.array(c + r.generated), CFG))
        for k in range(len(r.generated)):
            p = len(c) - 1 + k
            assert _gap(steps[k][i], full[p]) < LOGIT_TOL, (i, p)
            assert r.generated[k] == int(np.argmax(steps[k][i]))


def test_fused_equals_eager_bitwise(params):
    """Ten steps of random tokens at ragged positions: the one-dispatch
    step and the descriptor-by-descriptor eager twin agree bit for
    bit, their caches evolving alike."""
    fused, eager = _accl(2), _accl(2)
    mf = ds.DeepSeekV2(fused, CFG, params=params)
    me = ds.DeepSeekV2(eager, CFG, params=params)
    bf = mf.create_buffers(fused, 3, 16)
    be = me.create_buffers(eager, 3, 16)
    prog = mf.make_program(fused, bf)
    me.register_consumers(eager, be)
    for seed in range(10):
        rng = np.random.default_rng(7100 + seed)
        toks = rng.integers(0, CFG.vocab_size, 3)
        pos = rng.integers(0, 16, 3)
        mf.write_inputs(bf, toks, pos)
        prog.run(to_device=True)
        me.write_inputs(be, toks, pos)
        me.run_eager(eager, be)
        np.testing.assert_array_equal(
            mf.read_logits(bf, sync=True), me.read_logits(be),
            err_msg=f"seed {seed}: fused != eager")


def test_prefix_in_row_out_equals_whole_images_bitwise(params):
    """Ten chained steps: xp's [x, pos] prefix put on the device, the
    step run over device-resident buffers and rank 0's logits row read
    give the logits of staging xp's whole image and reading every
    rank's logits back, bit for bit."""
    pa, pw = _accl(2), _accl(2)
    ma = ds.DeepSeekV2(pa, CFG, params=params)
    mw = ds.DeepSeekV2(pw, CFG, params=params)
    ba, bw = ma.create_buffers(pa, 3, 16), mw.create_buffers(pw, 3, 16)
    prog_a, prog_w = ma.make_program(pa, ba), mw.make_program(pw, bw)
    d = bw.dims
    b_d = d.batch * d.d_model
    for seed in range(10):
        rng = np.random.default_rng(7200 + seed)
        toks = rng.integers(0, CFG.vocab_size, 3)
        pos = rng.integers(0, 16, 3)
        ma.write_inputs(ba, toks, pos)
        prog_a.run(from_device=True, to_device=True)
        row = np.zeros(d.n_out, np.float32)
        row[:b_d] = params["embed"][toks].reshape(-1)
        row[b_d:b_d + d.batch] = pos
        bw.xp.host = np.repeat(row[None], 2, 0)
        prog_w.run(to_device=True)
        bw.logits.sync_from_device()
        np.testing.assert_array_equal(
            ma.read_logits(ba, sync=True),
            bw.logits.host[0][:d.batch * d.vocab].reshape(d.batch, d.vocab),
            err_msg=f"step {seed}: prefix path != whole images")


def test_served_step_moves_the_prefix_in_and_one_row_out(params):
    """Each served step's decode.inputs span counts world x (B*D + B)
    floats put on the device, its decode.logits span B*V read back."""
    from accl_tpu import telemetry

    world, batch = 4, 3
    accl = _accl(world)
    srv = serve.DecodeServer(accl, ds.DeepSeekV2(accl, CFG, params=params,
                                                 prefill_chunk=4),
                             batch=batch, max_len=24)
    for n in (5, 9):
        srv.submit([1], 6, context=list(range(2, 2 + n)))
    srv.step()
    tr = telemetry.get_tracer()
    tr.clear()
    tr.enable()
    try:
        for _ in range(2):
            srv.step()
        ring = tr.snapshot()
    finally:
        tr.disable()
        tr.clear()
    moved = {name: [s["args"]["bytes"] for s in ring if s["name"] == name]
             for name in ("decode.inputs", "decode.logits")}
    assert moved == {
        "decode.inputs": [world * (batch * CFG.hidden_size + batch) * 4] * 2,
        "decode.logits": [batch * CFG.vocab_size * 4] * 2}


def test_expert_shares_add_up_to_the_layer(params):
    """Four ranks, two routed experts and a quarter of the shared
    expert's width each: their partial FFN outputs, the shared expert
    so counted once, sum to the uncut reference MoE layer."""
    world = 4
    mesh = Mesh(np.array(jax.devices()[:world]), ("ccl",))
    dims = ds.decode_dims(CFG, world, 5, 8)
    layer = 1
    spec = ds._ffn_weights(CFG, layer)
    lyr = params["layers"][layer]
    accl = ACCL(mesh)
    rows = [trf.rank_rows(accl, lyr[n], a).device for n, _, a, _ in spec]
    x = np.random.default_rng(3).standard_normal((5, 64)).astype(np.float32)

    def body(x, *rs):
        w = {n: r.reshape(-1) for (n, _, _, _), r in zip(spec, rs)}
        return ds._ffn(x, w, CFG, dims, layer, "ccl", "highest")[None]

    parts = jax.shard_map(body, mesh=mesh,
                          in_specs=(P(),) + (P("ccl"),) * len(rows),
                          out_specs=P("ccl"), check_vma=False)(x, *rows)
    with jax.default_matmul_precision("highest"):
        want = ref.ffn(jnp.asarray(x), lyr, CFG, layer)
    assert parts.shape == (world, 5, 64)
    assert _gap(np.asarray(parts).sum(0), np.asarray(want)) < LOGIT_TOL


def test_seeded_rows_are_slices_of_the_whole_weights(params):
    """Each rank makes only its own slice of a seeded weight; the rows
    equal the whole weight of the same seed, made in one compiled
    program as the benchmark's reference makes it, split over the
    ranks, bit for bit."""
    seed, L = 20240507, CFG.num_hidden_layers
    accl = _accl(4)
    model = ds.DeepSeekV2(accl, CFG, seed=seed)
    key = ds.seed_key(seed)
    layers = [(l, model.attn_operands[l] + model.ffn_operands[l],
               ds._attn_weights(CFG) + ds._ffn_weights(CFG, l))
              for l in range(L)]
    layers.append((L, model.head_operands, ds._head_weights(CFG)))
    for layer, bufs, spec in layers:
        for buf, (name, shape, axis, init) in zip(bufs, spec):
            whole = jax.jit(lambda k, name=name, shape=shape, init=init:
                            ds._weight(k, layer, name, shape, init))(key)
            np.testing.assert_array_equal(
                np.asarray(buf.device),
                np.asarray(trf.rank_rows(accl, whole, axis).device),
                err_msg=name)
    np.testing.assert_array_equal(model.embed, params["embed"])


def test_rows_outside_every_group_are_dropped(params, monkeypatch):
    """On the chip the grouped product leaves the rows of other ranks'
    experts (outside every group) unwritten: a stand-in that fills them
    with NaN must not reach the rank's output."""
    orig = jax.lax.ragged_dot

    def unwritten_tail(lhs, rhs, group_sizes, **kw):
        out = orig(lhs, rhs, group_sizes, **kw)
        rows = jnp.arange(out.shape[0])[:, None]
        return jnp.where(rows < group_sizes.sum(), out, jnp.nan)

    lyr = params["layers"][1]
    dims = ds.decode_dims(CFG, 4, 5, 8)
    w = {n: jnp.asarray(lyr[n])[:2].reshape(-1)
         for n in ("e_gate", "e_up", "e_down")}
    h = jnp.asarray(np.random.default_rng(5).standard_normal(
        (5, 64)).astype(np.float32))
    top_i = jnp.asarray([[0, 5], [1, 2], [7, 3], [0, 1], [4, 6]])
    top_p = jnp.full((5, 2), 0.5, jnp.float32)
    want = ds._local_experts(h, top_p, top_i, w, dims, 0, "highest")
    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten_tail)
    got = ds._local_experts(h, top_p, top_i, w, dims, 0, "highest")
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.all(np.asarray(got)[4] == 0)  # token 4 chose no expert here


def test_absorbed_mla_equals_naive(params):
    """One layer's attention over a whole sequence: the absorbed form
    (scores and context in latent space, the cache rows as the program
    keeps them) against the reference's decompress-then-attend."""
    T = 13
    lyr = params["layers"][0]
    dims = ds.decode_dims(CFG, 1, 1, T)
    w = {n: jnp.asarray(lyr[n]).reshape(-1)
         for n, _, _, _ in ds._attn_weights(CFG)}
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (T, 64)).astype(np.float32))
    got = ds._mla(x, jnp.arange(T), lambda rows: rows, w, CFG, dims,
                  "highest")
    with jax.default_matmul_precision("highest"):
        want = ref.attention(x, lyr, CFG)
    assert _gap(np.asarray(got), np.asarray(want)) < LOGIT_TOL


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_attn_consumer_rebuilds_no_whole_state_at_the_cells_dims():
    """At the benchmark cell's dims (the published config, four ranks,
    32 slots of 8192 positions), traced abstractly: the attention
    consumer concatenates and scatters nothing of the cache's size, so
    it writes the state in place; and the state's cache and length sit
    on the flat tile of 1024 numbers."""
    cfg, world = ds.DEEPSEEK_V2_LITE, 4
    dims = ds.decode_dims(cfg, world, 32, 8192)
    assert dims.cache_base % 1024 == 0 and dims.n_state % 1024 == 0
    rows = [jax.ShapeDtypeStruct(
        (int(np.prod(shape)) // (world if axis is not None else 1),),
        jnp.float32) for _, shape, axis, _ in ds._attn_weights(cfg)]
    state = jax.ShapeDtypeStruct((dims.n_state,), jnp.float32)
    consumer = ds.make_attn_consumer(cfg, dims, "highest")
    jaxpr = jax.make_jaxpr(consumer)(state, *rows)
    whole = dims.batch * dims.max_len * dims.cache_width
    big = [(e.primitive.name, v.aval.shape) for e in _eqns(jaxpr.jaxpr)
           for v in e.outvars
           if (e.primitive.name == "concatenate"
               or e.primitive.name.startswith("scatter"))
           and np.prod(v.aval.shape) >= whole]
    assert not big, big
    assert jaxpr.out_avals[0].shape == (dims.n_state,)


def test_attn_consumer_writes_only_the_o_prefix_and_the_new_rows(params):
    """On a random state the consumer leaves every number outside the o
    prefix and each slot's new latent row bitwise as it was; each
    written row is the slot's [c, k_pe] as `_mla` hands it to its write
    callback, and the prefix is `_mla`'s o partial over the written
    cache."""
    B, T = 3, 16
    dims = ds.decode_dims(CFG, 1, B, T)
    D, W, base = dims.d_model, dims.cache_width, dims.cache_base
    lyr = params["layers"][0]
    w = {n: jnp.asarray(lyr[n]).reshape(-1)
         for n, _, _, _ in ds._attn_weights(CFG)}
    rng = np.random.default_rng(11)
    state = rng.standard_normal(dims.n_state).astype(np.float32)
    pos = np.array([0, 9, T - 1])
    state[B * D:B * D + B] = pos
    consumer = ds.make_attn_consumer(CFG, dims, "highest")
    got = np.asarray(consumer(jnp.asarray(state),
                              *(w[n] for n, _, _, _ in
                                ds._attn_weights(CFG))))
    seen = {}
    cache = state[base:base + B * T * W].reshape(B, T, W).copy()

    def write(new):
        seen["new"] = np.asarray(new)
        cache[np.arange(B), pos] = seen["new"]
        return jnp.asarray(cache)

    o = ds._mla(jnp.asarray(state[:B * D].reshape(B, D)),
                jnp.asarray(pos), write, w, CFG, dims, "highest")
    at = [base + b * T * W + pos[b] * W for b in range(B)]
    for b in range(B):
        np.testing.assert_array_equal(got[at[b]:at[b] + W], seen["new"][b])
    np.testing.assert_array_equal(got[:B * D], np.asarray(o).reshape(-1))
    kept = np.ones(dims.n_state, bool)
    kept[:B * D] = False
    for a in at:
        kept[a:a + W] = False
    np.testing.assert_array_equal(got[kept], state[kept])


def _largest_constant(program) -> int:
    prep = program._prepared
    args = [prep.bufs[a].device for a in prep.seq.buffer_addrs]
    text = prep.fn.lower(*args).as_text()
    sizes = [int(np.prod([int(d) for d in m.split("x") if d] or [1]))
             for m in re.findall(
                 r"stablehlo\.constant dense(?:_resource)?<[^>]*>\s*:\s*"
                 r"tensor<([0-9x]*?)x?[a-z]+[0-9]+>", text)]
    return max(sizes)


def test_new_weights_reuse_the_compiled_step():
    """Weights are operands: a second recording with other weights of
    the same shapes is a lowering-cache hit, and no constant of the
    lowered step is as large as the smallest weight matrix."""
    accl = _accl(2)
    comp = accl.cclo.compiler
    a = ds.DeepSeekV2(accl, CFG, seed=1)
    prog_a = a.make_program(accl, a.create_buffers(accl, 2, 8))
    misses, hits = comp.lower_misses, comp.lower_hits
    b = ds.DeepSeekV2(accl, CFG, seed=2)
    prog_b = b.make_program(accl, b.create_buffers(accl, 2, 8))
    assert comp.lower_misses == misses and comp.lower_hits == hits + 1
    assert prog_b._prepared.fn is prog_a._prepared.fn
    smallest = min(int(np.prod(s)) for n, s, _, _ in ds._attn_weights(CFG)
                   if len(s) > 1)
    assert _largest_constant(prog_b) < smallest


def test_operands_are_read_only_inputs_of_their_step(params):
    """The lint and footprint passes see a consumer's operands as whole
    reads of its step; none of them is a program output."""
    accl = _accl(2)
    model = ds.DeepSeekV2(accl, CFG, params=params)
    prog = model.make_program(accl, model.create_buffers(accl, 2, 8))
    reads = dict(prog.footprint.reads)
    writes = dict(prog.footprint.writes)
    for buf in model.operand_buffers:
        assert reads[buf.address] == buf.shape[-1]
        assert buf.address not in writes
    assert not set(prog._prepared.seq.out_addrs) & {
        b.address for b in model.operand_buffers}


def test_config_from_the_published_json():
    cfg = ds.DeepSeekV2Config.from_json({
        "hidden_size": 2048, "num_hidden_layers": 27, "model_type": "x",
        "rope_scaling": {"type": "yarn", "factor": 40,
                         "original_max_position_embeddings": 4096,
                         "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                         "mscale_all_dim": 0.707}})
    assert cfg == ds.DEEPSEEK_V2_LITE
    with pytest.raises(ValueError, match="implemented"):
        ds.DeepSeekV2Config.from_json({"q_lora_rank": 1536})
    with pytest.raises(ValueError, match="divide"):
        ds.decode_dims(CFG, 3, 2, 8)


GOLDEN = pathlib.Path(__file__).with_name("data") / "deepseek_v2_logits.npz"


def _golden_runs(params) -> dict:
    """Logits of two fixed runs on two ranks: three contexts prefilled
    and four served steps with whole params, and two steps of the model
    seeded on the device."""
    accl = _accl(2)
    srv = serve.DecodeServer(accl, ds.DeepSeekV2(accl, CFG, params=params,
                                                 prefill_chunk=4),
                             batch=3, max_len=24)
    rng = np.random.default_rng(29)
    for n in (6, 11, 1):
        srv.submit([1], 4, context=rng.integers(0, CFG.vocab_size,
                                                n).tolist())
    served = []
    for _ in range(4):
        srv.step()
        served.append(srv.last_logits.copy())
    accl = _accl(2)
    model = ds.DeepSeekV2(accl, CFG, seed=7)
    bufs = model.create_buffers(accl, 3, 8)
    prog = model.make_program(accl, bufs)
    seeded = []
    for step in range(2):
        model.write_inputs(bufs, [3, 5, 7], [step, step + 2, 0])
        prog.run(from_device=True, to_device=True)
        seeded.append(model.read_logits(bufs, sync=True))
    return {"served": np.stack(served), "seeded": np.stack(seeded)}


def test_default_arguments_keep_the_logits_bitwise(params):
    """The shared MLA, router and held-experts code, at its defaults
    (yarn rope, softmax top-k, the world split of the experts), gives
    DeepSeek-V2 the logits it gave before those switches existed, bit
    for bit (recorded in GOLDEN)."""
    want = np.load(GOLDEN)
    got = _golden_runs(params)
    for name in ("served", "seeded"):
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
