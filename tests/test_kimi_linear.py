"""Kimi-Linear decode through `ACCL.sequence()` at a small size on the
CPU: prefill, then the fused step over KDA states and an MLA cache,
against the plain reference's full forward; the chunked KDA prefill
against the token-by-token recurrence; a re-admitted slot against a
fresh one; the held experts' shares adding up to the uncut layer; and
planted faults that the comparison must catch."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from accl_tpu.accl import ACCL
from accl_tpu.models import deepseek_v2 as ds
from accl_tpu.models import kimi_linear as kl
from accl_tpu.models import kimi_linear_reference as ref
from accl_tpu.models import serve

# five layers as the cell has them: KDA (dense FFN), KDA, KDA, MLA, KDA;
# 8 of the router's 32 experts held, from expert 8
CFG = kl.KimiLinearConfig(
    hidden_size=64, num_hidden_layers=5, vocab_size=256,
    num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
    moe_intermediate_size=24, n_routed_experts=32, num_experts_per_tok=4,
    full_attn_layers=(4,), kda_num_heads=2, kda_head_dim=16,
    experts_held=8, first_held_expert=8)
# fp32 on both sides; the program sums in other orders (the chunkwise
# KDA form, absorbed MLA, grouped experts) and reads about 1e-6 of the
# largest logit here. 1e-4 leaves that room; one bf16 rounding of a
# product (4e-3) would not fit.
LOGIT_TOL = 1e-4
CHUNK = 16  # prefill chunk: a context of 20 crosses it


def _accl():
    return ACCL(Mesh(np.array(jax.devices()[:1]), ("ccl",)))


def _gap(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _params(cfg, seed: int) -> dict:
    """Whole weights of the model's weight lists, drawn with numpy."""
    rng = np.random.default_rng(seed)

    def draw(spec):
        out = {}
        for name, shape, _, init in spec:
            if init[0] == "uniform":
                w = rng.uniform(init[1], init[2], shape)
            else:
                z = rng.standard_normal(shape)
                w = 1 + 0.1 * z if init[0] == "norm" else z * init[1]
            out[name] = w.astype(np.float32)
        return out

    L = cfg.num_hidden_layers
    params = draw(ds._head_weights(cfg))
    params["embed"] = rng.standard_normal(
        (cfg.vocab_size, cfg.hidden_size)).astype(np.float32)
    params["layers"] = [draw(kl.attn_weights(cfg, l)
                             + ds._ffn_weights(cfg, l)) for l in range(L)]
    return params


@pytest.fixture(scope="module")
def params():
    return _params(CFG, 20251030)


@pytest.fixture(scope="module")
def model(params):
    """One rank and the model's weights on it, shared by the servers of
    the tests (each server records and compiles its own step)."""
    accl = _accl()
    return accl, kl.KimiLinear(accl, CFG, params=params, prefill_chunk=CHUNK)


def _serve(model, ctxs, new_tokens, prompts=None):
    """Prefill each context into its slot (or stream `prompts` in from
    position 0), then decode `new_tokens` greedy tokens each: (the
    histories fed, the logits of each one's decoded positions)."""
    accl, m = model
    srv = serve.DecodeServer(accl, m, batch=len(ctxs), max_len=MAX_LEN)
    reqs = [srv.submit(c[-1:], new_tokens, context=c[:-1]) for c in ctxs] \
        if prompts is None else [srv.submit(p, new_tokens) for p in prompts]
    steps = []
    while srv.active:
        srv.step()
        steps.append(srv.last_logits.copy())
    fed = ctxs if prompts is None else [p[:1] for p in prompts]
    out = []
    for i, (c, r) in enumerate(zip(fed, reqs)):
        hist = (c if prompts is None else prompts[i]) + r.generated
        first = len(c) - 1
        out.append((hist, np.stack([s[i] for s in steps[:len(hist) - first]
                                    ])[:len(hist) - first - 1], first))
    srv.free()
    return out


MAX_LEN = 48
_forward = jax.jit(ref.forward, static_argnums=2)


def _full(params, hist, cfg=CFG):
    """The reference's logits over a history, run padded to MAX_LEN
    (causal: the padding changes nothing before it)."""
    toks = np.zeros(MAX_LEN, np.int64)
    toks[:len(hist)] = hist
    return np.asarray(_forward(params, toks, cfg))[:len(hist)]


def _worst_gap(params, served) -> float:
    """The widest gap of the decoded logits from the full forward's at
    the same positions."""
    worst = 0.0
    for hist, logits, first in served:
        full = _full(params, hist)
        for k, row in enumerate(logits):
            worst = max(worst, _gap(row, full[first + k]))
    return worst


CTXS = [list(range(3, 23)), [7, 9], list(range(40, 49))]  # 20, 2, 9 tokens


def test_prefill_then_decode_matches_the_full_forward(params, model):
    """Contexts prefilled at admission (one across a chunk boundary, one
    shorter than the conv kernel), then greedy tokens through the fused
    step: every decoded position's logits against the reference's full
    forward of the same history; the tokens are their argmax."""
    served = _serve(model, CTXS, 4)
    assert _worst_gap(params, served) < LOGIT_TOL
    for hist, logits, first in served:
        assert len(logits) == 4
        assert hist[first + 1:] == [int(np.argmax(r)) for r in logits]


_prefill = jax.jit(kl._kda_prefill, static_argnums=(5, 6))
_kda = jax.jit(ref.kda, static_argnums=2)


@pytest.mark.parametrize("n", [2, 16, 20, 37])
def test_chunked_prefill_equals_the_recurrence(params, n):
    """One KDA layer over n tokens, chunk after chunk from a zero state
    with the conv tails and S carried: the chunkwise form gives the
    token-by-token recurrence's outputs (n = 2 is shorter than the
    conv kernel; 20 and 37 cross chunks, ending in a part chunk)."""
    lyr = params["layers"][1]
    x = np.zeros((MAX_LEN, 64), np.float32)
    x[:n] = np.random.default_rng(n).standard_normal((n, 64))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(_kda(jnp.asarray(x), lyr, CFG))[:n]
    H, K = CFG.kda_num_heads, CFG.kda_head_dim
    tails, S = jnp.zeros((3, 3, H * K)), jnp.zeros((H, K, K))
    got = []
    for p0 in range(0, n, CHUNK):
        y, tails, S = _prefill(jnp.asarray(x[p0:p0 + CHUNK]), tails, S,
                               min(CHUNK, n - p0), lyr, CFG, "highest")
        got.append(np.asarray(y))
    assert _gap(np.concatenate(got)[:n], want) < 1e-5


def test_readmitted_slot_equals_a_fresh_slot(params, model):
    """A slot that served a finished session then decodes a new one as
    a fresh slot does: prefill starts its KDA state from zero, and a
    prompt streamed in from position 0 (no context) starts from zero in
    the step."""
    accl, m = model
    srv = serve.DecodeServer(accl, m, batch=1, max_len=MAX_LEN)
    srv.submit([5], 3, context=list(range(60, 80)))
    srv.run()
    ctx = [11, 12, 13, 14, 15]
    r = srv.submit([9], 3, context=ctx)
    again = []
    while srv.active:
        srv.step()
        again.append(srv.last_logits[0].copy())
    (hist, fresh, _), = _serve(model, [ctx + [9]], 3)
    np.testing.assert_array_equal(np.stack(again), fresh)
    assert ctx + [9] + r.generated == hist
    r = srv.submit([9, 4], 2)  # no context: streamed from position 0
    streamed = []
    while srv.active:
        srv.step()
        streamed.append(srv.last_logits[0].copy())
    want = _full(params, [9, 4] + r.generated[:1])
    assert len(streamed) == 3
    for k in range(3):
        assert _gap(streamed[k], want[k]) < LOGIT_TOL
    srv.free()


def test_expert_shares_add_up_to_the_uncut_layer(params):
    """Four chips' shares of a 32-expert layer, 8 experts each: their
    FFN outputs, the shared expert counted once, sum to the uncut
    reference layer, which holds every expert."""
    layer = 1
    uncut = dataclasses.replace(CFG, experts_held=None, first_held_expert=0)
    whole = _params(uncut, 7)["layers"][layer]
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (5, 64)).astype(np.float32))
    mesh = Mesh(np.array(jax.devices()[:1]), ("ccl",))

    @functools.partial(jax.jit, static_argnums=1)
    def share(w, first):
        cfg = dataclasses.replace(CFG, first_held_expert=first)
        dims = ds.decode_dims(cfg, 1, 5, 8)
        return jax.shard_map(
            lambda x, w: ds._ffn(x, w, cfg, dims, layer, "ccl", "highest"),
            mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False)(x, w)

    parts = []
    for first in (0, 8, 16, 24):
        w = dict(whole, **{n: whole[n][first:first + 8]
                           for n in ("e_gate", "e_up", "e_down")})
        parts.append(np.asarray(share(
            {n: jnp.asarray(v).reshape(-1) for n, v in w.items()}, first)))
    with jax.default_matmul_precision("highest"):
        h = ref.rmsnorm(x, whole["ffn_norm"], CFG.rms_norm_eps)
        shared = ref.swiglu(h, whole["s_gate"], whole["s_up"],
                            whole["s_down"])
        want = jax.jit(ref.ffn, static_argnums=(2, 3))(x, whole, uncut,
                                                       layer)
    got = sum(parts) - 3 * np.asarray(shared)
    assert _gap(got, np.asarray(want)) < LOGIT_TOL


def _no_decay(h, w, cfg, prec):
    return jnp.zeros_like(_decay(h, w, cfg, prec))


def _no_conv(seq, w):
    return jax.nn.silu(seq[w.shape[1] - 1:])


def _beta_off(h, w, cfg, prec):
    return 1.01 * _beta(h, w, cfg, prec)


_decay, _beta = kl._decay, kl._beta


@pytest.fixture
def fresh_consumers():
    """Consumers are built once per configuration and dims: clear them
    around a test that plants a fault, so that the fault is traced into
    new ones and leaves none behind."""
    def clear():
        for make in (kl.make_kda_consumer, ds.make_attn_consumer,
                     ds.make_ffn_consumer, ds.make_logits_consumer):
            make.cache_clear()

    clear()
    yield
    clear()


@pytest.mark.parametrize("fault", ["no_decay", "no_conv", "beta_off",
                                   "rope", "softmax"])
def test_planted_faults_are_caught(params, model, monkeypatch,
                                   fresh_consumers, fault):
    """Each fault in the program (the reference as it is) moves the
    decoded logits past the tolerance."""
    if fault == "no_decay":
        monkeypatch.setattr(kl, "_decay", _no_decay)
    elif fault == "no_conv":
        monkeypatch.setattr(kl, "_short_conv", _no_conv)
    elif fault == "beta_off":
        monkeypatch.setattr(kl, "_beta", _beta_off)
    elif fault == "rope":  # rotation on the NoPE MLA
        mla = ds._mla
        monkeypatch.setattr(ds, "_mla", lambda *a, **k: mla(*a[:7],
                                                            rope=True))
    else:  # softmax scores where the config says sigmoid
        route = ds._route
        monkeypatch.setattr(ds, "_route", lambda logits, w, c: route(
            logits, w, dataclasses.replace(c, scoring_func="softmax")))
    served = _serve(model, CTXS[:1], 2)
    assert _worst_gap(params, served) > LOGIT_TOL
