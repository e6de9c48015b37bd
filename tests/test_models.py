"""Model-layer unit tests: attention equivalences, SSD vs naive recurrence,
MoE dispatch, decode-vs-forward agreement."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.models import build_model
from repro.models import model as model_lib
from repro.models.attention import chunked_attention
from repro.models.common import cross_entropy, rms_norm
from repro.models.moe import moe_apply, moe_defs
from repro.models.ssm import _ssd_scan
from repro.models.common import init_params


def naive_attention(q, k, v, causal=True, window=None):
    B, S, H, hd = q.shape
    KV = k.shape[2]
    R = H // KV
    qg = q.reshape(B, S, KV, R, hd).astype(jnp.float32)
    s = jnp.einsum("bqkrh,bckh->bkrqc", qg, k.astype(jnp.float32)) / np.sqrt(hd)
    i = jnp.arange(S)
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= i[:, None] >= i[None, :]
    if window is not None:
        mask &= i[:, None] - i[None, :] < window
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkrqc,bckh->bkrqh", w, v.astype(jnp.float32))
    return jnp.moveaxis(o, 3, 1).reshape(B, S, H, hd)


class TestChunkedAttention:
    @pytest.mark.parametrize("S,chunk", [(64, 16), (64, 64), (60, 16), (128, 32)])
    @pytest.mark.parametrize("H,KV", [(4, 4), (8, 2)])
    def test_matches_naive_causal(self, rng, S, chunk, H, KV):
        B, hd = 2, 16
        ks = jax.random.split(rng, 3)
        q = jax.random.normal(ks[0], (B, S, H, hd))
        k = jax.random.normal(ks[1], (B, S, KV, hd))
        v = jax.random.normal(ks[2], (B, S, KV, hd))
        pos = jnp.arange(S)
        got = chunked_attention(q, k, v, pos, pos, causal=True, chunk=chunk)
        want = naive_attention(q, k, v, causal=True)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_sliding_window_matches_naive(self, rng):
        B, S, H, hd = 1, 96, 4, 8
        ks = jax.random.split(rng, 3)
        q = jax.random.normal(ks[0], (B, S, H, hd))
        k = jax.random.normal(ks[1], (B, S, H, hd))
        v = jax.random.normal(ks[2], (B, S, H, hd))
        pos = jnp.arange(S)
        got = chunked_attention(q, k, v, pos, pos, causal=True, window=16, chunk=32)
        want = naive_attention(q, k, v, causal=True, window=16)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_non_causal(self, rng):
        B, S, H, hd = 1, 48, 2, 8
        ks = jax.random.split(rng, 3)
        q = jax.random.normal(ks[0], (B, S, H, hd))
        k = jax.random.normal(ks[1], (B, S, H, hd))
        v = jax.random.normal(ks[2], (B, S, H, hd))
        pos = jnp.arange(S)
        got = chunked_attention(q, k, v, pos, pos, causal=False, chunk=16)
        want = naive_attention(q, k, v, causal=False)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def _ssd_scan_per_head(xh, dt, A, Bm, Cm, chunk, initial_state=None):
    """The chunked SSD with B, C and the C·B scores copied to every head
    (``jnp.repeat``): the formulation ``_ssd_scan`` replaced, kept as an
    oracle of the same mathematics summed in another order."""
    Bsz, S, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    R = H // G
    Q = min(chunk, S)
    nc = S // Q
    xc = xh.reshape(Bsz, nc, Q, H, P)
    dtc = dt.reshape(Bsz, nc, Q, H)
    Bc = Bm.reshape(Bsz, nc, Q, G, N)
    Cc = Cm.reshape(Bsz, nc, Q, G, N)
    dtx = dtc[..., None] * xc
    cum = jnp.cumsum(A * dtc, axis=2)
    cum_last = cum[:, :, -1]
    s = jnp.repeat(jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc), R, axis=2)
    decay = jnp.moveaxis(cum[:, :, :, None, :] - cum[:, :, None, :, :], -1, 2)
    iq = jnp.arange(Q)
    causal = (iq[:, None] >= iq[None, :])[None, None, None]
    M = s * jnp.exp(jnp.where(causal, decay, -jnp.inf))
    y_intra = jnp.einsum("bchij,bcjhp->bcihp", M, dtx)
    w_end = jnp.exp(cum_last[:, :, None, :] - cum)
    Bfull = jnp.repeat(Bc, R, axis=3)
    chunk_states = jnp.einsum("bcjhn,bcjhp,bcjh->bchnp", Bfull, dtx, w_end)
    Cfull = jnp.repeat(Cc, R, axis=3)
    state0 = (jnp.zeros((Bsz, H, N, P)) if initial_state is None else initial_state)

    def body(state, inp):
        c_full, cum_c, cum_last_c, cs = inp
        y_int = jnp.einsum("bqhn,bhnp,bqh->bqhp", c_full, state, jnp.exp(cum_c))
        return jnp.exp(cum_last_c)[..., None, None] * state + cs, y_int

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (Cfull, cum, cum_last, chunk_states))
    final_state, y_inter = jax.lax.scan(body, state0, xs)
    y_inter = jnp.moveaxis(y_inter, 0, 1)
    return (y_intra + y_inter).reshape(Bsz, S, H, P), final_state


HEAD_GROUPS = [(4, 1), (4, 2), (4, 4)]


class TestSSD:
    @pytest.mark.parametrize("H,G", HEAD_GROUPS)
    def test_matches_naive_recurrence(self, rng, H, G):
        """Chunked SSD == exact sequential state-space recurrence."""
        B, S, P, N = 2, 64, 8, 16
        ks = jax.random.split(rng, 4)
        x = jax.random.normal(ks[0], (B, S, H, P))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
        Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
        Cm = jax.random.normal(jax.random.fold_in(rng, 9), (B, S, G, N)) * 0.5

        y_chunk, state_chunk = _ssd_scan(x, dt, A, Bm, Cm, chunk=16)

        # naive: h_t = exp(A dt_t) h_{t-1} + dt_t B_t x_t ; y_t = C_t h_t
        R = H // G
        Bf = jnp.repeat(Bm, R, axis=2)
        Cf = jnp.repeat(Cm, R, axis=2)
        h = jnp.zeros((B, H, N, P))
        ys = []
        for t in range(S):
            a = jnp.exp(A[None] * dt[:, t])                       # (B,H)
            h = a[..., None, None] * h + jnp.einsum(
                "bhn,bhp->bhnp", Bf[:, t], dt[:, t][..., None] * x[:, t])
            ys.append(jnp.einsum("bhn,bhnp->bhp", Cf[:, t], h))
        y_naive = jnp.stack(ys, axis=1)
        np.testing.assert_allclose(y_chunk, y_naive, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(state_chunk, h, rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("G", [1, 2])
    def test_matches_per_head_formulation(self, rng, G):
        """Contracting each head against its group gives what copying B, C
        and the scores to every head gave: y, the final state and the
        gradients of every input, from a carried initial state."""
        B, S, H, P, N = 2, 64, 4, 8, 16
        ks = jax.random.split(rng, 6)
        args = (
            jax.random.normal(ks[0], (B, S, H, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))),
            -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3),
            jax.random.normal(ks[3], (B, S, G, N)) * 0.5,
            jax.random.normal(ks[4], (B, S, G, N)) * 0.5,
        )
        s0 = jax.random.normal(ks[5], (B, H, N, P)) * 0.3

        def run(scan):
            def loss(*a):
                y, st = scan(*a, 16, initial_state=s0)
                return jnp.sum(jnp.sin(y)) + jnp.sum(st ** 2)
            return (jax.jit(scan, static_argnums=5)(*args, 16, initial_state=s0),
                    jax.jit(jax.grad(loss, argnums=range(5)))(*args))

        (y, st), grads = run(_ssd_scan)
        (y_ref, st_ref), grads_ref = run(_ssd_scan_per_head)
        # f32 sums taken in another order: relative to each array's largest entry
        for got, want in [(y, y_ref), (st, st_ref), *zip(grads, grads_ref)]:
            np.testing.assert_allclose(got, want, rtol=1e-4,
                                       atol=1e-4 * float(jnp.max(jnp.abs(want))))

    def test_no_per_head_copy_of_B_or_C(self):
        """The lowered forward and backward hold no f32 array with the head
        axis beside the state axis: a per-head copy of B or C."""
        B, S, H, G, P, N, Q = 2, 32, 6, 1, 4, 10, 8
        shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in
                  [(B, S, H, P), (B, S, H), (H,), (B, S, G, N), (B, S, G, N)]]

        def loss(*a):
            y, st = _ssd_scan(*a, Q)
            return jnp.sum(y ** 2) + jnp.sum(st)

        text = jax.jit(jax.value_and_grad(loss, argnums=range(5))).lower(*shapes).as_text()
        assert "x6x4x10xf32" in text or "x6x10x4xf32" in text   # the state is per head
        copies = re.findall(rf"tensor<[0-9x]*x{H}x{N}x(?:{Q}x)?f32>", text)
        assert not copies, copies

    def test_initial_state_continuation(self, rng):
        """Running two halves with carried state == one full pass."""
        B, S, H, P, G, N = 1, 64, 2, 4, 1, 8
        ks = jax.random.split(rng, 5)
        x = jax.random.normal(ks[0], (B, S, H, P))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
        Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
        Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
        y_full, s_full = _ssd_scan(x, dt, A, Bm, Cm, chunk=16)
        y1, s1 = _ssd_scan(x[:, :32], dt[:, :32], A, Bm[:, :32], Cm[:, :32], 16)
        y2, s2 = _ssd_scan(x[:, 32:], dt[:, 32:], A, Bm[:, 32:], Cm[:, 32:], 16,
                           initial_state=s1)
        np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_full, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(s2, s_full, rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("H,G", HEAD_GROUPS)
    def test_gradient_finite_at_long_chunk(self, rng, H, G):
        """At a published chunk length (256) the decay between the ends of
        a chunk overflows f32 above the diagonal; the gradient must stay
        finite, and the forward must equal the same scan at a short chunk."""
        B, S, P, N = 1, 256, 4, 8
        ks = jax.random.split(rng, 5)
        x = jax.random.normal(ks[0], (B, S, H, P))
        dt = jnp.full((B, S, H), 0.5)
        A = jnp.array([-1.0, -8.0, -2.0, -4.0])   # Σ A·dt over the chunk ≈ −1024 ≪ −88
        Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
        Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5

        def loss(x, dt, Bm, Cm, chunk):
            return jnp.sum(_ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)[0] ** 2)

        grads = jax.grad(loss, argnums=(0, 1, 2, 3))(x, dt, Bm, Cm, 256)
        for g in grads:
            assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_allclose(loss(x, dt, Bm, Cm, 256),
                                   loss(x, dt, Bm, Cm, 32), rtol=2e-3)


class TestMoE:
    def _cfg(self, **kw):
        base = dict(name="t", arch_type="moe", source="t", n_layers=1, d_model=32,
                    n_heads=4, n_kv_heads=4, d_ff=64, vocab_size=64,
                    n_experts=4, top_k=2, d_ff_expert=32, capacity_factor=2.0)
        base.update(kw)
        return ModelConfig(**base)

    def test_output_shape_and_aux(self, rng):
        cfg = self._cfg()
        p = init_params(rng, moe_defs(cfg), jnp.float32)
        x = 0.1 * jax.random.normal(rng, (2, 8, 32))
        out, aux = moe_apply(p, cfg, x)
        assert out.shape == x.shape
        assert float(aux) >= 1.0 - 1e-3   # Switch aux ≥ 1 at balance

    def test_capacity_drop_is_graceful(self, rng):
        cfg = self._cfg(capacity_factor=0.1)   # force drops
        p = init_params(rng, moe_defs(cfg), jnp.float32)
        x = 0.1 * jax.random.normal(rng, (2, 16, 32))
        out, aux = moe_apply(p, cfg, x)
        assert bool(jnp.all(jnp.isfinite(out)))

    def test_shared_expert_always_active(self, rng):
        cfg = self._cfg(n_shared_experts=1)
        p = init_params(rng, moe_defs(cfg), jnp.float32)
        x = 0.1 * jax.random.normal(rng, (1, 4, 32))
        out, _ = moe_apply(p, cfg, x)
        # zeroing routed experts must keep shared-expert contribution
        p2 = dict(p)
        p2["down"] = jnp.zeros_like(p["down"])
        out2, _ = moe_apply(p2, cfg, x)
        assert float(jnp.max(jnp.abs(out2))) > 0.0


class TestCommon:
    def test_rms_norm_unit_scale(self, rng):
        x = jax.random.normal(rng, (4, 32)) * 7.0
        y = rms_norm(x, jnp.ones((32,)), 1e-6)
        rms = jnp.sqrt(jnp.mean(y * y, axis=-1))
        np.testing.assert_allclose(rms, 1.0, rtol=1e-3)

    def test_cross_entropy_perfect_prediction(self):
        logits = jnp.full((2, 4, 8), -20.0)
        labels = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 7]])
        logits = logits.at[
            jnp.arange(2)[:, None], jnp.arange(4)[None, :], labels
        ].set(20.0)
        loss, _ = cross_entropy(logits, labels, z_loss=0.0)
        assert float(loss) < 1e-3


def test_tied_embeddings_option(rng):
    cfg = dataclasses.replace(get_config("internlm2-1.8b").reduced(), tie_embeddings=True)
    model = build_model(cfg)
    params = model.init(rng)
    assert "lm_head" not in params
    batch = {"tokens": jnp.zeros((1, 32), jnp.int32), "labels": jnp.zeros((1, 32), jnp.int32)}
    loss, _ = jax.jit(model.loss_fn)(params, batch)
    assert jnp.isfinite(loss)


def _gather_chunked_ce(cfg, params, h, labels, mask):
    """The chunked CE as it was before rematerialisation: the gold logit by a
    gather, the scan body not checkpointed.  Same chunking, same sums."""
    B, S, D = h.shape
    c = min(model_lib.LOSS_CHUNK, S)
    n = S // c if S % c == 0 else 1
    c = S // n

    def body(carry, xs):
        tot, cnt = carry
        hh, ll, mm = xs
        logits32 = model_lib._lm_head(cfg, params, hh).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits32, axis=-1)
        gold = jnp.take_along_axis(logits32, ll[..., None], axis=-1)[..., 0]
        return (tot + jnp.sum((lse - gold) * mm), cnt + jnp.sum(mm)), None

    xs = tuple(jnp.moveaxis(a.reshape(B, n, c, *a.shape[2:]), 1, 0)
               for a in (h, labels, mask))
    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)), xs)
    return tot / jnp.maximum(cnt, 1.0)


def _plain_ce(cfg, params, h, labels, mask):
    """Unchunked f32 cross-entropy over the whole (B, S, V) logits."""
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", h.astype(jnp.float32), w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


class TestChunkedCE:
    """``_chunked_ce``, the LM loss of every ``loss_fn``: its value and its
    gradients with respect to the hidden states and the head (the embedding
    when tied)."""

    V, D, B = 1000, 64, 2

    def _case(self, tied, S, masked, dtype):
        cfg = dataclasses.replace(get_config("mamba2-130m").reduced(),
                                  vocab_size=self.V, tie_embeddings=tied)
        ks = jax.random.split(jax.random.PRNGKey(S + 2 * tied + masked), 4)
        h = jax.random.normal(ks[0], (self.B, S, self.D)).astype(dtype)
        w = (jax.random.normal(ks[1], (self.D, self.V)) * 0.3).astype(dtype)
        params = {"embed": w.T} if tied else {"lm_head": w}
        labels = jax.random.randint(ks[2], (self.B, S), 0, self.V)
        mask = jnp.ones((self.B, S), jnp.float32)
        if masked:
            mask = (jax.random.uniform(ks[3], (self.B, S)) > 0.3).astype(jnp.float32)
        return cfg, params, h, labels, mask

    @staticmethod
    def _value_and_grads(fn, cfg, params, h, labels, mask):
        f = jax.jit(jax.value_and_grad(
            lambda p, x: fn(cfg, p, x, labels, mask), argnums=(0, 1)))
        return f(params, h)

    @pytest.mark.parametrize("masked", [False, True], ids=["mask1", "mask0s"])
    @pytest.mark.parametrize("S", [2048, 300], ids=["S2048_4chunks", "S300_1chunk"])
    @pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
    def test_matches_plain_f32_ce(self, tied, S, masked):
        # bf16 parameters, as trained: bf16's tolerance against the plain f32 CE
        cfg, params, h, labels, mask = self._case(tied, S, masked, jnp.bfloat16)
        if masked:
            assert 0 < float(mask.sum()) < mask.size
        loss, (gp, gh) = self._value_and_grads(model_lib._chunked_ce, cfg, params,
                                               h, labels, mask)
        rloss, (rgp, rgh) = self._value_and_grads(_plain_ce, cfg, params, h, labels, mask)
        np.testing.assert_allclose(float(loss), float(rloss), rtol=4e-3)
        for got, want in [(gh, rgh), *zip(jax.tree_util.tree_leaves(gp),
                                          jax.tree_util.tree_leaves(rgp))]:
            assert got.dtype == want.dtype == jnp.bfloat16
            got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
            rel = np.linalg.norm(got - want) / np.linalg.norm(want)
            assert rel < 1e-2, rel

        # f32 parameters: the gather-based formula's loss exactly, and its
        # gradients to a few f32 ulps of their largest entry.  Bit equality
        # does not hold for the gradients: the select's cotangent fuses into
        # the softmax cotangent, where the CPU backend contracts the multiply
        # and the add into one rounding (an FMA), and the scatter's did not.
        cfg, params, h, labels, mask = self._case(tied, S, masked, jnp.float32)
        (loss, grads) = self._value_and_grads(model_lib._chunked_ce, cfg, params,
                                              h, labels, mask)
        (rloss, rgrads) = self._value_and_grads(_gather_chunked_ce, cfg, params,
                                                h, labels, mask)
        np.testing.assert_array_equal(np.asarray(loss), np.asarray(rloss))
        ulp = np.finfo(np.float32).eps
        for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(rgrads)):
            a, b = np.asarray(a), np.asarray(b)
            np.testing.assert_allclose(a, b, rtol=0, atol=4 * ulp * np.abs(b).max())

    def test_backward_keeps_no_logits_and_no_scatter(self):
        """The trainer's form, ``jit(vmap(value_and_grad(loss_fn)))``: the
        compiled program stacks no f32 (chunks, ..., V) logits for the
        backward pass and scatters no gold-logit cotangent.  The one scatter
        left is the token embedding's gradient, (W, V, D), the transpose of
        the lookup ``embed[tokens]``."""
        import re
        W, S = 2, 2048
        n_chunks = S // model_lib.LOSS_CHUNK
        cfg = dataclasses.replace(get_config("mamba2-130m").reduced(), vocab_size=self.V)
        assert n_chunks > 1 and self.V not in (cfg.d_model, S, n_chunks, W)
        model = build_model(cfg)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        tok = jax.ShapeDtypeStruct((W, 1, S), jnp.int32)
        step = jax.jit(jax.vmap(jax.value_and_grad(model.loss_fn, has_aux=True),
                                in_axes=(None, 0)))
        hlo = step.lower(params, {"tokens": tok, "labels": tok}).compile().as_text()

        def dims(shape):
            return tuple(int(x) for x in shape.split(",") if x)

        scattered = {dims(m) for m in re.findall(r"= \w+\[([\d,]*)\]\{[^}]*\} scatter\(", hlo)}
        assert scattered <= {(W, self.V, cfg.d_model)}, scattered
        stacked = {d for d in map(dims, re.findall(r"f32\[([\d,]*)\]", hlo))
                   if n_chunks in d and self.V in d}
        assert not stacked, stacked


class TestQuantKVCache:
    def test_int8_cache_close_to_bf16(self, rng):
        """§Perf serving lever: per-step decode with int8 cache tracks the
        bf16 cache within quantization tolerance (teacher-forced)."""
        import dataclasses
        from repro.configs import get_config
        cfg = get_config("llama3.2-3b").reduced()
        outs = {}
        for dt in ["bfloat16", "int8"]:
            c = dataclasses.replace(cfg, kv_cache_dtype=dt)
            model = build_model(c)
            params = model.init(jax.random.PRNGKey(0))
            batch = {"tokens": jnp.arange(2 * 32).reshape(2, 32) % c.vocab_size}
            lp, cache = jax.jit(lambda p, b: model.prefill(p, b, cache_len=64))(params, batch)
            ld, _ = jax.jit(model.decode_step)(params, cache, jnp.full((2, 1), 5, jnp.int32))
            outs[dt] = np.asarray(ld)
        a, b = outs["bfloat16"], outs["int8"]
        rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-9)
        assert rel < 0.1, rel
        assert (a.argmax(-1) == b.argmax(-1)).mean() == 1.0

    def test_quantize_roundtrip_bounded(self, rng):
        from repro.models.attention import _quantize
        x = jax.random.normal(rng, (4, 8, 2, 16))
        q, s = _quantize(x)
        deq = q.astype(jnp.float32) * s.astype(jnp.float32)[..., None]
        err = jnp.max(jnp.abs(deq - x)) / jnp.max(jnp.abs(x))
        assert float(err) < 1.0 / 100  # absmax int8: ≤ scale/2 per element
