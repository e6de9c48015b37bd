"""Model-layer unit tests: attention equivalences, SSD vs naive recurrence,
MoE dispatch, decode-vs-forward agreement."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.models import build_model
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


class TestSSD:
    def test_matches_naive_recurrence(self, rng):
        """Chunked SSD == exact sequential state-space recurrence."""
        B, S, H, P, G, N = 2, 64, 4, 8, 1, 16
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

    def test_gradient_finite_at_long_chunk(self, rng):
        """At a published chunk length (256) the decay between the ends of
        a chunk overflows f32 above the diagonal; the gradient must stay
        finite, and the forward must equal the same scan at a short chunk."""
        B, S, H, P, G, N = 1, 256, 2, 4, 1, 8
        ks = jax.random.split(rng, 5)
        x = jax.random.normal(ks[0], (B, S, H, P))
        dt = jnp.full((B, S, H), 0.5)
        A = jnp.array([-1.0, -8.0])   # Σ A·dt over the chunk ≈ −1024 ≪ −88
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
