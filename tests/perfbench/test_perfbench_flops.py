"""``flops_per_token`` of each reference family against counts made by
hand at tiny sizes."""
import pytest

from perfbench.reference import gqa_swiglu, mamba2


def test_mamba2_hand_count():
    m = dict(d_model=8, vocab_size=10, n_layers=1, ssm_expand=2, ssm_head_dim=4,
             ssm_groups=1, ssm_state=2, ssm_chunk=4)
    # d_inner 16, 4 heads of 4, B and C of 2; chunk 4 = the sequence
    proj = 8 * 16 + 8 * 16 + 8 * 2 + 8 * 2 + 8 * 4 + 16 * 8   # z x B C dt out
    head = 8 * 10
    # causal half of a 4-token chunk: 2.5 keys a query on average
    scores = 2 * 2.5 * 2            # C·B over N = 2
    mix = 2 * 2.5 * 4 * 4           # scores times x, 4 heads of 4
    state = 2 * 4 * 2 * 4 + 2 * 4 * 2 * 4   # B^T x into the state, C read-out
    want = 3 * (2 * (proj + head) + scores + mix + state)
    assert mamba2.flops_per_token(m, 4) == pytest.approx(want)
    # a chunk longer than the sequence is cut to it
    assert mamba2.flops_per_token(dict(m, ssm_chunk=256), 4) == pytest.approx(want)


def test_gqa_swiglu_hand_count():
    m = dict(d_model=8, vocab_size=10, n_layers=2, n_heads=2, n_kv_heads=1,
             head_dim=4, d_ff=16)
    layer = 8 * 2 * 4 + 8 * 4 + 8 * 4 + 2 * 4 * 8 + 3 * 8 * 16  # q k v o, MLP
    head = 8 * 10
    # per layer, q·k and a·v: 2 heads of 4 over 2.5 keys on average
    attn = 2 * (2 * (2 * 2 * 4 * 2.5))
    want = 3 * (2 * (2 * layer + head) + attn)
    assert gqa_swiglu.flops_per_token(m, 4) == pytest.approx(want)


def test_published_sizes_match_the_parameter_counts():
    """6 x matmul weights dominates: the count sits just above it."""
    from perfbench import registry
    m = registry.config("mamba2-130m")["model"]
    f = mamba2.flops_per_token(m, 2048)
    matmul = 24 * (768 * (3072 + 256 + 24) + 1536 * 768) + 768 * 50280
    assert 6 * matmul < f < 6 * matmul * 1.2
    g = registry.config("internlm2-1.8b.l1v8")["model"]
    f = gqa_swiglu.flops_per_token(g, 2048)
    matmul = 2048 * 32 * 128 + 128 * 16 * 2048 + 3 * 2048 * 8192 + 2048 * 11568
    assert 6 * matmul < f < 6 * matmul * 1.1
