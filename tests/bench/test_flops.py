"""bench/flops.py against counts made by hand."""

import pytest

from bench import flops

# paper-ee-100m attention and MLP widths
EE = {"d_model": 768, "n_heads": 12, "n_kv_heads": 12, "head_dim": 64,
      "d_ff": 3072, "vocab": 50257, "act": "gelu"}
# granite-3-2b: GQA group 4, SwiGLU
GR = {"d_model": 2048, "n_heads": 32, "n_kv_heads": 8, "head_dim": 64,
      "d_ff": 8192, "vocab": 49155, "act": "swiglu"}


def test_layer_params():
    # q, o: 768x768 each; k, v: 768x768 each; up, down 768x3072 each
    assert flops.layer_params(EE) == 4 * 768 * 768 + 2 * 768 * 3072
    # q, o: 2048x2048; k, v: 2048x512; gate, up, down: 2048x8192
    assert flops.layer_params(GR) == (2 * 2048 * 2048 + 2 * 2048 * 512
                                      + 3 * 2048 * 8192)


def test_decode_attention_by_hand():
    # two lanes with 130 and 1 keys, pages of 128, bf16 KV, f32 q
    f, b = flops.decode_attention(EE, [130, 1], page=128, kv_bytes=2,
                                  act_bytes=4)
    assert f == 4 * 12 * 64 * 131            # QK^T and PV per key
    pages = 2 + 1                            # 130 keys: 2 pages; 1: 1
    kv_page = 128 * (2 * 12 * 64 * 2 + 4)   # k, v bf16 + int32 positions
    assert b == pages * kv_page + 2 * (2 * 12 * 64 * 4)


def test_prefill_attention_by_hand():
    # one lane: 3 chunk rows after 256 history keys; GQA group 4, bf16
    f, b = flops.prefill_attention(GR, [(256, 3)], page=128, kv_bytes=2,
                                   act_bytes=2)
    pairs = 3 * 256 + (1 + 2 + 3)            # history, then causal
    assert f == 4 * 32 * 64 * pairs
    hist = 2 * 128 * (2 * 8 * 64 * 2 + 4)    # two history pages
    rows = 3 * (2 * 32 * 64 + 2 * 8 * 64) * 2 + 3 * 4
    assert b == hist + rows
    # an empty history reads no page
    assert flops.prefill_attention(GR, [(0, 1)], page=128, kv_bytes=2,
                                   act_bytes=2)[1] == (
        (2 * 32 * 64 + 2 * 8 * 64) * 2 + 4)


@pytest.mark.parametrize("node", [0, 2, 5])
def test_step_flops_one_decode_lane(node):
    sizes = [2] * 6
    ctx = 300
    layers = 2 * (node + 1)
    want = (layers * (2 * flops.layer_params(EE) + 4 * 12 * 64 * ctx)
            + (node + 1) * 2 * 768 * 50257)
    assert flops.step_flops(EE, sizes, [(ctx, node)], []) == want


def test_step_flops_chunk_and_decode():
    sizes = [8] * 5
    lp = flops.layer_params(GR)
    chunk = 40 * (2 * lp * 128 + 4 * 32 * 64 * (128 * 100 + 128 * 129 / 2))
    head = 2 * 2048 * 49155
    dec = 8 * (2 * lp + 4 * 32 * 64 * 10) + 2 * 2 * 2048 * 49155 \
        - 2 * 2048 * 49155
    got = flops.step_flops(GR, sizes, [(10, 0)], [(100, 128, True)])
    assert got == pytest.approx(chunk + head + dec, rel=1e-12)
    # a chunk that does not finish its prompt reads no head
    assert flops.step_flops(GR, sizes, [], [(100, 128, False)]) == \
        pytest.approx(chunk, rel=1e-12)
