"""Cost functions against counts made by hand at a tiny shape."""
import pytest

from costs import extend, model, paged_decode

# L=2 layers, D=8, H=4 query heads of hd=2 on K=2 KV heads, F=16, V=10
D = {"L": 2, "D": 8, "H": 4, "K": 2, "hd": 2, "F": 16, "V": 10, "Vp": 16}


def test_linear_params_by_hand():
    # per layer: q 8*4*2=64, k and v 8*2*2=32 each, o 64, mlp 3*8*16=384
    assert model.linear_params(D) == 2 * (64 + 32 + 32 + 64 + 384)


def test_weight_bytes_tied_and_untied():
    head = 8 * 16
    assert model.weight_bytes(D, True) == 2 * (1152 + head)
    assert model.weight_bytes(D, False) == 2 * (1152 + 2 * head)


def test_kv_bytes_per_position():
    # K and V, 2 layers, 2 heads of 2, bf16
    assert model.kv_bytes_per_position(D) == 2 * 2 * 2 * 2 * 2


@pytest.mark.parametrize("start,stop,want", [
    (0, 1, 1), (0, 3, 1 + 2 + 3), (2, 4, 3 + 4), (5, 5, 0)])
def test_causal_contexts(start, stop, want):
    assert model.causal_contexts(start, stop) == want


def test_tokens_flops_by_hand():
    # 3 tokens over contexts 1+2+3, one sampled: 2*1152*3 linear,
    # 4*L*H*hd*6 attention, 2*D*V logits
    want = 2 * 1152 * 3 + 4 * 2 * 4 * 2 * 6 + 2 * 8 * 10
    assert model.tokens_flops(D, 3, 6, 1) == want


def test_extend_cost_counts_suffix_only():
    c = extend.cost(D, True, 4, 6)       # positions 4 and 5: contexts 5, 6
    assert c["flops"] == 2 * 1152 * 2 + 4 * 2 * 4 * 2 * 11 + 2 * 8 * 10
    kv = 32
    assert c["bytes"] == model.weight_bytes(D, True) + kv * 4 + kv * 2


def test_paged_decode_cost():
    # two sequence-steps over contexts 7 and 9
    c = paged_decode.cost(D, 16, 2)
    assert c["flops"] == 4 * 2 * 4 * 2 * 16
    assert c["bytes"] == 32 * 16 + 2 * 2 * 2 * 4 * 2 * 2


def test_least_time_is_a_share_at_most_one():
    # the roofline's least time can never exceed the time of a device that
    # runs at its peaks: a share computed from it stays at or under 100%
    pk = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    c = extend.cost(D, True, 0, 64)
    least = max(c["flops"] / pk["bf16_flops_per_s"],
                c["bytes"] / pk["hbm_bytes_per_s"])
    device_time = (c["flops"] / pk["bf16_flops_per_s"]
                   + c["bytes"] / pk["hbm_bytes_per_s"])
    assert least <= device_time
