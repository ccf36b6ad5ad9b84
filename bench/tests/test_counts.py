"""FLOP and byte counts against hand counts at the smoke size."""
from bench.core import counts as C
from bench.core import models, weights

TINY = {"model_type": "qwen2", "hidden_size": 48, "intermediate_size": 96,
        "num_attention_heads": 3, "num_hidden_layers": 2,
        "num_key_value_heads": 1, "head_dim": 16, "vocab_size": 256}
MOE = {"model_type": "qwen2_moe", "hidden_size": 32,
       "num_attention_heads": 2, "num_hidden_layers": 2,
       "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
       "num_experts": 6, "num_experts_per_tok": 2,
       "moe_intermediate_size": 32, "shared_expert_intermediate_size": 64}


def test_life_steps():
    # prompt 35 in chunks of 16: 16, 16, 3 (the last yields token 1),
    # then 3 decode steps for tokens 2..4
    assert C.life_steps(35, 4, 16) == [
        (0, 16, False), (16, 16, False), (32, 3, True),
        (35, 1, True), (36, 1, True), (37, 1, True)]


def test_attn_pairs():
    assert C.attn_pairs(0, 3) == 1 + 2 + 3
    assert C.attn_pairs(10, 2) == 11 + 12


def test_dense_flops_by_hand():
    d = weights.dims(TINY)
    fam = models.load(d)
    # per layer: q 48x48, k 48x16, v 48x16, o 48x48 -> 2*(2304+768+768+2304)
    # mlp 3 x 48x96 -> 2*13824
    per_layer = 2 * (2304 + 768 + 768 + 2304) + 2 * 13824
    assert fam.linear_flops_per_token(d) == 2 * per_layer
    assert fam.attn_flops(d, 10) == 2 * 4 * 3 * 16 * 10
    assert C.head_flops(d) == 2 * 48 * 256


def test_moe_flops_by_hand():
    d = weights.dims(MOE)
    fam = models.load(d)
    proj = 2 * 32 * (2 + 4) * 16 + 2 * 2 * 16 * 32
    # router 32x6, two active experts of 3 x 32x32, shared 3 x 32x64
    ffn = 2 * 32 * 6 + 2 * 6 * 32 * 32 + 6 * 32 * 64
    assert fam.linear_flops_per_token(d) == 2 * (proj + ffn)


def test_attn_bytes_by_hand():
    d = weights.dims(TINY)
    fam = models.load(d)
    # 5 keys + 5 values of 1 head x 16 x 2 B, 2 queries + 2 outputs of
    # 3 heads x 16 x 2 B, per layer
    assert fam.attn_bytes(d, 5, 2) == 2 * (2 * 5 * 16 * 2 + 2 * 2 * 48 * 2)


def test_step_work_and_roofline():
    d = weights.dims(TINY)
    fam = models.load(d)
    w = C.StepWork(d)
    w.add_request(10, 35, 4, 16)
    tot = w.totals(0, 100)
    pairs = sum(C.attn_pairs(q, n) for q, n, _ in C.life_steps(35, 4, 16))
    assert tot["attn_flops"] == fam.attn_flops(d, pairs)
    lin = fam.linear_flops_per_token(d) * (35 + 3)
    assert tot["model_flops"] == lin + fam.attn_flops(d, pairs) + \
        4 * C.head_flops(d)
    # steps before admission carry nothing; a window over part of the
    # life carries part of it
    assert w.totals(0, 10)["model_flops"] == 0
    assert 0 < w.totals(10, 12)["model_flops"] < tot["model_flops"]
    t, mem = w.attn_roofline_s(0, 100, 1e12, 1e9)
    assert mem == 6 and t > 0  # every step memory-bound at these peaks
