"""flops_per_sample and attention_cost against counts made by hand."""
from chipbench import manifest


def test_bert_base_t512_by_hand():
    cell = manifest.resolve('bert_base.t512')
    # per layer: qkv 768x2304, proj 768x768, ffn1 768x3072, ffn2 3072x768
    per_layer = 768 * 2304 + 768 * 768 + 768 * 3072 + 3072 * 768
    assert per_layer == 7077888
    encoder = 6 * 12 * per_layer * 512                  # 260.9 GFLOP
    attention = 12 * 12 * 768 * 512 * 512               # 29.0 GFLOP
    head = 6 * (768 * 768 + 768 * 30522) * 80           # 11.5 GFLOP
    pooler = 6 * (768 * 768 + 2 * 768)
    want = encoder + attention + head + pooler
    assert want == 301448586240
    assert cell.family.flops_per_sample(cell.config, cell.traffic) == want
    cost = cell.family.attention_cost(cell.config, cell.traffic)
    assert cost['flops'] == attention
    # 12 bf16 operand passes of (512, 768) and three of the float32 row
    # statistics (512 per head), per layer
    assert cost['bytes'] == 12 * (12 * 512 * 768 * 2 + 3 * 512 * 12 * 4)


def test_bert_base_t128_has_the_same_head_work_a_position():
    t512 = manifest.resolve('bert_base.t512')
    t128 = manifest.resolve('bert_base.t128')
    f = t512.family.flops_per_sample
    a, b = f(t512.config, t512.traffic), f(t128.config, t128.traffic)
    # a quarter of the positions and of the predictions; attention is
    # quadratic, so four t128 sequences need less than one t512
    assert 4 * b < a
    assert a - 4 * b == 12 * 12 * 768 * (512 * 512 - 4 * 128 * 128) \
        - 3 * 6 * (768 * 768 + 2 * 768)
    assert 4 * t128.traffic['per_chip_batch'] * t128.traffic['seq_len'] \
        == 4 * t512.traffic['per_chip_batch'] * t512.traffic['seq_len']


def test_gpt2_small_t1024_by_hand():
    cell = manifest.resolve('gpt2_small.t1024')
    blocks = 6 * 12 * 7077888 * 1024                    # 521.8 GFLOP
    head = 6 * 768 * 50257 * 1024                       # 237.1 GFLOP
    attention = 12 * 12 * 768 * 1024 * 1025 // 2        # lower triangle
    want = blocks + head + attention
    assert want == 817019486208
    assert cell.family.flops_per_sample(cell.config, cell.traffic) == want
    cost = cell.family.attention_cost(cell.config, cell.traffic)
    assert cost['flops'] == attention
    assert cost['bytes'] == 12 * (12 * 1024 * 768 * 2 + 3 * 1024 * 12 * 4)
