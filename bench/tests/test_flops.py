"""The operation and byte counts against hand counts at small shapes."""
from bench import flops, harness
from bench.references import dense_gqa

ARCH = dict(d_model=4, d_ff=6, n_heads=2, n_kv_heads=1, head_dim=2, n_layers=3,
            vocab_size=10)


def test_matmul_params_by_hand():
    # per layer: wq 4x4 + wk, wv 4x2 each + wo 4x4 + gate, up 4x6 + down 6x4
    per_layer = 16 + 8 + 8 + 16 + 24 + 24 + 24
    assert dense_gqa.matmul_params(ARCH) == 3 * per_layer + 4 * 10


def test_model_flops_per_token_by_hand():
    # 6 x 400 matrix parameters + 12 x layers 3 x heads 2 x head_dim 2 x seq 5
    assert dense_gqa.flops_per_token(ARCH, 5) == 6 * 400 + 12 * 3 * 2 * 2 * 5


def test_glm_cell_count_is_unchanged():
    """The GLM cell's count, read through its configuration's reference
    module, is the integer the harness counted when the count lived in
    ``bench/flops.py``: 35.1 TFLOP a step of 8 x 1024 tokens."""
    cell = harness.load_cell("glm4.coap8.b8s1024")
    arch, seq = harness.arch_fields(cell.config), cell.traffic["seq"]
    assert cell.reference.flops_per_token(arch, seq) == 4287627264
    assert dense_gqa.flops_per_token(arch, seq) == 4287627264
    assert 8 * 1024 * dense_gqa.flops_per_token(arch, seq) == 35124242546688


def test_fused_update_cost_by_hand():
    m, n, r = 8, 4, 2
    f, b = flops.fused_update_cost(m, n, r, quantize=False)
    assert f == 2 * (2 * m * n * r)
    assert b == 4 * m * n + 4 * m * n + 4 * n * r + 4 * (4 * m * r)
    f8, b8 = flops.fused_update_cost(m, n, r, quantize=True, block=256)
    assert f8 == f
    # int8 codes of M and V in and out, one fp32 scale per row of each
    assert b8 == 4 * m * n + 4 * m * n + 4 * n * r + 2 * (2 * m * r + 2 * 4 * m)


def test_projected_matrices_by_hand():
    arch = dict(ARCH, d_model=256, d_ff=512, n_heads=4, head_dim=64, vocab_size=1024)
    shapes = dense_gqa.layout(arch)
    got = flops.projected_matrices(shapes, {"rank": 64, "min_dim": 128})
    # embedding and norms stay dense, as do wk and wv (256 x 64: a side
    # under min_dim); the head and gate/up are taken transposed
    assert got == [
        ("lm_head/w", 1, 1024, 256, 64),
        ("stack/attn/wo", 3, 256, 256, 64),
        ("stack/attn/wq", 3, 256, 256, 64),
        ("stack/mlp/down", 3, 512, 256, 64),
        ("stack/mlp/gate", 3, 512, 256, 64),
        ("stack/mlp/up", 3, 512, 256, 64),
    ]
