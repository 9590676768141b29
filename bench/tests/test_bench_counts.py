"""The dense architecture's operation and byte counts
(``bench/models/dense_gqa.py``) against hand arithmetic, and the peaks
table."""
import pytest

import bench_tiny
import counts

ARCH = bench_tiny.arch()
# L=2, D=8, H=4, KV=2, hd=2, F=16, V=10, biased MLP, layer norms, bf16
SMALL = ARCH.Dims(layers=2, d_model=8, heads=4, kv_heads=2, head_dim=2,
                  d_ff=16, vocab=10, mlp_bias=True)


def test_linear_flops_per_token_by_hand():
    # qkv 2*8*(4+2*2)*2 = 256, out 2*4*2*8 = 128, MLP 2*2*8*16 = 512
    assert SMALL.linear_flops_per_token() == 256 + 128 + 512


def test_prefill_flops_by_hand():
    # n=3: per layer 3*896 linear + attention 4*4*2*(1+2+3) = 192, head 2*8*10
    assert SMALL.prefill_flops(3) == 2 * (3 * 896 + 192) + 160


def test_decode_flops_by_hand():
    # two slots attending 5 and 2 rows: per layer 2*896 + 32*(5+2), head 2*160
    assert SMALL.decode_flops([5, 2]) == 2 * (2 * 896 + 32 * 7) + 320


def test_decode_bytes_by_hand():
    # layer params: attention 8*8*2 + 4*2*8 = 192, MLP 2*8*16 = 256,
    # norms 2*16 = 32, biases 16 + 8 = 24 -> 504; weights 2*504 + 16 + 80
    assert SMALL.layer_params() == 504
    assert SMALL.weight_bytes() == 2 * (2 * 504 + 16 + 80)
    # a cache row: 2 layers * (k, v) * 2 heads * 2 dims * 2 bytes = 32
    assert SMALL.kv_row_bytes() == 32
    # weights + 2 embedding rows (2*8*2) + rows read (5+2) and written (2)
    assert SMALL.decode_bytes([5, 2]) == SMALL.weight_bytes() + 32 + 32 * 9


def test_dims_from_a_configuration_file():
    d = ARCH.dims(bench_tiny.MODEL)
    assert (d.layers, d.d_model, d.heads, d.kv_heads, d.head_dim, d.d_ff,
            d.vocab, d.mlp_bias, d.layernorm, d.dtype_bytes) == (
        2, 64, 4, 2, 16, 128, 512, True, True, 2)


def test_peaks_of_a_known_chip():
    p = counts.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks_for(kind)
