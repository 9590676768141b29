"""Operations and bytes per serving step, from shapes alone, and the table
of chip peaks they are divided by.

These counts are the benchmark's yardstick: they follow the dense GQA
equations of the plain reference (``bench/reference.py``), not the
program's kernels, so a change to a kernel cannot change what it is
measured against.  A multiply-add is two operations.
"""
from __future__ import annotations

import dataclasses

#: Published peaks per chip, keyed by ``device_kind`` as JAX reports it.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of one chip; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


@dataclasses.dataclass(frozen=True)
class Dims:
    """The shapes of a dense GQA decoder that the counts need."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    mlp_bias: bool = False
    layernorm: bool = True
    dtype_bytes: int = 2

    @classmethod
    def from_model(cls, m: dict) -> "Dims":
        """From a configuration file's ``model`` block."""
        return cls(layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                   heads=m["num_attention_heads"],
                   kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
                   d_ff=m["intermediate_size"], vocab=m["vocab_size"],
                   mlp_bias=bool(m.get("use_bias", False)),
                   layernorm=m["norm_type"] == "layer_norm",
                   dtype_bytes={"bfloat16": 2, "float32": 4}[m["torch_dtype"]])

    # -- operations -----------------------------------------------------------
    def linear_flops_per_token(self) -> int:
        """q, k, v and output projections plus the two MLP matmuls, per
        layer and token."""
        d, hd = self.d_model, self.head_dim
        qkv = 2 * d * (self.heads + 2 * self.kv_heads) * hd
        out = 2 * self.heads * hd * d
        mlp = 2 * 2 * d * self.d_ff
        return qkv + out + mlp

    def attention_flops(self, attended: int) -> int:
        """Scores and weighted values for one query over ``attended`` keys,
        per layer."""
        return 4 * self.heads * self.head_dim * attended

    def head_flops(self, rows: int) -> int:
        return 2 * self.d_model * self.vocab * rows

    def prefill_flops(self, n: int) -> int:
        """One prompt of ``n`` true tokens: every layer at every position,
        causal attention, and the head at the last position only."""
        per_layer = n * self.linear_flops_per_token() + \
            self.attention_flops(1) * n * (n + 1) // 2
        return self.layers * per_layer + self.head_flops(1)

    def decode_flops(self, attended: list[int]) -> int:
        """One decode step; ``attended[i]`` is the number of cache rows the
        i-th active sequence attends to, its new row included."""
        b = len(attended)
        per_layer = b * self.linear_flops_per_token() + \
            sum(self.attention_flops(c) for c in attended)
        return self.layers * per_layer + self.head_flops(b)

    # -- bytes ----------------------------------------------------------------
    def norm_params(self) -> int:
        return (2 if self.layernorm else 1) * self.d_model   # scale (, bias)

    def layer_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        n = d * (self.heads + 2 * self.kv_heads) * hd + self.heads * hd * d
        n += 2 * d * self.d_ff + 2 * self.norm_params()
        if self.mlp_bias:
            n += self.d_ff + d
        return n

    def weight_bytes(self) -> int:
        """Every weight a decode step streams once: the layers, the final
        norm and the head, which is the embedding table when they are tied
        (an untied table is read row by row)."""
        n = (self.layers * self.layer_params() + self.norm_params()
             + self.d_model * self.vocab)
        return n * self.dtype_bytes

    def kv_row_bytes(self) -> int:
        """Keys and values of one position, all layers."""
        return self.layers * 2 * self.kv_heads * self.head_dim * self.dtype_bytes

    def decode_bytes(self, attended: list[int]) -> int:
        """Least bytes one decode step moves: the weights once, each active
        sequence's embedding row, its live cache rows read, its new rows
        written."""
        b = len(attended)
        return (self.weight_bytes() + b * self.d_model * self.dtype_bytes
                + self.kv_row_bytes() * (sum(attended) + b))
