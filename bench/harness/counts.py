"""Operations and bytes that the served model's work requires, from the
configuration's shapes alone. They count the same work whatever
implements it, and never less than the format requires:

* weights are read once per launch, bf16 at 2 bytes, SWIS layer weights
  at the paper's section 3.3 size, ``(M * (1 + N) + 3 * N) / M`` bits per
  weight (8 for N = 4 shifts in groups of M = 4; the program stores 9);
* attention reads each row's K/V at the row's actual length, not at the
  cache's capacity, and writes the K/V of each new token;
* logits are required once per row that asks for them.

And the table of peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Tuple

# Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s bf16 and 819 GB/s
# of HBM bandwidth per chip.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud TPU documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


@dataclass(frozen=True)
class Shapes:
    d: int
    f: int
    heads: int
    kv_heads: int
    head_dim: int
    layers: int
    vocab: int
    weight_bits: float  # per layer-GEMM weight, as the format requires
    kv_itemsize: int = 2
    embed_itemsize: int = 2

    @classmethod
    def from_config(cls, cfg: dict) -> "Shapes":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        swis = cfg.get("swis")
        if swis:
            m, n = swis["group_size"], swis["n_shifts"]
            bits = (m * (1 + n) + 3 * n) / m
        else:
            bits = 16.0
        return cls(d=d, f=cfg["intermediate_size"], heads=h,
                   kv_heads=cfg["num_key_value_heads"], head_dim=d // h,
                   layers=cfg["num_hidden_layers"],
                   vocab=cfg["vocab_size"], weight_bits=bits)

    # -- per token ------------------------------------------------------

    @property
    def layer_matmul_params(self) -> int:
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        per = self.d * q * 2 + self.d * kv * 2 + 3 * self.d * self.f
        return self.layers * per

    @property
    def unembed_params(self) -> int:
        return self.d * self.vocab

    @property
    def matmul_flops_per_token(self) -> int:
        """2 x every matmul parameter, the unembedding included."""
        return 2 * (self.layer_matmul_params + self.unembed_params)

    @property
    def kv_bytes_per_token(self) -> int:
        return (2 * self.layers * self.kv_heads * self.head_dim
                * self.kv_itemsize)

    @property
    def weight_bytes(self) -> float:
        """Weights one launch has to read: layer GEMMs at the format's
        size, the bf16 unembedding and the norm scales."""
        norms = (2 * self.layers + 1) * self.d * 2
        return (self.layer_matmul_params * self.weight_bits / 8
                + self.unembed_params * self.embed_itemsize + norms)

    # -- attention at actual lengths ------------------------------------

    def attention_flops(self, rows: Iterable[Tuple[int, int]]) -> int:
        """QK^T and PV over every layer; ``rows``: (start, n_new): the row's
        queries sit at positions start .. start + n_new - 1 and each sees
        itself and everything before it."""
        keys = sum(n * start + n * (n + 1) // 2 for start, n in rows)
        return 4 * self.layers * self.heads * self.head_dim * keys

    def kv_read_bytes(self, rows: Iterable[Tuple[int, int]]) -> int:
        return sum((start + n) for start, n in rows) * self.kv_bytes_per_token

    def kv_write_bytes(self, rows: Iterable[Tuple[int, int]]) -> int:
        return sum(n for _, n in rows) * self.kv_bytes_per_token

    # -- one launch ------------------------------------------------------

    def launch(self, rows) -> dict:
        """Required work of one step program over live ``rows``; each row
        asks for one row of logits."""
        rows = [(int(s), int(n)) for s, n in rows if n > 0]
        tokens = sum(n for _, n in rows)
        flops = (2 * self.layer_matmul_params * tokens
                 + 2 * self.unembed_params * len(rows)
                 + self.attention_flops(rows))
        act = tokens * self.d * self.embed_itemsize  # embedding rows read
        nbytes = (self.weight_bytes + self.kv_read_bytes(rows)
                  + self.kv_write_bytes(rows) + act)
        return {"flops": flops, "bytes": nbytes, "tokens": tokens,
                "rows": len(rows)}

    def attention_kernel(self, rows) -> dict:
        """Required work of the paged attention kernel over live ``rows``:
        its FLOPs, K/V read at actual length, queries read (bf16) and the
        float32 output written."""
        rows = [(int(s), int(n)) for s, n in rows if n > 0]
        tokens = sum(n for _, n in rows)
        io = tokens * self.layers * self.heads * self.head_dim * (2 + 4)
        return {"flops": self.attention_flops(rows),
                "bytes": self.kv_read_bytes(rows) + io}


def least_seconds(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """Roofline time of some work and which side bounds it."""
    t_c = flops / peak["flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
