"""Quickstart: SWIS post-training quantization in 5 minutes.

1. Quantize a weight matrix with SWIS / SWIS-C / truncation and compare RMSE
   (paper Table 1).
2. Pack to the compressed bit-plane format and run the dequant-in-kernel
   matmul (the compiled Pallas kernel on a TPU, interpret mode elsewhere)
   against the dense result.
3. Quantize a whole model (PTQ) and compare task accuracy vs truncation.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.swis import QuantConfig, fake_quant, quantize, rmse
from repro.kernels import ops


def main():
    rng = np.random.default_rng(0)

    print("== 1. SWIS vs SWIS-C vs truncation (RMSE, group=4) ==")
    w = jnp.asarray(rng.normal(0, 0.05, (256, 128)).astype(np.float32))
    for n in (2, 3, 4):
        row = []
        for method in ("swis", "swis_c", "trunc"):
            q = fake_quant(w, QuantConfig(method=method, n_shifts=n,
                                          group_size=4))
            row.append(f"{method}={float(rmse(w, q)):.5f}")
        print(f"  N={n}: " + "  ".join(row))

    print("\n== 2. Packed bit-plane matmul (the TPU serving path) ==")
    qcfg = QuantConfig(method="swis", n_shifts=3, group_size=4)
    qw = quantize(w, qcfg)
    pw = packing.pack(qw)
    print(f"  compression: {pw.compression_ratio:.2f}x vs int8 "
          f"({pw.stored_bits / 8 / 1024:.1f} KiB packed)")
    x = jnp.asarray(rng.normal(0, 1, (16, 256)).astype(np.float32))
    y_packed = ops.swis_matmul(x, pw, use_pallas=True,
                               interpret=jax.default_backend() != "tpu")
    y_dense = x @ qw.qweights
    err = float(jnp.max(jnp.abs(y_packed - y_dense))
                / jnp.max(jnp.abs(y_dense)))
    print(f"  pallas-vs-dense rel err: {err:.2e}")

    print("\n== 3. Whole-model PTQ on a small LM ==")
    from benchmarks.common import quant_policy, trained_smoke_model

    cfg, params, eval_acc = trained_smoke_model(steps=200)
    print(f"  fp32 accuracy:        {eval_acc(cfg):.4f}")
    for n in (2, 3, 4):
        a_swis = eval_acc(cfg.replace(quant=quant_policy("swis", n)))
        a_tr = eval_acc(cfg.replace(quant=quant_policy("trunc", n)))
        print(f"  N={n}: swis={a_swis:.4f}  wgt-trunc={a_tr:.4f}")


if __name__ == "__main__":
    main()
