"""One function per paper table/figure. Each returns rows
(name, us_per_call, derived) for the CSV printed by benchmarks.run."""
from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import quant_policy, time_us, trained_smoke_model

Row = Tuple[str, float, str]


# ---------------------------------------------------------------------------
# Fig. 2 — P(lossless quantization), Eqs. 8-10
# ---------------------------------------------------------------------------

def fig2_lossless_probability() -> List[Row]:
    from repro.core import probability as P

    rows: List[Row] = []
    us = time_us(lambda: P.lossless_table(), n=10)
    for n in range(1, 9):
        rows.append((f"fig2/swis/N{n}", us, f"{P.p_lossless_swis(n):.6f}"))
        rows.append((f"fig2/swis_c/N{n}", us, f"{P.p_lossless_swis_c(n):.6f}"))
        rows.append((f"fig2/layerwise/N{n}", us,
                     f"{P.p_lossless_layerwise(n):.6f}"))
    return rows


# ---------------------------------------------------------------------------
# Table 1 — RMSE of SWIS / SWIS-C / layer-wise truncation
# ---------------------------------------------------------------------------

def table1_rmse() -> List[Row]:
    from repro.core.swis import QuantConfig, fake_quant, rmse

    rows: List[Row] = []
    rng = np.random.default_rng(0)
    # resnet18-conv1-like (K=7*7*3 -> 148 padded; bell-shaped) and
    # mobilenet-pw1-like (K=32; heavier tails) weight matrices
    layers = {
        "resnet_conv": rng.normal(0, 0.05, (148, 64)).astype(np.float32),
        "mobilenet_pw": (rng.standard_t(4, (32, 96)) * 0.04).astype(np.float32),
    }
    for lname, w in layers.items():
        wj = jnp.asarray(w)
        for g in (1, 4):
            for n in (2, 3, 4, 5):
                for m in ("swis", "swis_c", "trunc"):
                    if g == 1 and m == "trunc":
                        g_eff = 1
                    cfg = QuantConfig(method=m, n_shifts=n, group_size=g)

                    def f(cfg=cfg):
                        return rmse(wj, fake_quant(wj, cfg))
                    us = time_us(f, n=1)
                    rows.append((f"table1/{lname}/g{g}/N{n}/{m}", us,
                                 f"{float(f()):.5f}"))
    return rows


# ---------------------------------------------------------------------------
# Fig. 5 — weight storage compression ratios (+ DPRed)
# ---------------------------------------------------------------------------

def fig5_compression() -> List[Row]:
    from repro.core.packing import compression_ratio, dpred_compression

    rows: List[Row] = []
    rng = np.random.default_rng(0)
    mags = np.abs(rng.normal(0, 24, (4096, 64))).clip(0, 255).round()
    for g in (2, 4, 8, 16):
        for n in (2, 3, 4, 5, 6):
            rows.append((f"fig5/swis/g{g}/N{n}", 0.0,
                         f"{compression_ratio(g, n, 'swis'):.3f}"))
            rows.append((f"fig5/swis_c/g{g}/N{n}", 0.0,
                         f"{compression_ratio(g, n, 'swis_c'):.3f}"))
        rows.append((f"fig5/dpred/g{g}", 0.0,
                     f"{dpred_compression(mags, g):.3f}"))
    return rows


# ---------------------------------------------------------------------------
# Fig. 3 — PE area / energy / throughput-per-area
# ---------------------------------------------------------------------------

def fig3_pe() -> List[Row]:
    from repro.perfmodel.pe import PE_LIBRARY

    rows: List[Row] = []
    for name in ("swis_ss", "swis_ds"):
        pe = PE_LIBRARY[name]
        for n in (2, 4, 6):
            e = pe.energy_per_mac_pj(n)
            rows.append((f"fig3/{name}/energy_pj/N{n}", 0.0, f"{e:.4f}"))
            tpa = pe.macs_per_cycle(n) / pe.area_mm2()
            rows.append((f"fig3/{name}/macs_per_cyc_mm2/N{n}", 0.0,
                         f"{tpa:.1f}"))
        rows.append((f"fig3/{name}/area_mm2", 0.0, f"{pe.area_mm2():.5f}"))
    return rows


# ---------------------------------------------------------------------------
# Fig. 1 — DRAM weight/activation access ratio (ResNet-18)
# ---------------------------------------------------------------------------

def fig1_dram_ratio() -> List[Row]:
    from repro.perfmodel.evaluate import fig1_dram_ratio as f1

    rows = []
    for name, ratio in f1():
        rows.append((f"fig1/resnet18/{name}", 0.0, f"{ratio:.2f}"))
    return rows


# ---------------------------------------------------------------------------
# Table 4 — F/J and F/s for all accelerator configs
# ---------------------------------------------------------------------------

def table4_performance() -> List[Row]:
    from repro.perfmodel.evaluate import evaluate_table4, headline_ratios

    rows: List[Row] = []
    t0 = time.perf_counter()
    table = evaluate_table4()
    us = (time.perf_counter() - t0) * 1e6 / max(len(table), 1)
    for r in table:
        key = f"table4/{r['network']}/{r['point']}/{r['config']}/S{r['n_shifts']}"
        rows.append((key + "/fps", us, f"{r['frames_per_s']:.2f}"))
        rows.append((key + "/fpj", us, f"{r['frames_per_j']:.2f}"))
    for k, v in headline_ratios().items():
        rows.append((f"table4/headline/{k}", 0.0, f"{v:.3f}"))
    return rows


# ---------------------------------------------------------------------------
# Table 3 — post-training quantization accuracy (synthetic task; orderings)
# ---------------------------------------------------------------------------

def table3_ptq() -> List[Row]:
    cfg, params, eval_acc = trained_smoke_model()
    rows: List[Row] = []
    base = eval_acc(cfg)
    rows.append(("table3/baseline_fp32", 0.0, f"{base:.4f}"))
    for n in (2, 2.5, 3, 4):
        for m, ds in (("swis", False), ("swis", True), ("swis_c", False),
                      ("swis_c", True)):
            qcfg = cfg.replace(quant=quant_policy(m, n, ds=ds))
            t0 = time.perf_counter()
            acc = eval_acc(qcfg)
            us = (time.perf_counter() - t0) * 1e6
            tag = "ds" if ds else "ss"
            rows.append((f"table3/{m}_{tag}/N{n}", us, f"{acc:.4f}"))
        if float(n).is_integer():
            qcfg = cfg.replace(quant=quant_policy("trunc", n))
            rows.append((f"table3/wgt_trunc/N{n}", 0.0,
                         f"{eval_acc(qcfg):.4f}"))
            qcfg = cfg.replace(quant=quant_policy("act_trunc", n))
            rows.append((f"table3/act_trunc/N{n}", 0.0,
                         f"{eval_acc(qcfg):.4f}"))
    return rows


# ---------------------------------------------------------------------------
# Table 2 / §4.3 — filter scheduling benefit
# ---------------------------------------------------------------------------

def table2_scheduling() -> List[Row]:
    cfg, params, eval_acc = trained_smoke_model()
    rows: List[Row] = []
    for n in (2, 2.5, 3):
        for ds in (False, True):
            qcfg = cfg.replace(quant=quant_policy("swis", n, ds=ds,
                                                  schedule=True))
            tag = "double" if ds else "single"
            rows.append((f"table2/sched_{tag}/N{n}", 0.0,
                         f"{eval_acc(qcfg):.4f}"))
        if float(n).is_integer():
            qcfg = cfg.replace(quant=quant_policy("swis", n, schedule=False))
            rows.append((f"table2/none/N{n}", 0.0, f"{eval_acc(qcfg):.4f}"))
    # offline exact scheduler (§4.3 two-phase) on a real weight matrix
    from repro.core import scheduling
    from repro.core.swis import QuantConfig, _to_int_domain, _column_costs

    w = params["blocks"]["sub0_attn"]["mlp"]["wi"]["w"][0]
    qc = QuantConfig(n_shifts=3, group_size=4)
    mags, signs, _ = _to_int_domain(jnp.asarray(w, jnp.float32), 8, False)

    def cost_fn(n):
        _, c = _column_costs(mags, signs, n, qc)
        return np.asarray(c)

    sched25 = scheduling.schedule_layer(cost_fn, 2.5, levels=[1, 2, 3, 4],
                                        sa_cols=8)
    rows.append(("table2/offline/effective_shifts", 0.0,
                 f"{sched25.effective_shifts:.3f}"))
    # iso-budget: scheduled average-3 must never cost more than uniform 3
    sched3 = scheduling.schedule_layer(cost_fn, 3.0, levels=[2, 3, 4],
                                       sa_cols=8)
    uniform3 = float(cost_fn(3).sum())
    rows.append(("table2/offline/cost_sched3_vs_uniform3", 0.0,
                 f"{sched3.total_cost / uniform3:.3f}"))
    # the fractional point sits strictly between its integer neighbours
    uniform2 = float(cost_fn(2).sum())
    rows.append(("table2/offline/cost_sched2.5_vs_uniform2", 0.0,
                 f"{sched25.total_cost / uniform2:.3f}"))
    return rows


# ---------------------------------------------------------------------------
# Table 5 — quantization-aware retraining recovers accuracy
# ---------------------------------------------------------------------------

def table5_retraining() -> List[Row]:
    from repro.train.loop import Trainer

    rows: List[Row] = []
    cfg, params, eval_acc = trained_smoke_model()
    n = 2
    ptq = cfg.replace(quant=quant_policy("swis", n))
    acc_ptq = eval_acc(ptq)
    rows.append((f"table5/ptq_swis/N{n}", 0.0, f"{acc_ptq:.4f}"))
    # QAT: continue training WITH swis fake-quant in the graph (STE)
    qat_cfg = cfg.replace(quant=quant_policy("swis", n))
    qat_cfg = qat_cfg.replace(quant=qat_cfg.quant.__class__(
        cfg=qat_cfg.quant.cfg, mode="qat"))
    # Table 5 = RETRAINING: warm-start from a COPY of the fp32-trained
    # weights (the train step donates its state; identity tree.map would
    # alias — and invalidate — the shared cached params)
    import jax as _jax
    import jax.numpy as _jnp

    tr = Trainer(qat_cfg, seq_len=64, global_batch=16, total_steps=150,
                 warmup=10, peak_lr=5e-4,
                 init_params=_jax.tree.map(_jnp.array, params))
    t0 = time.perf_counter()
    out = tr.run(150)
    us = (time.perf_counter() - t0) * 1e6 / 150
    acc_qat = eval_acc(ptq, eval_params=out["state"].params)
    rows.append((f"table5/qat_swis/N{n}", us, f"{acc_qat:.4f}"))
    trunc = cfg.replace(quant=quant_policy("trunc", n))
    rows.append((f"table5/ptq_trunc/N{n}", 0.0, f"{eval_acc(trunc):.4f}"))
    return rows


# ---------------------------------------------------------------------------
# Fig. 6 — accuracy (RMSE proxy + task accuracy) vs group size
# ---------------------------------------------------------------------------

def fig6_groupsize() -> List[Row]:
    from repro.core.swis import QuantConfig, fake_quant, rmse

    cfg, params, eval_acc = trained_smoke_model()
    rows: List[Row] = []
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(0, 0.04, (256, 128)).astype(np.float32))
    for g in (1, 2, 4, 8, 16):
        for n in (2, 3, 4):
            for m in ("swis", "swis_c"):
                q = fake_quant(w, QuantConfig(method=m, n_shifts=n,
                                              group_size=g))
                rows.append((f"fig6/rmse/{m}/g{g}/N{n}", 0.0,
                             f"{float(rmse(w, q)):.5f}"))
    for g in (2, 4, 8):
        qcfg = cfg.replace(quant=quant_policy("swis", 3, group=g))
        rows.append((f"fig6/acc/swis/g{g}/N3", 0.0, f"{eval_acc(qcfg):.4f}"))
    return rows


# ---------------------------------------------------------------------------
# Kernel microbenchmark (Pallas vs jnp reference; the kernel is
# interpreted off the TPU)
# ---------------------------------------------------------------------------

def kernel_bench() -> List[Row]:
    from repro.core import packing, swis
    from repro.kernels import ops

    rows: List[Row] = []
    rng = np.random.default_rng(0)
    interpret = jax.default_backend() != "tpu"
    for (mm, kk, nn, g, ns) in [(64, 512, 256, 4, 3), (128, 1024, 512, 8, 2)]:
        w = rng.normal(0, 0.05, (kk, nn)).astype(np.float32)
        x = jnp.asarray(rng.normal(0, 1, (mm, kk)).astype(np.float32))
        qw = swis.quantize(jnp.asarray(w),
                           swis.QuantConfig(n_shifts=ns, group_size=g))
        pw = packing.pack(qw)
        us_ref = time_us(lambda: ops.swis_matmul(x, pw, use_pallas=False))
        us_pal = time_us(lambda: ops.swis_matmul(x, pw, use_pallas=True,
                                                 interpret=interpret))
        rows.append((f"kernel/swis_matmul_ref/{mm}x{kk}x{nn}/g{g}N{ns}",
                     us_ref, "jnp"))
        rows.append((f"kernel/swis_matmul_pallas/{mm}x{kk}x{nn}/g{g}N{ns}",
                     us_pal, "interpret" if interpret else "compiled"))
        us_q = time_us(lambda: swis.fake_quant(
            jnp.asarray(w), swis.QuantConfig(n_shifts=ns, group_size=g)))
        rows.append((f"kernel/quantize/{kk}x{nn}/g{g}N{ns}", us_q, "ptq"))
    return rows


ALL = [
    fig2_lossless_probability,
    table1_rmse,
    fig5_compression,
    fig3_pe,
    fig1_dram_ratio,
    table4_performance,
    table2_scheduling,
    table3_ptq,
    table5_retraining,
    fig6_groupsize,
    kernel_bench,
]
