"""Chip smoke test: the SWIS serve path once on a TPU, at the full width of
smollm-135m (30 layers, d_model 576, 9/3 heads, d_ff 1536, vocab 49152),
in bf16, with random weights from a fixed seed.

  python chip_smoke.py              # one chip: phases (a)-(e)
  python chip_smoke.py --chips 4    # four chips: sharded QAT training only

Phases, in order; any failure raises and the script exits non-zero:
  (a) device  — the platform must be ``tpu``; there is no CPU fallback;
  (b) serve   — continuous batching, bf16 KV cache, fused chunked prefill,
      the compiled paged Pallas attention kernel; a cold pass (compiles
      included) and a warm pass over requests of the same lengths;
  (c) paged   — one decode step's logits through the paged kernel against
      the gather path on the same cache state;
  (d) packed  — (b) again on SWIS bit-plane packed weights;
  (e) swis    — the compiled ``swis_matmul_packed`` kernel at every packed
      GEMM shape of the model against ``swis_matmul_ref``.
With ``--chips 4`` only: a few QAT training steps through ``Trainer`` on a
2x2 (data, model) mesh, compared with the same steps on one chip.

Timings go to earlier lines; the last line of standard output is one JSON
object, ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.configs as C  # noqa: E402
from repro.kernels.ref import swis_matmul_ref  # noqa: E402
from repro.kernels.swis_matmul import swis_matmul_packed  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import params as pp  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.serve import (ContinuousBatchingEngine, EngineConfig,  # noqa: E402
                         SamplingParams)
from repro.serve.quantized import is_packed  # noqa: E402

ARCH = "smollm-135m"
N_SLOTS = 8
MAX_LEN = 320
PREFILL_CHUNK = 16
NEW_TOKENS = 16
CHECK_TOKENS = 96
PROMPT_LENS = np.linspace(20, 200, N_SLOTS).astype(int)  # 20, 45, ..., 200
# (c): max |paged - gather| over max |gather| for bf16 logits. The two
# paths differ only in accumulation order (f32 online softmax vs one bf16
# softmax), a few bf16 ulps (2^-8) through 30 layers.
PAGED_RTOL = 3e-2
# (e): bf16 activations against a reference that rounds the scaled weights
# to bf16 before the matmul, where the kernel scales the f32 sum.
SWIS_RTOL = 2e-2
# --chips 4: first-step loss on the mesh against one chip. Only the order
# of the bf16 partial sums differs; a wrong sharding is off by far more.
TRAIN_RTOL = 5e-3
# --chips 4 trains 8 of the 30 layers at full width: QAT fake-quant keeps
# each weight group of 4 on the lane axis, padded 32x on a TPU, so all 30
# layers need 20.7 GB of HBM for one step on one chip (16 GB).
TRAIN_LAYERS = 8


class CompileClock:
    """Sums XLA compile time (a persistent-cache load counts as a compile)
    and counts the compiles the persistent cache served. Tracing and
    lowering are left in the wall times: nested jits would count twice."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def since(self, mark) -> str:
        s, n, h = mark
        return (f"XLA compile {self.seconds - s:.3f} s ({self.compiles - n} "
                f"programs, {self.cache_hits - h} from the persistent cache)")

    def mark(self):
        return self.seconds, self.compiles, self.cache_hits


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check_device(n_chips: int) -> dict:
    """(a): the device JAX sees; raises unless it is ``n_chips`` TPUs."""
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"(a) device: {dev}")
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found platform "
                         f"{dev['platform']!r} ({dev['kind']})")
    if dev["count"] < n_chips:
        raise SystemExit(f"--chips {n_chips} needs {n_chips} TPU chips; "
                         f"JAX found {dev['count']}")
    return dev


def prompts(vocab: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in PROMPT_LENS]


def serve_pass(engine, vocab: int, seed: int, clock: CompileClock) -> None:
    """Serve one request per prompt length and drain; every request must
    return exactly ``NEW_TOKENS`` tokens inside the vocabulary."""
    c0, t0 = clock.mark(), time.perf_counter()
    ps = prompts(vocab, seed)
    rids = [engine.submit(p, SamplingParams(max_tokens=NEW_TOKENS))
            for p in ps]
    out = engine.drain()
    wall = time.perf_counter() - t0
    for rid, p in zip(rids, ps):
        gen = out[rid][len(p):]
        if len(gen) != NEW_TOKENS or gen.min() < 0 or gen.max() >= vocab:
            raise AssertionError(
                f"request {rid} (prompt {len(p)}): {len(gen)} tokens in "
                f"[{gen.min()}, {gen.max()}], want {NEW_TOKENS} in "
                f"[0, {vocab})")
    log(f"    {len(rids)} requests x {NEW_TOKENS} tokens, prompts "
        f"{PROMPT_LENS.min()}-{PROMPT_LENS.max()}: wall {wall:.3f} s, "
        f"{clock.since(c0)}")


def build_engine(cfg, params, *, packed: bool):
    t0 = time.perf_counter()
    engine = ContinuousBatchingEngine(cfg, params, config=EngineConfig(
        max_len=MAX_LEN, n_slots=N_SLOTS, cache_dtype=jnp.bfloat16,
        prefill_chunk=PREFILL_CHUNK, fused_step=True, use_paged_kernel=True,
        packed=packed))
    log(f"    engine setup {time.perf_counter() - t0:.3f} s"
        + (f" (packed {engine.pack_stats['n_packed']} weight stacks)"
           if packed else ""))
    return engine


def paged_vs_gather(engine, vocab: int, seed: int) -> float:
    """(c): step a batch until every slot decodes, then score one decode
    step through the engine's paged kernel and through the gather path on
    the same cache state. Returns the relative max difference."""
    model = engine.model
    ps = prompts(vocab, seed)
    for p in ps:  # long enough that the shortest still decodes when the
        # longest finishes its prefill
        engine.submit(p, SamplingParams(max_tokens=CHECK_TOKENS))
    while engine.scheduler.gauges()["decoding_slots"] < N_SLOTS:
        if not engine.scheduler.pending():
            raise AssertionError("requests finished before all slots decoded")
        engine.step()
    toks, idxs, *_ = engine.scheduler.decode_batch(jax.random.key(0))
    args = (engine.params, jnp.asarray(toks)[:, None], engine.cache.tree,
            jnp.asarray(idxs), engine.cache.tables_device())
    paged = jax.jit(functools.partial(model.decode_step,
                                      paged=engine.paged_impl))(*args)[0]
    gather = jax.jit(functools.partial(model.decode_step,
                                       paged=None))(*args)[0]
    paged = np.asarray(paged, np.float32)[:, :vocab]
    gather = np.asarray(gather, np.float32)[:, :vocab]
    if not (np.isfinite(paged).all() and np.isfinite(gather).all()):
        raise AssertionError("non-finite logits")
    rel = float(np.abs(paged - gather).max() / np.abs(gather).max())
    agree = int((paged.argmax(-1) == gather.argmax(-1)).sum())
    log(f"(c) paged ({engine.paged_impl}) vs gather at positions "
        f"{idxs.min()}-{idxs.max()}: max|diff|/max|logit| = {rel:.6f} "
        f"(limit {PAGED_RTOL}), argmax agrees on {agree}/{N_SLOTS} rows")
    if rel > PAGED_RTOL:
        raise AssertionError(f"paged logits off by {rel} > {PAGED_RTOL}")
    engine.drain()
    return rel


def swis_kernel_check(packed_params, cfg, clock: CompileClock) -> dict:
    """(e): the compiled kernel once per distinct packed GEMM shape (the
    first layer's weight) against the jnp reference."""
    seen = {}

    def walk(node):
        if is_packed(node):
            shape = (node["sign_plane"].shape[-2] * 32,
                     node["sign_plane"].shape[-1])
            if shape not in seen:
                seen[shape] = jax.tree.map(lambda a: a[0], node)
        elif isinstance(node, dict):
            for v in node.values():
                walk(v)

    walk(packed_params)
    qcfg = cfg.quant.cfg
    consecutive = qcfg.method == "swis_c"
    rng = np.random.default_rng(0)
    errs = {}
    for (k, n), leaf in sorted(seen.items()):
        x = jnp.asarray(rng.normal(0, 1, (N_SLOTS, k)), jnp.bfloat16)
        planes = (leaf["sign_plane"], leaf["mask_planes"], leaf["shifts"],
                  leaf["scale"])
        group = k // leaf["shifts"].shape[0]
        n_shifts = int(leaf["mask_planes"].shape[0])
        c0, t0 = clock.mark(), time.perf_counter()
        got = swis_matmul_packed(x, *planes, n_shifts=n_shifts, group=group,
                                 consecutive=consecutive, interpret=False)
        got = np.asarray(got)
        dt, compiled = time.perf_counter() - t0, clock.since(c0)
        want = np.asarray(swis_matmul_ref(x, *planes, group=group,
                                          consecutive=consecutive))
        rel = float(np.abs(got - want).max() / np.abs(want).max())
        errs[f"{k}x{n}"] = rel
        log(f"(e) swis_matmul_packed {N_SLOTS}x{k} @ {k}x{n}: "
            f"max|diff|/max|ref| = {rel:.6f} (limit {SWIS_RTOL}), "
            f"wall {dt:.3f} s, {compiled}")
        if not rel <= SWIS_RTOL:
            raise AssertionError(f"swis kernel {k}x{n} off by {rel}")
    return errs


def one_chip(seed: int, clock: CompileClock) -> None:
    cfg = C.get_config(ARCH)
    c0, t0 = clock.mark(), time.perf_counter()
    params = pp.init_params(Model(cfg).build(), jax.random.key(seed))
    jax.block_until_ready(params)
    log(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab}, {cfg.compute_dtype}; params init "
        f"{time.perf_counter() - t0:.3f} s, {clock.since(c0)}")

    log("(b) serve")
    engine = build_engine(cfg, params, packed=False)
    if engine.paged_impl != "pallas":
        raise AssertionError(f"paged_impl is {engine.paged_impl!r}, "
                             f"want 'pallas'")
    log("    cold pass")
    serve_pass(engine, cfg.vocab, seed + 1, clock)
    log("    warm pass")
    serve_pass(engine, cfg.vocab, seed + 2, clock)
    paged_vs_gather(engine, cfg.vocab, seed + 3)
    del engine

    log("(d) packed serve")
    engine = build_engine(cfg, params, packed=True)
    del params
    log("    cold pass")
    serve_pass(engine, cfg.vocab, seed + 4, clock)
    errs = swis_kernel_check(engine.params, engine.cfg, clock)
    if len(errs) != 4:
        raise AssertionError(f"expected 4 packed GEMM shapes, got {errs}")


def four_chips(seed: int, steps: int, clock: CompileClock) -> None:
    """QAT training of the full-width model cut to ``TRAIN_LAYERS``: 2x2
    (data, model) mesh against one chip, same initial params, batches and
    steps."""
    from repro.configs.base import QuantPolicy
    from repro.core.swis import QuantConfig
    from repro.launch.mesh import make_mesh
    from repro.train.loop import Trainer

    cfg = C.get_config(ARCH).replace(n_layers=TRAIN_LAYERS, quant=QuantPolicy(
        cfg=QuantConfig(method="swis", n_shifts=3, group_size=4), mode="qat"))
    kw = dict(seq_len=256, global_batch=8, total_steps=steps, warmup=1,
              peak_lr=1e-3, seed=seed)
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    runs = {}
    for name, m in (("2x2 mesh", mesh), ("one chip", None)):
        c0, t0 = clock.mark(), time.perf_counter()
        out = Trainer(cfg, mesh=m, **kw).run(steps)
        runs[name] = out
        log(f"{name}: {steps} QAT steps, wall {time.perf_counter() - t0:.3f} "
            f"s, {clock.since(c0)}; losses {out['losses']}")
    spec = runs["2x2 mesh"]["state"].params["blocks"]["sub0_attn"]["attn"][
        "wq"]["w"].sharding.spec
    log(f"wq sharding on the mesh: {spec}")
    if "model" not in str(spec):
        raise AssertionError(f"wq is not sharded over 'model': {spec}")
    a, b = runs["2x2 mesh"]["losses"], runs["one chip"]["losses"]
    if not np.isfinite(a + b).all():
        raise AssertionError(f"non-finite losses {a} {b}")
    rel = abs(a[0] - b[0]) / abs(b[0])
    log(f"first-step loss {a[0]:.6f} (mesh) vs {b[0]:.6f} (one chip): "
        f"relative difference {rel:.2e} (limit {TRAIN_RTOL})")
    if rel > TRAIN_RTOL:
        raise AssertionError(f"first-step losses differ by {rel}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-training phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=3,
                    help="training steps of the --chips 4 phase")
    args = ap.parse_args()
    log(f"compile cache: {enable_compile_cache()}")
    dev = check_device(args.chips)
    clock, t0 = CompileClock(), time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed, args.steps, clock)
    else:
        one_chip(args.seed, clock)
    log(f"total: wall {time.perf_counter() - t0:.3f} s, "
        f"{clock.since((0.0, 0, 0))}")
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
