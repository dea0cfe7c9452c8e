import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes and record memory / cost / collective analyses.

MUST be run as its own process (the 512-device XLA flag above is set before
any other import, including jax). Results are cached as JSON per cell under
--out; re-runs skip completed cells, so the full sweep is resumable.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-moe-a2.7b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro.configs.base import ArchConfig, SHAPES, ShapeConfig, shape_applicable, QuantPolicy
from repro.core.swis import QuantConfig
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh
from repro.models import params as pp
from repro.models.model import Model
from repro.optim import AdamW
from repro.optim.schedule import warmup_cosine
from repro.parallel import ctx as par_ctx
from repro.parallel.sharding import Rules
from repro.serve.quantized import pack_placeholders
from repro.train.steps import TrainState, make_train_step


def _abstract_state(model: Model, rules: Rules) -> tuple[Any, Any]:
    tree = model.build()
    params = pp.abstract_params(tree)
    opt = {"m": pp.abstract_params(tree), "v": pp.abstract_params(tree)}
    state = TrainState(step=jax.ShapeDtypeStruct((), jnp.int32),
                       params=params, opt=opt)
    cfgp = model.cfg.parallel
    psh = rules.param_shardings(tree, fsdp=cfgp.fsdp_params)
    osh = {"m": rules.param_shardings(tree, fsdp=cfgp.fsdp_opt),
           "v": rules.param_shardings(tree, fsdp=cfgp.fsdp_opt)}
    sh = TrainState(step=rules.replicated(), params=psh, opt=osh)
    return state, sh


def _active_params(cfg: ArchConfig, tree) -> float:
    """Parameter count weighted by MoE activation fraction."""
    total = 0.0
    flat = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=pp.is_placeholder)[0]
    for path, leaf in flat:
        n = float(np.prod(leaf.shape))
        keys = "/".join(str(getattr(p, "key", p)) for p in path)
        if cfg.moe is not None and any(
                k in keys for k in ("/wi", "/wo", "/wg")) and "shared" not in keys \
                and "moe" in keys:
            n *= cfg.moe.top_k / cfg.moe.n_experts
        total += n
    return total


def _build_lowered(model_cfg: ArchConfig, shape: ShapeConfig, mesh, *,
                   quant: str, qcfg: QuantConfig):
    """Lower one step function for the given config (no compile)."""
    if shape.kind != "train" and model_cfg.parallel.sp:
        # measured (EXPERIMENTS.md §Perf): for fwd-only serving, plain TP
        # (one AR per block) beats SP (two AGs + RS) on wire bytes
        model_cfg = model_cfg.replace(parallel=dataclasses.replace(
            model_cfg.parallel, sp=False))
    model = Model(model_cfg)
    rules = Rules.for_arch(mesh, model_cfg)

    with par_ctx.use_rules(rules), mesh:
        if shape.kind == "train":
            gather_sh = None
            if model_cfg.parallel.fsdp_params:
                # pin the bf16 copy to the FSDP spec: the per-layer ZeRO-3
                # gather then happens inside the scan (bounded memory) but
                # provably on compute-dtype bytes (2x wire saving vs fp32)
                gather_sh = rules.param_shardings(model.build(), fsdp=True)
            step = make_train_step(model, AdamW(),
                                   warmup_cosine(1e-4, 100, 10000),
                                   compute_shardings=gather_sh)
            state, state_sh = _abstract_state(model, rules)
            batch = model.input_specs(shape)
            batch_sh = rules.batch_specs(batch)
            lowered = jax.jit(
                step, in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None), donate_argnums=(0,)
            ).lower(state, batch)
        elif shape.kind == "prefill":
            tree = model.build()
            if quant != "off":
                tree = pack_placeholders(tree, qcfg)
            # serving runs on compute-dtype params (bf16); packed leaves
            # keep their explicit uint32/int8 plane dtypes
            params = pp.abstract_params(tree, dtype=jnp.bfloat16)
            psh = rules.param_shardings(tree)
            batch = model.input_specs(shape)
            batch_sh = rules.batch_specs(batch)
            if model_cfg.family == "encoder":
                def fn(p, b):
                    return model.apply(p, b)[0]
                lowered = jax.jit(fn, in_shardings=(psh, batch_sh)
                                  ).lower(params, batch)
            else:
                ctree = model.build_cache(shape.global_batch, shape.seq_len,
                                          jnp.bfloat16)
                cache = pp.abstract_params(ctree)
                csh = rules.param_shardings(ctree)
                lowered = jax.jit(
                    model.prefill, in_shardings=(psh, batch_sh, csh),
                    donate_argnums=(2,)).lower(params, batch, cache)
        else:  # decode
            tree = model.build()
            if quant != "off":
                tree = pack_placeholders(tree, qcfg)
            params = pp.abstract_params(tree, dtype=jnp.bfloat16)
            psh = rules.param_shardings(tree)
            ctree = model.build_cache(shape.global_batch, shape.seq_len,
                                      jnp.bfloat16)
            cache = pp.abstract_params(ctree)
            csh = rules.param_shardings(ctree)
            tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
            idx = jax.ShapeDtypeStruct((), jnp.int32)

            if model_cfg.family == "vlm":
                patches = jax.ShapeDtypeStruct(
                    (shape.global_batch, model_cfg.vlm.n_patches,
                     model_cfg.vlm.vision_dim), jnp.bfloat16)

                def fn(p, t, c, i, pt):
                    logits, c2, _ = model.apply(
                        p, {"tokens": t, "patches": pt}, cache=c,
                        cache_index=i)
                    return logits[:, -1], c2

                lowered = jax.jit(
                    fn,
                    in_shardings=(psh, rules.batch_specs(tok), csh,
                                  rules.replicated(),
                                  rules.batch_specs(patches)),
                    donate_argnums=(2,),
                ).lower(params, tok, cache, idx, patches)
            else:
                lowered = jax.jit(
                    model.decode_step,
                    in_shardings=(psh, rules.batch_specs(tok), csh,
                                  rules.replicated()),
                    donate_argnums=(2,),
                ).lower(params, tok, cache, idx)

    return lowered


def _compiled_costs(compiled) -> Dict[str, float]:
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = RL.collective_bytes(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collective_wire": float(coll["total"]),
        "collective_operand": float(coll["operand_total"]),
        "collectives": {k: v for k, v in coll.items()
                        if k in RL.COLLECTIVES},
        "collective_counts": coll["counts"],
    }


def _shallow_cfg(cfg: ArchConfig, k_units: int) -> ArchConfig:
    """Reduced-depth, unrolled, single-microbatch config for exact costing."""
    unit_len = len(Model(cfg).unit)
    tail = cfg.n_layers % unit_len
    return cfg.replace(
        n_layers=k_units * unit_len + tail,
        parallel=dataclasses.replace(cfg.parallel, scan_layers=False,
                                     grad_accum=1),
    )


def lower_cell(cfg: ArchConfig, shape: ShapeConfig, mesh, *,
               quant: str = "qat", qcfg: Optional[QuantConfig] = None,
               save_hlo: Optional[str] = None,
               depth_correct: bool = True) -> Dict[str, Any]:
    """Lower + compile one (arch x shape x mesh) cell; return the record.

    XLA's cost analysis counts a while-loop (lax.scan) body ONCE, so the
    full scanned compile (which proves sharding coherence + memory fit)
    undercounts flops/bytes/collectives. We therefore also compile the model
    at 1 and 2 pattern units (unrolled, exact) and extrapolate linearly:
    total = cost(1 unit + tail) + (n_units - 1) * [cost(2u) - cost(1u)].
    """
    qcfg = qcfg or QuantConfig(method="swis", n_shifts=4, group_size=4)
    model_cfg = cfg
    if shape.kind == "train":
        model_cfg = cfg.replace(
            quant=QuantPolicy(cfg=qcfg, mode="qat" if quant == "qat" else "off"))

    t0 = time.monotonic()
    lowered = _build_lowered(model_cfg, shape, mesh, quant=quant, qcfg=qcfg)
    t_lower = time.monotonic() - t0
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0 - t_lower

    mem = compiled.memory_analysis()
    raw = _compiled_costs(compiled)
    if save_hlo:
        import gzip
        with gzip.open(save_hlo, "wt") as f:
            f.write(compiled.as_text())
    del compiled, lowered

    # --- depth-differential cost correction ---
    n_units = Model(model_cfg).n_units
    corrected = dict(raw)
    per_unit = None
    if depth_correct and n_units > 2:
        shallow = []
        for k in (1, 2):
            scfg = _shallow_cfg(model_cfg, k)
            low = _build_lowered(scfg, shape, mesh, quant=quant, qcfg=qcfg)
            shallow.append(_compiled_costs(low.compile()))
        per_unit = {f: shallow[1][f] - shallow[0][f]
                    for f in ("flops", "bytes_accessed", "collective_wire",
                              "collective_operand")}
        corrected = {
            f: shallow[0][f] + (n_units - 1) * per_unit[f]
            for f in per_unit
        }
        corrected["collectives"] = {
            k: shallow[0]["collectives"][k]
            + (n_units - 1) * (shallow[1]["collectives"][k]
                               - shallow[0]["collectives"][k])
            for k in RL.COLLECTIVES
        }

    chips = int(np.prod(list(mesh.shape.values())))
    flops = corrected["flops"]
    bytes_accessed = corrected["bytes_accessed"]
    terms = RL.roofline_terms(flops, bytes_accessed,
                              corrected["collective_wire"])

    tree = Model(cfg).build()
    n_params = pp.count_params(tree)
    n_active = _active_params(cfg, tree)
    tokens = shape.global_batch * (shape.seq_len
                                   if shape.kind in ("train", "prefill")
                                   else 1)
    mf_global = RL.model_flops(n_params, n_active, tokens,
                               "train" if shape.kind == "train" else "fwd")
    mf_per_chip = mf_global / chips

    record = {
        "arch": cfg.name,
        "shape": shape.name,
        "kind": shape.kind,
        "mesh": dict(mesh.shape),
        "chips": chips,
        "quant": quant,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
        },
        "cost_raw_scan": {k: raw[k] for k in
                          ("flops", "bytes_accessed", "collective_wire")},
        "cost": {"flops": flops, "bytes_accessed": bytes_accessed,
                 "collective_wire": corrected["collective_wire"],
                 "collective_operand": corrected["collective_operand"]},
        "cost_per_unit": per_unit,
        "n_units": n_units,
        "collectives": corrected.get("collectives", raw["collectives"]),
        "collective_counts": raw["collective_counts"],
        "roofline": terms,
        "model_flops_per_chip": mf_per_chip,
        "useful_flops_fraction": (mf_per_chip / flops) if flops else 0.0,
        "n_params": n_params,
        "n_active_params": n_active,
    }
    return record


def cell_name(arch: str, shape: str, mesh_kind: str, quant: str) -> str:
    return f"{arch}__{shape}__{mesh_kind}__{quant}"


def run_cells(cells, out_dir: str, quant: str = "qat", force: bool = False):
    """Lower and compile each cell; returns the names of the cells that
    failed."""
    os.makedirs(out_dir, exist_ok=True)
    meshes = {}
    failed = []
    for arch_id, shape_name, mesh_kind in cells:
        name = cell_name(arch_id, shape_name, mesh_kind, quant)
        path = os.path.join(out_dir, name + ".json")
        if os.path.exists(path) and not force:
            print(f"[skip] {name}")
            continue
        cfg = C.get_config(arch_id)
        shape = SHAPES[shape_name]
        ok, why = shape_applicable(cfg, shape)
        if not ok:
            rec = {"arch": arch_id, "shape": shape_name, "mesh": mesh_kind,
                   "skipped": why}
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"[n/a ] {name}: {why}")
            continue
        if mesh_kind not in meshes:
            meshes[mesh_kind] = make_production_mesh(
                multi_pod=(mesh_kind == "multi"))
        print(f"[run ] {name} ...", flush=True)
        try:
            rec = lower_cell(cfg, shape, meshes[mesh_kind], quant=quant)
            rec["mesh_kind"] = mesh_kind
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            r = rec["roofline"]
            print(f"  ok lower={rec['lower_s']}s compile={rec['compile_s']}s "
                  f"compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
                  f"coll={r['collective_s']:.4f}s -> {r['bottleneck']}",
                  flush=True)
        except Exception as e:
            print(f"  FAIL {type(e).__name__}: {e}")
            traceback.print_exc()
            with open(os.path.join(out_dir, name + ".err"), "w") as f:
                f.write(traceback.format_exc())
            failed.append(name)
    return failed


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--quant", default="qat", choices=["qat", "off"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    mesh_kinds = (["single", "multi"] if args.mesh == "both"
                  else [args.mesh])
    if args.all:
        archs = list(C.ARCH_IDS)
        shapes = list(SHAPES)
    else:
        archs = [args.arch] if args.arch else list(C.ARCH_IDS)
        shapes = [args.shape] if args.shape else list(SHAPES)
    cells = [(a, s, m) for a in archs for s in shapes for m in mesh_kinds]
    failed = run_cells(cells, args.out, quant=args.quant, force=args.force)
    if failed:
        sys.exit(f"{len(failed)} of {len(cells)} cells failed: "
                 f"{', '.join(failed)}")


if __name__ == "__main__":
    main()
