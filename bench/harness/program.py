"""The system under test: the configuration's model, served by the
program's ``ContinuousBatchingEngine`` in its production mode (fused
chunked prefill, the paged Pallas attention kernel, bf16 KV arena).

This is the only module of the benchmark that imports the program, but
for a family's ``arch_config``, which imports the program's config types
after this module has put the program on the path. It builds the program's parameter tree from the seed
(``weights.draw_tree``), the engine from the configuration's deployment
fields, records what each step program was asked to do, and warms every
shape the traffic uses.
"""
from __future__ import annotations

import functools
import math
import os
import sys
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro.configs.base import ArchConfig  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.models.params import is_placeholder  # noqa: E402
from repro.serve import (ContinuousBatchingEngine, EngineConfig,  # noqa: E402
                         SamplingParams)
from repro.serve import engine as engine_mod  # noqa: E402
from repro.serve.quantized import pack_placeholders  # noqa: E402

from bench.harness import spec  # noqa: E402
from bench.harness import weights as W  # noqa: E402


def param_spec(arch: ArchConfig, swis: bool):
    """(shape, stacked, kind) per leaf of the program's parameter tree."""
    tree = Model(arch).build()
    if swis:
        tree = pack_placeholders(tree, arch.quant.cfg)

    def walk(node, path):
        if is_placeholder(node):
            return (tuple(node.shape), path.startswith("blocks"), "dense")
        if "mask_planes" in node:
            kw, c = node["sign_plane"].shape[-2:]
            lead = tuple(node["sign_plane"].shape[:-2])
            return (lead + (kw * 32, c), path.startswith("blocks"), "swis")
        return {k: walk(v, f"{path}/{k}" if path else k)
                for k, v in node.items()}

    return walk(tree, "")


def draw_params(cfg: dict, arch: ArchConfig, seed: int):
    spec = param_spec(arch, bool(cfg.get("swis")))
    params = W.draw_tree(spec, seed, cfg["vocab_size"], cfg.get("swis"))
    return jax.block_until_ready(params)


def engine_config(deploy: dict) -> EngineConfig:
    return EngineConfig(
        max_len=deploy["max_len"], n_slots=deploy["n_slots"],
        n_cache_blocks=deploy["n_cache_blocks"],
        prefill_chunk=deploy["prefill_chunk"], cache_dtype=jnp.bfloat16,
        fused_step=True, use_paged_kernel=True)


class Recorder:
    """Wraps the engine's two step programs. While ``on``, keeps each
    launch's row layout (device arrays, read back after the window);
    with ``annotate``, also writes a host span around each launch and
    around sampling into the profiler's trace. While ``keep_logits``,
    keeps every logits row that a served token is sampled from, by
    ``(rid, token index)``: each launch's logits start their copy to the
    host as they are returned and are read on the next launch, when the
    engine has long waited for them."""

    def __init__(self, engine):
        self.on = False
        self.annotate = False
        self.keep_logits = False
        self.calls: List[tuple] = []
        self.logits: Dict[tuple, np.ndarray] = {}
        self._held: List[tuple] = []  # (device logits, rows) not yet read
        self.engine = engine
        self.kv_block = engine.cache.block_size
        engine._mixed = self._wrap("mixed", engine._mixed)
        engine._decode = self._wrap("decode", engine._decode)
        self._sample = engine_mod.sample_step

    def _wrap(self, kind, fn):
        @functools.wraps(fn)
        def call(*args, **kw):
            if self.on:
                # mixed: (params, batch, cache, start, q_lens, last, tables)
                # decode: (params, token, cache, index, tables)
                self.calls.append((kind, args[3], args[4]))
            rows = self._token_rows(kind) if self.keep_logits else None
            if self.annotate:
                with jax.profiler.TraceAnnotation(f"bench.dispatch.{kind}"):
                    out = fn(*args, **kw)
            else:
                out = fn(*args, **kw)
            if rows:
                self.read_logits()
                out[0].copy_to_host_async()
                self._held.append((out[0], rows))
            return out
        return call

    def _token_rows(self, kind):
        """(row, rid, token index) of every row whose logits this launch
        samples a served token from: the live decode rows, and on a
        fused group's last chunk its rows' first tokens."""
        eng = self.engine
        sched = eng.scheduler
        rows = [(s, sched.slots[s].req.rid, len(sched.slots[s].tokens))
                for s in sched.decoding_slots()]
        if kind == "mixed":
            grp = eng._prefill_groups[0]
            if grp["done"] == grp["n_chunks"] - 1:
                rows += [(eng.n_slots + i, st.req.rid, 0)
                         for i, (_, st) in enumerate(grp["members"])]
        return rows

    def read_logits(self) -> None:
        """Move every held launch's rows to the host."""
        for dev, rows in self._held:
            host = np.asarray(dev).reshape(dev.shape[0], -1)
            for row, rid, j in rows:
                self.logits[(rid, j)] = host[row].copy()
        self._held = []

    def served_logits(self, rid: int, n: int):
        """The logits rows of request ``rid``'s ``n`` served tokens, or
        None where one is missing."""
        self.read_logits()
        rows = [self.logits.get((rid, j)) for j in range(n)]
        if any(r is None for r in rows):
            return None
        return np.stack(rows)

    def annotate_sampling(self, on: bool) -> None:
        if not on:
            engine_mod.sample_step = self._sample
            return
        sample = self._sample

        def traced(*args, **kw):
            with jax.profiler.TraceAnnotation("bench.sample"):
                return sample(*args, **kw)
        engine_mod.sample_step = traced

    def rows(self):
        """Per launch: its kind and its live rows as (start, n_new)."""
        out = []
        for kind, a, b in self.calls:
            a, b = np.asarray(a), np.asarray(b)
            if kind == "mixed":  # a: start, b: q_lens
                rows = [(int(s), int(n)) for s, n in zip(a, b) if n > 0]
            else:  # a: positions, b: block tables (trash block = idle row)
                rows = [(int(s), 1) for s, t in zip(a, b) if t[0] != 0]
            out.append((kind, rows))
        return out


def build(cfg: dict, deploy: dict, seed: int, family=None):
    """The engine and its recorder; ``family``: the configuration's
    family module (``spec.family``), looked up where not given."""
    family = family or spec.family(cfg)
    arch = family.arch_config(cfg)
    params = draw_params(cfg, arch, seed)
    engine = ContinuousBatchingEngine(arch, params, config=engine_config(
        deploy))
    del params
    return engine, Recorder(engine)


def submit(engine, prompt, max_tokens: int) -> int:
    return engine.submit(prompt, SamplingParams(max_tokens=int(max_tokens)))


def max_group(deploy: dict, mix: dict) -> int:
    """The most requests one chunk group can hold: a group is formed from
    requests admitted in one step, at most every slot, and in a closed
    loop at most every client."""
    n = deploy["n_slots"]
    return min(n, mix["clients"]) if mix["loop"] == "closed" else n


def warm_up(engine, deploy: dict, mix: dict, vocab: int, seed: int) -> dict:
    """Run every step shape the traffic can reach, then reset the engine.

    A chunk group of ``g`` requests admitted together launches the fused
    step at ``(n_slots + g, prefill_chunk)`` for its full chunks and at
    ``(n_slots + g, t)`` for its last one, ``t`` a power of two from 8 to
    ``prefill_chunk``. For each ``g`` up to :func:`max_group`, ``g``
    one-chunk prompts of each length ``t`` are sent at once: each length
    forms a group of ``g`` and takes one fused step (``t =
    prefill_chunk`` is the full chunk's shape). One request that decodes
    once more covers the decode step.
    """
    chunk, n_slots = deploy["prefill_chunk"], deploy["n_slots"]
    rng = np.random.default_rng(seed)
    tails = [1 << i for i in range(3, int(math.log2(chunk)) + 1)]
    shapes = 0
    for g in range(1, max_group(deploy, mix) + 1):
        # as many lengths at once as the slots hold whole groups of g
        per = max(1, n_slots // g)
        for lo in range(0, len(tails), per):
            for t in tails[lo:lo + per]:
                for _ in range(g):
                    submit(engine, rng.integers(0, vocab, t, np.int32), 1)
                shapes += 1
            engine.drain()
    submit(engine, rng.integers(0, vocab, 8, np.int32), 2)
    engine.drain()
    # the pos-plane scrub pads its block list to a power of two: take each
    # size up to every slot's blocks at once (block 0 is the trash block)
    n = 1
    while n <= max(1, n_slots * engine.cache.blocks_per_slot):
        engine.cache.invalidate_blocks([0] * n)
        n *= 2
    jax.block_until_ready(engine.cache.tree)
    engine.reset()
    return {"groups": shapes}


def memory_peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))
