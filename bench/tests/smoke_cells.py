"""A checkout in miniature for the CPU tests: BENCHMARK.json, one smoke
configuration per weight format, small traffic, and the real metric
readers and model families, under a temporary root."""
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMOKE = {
    "name": "smoke", "model_type": "phi3", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "vocab_size": 500, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
    "deployment": {"n_slots": 4, "max_len": 128, "n_cache_blocks": 0,
                   "prefill_chunk": 16},
    # smoke-size readings (CPU): served tokens' gap up to 1.4e-3 and
    # logit error up to 0.026; the fp8 control 0.028-0.061 and 0.29-0.31
    "limits": {"widest_gap": 0.02, "logit_err": 0.08},
}
SWIS = dict(SMOKE, name="smoke-swis",
            swis={"method": "swis", "n_shifts": 4, "group_size": 4})
CHAT = {
    "loop": "open", "rate_per_s": 20.0,
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.6, "min": 8,
               "max": 90},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 2,
               "max": 30},
    "check": {"sample_seqs": 4, "min_tokens": 30},
}
DOC = {
    "loop": "closed", "clients": 2, "requests": 6, "strata": 2,
    "prompt": {"dist": "uniform", "min": 60, "max": 100},
    "output": {"dist": "uniform", "min": 8, "max": 20},
    "check": {"sample_seqs": 2, "min_tokens": 16},
}


# the real cells and the smoke cells that stand in for them
SMOKE_CELLS = {"phi3-bf16.longdoc": ["smoke.doc"]}
CHAT_CELLS = ["smoke.chat", "smoke-swis.chat"]
# what an open-loop chat cell declares beside the real metrics: its
# first-token time and the scheduler's queue wait
CHAT_E2E = {"name": "ttft_p50_ms", "unit": "ms", "better": "lower",
            "bound": 0.25, "source": "host_clock", "workloads": CHAT_CELLS}
CHAT_LAYER = {"name": "queue_wait_p50_ms", "unit": "ms", "better": "lower",
              "source": "program_span", "layer": "scheduler",
              "moves": "ttft_p50_ms", "workloads": CHAT_CELLS}


def benchmark_json(bench_file: str) -> dict:
    with open(bench_file) as f:
        real = json.load(f)
    return {
        "command": real["command"], "paths": real["paths"],
        "run_seconds": 2,
        "configs": [
            {"name": "smoke", "source": "test", "file":
             "bench/configs/smoke.json", "reduced": [], "why": "test"},
            {"name": "smoke-swis", "source": "test", "file":
             "bench/configs/smoke-swis.json", "reduced": [], "why": "test"}],
        "workloads": [
            {"name": "smoke.chat", "config": "smoke", "traffic": "chat",
             "chips": 1, "why": "test"},
            {"name": "smoke-swis.chat", "config": "smoke-swis",
             "traffic": "chat", "chips": 1, "why": "test"},
            {"name": "smoke.doc", "config": "smoke", "traffic": "doc",
             "chips": 1, "why": "test"}],
        "end_to_end": real["end_to_end"],
        "per_layer": real["per_layer"],
    }


def make_root(tmp) -> str:
    """A miniature checkout under ``tmp``; the metric readers and the
    model families are the real ones."""
    root = str(tmp)
    os.makedirs(os.path.join(root, "bench", "configs"))
    os.makedirs(os.path.join(root, "bench", "traffic"))
    for folder in ("metrics", "families"):
        shutil.copytree(os.path.join(BENCH, folder),
                        os.path.join(root, "bench", folder),
                        ignore=shutil.ignore_patterns("__pycache__"))
    real = os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")
    bench = benchmark_json(real)
    # the real metrics, pointed at the smoke cells of the same traffic,
    # and the chat cells' own
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [name for w in m["workloads"]
                              for name in SMOKE_CELLS[w]]
    bench["end_to_end"].append(CHAT_E2E)
    bench["per_layer"].append(CHAT_LAYER)
    files = {"BENCHMARK.json": bench,
             "bench/configs/smoke.json": SMOKE,
             "bench/configs/smoke-swis.json": SWIS,
             "bench/traffic/chat.json": CHAT,
             "bench/traffic/doc.json": DOC}
    for rel, obj in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)
    return root
