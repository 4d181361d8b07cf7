"""A benchmark layout at a size the CPU runs in seconds: Qwen1.5's
family at 2 layers, hidden 64, 4 heads, vocabulary 512, served by the
program's ``Engine.session`` like the real cells, with the real metric
readers and model module copied in."""
from __future__ import annotations

import json
import os
import shutil
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP))
for p in (CHIP, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_HF = {"hidden_size": 64, "intermediate_size": 128,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 4, "vocab_size": 512,
           "rope_theta": 1000000.0, "rms_norm_eps": 1e-06}
TINY_SERVING = {"slots": 4, "max_len": 96, "page_size": 8, "chunk": 4,
                "kv_dtype": "bf16", "kv_pool_pages": 49}
#: between the program's widest gap at this size (0.019 and less on four
#: seeds of each tiny cell) and the float8 control's (0.07 and more)
LIMITS = {"max_logit_gap": 0.04, "min_checked_tokens": 16}


def tiny_arch():
    """The program's configuration at the tiny widths."""
    import dataclasses
    from repro.configs import get
    return dataclasses.replace(
        get("qwen1.5-0.5b"), name="qwen1.5-tiny", n_layers=2, d_model=64,
        n_heads=4, n_kv=4, d_ff=128, vocab=512, d_head=16)


def _dump(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_layout(root: str):
    """Write a tiny benchmark under ``root``; returns its Layout."""
    from chipbench.layout import Layout
    for kind in ("metrics", "models"):
        shutil.copytree(os.path.join(CHIP, kind), os.path.join(root, kind),
                        ignore=shutil.ignore_patterns("__pycache__"))
    base = {"family": "qwen1_5", "arch": "qwen1.5-tiny", **TINY_HF,
            "serving": TINY_SERVING, "compression": None,
            "weight_format": {"dense_bits": 16}}
    _dump(f"{root}/configs/tiny-dense.json", {"name": "tiny-dense", **base})
    _dump(f"{root}/configs/tiny-aida.json", {
        **base, "name": "tiny-aida",
        "compression": {"mode": "aida", "density": 0.25,
                        "codebook_size": 16, "kmeans_iters": 25,
                        "block_rows": 128},
        "weight_format": {"code_bits": 4, "col_index_bits": 12}})
    _dump(f"{root}/traffic/chat.json", {
        "loop": "open", "temperature": 0.0, "ramp_blocks": 1,
        "drain_s": 20, "check_tokens": 48, "check_requests": 4,
        "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 1.0,
                       "min": 2, "max": 40},
        "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.8,
                       "min": 2, "max": 24}})
    _dump(f"{root}/traffic/gen.json", {
        "loop": "closed", "clients": 4, "block": 4, "temperature": 0.0,
        "check_tokens": 160, "check_requests": 8,
        "prompt_len": {"dist": "uniform", "min": 3, "max": 20},
        "output_len": {"dist": "uniform", "min": 20, "max": 60}})
    cells = [("tiny-dense.chat", "tiny-dense", "chat",
              {"rate_per_s": 8.0, "block": 8}),
             ("tiny-dense.gen", "tiny-dense", "gen", {}),
             ("tiny-aida.gen", "tiny-aida", "gen", {})]
    for name, _, _, load in cells:
        _dump(f"{root}/workloads/{name}.json", {**load, "limits": LIMITS})
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": n, "source": "test", "file": n,
                         "reduced": [], "why": "test"}
                        for n in ("tiny-dense", "tiny-aida")]
    bench["workloads"] = [{"name": n, "config": c, "traffic": t,
                           "chips": 1, "why": "test"}
                          for n, c, t, _ in cells]
    # the real metrics, each limited to the tiny cells that report it
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m:
                m["workloads"] = [n for n, _, _, _ in cells]
    _dump(f"{root}/BENCHMARK.json", bench)
    return Layout(root=root, bench_file=f"{root}/BENCHMARK.json")


def run_tiny(layout, cell: str, seed: int = 3, seconds: float = 1.0,
             **kw):
    """One CPU run of a tiny cell, the chip check skipped."""
    import time
    from chipbench import cell as cell_mod
    return cell_mod.run_cell(layout, cell, seed, seconds, False,
                             process_start=time.perf_counter(),
                             need_chip=False, arch=tiny_arch(), **kw)
