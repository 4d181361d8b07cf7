"""The Mellum2 family module and the expert layer's two metrics: the
work counts by hand-computed numbers, YaRN's correction range at the
published values, a whole CPU run of a tiny Mellum-shaped cell that the
reference finds correct and its float8 control does not, and the
readers on synthetic runs."""
import json
import os
import shutil
import time
import types

import chipbench_testkit as kit
import numpy as np
import pytest

from chipbench import serve
from chipbench.cell import RunView
from chipbench.layout import Layout
from chipbench.tracefile import Reduction
from chipbench.work import StepWork

#: between the program's widest gap on this tiny cell (0.009-0.018 on
#: three seeds) and the float8 control's (0.41-0.66).  The router is
#: Mellum's: 64 experts, top 8, 16 held.  (At top 2 of 8 a routing
#: near-tie flips an expert of weight near 0.5 and the program reads
#: 0.4-1.1, as wide as its control.)
LIMITS = {"max_logit_gap": 0.1, "min_checked_tokens": 16}

TINY_HF = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "rms_norm_eps": 1e-6, "sliding_window": 8,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "rope_parameters": {
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0},
        "full_attention": {"rope_type": "yarn", "rope_theta": 10000.0,
                           "factor": 4.0,
                           "original_max_position_embeddings": 16,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.1386294361119891}},
    "num_experts": 16, "num_experts_per_tok": 8,
    "moe_intermediate_size": 16,
    "deployment": {"experts": 64, "experts_held": list(range(16))}}


@pytest.fixture(scope="module")
def model():
    return Layout().model("mellum2")


@pytest.fixture(scope="module")
def dims(model):
    conf = json.load(open(os.path.join(kit.CHIP, "configs",
                                       "mellum2-12b-ep4.json")))
    return model.Dims(conf)


def tiny_arch():
    import dataclasses
    from repro.configs import get
    from repro.configs.base import MoECfg, YaRN
    return dataclasses.replace(
        get("mellum2-12b-ep4"), name="mellum2-tiny", n_layers=4,
        d_model=64, n_heads=8, n_kv=2, d_head=16, d_ff=128, vocab=512,
        window=8, rope_theta=10000.0,
        yarn=YaRN(factor=4.0, original_max_position=16, beta_fast=32.0,
                  beta_slow=1.0, attention_factor=1.1386294361119891),
        moe=MoECfg(n_experts=64, top_k=8, d_expert=16,
                   held=tuple(range(16))))


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tinymellum"))
    for kind in ("metrics", "models"):
        shutil.copytree(os.path.join(kit.CHIP, kind),
                        os.path.join(root, kind),
                        ignore=shutil.ignore_patterns("__pycache__"))
    kit._dump(f"{root}/configs/tiny-mellum.json", {
        "name": "tiny-mellum", "family": "mellum2", "arch": "mellum2-tiny",
        **TINY_HF, "serving": {**kit.TINY_SERVING, "max_len": 96},
        "weight_format": {"dense_bits": 16}, "compression": None})
    kit._dump(f"{root}/traffic/gen.json", {
        "loop": "closed", "clients": 4, "block": 4, "temperature": 0.0,
        "check_tokens": 160, "check_requests": 8,
        "prompt_len": {"dist": "uniform", "min": 3, "max": 20},
        "output_len": {"dist": "uniform", "min": 20, "max": 60}})
    kit._dump(f"{root}/workloads/tiny-mellum.gen.json", {"limits": LIMITS})
    with open(os.path.join(kit.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny-mellum", "source": "test",
                         "file": "tiny-mellum", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": "tiny-mellum.gen",
                           "config": "tiny-mellum", "traffic": "gen",
                           "chips": 1, "why": "test"}]
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m:
                m["workloads"] = ["tiny-mellum.gen"]
    kit._dump(f"{root}/BENCHMARK.json", bench)
    return Layout(root=root, bench_file=f"{root}/BENCHMARK.json")


# ------------------------------------------------------------- counts
def test_published_yarn_correction_range(model, dims):
    assert model.yarn_range(dims) == (18, 35)
    freqs, scale = model.inv_freqs(dims)
    assert freqs.shape == (8, 64)
    assert list(scale) == pytest.approx([1, 1, 1, 1.2772588722239782] * 2)
    # below the range YaRN extrapolates (plain RoPE), above it
    # interpolates (plain / factor)
    plain = 1 / 500000.0 ** (np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(freqs[3, :18], plain[:18], rtol=1e-6)
    np.testing.assert_allclose(freqs[3, 35:], plain[35:] / 16, rtol=1e-6)
    np.testing.assert_allclose(freqs[0], plain, rtol=1e-6)


def test_token_flops_by_hand(model, dims):
    # per layer: q/k/v/o 2 * (2*2304*4096 + 2*2304*512) = 42,467,328;
    # router 2 * 2304 * 64 = 294,912; experts computed here in
    # expectation 2 * 8 * 16/64 * 3 * 2304 * 896 = 24,772,608
    per_layer = 42_467_328 + 294_912 + 24_772_608
    assert model.token_flops(dims, 0, False) == 8 * per_layer
    # attention over 100 keys: 4 * 32 * 128 * 100 per layer
    assert model.token_flops(dims, 100, False) \
        == 8 * (per_layer + 1_638_400)
    # the logits: 2 * 98,304 * 2304
    assert model.token_flops(dims, 0, True) \
        - model.token_flops(dims, 0, False) == 452_984_832


def test_step_bytes_by_hand(model, dims):
    # weights once in bf16: attention 21,233,664 + router 147,456 + 16
    # held experts of 6,193,152 each, in each of 8 layers
    weights = 8 * (21_233_664 + 147_456 + 16 * 6_193_152) * 2
    assert model.step_bytes(dims, [], 0) == weights
    # KV of one position in one layer: 2 * 4 * 128 * 2 = 2,048 B; a
    # 3,000-position context: 2 full layers read 3,000, 6 windowed 1,024
    kv = 2048 * (2 * 3000 + 6 * 1024)
    assert model.step_bytes(dims, [3000], 0) == weights + kv
    # a 500-position context is inside every window
    assert model.step_bytes(dims, [500], 1) == weights \
        + 98_304 * 2304 * 2 + 2048 * 8 * 500


def test_attention_context_over_the_layer_pattern(model):
    # one query at position 0: one key in every layer
    assert model.attention_context(0, 1) == 1
    # one query at position 2,047: 2,048 keys in a full layer, 1,024 in
    # each of the three windowed ones of a period
    assert model.attention_context(2047, 1) == (2048 + 3 * 1024) / 4
    # 16 queries after 100: all inside the window
    assert model.attention_context(100, 16) == 16 * 100 + 16 * 17 / 2


# ---------------------------------------------------------- a whole run
def test_tiny_cell_is_correct_and_its_control_is_not(layout):
    from chipbench import cell
    out = cell.run_cell(layout, "tiny-mellum.gen", 2**31 + 41, 1.0, False,
                        process_start=time.perf_counter(), need_chip=False,
                        arch=tiny_arch(), control=True)
    assert out["control"]["served_correct"], out["control"]
    assert out["control"]["served_max_gap"] <= LIMITS["max_logit_gap"]
    assert not out["correct"]
    assert out["control"]["control_max_gap"] > LIMITS["max_logit_gap"]
    assert out["checks"]["checked_tokens"]["value"] >= 16
    assert {"output_tok_s", "itl_p95_ms", "setup_s"} <= set(out["metrics"])


# ------------------------------------------------------------- readers
def synthetic_run(dims, stats, trace_ops, steps_tokens):
    logs = {}
    served = serve.Served(logs, [serve.StepLog(i, 0.0, 1.0)
                                 for i in range(len(steps_tokens))],
                          (0.0, 1.0), (0.0, 1.0), (0.0, 1.0), stats, 1.0)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = Reduction(window_s=1.0, busy_s=0.9, op_s=trace_ops,
                      gaps_s={}, chips=1)
    run = RunView(served, 1.0, {"serving": {"chunk": 16}}, dims, None,
                  peaks, trace)
    work = [StepWork(i, False, n, n, 0.0, 0.0, []) for i, n in
            enumerate(steps_tokens)]
    run.traced_steps = types.MethodType(lambda self: work, run)
    return run


def test_pairs_per_expert_reader(dims):
    reader = Layout().metric("moe.pairs_per_expert")
    # 2 of a token's 8 chosen experts held here on average; the run's
    # counters include a set-up prefill of 2,048 tokens a call
    stats = {"moe_pairs_here": 2 * 8 * (10 * 2048 + 10 * 128),
             "moe_tokens": 8 * (10 * 2048 + 10 * 128),
             "moe_layer_calls": 20 * 8}
    # traced decode steps of 128 tokens: 256 pairs over 16 held experts
    run = synthetic_run(dims, stats, {}, [128] * 10)
    assert reader.read(run) == pytest.approx(16.0)
    # a chunked step that also feeds a 16-token prompt chunk
    run = synthetic_run(dims, stats, {}, [128, 143])
    assert reader.read(run) == pytest.approx(2 * 271 / 2 / 16)
    # a program without the counters reports nothing
    assert reader.read(synthetic_run(dims, {}, {}, [128])) is None


def test_expert_roofline_reader(dims):
    reader = Layout().metric("moe.expert_roofline")
    stats = {"moe_pairs_here": 256, "moe_tokens": 128,
             "moe_layer_calls": 8}
    # one decode step of 128 tokens: its bytes bound it (1.586 GB of
    # held experts and 2,048 pairs of 9,216 B), 1.96 ms at 819 GB/s
    weights = 8 * 16 * 3 * 2304 * 896 * 2
    least = (weights + 128 * 8 * 2 * 4 * 2304) / 819e9
    ops = {"ragged-dot-none bf16[1024,896]": 2 * least,
           "ragged-dot-metadata s32[17]": 0.0, "fusion.3 f32[8]": 5.0}
    run = synthetic_run(dims, stats, ops, [128])
    got = reader.read(run)
    assert got == pytest.approx(50.0)
    assert 0 < got <= 100
    # nothing where the trace has no grouped matmul or no counters
    assert reader.read(synthetic_run(dims, stats, {"fusion.3": 1.0},
                                     [128])) is None
    assert reader.read(synthetic_run(dims, {}, ops, [128])) is None
