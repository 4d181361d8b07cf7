"""Whole runs of tiny cells on the CPU, the chip check skipped: the
Qwen1.5 reference agrees with what ``Engine.session`` served (dense and
AIDA); the float8 control fails the same check; a new metric or cell is
picked up from new files alone; and a broken serving path makes
``correct`` come out false."""
import json
import os

import chipbench_testkit as kit
import numpy as np
import pytest


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    return kit.make_layout(str(tmp_path_factory.mktemp("tinybench")))


@pytest.mark.parametrize("cell", ["tiny-dense.gen", "tiny-aida.gen",
                                  "tiny-dense.chat"])
def test_reference_agrees_and_control_does_not(layout, cell):
    out = kit.run_tiny(layout, cell, seed=2**31 + 17, control=True)
    # the control in the program's place fails the cell's own verdict
    assert not out["correct"], out["checks"]
    limit = out["checks"]["max_logit_gap"]["limit"]
    assert out["checks"]["max_logit_gap"]["value"] > limit
    assert out["control"]["control_max_gap"] > limit
    # the program's served tokens, read on the same run, pass it
    assert out["control"]["served_correct"]
    assert out["control"]["served_max_gap"] <= limit
    assert out["control"]["tokens"] >= 16
    assert out["checks"]["checked_tokens"]["value"] >= 16
    names = set(out["metrics"])
    assert {"output_tok_s", "itl_p95_ms", "setup_s"} <= names
    assert out["attempted"] > 0


def test_aida_reference_compression_is_the_programs():
    import jax.numpy as jnp
    from chipbench.layout import Layout
    from repro.core import sparse_fc as sfc
    model = Layout().model("qwen1_5")
    rng = np.random.default_rng(0)
    w = rng.normal(size=(96, 160)).astype(np.float32)    # [d_in, d_out]
    ours = np.asarray(model._aida_dense(jnp.asarray(w), 0.25, 16, 25))
    theirs = sfc.dense_equivalent(sfc.compress(w.T, mode="aida",
                                               density=0.25)).T
    assert (ours != 0).sum() == (theirs != 0).sum() == round(0.25 * w.size)
    np.testing.assert_allclose(ours, theirs, rtol=0, atol=1e-6)


def test_new_metric_and_cell_are_picked_up_from_new_files(layout):
    root = layout.root
    with open(os.path.join(root, "metrics", "tokens_total.py"), "w") as f:
        f.write("def read(run):\n"
                "    return float(sum(len(l.tokens)\n"
                "                     for l in run.served.logs.values()))\n")
    with open(os.path.join(root, "workloads", "tiny-aida.chat.json"),
              "w") as f:
        json.dump({"rate_per_s": 8.0, "block": 8, "limits": kit.LIMITS}, f)
    bench = json.load(open(layout.bench_file))
    bench["end_to_end"].append({"name": "tokens_total", "unit": "tokens",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock"})
    bench["workloads"].append({"name": "tiny-dense.chat2",
                               "config": "tiny-dense", "traffic": "chat",
                               "chips": 1, "why": "test"})
    with open(os.path.join(root, "workloads", "tiny-dense.chat2.json"),
              "w") as f:
        json.dump({"rate_per_s": 16.0, "block": 16, "limits": kit.LIMITS},
                  f)
    path = os.path.join(root, "BENCHMARK2.json")
    json.dump(bench, open(path, "w"))
    from chipbench.layout import Layout
    lay = Layout(root=root, bench_file=path)
    out = kit.run_tiny(lay, "tiny-dense.chat2", seed=11)
    assert out["metrics"]["tokens_total"]["value"] > 0
    assert out["correct"]


def _freeze_state(sess):
    """The step returns the state it was given: no KV write, no position
    advance, as if the cache update were lost."""
    import jax
    for name in ("_step", "_prefill"):
        step = getattr(sess, name)

        def frozen(params, state, *args, step=step):
            kept = jax.tree.map(lambda x: x.copy(), state)   # step donates
            _, logits = step(params, state, *args)
            return kept, logits
        setattr(sess, name, frozen)


def _alter_tokens(sess):
    """The sampler is handed logits rolled by one: every token is the
    neighbour of the one the model chose."""
    emit = sess._emit
    sess._emit = lambda i, logits, now: emit(i, np.roll(logits, 1), now)


@pytest.mark.parametrize("fault", [_freeze_state, _alter_tokens],
                         ids=["state_unchanged", "token_altered"])
def test_broken_serving_path_is_not_correct(layout, fault):
    out = kit.run_tiny(layout, "tiny-dense.gen", seed=23,
                       on_session=fault)
    assert not out["correct"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
