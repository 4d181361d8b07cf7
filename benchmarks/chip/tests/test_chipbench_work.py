"""The work of each step, rebuilt from the logs under the chunked-prefill
rule: valid tokens only, sampled positions only."""
import chipbench_testkit as kit
import pytest

from chipbench import serve, work
from chipbench.layout import Layout
from chipbench.traffic import Req


@pytest.fixture(scope="module")
def qwen():
    model = Layout().model("qwen1_5")
    conf = {**kit.TINY_HF, "serving": kit.TINY_SERVING}
    return model, model.Dims(conf)


def served(admit_steps, emit_steps, prompts):
    logs = {}
    for i, (a, e, p) in enumerate(zip(admit_steps, emit_steps, prompts)):
        logs[i] = serve.RequestLog(Req(i, [1] * p, len(e), 0.0, 0.0), 0.0,
                                   times=[float(s) for s in e],
                                   tokens=[0] * len(e), steps=list(e),
                                   admit_step=a)
    steps = [serve.StepLog(s, float(s), s + 0.5) for s in range(8)]
    return serve.Served(logs, steps, (0.0, 8.0), (0.0, 8.0), None, {}, 8.0)


def test_prefill_then_decode(qwen):
    model, dims = qwen
    # a 10-token prompt at chunk 4: 4, 4, 2 prompt tokens in steps 0-2
    # (the first token sampled in step 2), then one token a step
    got = work.rebuild(served([0], [[2, 3, 4]], [10]), model, dims, 4)
    assert [got[s].tokens for s in range(5)] == [4, 4, 2, 1, 1]
    assert [got[s].sampled for s in range(5)] == [0, 0, 1, 1, 1]
    assert [got[s].prefill for s in range(5)] == [True] * 3 + [False] * 2
    assert [got[s].contexts for s in range(5)] == [[4], [8], [10], [11],
                                                   [12]]
    assert got[5].tokens == 0
    per_key = dims.layers * 4 * dims.heads * dims.head_dim
    fc = model.token_flops(dims, 0, False)
    head = model.token_flops(dims, 0, True) - fc
    # step 1: 4 tokens after 4, attending 5..8 keys, none sampled
    assert got[1].flops == 4 * fc + per_key * (5 + 6 + 7 + 8)
    # step 3: the first output token at position 10, sampled
    assert got[3].flops == fc + per_key * 11 + head
    assert got[3].bytes == model.step_bytes(dims, [11], 1)


def test_two_sequences_share_a_step(qwen):
    model, dims = qwen
    got = work.rebuild(served([0, 1], [[0, 1, 2], [1, 2]], [3, 4]),
                       model, dims, 4)
    assert got[1].tokens == 1 + 4 and got[1].sampled == 2
    assert got[1].prefill and not got[2].prefill
    assert sorted(got[2].contexts) == [5, 5]


def test_logs_off_the_rule_rebuild_nothing(qwen):
    model, dims = qwen
    # a first token one step late (a preemption, another schedule)
    assert work.rebuild(served([0], [[3, 4]], [10]), model, dims, 4) \
        is None
