"""Operations and bytes per token of both configurations, against hand
counts."""
import chipbench_testkit  # noqa: F401  (puts the harness on sys.path)
import pytest

from chipbench.layout import Layout


@pytest.fixture(scope="module")
def qwen():
    lay = Layout()
    model = lay.model("qwen1_5")
    return (model, model.Dims(lay.config("qwen05b-dense")),
            model.Dims(lay.config("qwen05b-aida")))


def test_dense_counts(qwen):
    model, dense, _ = qwen
    # per layer: q, k, v, o are 1024 x 1024; gate, up, down 1024 x 2816
    per_layer = 4 * 1024 * 1024 + 3 * 1024 * 2816
    assert per_layer == 12_845_056
    assert model.fc_weights(dense) == 24 * per_layer == 308_281_344
    # bf16 weights, not the f32 masters
    assert model.fc_weight_bytes(dense) == 2 * 308_281_344
    # K and V, 24 layers, 16 heads of 64, bf16
    assert model.kv_bytes_per_token(dense) == 24 * 2 * 16 * 64 * 2 == 98_304
    # a token that attends 1,000 keys and is sampled
    want = 2 * 308_281_344 + 24 * 4 * 16 * 64 * 1000 + 2 * 151_936 * 1024
    assert model.token_flops(dense, 1000, True) == want
    assert model.token_flops(dense, 1000, False) == \
        want - 2 * 151_936 * 1024
    # one step: weights once, the bf16 unembedding, valid KV of two slots
    assert model.step_bytes(dense, [100, 300], sampled=2) == \
        2 * 308_281_344 + 2 * 151_936 * 1024 + 98_304 * 400


def test_aida_counts(qwen):
    model, _, aida = qwen
    # a quarter of each matrix kept: 262,144 of q/k/v/o, 720,896 of
    # gate/up/down; 3,211,264 a layer
    assert model.proj_nnz(aida) == [262_144] * 4 + [720_896] * 3
    assert model.fc_weights(aida) == 24 * 3_211_264 == 77_070_336
    # a 4-bit code and a 12-bit column index per kept weight
    assert model.fc_weight_bytes(aida) == 77_070_336 * 2
    assert model.token_flops(aida, 1, False) == \
        2 * 77_070_336 + 24 * 4 * 1024
    ops, byt = model.aida_call_work(aida, 32)
    assert ops == 2 * 77_070_336 * 32
    # codes and indices once, f32 activations in and out of every call
    acts = 4 * 32 * (4 * 2048 + 3 * (1024 + 2816))
    assert byt == 77_070_336 * 2 + 24 * acts


def test_attention_context_counts_each_query_and_its_past():
    model = Layout().model("qwen1_5")
    # 3 queries after 5 positions attend 6, 7 and 8 keys
    assert model.attention_context(5, 3) == 21
