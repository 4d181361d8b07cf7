"""The one traffic generator: seeded, stratified, block-aligned, and a
closed loop that starts at steady state."""
import chipbench_testkit  # noqa: F401  (puts the harness on sys.path)
import numpy as np
import pytest

from chipbench import traffic

CHAT = {"loop": "open", "rate_per_s": 0.4, "block": 12,
        "prompt_len": {"dist": "lognormal", "median": 512, "sigma": 1.0,
                       "min": 32, "max": 2048},
        "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                       "min": 16, "max": 512}}
GEN = {"loop": "closed", "clients": 32, "block": 32,
       "prompt_len": {"dist": "uniform", "min": 64, "max": 512},
       "output_len": {"dist": "uniform", "min": 512, "max": 2048}}


def shape(reqs):
    return [(len(r.prompt), r.max_new, r.due_s) for r in reqs]


@pytest.mark.parametrize("mix", [CHAT, GEN], ids=["open", "closed"])
def test_same_seed_same_requests(mix):
    a = traffic.take(mix, 2**31 + 99, 151936, 40)
    b = traffic.take(mix, 2**31 + 99, 151936, 40)
    assert shape(a) == shape(b)
    assert [r.prompt for r in a] == [r.prompt for r in b]


@pytest.mark.parametrize("mix", [CHAT, GEN], ids=["open", "closed"])
def test_seeds_permute_one_set_of_sizes(mix):
    """Every seed offers the same sizes and arrivals in the same order;
    the seed draws the token ids."""
    n = mix["block"]
    first = mix.get("clients", 0) if mix["loop"] == "closed" else 0
    a = traffic.take(mix, 1, 1000, first + 2 * n)
    b = traffic.take(mix, 2**31 + 5, 1000, first + 2 * n)
    assert shape(a) == shape(b)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    a = a[first:]
    for block in (a[:n], a[n:]):
        assert sorted(len(r.prompt) for r in block) == \
            sorted(traffic.quantiles(mix["prompt_len"], n))
        assert sorted(r.max_new for r in block) == \
            sorted(traffic.quantiles(mix["output_len"], n))
    for r in a:
        assert mix["prompt_len"]["min"] <= len(r.prompt) \
            <= mix["prompt_len"]["max"]
        assert mix["output_len"]["min"] <= r.max_new \
            <= mix["output_len"]["max"]


def test_every_prefix_of_the_order_spreads():
    order = traffic.spread_order(32, "prompt")
    assert sorted(order) == list(range(32))
    # any 4 consecutive requests reach both halves of the distribution
    for k in range(29):
        ranks = order[k:k + 4]
        assert ranks.min() < 16 <= ranks.max()


def test_steady_start_catches_requests_in_progress():
    first = traffic.in_progress(GEN)
    assert len(first) == GEN["clients"]
    reqs = traffic.take(GEN, 4, 1000, 2 * GEN["clients"])
    assert [(len(r.prompt), r.max_new) for r in reqs[:32]] == first
    total = [p + o for p, o in first]
    # prompt, produced share and rest make up a request of the mix
    assert min(total) >= 64 + 512 and max(total) <= 512 + 2048
    assert all(o >= 1 for _, o in first)
    # the outputs caught in progress are length-biased: their mean is
    # E[O^2] / E[O] = 1,434 for O uniform over 512..2048, not 1,280
    biased = traffic.biased_quantiles(GEN["output_len"], 4096)
    assert biased.mean() == pytest.approx(1434, rel=0.01)
    # a stratified share of each is done: some finish soon, contexts
    # average about the prompt (288) plus half a biased output (717)
    assert min(o for _, o in first) < 64
    assert np.mean([p for p, _ in first]) == pytest.approx(1005, rel=0.05)
    # after the first requests come the mix's own blocks
    assert sorted(len(r.prompt) for r in reqs[32:]) == \
        sorted(traffic.quantiles(GEN["prompt_len"], 32))


def test_open_loop_blocks_arrive_in_their_own_span():
    span = traffic.block_seconds(CHAT)
    assert span == pytest.approx(30.0)
    reqs = traffic.take(CHAT, 5, 1000, 3 * CHAT["block"])
    for r in reqs:
        k = r.index // CHAT["block"]
        assert k * span <= r.due_s < (k + 1) * span
    dues = [r.due_s for r in reqs]
    assert dues == sorted(dues)
    # the mean arrival rate is the mix's rate
    gaps = np.diff(dues)
    assert len(dues) / (3 * span) == pytest.approx(CHAT["rate_per_s"])
    assert (gaps > 0).all()


def test_lognormal_quantiles_have_the_stated_median_and_clip():
    q = traffic.quantiles(CHAT["prompt_len"], 101)
    assert q[50] == 512
    assert q.min() >= 32 and q.max() == 2048
