"""The end-to-end arithmetic: a rate is over the whole window, and tails
are over all requests and all gaps, so a stall inserted into a synthetic
window moves them."""
import chipbench_testkit  # noqa: F401  (puts the harness on sys.path)
import pytest

from chipbench import serve, stats
from chipbench.cell import RunView
from chipbench.layout import Layout
from chipbench.traffic import Req


def window(stall: float = 0.0, n_req: int = 20, step: float = 0.1,
           late_step: float = 0.1):
    """20 requests due 0.5 s apart in a 10 s window, each served 5 tokens
    one step apart (``late_step`` for those due from t = 5 s) from 0.2 s
    after it was due; ``stall`` delays every token emitted after t = 5 s."""
    logs = {}
    for i in range(n_req):
        due = 0.5 * i
        gap = step if due < 5.0 else late_step
        times = [due + 0.2 + gap * j for j in range(5)]
        times = [t + stall if t > 5.0 else t for t in times]
        logs[i] = serve.RequestLog(Req(i, [1] * 8, 5, due, 0.0), due,
                                   times=times, tokens=[0] * 5,
                                   admit_time=due + 0.05 +
                                   (stall if due + 0.05 > 5.0 else 0.0))
    served = serve.Served(logs, [], (0.0, 10.0), (0.0, 10.0), None,
                          {"pages_peak": 40}, give_up=10.0)
    conf = {"serving": {"kv_pool_pages": 81, "chunk": 4, "slots": 4}}
    return RunView(served, 1.0, conf, None, None, None, None)


@pytest.fixture(scope="module")
def readers():
    lay = Layout()
    return {m: lay.metric(m) for m in (
        "output_tok_s", "itl_p95_ms", "setup_s", "kv.pages_peak_share")}


def test_rate_is_over_the_whole_window(readers):
    # 100 tokens, 99 inside (0, 10] (the last one lands at 10.1 s)
    assert readers["output_tok_s"].read(window()) == pytest.approx(9.9)
    # a stall pushes tokens past the window's end: fewer in it
    assert readers["output_tok_s"].read(window(stall=3.0)) < 9.0


def test_tails_are_over_all_requests_and_gaps(readers):
    calm, slow = window(), window(late_step=0.3)
    assert readers["itl_p95_ms"].read(calm) == pytest.approx(100.0)
    # the requests of the window's second half emit a token every 0.3 s:
    # half of all gaps, so the p95 is theirs
    assert readers["itl_p95_ms"].read(slow) == pytest.approx(300.0)


def test_one_long_gap_in_twenty_sets_the_p95(readers):
    run = window()
    # a single 2 s gap among 80 gaps is beyond the 95th percentile ...
    log = run.served.logs[3]
    log.times[4] += 2.0
    assert readers["itl_p95_ms"].read(run) == pytest.approx(100.0)
    # ... five are not
    for i in (4, 5, 6, 7):
        run.served.logs[i].times[4] += 2.0
    assert readers["itl_p95_ms"].read(run) > 2000.0


def test_counters_and_setup(readers):
    run = window()
    assert readers["setup_s"].read(run) == 1.0
    assert readers["kv.pages_peak_share"].read(run) == pytest.approx(50.0)


def test_nearest_rank_percentile():
    assert stats.percentile(list(range(1, 21)), 90) == 18
    assert stats.percentile(list(range(1, 21)), 95) == 19
    assert stats.percentile([7.0], 95) == 7.0
