"""``serve_closed.on_deliveries``, ``reduce_records`` and
``check_sample`` on records made by hand: a window opened and closed on
deliveries holds whole ticks whatever the instants it was asked for, the
rate counts what was delivered in it over its own length, and the sample
the reference follows holds what the answer cut furthest along had
said."""

import pytest

from benchmarks.kinds import serve_closed


def stream(first_tick, n_ticks, tick_s, plen=5, outcome="cut", skew=0.0):
    times = [(first_tick + k) * tick_s + skew for k in range(n_ticks)]
    return {"sent": times[0] - 0.01, "first": times[0],
            "line_times": times, "line_tokens": [1] * n_ticks,
            "streamed": list(range(n_ticks)),
            "done": None, "result": None, "phases": None,
            "outcome": outcome, "prompt": [0] * plen, "max_new": n_ticks}


@pytest.mark.parametrize("t_open,t_close", [(0.95, 10.95), (1.0, 11.0),
                                            (1.07, 11.02)])
def test_the_rate_counts_whole_ticks_over_their_own_time(t_open, t_close):
    tick = 0.2
    records = [stream(0, 80, tick, skew=0.001 * i) for i in range(4)]
    records.append(stream(0, 80, tick, outcome="error:OSError"))
    cut = serve_closed.reduce_records(records, t_open, t_close)
    assert cut["window_s"] == pytest.approx(t_close - t_open)
    # cut at arbitrary instants the window holds one tick more or fewer
    assert cut["out_tokens_per_s"] == pytest.approx(4 / tick, rel=0.025)
    red = serve_closed.reduce_records(
        records, *serve_closed.on_deliveries(records, t_open, t_close))
    assert red["out_tokens_per_s"] == pytest.approx(4 / tick, rel=1e-3)
    assert red["window_s"] == pytest.approx(10.0, abs=tick)
    assert len(red["failed"]) == 1
    assert all(g == pytest.approx(tick * 1e3) for g in red["gaps_ms"])
    # every token counted once, with its position in its sequence
    assert sum(b - a for a, b in red["token_ranges"]) == \
        pytest.approx(red["out_tokens_per_s"] * red["window_s"])
    assert sum(red["delivered_by_second"]) == \
        pytest.approx(red["out_tokens_per_s"] * red["window_s"])


def test_a_window_that_saw_no_delivery_keeps_its_own_edges():
    assert serve_closed.on_deliveries([], 1.0, 3.0) == (1.0, 3.0)
    red = serve_closed.reduce_records([], 1.0, 3.0)
    assert red["out_tokens_per_s"] == 0 and red["window_s"] == 2.0


def test_the_sample_holds_the_longest_finished_and_the_furthest_cut():
    done = [dict(stream(0, n, 0.1, plen=3, outcome="ok"),
                 result=[0] * (3 + n)) for n in (4, 9, 6, 5)]
    cuts = [stream(0, 7, 0.1, plen=2), stream(0, 5, 0.1, plen=30),
            dict(stream(0, 1, 0.1, plen=90), streamed=[])]
    sample = serve_closed.check_sample(done + cuts, 3, seed=5)
    assert len(sample) == 4
    assert len(sample[0]["result"]) == 12          # the longest finished
    assert sample[-1]["prompt"] == [0] * 30        # the furthest cut
    assert sample[-1]["result"] == [0] * 30 + [0, 1, 2, 3, 4]
    assert serve_closed.check_sample(cuts[2:], 3, seed=5) == []
