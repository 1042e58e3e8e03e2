"""The window's arithmetic on made-up records."""

from harness import window


def ctx_of(bursts, t0=100.0, t1=110.0, width=0.012, lanes=4, tokens=8):
    """One record per lane; each burst delivers ``tokens`` to every lane
    within ``width`` seconds of its start."""
    records = [{"events": [(b + width * lane / lanes, tokens) for b in bursts]}
               for lane in range(lanes)]
    return {"window": (t0, t1), "records": records}


def test_a_rate_does_not_jump_when_a_burst_straddles_an_edge():
    period = 0.7
    rates = []
    for phase in (0.0, 0.1, 0.35, 0.69, 0.695):  # the last two put a burst across t0
        bursts = [99.0 - 0.006 + phase + period * i for i in range(20)]
        units, seconds = window.burst_span(ctx_of(bursts))
        rates.append(units / seconds)
    assert max(rates) - min(rates) < 1e-6 * rates[0]
    assert abs(rates[0] - 4 * 8 / period) < 1e-6 * rates[0]


def test_replies_that_never_pause_are_counted_over_the_whole_window():
    records = [{"events": [(100.0 + 0.01 * i, 1) for i in range(-50, 1500)]}]
    units, seconds = window.burst_span({"window": (100.0, 110.0), "records": records})
    assert (units, seconds) == (1000, 10.0)


def test_failed_requests_are_those_sent_inside_and_not_cut_by_us():
    records = [
        {"t_send": 101.0, "events": [], "error": "HTTP 503", "aborted": False},
        {"t_send": 109.0, "events": [], "error": "OSError: cut", "aborted": True},
        {"t_send": 99.0, "events": [], "error": "HTTP 503", "aborted": False},
        {"t_send": 105.0, "events": [(106.0, 8)], "error": None},
    ]
    ctx = {"window": (100.0, 110.0), "records": records}
    assert len(window.sent_in_window(ctx)) == 3
    assert [r["t_send"] for r in window.failed_in_window(ctx)] == [101.0]
    assert window.ttfts_ms(ctx) == [1000.0]
