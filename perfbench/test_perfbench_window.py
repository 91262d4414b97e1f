"""End-to-end arithmetic of the measured window, on synthetic timestamps."""
import statistics

import pytest

from perfbench import window
from perfbench.window import Delivery, Window


def make_window():
    """One client, window [0, 10] s.  Request 0 (4 tokens) completes at 3.0;
    request 1 (3 tokens) is admitted then and completes at 6.0; request 2
    (10 tokens) is admitted at 6.0 and is in flight at the close."""
    ds = [Delivery(0, 1.0, 1), Delivery(0, 2.0, 2), Delivery(0, 3.0, 1),
          Delivery(1, 3.5, 1), Delivery(1, 6.0, 2),
          Delivery(2, 7.0, 1), Delivery(2, 9.0, 1)]
    toks = {}
    for d in ds:
        toks.setdefault(d.rid, []).extend([7] * d.n)
    return Window(0.0, 10.0, ds, toks), [4, 3, 10, 5]


def test_tokens_per_s_counts_every_delivered_token_over_the_window():
    w, _ = make_window()
    assert window.tokens_per_s(w) == pytest.approx(9 / 10)


def test_tpot_is_a_ratio_of_totals_and_includes_the_request_in_flight():
    w, _ = make_window()
    # spans 2.0 + 2.5 + 2.0 over tokens after each first: 3 + 2 + 1
    assert window.tpot_ms(w) == pytest.approx(1e3 * 6.5 / 6)


def test_gap_p95_over_every_delivery_but_the_first():
    w, _ = make_window()
    gaps = sorted(window.gaps_s(w))
    assert gaps == pytest.approx([1.0, 1.0, 2.0, 2.5])
    want = statistics.quantiles(gaps, n=20, method="inclusive")[18]
    assert window.gap_p95_ms(w) == pytest.approx(1e3 * want)


def test_ttft_admits_each_request_when_the_previous_completes():
    w, max_new = make_window()
    assert window.ttft_s(w, max_new, clients=1) == pytest.approx(
        {0: 1.0, 1: 0.5, 2: 1.0})
    assert window.ttft_ms(w, max_new, clients=1) == pytest.approx(1e3)


def test_ttft_with_two_clients_admits_two_at_the_start():
    w, max_new = make_window()
    # clients=2: requests 0 and 1 start at 0; request 2 waits for the
    # first completion (3.0)
    assert window.ttft_s(w, max_new, clients=2) == pytest.approx(
        {0: 1.0, 1: 3.5, 2: 4.0})


def test_outcome_counts_completed_and_failed_but_not_in_flight():
    w, max_new = make_window()
    # request 1 ended by the program with an I/O error: failed; request 2
    # was in flight at the close and request 3 never started: neither
    finish = {0: "length", 1: "io_error", 2: "aborted", 3: "aborted"}
    assert window.outcome(w, max_new, finish) == (2, 1)
    # closed right after request 1's last token: complete, not failed
    finish = {0: "length", 1: "aborted", 2: "aborted", 3: "aborted"}
    assert window.outcome(w, max_new, finish) == (2, 0)


def test_drive_groups_a_blocks_tokens_and_stops_at_the_close():
    clock_t = iter([0.10, 0.1001, 0.1002, 0.30, 0.50, 0.5001, 1.2])
    stream = iter([("0", 5), ("0", 6), ("0", 7), ("0", 8), ("1", 9),
                   ("1", 10), ("1", 11)])
    w = window.drive(stream, 0.0, 1.0, clock=lambda: next(clock_t))
    assert [(d.rid, d.t, d.n) for d in w.deliveries] == [
        (0, 0.10, 3), (0, 0.30, 1), (1, 0.50, 2)]
    assert w.tokens == {0: [5, 6, 7, 8], 1: [9, 10]}
