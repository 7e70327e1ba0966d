"""The open loop's lateness and stall readings, on hand-made send times."""

import numpy as np

import openloop


def result(due, sent):
    n = len(due)
    return openloop.OpenLoopResult(
        np.asarray(due, float), np.asarray(sent, float), np.full(n, np.nan),
        np.zeros(n, np.int64), np.zeros(n, bool), 0, float(sent[-1]))


def test_a_hold_is_counted_once_against_the_request_it_delayed():
    # request 1 is sent 0.3 s late; request 2, due while the generator
    # was held, goes 0.05 s after request 1; request 3 is on time
    res = result([0.0, 1.0, 1.1, 2.0], [0.0, 1.3, 1.35, 2.0])
    assert np.allclose(openloop.stalls(res), [[300.0, 1.0], [50.0, 1.1]])
    late = openloop.lateness_ms(res)
    assert np.isclose(late["max"], 300.0) and np.isclose(late["p50"], 125.0)


def test_no_hold_over_the_floor_reads_empty():
    res = result([0.0, 1.0], [0.001, 1.002])
    assert openloop.stalls(res) == []
