"""tools/idle_split.py on a hand-built trace: each idle instant goes to the
innermost program span open then, across host threads."""
import pytest

from tools.idle_split import split

MS = 1e-3


def test_idle_goes_to_the_innermost_open_span():
    window = (0.0, 20 * MS)
    ops = [(2 * MS, 6 * MS), (6 * MS, 7 * MS), (12 * MS, 18 * MS)]
    spans = [
        # loop thread: one microbatch's device round trip, then coalescing
        (1 * MS, 9 * MS, "frontend.device"),
        (9 * MS, 11 * MS, "frontend.coalesce"),
        (10 * MS, 11 * MS, "frontend.hold"),
        # device thread, inside the round trip
        (1 * MS, 8 * MS, "engine.search"),
        (1 * MS, 2 * MS, "engine.dispatch"),
        (7 * MS, 8 * MS, "frontend.fetch"),
    ]
    r = split(window, ops, spans)
    assert r["busy_s"] == pytest.approx(11 * MS)
    assert r["idle_s"] == pytest.approx(9 * MS)
    got = {name: (s / MS, n) for name, (s, n) in r["idle_by_span"].items()}
    # idle: [0,2] [7,12] [18,20]
    assert got["(none)"][0] == pytest.approx(1 + 1 + 2)
    assert got["engine.dispatch"][0] == pytest.approx(1)
    assert got["frontend.fetch"][0] == pytest.approx(1)
    assert got["frontend.device"][0] == pytest.approx(1)
    assert got["frontend.coalesce"][0] == pytest.approx(1)
    assert got["frontend.hold"][0] == pytest.approx(1)
    assert sum(n for _, n in got.values()) == 3
    assert r["microbatches"] == 1
