"""``host_turnaround_ms_per_round`` on hand-made span lists: each
``block_exec`` pairs with the latest ``loss_sync`` before it, the window's
first block has no pair, and a window with no rounds or no pairs reads
nothing."""
from types import SimpleNamespace

import pytest

from bench import harness


def _span(name, ts, dur):
    return {"name": name, "ts": float(ts), "dur": float(dur)}


def _read(spans, rounds):
    return harness.reader("host_turnaround_ms_per_round").read(
        SimpleNamespace(spans=spans, rounds=rounds))


def _blocks(n, gap_us=300.0, exec_us=100.0, sync_us=5000.0):
    """``n`` blocks of one cluster: exec, then the loss sync; the next
    block's exec ends ``gap_us`` after the sync ends."""
    spans, t = [], 0.0
    for _ in range(n):
        start = t + gap_us - exec_us
        spans.append(_span("block_exec", start, exec_us))
        spans.append(_span("loss_sync", start + exec_us, sync_us))
        spans.append(_span("round_block", start - 10, exec_us + sync_us + 20))
        t = start + exec_us + sync_us
    return spans


def test_pairs_each_block_with_the_previous_sync():
    # 4 blocks of 4 rounds: the first exec has no sync before it
    assert _read(_blocks(4), 16) == pytest.approx(3 * 0.3 / 16)


def test_first_block_is_left_out():
    assert _read(_blocks(1), 4) is None
    spans = _blocks(2)
    assert _read(spans, 8) == pytest.approx(0.3 / 8)
    # the order of the list does not matter
    assert _read(spans[::-1], 8) == pytest.approx(0.3 / 8)


def test_clusters_in_one_block():
    """Three clusters a block: each exec pairs with the sync of the cluster
    before it, so the host work between clusters counts too."""
    spans, t = [], 0.0
    for _ in range(2):
        for _level in range(3):
            spans.append(_span("block_exec", t + 150, 50))   # 200 after sync
            spans.append(_span("loss_sync", t + 200, 1000))
            t += 1200
    assert _read(spans, 8) == pytest.approx(5 * 0.2 / 8)


def test_exec_without_a_sync_between_pairs_once():
    spans = [_span("loss_sync", 0, 10), _span("block_exec", 50, 10),
             _span("block_exec", 100, 10)]
    assert _read(spans, 4) == pytest.approx(0.05 / 4)


def test_no_rounds_or_no_sync_reads_nothing():
    assert _read(_blocks(3), 0) is None
    assert _read([s for s in _blocks(3) if s["name"] != "loss_sync"],
                 12) is None
    assert _read([], 4) is None
