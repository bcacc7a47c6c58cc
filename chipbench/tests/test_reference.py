"""The plain reference against the program's CREATEEDGE."""
import collections
from pathlib import Path

from chipbench import reference
from chipbench.traffic import generate, load_traffic

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def test_reference_mapping_equals_create_edges():
    from repro.core.transform import create_edges, tweet_mapping

    ev = generate(load_traffic(TRAFFIC / "flash_crowd.json"),
                  {"rate_events_per_s": 40, "n_users": 10**7, "n_tags": 10**6},
                  2**31 + 3, 12)
    assert 300 <= len(ev) <= 2000
    raw = create_edges(ev.records(), tweet_mapping())
    program = collections.Counter(zip(raw.src.tolist(), raw.dst.tolist(),
                                      raw.etype.tolist()))
    edges, degree, count = reference.build(ev, [range(len(ev))])
    assert program == collections.Counter(edges)
    assert sum(edges.values()) == 4 * len(ev)
    assert all(count[n] == 1 for n in count)  # one commit: count 1 each
    assert sum(degree.values()) == 2 * len(edges)


def test_counts_follow_commits():
    ev = generate(load_traffic(TRAFFIC / "steady.json"),
                  {"rate_events_per_s": 20, "n_users": 50, "n_tags": 5}, 1, 3)
    n = len(ev)
    edges1, _, count1 = reference.build(ev, [range(n)])
    edges2, _, count2 = reference.build(ev, [range(n // 2), range(n // 2, n)])
    assert edges1 == edges2
    assert sum(count2.values()) > sum(count1.values())


def test_mismatches_counts_both_sides():
    assert reference.mismatches({1: 2, 3: 4}, {1: 2, 3: 5, 6: 7}) == 2
