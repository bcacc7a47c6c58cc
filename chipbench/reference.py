"""The plain reference: the paper's Fig. 6 tweet mapping over the
generated events, as dicts.

It imports nothing of the program and reads nothing the program made
except the list of events that each acknowledged commit carried.  Node
identity is its own copy of the program's documented id: FNV-1a over
the key's UTF-8 bytes, the node type in the top byte, then the
splitmix64 finaliser, with 0 moved to 1 (0 marks an empty store slot).

Store semantics that the reference reproduces (MERGE nodes,
CREATE-or-count edges, Algorithm 3):
  edges   (src, dst, etype) -> how many times an acknowledged event
          carried that edge;
  degree  node -> number of distinct edges that touch it;
  count   node -> number of acknowledged commits whose events touch it.
"""
from __future__ import annotations

import collections
from typing import Dict, Iterable, List, Sequence, Tuple

MASK = (1 << 64) - 1
FNV_OFFSET, FNV_PRIME = 1469598103934665603, 1099511628211
# node types and edge types of Fig. 6
USER, TWEET, HASHTAG = 1, 2, 3
OWNER, MENTIONED, HT_USED_IN, MENTIONED_WITH_HT = 1, 2, 3, 4


def splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & MASK
    z = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & MASK
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & MASK
    return z ^ (z >> 31)


def node_id(node_type: int, key: str) -> int:
    h = FNV_OFFSET
    for b in key.encode("utf-8"):
        h = ((h ^ b) * FNV_PRIME) & MASK
    h ^= node_type << 56
    return splitmix64(h) or 1


class Ids:
    """Memoised node ids: users and tags repeat across events."""

    def __init__(self):
        self._cache: Dict[Tuple[int, str], int] = {}

    def __call__(self, node_type: int, key: str) -> int:
        k = (node_type, key)
        v = self._cache.get(k)
        if v is None:
            v = self._cache[k] = node_id(node_type, key)
        return v


def event_edges(ids: Ids, tweet: int, user: int, tag: int,
                mention: int) -> List[Tuple[int, int, int]]:
    """Fig. 6 for a tweet with one hashtag and one mention."""
    t = ids(TWEET, f"t{tweet}")
    u = ids(USER, f"u{user}")
    m = ids(USER, f"u{mention}")
    h = ids(HASHTAG, f"h{tag}")
    return [(u, t, OWNER), (t, m, MENTIONED), (h, t, HT_USED_IN),
            (h, m, MENTIONED_WITH_HT)]


def build(events, batches: Iterable[Sequence[int]]):
    """(edges, degree, count) from the acknowledged commits, each given
    as the indices of the events it carried."""
    ids = Ids()
    edges: collections.Counter = collections.Counter()
    count: collections.Counter = collections.Counter()
    tw, us, tg, mn = (events.tweet.tolist(), events.user.tolist(),
                      events.tag.tolist(), events.mention.tolist())
    for batch in batches:
        nodes = set()
        for i in batch:
            for e in event_edges(ids, tw[i], us[i], tg[i], mn[i]):
                edges[e] += 1
                nodes.add(e[0])
                nodes.add(e[1])
        count.update(nodes)
    degree: collections.Counter = collections.Counter()
    for s, d, _ in edges:
        degree[s] += 1
        degree[d] += 1
    return dict(edges), dict(degree), dict(count)


def weighted_degree(edges: Dict) -> Dict[int, int]:
    """node -> sum of the counts of the edges that touch it (what a
    count-min degree sketch must not under-report)."""
    w: collections.Counter = collections.Counter()
    for (s, d, _), c in edges.items():
        w[s] += c
        w[d] += c
    return dict(w)


def mismatches(want: Dict, got: Dict) -> int:
    """Keys whose values differ, counting keys missing on either side."""
    return sum(want.get(k) != got.get(k) for k in want.keys() | got.keys())
