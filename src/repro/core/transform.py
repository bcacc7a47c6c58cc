"""Model transformation (§II-A, Fig. 5/6, Algorithm 1 CREATEEDGE).

Transforms native stream objects (tweet-like JSON dicts) into property-
graph edge batches.  Portability works exactly as in the paper: the
problem-specific part is a declarative `MappingSpec` (the paper's XML
map file — here a python/JSON structure with the same content: input
model, output model, node types, edge defs, extractor bindings), while
the extraction library is generic over dict-shaped records.

Output is device-ready: fixed-capacity int64 id arrays (nodes are
identified by a 64-bit splitmix hash of (type_tag, key) — the TPU
adaptation of the paper's string node index, see DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

# ---------------------------------------------------------------------------
# 64-bit hashing (shared with the Pallas kernels and the graph store)
# ---------------------------------------------------------------------------

_MASK = np.uint64(0xFFFFFFFFFFFFFFFF)


def splitmix64(x: np.ndarray) -> np.ndarray:
    """Vectorised splitmix64 finaliser (uint64 -> uint64)."""
    x = x.astype(np.uint64)
    with np.errstate(over="ignore"):
        x = (x + np.uint64(0x9E3779B97F4A7C15)) & _MASK
        z = x
        z = ((z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)) & _MASK
        z = ((z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)) & _MASK
        z = z ^ (z >> np.uint64(31))
    return z


_FNV_OFFSET = np.uint64(1469598103934665603)
_FNV_PRIME = np.uint64(1099511628211)


def fnv1a(keys: Sequence[str]) -> np.ndarray:
    """64-bit FNV-1a of each key's UTF-8 bytes, all keys at once.

    The keys are encoded into one byte buffer with explicit offsets and
    lengths (so a NUL is a byte like any other), and FNV runs column by
    column: with the keys ordered longest first, the keys that still
    have a byte at position j are a prefix of that order."""
    enc = [k.encode("utf-8") for k in keys]
    n = len(enc)
    lens = np.fromiter(map(len, enc), np.int64, n)
    order = np.argsort(-lens, kind="stable")
    starts = (np.cumsum(lens) - lens)[order]
    buf = np.frombuffer(b"".join(enc), np.uint8).astype(np.uint64)
    longest = int(lens.max()) if n else 0
    # live[j]: how many keys have a byte at position j
    live = np.searchsorted(-lens[order], -np.arange(longest), side="left")
    h = np.full(n, _FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for j, m in enumerate(live):
            h[:m] = (h[:m] ^ buf[starts[:m] + j]) * _FNV_PRIME
    out = np.empty(n, np.uint64)
    out[order] = h
    return out


def finalise_ids(fnv: np.ndarray, type_tags: np.ndarray) -> np.ndarray:
    """Node ids from FNV hashes: the node type XORed into the top byte,
    then splitmix64; 0 (the empty-slot sentinel) becomes 1."""
    ids = splitmix64(fnv ^ (np.asarray(type_tags, np.uint64) << np.uint64(56)))
    ids[ids == 0] = 1
    return ids


def hash_keys(type_tags: np.ndarray, keys: Sequence[str]) -> np.ndarray:
    """Stable node ids for (node_type, key) pairs, all keys at once."""
    return finalise_ids(fnv1a(keys), type_tags)


def hash_str(type_tag: int, s: str) -> int:
    """Stable node id for (node_type, key): `hash_keys` of one key."""
    return int(hash_keys(np.asarray([type_tag]), [s])[0])


# ---------------------------------------------------------------------------
# Mapping spec (the paper's XML map file, Fig. 5)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class NodeDef:
    type_name: str
    type_tag: int
    key: Callable[[dict], Optional[str]]  # extraction binding (getName()...)


@dataclasses.dataclass(frozen=True)
class EdgeDef:
    name: str
    etype: int
    # return list of (src_key, dst_key) string pairs for one record
    extract: Callable[[dict], List[Tuple[str, str]]]
    src_type: int = 0
    dst_type: int = 0


@dataclasses.dataclass(frozen=True)
class MappingSpec:
    input_model: str  # "json"
    output_model: str  # "property-graph"
    nodes: Tuple[NodeDef, ...]
    edges: Tuple[EdgeDef, ...]
    max_edges_per_record: int = 24


# node type tags
T_USER, T_TWEET, T_HASHTAG = 1, 2, 3
# edge types (Fig. 6)
E_OWNER, E_MENTIONED, E_HT_USED_IN, E_MENTIONED_WITH_HT = 1, 2, 3, 4


def tweet_mapping() -> MappingSpec:
    """The paper's Twitter mapping (Fig. 6): user/tweet/hashtag nodes,
    owner / mentioned / hashtag-used-in / mentioned-with-ht edges."""

    def owner(r):
        return [(r["user"], r["id"])]

    def mentioned(r):
        return [(r["id"], m) for m in r.get("mentions", ())]

    def ht_used(r):
        return [(h, r["id"]) for h in r.get("hashtags", ())]

    def ht_mention(r):
        return [
            (h, m)
            for h in r.get("hashtags", ())
            for m in r.get("mentions", ())
        ]

    return MappingSpec(
        input_model="json",
        output_model="property-graph",
        nodes=(
            NodeDef("user", T_USER, lambda r: r["user"]),
            NodeDef("tweet", T_TWEET, lambda r: r["id"]),
            NodeDef("hashtag", T_HASHTAG, lambda r: None),
        ),
        edges=(
            EdgeDef("owner", E_OWNER, owner, T_USER, T_TWEET),
            EdgeDef("mentioned", E_MENTIONED, mentioned, T_TWEET, T_USER),
            EdgeDef("hashtag-used-in", E_HT_USED_IN, ht_used, T_HASHTAG, T_TWEET),
            EdgeDef("mentioned-with-ht", E_MENTIONED_WITH_HT, ht_mention, T_HASHTAG, T_USER),
        ),
    )


def reddit_mapping() -> MappingSpec:
    """Portability demo (paper §III-B): same data model, different map —
    author/post/subreddit graph from reddit-like records."""

    def authored(r):
        return [(r["author"], r["id"])]

    def posted_in(r):
        return [(r["id"], r["subreddit"])]

    def replied(r):
        p = r.get("parent")
        return [(r["id"], p)] if p else []

    return MappingSpec(
        input_model="json",
        output_model="property-graph",
        nodes=(
            NodeDef("author", T_USER, lambda r: r["author"]),
            NodeDef("post", T_TWEET, lambda r: r["id"]),
            NodeDef("subreddit", T_HASHTAG, lambda r: r["subreddit"]),
        ),
        edges=(
            EdgeDef("authored", E_OWNER, authored, T_USER, T_TWEET),
            EdgeDef("posted-in", E_HT_USED_IN, posted_in, T_TWEET, T_HASHTAG),
            EdgeDef("replied-to", E_MENTIONED, replied, T_TWEET, T_TWEET),
        ),
    )


# ---------------------------------------------------------------------------
# CREATEEDGE (Algorithm 1) — batch transformation to edge arrays
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RawEdgeBatch:
    """Device-ready edge batch (pre-compression)."""

    src: np.ndarray  # (n,) uint64 node ids
    dst: np.ndarray  # (n,) uint64
    etype: np.ndarray  # (n,) int32
    src_type: np.ndarray  # (n,) int32
    dst_type: np.ndarray  # (n,) int32
    n_records: int

    @property
    def n_edges(self) -> int:
        return int(self.src.shape[0])


def create_edges(records: Sequence[dict], mapping: MappingSpec) -> RawEdgeBatch:
    """CREATEEDGE over a mini-batch of records.  Linear in #edges.

    The mapping's extract callables run per record, in order; then one
    `hash_keys` call turns every endpoint key of the batch into its id."""
    cut = mapping.max_edges_per_record
    pairs: List[Tuple[str, str]] = []
    runs: List[int] = []  # edges each (record, edge def) produced
    for r in records:
        for ed in mapping.edges:
            p = ed.extract(r)
            if len(p) > cut:
                p = p[:cut]
            pairs.extend(p)
            runs.append(len(p))
    per_def = np.repeat(np.tile(np.arange(len(mapping.edges)), len(records)),
                        np.asarray(runs, np.int64))
    col = lambda attr: np.asarray(
        [getattr(ed, attr) for ed in mapping.edges], np.int32)[per_def]
    src_type, dst_type = col("src_type"), col("dst_type")
    keys = [str(sk) for sk, _ in pairs] + [str(dk) for _, dk in pairs]
    ids = hash_keys(np.concatenate([src_type, dst_type]), keys)
    n = len(pairs)
    return RawEdgeBatch(
        src=ids[:n],
        dst=ids[n:],
        etype=col("etype"),
        src_type=src_type,
        dst_type=dst_type,
        n_records=len(records),
    )
