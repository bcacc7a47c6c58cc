"""CREATEEDGE's batched node-id hash against the per-edge scalar hash
it replaced, kept here as the reference."""
import numpy as np
import pytest

from repro.core.transform import (
    MappingSpec,
    create_edges,
    finalise_ids,
    hash_keys,
    hash_str,
    reddit_mapping,
    splitmix64,
    tweet_mapping,
)

MASK = (1 << 64) - 1
GOLDEN = 0x9E3779B97F4A7C15
M1, M2 = 0xBF58476D1CE4E5B9, 0x94D049BB133111EB


def ref_splitmix64(x: int) -> int:
    x = (x + GOLDEN) & MASK
    z = ((x ^ (x >> 30)) * M1) & MASK
    z = ((z ^ (z >> 27)) * M2) & MASK
    return z ^ (z >> 31)


def ref_hash_str(type_tag: int, s: str) -> int:
    """The per-key scalar hash that `create_edges` ran twice an edge."""
    h = 1469598103934665603
    for b in s.encode("utf-8"):
        h = ((h ^ b) * 1099511628211) & MASK
    h ^= (type_tag << 56) & MASK
    return ref_splitmix64(h) or 1


def ref_create_edges(records, mapping):
    """The per-edge loop `create_edges` was, as five lists."""
    cols = ([], [], [], [], [])
    for r in records:
        for ed in mapping.edges:
            pairs = ed.extract(r)[: mapping.max_edges_per_record]
            for sk, dk in pairs:
                for col, v in zip(cols, (
                        ref_hash_str(ed.src_type, str(sk)),
                        ref_hash_str(ed.dst_type, str(dk)),
                        ed.etype, ed.src_type, ed.dst_type)):
                    col.append(v)
    return cols


def assert_same(records, mapping):
    raw = create_edges(records, mapping)
    want = ref_create_edges(records, mapping)
    got = (raw.src, raw.dst, raw.etype, raw.src_type, raw.dst_type)
    for g, w, dtype in zip(got, want, (np.uint64, np.uint64, np.int32,
                                       np.int32, np.int32)):
        assert g.dtype == dtype and g.shape == (len(w),)
        assert g.tolist() == w
    assert raw.n_records == len(records)
    return raw


ODD_KEYS = ["héllo", "日本語のユーザー", "🙂🙃", "", "a\x00b", "tail\x00",
            "\x00", "\x00\x00", "x" * 300, "y"]


def _tweets(n, keys=None):
    keys = keys or [f"u{i % 7}" for i in range(n)]
    return [{"id": f"t{i}", "user": keys[i % len(keys)],
             "hashtags": [f"#h{i % 3}", keys[(i + 1) % len(keys)]],
             "mentions": [keys[(i + 2) % len(keys)]] * (i % 3)}
            for i in range(n)]


def _posts(n, keys=None):
    keys = keys or [f"a{i % 5}" for i in range(n)]
    return [{"id": f"p{i}", "author": keys[i % len(keys)],
             "subreddit": keys[(i + 3) % len(keys)],
             "parent": f"p{i - 1}" if i % 2 else None}
            for i in range(n)]


@pytest.mark.parametrize("mapping,records", [
    (tweet_mapping(), _tweets(40)),
    (tweet_mapping(), _tweets(len(ODD_KEYS) * 2, ODD_KEYS)),
    (reddit_mapping(), _posts(40)),
    (reddit_mapping(), _posts(len(ODD_KEYS) * 2, ODD_KEYS)),
], ids=["tweet", "tweet_odd_keys", "reddit", "reddit_odd_keys"])
def test_create_edges_equals_the_scalar_hash(mapping, records):
    raw = assert_same(records, mapping)
    assert raw.n_edges > len(records)


def test_odd_keys_hash_as_their_bytes():
    # a trailing or embedded NUL changes the id; so does every key here
    ids = hash_keys(np.full(len(ODD_KEYS), 1), ODD_KEYS)
    assert ids.tolist() == [ref_hash_str(1, k) for k in ODD_KEYS]
    assert len(set(ids.tolist())) == len(ODD_KEYS)
    assert hash_str(9, "a\x00") != hash_str(9, "a")


@pytest.mark.parametrize("mapping", [tweet_mapping(), reddit_mapping()],
                         ids=["tweet", "reddit"])
def test_no_records_give_empty_columns(mapping):
    raw = assert_same([], mapping)
    assert raw.n_edges == 0 and raw.n_records == 0


def test_a_record_past_the_per_record_cut():
    m = tweet_mapping()
    big = {"id": "t0", "user": "u0",
           "hashtags": [f"#h{i}" for i in range(30)],
           "mentions": [f"m{i}" for i in range(5)]}
    records = [big, {"id": "t1", "user": "u1", "hashtags": ["#x"],
                     "mentions": []}]
    raw = assert_same(records, m)
    # owner 1, mentioned 5, hashtag-used-in cut to 24 of 30, and the
    # 150 hashtag-mention pairs cut to 24; then t1's owner and hashtag
    assert raw.n_edges == (1 + 5 + 24 + 24) + 2
    small = MappingSpec(m.input_model, m.output_model, m.nodes, m.edges,
                        max_edges_per_record=2)
    assert assert_same(records, small).n_edges == (1 + 2 + 2 + 2) + 2


def test_keys_of_very_different_lengths_in_one_batch():
    keys = ["", "a", "b" * 4096, "ü" * 7, "c" * 63, "d" * 64, "e" * 65]
    assert_same(_tweets(30, keys), tweet_mapping())
    assert hash_keys(np.arange(len(keys)), keys).tolist() == [
        ref_hash_str(t, k) for t, k in enumerate(keys)]


def test_hash_str_is_the_one_key_batch():
    for t, k in [(9, "word"), (1, ""), (3, "#日本"), (255, "z\x00")]:
        assert hash_str(t, k) == ref_hash_str(t, k)
        assert hash_str(t, k) == int(hash_keys(np.asarray([t]), [k])[0])


def unsplitmix64(z: int) -> int:
    """The inverse of `ref_splitmix64`."""
    def unxorshift(y, s):
        x = y
        for _ in range(64 // s + 1):
            x = y ^ (x >> s)
        return x

    z = unxorshift(z, 31)
    z = unxorshift((z * pow(M2, -1, 1 << 64)) & MASK, 27)
    x = unxorshift((z * pow(M1, -1, 1 << 64)) & MASK, 30)
    return (x - GOLDEN) & MASK


def test_the_one_preimage_of_zero_maps_to_one():
    rng = np.random.default_rng(5)
    for v in rng.integers(0, 2**63, 50, dtype=np.uint64).tolist() + [MASK]:
        assert unsplitmix64(ref_splitmix64(v)) == v
    pre = unsplitmix64(0)
    assert ref_splitmix64(pre) == 0
    assert int(splitmix64(np.asarray([pre], np.uint64))[0]) == 0
    # a type tag XORs into the top byte first: undo it in the input
    for tag in (0, 1, 3):
        fnv = np.asarray([pre ^ (tag << 56), pre ^ (tag << 56) ^ 1], np.uint64)
        ids = finalise_ids(fnv, np.full(2, tag))
        assert ids[0] == 1
        assert ids[1] == ref_splitmix64(pre ^ 1)
