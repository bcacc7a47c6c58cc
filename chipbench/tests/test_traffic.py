"""The generator: seeded, and faithful to each traffic file."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench.traffic import TRAFFIC_KEYS, generate, load_traffic

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
CFG = {"rate_events_per_s": 600, "n_users": 20000, "n_tags": 4000}


def test_same_seed_same_events():
    tr = load_traffic(TRAFFIC / "flash_crowd.json")
    a = generate(tr, CFG, 2**31 + 11, 20)
    b = generate(tr, CFG, 2**31 + 11, 20)
    c = generate(tr, CFG, 2**31 + 12, 20)
    for f in ("due", "tweet", "user", "tag", "mention"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.user[:100], c.user[:100])
    assert a.records() == b.records()


@pytest.mark.parametrize("name", ["steady", "flash_crowd"])
def test_every_seed_offers_the_same_work(name):
    tr = load_traffic(TRAFFIC / f"{name}.json")
    a = generate(tr, CFG, 2**31 + 21, 20, lead_s=1.0)
    b = generate(tr, CFG, 7, 20, lead_s=1.0)
    per_s = lambda ev: np.bincount(np.floor(ev.due + 1).astype(int))
    assert np.array_equal(per_s(a), per_s(b))
    assert not np.array_equal(a.due, b.due)
    assert not np.array_equal(a.user[:100], b.user[:100])


def test_streams_differ():
    tr = load_traffic(TRAFFIC / "steady.json")
    a = generate(tr, CFG, 5, 5, stream=0)
    b = generate(tr, CFG, 5, 5, stream=1)
    assert not np.array_equal(a.user[:50], b.user[:50])


@pytest.mark.parametrize("name", sorted(p.stem for p in TRAFFIC.glob("*.json")))
def test_schedule_is_sorted_and_in_range(name):
    tr = load_traffic(TRAFFIC / f"{name}.json")
    ev = generate(tr, CFG, 3, 12, lead_s=1.0)
    assert np.all(np.diff(ev.due) >= 0)
    assert ev.due[0] >= -1.0 and ev.due[-1] < 12.0
    assert (ev.due < 0).sum() > 0  # the lead-in backlog
    assert ev.user.max() < CFG["n_users"] and ev.tag.max() < CFG["n_tags"]
    # one hashtag and one mention per tweet: the four Fig. 6 edges
    r = ev.records()[0]
    assert len(r["hashtags"]) == 1 and len(r["mentions"]) == 1


def test_flash_step_and_hot_tags_are_honoured():
    tr = load_traffic(TRAFFIC / "flash_crowd.json")
    ev = generate(tr, CFG, 9, 20)
    per_s = np.bincount(np.floor(ev.due).astype(int), minlength=20)
    before, after = per_s[:10].mean(), per_s[10:13].mean()
    assert 5.0 < after / before < 12.0  # 8x step plus self-excitation
    hot = set(range(tr["topic_base"], tr["topic_base"] + tr["burst_ntags"]))
    share = lambda m: np.isin(ev.tag[m], list(hot)).mean()
    assert share(ev.due >= 10) > share(ev.due < 10) + 0.3


def test_steady_has_no_step_and_duplicates_match():
    tr = load_traffic(TRAFFIC / "steady.json")
    ev = generate(tr, CFG, 4, 30)
    per_s = np.bincount(np.floor(ev.due).astype(int), minlength=30)
    assert per_s.max() < 2.0 * CFG["rate_events_per_s"]
    dup = 1.0 - len(np.unique(ev.tweet)) / len(ev)
    assert abs(dup - tr["duplicate_frac"]) < 0.02


def test_unknown_traffic_key_is_refused(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"noise_frac": 0.1, "flash_tt": 3}))
    with pytest.raises(ValueError):
        load_traffic(p)
    assert set(load_traffic(TRAFFIC / "steady.json")) >= set(TRAFFIC_KEYS)
