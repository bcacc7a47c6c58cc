"""The one traffic generator: an open-loop event schedule from a seed.

A traffic mix is a JSON file of parameters (`chipbench/traffic/<name>.json`);
a configuration supplies the rate and the id spaces.  This module turns
both, with `--seed`, into events stamped with the wall second at which
each is due, counted from the opening of the measured window.

It is a NumPy copy of what the program's scenario family means
(`repro.workloads.samplers.rate_trajectory`, `repro.kernels.sampler`,
`repro.workloads.source.ScenarioSource`), kept here so that a change to
the program's sampler cannot move the benchmark's inputs:

* per second of stream time, a rate: the base rate, times a flash step
  `1 + (mult - 1) exp(-(t - flash_t) / decay)` from `flash_t` on, plus
  Hawkes self-excitation `alpha * beta * excite`, times jitter
  `1 + noise * (2u - 1)`; the count is that rate plus a Gaussian
  approximation to Poisson noise, and the events of a second are spread
  uniformly over it;
* per event: a Zipf user, a hashtag that is one of the hot topics with a
  share that grows with the burst level `1 - base / rate`, else a Zipf
  tag, and a mention that copies the author of an earlier event of the
  same second with probability `copy_frac`, else a Zipf celebrity;
* with probability `duplicate_frac`, an event is an exact copy of one of
  the last 500 original tweets (same tweet id).

The rate schedule, and so the count of events in every second, is drawn
from a fixed seed of its own: every `--seed` offers the same amount of
work, second by second, and the seed draws which events they are and
where in its second each falls.

Every event maps to four edges under the paper's Fig. 6 mapping: one
hashtag and one mention per tweet, as in the scenario family.
"""
from __future__ import annotations

import collections
import dataclasses
import json
from pathlib import Path
from typing import Dict, List

import numpy as np

RECENT_WINDOW = 500  # originals a duplicate may copy (ScenarioSource)
SCHEDULE_SEED = 0x5EED  # the rate schedule's, the same for every --seed

# keys a traffic file must give; defaults are the scenario family's
TRAFFIC_KEYS = {
    "noise_frac": 0.25, "hawkes_alpha": 0.0, "hawkes_beta": 0.5,
    "flash_t": None, "flash_mult": 1.0, "flash_decay": 40.0,
    "rate_cap_mult": 50.0, "zipf_user": 1.3, "zipf_tag": 1.2,
    "zipf_mention": 2.0, "copy_frac": 0.3, "topic_frac": 0.1,
    "topic_frac_burst": 0.8, "burst_ntags": 12, "topic_base": 17,
    "duplicate_frac": 0.125,
}


def load_traffic(path: Path) -> Dict:
    """A traffic file's parameters, with every key the generator reads."""
    params = json.loads(Path(path).read_text())
    unknown = set(params) - set(TRAFFIC_KEYS) - {"name", "why"}
    if unknown:
        raise ValueError(f"{path}: unknown traffic keys {sorted(unknown)}")
    return {**TRAFFIC_KEYS, **params}


@dataclasses.dataclass
class Events:
    """An event schedule, sorted by due time."""

    due: np.ndarray      # (n,) float64 s, relative to the window's opening
    tweet: np.ndarray    # (n,) int64 tweet number (duplicates share it)
    user: np.ndarray     # (n,) int64
    tag: np.ndarray      # (n,) int64
    mention: np.ndarray  # (n,) int64

    def __len__(self) -> int:
        return int(self.due.shape[0])

    def records(self) -> List[dict]:
        """Tweet-shaped dicts for the program's pipeline; `eid` is the
        event's index here, which the harness uses to find the commit
        that acknowledged it."""
        return [{"id": f"t{t}", "user": f"u{u}", "hashtags": [f"h{h}"],
                 "mentions": [f"u{m}"], "text": "", "ts": float(d), "eid": i}
                for i, (d, t, u, h, m) in enumerate(zip(
                    self.due.tolist(), self.tweet.tolist(),
                    self.user.tolist(), self.tag.tolist(),
                    self.mention.tolist()))]


def zipf_rank(u: np.ndarray, n: int, a: float) -> np.ndarray:
    """Bounded-Pareto inverse CDF on [1, n+1): ranks in [0, n)."""
    one_m_a = 1.0 - a
    top = (n + 1.0) ** one_m_a - 1.0
    x = (1.0 + u * top) ** (1.0 / one_m_a)
    return np.clip(x.astype(np.int64) - 1, 0, n - 1)


def rates(tr: Dict, base: float, seconds: int, rng) -> tuple:
    """Per-second (rate, count) of the stream, seconds 0..seconds-1."""
    flash_t = tr["flash_t"]
    g = np.exp(-tr["hawkes_beta"])
    excite = 0.0
    lam_out = np.zeros(seconds)
    cnt_out = np.zeros(seconds, np.int64)
    for k in range(seconds):
        env = base
        if flash_t is not None and k >= flash_t:
            env *= 1.0 + (tr["flash_mult"] - 1.0) * np.exp(
                -(k - flash_t) / tr["flash_decay"])
        lam = env + tr["hawkes_alpha"] * tr["hawkes_beta"] * excite
        lam *= 1.0 + tr["noise_frac"] * (2.0 * rng.random() - 1.0)
        lam = float(np.clip(lam, 0.0, tr["rate_cap_mult"] * base))
        c = max(round(lam + np.sqrt(lam) * rng.standard_normal()), 0)
        c = int(min(c, tr["rate_cap_mult"] * base))
        excite = g * (excite + c)
        lam_out[k], cnt_out[k] = lam, c
    return lam_out, cnt_out


def generate(tr: Dict, cfg: Dict, seed: int, seconds: float,
             lead_s: float = 0.0, stream: int = 0) -> Events:
    """Events due over [-lead_s, seconds) for the config's rate and id
    spaces.  `stream` separates independent schedules of one seed (the
    window's and the warm-up's)."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), stream]))
    base = float(cfg["rate_events_per_s"])
    n_users, n_tags = int(cfg["n_users"]), int(cfg["n_tags"])
    lead = int(np.ceil(lead_s))
    total = lead + int(np.ceil(seconds))
    shifted = dict(tr)
    if tr["flash_t"] is not None:
        shifted["flash_t"] = tr["flash_t"] + lead
    schedule = np.random.default_rng(
        np.random.SeedSequence([SCHEDULE_SEED, stream]))
    lam, cnt = rates(shifted, base, total, schedule)
    n = int(cnt.sum())
    sec = np.repeat(np.arange(total), cnt)
    start = np.repeat(np.cumsum(cnt) - cnt, cnt)
    pos = np.arange(n) - start
    due = sec + rng.random(n)
    order = np.lexsort((due, sec))  # sorted within each second
    due = due[order]
    # burst level per second drives the hot-topic share
    b = np.maximum(0.0, 1.0 - base / np.maximum(lam, base))
    frac = tr["topic_frac"] + (tr["topic_frac_burst"] - tr["topic_frac"]) * b
    u = rng.random((6, n))
    user = zipf_rank(u[0], n_users, tr["zipf_user"])
    hot = (tr["topic_base"]
           + (u[1] * tr["burst_ntags"]).astype(np.int64)) % n_tags
    tag = np.where(u[2] < frac[sec], hot,
                   zipf_rank(u[1], n_tags, tr["zipf_tag"]))
    j = start + (u[3] * pos).astype(np.int64)
    copy = (u[4] < tr["copy_frac"]) & (pos > 0)
    mention = np.where(copy, user[j],
                       zipf_rank(u[5], n_users, tr["zipf_mention"]))
    # duplicates: exact copies of one of the last RECENT_WINDOW originals
    u_dup, u_pick = rng.random((2, n))
    tweet = np.arange(1, n + 1, dtype=np.int64)
    recent: collections.deque = collections.deque(maxlen=RECENT_WINDOW)
    for i in range(n):
        if recent and u_dup[i] < tr["duplicate_frac"]:
            o = recent[min(int(u_pick[i] * len(recent)), len(recent) - 1)]
            tweet[i], user[i], tag[i], mention[i] = (
                tweet[o], user[o], tag[o], mention[o])
        else:
            recent.append(i)
    return Events(due=due - lead, tweet=tweet, user=user, tag=tag,
                  mention=mention)
