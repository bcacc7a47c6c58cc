"""End-to-end behaviour tests for the paper's system (§IV claims)."""
import numpy as np
import pytest

from repro.api import PipelineBuilder
from repro.configs.paper_ingest import IngestConfig
from repro.ingest.sources import BurstyTweetSource


def _pipe(cfg, uncontrolled, compress, spill_dir):
    return (PipelineBuilder(cfg).uncontrolled(uncontrolled)
            .compressed(compress).spill_dir(spill_dir).build())


def _run(uncontrolled, compress, ticks=150, seed=3, **cfg_kw):
    src = BurstyTweetSource(seed=seed)
    pipe = _pipe(IngestConfig(**cfg_kw), uncontrolled, compress,
                 f"/tmp/repro_spill_sys_{uncontrolled}_{compress}")
    return pipe.run(src.ticks(), max_ticks=ticks), pipe


def test_controlled_beats_uncontrolled_on_load():
    """Fig 7 vs Fig 12: the controller keeps consumer load bounded."""
    r_unc, _ = _run(uncontrolled=True, compress=False)
    r_ctl, _ = _run(uncontrolled=False, compress=True)
    mu_u, mu_c = r_unc.samples["mu"], r_ctl.samples["mu"]
    assert mu_c.max() <= mu_u.max() + 1e-9
    assert (mu_c > 0.95).mean() < (mu_u > 0.95).mean() + 1e-9
    # delay (Eq. 3) improves too
    assert r_ctl.samples["delay_s"].max() <= r_unc.samples["delay_s"].max() + 1e-9


def test_compression_reduces_instruction_load():
    """Compression cuts the effective insert-instruction stream."""
    r, _ = _run(uncontrolled=False, compress=True)
    assert r.total_instructions < r.raw_instructions
    assert 0.05 < r.mean_compression < 0.95


def test_compression_better_during_bursts():
    """Fig 13 narrative: a hashtag storm (few hot tags, heavy retweets)
    compresses better than a diverse calm day."""
    # uncontrolled+compress isolates the compression measurement from the
    # controller (which rightly throttles a *permanent* 5x storm)
    src = BurstyTweetSource(seed=5, p_burst_start=1.0, p_burst_end=0.0,
                            burst_hashtags=6, duplicate_frac=0.2)  # storm
    pipe = _pipe(IngestConfig(), True, True, "/tmp/repro_spill_b1")
    r_burst = pipe.run(src.ticks(), max_ticks=80)
    src2 = BurstyTweetSource(seed=5, p_burst_start=0.0, n_hashtags=20_000,
                             duplicate_frac=0.05)  # diverse calm day
    pipe2 = _pipe(IngestConfig(), True, True, "/tmp/repro_spill_b2")
    r_calm = pipe2.run(src2.ticks(), max_ticks=80)
    assert r_burst.mean_compression < r_calm.mean_compression


def test_store_consistent_with_stream():
    """Every unique node that entered the pipeline exists in the store."""
    r, pipe = _run(uncontrolled=False, compress=True, ticks=60)
    store = pipe.sink.ingestor.store
    assert int(store.n_nodes) > 0
    assert int(store.n_edges) > 0
    # edge-count conservation: stored counts == committed raw edges
    assert int(store.edge_count.sum()) <= r.raw_instructions


def test_throttling_rare_under_normal_load():
    """Paper: 'only on rare occasions resort to spilling'."""
    r, _ = _run(uncontrolled=False, compress=True, ticks=200)
    assert r.spill_events <= 0.1 * len(r.actions)


def test_commit_failure_archives_and_retries():
    """Algorithm 3: failed commits archive, then replay."""
    from repro.core.edge_table import from_raw_batch
    from repro.core.transform import create_edges, tweet_mapping
    from repro.core.ingestor import GraphIngestor
    from repro.graphstore.store import init_store

    recs = [{"id": f"t{i}", "user": f"u{i}", "hashtags": ["x"], "mentions": []}
            for i in range(10)]
    et = from_raw_batch(create_edges(recs, tweet_mapping()), 64)
    fail = {"on": True}
    ing = GraphIngestor(init_store(512, 1024), fail_hook=lambda: fail["on"])
    out = ing.push(et)
    assert not out["committed"] and len(ing.archive) == 1
    fail["on"] = False
    assert ing.retry_archive() == 1
    assert int(ing.store.n_nodes) > 0
