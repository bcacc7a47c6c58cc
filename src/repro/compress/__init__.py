"""repro.compress — ingestion-time dictionary compression (GraphZip).

Cross-batch counterpart of the Algorithm-1 within-batch dedup: a
device-resident dictionary of frequently recurring edges (members of
mined star-burst / cascade-chain / hot-edge patterns) lets the
pipeline rewrite each batch into pattern *references* plus a residual,
both masks over the batch table's lanes, so the path's programs depend
on the table's capacity alone.  References commit by direct scatter to
their cached store slots, with no probe of their own; the residual's
sweeps still run over all of the table's lanes (GraphZip, Packer &
Holder, arXiv:1703.08614; the ROADMAP "ingestion-time dictionary
compression" item).

    pipe = (PipelineBuilder(cfg)
            .with_source(src)
            .with_compression()          # DictionaryStage + rewrite
            .build())

Pieces:
  * `repro.kernels.pattern_mine` — per-batch frequent-substructure
    miner (Pallas kernel + bit-exact jnp oracle),
  * `PatternDictionary` (`dictionary.py`) — fixed-capacity signature
    table + ref counts + LRU clock, counter-deterministic eviction,
  * `DictionaryStage` / `CompressingTransform` (`stage.py`) — the
    pipeline stages producing `CompressedCommit` batches,
  * `commit_compressed` (repro.graphstore.store) — the pattern-aware
    commit expanding references bit-exactly into the store.
"""
from repro.compress.dictionary import (
    DICT_PROBES,
    PatternDictionary,
    dict_admit,
    dict_lookup,
    init_dictionary,
)
from repro.compress.stage import (
    CompressedCommit,
    CompressingTransform,
    DictionaryStage,
)

__all__ = [
    "DICT_PROBES",
    "PatternDictionary",
    "dict_admit",
    "dict_lookup",
    "init_dictionary",
    "CompressedCommit",
    "CompressingTransform",
    "DictionaryStage",
]
