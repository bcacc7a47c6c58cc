"""The main path's hot ops, each with one declared implementation.

`IMPL` names what each op runs.  A Pallas kernel is declared for an op
only where it compiles for TPU v5e at the main path's widths and is
bit-exact with its jnp oracle.  None does yet (ROADMAP D3 records each
kernel's compiler refusal), so every op runs its XLA implementation,
the same one on TPU and on CPU.

The kernels stay in their modules (`repro.kernels.upsert`, `sampler`,
`sketch`, `pattern_mine`, ...) with their interpret-mode tests; they
run in interpret mode only when a caller passes `interpret=True`.
Nothing here calls a kernel.
"""
from __future__ import annotations

from repro.kernels import pattern_mine as _mine
from repro.kernels import sampler as _sampler
from repro.kernels import sketch as _sketch
from repro.kernels import upsert as _upsert

# op -> implementation on every backend ("xla" = the jnp oracle, compiled
# by XLA); a compiling, bit-exact kernel would be named here instead
IMPL = {
    "fused_upsert": "xla",
    "traffic_sample": "xla",
    "sketch_scatter": "xla",
    "pattern_mine": "xla",
}


def fused_upsert(table_keys, keys, valid, n_probes):
    """Fused lookup-or-insert (GRAPHPUSH commit hot path): one probe
    sweep per table instead of lookup-then-insert.  Returns
    (table_keys', slot (-1 = dropped), is_new, rounds), `rounds` the
    probe rounds the sweep ran (it ends once every lane is placed)."""
    return _upsert.fused_upsert_ref(table_keys, keys, valid, n_probes)


def traffic_sample(seed, ctr0, n: int, iparams, fparams):
    """Counter-based traffic-id block for the workload generator
    (repro.workloads): (uid, tag, mention, u_dup, u_dupi).
    Deterministic in (seed, ctr0)."""
    return _sampler.traffic_ids_ref(seed, ctr0, n, iparams, fparams)


def sketch_scatter(edge_w, out_deg, in_deg, r, c, cnt):
    """Graph-sketch scatter-add hot path (repro.query.sketch)."""
    return _sketch.scatter_add(edge_w, out_deg, in_deg, r, c, cnt)


def pattern_mine(src, dst, etype, count, valid, star_min, hot_min):
    """Frequent-substructure mining over a dedup'd batch (GraphZip
    front-end, repro.compress): (fan_out, fan_in, flags, psig) per
    edge."""
    return _mine.pattern_mine_ref(src, dst, etype, count, valid,
                                  star_min, hot_min)
