"""`loop_self_ms` in the cells that report `ingest_eps`; see
`chipbench.readers.loop_self_ms`."""
from chipbench.readers import loop_self_ms as read  # noqa: F401
