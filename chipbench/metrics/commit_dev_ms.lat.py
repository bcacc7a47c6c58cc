"""`commit_dev_ms` in the cells that report `latency_p95_ms`; see
`chipbench.readers.commit_dev_ms`."""
from chipbench.readers import commit_dev_ms as read  # noqa: F401
