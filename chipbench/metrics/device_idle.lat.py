"""`device_idle` in the cells that report `latency_p95_ms`; see
`chipbench.readers.device_idle`."""
from chipbench.readers import device_idle as read  # noqa: F401
