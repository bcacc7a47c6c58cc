"""`device_idle` in the cells that report `ingest_eps`; see
`chipbench.readers.device_idle`."""
from chipbench.readers import device_idle as read  # noqa: F401
