"""memory_stats()["peak_bytes_in_use"] after the window, in GiB."""
from chipbench.readers import peak_hbm_gib as read  # noqa: F401
