"""``prefill_device_ms_per_ktok`` in a cell with no first-token time: in
an offline backlog a prefill's device time is taken from the decode
steps, so it moves the output rate."""

from bench.metrics.prefill_device_ms_per_ktok import read  # noqa: F401
