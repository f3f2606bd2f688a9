"""Share of the traced span in which no operation ran on the device."""
from benchmark.layer_metrics._common import device_idle as read  # noqa: F401
